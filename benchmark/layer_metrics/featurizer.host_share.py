"""Share of the transforms' wall time spent outside
``ModelRunner.apply_batch``, in percent: per-image conversion, stacking and
the column bookkeeping of ``ImageFeaturizer`` and ``JaxModel``.  Both spans
are the harness's own, the inner one put around the runner's bound method
from outside."""


def read(run):
    lo, hi = run.window_start_s, run.window_end_s
    outer = run.spans.total("transform", lo, hi)
    if outer <= 0:
        return None
    inner = run.spans.total("apply_batch", lo, hi)
    return 100.0 * (outer - inner) / outer
