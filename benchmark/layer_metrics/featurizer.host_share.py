"""Share of the transforms' wall time spent outside
``ModelRunner.apply_batch``, in percent: the dtype decision over the rows,
the output column and ``collect`` of ``ImageFeaturizer`` and ``JaxModel``.
Since PR 32 the stage hands the runner a row source, so the stacking of the
images and their upload happen INSIDE the ``apply_batch`` span and are not
read here.  Both spans are the harness's own, the inner one put around the
runner's bound method from outside."""


def read(run):
    lo, hi = run.window_start_s, run.window_end_s
    outer = run.spans.total("transform", lo, hi)
    if outer <= 0:
        return None
    inner = run.spans.total("apply_batch", lo, hi)
    return 100.0 * (outer - inner) / outer
