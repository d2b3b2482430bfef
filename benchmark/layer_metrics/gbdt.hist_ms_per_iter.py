"""Device milliseconds per boosting iteration under the ``gbdt.hist`` scope:
the histogram build but for its layout: one-hot operands, the int8 ``einsum``,
accumulation, sibling subtraction, the rescale to floats.  Own time of the
traced operations whose scope path names it (``benchmark/phase_times.py``)."""
from benchmark import phase_times


def read(run):
    return phase_times.ms_per_iter(run, "gbdt.hist")
