"""Device milliseconds per boosting iteration under the ``gbdt.quantize``
scope: the once-a-tree stochastic rounding of gradients and hessians to small
integers.  Own time of the traced operations whose scope path names it
(``benchmark/phase_times.py``)."""
from benchmark import phase_times


def read(run):
    return phase_times.ms_per_iter(run, "gbdt.quantize")
