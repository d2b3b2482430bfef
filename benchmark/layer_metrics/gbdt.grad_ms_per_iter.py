"""Device milliseconds per boosting iteration under the ``gbdt.grad`` scope:
the objective's gradients and hessians, and the bagging, GOSS and
feature-fraction masks.  Own time of the traced operations whose scope path
names it (``benchmark/phase_times.py``)."""
from benchmark import phase_times


def read(run):
    return phase_times.ms_per_iter(run, "gbdt.grad")
