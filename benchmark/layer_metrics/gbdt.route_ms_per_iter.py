"""Device milliseconds per boosting iteration under the ``gbdt.route`` scope:
the routing of every row to its child at the end of each level.  Own time of
the traced operations whose scope path names it
(``benchmark/phase_times.py``)."""
from benchmark import phase_times


def read(run):
    return phase_times.ms_per_iter(run, "gbdt.route")
