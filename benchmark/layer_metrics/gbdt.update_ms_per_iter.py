"""Device milliseconds per boosting iteration under the ``gbdt.update`` scope:
leaf values, the update of the scores, the stacking of the tree arrays.  Own
time of the traced operations whose scope path names it
(``benchmark/phase_times.py``)."""
from benchmark import phase_times


def read(run):
    return phase_times.ms_per_iter(run, "gbdt.update")
