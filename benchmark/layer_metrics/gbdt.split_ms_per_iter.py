"""Device milliseconds per boosting iteration under the ``gbdt.split`` scope:
the split scan: gains over nodes x features x bins, the arg-max, the writes of
the chosen splits into the tree arrays.  Own time of the traced operations
whose scope path names it (``benchmark/phase_times.py``)."""
from benchmark import phase_times


def read(run):
    return phase_times.ms_per_iter(run, "gbdt.split")
