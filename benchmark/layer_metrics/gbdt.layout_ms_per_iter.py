"""Device milliseconds per boosting iteration under the ``gbdt.layout`` scope:
the sort of the rows into node-pure blocks that the matmul histogram builders
run before every build (``ops/histogram._node_pure_layout``).  Own time of the
traced operations whose scope path names it (``benchmark/phase_times.py``)."""
from benchmark import phase_times


def read(run):
    return phase_times.ms_per_iter(run, "gbdt.layout")
