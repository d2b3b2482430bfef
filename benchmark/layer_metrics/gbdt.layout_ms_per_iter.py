"""Device milliseconds per boosting iteration under the ``gbdt.layout`` scope:
the rows laid out as node-pure blocks for the matmul histogram builders
(``ops/histogram._node_pure_layout``): one stable sort by node, the blocks as
slices of the sorted order, the binned rows gathered by the blocks' row ids.
Since PR 27 a build of ONE node (the root of every tree, and sharded the level
below it) is not sorted at all and pays only the pad of the binned matrix to
whole blocks.  Own time of the traced operations whose scope path names it
(``benchmark/phase_times.py``)."""
from benchmark import phase_times


def read(run):
    return phase_times.ms_per_iter(run, "gbdt.layout")
