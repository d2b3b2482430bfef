"""Device milliseconds of a decode step under the ``lm.indexer`` scope: the
indexer's projections, the gather of its keys over the whole table width and
its scores (``mmlspark_tpu/models/sparse_moe.py``).  Own time of the step
program's traced operations whose scope path names it
(``benchmark/lm_phase_times.py``), over the steps counted in the window."""
from benchmark import lm_phase_times


def read(run):
    return lm_phase_times.ms_per_step(run, "lm.indexer")
