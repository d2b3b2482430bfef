"""Device milliseconds of a decode step under the ``lm.select`` scope: the
exact top-k of the indexer's scores over the table width, once a layer
(``benchmark/lm_phase_times.py``)."""
from benchmark import lm_phase_times


def read(run):
    return lm_phase_times.ms_per_step(run, "lm.select")
