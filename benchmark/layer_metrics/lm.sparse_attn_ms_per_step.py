"""Device milliseconds of a decode step under the ``lm.sparse_attn`` scope:
the gather of the selected positions' keys and values from the paged cache
and attention over them (``benchmark/lm_phase_times.py``)."""
from benchmark import lm_phase_times


def read(run):
    return lm_phase_times.ms_per_step(run, "lm.sparse_attn")
