"""Device milliseconds of a decode step under the ``lm.full_attn`` scope
(``mmlspark_tpu/models/window_moe.py``): the full-attention layers: the gather of each slot's pages over the table width and attention over them.  Own time of the step program's traced operations whose
scope path names it (``benchmark/lm_phase_times.py``), over the steps
counted in the window."""
from benchmark import lm_phase_times


def read(run):
    return lm_phase_times.ms_per_step(run, "lm.full_attn")
