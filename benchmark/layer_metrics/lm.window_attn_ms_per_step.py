"""Device milliseconds of a decode step under the ``lm.window_attn`` scope
(``mmlspark_tpu/models/window_moe.py``): the window layers' attention: a step reads each slot's ring of ``window`` rows in place, whatever the context length.  Own time of the step program's traced operations whose
scope path names it (``benchmark/lm_phase_times.py``), over the steps
counted in the window."""
from benchmark import lm_phase_times


def read(run):
    return lm_phase_times.ms_per_step(run, "lm.window_attn")
