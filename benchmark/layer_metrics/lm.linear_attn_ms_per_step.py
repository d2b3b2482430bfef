"""Device milliseconds of a decode step under the ``lm.linear_attn`` scope
(``mmlspark_tpu/models/linear_moe.py``): the linear-attention layers' conv,
gates, L2 norms, the recurrence's update and the read and write of each
row's recurrent state and conv history.  Own time of the step program's
traced operations whose scope path names it
(``benchmark/lm_phase_times.py``), over the steps counted in the window.
Fixed a row whatever the context length."""
from benchmark import lm_phase_times


def read(run):
    return lm_phase_times.ms_per_step(run, "lm.linear_attn")
