"""Device milliseconds of a decode step under the ``lm.dense`` scope
(``mmlspark_tpu/models/window_moe.py``): the embedding, the q/k/v/o projections, the norms and the dense MLP.  Own time of the step program's traced operations whose
scope path names it (``benchmark/lm_phase_times.py``), over the steps
counted in the window."""
from benchmark import lm_phase_times


def read(run):
    return lm_phase_times.ms_per_step(run, "lm.dense")
