"""Device milliseconds of a decode step under the ``lm.experts`` scope: the
sort of the step's (token, expert) assignments and the loop over the experts
that have a token (``benchmark/lm_phase_times.py``)."""
from benchmark import lm_phase_times


def read(run):
    return lm_phase_times.ms_per_step(run, "lm.experts")
