"""Percent of the window's device time under no ``lm.*`` scope, every program
counted (steps, joins' prefills, samplers, page copies): the coverage guard
of the ``lm.*_ms_per_step`` metrics, which explain a step only while this is
small (``benchmark/phase_times.py``)."""
from benchmark import phase_times


def read(run):
    found = phase_times.by_phase(run, prefix="lm.")
    if found is None:
        return None
    return 100.0 * found["unscoped_s"] / found["total_s"]
