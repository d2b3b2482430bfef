"""Milliseconds of a decode step in which the device stood idle under NO phase
of the engine's thread: asleep in ``_wait_for_work``, between two rounds, or
before its first lap.  The coverage guard of the other three parts of
``decode.host_ms_per_step`` (``benchmark/host_phases.py``): it should stay
under a fifth of the whole."""
from benchmark import host_phases


def read(run):
    return host_phases.idle_ms_per_step(run, host_phases.NO_PHASE)
