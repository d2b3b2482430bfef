"""Milliseconds a batch in which the device stood idle under NO phase of
``apply_batch``: the stage's own code around the call and ``collect`` between
two transforms.  One of the four parts of the window's idle time per batch
(``benchmark/host_phases.py``)."""
from benchmark import host_phases


def read(run):
    return host_phases.idle_ms_per_batch(run, host_phases.NO_PHASE)
