"""Milliseconds a batch in which the device stood idle while ``apply_batch``
was in ``dispatch``: the look-up of the executable, the jitted call and the
ask for its output's copy.  One of the four parts of the window's idle time
per batch (``benchmark/host_phases.py``)."""
from benchmark import host_phases


def read(run):
    return host_phases.idle_ms_per_batch(run, "dispatch")
