"""Milliseconds a decode step the engine's thread WORKED: the time inside the
window in every phase of its round but the two in which it is blocked on the
device (``fetch``, ``join_fetch``).  Since the engine keeps a step in flight
this work runs beside the device, and ``decode.host_ms_per_step`` no longer
sees it; it has to stay under ``decode.step_ms`` for that overlap to hide it
(``benchmark/host_phases.py``)."""
from benchmark import host_phases


def read(run):
    return host_phases.work_ms_per_step(run, "fetch", "join_fetch")
