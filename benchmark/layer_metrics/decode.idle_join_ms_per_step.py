"""Milliseconds of a decode step in which the device stood idle while the
engine's thread was in a join: ``join_prefill`` (the chunks' dispatches),
``join_fetch`` (blocked on the joiner's first token) or ``join_splice`` (host
state after it).  Per STEP, not per join: a window without a join reads 0.0.
One of the four parts of ``decode.host_ms_per_step``
(``benchmark/host_phases.py``)."""
from benchmark import host_phases


def read(run):
    return host_phases.idle_ms_per_step(run, "join_prefill", "join_fetch",
                                        "join_splice")
