"""Milliseconds a batch in which the device stood idle while ``apply_batch``
drained: ``wait`` (``block_until_ready`` of the oldest chunk's output),
``fetch`` (its copy into numpy) or ``concat`` (the final concatenation).  One
of the four parts of the window's idle time per batch
(``benchmark/host_phases.py``)."""
from benchmark import host_phases


def read(run):
    return host_phases.idle_ms_per_batch(run, "wait", "fetch", "concat")
