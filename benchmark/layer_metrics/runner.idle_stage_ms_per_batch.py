"""Milliseconds a batch in which the device stood idle while ``apply_batch``
was in ``stage``: a chunk filled into its staging buffer (or a dense input's
slice padded).  An upload has no phase of its own, so the first upload of a
call, which runs while the host stages the second chunk, is booked here.  One
of the four parts of the window's idle time per batch
(``benchmark/host_phases.py``)."""
from benchmark import host_phases


def read(run):
    return host_phases.idle_ms_per_batch(run, "stage")
