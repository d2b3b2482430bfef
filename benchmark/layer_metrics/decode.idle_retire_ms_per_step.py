"""Milliseconds of a decode step in which the device stood idle while the
engine's thread retired a step: ``fetch`` (blocked in the one host fetch of its
tokens), ``book`` (tokens onto handles, counters, releases) or ``notify``
(``done.set()`` and the ``on_done`` callbacks).  One of the four parts of
``decode.host_ms_per_step`` (``benchmark/host_phases.py``); 0.0 where no idle
gap fell under the three."""
from benchmark import host_phases


def read(run):
    return host_phases.idle_ms_per_step(run, "fetch", "book", "notify")
