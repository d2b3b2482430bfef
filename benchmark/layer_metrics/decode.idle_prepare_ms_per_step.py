"""Milliseconds of a decode step in which the device stood idle while the
engine's thread was in ``prepare`` (deadline leaves, positions, page extends,
the table's and the tokens' uploads) or ``dispatch`` (the ``_step`` call): what
a step dispatched with nothing in flight, the step after a join, costs.  One
of the four parts of ``decode.host_ms_per_step`` (``benchmark/host_phases.py``);
0.0 where no idle gap fell under the two."""
from benchmark import host_phases


def read(run):
    return host_phases.idle_ms_per_step(run, "prepare", "dispatch")
