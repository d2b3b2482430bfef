"""Device seconds of the decode STEP program by ``lm.*`` phase.

``phase_times.by_phase(run, prefix="lm.")`` books every traced operation of
the window to its scope, whatever program ran it; a join's prefill runs under
the same scopes as a step.  The per-step metrics and the kernels' roofline
shares are of the step program alone, so this reads the same trace file the
same way (own times from the ``XLA Ops`` line, the scope path from the
event's metadata) and keeps the operations whose path starts with the step
program's name (``jit(_step)/...``).  ``None`` without a trace, a device
plane or a scope: off a TPU, or on a program without the scopes.
"""
from __future__ import annotations

from typing import Dict, Optional

from . import phase_times

PREFIX = "lm."
STEP_PATH = "jit(_step)/"

#: trace file -> {phase: seconds in the step programs}
_BY_FILE: Dict[str, Optional[Dict[str, float]]] = {}


def reduce_chips(chips, prefix: str = PREFIX, program: str = STEP_PATH
                 ) -> Optional[Dict[str, float]]:
    """``phase_times.reduce_chips``'s seconds by phase over the operations of
    ``program`` alone (a program's operations nest only in its own, so they
    can be picked out before own times are taken)."""
    found = phase_times.reduce_chips(
        [[op for op in ops if op[1].startswith(program)] for ops in chips],
        prefix)
    return found and found["phases"]


def step_phases(run) -> Optional[Dict[str, float]]:
    if run.trace_summary is None:
        return None
    path = phase_times.trace_file(run)
    if path is None:
        return None
    if path not in _BY_FILE:
        with open(path, "rb") as f:
            _BY_FILE[path] = reduce_chips(phase_times.device_ops(f.read()))
        if _BY_FILE[path]:
            run.note("device seconds of the step programs by phase: "
                     + ", ".join(f"{k} {v:.4f}" for k, v in sorted(
                         _BY_FILE[path].items(), key=lambda kv: -kv[1])))
    return _BY_FILE[path]


def step_seconds(run, *phases: str) -> Optional[float]:
    """Seconds of the step programs under the named phases; ``None`` where
    there is nothing to read or none of them ran an operation."""
    found = step_phases(run)
    if found is None or not any(p in found for p in phases):
        return None
    return sum(found.get(p, 0.0) for p in phases)


def ms_per_step(run, *phases: str) -> Optional[float]:
    seconds = step_seconds(run, *phases)
    steps = run.counter("mmlspark_runner_decode_steps_total")
    if seconds is None or not steps:
        return None
    return seconds * 1e3 / steps
