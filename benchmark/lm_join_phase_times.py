"""Device seconds of the decode engine's JOIN prefill program by ``lm.*``
phase: ``lm_phase_times``' reading of the trace (own times, each booked to
the last ``lm.`` scope of its path) over the operations whose path starts
with the prefill program's name (``jit(_prefill)/...``).  ``None`` without a
trace, a device plane or a scope: off a TPU, or on a program without the
scopes."""
from __future__ import annotations

from typing import Dict, Optional

from . import lm_phase_times, phase_times

PREFILL_PATH = "jit(_prefill)/"

#: trace file -> {phase: seconds in the prefill programs}
_BY_FILE: Dict[str, Optional[Dict[str, float]]] = {}


def prefill_seconds(run, *phases: str) -> Optional[float]:
    """Seconds of the prefill programs under the named phases; ``None``
    where there is nothing to read or none of them ran an operation."""
    if run.trace_summary is None:
        return None
    path = phase_times.trace_file(run)
    if path is None:
        return None
    if path not in _BY_FILE:
        with open(path, "rb") as f:
            _BY_FILE[path] = lm_phase_times.reduce_chips(
                phase_times.device_ops(f.read()), program=PREFILL_PATH)
        if _BY_FILE[path]:
            run.note("device seconds of the prefill programs by phase: "
                     + ", ".join(f"{k} {v:.4f}" for k, v in sorted(
                         _BY_FILE[path].items(), key=lambda kv: -kv[1])))
    found = _BY_FILE[path]
    if found is None or not any(p in found for p in phases):
        return None
    return sum(found.get(p, 0.0) for p in phases)
