"""Device seconds by PROGRAM: what each compiled program (XLA module) took on
the device inside the traced window.

A device plane of the trace has, beside ``XLA Ops`` (one event an executed
operation, which ``trace_reduce`` reads), the line ``XLA Modules``: one event
for every execution of a whole program, named after the jitted function
(``jit__step(...)`` for a function ``_step``).  Where a system runs several
programs in turn, as a decode engine runs a step program and a join's prefill
and sampler, the time of each is the sum of its events, cut to the window.
Mean over the chips.  On the CPU backend there is no device plane: ``None``,
and the metrics that read this are left out of the line.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from . import phase_times
from .trace_reduce import DEVICE_PLANE, profile_start_ns

MODULES_LINE = "XLA Modules"
#: the decode engine's programs (``ModelRunner._decode_executables``,
#: ``_sample_executable``), by the names of the functions they are jitted from
STEP_PROGRAMS = r"^jit__step\b"
JOIN_PROGRAMS = r"^jit__(prefill|sample)\b"

#: trace file -> {program name: seconds in the window}
_BY_FILE: Dict[str, Dict[str, float]] = {}


def reduce_profile(profile, lo_ns: float, hi_ns: float) -> Dict[str, float]:
    """Seconds of each program's executions inside ``[lo_ns, hi_ns]`` (the
    trace's own clock), mean over the device planes."""
    chips = 0
    seconds: Dict[str, float] = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        chips += 1
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for e in line.events:
                s = max(float(e.start_ns), lo_ns)
                t = min(float(e.start_ns + e.duration_ns), hi_ns)
                if t > s:
                    seconds[e.name] = seconds.get(e.name, 0.0) + (t - s) / 1e9
    return {name: s / chips for name, s in seconds.items()} if chips else {}


def by_program(run) -> Optional[Dict[str, float]]:
    """``reduce_profile`` of the run's trace over its window; ``None``
    without a trace, off a TPU, or where the trace names no program."""
    if run.trace_summary is None or run.platform != "tpu":
        return None
    path = phase_times.trace_file(run)
    if path is None:
        return None
    if path not in _BY_FILE:
        from jax.profiler import ProfileData
        profile = ProfileData.from_file(path)
        lo = run.window_start_wall_ns - profile_start_ns(profile)
        _BY_FILE[path] = reduce_profile(
            profile, lo, lo + (run.window_end_s - run.window_start_s) * 1e9)
        top = sorted(_BY_FILE[path].items(), key=lambda kv: -kv[1])[:6]
        run.note("device seconds by program: " + ", ".join(
            f"{name} {s:.4f}" for name, s in top))
    return _BY_FILE[path] or None


def seconds_of(run, pattern: str) -> Optional[float]:
    """Seconds of the programs whose name matches ``pattern`` (a regular
    expression, searched); ``None`` where there is nothing to read."""
    programs = by_program(run)
    if programs is None:
        return None
    hit = [s for name, s in programs.items() if re.search(pattern, name)]
    return sum(hit) if hit else None
