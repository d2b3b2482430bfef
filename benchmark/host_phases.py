"""The device's idle gaps by what the program's own loop was doing in them.

The two loops of ``ModelRunner`` lap a clock on ``time.perf_counter``
(``mmlspark_tpu.observability.tracing.LapClock``): the continuous engine's
round (loop ``decode``) and ``apply_batch``'s chunks (loop ``batch``).  Each
ended phase is a record ``(loop, name, start_s, end_s)`` in the registry's
ring (``phase_log(registry).snapshot()``); the phases of one loop's thread
are flat, so they never overlap.  This module reads the trace file a second
time (as ``program_times`` and ``phase_times`` do) and lays three things on
the trace's one clock:

- the first chip's idle gaps inside the window, from the operations and the
  interval arithmetic of ``trace_reduce.reduce_profile``, so that they add up
  to ``window_s - busy_s`` of the same run;
- the loop's phase records, put there as the harness puts its own spans:
  ``window_start_wall_ns + (t - window_start_s) * 1e9 - profile_start_ns``;
- the executions of the programs (``XLA Modules``), for the check of the
  clock: a phase in which the host waits for a program ends just after that
  program's execution does, if the two clocks are laid over each other
  rightly.

Every gap is SPLIT BY OVERLAP over the phases it crosses (a gap of 5 ms crosses
several phases shorter than a millisecond); what no phase covers goes to
``(no phase)``.  An upload has no phase: the call that starts it returns at
once, so idle time under an upload is booked to the phase the host is in.

A reader built on this returns a number whenever the run was traced and its
denominator (steps, batches) is above zero: a phase with no idle under it
reads 0.0.  ``None`` is for an untraced run, and for a program that keeps no
phase ring (one from before this module).
"""
from __future__ import annotations

import re
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import phase_times, program_times, trace_reduce
from .trace_reduce import Interval

NO_PHASE = "(no phase)"

Phase = Tuple[str, float, float]        # (name, start_ns, end_ns)


# ------------------------------------------------------------ the arithmetic

def in_order(phases: Sequence[Phase]) -> List[Phase]:
    """``phases`` by start.  One thread's records never overlap, and the
    split below counts on it: two that do (two threads in one loop) raise."""
    out = sorted(phases, key=lambda p: (p[1], p[2]))
    for (a, _, a_end), (b, b_start, _) in zip(out, out[1:]):
        if b_start < a_end:
            raise ValueError(f"phase {b} starts {a_end - b_start:.0f} ns "
                             f"inside {a}: more than one thread lapped "
                             "this loop")
    return out


def split(gaps: Sequence[Interval], phases: Sequence[Phase]
          ) -> Tuple[Dict[str, float], Tuple[float, str]]:
    """``({phase: ns of the gaps under it}, (the longest gap's ns, the phase
    that held most of it))``.  ``gaps`` sorted and disjoint, ``phases`` as
    ``in_order`` leaves them; ``NO_PHASE`` takes what no phase covers, and is in
    the result even where that is nothing."""
    under: Dict[str, float] = {NO_PHASE: 0.0}
    longest: Tuple[float, str] = (0.0, NO_PHASE)
    j = 0
    for g0, g1 in gaps:
        while j < len(phases) and phases[j][2] <= g0:
            j += 1
        mine: Dict[str, float] = {}
        k = j
        while k < len(phases) and phases[k][1] < g1:
            name, s, e = phases[k]
            over = min(g1, e) - max(g0, s)
            if over > 0:
                mine[name] = mine.get(name, 0.0) + over
            k += 1
        mine[NO_PHASE] = max(0.0, (g1 - g0) - sum(mine.values()))
        for name, ns in mine.items():
            under[name] = under.get(name, 0.0) + ns
        if g1 - g0 > longest[0]:
            longest = (g1 - g0, max(mine, key=mine.get))
    return under, longest


def clipped_seconds(phases: Sequence[Phase], lo: float, hi: float
                    ) -> Dict[str, float]:
    """Seconds of each phase inside ``[lo, hi]``."""
    out: Dict[str, float] = {}
    for name, s, e in phases:
        over = min(e, hi) - max(s, lo)
        if over > 0:
            out[name] = out.get(name, 0.0) + over / 1e9
    return out


def clock_lag_ms(wait_ends: Sequence[float], program_ends: Sequence[float]
                 ) -> Optional[float]:
    """The median, over ``wait_ends`` (ns, the ends of the phases in which
    the host was blocked on a program: the engine's ``fetch``, the batch
    loop's ``wait``), of the distance to the NEAREST of ``program_ends`` (ns,
    the ends of that program's executions on the device), in ms and signed:
    positive where the host came back after the program ended.  The host
    comes back a wake-up (and, for a fetch, the copy of a few tokens) after
    the program, so the median is positive when the host's clock and the
    trace's are laid over each other rightly, and reads negative when the
    host's is laid early by more than that.  It is a median because single
    laps pair with the wrong end (a fetch that came late to a step already
    ended, the window's first fetch, whose step began before the trace did),
    and it cannot see an offset of half a step or more.  ``None`` where
    either list is empty."""
    ends = sorted(program_ends)
    if not ends or not wait_ends:
        return None
    lags = []
    i = 0
    for f in sorted(wait_ends):
        while i + 1 < len(ends) and abs(ends[i + 1] - f) <= abs(ends[i] - f):
            i += 1
        lags.append((f - ends[i]) / 1e6)
    return statistics.median(lags)


# ------------------------------------------------------------------ one run

#: loop -> (the phase in which its thread waits for a program, that program
#: by its name on ``XLA Modules``): what the check of the clock pairs.  The
#: batch loop's ``wait`` is a bare ``block_until_ready``, no copy: it reads
#: the wake-up alone, which says how much of the engine's lag is the copy
BLOCKED_ON = {"decode": ("fetch", program_times.STEP_PROGRAMS),
              "batch": ("wait", r"^jit_")}


def _program_ends(profile) -> List[Tuple[str, float]]:
    """``(name, end ns)`` of the programs' executions on the first device
    plane; empty on the CPU, which has none."""
    planes = sorted((int(m.group(2)), p) for p in profile.planes
                    for m in [trace_reduce.DEVICE_PLANE.match(p.name)] if m)
    if not planes:
        return []
    return [(e.name, float(e.start_ns + e.duration_ns))
            for line in planes[0][1].lines
            if line.name == program_times.MODULES_LINE for e in line.events]


def _trace_gaps(run) -> Tuple[float, float, List[Interval],
                              List[Tuple[str, float]]]:
    """From the run's trace file: the window on the trace's clock, the first
    chip's idle gaps in it and the ends of the programs' executions."""
    path = phase_times.trace_file(run)
    if path is None:
        raise RuntimeError("a traced run without its one trace file")
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    lo = run.window_start_wall_ns - trace_reduce.profile_start_ns(profile)
    hi = lo + (run.window_end_s - run.window_start_s) * 1e9
    first = trace_reduce._device_ops(profile, run.platform)[0]
    busy = trace_reduce.union((max(s, lo), min(e, hi)) for _, s, e in first)
    return lo, hi, trace_reduce.gaps(busy, lo, hi), _program_ends(profile)


def _laid_out(run) -> Optional[Dict[str, Any]]:
    """The run's gaps, window and phase records on the trace's clock, made
    once a run (kept in ``run.facts``); ``None`` for an untraced run or a
    program without a phase ring."""
    if run.trace_summary is None:
        return None
    try:
        from mmlspark_tpu.observability.tracing import phase_log
    except ImportError:
        return None
    if "host_phases" not in run.facts:
        lo, hi, gaps, program_ends = _trace_gaps(run)
        snap = phase_log(run.registry).snapshot()
        records = snap["records"]
        if snap["dropped"] and records \
                and min(r[2] for r in records) > run.window_start_s:
            run.fail(f"the phase ring of {snap['capacity']} records dropped "
                     f"{snap['dropped']}, some of them inside the window: "
                     "the idle time by phase is read from what is left")
        by_loop: Dict[str, List[Phase]] = {}
        for loop, name, t0, t1 in records:
            by_loop.setdefault(loop, []).append(
                (name, lo + (t0 - run.window_start_s) * 1e9,
                 lo + (t1 - run.window_start_s) * 1e9))
        run.facts["host_phases"] = {
            "lo": lo, "hi": hi, "gaps": gaps, "program_ends": program_ends,
            "phases": {loop: in_order(p) for loop, p in by_loop.items()},
            "by_loop": {}}
    return run.facts["host_phases"]


def _by_loop(run, loop: str) -> Optional[Dict[str, Any]]:
    laid = _laid_out(run)
    if laid is None:
        return None
    if loop not in laid["by_loop"]:
        lo, hi = laid["lo"], laid["hi"]
        phases = laid["phases"].get(loop, [])
        under, longest = split(laid["gaps"], phases)
        idle = {name: ns / 1e9 for name, ns in under.items()}
        seconds = clipped_seconds(phases, lo, hi)
        laid["by_loop"][loop] = {"idle": idle, "seconds": seconds}

        def table(d):
            return phase_times._top(d, len(d))
        run.note(f"idle seconds of the first chip by phase of the {loop} "
                 f"loop ({sum(idle.values()):.4f} in all): {table(idle)}")
        run.note(f"seconds of the {loop} loop's thread by phase: "
                 f"{table(seconds)}; the longest idle gap "
                 f"{longest[0] / 1e6:.3f} ms, most of it under {longest[1]}")
        blocked, program = BLOCKED_ON[loop]
        lag = clock_lag_ms(
            [e for n, _, e in phases if n == blocked and lo <= e <= hi],
            [e for n, e in laid["program_ends"] if re.search(program, n)])
        run.note(f"clock check: a {blocked} phase of the {loop} loop ends "
                 + ("(nothing to pair in this trace)" if lag is None
                    else f"{lag:.4f} ms (median)")
                 + f" after the nearest end of a {program} execution")
    return laid["by_loop"][loop]


def idle_by_phase(run, loop: str) -> Optional[Dict[str, float]]:
    """Seconds of the first chip's idle gaps in the window under each phase
    of ``loop`` and under ``NO_PHASE``; they add up to the window less the
    chip's busy time."""
    mine = _by_loop(run, loop)
    return None if mine is None else mine["idle"]


def seconds_by_phase(run, loop: str) -> Optional[Dict[str, float]]:
    """Seconds ``loop``'s thread spent in each phase inside the window."""
    mine = _by_loop(run, loop)
    return None if mine is None else mine["seconds"]


def _ms_per(seconds: Optional[Dict[str, float]], names: Sequence[str],
            count: Optional[float]) -> Optional[float]:
    if seconds is None or not count:
        return None
    return sum(seconds.get(n, 0.0) for n in names) * 1e3 / count


def _steps(run) -> Optional[float]:
    return run.counter("mmlspark_runner_decode_steps_total")


def _batches(run) -> Optional[float]:
    # the count ``runner.device_ms_per_batch`` divides by
    return run.counter("mmlspark_runner_batches_total", runner="dl.jax_model")


def idle_ms_per_step(run, *names: str) -> Optional[float]:
    """Idle ms under the engine's phases ``names``, per decode step."""
    return _ms_per(idle_by_phase(run, "decode"), names, _steps(run))


def work_ms_per_step(run, *but: str) -> Optional[float]:
    """Ms the engine's thread spent in every phase except ``but``, per step."""
    seconds = seconds_by_phase(run, "decode")
    if seconds is None:
        return None
    return _ms_per(seconds, [n for n in seconds if n not in but], _steps(run))


def idle_ms_per_batch(run, *names: str) -> Optional[float]:
    """Idle ms under ``apply_batch``'s phases ``names``, per batch."""
    return _ms_per(idle_by_phase(run, "batch"), names, _batches(run))
