"""From a profiler trace (``.xplane.pb``) to numbers: device busy time, the
operations that took it, and the idle gaps of the device by what the host was
doing in them.

Read with nothing but JAX (``jax.profiler.ProfileData``).  Checked in
``tests/benchmark_tests`` against a recorded trace.

What a trace of this stack looks like (v5e, jax 0.9.0):

- one plane ``/device:TPU:<i>`` per chip; its line ``XLA Ops`` holds one event
  per executed HLO operation (start, duration), named by the operation's whole
  HLO text (``%fusion.6 = bf16[2048,56,56,256]{...} fusion(...)``); ``XLA
  Modules`` holds one per program and ``Async XLA Ops`` the asynchronous
  copies (not read here).  Events of ``XLA Ops`` nest: a ``while`` spans the
  operations of its body.  Busy time is the union of the ``XLA Ops``
  intervals, containers included: a program is on the device, and a wait
  inside it is the device's, not the host's.  An operation's own time is its
  duration less that of the events nested in it, so such a wait shows as the
  container's own time.
- times count from the ``profile_start_time`` (wall clock) of the ``Task
  Environment`` plane, which is how the harness's own window and spans are
  laid over the trace.
- on the CPU backend (``platform="cpu"``, the tests) there is no device
  plane; operations are the events of ``/host:CPU`` that carry an ``hlo_op``
  stat, there when the host tracer is on.  A CPU run never reports a device
  metric.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


# ------------------------------------------------------ interval arithmetic

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of the disjoint, sorted intervals ``a`` that ``b`` (also
    disjoint and sorted) does not cover."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


# -------------------------------------------------------------- the summary

@dataclasses.dataclass
class TraceSummary:
    chips: int
    window_s: float
    #: seconds with an operation on the device, mean over the chips
    busy_s: float
    busy_s_per_chip: List[float]
    #: kind of operation (opcode and result shape, with the operations of
    #: that kind) -> seconds of their own time, mean over the chips
    op_seconds: Dict[str, float]
    #: harness span -> seconds of the first chip's idle gaps that fell in it
    gap_seconds: Dict[str, float]
    longest_gap_s: float

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, k: int) -> List[List[object]]:
        return [[n, s] for n, s in sorted(self.op_seconds.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k: int) -> List[List[object]]:
        return [[n, s] for n, s in sorted(self.gap_seconds.items(),
                                          key=lambda kv: -kv[1])[:k]]


_HLO = re.compile(r"^%?(?P<name>\S+) = (?P<type>\(|\w+\[[\d,]*\])\S* "
                  r"(?:.*?\) )?(?P<opcode>[\w\-]+)\(")


def op_kind(event_name: str) -> str:
    """What an operation is, without which one it is: ``fusion
    bf16[2048,56,56,256]`` (opcode and result shape) from its HLO text.  The
    same step of an algorithm at another level or layer is another operation
    of the same kind; a ranking by kind adds them up.  An event that is no
    HLO text is its own kind."""
    m = _HLO.match(event_name)
    if not m:
        return op_name(event_name)[:120]
    shape = "(tuple)" if m.group("type") == "(" else m.group("type")
    return f"{m.group('opcode')} {shape}"


def op_name(event_name: str) -> str:
    """The operation's name alone: ``all-reduce.5`` from its HLO text."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(ops: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float]]:
    """``(name, own_ns)`` of events that nest on one timeline: an event's own
    time is its duration less the durations of the events directly inside
    it.  A ``while`` then keeps only what its body's operations leave."""
    out: List[List[object]] = []
    stack: List[int] = []                     # indices into out, by nesting
    ends: List[float] = []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and ends[-1] <= s:
            stack.pop()
            ends.pop()
        if stack:
            out[stack[-1]][1] -= e - s
        out.append([name, e - s])
        stack.append(len(out) - 1)
        ends.append(e)
    return [(n, max(t, 0.0)) for n, t in out]


def _device_ops(profile, platform: str
                ) -> List[List[Tuple[str, float, float]]]:
    """Per chip, the executed operations as ``(name, start_ns, end_ns)``."""
    if platform == "cpu":
        # no device plane: the operations are host events with an hlo_op stat
        return [[(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                 for plane in profile.planes if plane.name == HOST_PLANE
                 for line in plane.lines for e in line.events
                 if any(k == "hlo_op" for k, _ in e.stats)]]
    chips = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chips.append((int(m.group(2)), [
                (e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]))
    return [ops for _, ops in sorted(chips, key=lambda c: c[0])]


def _attribute(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost span that holds the middle of the gap."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "(no span)"


def profile_start_ns(profile) -> float:
    """Wall-clock time (ns since the epoch) that the trace's own clock
    counts from: the ``profile_start_time`` of its ``Task Environment``."""
    for plane in profile.planes:
        if plane.name == "Task Environment":
            for key, value in plane.stats:
                if key == "profile_start_time":
                    return float(value)
    raise ValueError("the trace does not say when it started")


def reduce_profile(profile, window_ns: Interval,
                   spans: Sequence[Tuple[str, float, float]] = (),
                   platform: str = "tpu") -> TraceSummary:
    """Reduce a ``ProfileData`` over the window ``window_ns``.  Idle gaps of
    the first chip go to the innermost of ``spans`` (``(name, start, end)``,
    the harness's own) that holds their middle.  Window and spans are in ns
    on the trace's own clock."""
    chips = _device_ops(profile, platform)
    if not any(chips):
        raise ValueError(f"the trace holds no {platform} operation")
    lo, hi = window_ns
    if hi <= lo:
        raise ValueError("the traced window is empty")

    n = len(chips)
    busy_per_chip = []
    kind_seconds: Dict[str, float] = {}
    kind_names: Dict[str, set] = {}
    first_busy: List[Interval] = []
    for i, ops in enumerate(chips):
        clipped = [(name, max(s, lo), min(e, hi)) for name, s, e in ops
                   if min(e, hi) > max(s, lo)]
        busy = union((s, e) for _, s, e in clipped)
        if i == 0:
            first_busy = busy
        busy_per_chip.append(total(busy) / 1e9)
        for name, own in self_times(clipped):
            kind = op_kind(name)
            kind_seconds[kind] = kind_seconds.get(kind, 0.0) + own / 1e9 / n
            kind_names.setdefault(kind, set()).add(op_name(name))

    op_seconds = {}
    for kind, seconds in kind_seconds.items():
        names = sorted(kind_names[kind])
        more = f" +{len(names) - 1}" if len(names) > 1 else ""
        label = kind if names == [kind] else f"{kind} ({names[0]}{more})"
        op_seconds[label] = seconds
    gap_seconds: Dict[str, float] = {}
    longest = 0.0
    for g in gaps(first_busy, lo, hi):
        name = _attribute(g, spans)
        gap_seconds[name] = gap_seconds.get(name, 0.0) + (g[1] - g[0]) / 1e9
        longest = max(longest, (g[1] - g[0]) / 1e9)
    return TraceSummary(
        chips=n, window_s=(hi - lo) / 1e9,
        busy_s=sum(busy_per_chip) / n, busy_s_per_chip=busy_per_chip,
        op_seconds=op_seconds, gap_seconds=gap_seconds,
        longest_gap_s=longest)


def reduce_file(path: str, window_wall_ns: Interval,
                wall_spans: Sequence[Tuple[str, float, float]] = (),
                platform: str = "tpu") -> TraceSummary:
    """Reduce the trace at ``path``; the window and the spans are in
    wall-clock ns, as the harness read them."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    zero = profile_start_ns(profile)
    return reduce_profile(
        profile, (window_wall_ns[0] - zero, window_wall_ns[1] - zero),
        [(n, s - zero, e - zero) for n, s, e in wall_spans], platform)
