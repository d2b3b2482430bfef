"""Device seconds by program phase: what the device trace's operations say
about WHERE in the program they ran, added up per phase and per tree level.

The program names its device phases with ``jax.named_scope`` (the GBDT
growers: ``mmlspark_tpu/lightgbm/core.py DEVICE_PHASES``, each tree level
inside an ``L<d>`` scope besides).  A scope is metadata of the lowered
operations; the compiler carries it into each HLO instruction's
``metadata.op_name`` (``jit(multi)/.../L3/gbdt.hist/.../dot_general``) and the
TPU profiler copies that into the stat ``tf_op`` (``SCOPE_STATS``) of the
traced event's METADATA, the record that the events of one instruction share,
as ``jit(multi)/while/body/closed_call/L4/gbdt.route/gather:`` (v5e, jax
0.9.0; ``benchmark/tools/cut_scoped.py stats`` lists what a trace carries).
``jax.profiler.ProfileData`` shows an event's own stats and not its
metadata's, so the trace file is read here as what it is, a protocol-buffer
``XSpace``, by the few lines of wire format at the end of this module: that
needs no package at all.  From the ``XLA Ops`` line of every device plane it
takes each event's OWN time (``trace_reduce.self_times``: a ``while`` keeps
only what its body leaves) and books it to the LAST component of the path
that starts with the prefix (``gbdt.``), so ``gbdt.hist/gbdt.allreduce/...``
is ``gbdt.allreduce``, and to its ``L<d>`` component, if it has one.

What it cannot do.  XLA fuses across scope boundaries and a fusion carries one
``op_name``: a fused operation is booked whole to the scope its own metadata
names.  The note a traced run prints (each phase's largest kinds of operation)
is there to judge how much that blurs.  Operations on other lines of the
plane (``Async XLA Ops``) are not read, as in ``trace_reduce``.  On the CPU
backend the events carry no scope path, and a program without the scopes has
none to read: both give ``None``, and the metrics are left out of the line.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from . import trace_reduce
from .harness import TRACE_DIR
from .trace_reduce import DEVICE_PLANE, OPS_LINE

#: the stats of a device event's metadata that hold its instruction's
#: ``op_name``, in the order they are tried
SCOPE_STATS = ("tf_op",)
LEVEL = re.compile(r"^L\d+$")
UNSCOPED = "(unscoped)"
#: how far the sum of own times read here may lie from the harness's own
#: reduction of the same trace before the reading is thrown away
TOLERANCE = 0.005

#: one executed operation: (HLO text, scope path, start_ns, end_ns)
Op = Tuple[str, str, float, float]

#: trace file -> what ``by_phase`` made of it (nine readers ask)
_BY_FILE: Dict[str, Optional[Dict[str, Any]]] = {}


def book(path: str, prefix: str) -> Tuple[Optional[str], Optional[str]]:
    """``(phase, level)`` of a scope path: the last component that starts
    with ``prefix`` and the first that is ``L<d>``; ``None`` for each that
    the path lacks."""
    phase = level = None
    for part in path.split("/"):
        if part.startswith(prefix):
            phase = part
        elif level is None and LEVEL.match(part):
            level = part
    return phase, level


def reduce_chips(chips: Sequence[Sequence[Op]], prefix: str = "gbdt."
                 ) -> Optional[Dict[str, Any]]:
    """Seconds of own time by phase and by level over the operations of each
    chip (``device_ops``), mean over the chips; ``kinds`` holds, per phase,
    the seconds of each kind of operation (``trace_reduce.op_kind``).
    ``None`` where there is no operation or none under a ``prefix`` scope."""
    if not any(chips):
        return None
    n = len(chips)
    phases: Dict[str, float] = {}
    levels: Dict[str, float] = {}
    kinds: Dict[str, Dict[str, float]] = {}
    total = 0.0
    # the events of one instruction share their text and path: work each out once
    booked: Dict[Tuple[str, str], Tuple[str, Optional[str], str]] = {}
    for ops in chips:
        own = trace_reduce.self_times(
            [(i, s, e) for i, (_, _, s, e) in enumerate(ops)])
        for i, own_ns in own:
            label = ops[i][:2]
            if label not in booked:
                phase, level = book(label[1], prefix)
                booked[label] = (phase or UNSCOPED, level,
                                 trace_reduce.op_kind(label[0]))
            phase, level, kind = booked[label]
            s = own_ns / 1e9 / n
            total += s
            phases[phase] = phases.get(phase, 0.0) + s
            if level is not None:
                levels[level] = levels.get(level, 0.0) + s
            by_kind = kinds.setdefault(phase, {})
            by_kind[kind] = by_kind.get(kind, 0.0) + s
    unscoped = phases.pop(UNSCOPED, 0.0)
    if not phases:
        return None
    return {"phases": phases, "levels": levels, "unscoped_s": unscoped,
            "total_s": total, "kinds": kinds, "chips": n}


def trace_file(run) -> Optional[str]:
    """The traced run's one ``.xplane.pb``, where the harness left it."""
    files = glob.glob(os.path.join(
        run.manifest.path(TRACE_DIR, run.cell["name"]),
        "plugins", "profile", "*", "*.xplane.pb"))
    return files[0] if len(files) == 1 else None


def _top(seconds: Dict[str, float], k: int) -> str:
    return ", ".join(f"{name} {s:.4f}" for name, s in
                     sorted(seconds.items(), key=lambda kv: -kv[1])[:k])


def by_phase(run, prefix: str = "gbdt.") -> Optional[Dict[str, Any]]:
    """``{"phases": {name: s}, "levels": {"L0": s, ...}, "unscoped_s": s,
    "total_s": s}`` of the run's traced window, or ``None`` without a trace,
    a device plane or a scope.  The profiler runs for exactly the harness's
    window, so every device event of the file is taken, and the sum of their
    own times is then held against the harness's own reduction of the same
    file (``run.trace_summary``): off by more than ``TOLERANCE``, this
    reading is of something else, and is dropped with a note."""
    if run.trace_summary is None:
        return None
    path = trace_file(run)
    if path is None:
        return None
    if path in _BY_FILE:
        return _BY_FILE[path]
    with open(path, "rb") as f:
        found = reduce_chips(device_ops(f.read()), prefix)
    if found is not None:
        want = sum(run.trace_summary.op_seconds.values())
        if abs(found["total_s"] - want) > TOLERANCE * want:
            run.note(f"phase_times: own times add up to {found['total_s']:.6f}"
                     f" s, the harness's reduction to {want:.6f} s: not the "
                     f"same window, no phase metric")
            found = None
    if found is not None:
        share = 100.0 * found["unscoped_s"] / found["total_s"]
        run.note(f"device seconds by phase (mean of {found['chips']} chips, "
                 f"{found['total_s']:.4f} in all, {found['unscoped_s']:.4f} = "
                 f"{share:.2f}% under no {prefix}* scope): "
                 f"{_top(found['phases'], 99)}")
        run.note("device seconds by level: "
                 + ", ".join(f"{k} {v:.4f}" for k, v in sorted(
                     found["levels"].items(), key=lambda kv: int(kv[0][1:]))))
        for phase in sorted(found["kinds"]):
            run.note(f"largest kinds under {phase}: "
                     f"{_top(found['kinds'][phase], 3)}")
    _BY_FILE[path] = found
    return found


def ms_per_iter(run, phase: str) -> Optional[float]:
    """Milliseconds of device time under ``phase`` per boosting iteration of
    the window; ``None`` where ``by_phase`` has nothing or the phase ran no
    operation."""
    found, iters = by_phase(run), run.facts.get("iterations")
    if found is None or not iters or phase not in found["phases"]:
        return None
    return found["phases"][phase] * 1e3 / iters


def unscoped_share(run) -> Optional[float]:
    """Percent of the window's device time under no phase scope."""
    found = by_phase(run)
    if found is None:
        return None
    return 100.0 * found["unscoped_s"] / found["total_s"]


# ------------------------------------------- the trace file's wire format
#
# An ``.xplane.pb`` is a serialized ``XSpace`` (tsl/profiler/protobuf/
# xplane.proto).  The fields read here, by number:
#   XSpace          1 planes
#   XPlane          2 name, 3 lines, 4 event_metadata (map), 5 stat_metadata (map)
#   XLine           2 name, 3 timestamp_ns, 4 events
#   XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps, 4 stats
#   XEventMetadata  2 name, 5 stats
#   XStatMetadata   2 name
#   XStat           1 metadata_id, 5 str_value, 7 ref_value (the id of a stat
#                   metadata whose NAME is the value: how strings are shared)
#   a map entry     1 key, 2 value

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def wire_fields(buf) -> Iterator[Tuple[int, Any]]:
    """``(field number, value)`` of a message: an int for a varint, the bytes
    for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def stats_by_name(buf_list, stat_names: Dict[int, str]) -> Dict[str, Any]:
    """The ``XStat`` messages of an event or of an event metadata, by name."""
    out: Dict[str, Any] = {}
    for buf in buf_list:
        key = value = None
        for f, v in wire_fields(buf):
            if f == 1:
                key = stat_names.get(v, str(v))
            elif f == 5:
                value = _text(v)
            elif f == 7:
                value = stat_names.get(v, "")
            elif value is None:
                value = v if isinstance(v, int) else bytes(v)
        if key is not None:
            out[key] = value
    return out


def _scope(stats: Dict[str, Any]) -> str:
    for key in SCOPE_STATS:
        if key in stats:
            return str(stats[key])
    return ""


def device_planes(blob: bytes) -> Iterator[Tuple[int, Dict[int, str], Dict[
        int, Tuple[str, list]], List[Tuple[float, list]]]]:
    """Per device plane of a serialized ``XSpace``: ``(chip, stat names by
    id, event metadata by id as (name, stat messages), the XLA Ops lines as
    (timestamp_ns, event messages))``."""
    for f, plane in wire_fields(memoryview(blob)):
        if f != 1:
            continue
        fields = list(wire_fields(plane))
        m = DEVICE_PLANE.match(next((_text(v) for pf, v in fields if pf == 2),
                                    ""))
        if not m:
            continue
        metadata, stat_names, ops_lines = {}, {}, []
        for pf, v in fields:
            if pf in (4, 5):
                entry = dict(wire_fields(v))
                body = list(wire_fields(entry.get(2, b"")))
                label = next((_text(x) for k, x in body if k == 2), "")
                if pf == 5:
                    stat_names[entry.get(1, 0)] = label
                else:
                    metadata[entry.get(1, 0)] = (
                        label, [x for k, x in body if k == 5])
            elif pf == 3:
                line = list(wire_fields(v))
                if any(lf == 2 and _text(x) == OPS_LINE for lf, x in line):
                    t0 = next((x for lf, x in line if lf == 3), 0)
                    ops_lines.append(
                        (float(t0), [x for lf, x in line if lf == 4]))
        yield int(m.group(2)), stat_names, metadata, ops_lines


def device_ops(blob: bytes) -> List[List[Op]]:
    """The executed operations of each chip, in the order of the chips, as
    ``trace_reduce`` takes them from ``ProfileData``, each with the scope
    path its metadata carries."""
    chips = []
    for chip, stat_names, metadata, ops_lines in device_planes(blob):
        known = {mid: (label, _scope(stats_by_name(stats, stat_names)))
                 for mid, (label, stats) in metadata.items()}
        ops: List[Op] = []
        for t0, events in ops_lines:
            for event in events:
                mid = offset = duration = 0
                for f, v in wire_fields(event):
                    if f == 1:
                        mid = v
                    elif f == 2:
                        offset = v
                    elif f == 3:
                        duration = v
                start = t0 + offset / 1000.0
                ops.append(known.get(mid, ("", ""))
                           + (start, start + duration / 1000.0))
        chips.append((chip, ops))
    return [ops for _, ops in sorted(chips, key=lambda c: c[0])]
