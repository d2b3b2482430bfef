"""The builder's tools for the scope paths of a profiler trace, no part of a
run (``trace_tool.py`` beside this file cuts and dumps traces without them).

    python3 benchmark/tools/cut_scoped.py stats <trace.xplane.pb>
        which stats the device's operation events carry, themselves or on the
        metadata their instruction's events share, on how many events, and
        one whole value of each: how to find the stat that holds an
        instruction's ``op_name`` (``phase_times.SCOPE_STATS``) after a change
        of the compiler or the profiler

    python3 benchmark/tools/cut_scoped.py cut <trace.xplane.pb> <out.xplane.pb> \
            --seconds 0.25 [--skip 1.0] [--devices 1]
        a small trace for the tests, as ``trace_tool.py cut`` makes one, of the
        device's ``XLA Ops`` alone, that keeps each event's scope path: one
        ``stat_metadata`` entry, and one ``stats`` entry on the
        ``event_metadata`` of each operation.  Written through ProfileData's
        own text-proto converter, so it needs nothing but JAX.
"""
from __future__ import annotations

import argparse
import os
import sys
from collections import Counter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import phase_times, trace_reduce  # noqa: E402


def stats(path: str) -> None:
    with open(path, "rb") as f:
        blob = f.read()
    events = 0
    seen: Counter = Counter()
    example = {}
    for _, stat_names, metadata, ops_lines in phase_times.device_planes(blob):
        on_metadata = {mid: phase_times.stats_by_name(st, stat_names)
                       for mid, (_, st) in metadata.items()}
        for _, line in ops_lines:
            for event in line:
                events += 1
                fields = list(phase_times.wire_fields(event))
                mid = next((v for f, v in fields if f == 1), 0)
                own = phase_times.stats_by_name([v for f, v in fields if f == 4],
                                         stat_names)
                for where, found in (("event", own),
                                     ("metadata", on_metadata.get(mid, {}))):
                    for key, value in found.items():
                        seen[(where, key)] += 1
                        example.setdefault((where, key), (mid, value))
    print(f"{path}: {events} events on the devices' {trace_reduce.OPS_LINE!r}")
    for (where, key), n in seen.most_common():
        mid, value = example[(where, key)]
        print(f"  {key!r} on the {where} of {n} events, e.g. {value!r} "
              f"(event metadata {mid})")
    print(f"phase_times.SCOPE_STATS = {phase_times.SCOPE_STATS}")


def _quote(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'


def cut(path: str, out: str, seconds: float, skip: float, devices: int) -> None:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        chips = phase_times.device_ops(f.read())[:devices]
    starts = [s for ops in chips for _, _, s, _ in ops]
    if not starts:
        raise SystemExit("the trace has no device operation")
    lo = min(starts) + skip * 1e9
    hi = lo + seconds * 1e9
    stat = phase_times.SCOPE_STATS[0]
    text, kept = [], 0
    for chip, ops in enumerate(chips):
        ids = {}                       # (HLO text, scope path) -> metadata id
        text.append(f'planes {{ id: {chip + 1} name: "/device:TPU:{chip}"')
        text.append(f"  lines {{ id: 1 name: {_quote(trace_reduce.OPS_LINE)} "
                    f"timestamp_ns: {int(lo)}")
        for name, scope, s, t in ops:
            if t <= lo or s >= hi:
                continue
            mid = ids.setdefault((name, scope), len(ids) + 1)
            s, t = max(s, lo), min(t, hi)
            text.append(f"    events {{ metadata_id: {mid} offset_ps: "
                        f"{int((s - lo) * 1000)} duration_ps: "
                        f"{int((t - s) * 1000)} }}")
            kept += 1
        text.append("  }")
        for (name, scope), mid in ids.items():
            scoped = f" stats {{ metadata_id: 1 str_value: {_quote(scope)} }}" \
                if scope else ""
            text.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                        f"name: {_quote(name)}{scoped} }} }}")
        text.append(f"  stat_metadata {{ key: 1 value {{ id: 1 name: "
                    f"{_quote(stat)} }} }}")
        text.append("}")
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(text))
    with open(out, "wb") as f:
        f.write(blob)
    print(f"wrote {out}: {len(blob)} bytes, {kept} events, scope paths under "
          f"the stat {stat!r}")


def main() -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("stats")
    s.add_argument("trace")
    c = sub.add_parser("cut")
    c.add_argument("trace")
    c.add_argument("out")
    c.add_argument("--seconds", type=float, default=0.25)
    c.add_argument("--skip", type=float, default=1.0)
    c.add_argument("--devices", type=int, default=1)
    args = ap.parse_args()
    if args.cmd == "stats":
        stats(args.trace)
    else:
        cut(args.trace, args.out, args.seconds, args.skip, args.devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
