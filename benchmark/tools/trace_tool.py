"""The builder's tools for profiler traces, no part of a run.

    python3 benchmark/tools/trace_tool.py dump <trace.xplane.pb>
        what the trace holds: planes, lines, event counts, the first events
        of each line with their stats, the longest operations

    python3 benchmark/tools/trace_tool.py cut <trace.xplane.pb> <out.xplane.pb> \
            --seconds 0.05 [--skip 1.0] [--devices 1]
        a small trace for the tests: the operations of the first chips and,
        where the host was traced, its spans (the harness's annotations and
        JAX's PjitFunction events) that fall in ``--seconds``, starting
        ``--skip`` seconds after the first device operation, and a host event
        ``window`` that marks the cut's extent.  Written through
        ProfileData's own text-proto converter, so it needs nothing but JAX.
"""
from __future__ import annotations

import argparse
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402


def dump(path: str, head: int = 4) -> None:
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in profile.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines, stats "
              f"{[(k, v) for k, v in plane.stats][:6]}")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            lo = min(e.start_ns for e in events)
            hi = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{lo:.0f} .. {hi:.0f} ns ({(hi - lo) / 1e9:.3f} s)")
            for e in events[:head]:
                print(f"      {e.name!r} start {e.start_ns:.0f} dur "
                      f"{e.duration_ns:.0f} stats "
                      f"{[(k, str(v)[:60]) for k, v in e.stats][:8]}")
    ops = [o for chip in trace_reduce._device_ops(profile, "tpu") for o in chip]
    if not ops:
        print("NO SUMMARY: no device operation")
        return
    summary = trace_reduce.reduce_profile(
        profile, (min(s for _, s, _ in ops), max(e for _, _, e in ops)))
    print(f"SUMMARY chips={summary.chips} window_s={summary.window_s:.4f} "
          f"busy_s={summary.busy_s:.4f} per_chip={summary.busy_s_per_chip} "
          f"longest_gap_s={summary.longest_gap_s:.4f}")
    print("TOP OPS", summary.top_ops(25))


def _quote(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'


def cut(path: str, out: str, seconds: float, skip: float, devices: int) -> None:
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    starts = [s for chip in trace_reduce._device_ops(profile, "tpu")
              for _, s, _ in chip]
    if not starts:
        raise SystemExit("the trace has no device operation")
    lo = min(starts) + skip * 1e9
    hi = lo + seconds * 1e9
    keep_host = re.compile(r"^(fit|transform|apply_batch|PjitFunction\(.*)$")
    planes = []
    for plane in profile.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        host = plane.name == trace_reduce.HOST_PLANE
        if not host and not (m and int(m.group(2)) < devices):
            continue
        lines = []
        for line in plane.lines:
            if m and line.name != trace_reduce.OPS_LINE:
                continue
            events = []
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t <= lo or s >= hi or (host and not keep_host.match(e.name)):
                    continue
                events.append((e.name, max(s, lo), min(t, hi)))
            if events:
                lines.append((line.name, events))
        planes.append((plane.name, lines))
    # the cut's extent, for the test that reads the small trace
    mark = ("benchmark.cut", [("window", lo, hi)])
    for pname, lines in planes:
        if pname == trace_reduce.HOST_PLANE:
            lines.append(mark)
            break
    else:
        planes.append((trace_reduce.HOST_PLANE, [mark]))

    text = []
    for pid, (pname, lines) in enumerate(planes, 1):
        names = sorted({n for _, evs in lines for n, _, _ in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        text.append(f"planes {{ id: {pid} name: {_quote(pname)}")
        for lid, (lname, events) in enumerate(lines, 1):
            text.append(f"  lines {{ id: {lid} name: {_quote(lname)} "
                        f"timestamp_ns: {int(lo)}")
            for n, s, t in events:
                text.append(f"    events {{ metadata_id: {ids[n]} offset_ps: "
                            f"{int((s - lo) * 1000)} duration_ps: "
                            f"{int((t - s) * 1000)} }}")
            text.append("  }")
        for n, i in ids.items():
            text.append(f"  event_metadata {{ key: {i} value {{ id: {i} "
                        f"name: {_quote(n)} }} }}")
        text.append("}")
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(text))
    with open(out, "wb") as f:
        f.write(blob)
    print(f"wrote {out}: {len(blob)} bytes, "
          f"{sum(len(e) for _, ls in planes for _, e in ls)} events")


def main() -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)
    d = sub.add_parser("dump")
    d.add_argument("trace")
    c = sub.add_parser("cut")
    c.add_argument("trace")
    c.add_argument("out")
    c.add_argument("--seconds", type=float, default=0.05)
    c.add_argument("--skip", type=float, default=1.0)
    c.add_argument("--devices", type=int, default=1)
    args = ap.parse_args()
    if args.cmd == "dump":
        dump(args.trace)
    else:
        cut(args.trace, args.out, args.seconds, args.skip, args.devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
