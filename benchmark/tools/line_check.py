"""What a result line lacks: the builder's check, no part of a run.

    python3 benchmark/tools/line_check.py <cell> [<file>]

Reads a run's standard output from ``<file>`` or standard input, takes its
last line (the result), and holds it to ``BENCHMARK.json``: ``per_layer`` for
a traced line (one with ``device.window_s``), ``end_to_end`` otherwise.
Prints every name listed for the cell that ``metrics`` lacks and exits 1 if
there is one: the driver refuses such a line, and the harness leaves a metric
out silently when its reader returns ``None``.  For a traced line it also
prints the two sums that hold by construction: the four ``decode.idle_*``
against ``decode.host_ms_per_step``, the four ``runner.idle_*`` against the
window's idle time a batch.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import Manifest  # noqa: E402


def missing(cell: str, line: dict, manifest: Manifest) -> list:
    group = "per_layer" if "window_s" in line.get("device", {}) \
        else "end_to_end"
    return [m["name"] for m in manifest.metrics_for(group, cell)
            if m["name"] not in line.get("metrics", {})]


def sums(line: dict) -> list:
    """``(what, parts' sum, whole)`` of the identities the line can show."""
    value = {n: m["value"] for n, m in line.get("metrics", {}).items()}
    dev, out = line.get("device", {}), []
    for prefix, whole in (
            ("decode.idle_", value.get("decode.host_ms_per_step")),
            ("runner.idle_", None if "runner.device_ms_per_batch" not in value
             or not dev.get("busy_s") else (dev["window_s"] - dev["busy_s"])
             / dev["busy_s"] * value["runner.device_ms_per_batch"])):
        parts = [v for n, v in value.items() if n.startswith(prefix)]
        if parts and whole is not None:
            out.append((prefix + "*", sum(parts), whole))
    return out


def main(argv) -> int:
    if not 2 <= len(argv) <= 3:
        print(__doc__, file=sys.stderr)
        return 2
    manifest = Manifest(ROOT)
    manifest.cell(argv[1])
    with (open(argv[2]) if len(argv) == 3 else sys.stdin) as f:
        line = json.loads([t for t in f.read().splitlines() if t.strip()][-1])
    for what, parts, whole in sums(line):
        print(f"{argv[1]}: {what} add up to {parts:.6f}, the whole is "
              f"{whole:.6f} ({100.0 * parts / whole if whole else 0:.2f}%)")
    lacks = missing(argv[1], line, manifest)
    print(f"{argv[1]}: {len(line.get('metrics', {}))} metrics on the line, "
          f"correct={line.get('correct')}, lacks {lacks or 'nothing'}")
    return 1 if lacks else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
