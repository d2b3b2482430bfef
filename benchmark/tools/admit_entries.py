"""Append held-back entries to a checkout's ``BENCHMARK.json``: no part of a
run.

    python3 benchmark/tools/admit_entries.py <root> <entries.json>

``<entries.json>`` holds lists under ``workloads``, ``end_to_end`` and
``per_layer`` (any of them; ``benchmark/host_phases_entries.json`` has the
last alone), as they would stand in ``BENCHMARK.json``.  Each list is
appended to ``<root>/BENCHMARK.json``'s, nothing else is touched, and the
result must hold to the contract (``Manifest.problems()``).  A benchmark PR
runs it on the repo; before that the tests and the builder run it on a COPY,
so that the harness reads the entries' metrics there.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import Manifest, load_json  # noqa: E402

GROUPS = ("workloads", "end_to_end", "per_layer")


def admit(root: str, entries: str) -> list:
    """The names appended to ``<root>/BENCHMARK.json`` from ``entries``."""
    held = load_json(entries)
    path = os.path.join(root, "BENCHMARK.json")
    bench = load_json(path)
    added = []
    for group in GROUPS:
        bench[group] += held.get(group, [])
        added += [e["name"] for e in held.get(group, [])]
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
        f.write("\n")
    problems = Manifest(root).problems()
    if problems:
        raise SystemExit(f"{path} with {entries} breaks the contract: "
                         f"{problems}")
    return added


if __name__ == "__main__":
    if len(sys.argv) != 3:
        print(__doc__, file=sys.stderr)
        sys.exit(2)
    print("admitted", admit(sys.argv[1], sys.argv[2]))
