"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell in a new process, on the machine it is started on.  The
last line of standard output is the result: one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown`` when
traced).  Everything else goes on earlier lines.  Without a TPU, or with fewer
chips than the cell asks for, it prints no result and exits non-zero.
"""
from __future__ import annotations

import time

_PROCESS_START_S = time.perf_counter()      # before the heavy imports

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    # defaults only: a switch left in the environment would have the cell
    # measure another path than the one users get
    switches = sorted(k for k in os.environ if k.startswith("MMLSPARK_TPU_"))
    if switches:
        print(f"benchmark: refusing to run with {switches} set",
              file=sys.stderr)
        return 2
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.harness import run_cell
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), process_start_s=_PROCESS_START_S)
    except Exception as e:  # noqa: BLE001 - no result line on any failure
        import traceback
        traceback.print_exc()
        print(f"benchmark: {type(e).__name__}: {e}; no result",
              file=sys.stderr)
        return 1
    # each number compared beside its limit: the last lines of standard error
    for name, c in result.get("checks", {}).items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
