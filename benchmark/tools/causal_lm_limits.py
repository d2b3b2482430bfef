"""The builder's tool, no part of a run: the two readings that a ``causal_lm``
cell's ``served_gap_mean_limit`` is set between ("How correct is decided", steps 3
to 5), on the chip, at the cell's own size and load, many seeds in one
process because set-up is most of a run.

    python3 benchmark/tools/causal_lm_limits.py <cell> <seconds> <seed> [<seed> ...]

For every seed it runs the cell as ``benchmark/run.py`` does, with a window of
``<seconds>`` (long enough to finish the mix's longest requests), and has the
comparison also run the control: the plain reference with every matmul
operand rounded to float8 (e4m3, one scale a tensor), the nearest precision
below the configuration's bfloat16.  Prints, per seed, the program's gaps below the
reference's best logit (mean, widest, share of tokens flipped; the LOWER
reading of each is the largest over the seeds) and the control's at the same
positions (the UPPER reading is the smallest), and both readings at the end.  Results also go to
``chiprun_out/causal_lm_limits.json``.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROL = "fp8"


def main(argv) -> int:
    cell, seconds, seeds = argv[0], float(argv[1]), [int(a) for a in argv[2:]]
    from benchmark.families import causal_lm_reference as comparison
    from benchmark.harness import run_cell
    readings = []
    plain = comparison.check_served

    def with_control(*args, **kwargs):
        got = plain(*args, **dict(kwargs, control=CONTROL))
        readings.append(got)
        return got
    comparison.check_served = with_control
    rows = []
    for seed in seeds:
        result = run_cell(ROOT, cell, seed, seconds, trace=False)
        got = readings[-1]
        rows.append(dict(got, seed=seed, checks=result.get("checks"),
                         metrics=result["metrics"], memory_peak_bytes=result[
                             "device"]["memory_peak_bytes"]))
        print(f"seed {seed}: " + ", ".join(
            f"{k} {v:.6f}" for k, v in got.items()
            if k.startswith(("served", "control"))), flush=True)
    for stat in ("gap_mean", "gap_max", "flipped"):
        lower = max(r["served_" + stat] for r in rows)
        upper = min(r["control_" + stat] for r in rows)
        print(f"{stat}: lower reading (largest of the program's) "
              f"{lower:.6f}; upper reading (smallest of the control's) "
              f"{upper:.6f}; ratio "
              f"{upper / lower if lower else float('inf'):.1f}")
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "causal_lm_limits.json"), "w") as f:
        json.dump({"cell": cell, "control": CONTROL, "rows": rows}, f,
                  indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
