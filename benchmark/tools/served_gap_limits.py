"""The builder's tool, no part of a run: ``causal_lm_limits.py``'s method for
a cell whose family names its comparison in the configuration
(``reference.module``: a file in ``benchmark/families/`` with a
``check_served(..., control=)``), as ``causal_lm_long`` does.

    python3 benchmark/tools/served_gap_limits.py <cell> <seconds> <seed> [<seed> ...]

For every seed it runs the cell as ``benchmark/run.py`` does, with a window of
``<seconds>``, and has the comparison also run the control: the plain
reference with every matmul operand rounded to float8 (e4m3, one scale a
tensor), the nearest precision below the configuration's bfloat16, put in the
program's place at the same rows.  Prints, per seed, the program's gaps below
the reference's best logit and the control's, and both readings at the end
(the LOWER reading is the largest of the program's over the seeds, the UPPER
the smallest of the control's).  Results also go to
``chiprun_out/served_gap_limits.json``.
"""
from __future__ import annotations

import gc
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
    from benchmark.harness import run_cell
    from benchmark.manifest import Manifest
    readings = []
    plain_module = Manifest.module

    def module(self, kind, name):
        """The comparison's file, with the control switched on."""
        mod = plain_module(self, kind, name)
        if kind == "families" and hasattr(mod, "check_served"):
            plain = mod.check_served

            def with_control(*args, **kwargs):
                got = plain(*args, **dict(kwargs, control=CONTROL))
                readings.append(got)
                return got
            mod.check_served = with_control
        return mod
    Manifest.module = module
    rows = []
    for seed in seeds:
        gc.collect()       # the last seed's weights, held by cycles: 8.7 GB
        result = run_cell(ROOT, cell, seed, seconds, trace=False)
        got = readings[-1]
        rows.append(dict(got, seed=seed, checks=result.get("checks"),
                         metrics=result["metrics"], memory_peak_bytes=result[
                             "device"]["memory_peak_bytes"]))
        print(f"seed {seed}: " + ", ".join(
            f"{k} {v:.6f}" for k, v in got.items()
            if k.startswith(("served", "control", "reference"))), flush=True)
        out = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "served_gap_limits.json"), "w") as f:
            json.dump({"cell": cell, "control": CONTROL, "rows": rows}, f,
                      indent=1)
    for stat in ("gap_mean", "gap_max", "flipped"):
        lower = max(r["served_" + stat] for r in rows)
        upper = min(r["control_" + stat] for r in rows)
        print(f"{stat}: lower reading (largest of the program's) "
              f"{lower:.6f}; upper reading (smallest of the control's) "
              f"{upper:.6f}; ratio "
              f"{upper / lower if lower else float('inf'):.1f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
