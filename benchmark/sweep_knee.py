"""The builder's tool, no part of a run: find the highest request rate a served
cell sustains, once, on the chip.  The cell's traffic file then fixes its rate
at about four fifths of that knee, as a number.

    python3 benchmark/sweep_knee.py --workload resnet50-serve \
        --rates 40,80,120,160 --seconds 8 [--seed 0]

``resnet50-serve`` is held out of ``BENCHMARK.json``: run this from a copy of
the checkout with the entries of ``benchmark/held_out/resnet50-serve.json``
added, as ``benchmark/README.md`` says.

One process, one server; each rate gets its own window and its own load
generator.  A rate is sustained when the replies completed per second keep up
with the offered rate and the generator did not run late; past the knee the
completed rate flattens and lateness grows with the window.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import harness
    run, stamp = harness.prepare(ROOT, args.workload, args.seed, args.seconds,
                                 False, time.perf_counter())
    manifest = run.manifest
    traffic = manifest.module("traffic", run.mix["kind"])
    family = manifest.module("families", run.config["family"])
    server = traffic.start_server(run, family.build(run))
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            out = traffic.measure_window(run, server, rate, args.seconds,
                                         args.seed)
            row = traffic.summarize(out, float(run.mix["timeout_s"]),
                                    args.seconds)
            print(json.dumps(dict(row, rate_per_s=rate, device=stamp["kind"],
                                  seconds=args.seconds)), flush=True)
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
