"""Whole operations run back to back on a warmed system: fits of a trainer,
transforms of a table.  The traffic file gives ``rate_metric`` (the name the
completed work per second is reported under), ``at_least`` (operations that
always run) and ``trace_seconds``.

A new operation starts only while ``elapsed + the last operation's time <=
seconds``, so the window ends at or just before ``--seconds``; the rate is the
work of the whole operations over the time at which the last one returned.
"""
from __future__ import annotations

import time
from typing import Any, Dict

from benchmark import measure


def run(run, family) -> Dict[str, Any]:
    system = family.build(run)
    system.warm_up()
    at_least = 1 if run.trace else int(run.mix["at_least"])
    units = 0.0
    done = raised = 0
    last_s = 0.0
    run.setup_done()
    with run.window():
        t0 = time.perf_counter()
        elapsed = 0.0
        while measure.may_start(elapsed, last_s, run.seconds, done, at_least):
            t_op = time.perf_counter()
            try:
                with run.spans.span(system.op_name):
                    units += system.operation(done)
            except Exception as e:  # noqa: BLE001 - a failed operation is a result
                raised += 1
                run.fail(f"{system.op_name} {done} raised {e!r}")
                if raised >= 3:
                    break
            now = time.perf_counter()
            last_s, elapsed, done = now - t_op, now - t0, done + 1
    failed = raised + system.failed_operations()
    run.facts.update(system.window_facts(), operations=done, units=units,
                     elapsed_s=elapsed)
    value = measure.rate(units, elapsed)
    run.note(f"{done} x {system.op_name} in {elapsed:.3f} s: "
             f"{run.mix['rate_metric']} = {value:.1f}")
    return {"attempted": done, "failed": failed,
            "end_to_end": {run.mix["rate_metric"]: value}}
