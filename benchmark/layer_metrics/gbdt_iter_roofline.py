"""Roofline share of one boosting iteration, in percent: the least time a
chip with the published peaks could take for it (``benchmark/shapes.py``: the
larger of its int8 operations over the int8 peak and its bytes over the HBM
peak; on a v5e the int8 compute binds) over the device time per iteration the
trace shows."""
from benchmark import shapes


def read(run):
    busy, iters = run.device_busy_s(), run.facts.get("iterations")
    if busy is None or not iters or run.peaks is None:
        return None
    cfg = run.config
    need = shapes.gbdt_iteration_need(
        cfg["rows"] // int(run.cell["chips"]), cfg["features"],
        cfg["params"]["max_bin"] + 1, cfg["params"]["max_depth"])
    least_s, _ = shapes.gbdt_iteration_least_s(need, run.peaks)
    return 100.0 * least_s / (busy / iters)
