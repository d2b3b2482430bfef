"""Roofline share of the routed layers in the decode steps, in percent: the
least time a chip with the published peaks could take
(``benchmark/shapes_window_moe.py``: every held expert TOUCHED read once, a
row in and out a LOCAL assignment; HBM binds) over the step programs' device
time under the ``lm.experts`` scope."""
from benchmark import lm_phase_times, shapes, shapes_window_moe


def read(run):
    seconds = lm_phase_times.step_seconds(run, "lm.experts")
    touched = run.counter("mmlspark_runner_moe_experts_touched_total")
    local = run.counter("mmlspark_runner_moe_local_assignments_total")
    sizes = run.config.get("sizes")
    if not seconds or not touched or local is None or not sizes \
            or run.peaks is None:
        return None
    need = shapes_window_moe.experts_need(touched, local, sizes)
    least_s, _ = shapes.least_s(need["flops"], need["hbm_bytes"], run.peaks)
    return 100.0 * least_s / seconds
