"""Roofline share of the expert layers in the decode steps, in percent: the
least time a chip with the published peaks could take
(``benchmark/shapes_sparse_moe.py``: every expert TOUCHED read once, a row in
and out an assignment; HBM binds) over the step programs' device time under
the ``lm.experts`` scope."""
from benchmark import lm_phase_times, shapes, shapes_sparse_moe


def read(run):
    seconds = lm_phase_times.step_seconds(run, "lm.experts")
    touched = run.counter("mmlspark_runner_moe_experts_touched_total")
    sizes, facts = run.config.get("sizes"), run.facts
    if not seconds or not touched or not sizes or run.peaks is None \
            or "step_tokens" not in facts:
        return None
    need = shapes_sparse_moe.experts_need(
        touched, facts["step_tokens"] * sizes["layers"]
        * sizes["experts_per_token"], sizes)
    least_s, _ = shapes.least_s(need["flops"], need["hbm_bytes"], run.peaks)
    return 100.0 * least_s / seconds
