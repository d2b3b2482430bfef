"""Roofline share of the linear-attention layers in the decode steps, in
percent: the least time (``benchmark/shapes_linear_moe.py``: every LIVE row
stepped has its recurrent state and conv history read and written once and
its q/k/v/g/beta in and o out, HBM binds) over the step programs' device
time under ``lm.linear_attn``.  Empty or stale rows that a step computes
anyway show as the gap."""
from benchmark import lm_phase_times, shapes, shapes_linear_moe


def read(run):
    seconds = lm_phase_times.step_seconds(run, "lm.linear_attn")
    sizes, tokens = run.config.get("sizes"), run.facts.get("step_tokens")
    if not seconds or not sizes or not tokens or run.peaks is None:
        return None
    need = shapes_linear_moe.linear_step_need(tokens, sizes)
    least_s, _ = shapes.least_s(need["flops"], need["hbm_bytes"], run.peaks)
    return 100.0 * least_s / seconds
