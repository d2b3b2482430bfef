"""Roofline share of attention in the decode steps, in percent: the least
time (``benchmark/shapes_window_moe.py``: ``window`` K/V rows a token a
window layer and the TRUE context a token a full layer, at 4,096 B a row)
over the step programs' device time under ``lm.window_attn`` +
``lm.full_attn``."""
from benchmark import lm_phase_times, shapes, shapes_window_moe


def read(run):
    seconds = lm_phase_times.step_seconds(run, "lm.window_attn",
                                          "lm.full_attn")
    sizes, facts = run.config.get("sizes"), run.facts
    if not seconds or not sizes or run.peaks is None \
            or not facts.get("step_tokens"):
        return None
    tokens = facts["step_tokens"]
    need = shapes_window_moe.attention_need(
        tokens, facts["step_context_tokens"], tokens * sizes["window"], sizes)
    least_s, _ = shapes.least_s(need["flops"], need["hbm_bytes"], run.peaks)
    return 100.0 * least_s / seconds
