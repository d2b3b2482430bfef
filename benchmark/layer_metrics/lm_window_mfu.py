"""Share of the chip's bf16 peak that the whole window's device work reached,
in percent: the operations of every position the window generated or
prefilled, at TRUE lengths (``benchmark/shapes.py``: two a matmul parameter a
position, and attention's two products over the positions attended to),
over the bf16 peak times the device's busy time.  The one share of the whole
step: a kernel taken off the path leaves its own roofline silent and still
shows here."""
from benchmark import shapes


def read(run):
    busy = run.device_busy_s()
    sizes, facts = run.config.get("sizes"), run.facts
    if busy is None or busy <= 0 or run.peaks is None or not sizes \
            or "step_tokens" not in facts:
        return None
    need = shapes.causal_lm_need(sizes)
    flops = shapes.causal_lm_flops(
        need["matmul_params"], facts["step_tokens"] + facts["prefill_tokens"],
        facts["step_context_tokens"] + facts["prefill_context_tokens"],
        need["layers"], need["width"])
    return 100.0 * flops / (run.peaks["bf16_flops_per_s"] * busy)
