"""Roofline share of the recurrence's chunk form in the join prefills, in
percent: the larger of its operations at the TRUE prompt tokens at the bf16
peak and its bytes (each chunk's state read and written, every position's
activations in and out) at the HBM peak (``benchmark/shapes_linear_moe.py``)
over the prefill programs' device time under ``lm.linear_attn``."""
from benchmark import lm_join_phase_times, shapes, shapes_linear_moe


def read(run):
    seconds = lm_join_phase_times.prefill_seconds(run, "lm.linear_attn")
    chunks = run.counter("mmlspark_runner_prefill_chunks_total")
    sizes, tokens = run.config.get("sizes"), run.facts.get("prefill_tokens")
    if not seconds or not chunks or not sizes or not tokens \
            or run.peaks is None:
        return None
    need = shapes_linear_moe.chunk_need(tokens, chunks, sizes)
    least_s, _ = shapes.least_s(need["flops"], need["hbm_bytes"], run.peaks)
    return 100.0 * least_s / seconds
