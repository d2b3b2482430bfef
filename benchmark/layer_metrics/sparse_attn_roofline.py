"""Roofline share of indexer, selection and sparse attention in the decode
steps, in percent: the least time (``benchmark/shapes_sparse_moe.py``: the
indexer's keys read at the TRUE context lengths and K/V rows of min(context,
topk) positions a live sequence a layer) over the step programs' device time
under ``lm.indexer`` + ``lm.select`` + ``lm.sparse_attn``."""
from benchmark import lm_phase_times, shapes, shapes_sparse_moe


def read(run):
    seconds = lm_phase_times.step_seconds(run, "lm.indexer", "lm.select",
                                          "lm.sparse_attn")
    sizes, facts = run.config.get("sizes"), run.facts
    if not seconds or not sizes or run.peaks is None \
            or "step_spans" not in facts:
        return None
    _, contexts, selected = shapes_sparse_moe.span_sums(
        facts["step_spans"], int(sizes["index_topk"]))
    need = shapes_sparse_moe.sparse_attention_need(contexts, selected, sizes)
    least_s, _ = shapes.least_s(need["flops"], need["hbm_bytes"], run.peaks)
    return 100.0 * least_s / seconds
