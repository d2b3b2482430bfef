"""Share of the chip's bf16 peak that the whole window's device work reached,
in percent: the operations of every position the window generated or
prefilled (``benchmark/shapes_sparse_moe.py``: two a parameter a position
really multiplies, 8 experts and not 128, plus the indexer's and attention's
products at true and selected lengths) over the bf16 peak times the device's
busy time.  The one share of the whole window."""
from benchmark import shapes_sparse_moe


def read(run):
    busy = run.device_busy_s()
    sizes, facts = run.config.get("sizes"), run.facts
    if not busy or run.peaks is None or not sizes \
            or "step_spans" not in facts:
        return None
    flops = shapes_sparse_moe.window_flops(
        facts["step_spans"], facts["prefill_spans"],
        len(facts["prefill_spans"]), sizes)
    return 100.0 * flops / (run.peaks["bf16_flops_per_s"] * busy)
