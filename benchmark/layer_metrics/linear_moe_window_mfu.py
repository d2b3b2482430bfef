"""Share of the chip's bf16 peak that the whole window's device work reached,
in percent: the operations of every position the window generated or
prefilled (``benchmark/shapes_linear_moe.py``: two a parameter a position
really multiplies, its LOCAL experts only, plus the recurrence's and the
full layer's attention products at true lengths) over the bf16 peak times
the device's busy time.  The one share of the whole window."""
from benchmark import shapes_linear_moe


def read(run):
    busy = run.device_busy_s()
    local = run.counter("mmlspark_runner_moe_local_assignments_total")
    sizes, facts = run.config.get("sizes"), run.facts
    if not busy or run.peaks is None or not sizes or local is None \
            or not facts.get("step_tokens"):
        return None
    flops = shapes_linear_moe.window_flops(
        facts["step_tokens"], facts["step_context_tokens"], local,
        facts["prefill_tokens"], facts["prefill_context_tokens"],
        run.counter("mmlspark_runner_slots_joined_total") or 0.0, sizes)
    return 100.0 * flops / (run.peaks["bf16_flops_per_s"] * busy)
