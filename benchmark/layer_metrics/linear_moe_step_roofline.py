"""Roofline share of the whole decode steps, in percent: the least time of
the window's steps (``benchmark/shapes_linear_moe.py``: once a step the
weights outside the experts, the held experts touched, the live rows'
linear state read and written and the full layer's K/V at the true
contexts) over the device time of the step programs (``jit__step``)."""
from benchmark import program_times, shapes, shapes_linear_moe


def read(run):
    seconds = program_times.seconds_of(run, program_times.STEP_PROGRAMS)
    steps = run.counter("mmlspark_runner_decode_steps_total")
    touched = run.counter("mmlspark_runner_moe_experts_touched_total")
    local = run.counter("mmlspark_runner_moe_local_assignments_total")
    sizes, facts = run.config.get("sizes"), run.facts
    if not seconds or not steps or touched is None or local is None \
            or not sizes or run.peaks is None \
            or not facts.get("step_tokens"):
        return None
    need = shapes_linear_moe.steps_need(
        steps, facts["step_tokens"], facts["step_context_tokens"], touched,
        local, sizes)
    least_s, _ = shapes.least_s(need["flops"], need["hbm_bytes"], run.peaks)
    return 100.0 * least_s / seconds
