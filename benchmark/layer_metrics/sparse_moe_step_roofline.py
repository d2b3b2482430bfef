"""Roofline share of the whole decode steps, in percent: the least time of
the window's steps (``benchmark/shapes_sparse_moe.py``: the experts touched,
the indexer's keys at true lengths and the selected K/V rows, and once a step
the other weights and the head) over the device time of the step programs
(``jit__step``)."""
from benchmark import program_times, shapes, shapes_sparse_moe


def read(run):
    seconds = program_times.seconds_of(run, program_times.STEP_PROGRAMS)
    steps = run.counter("mmlspark_runner_decode_steps_total")
    touched = run.counter("mmlspark_runner_moe_experts_touched_total")
    sizes, facts = run.config.get("sizes"), run.facts
    if not seconds or not steps or not touched or not sizes \
            or run.peaks is None or "step_spans" not in facts:
        return None
    need = shapes_sparse_moe.steps_need(steps, facts["step_spans"], touched,
                                        sizes)
    least_s, _ = shapes.least_s(need["flops"], need["hbm_bytes"], run.peaks)
    return 100.0 * least_s / seconds
