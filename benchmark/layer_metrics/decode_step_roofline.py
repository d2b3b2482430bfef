"""Roofline share of the decode steps, in percent: the least time a chip
with the published peaks could take for the window's steps
(``benchmark/shapes.py``: the weights read once a step and every live
sequence's keys and values read once at its TRUE length, over the HBM peak;
or the steps' operations over the bf16 peak, whichever is larger: memory
binds) over the device time of the step programs.  It prices an ideal paged
read, so whatever the program moves beyond that shows as the gap."""
from benchmark import program_times, shapes


def read(run):
    seconds = program_times.seconds_of(run, program_times.STEP_PROGRAMS)
    steps = run.counter("mmlspark_runner_decode_steps_total")
    sizes, facts = run.config.get("sizes"), run.facts
    if seconds is None or not steps or not sizes or run.peaks is None \
            or "step_tokens" not in facts:
        return None
    need = shapes.causal_lm_need(sizes)
    hbm = shapes.decode_steps_least_bytes(
        steps, facts["step_context_tokens"], need["param_bytes"],
        need["kv_token_bytes"])
    flops = shapes.causal_lm_flops(
        need["matmul_params"], facts["step_tokens"],
        facts["step_context_tokens"], need["layers"], need["width"])
    least_s, _ = shapes.least_s(flops, hbm, run.peaks)
    return 100.0 * least_s / seconds
