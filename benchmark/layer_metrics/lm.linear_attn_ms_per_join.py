"""Device milliseconds under the ``lm.linear_attn`` scope in the join
prefill programs (``benchmark/lm_join_phase_times.py``), per slot joined in
the window (``mmlspark_runner_slots_joined_total``): the linear-attention
layers' chunk form over every prefill chunk of a join."""
from benchmark import lm_join_phase_times


def read(run):
    seconds = lm_join_phase_times.prefill_seconds(run, "lm.linear_attn")
    joins = run.counter("mmlspark_runner_slots_joined_total")
    if seconds is None or not joins:
        return None
    return seconds * 1e3 / joins
