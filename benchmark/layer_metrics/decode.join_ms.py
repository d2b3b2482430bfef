"""Device milliseconds of one join: the time of the prefill of an arrival
alone at the engine's one prompt bucket and of its first token's sampler
(``jit__prefill``, ``jit__sample``) inside the traced window, over the
requests the program spliced in there
(``mmlspark_runner_slots_joined_total``)."""
from benchmark import program_times


def read(run):
    seconds = program_times.seconds_of(run, program_times.JOIN_PROGRAMS)
    joins = run.counter("mmlspark_runner_slots_joined_total")
    if seconds is None or not joins:
        return None
    return seconds * 1e3 / joins
