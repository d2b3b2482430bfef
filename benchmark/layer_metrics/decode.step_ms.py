"""Device milliseconds of one decode step: the time of the engine's step
program (``jit__step``: one token for every slot, through the paged cache)
inside the traced window, over the steps the program counted there
(``mmlspark_runner_decode_steps_total``)."""
from benchmark import program_times


def read(run):
    seconds = program_times.seconds_of(run, program_times.STEP_PROGRAMS)
    steps = run.counter("mmlspark_runner_decode_steps_total")
    if seconds is None or not steps:
        return None
    return seconds * 1e3 / steps
