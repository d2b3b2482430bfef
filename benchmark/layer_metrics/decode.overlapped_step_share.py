"""Share of the window's decode steps that were dispatched while the step
before them was still unfetched, in percent:
``mmlspark_runner_decode_steps_overlapped_total`` over
``mmlspark_runner_decode_steps_total``.  How often the engine thread's one
step in flight engages: a join drains the pipe, so the share falls with the
joins a step.  ``None`` for a program that has no such counter."""


def read(run):
    overlapped = run.counter("mmlspark_runner_decode_steps_overlapped_total")
    steps = run.counter("mmlspark_runner_decode_steps_total")
    if overlapped is None or not steps:
        return None
    return 100.0 * overlapped / steps
