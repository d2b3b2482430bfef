"""Milliseconds of a decode step in which nothing ran on the device: the
traced window less the device's busy time, over the steps counted in it.
The dispatch of the step program and the one host fetch of its tokens, the
slot bookkeeping, and whatever of a join's host work the device waits for.
The mean of the program's own dispatch histogram
(``mmlspark_runner_decode_phase_seconds{phase="dispatch"}``) goes on an
earlier line beside it."""


def read(run):
    busy = run.device_busy_s()
    steps = run.counter("mmlspark_runner_decode_steps_total")
    if busy is None or not steps or run.trace_summary is None:
        return None
    dispatch = run.histogram("mmlspark_runner_decode_phase_seconds",
                             phase="dispatch")
    if dispatch:
        run.note(f"dispatch of a step, the program's own clock: mean "
                 f"{dispatch['sum'] * 1e3 / dispatch['count']:.3f} ms over "
                 f"{dispatch['count']}")
    return (run.trace_summary.window_s - busy) * 1e3 / steps
