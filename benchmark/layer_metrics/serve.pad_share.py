"""Padding rows over all rows the runner put on the device in the window, in
percent (``mmlspark_runner_pad_rows_total`` over it plus
``mmlspark_runner_rows_total``): device work that bucketing wasted."""


def read(run):
    pad = run.counter("mmlspark_runner_pad_rows_total", runner="dl.jax_model")
    rows = run.counter("mmlspark_runner_rows_total", runner="dl.jax_model")
    if pad is None or not rows:
        return None
    return 100.0 * pad / (pad + rows)
