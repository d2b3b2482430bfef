"""Requests per device batch in the window: rows the runner scored over the
batches it dispatched.  How much the continuous drain batches at this rate."""


def read(run):
    rows = run.counter("mmlspark_runner_rows_total", runner="dl.jax_model")
    batches = run.counter("mmlspark_runner_batches_total",
                          runner="dl.jax_model")
    if not rows or not batches:
        return None
    return rows / batches
