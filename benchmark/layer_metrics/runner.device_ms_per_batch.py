"""Device busy time of the window over the batches ``ModelRunner``
dispatched in it (``mmlspark_runner_batches_total``), in milliseconds."""


def read(run):
    return run.device_ms_per(run.counter("mmlspark_runner_batches_total",
                                         runner="dl.jax_model"))
