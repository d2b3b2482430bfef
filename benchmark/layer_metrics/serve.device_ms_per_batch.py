"""Device busy time of the window over the batches ``ModelRunner``
dispatched in it for the server (``mmlspark_runner_batches_total``), in
milliseconds: what the chip adds to a reply."""


def read(run):
    return run.device_ms_per(run.counter("mmlspark_runner_batches_total",
                                         runner="dl.jax_model"))
