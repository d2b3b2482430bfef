"""Median time of the batch a request was scored in, in milliseconds, from
the server's own ``mmlspark_serving_phase_seconds`` histogram (phase
``score``) over the window; interpolated inside its bucket."""
from benchmark import measure


def read(run):
    h = run.histogram("mmlspark_serving_phase_seconds", phase="score")
    if h is None:
        return None
    return 1e3 * measure.bucket_percentile(h["buckets"], 50)
