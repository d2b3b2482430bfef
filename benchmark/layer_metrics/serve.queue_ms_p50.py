"""Median wait of a request between admission and the start of its batch, in
milliseconds, from the server's own ``mmlspark_serving_phase_seconds``
histogram (phase ``queue``) over the window.  The histogram has four buckets
per decade; the median is interpolated inside its bucket."""
from benchmark import measure


def read(run):
    h = run.histogram("mmlspark_serving_phase_seconds", phase="queue")
    if h is None:
        return None
    return 1e3 * measure.bucket_percentile(h["buckets"], 50)
