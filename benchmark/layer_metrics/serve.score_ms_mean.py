"""Mean time of the batch a request was scored in, in milliseconds: sum over
count of the server's ``score`` phase histogram over the window.  Exact where
the interpolated median is good to a bucket."""


def read(run):
    h = run.histogram("mmlspark_serving_phase_seconds", phase="score")
    if h is None:
        return None
    return 1e3 * h["sum"] / h["count"]
