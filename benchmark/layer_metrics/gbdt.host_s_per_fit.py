"""Seconds of a fit in which the device did nothing: the wall time of the
window's fits minus the device's busy time, per fit.  Label upload, init
score, dispatch gaps, the fetch of the trees, and in the sharded path the
upload of the binned matrix all land here."""


def read(run):
    busy, fits = run.device_busy_s(), run.facts.get("fits")
    if busy is None or not fits:
        return None
    wall = run.spans.total("fit", run.window_start_s, run.window_end_s)
    return (wall - busy) / fits
