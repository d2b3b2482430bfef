"""Device busy time of the window over the boosting iterations it ran, in
milliseconds, mean over the chips."""


def read(run):
    return run.device_ms_per(run.facts.get("iterations"))
