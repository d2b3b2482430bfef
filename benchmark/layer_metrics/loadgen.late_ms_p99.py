"""How late the load generator sent, against its schedule: 99th percentile
of send time minus due time, in milliseconds.  It guards the latencies: a
generator that runs late offers less load than the cell states."""


def read(run):
    return run.facts.get("late_ms_p99")
