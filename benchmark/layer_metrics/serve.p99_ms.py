"""99th percentile of the reply latency from the due instant, in
milliseconds, misses counted at the time limit.  It is the tail users feel,
kept here because a hundredth of a window's requests is too few for it to
repeat within a bound the contract allows (PERF.md, PR 23); ``p95_ms`` stands
for the tail among the end-to-end metrics."""


def read(run):
    return run.facts.get("p99_ms")
