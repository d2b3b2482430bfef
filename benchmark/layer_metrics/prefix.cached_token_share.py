"""Share of the prompt tokens of the window's joins whose keys and values
the prefix index already held, in percent:
``mmlspark_runner_prefill_tokens_total{source="cached"}`` over cached +
computed."""


def read(run):
    cached = run.counter("mmlspark_runner_prefill_tokens_total",
                         source="cached")
    computed = run.counter("mmlspark_runner_prefill_tokens_total",
                           source="computed")
    if cached is None or computed is None or cached + computed <= 0:
        return None
    return 100.0 * cached / (cached + computed)
