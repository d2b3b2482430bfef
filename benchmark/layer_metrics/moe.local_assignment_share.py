"""Share of the decode steps' token-expert assignments that landed on an
expert held here, in percent: ``mmlspark_runner_moe_local_assignments_total``
(sown by the module, fetched with the step's tokens) over step tokens x
experts per token x routed layers.  ``experts_held / router_width`` (12.5%
for 16 of 128) when routing is even."""


def read(run):
    local = run.counter("mmlspark_runner_moe_local_assignments_total")
    sizes, tokens = run.config.get("sizes") or {}, \
        run.facts.get("step_tokens")
    if local is None or not tokens or not sizes.get("moe_layers"):
        return None
    return 100.0 * local / (tokens * sizes["experts_per_token"]
                            * sizes["moe_layers"])
