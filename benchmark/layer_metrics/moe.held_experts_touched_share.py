"""Share of the experts HELD here that a decode step's tokens were routed
to, in percent: ``mmlspark_runner_moe_experts_touched_total`` (held experts
with at least one token, summed over layers and steps) over steps x routed
layers x experts held.  What an expert layer that reads only touched
experts has to read."""


def read(run):
    touched = run.counter("mmlspark_runner_moe_experts_touched_total")
    steps = run.counter("mmlspark_runner_decode_steps_total")
    sizes = run.config.get("sizes") or {}
    if touched is None or not steps or not sizes.get("experts_held"):
        return None
    return 100.0 * touched / (steps * sizes["moe_layers"]
                              * sizes["experts_held"])
