"""Share of the step programs' rows that carried a live sequence, in percent:
the tokens the steps generated in the window (the traffic kind's count from
the request handles; a request's first token comes from its join) over steps
x slots (``mmlspark_runner_decode_steps_total``)."""


def read(run):
    steps = run.counter("mmlspark_runner_decode_steps_total")
    tokens, slots = run.facts.get("step_tokens"), run.facts.get("slots")
    if not steps or tokens is None or not slots:
        return None
    return 100.0 * tokens / (steps * slots)
