"""A causal language model some of whose layers keep a bounded window of
positions a slot instead of pages: ``causal_lm``'s system with prompts longer
than one prefill chunk and the prefix cache off.

    runner.decode_stream(slots=, prompt_bucket= (the prefill CHUNK),
                         max_prompt_len=, max_new_tokens=, page_size=)

over the runner's automatic pool, which hands out pages for the layers that
keep everything and, beside them, the window layers' per-slot state
(``PagePool.window_nbytes``).  Prefix sharing over window-layer state is
refused by the engine, so it is never asked for.  This file names no model
either: module, weights, sizes and reference are the configuration's, as in
``causal_lm.py``, whose builders it uses.

The comparison that decides ``correct`` is ``causal_lm_long``'s: the
reference is asked for the rows that produced the served tokens only.
"""
from __future__ import annotations

from benchmark.families import causal_lm, causal_lm_long


def build(run) -> "WindowLMSystem":
    return WindowLMSystem(run)


class WindowLMSystem(causal_lm.CausalLMSystem):
    def __init__(self, run):
        from mmlspark_tpu.models.runner import ModelRunner
        cfg = self.cfg = run.config
        self.run = run
        e = self.engine = dict(cfg["engine"])
        with run.spans.span("make_weights"):
            self.module = causal_lm.make_module(cfg["model"])
            self.variables = causal_lm.make_variables(
                self.module, run.seed, cfg["model"]["kwargs"]["dtype"],
                cfg["weights"])
        self.runner = ModelRunner(module=self.module,
                                  variables=self.variables, name="causal_lm")
        self.decoder = self.runner.decode_stream(
            slots=e["slots"], prompt_bucket=e["prompt_bucket"],
            max_prompt_len=e["max_prompt_len"],
            max_new_tokens=e["max_new_tokens"], page_size=e["page_size"])
        self.slots = int(e["slots"])
        self.vocab_size = int(cfg["sizes"]["vocab"])
        self.pool = self.decoder.pool
        run.facts.update(slots=self.slots,
                         pool_pages=int(self.pool.capacity))

    def close(self) -> None:
        super().close()
        self.run.facts["window_state_bytes"] = int(self.pool.window_nbytes())
        self.run.note(f"window state beside the pages: "
                      f"{self.pool.window_nbytes()} B; a page holds "
                      f"{self.pool.page_nbytes()} B")
        # how this seed's router spread the tokens, over the whole run: an
        # untraced run has no other reading of it, and the work of a step
        # follows the held experts touched
        steps, touched, local = (
            self.run.registry.family(f"mmlspark_runner_{name}_total").labels(
                runner="causal_lm").value
            for name in ("decode_steps", "moe_experts_touched",
                         "moe_local_assignments"))
        if steps:
            self.run.note(f"over {steps:.0f} steps since start-up: "
                          f"{touched / steps:.2f} held experts touched and "
                          f"{local / steps:.1f} local assignments a step")

    check_served = causal_lm_long.LongContextLMSystem.check_served
