"""A causal language model some of whose layers keep a fixed-size recurrent
state a slot instead of pages: ``causal_lm``'s system with prompts longer
than one prefill chunk and the prefix cache off.

    runner.decode_stream(slots=, prompt_bucket= (the prefill CHUNK),
                         max_prompt_len=, max_new_tokens=, page_size=)

over the runner's automatic pool, which hands out pages for the layers that
keep everything and, beside them, the recurrent layers' per-slot state
(``PagePool.slot_state_nbytes``).  Prefix sharing over per-slot state is
refused by the engine, so it is never asked for.  This file names no model
either: module, weights, sizes and reference are the configuration's.

Weights are ``causal_lm.make_variables``' rule, and then the leaves the
configuration's ``weights.by_name`` names are drawn again by the rule given
there (a linear-attention layer's decay parameters and conv taps sit where
their initialisers put them, not at a kernel's scale): ``log_uniform`` ``[lo,
hi]`` is ``log U(lo, hi)``; ``inverse_softplus_log_uniform`` ``[lo, hi]`` is
``x + log(-expm1(-x))`` for ``x = exp(U(log lo, log hi))`` (``softplus`` of
it is ``x``); ``uniform`` ``[lo, hi]`` is ``U(lo, hi)``.

The comparison that decides ``correct`` is ``causal_lm_long``'s: the
reference is asked for the rows that produced the served tokens only.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict

from benchmark.families import causal_lm, causal_lm_long


def build(run) -> "LinearLMSystem":
    return LinearLMSystem(run)


def _draw(key, rule, shape):
    import jax
    import jax.numpy as jnp
    kind, (lo, hi) = rule[0], rule[1:]
    if kind == "log_uniform":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    if kind == "inverse_softplus_log_uniform":
        x = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                       jnp.log(lo), jnp.log(hi)))
        return x + jnp.log(-jnp.expm1(-x))
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)
    raise ValueError(f"no draw {kind!r}")


def make_variables(module, seed: int, dtype: str, rule: Dict[str, Any]):
    """``causal_lm.make_variables``, then each leaf whose name
    ``rule["by_name"]`` lists drawn again by its rule, in ``dtype``, from
    the same seed.  Only those leaves go through the second program: the
    others are not copied."""
    import jax
    import jax.numpy as jnp
    variables = causal_lm.make_variables(module, seed, dtype, rule)
    by_name = rule.get("by_name", {})
    leaves, treedef = jax.tree_util.tree_flatten_with_path(variables)
    picked = [(i, path, leaf) for i, (path, leaf) in enumerate(leaves)
              if getattr(path[-1], "key", "") in by_name]
    key = jax.random.fold_in(causal_lm.prng_key(seed), 0x51A)

    def draw():
        return [_draw(jax.random.fold_in(key, zlib.crc32(
            jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF),
            by_name[path[-1].key], leaf.shape).astype(jnp.dtype(dtype))
            for _, path, leaf in picked]

    out = [leaf for _, leaf in leaves]
    for (i, _, _), x in zip(picked, jax.jit(draw)()):
        out[i] = x
    return jax.tree_util.tree_unflatten(treedef, out)


class LinearLMSystem(causal_lm.CausalLMSystem):
    def __init__(self, run):
        from mmlspark_tpu.models.runner import ModelRunner
        cfg = self.cfg = run.config
        self.run = run
        e = self.engine = dict(cfg["engine"])
        with run.spans.span("make_weights"):
            self.module = causal_lm.make_module(cfg["model"])
            self.variables = make_variables(
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
        state = int(self.pool.slot_state_nbytes())
        self.run.facts["slot_state_bytes"] = state
        self.run.note(f"slot state beside the pages "
                      f"(mmlspark_runner_slot_state_bytes): {state} B; a "
                      f"page holds {self.pool.page_nbytes()} B")
        # how this seed's router spread the tokens, over the whole run: an
        # untraced run has no other reading of it
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
