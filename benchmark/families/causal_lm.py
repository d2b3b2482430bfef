"""A causal language model as a system under test, built as a user builds it:
a ``ModelRunner`` over the configuration's module, ``runner.decode_stream(...)``
(the ``ContinuousDecoder`` over the runner's ``PagePool``), ``warmup()``,
``start()`` (the engine thread ``PipelineServer`` drives), and requests through
``ContinuousDecoder.submit(prompt, max_new_tokens=, on_done=)``.

This file names no model.  The configuration gives the module as a dotted
factory with its keyword arguments (``model``), the rule its weights are drawn
by (``weights``), the engine's sizes (``engine``) and its plain reference: the
file beside this one that holds it, the function's name and its arguments
(``reference``).  A later configuration brings a file of sizes and, where its
equations differ, a reference file of its own.

Weights are made on the device from ``--seed`` in one jitted call, in the type
they are served in, by the leaf's name in the module's parameter tree: kernels
and embeddings normal, biases normal about 0 and LayerNorm scales uniform
about 1, so that no bias, offset or scale sits at its inert initial value.  The
same arrays go to the program and to the reference.
"""
from __future__ import annotations

import functools
import importlib
from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np


def build(run) -> "CausalLMSystem":
    return CausalLMSystem(run)


def _dotted(name: str) -> Callable:
    module, _, attr = name.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def make_module(model: Dict[str, Any]):
    """The module the configuration names: ``factory(**kwargs)``, with
    ``dtype`` given as a string."""
    import jax.numpy as jnp
    kwargs = dict(model["kwargs"])
    if "dtype" in kwargs:
        kwargs["dtype"] = jnp.dtype(kwargs["dtype"])
    return _dotted(model["factory"])(**kwargs)


def prng_key(seed: int):
    """A key from any whole number up to a little over 2**31: the low 31
    bits seed it and what is above them is folded in."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_variables(module, seed: int, dtype: str, rule: Dict[str, Any]):
    """The module's variables, drawn on the device in one jitted call from the
    seed and in ``dtype``; only the shapes come from ``module.init`` (traced,
    never run).  By the leaf's name: ``scale`` uniform over ``scale_range``,
    ``bias`` normal with ``bias_std``, every other leaf (kernels, embeddings)
    normal with ``std``."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    lo, hi = rule["scale_range"]
    # leaves of one name and shape (a layer's kernel, 48 times) are drawn as
    # one stacked array: a program of a dozen draws, not of six hundred
    groups: Dict[Tuple[str, Tuple[int, ...]], List[int]] = {}
    for n, (path, leaf) in enumerate(leaves):
        groups.setdefault((getattr(path[-1], "key", ""), leaf.shape),
                          []).append(n)

    def draw(key):
        out: List[Any] = [None] * len(leaves)
        for g, ((name, shape), members) in enumerate(sorted(groups.items())):
            k = jax.random.fold_in(key, g + 1)
            stacked = (len(members),) + shape
            if name == "scale":
                x = jax.random.uniform(k, stacked, jnp.float32, lo, hi)
            elif name == "bias":
                x = rule["bias_std"] * jax.random.normal(k, stacked,
                                                         jnp.float32)
            else:
                x = rule["std"] * jax.random.normal(k, stacked, dt)
            for i, n in enumerate(members):
                out[n] = x[i].astype(dt)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(prng_key(seed))


class CausalLMSystem:
    def __init__(self, run):
        from mmlspark_tpu.models.runner import ModelRunner
        cfg = self.cfg = run.config
        self.run = run
        self.engine = dict(cfg["engine"])
        with run.spans.span("make_weights"):
            self.module = make_module(cfg["model"])
            self.variables = make_variables(
                self.module, run.seed, cfg["model"]["kwargs"]["dtype"],
                cfg["weights"])
        self.runner = ModelRunner(module=self.module,
                                  variables=self.variables, name="causal_lm")
        e = self.engine
        self.decoder = self.runner.decode_stream(
            slots=e["slots"], prompt_bucket=e["prompt_bucket"],
            max_new_tokens=e["max_new_tokens"], page_size=e["page_size"])
        self.slots = int(e["slots"])
        self.vocab_size = int(cfg["sizes"]["vocab"])
        self.pool = self.decoder.pool
        run.facts.update(slots=self.slots,
                         pool_pages=int(self.pool.capacity))

    # ----------------------------------------------------------- the engine
    def warm_up(self) -> None:
        """Every program the engine can run (join prefill, sampler, step) at
        its one geometry, then the engine thread."""
        with self.run.spans.span("warm_engine"):
            self.decoder.warmup()
        self.decoder.start()

    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               on_done: Callable):
        """One request; raises ``SlotsExhausted`` / ``PagePoolExhausted`` as
        the engine's admission does."""
        return self.decoder.submit(prompt, max_new_tokens=max_new_tokens,
                                   on_done=on_done)

    @staticmethod
    def admission_errors() -> Tuple[type, type]:
        from mmlspark_tpu.models.runner import PagePoolExhausted, \
            SlotsExhausted
        return SlotsExhausted, PagePoolExhausted

    def close(self) -> None:
        """Stop the engine; what is in flight is cancelled and its pages
        go back to the pool."""
        self.decoder.close()

    def pages_in_use(self) -> int:
        return self.pool.pages_in_use()

    def pool_high_water(self) -> int:
        return int(self.pool.high_water)

    def release(self) -> None:
        """Drop the engine and the pool's slabs: what follows on the device
        is the reference's alone."""
        self.pool.borrow_cache()         # taken from the pool, and dropped
        self.decoder = self.pool = self.runner = None

    # ------------------------------------------------------- the reference
    def reference_forward(self) -> Callable:
        ref = self.cfg["reference"]
        module = self.run.manifest.module("families", ref["module"])
        return functools.partial(getattr(module, ref["function"]),
                                 **ref["kwargs"])

    def check_served(self, finished: Sequence[Any]) -> None:
        """The comparison that decides ``correct``: a sample of the requests
        the window finished, drawn from the seed and with the longest in it,
        against the plain reference's pass over each prompt and its served
        tokens: the mean gap by which a served token's logit lies below the
        reference's best, held to the configuration's limit."""
        from benchmark.families import causal_lm_reference as comparison
        ref = self.cfg["reference"]
        if not finished:
            self.run.fail("no request finished in the window: nothing to "
                          "compare with the reference")
            return
        sample = sample_requests(finished, int(ref["sample_requests"]),
                                 self.run.seed)
        e = self.engine
        with self.run.spans.span("check_reference"):
            got = comparison.check_served(
                self.reference_forward(), self.variables,
                [(h.prompt, list(h.tokens)) for h in sample],
                pad_to=e["prompt_bucket"] + e["max_new_tokens"])
        self.run.facts.update(got)
        self.run.note(
            f"{got['positions']} served tokens of {got['requests']} requests "
            f"against the float32 reference, gap below its best logit: mean "
            f"{got['served_gap_mean']:.6f} (compared), widest "
            f"{got['served_gap_max']:.5f}, {100 * got['served_flipped']:.1f}% "
            f"of the tokens are not its best")
        self.run.check("served_gap_mean", got["served_gap_mean"],
                       ref["served_gap_mean_limit"])


def sample_requests(finished: Sequence[Any], k: int, seed: int) -> List[Any]:
    """``k`` of the finished requests, drawn from the seed, the longest
    (prompt + answer) always among them."""
    longest = max(range(len(finished)),
                  key=lambda i: finished[i].length + len(finished[i].tokens))
    others = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng([int(seed), 0x5A3])
    picks = rng.permutation(others)[:max(0, k - 1)] if others else []
    return [finished[longest]] + [finished[int(i)] for i in picks]
