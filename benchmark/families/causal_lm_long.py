"""A causal language model served over contexts far longer than one prefill
chunk, most of each shared with other requests: ``causal_lm``'s system with
the engine built as such a deployment builds it.

    runner.page_pool(page_size, num_pages=engine.pool_pages)     an explicit pool
    runner.prefix_cache(page_size, budget_pages=..., pool=pool)  the prefix index
    runner.decode_stream(slots=, prompt_bucket= (the prefill CHUNK),
                         max_prompt_len=, max_new_tokens=, pool=pool,
                         prefix_cache=True)

so that documents prefilled once stay resident in the pool and a later
request over one of them joins with its uncovered part alone.  This file
names no model either: module, weights, sizes and reference are the
configuration's, as in ``causal_lm.py``, whose builders it uses.

The comparison that decides ``correct`` is ``causal_lm``'s (the mean gap by
which a served token's logit lies below the plain reference's best), but the
reference is asked for the rows that produced the served tokens only: a
whole table of logits over 33k positions and a vocabulary of 152k would be
20 GB.
"""
from __future__ import annotations

from typing import Any, Sequence

from benchmark.families import causal_lm


def build(run) -> "LongContextLMSystem":
    return LongContextLMSystem(run)


class LongContextLMSystem(causal_lm.CausalLMSystem):
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
        self.pool = self.runner.page_pool(e["page_size"],
                                          num_pages=e["pool_pages"])
        self.index = self.runner.prefix_cache(
            e["page_size"], budget_pages=e["prefix_budget_pages"],
            pool=self.pool)
        self.decoder = self.runner.decode_stream(
            slots=e["slots"], prompt_bucket=e["prompt_bucket"],
            max_prompt_len=e["max_prompt_len"],
            max_new_tokens=e["max_new_tokens"], pool=self.pool,
            prefix_cache=True)
        self.slots = int(e["slots"])
        self.vocab_size = int(cfg["sizes"]["vocab"])
        run.facts.update(slots=self.slots,
                         pool_pages=int(self.pool.capacity))

    def flush_prefix_index(self) -> int:
        """Give up what the prefix index retains; the pages it alone held
        go back to the pool.  Returns how many it retained."""
        return self.index.flush(reason="benchmark_end")

    def release(self) -> None:
        super().release()
        self.index = None

    def check_served(self, finished: Sequence[Any]) -> None:
        """``causal_lm``'s comparison over a sample of the requests the
        window finished (drawn from the seed, the longest among them), each
        one pass of the plain reference over its whole context."""
        ref = self.cfg["reference"]
        comparison = self.run.manifest.module("families", ref["module"])
        if not finished:
            self.run.fail("no request finished in the window: nothing to "
                          "compare with the reference")
            return
        sample = causal_lm.sample_requests(
            finished, int(ref["sample_requests"]), self.run.seed)
        e = self.engine
        with self.run.spans.span("check_reference"):
            got = comparison.check_served(
                self.reference_forward(), self.variables,
                [(h.prompt, list(h.tokens)) for h in sample],
                pad_to=e["max_prompt_len"] + e["max_new_tokens"],
                rows=e["max_new_tokens"])
        self.run.facts.update(got)
        self.run.note(
            f"{got['positions']} served tokens of {got['requests']} requests "
            f"(contexts of {min(h.length for h in sample)}-"
            f"{max(h.length for h in sample)} prompt tokens) against the "
            f"float32 reference in {got['reference_seconds']:.1f} s, gap "
            f"below its best logit: mean {got['served_gap_mean']:.6f} "
            f"(compared), widest {got['served_gap_max']:.5f}, "
            f"{100 * got['served_flipped']:.1f}% of the tokens are not its "
            f"best")
        self.run.check("served_gap_mean", got["served_gap_mean"],
                       ref["served_gap_mean_limit"])
