"""What a decoder with a routed expert layer and learned sparse attention
needs, from its shapes alone: parameters, and the least bytes and the
operations of its decode steps and prefills.  ``benchmark/shapes.py``'s
siblings for this block; a roofline share is the least time (``shapes.
least_s``) over the device time a trace shows.  ``sizes`` is a
configuration's: ``layers``, ``width``, ``heads``, ``kv_heads``,
``head_dim``, ``experts``, ``experts_per_token``, ``expert_width``,
``index_heads``, ``index_dim``, ``index_topk``, ``vocab``,
``bytes_per_value``.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def params(sizes: Dict[str, float]) -> Dict[str, float]:
    """Parameters by part.  A layer: attention (q, k, v, o; no biases), the
    indexer (its queries, one key head with a LayerNorm, head weights), the
    router, the norms (two RMSNorms of the width, two of a head), and the
    experts (three matrices each).  Outside the layers: an embedding, a final
    RMSNorm and an untied head.  Keye-VL-2.0-30B-A3B's language model at 6
    layers: 625,381,760 a layer, 4,374,622,464 in all."""
    w, d = sizes["width"], sizes["head_dim"]
    q, kv = sizes["heads"] * d, sizes["kv_heads"] * d
    attention = w * q + 2 * w * kv + q * w
    indexer = w * sizes["index_heads"] * sizes["index_dim"] \
        + w * sizes["index_dim"] + w * sizes["index_heads"] \
        + 2 * sizes["index_dim"]
    router = w * sizes["experts"]
    norms = 2 * w + 2 * d
    expert = 3 * w * sizes["expert_width"]
    layer = attention + indexer + router + norms + sizes["experts"] * expert
    embedding = head = sizes["vocab"] * w
    return {"attention": float(attention), "indexer": float(indexer),
            "router": float(router), "norms": float(norms),
            "expert": float(expert), "layer": float(layer),
            "embedding": float(embedding), "head": float(head),
            "total": float(sizes["layers"] * layer + embedding + head + w),
            # what every position multiplies, whatever it is routed to
            "dense_matmul": float(sizes["layers"] * (
                attention + indexer - 2 * sizes["index_dim"] + router)),
            "routed_matmul": float(sizes["layers"]
                                   * sizes["experts_per_token"] * expert)}


def cache_bytes_per_token(sizes: Dict[str, float]) -> Dict[str, float]:
    """Bytes a position holds in ONE layer: its key and value over the KV
    heads, and the indexer's key."""
    b = sizes["bytes_per_value"]
    return {"kv": 2.0 * sizes["kv_heads"] * sizes["head_dim"] * b,
            "index": float(sizes["index_dim"] * b)}


def span_sums(spans: Iterable[Tuple[int, int]], topk: int
              ) -> Tuple[float, float, float]:
    """Over spans ``(first context, count)`` of positions whose contexts grow
    by one: ``(positions, sum of contexts, sum of min(context, topk))``."""
    n = ctx = sel = 0.0
    for first, count in spans:
        last = first + count - 1
        n += count
        ctx += (first + last) * count / 2.0
        below = max(0, min(last, topk) - first + 1)        # contexts <= topk
        sel += (first + first + below - 1) * below / 2.0 \
            + (count - below) * float(topk)
    return n, ctx, sel


def experts_need(touched: float, assignments: float,
                 sizes: Dict[str, float]) -> Dict[str, float]:
    """The expert layers' least traffic and operations: every expert TOUCHED
    (distinct experts with a token, summed over layers and steps) read once,
    a row in and a row out for every (token, expert) assignment."""
    b, w = sizes["bytes_per_value"], sizes["width"]
    expert = params(sizes)["expert"]
    return {"hbm_bytes": touched * expert * b + assignments * 2.0 * w * b,
            "flops": 2.0 * assignments * expert}


def sparse_attention_need(contexts: float, selected: float,
                          sizes: Dict[str, float]) -> Dict[str, float]:
    """Indexer, selection and attention over all layers: the indexer's keys
    read at the TRUE context lengths (``contexts``: their sum over the
    positions computed) and K/V rows of the selected positions only
    (``selected``: the sum of min(context, topk))."""
    per = cache_bytes_per_token(sizes)
    layers = sizes["layers"]
    return {"hbm_bytes": layers * (contexts * per["index"]
                                   + selected * per["kv"]),
            "flops": layers * (
                2.0 * contexts * sizes["index_heads"] * sizes["index_dim"]
                + 4.0 * selected * sizes["heads"] * sizes["head_dim"])}


def steps_need(steps: float, spans: Iterable[Tuple[int, int]],
               touched: float, sizes: Dict[str, float]) -> Dict[str, float]:
    """Whole decode steps: the experts touched, the sparse attention, and
    once a step the weights every token multiplies (attention, indexer,
    router, norms, the head and the final norm; the embedding's rows are
    left out)."""
    p, b = params(sizes), sizes["bytes_per_value"]
    tokens, contexts, selected = span_sums(spans, int(sizes["index_topk"]))
    experts = experts_need(touched, tokens * sizes["layers"]
                           * sizes["experts_per_token"], sizes)
    attn = sparse_attention_need(contexts, selected, sizes)
    other = sizes["layers"] * (p["layer"] - sizes["experts"] * p["expert"]) \
        + p["head"] + sizes["width"]
    return {"hbm_bytes": steps * other * b + experts["hbm_bytes"]
            + attn["hbm_bytes"],
            "flops": 2.0 * (p["dense_matmul"] + p["head"]) * tokens
            + experts["flops"] + attn["flops"]}


def window_flops(step_spans: Iterable[Tuple[int, int]],
                 prefill_spans: Iterable[Tuple[int, int]], joins: float,
                 sizes: Dict[str, float]) -> float:
    """Operations of every position a window generated or prefilled: two a
    parameter a position really multiplies (``experts_per_token`` experts,
    not all of them; the head once a generated token and once a join), plus
    the indexer's and attention's products at true and selected lengths.
    ``prefill_spans`` are ``(covered, length)``: positions ``covered ...
    length - 1`` were computed, position ``i`` over a context of ``i + 1``."""
    p, topk = params(sizes), int(sizes["index_topk"])
    gen, ctx, sel = span_sums(step_spans, topk)
    pre, pctx, psel = span_sums(
        [(c + 1, n - c) for c, n in prefill_spans], topk)
    attn = sparse_attention_need(ctx + pctx, sel + psel, sizes)
    return 2.0 * (p["dense_matmul"] + p["routed_matmul"]) * (gen + pre) \
        + 2.0 * p["head"] * (gen + joins) + attn["flops"]
