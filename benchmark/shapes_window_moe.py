"""What a decoder of window and full attention layers with a share of its
routed experts needs, from its shapes alone: parameters, and the least bytes
and the operations of its decode steps and prefills.  ``benchmark/shapes.py``'s
sibling for this block; a roofline share is the least time (``shapes.
least_s``) over the device time a trace shows.  ``sizes`` is a
configuration's: ``layers``, ``width``, ``heads``, ``kv_heads``, ``head_dim``,
``experts_per_token``, ``expert_width``, ``vocab``, ``bytes_per_value`` (as
the other decoders') and ``moe_layers``, ``window_layers``, ``full_layers``,
``window``, ``experts_held``, ``router_width``, ``dense_width``,
``shared_width``.
"""
from __future__ import annotations

from typing import Dict


def params(sizes: Dict[str, float]) -> Dict[str, float]:
    """Parameters by part, of what is HELD here.  Every layer: attention (q,
    k, v, o; no biases) and four norms (two of the width on the sublayers'
    outputs, two of a head on q and k).  The ``layers - moe_layers`` leading
    layers: a dense MLP of three matrices.  A routed layer: the router over
    its whole width with its correction bias, ``experts_held`` experts and
    one shared expert, three matrices each.  Outside the layers: an
    embedding, a final RMSNorm and an untied head over the vocabulary held.
    K-EXAONE-236B-A23B as the configuration cuts it (5 layers, 16 of 128
    experts, 19,200 of 153,600 rows): 3,712,028,416."""
    w, d = sizes["width"], sizes["head_dim"]
    q, kv = sizes["heads"] * d, sizes["kv_heads"] * d
    attention = w * q + 2 * w * kv + q * w
    norms = 2 * w + 2 * d
    dense = 3 * w * sizes["dense_width"]
    expert = 3 * w * sizes["expert_width"]
    shared = 3 * w * sizes["shared_width"]
    router = w * sizes["router_width"] + sizes["router_width"]
    moe, plain = sizes["moe_layers"], sizes["layers"] - sizes["moe_layers"]
    dense_layer = attention + norms + dense
    moe_layer = attention + norms + router + shared \
        + sizes["experts_held"] * expert
    embedding = head = sizes["vocab"] * w
    # what every position multiplies, whatever it is routed to
    every = sizes["layers"] * attention + plain * dense \
        + moe * (shared + router - sizes["router_width"])
    return {"attention": float(attention), "norms": float(norms),
            "dense": float(dense), "expert": float(expert),
            "shared": float(shared), "router": float(router),
            "dense_layer": float(dense_layer), "moe_layer": float(moe_layer),
            "embedding": float(embedding), "head": float(head),
            "total": float(plain * dense_layer + moe * moe_layer
                           + embedding + head + w),
            "every_matmul": float(every),
            # read once a step: all but the routed experts and the
            # embedding, of which a step reads a row a token
            "step_weights": float(plain * dense_layer + moe * (
                moe_layer - sizes["experts_held"] * expert) + head + w)}


def kv_row_bytes(sizes: Dict[str, float]) -> float:
    """Bytes a position holds in ONE layer: its key and its value."""
    return 2.0 * sizes["kv_heads"] * sizes["head_dim"] \
        * sizes["bytes_per_value"]


def experts_need(touched: float, local: float,
                 sizes: Dict[str, float]) -> Dict[str, float]:
    """The routed layers' least traffic and operations: every held expert
    TOUCHED (with a token; summed over layers and steps) read once, a row in
    and a row out for every LOCAL assignment (a token-expert pair that
    landed on a held expert)."""
    b, w = sizes["bytes_per_value"], sizes["width"]
    expert = params(sizes)["expert"]
    return {"hbm_bytes": touched * expert * b + local * 2.0 * w * b,
            "flops": 2.0 * local * expert}


def attention_need(tokens: float, contexts: float, window_keys: float,
                   sizes: Dict[str, float]) -> Dict[str, float]:
    """Attention over all layers for ``tokens`` query positions: a window
    layer reads ``window_keys`` K/V rows in all (``window`` a token once a
    sequence is that long), a full layer ``contexts`` (the sum of the TRUE
    context lengths)."""
    rows = sizes["window_layers"] * window_keys \
        + sizes["full_layers"] * contexts
    return {"hbm_bytes": rows * kv_row_bytes(sizes),
            "flops": 4.0 * rows * sizes["heads"] * sizes["head_dim"]}


def steps_need(steps: float, tokens: float, contexts: float, touched: float,
               local: float, sizes: Dict[str, float]) -> Dict[str, float]:
    """Whole decode steps: once a step the weights every token multiplies
    (attention, norms, dense MLP, routers, shared experts, the head), the
    held experts touched, and attention's rows (``window`` a token a window
    layer: the cell's prompts are never shorter)."""
    p, b = params(sizes), sizes["bytes_per_value"]
    experts = experts_need(touched, local, sizes)
    attn = attention_need(tokens, contexts, tokens * sizes["window"], sizes)
    return {"hbm_bytes": steps * p["step_weights"] * b
            + experts["hbm_bytes"] + attn["hbm_bytes"],
            "flops": 2.0 * (p["every_matmul"] + p["head"]) * tokens
            + experts["flops"] + attn["flops"]}


def window_flops(step_tokens: float, step_contexts: float, local: float,
                 prefill_tokens: float, prefill_contexts: float, joins: float,
                 sizes: Dict[str, float]) -> float:
    """Operations of every position a window generated or prefilled: two a
    parameter a position really multiplies (LOCAL experts only: ``local``
    assignments in the steps, and the same share of a prefilled position's;
    the head once a generated token and once a join), plus attention's
    products at windowed and true lengths (a prompt of ``n >= window``
    positions has ``window * n - window * (window - 1) / 2`` visible
    keys in a window layer)."""
    p, wn = params(sizes), sizes["window"]
    positions = step_tokens + prefill_tokens
    local_all = local * (positions / step_tokens if step_tokens else 0.0)
    prefill_window = max(0.0, wn * prefill_tokens
                         - joins * wn * (wn - 1) / 2.0)
    attn = attention_need(positions, step_contexts + prefill_contexts,
                          step_tokens * wn + prefill_window, sizes)
    return 2.0 * p["every_matmul"] * positions \
        + 2.0 * p["expert"] * local_all \
        + 2.0 * p["head"] * (step_tokens + joins) + attn["flops"]
