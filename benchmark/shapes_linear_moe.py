"""What a hybrid decoder of linear-attention (Kimi Delta Attention) and gated
full-attention layers with a share of its routed experts needs, from its
shapes alone: parameters, the least bytes and operations of its decode
steps, of the recurrence's chunk form in a prefill, and of a whole window.
``benchmark/shapes.py``'s sibling for this block; a roofline share is the
least time (``shapes.least_s``) over the device time a trace shows.
``sizes`` is a configuration's: ``layers``, ``width``, ``heads``,
``kv_heads``, ``head_dim``, ``experts_per_token``, ``expert_width``,
``vocab``, ``bytes_per_value``, ``moe_layers``, ``full_layers``,
``experts_held``, ``router_width``, ``shared_width`` (as the window
decoder's) and ``linear_layers``, ``linear_heads``, ``linear_head_dim``,
``conv_kernel``, ``chunk_block``, ``state_bytes_per_value``.
"""
from __future__ import annotations

from typing import Dict

from benchmark import shapes_window_moe


def params(sizes: Dict[str, float]) -> Dict[str, float]:
    """Parameters by part, of what is HELD here.  A linear layer's attention:
    q, k, v and o, the low-rank decay and output-gate projections through
    ``linear_head_dim``, beta's projection, the three convs' taps, ``A_log``
    a head, ``dt_bias`` a channel and the gated head norm.  A full layer's:
    q and its gate, k, v and o.  Every layer: two norms of the width and a
    routed MLP (the router over its whole width with its correction bias,
    ``experts_held`` experts and one shared expert, three matrices each).
    Outside the layers: an embedding, a final RMSNorm and an untied head
    over the vocabulary held.  Solar Open 2 as the configuration cuts it (4
    layers, 40 of 320 experts, 24,576 of 196,608 rows): 3,308,353,344."""
    w, d = sizes["width"], sizes["head_dim"]
    dl, K = sizes["linear_head_dim"], sizes["conv_kernel"]
    C = sizes["linear_heads"] * dl
    linear_small = 3 * C * K + sizes["linear_heads"] + C + dl
    linear = 4 * w * C + 2 * (w * dl + dl * C) + w * sizes["linear_heads"] \
        + linear_small
    q, kv = sizes["heads"] * d, sizes["kv_heads"] * d
    full = 2 * w * q + 2 * w * kv + q * w
    expert = 3 * w * sizes["expert_width"]
    shared = 3 * w * sizes["shared_width"]
    router = w * sizes["router_width"] + sizes["router_width"]
    routed = sizes["experts_held"] * expert + shared + router
    norms = 2 * w
    embedding = head = sizes["vocab"] * w
    total = sizes["linear_layers"] * (linear + routed + norms) \
        + sizes["full_layers"] * (full + routed + norms) \
        + embedding + head + w
    # what every position multiplies, whatever it is routed to
    every = sizes["linear_layers"] * (linear - linear_small) \
        + sizes["full_layers"] * full \
        + sizes["moe_layers"] * (shared + router - sizes["router_width"])
    return {"linear": float(linear), "full": float(full),
            "expert": float(expert), "shared": float(shared),
            "router": float(router), "norms": float(norms),
            "embedding": float(embedding), "head": float(head),
            "total": float(total), "every_matmul": float(every),
            # read once a step: all but the routed experts and the
            # embedding, of which a step reads a row a token
            "step_weights": float(total - embedding - sizes["moe_layers"]
                                  * sizes["experts_held"] * expert)}


def state_row_bytes(sizes: Dict[str, float]) -> float:
    """The per-slot state of ONE linear layer and one row: the recurrent
    state (heads x d x d) and the conv history (``conv_kernel - 1`` inputs
    of the q, k and v convs)."""
    dl, H = sizes["linear_head_dim"], sizes["linear_heads"]
    return H * dl * dl * sizes["state_bytes_per_value"] \
        + (sizes["conv_kernel"] - 1) * 3 * H * dl * sizes["bytes_per_value"]


def _io_row_bytes(sizes: Dict[str, float]) -> float:
    """A position's q, k, v, g and beta in and o out of one linear layer."""
    H, dl = sizes["linear_heads"], sizes["linear_head_dim"]
    return (5 * H * dl + H) * sizes["bytes_per_value"]


def linear_step_need(tokens: float, sizes: Dict[str, float]
                     ) -> Dict[str, float]:
    """The linear layers' work in decode steps for ``tokens`` LIVE rows
    stepped: every such row's state read and written once, its
    activations in and out; per head ``7 d^2`` operations (two products
    against the decayed state and the rank-one update)."""
    n = sizes["linear_layers"] * tokens
    dl = sizes["linear_head_dim"]
    return {"hbm_bytes": n * (2.0 * state_row_bytes(sizes)
                              + _io_row_bytes(sizes)),
            "flops": n * sizes["linear_heads"] * 7.0 * dl * dl}


def chunk_flops_per_position(sizes: Dict[str, float]) -> float:
    """Operations of the chunk form for one position of one linear layer,
    blocks of ``chunk_block`` = C: per head ``4 C d`` for the two decay-
    weighted products inside the block (A and P, the whole square), ``C d``
    for the triangular solve, ``2 C d`` for ``P U`` and ``6 d^2`` for the
    three products with the state (in, out and the new state)."""
    C, dl = sizes["chunk_block"], sizes["linear_head_dim"]
    return sizes["linear_heads"] * (7.0 * C * dl + 6.0 * dl * dl)


def chunk_need(tokens: float, chunks: float, sizes: Dict[str, float]
               ) -> Dict[str, float]:
    """The chunk form in the prefills, at the TRUE prompt ``tokens``: the
    operations of every real position, each chunk's state read and written
    once, every position's activations in and out."""
    L = sizes["linear_layers"]
    return {"flops": L * tokens * chunk_flops_per_position(sizes),
            "hbm_bytes": L * (chunks * 2.0 * state_row_bytes(sizes)
                              + tokens * _io_row_bytes(sizes))}


def steps_need(steps: float, tokens: float, contexts: float, touched: float,
               local: float, sizes: Dict[str, float]) -> Dict[str, float]:
    """Whole decode steps: once a step the weights outside the experts (the
    head among them), the held experts touched, the live rows' linear state
    read and written, and the full layers' K/V at the TRUE contexts."""
    p, b = params(sizes), sizes["bytes_per_value"]
    experts = shapes_window_moe.experts_need(touched, local, sizes)
    linear = linear_step_need(tokens, sizes)
    kv = sizes["full_layers"] * contexts
    return {"hbm_bytes": steps * p["step_weights"] * b
            + experts["hbm_bytes"] + linear["hbm_bytes"]
            + kv * shapes_window_moe.kv_row_bytes(sizes),
            "flops": 2.0 * (p["every_matmul"] + p["head"]) * tokens
            + experts["flops"] + linear["flops"]
            + 4.0 * kv * sizes["heads"] * sizes["head_dim"]}


def window_flops(step_tokens: float, step_contexts: float, local: float,
                 prefill_tokens: float, prefill_contexts: float, joins: float,
                 sizes: Dict[str, float]) -> float:
    """Operations of every position a window generated or prefilled: two a
    parameter a position really multiplies (LOCAL experts only: ``local``
    assignments in the steps, and the same share of a prefilled position's;
    the head once a generated token and once a join), the recurrence's (a
    step's update, the chunk form's in a prefill) and the full layers'
    attention products at the true lengths."""
    p = params(sizes)
    positions = step_tokens + prefill_tokens
    local_all = local * (positions / step_tokens if step_tokens else 0.0)
    recurrence = linear_step_need(step_tokens, sizes)["flops"] \
        + chunk_need(prefill_tokens, 0.0, sizes)["flops"]
    attention = 4.0 * sizes["full_layers"] * sizes["heads"] \
        * sizes["head_dim"] * (step_contexts + prefill_contexts)
    return 2.0 * p["every_matmul"] * positions \
        + 2.0 * p["expert"] * local_all \
        + 2.0 * p["head"] * (step_tokens + joins) + recurrence + attention
