"""Plain reference for a decoder whose layers differ by kind (window or full
attention, a dense or a routed MLP beside a shared expert) and whose routed
layers hold one chip's share of the experts; the comparison that decides
``correct`` is ``sparse_moe_lm_reference.check_served`` (the logits of the
rows that produced the served tokens).

``window_moe_forward`` is the forward pass of K-EXAONE-236B-A23B
(``huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B``, ``config.json``,
``model_type`` ``exaone_moe``) over ONE sequence, in plain ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``: no cache, no
batching, no flax module, nothing imported from the program.  Layer ``i``,
``x`` (T, D):

    q, k, v = x Wq, x Wk, x Wv        (H, d), (KV, d), (KV, d); no bias, no
                                      norm on the sublayer's input
    q, k = RMSNorm_d(q), RMSNorm_d(k)                  one scale of d each
    layer_types[i] == sliding_attention:  q, k = RoPE(q, k) (rotate-half,
        theta); key s visible to query t iff 0 <= t - s < window
    layer_types[i] == full_attention:     no RoPE; key s visible iff s <= t
    a_h = softmax_s(q_h . k_{h // (H/KV), s} / sqrt(d)) v_{h // (H/KV), s}
    x = x + RMSNorm(a Wo)
    mlp_layer_types[i] == dense:   x = x + RMSNorm((silu(x Wg) * (x Wu)) Wd)
    mlp_layer_types[i] == sparse:
        s = sigmoid(x Wr)           over the router's whole published width
        T = top-k of (s + b)        b: the correction bias, selection only
        w_e = scale * s_e / (sum_{e' in T} s_e' + 1e-20)      for e in T
        y = sum_{e in T, e held} w_e FFN_e(x) + FFN_shared(x)
        x = x + RMSNorm(y)
    logits = RMSNorm(x_L) W_head

What the config has no key for is EXAONE 4.0's convention, whose released
code (``transformers/models/exaone4/modeling_exaone4.py``) this follows:
QK-norm before RoPE; RoPE on sliding layers only; the two RMSNorms of a
layer on the sublayer's OUTPUT before the residual add, none on its input;
the router is ``DeepseekV3TopkRouter`` (whose keys the config uses) with
``n_group`` = ``topk_group`` = 1, so no grouping.  The shared expert is
inside the normed sum.

Departures from the published model, each also in the configuration file:
the depth is the configuration's (the leading dense layer and one ``LLLG``
period); the experts are the HELD ones, ``first_expert ...`` of the
router's width (the stacked matrices' first axis says how many), and what a
token's other chosen experts would add is left out, as on one chip of the
eight that share a layer; the vocabulary is the slice the configuration
holds; the multi-token-prediction module is left out (next-token logits do
not depend on it).

It runs layer by layer; attention in blocks of ``query_block`` rows, a
window layer against the ``window - 1`` positions before the block and the
block itself, a full layer against the positions up to the block's end (in
steps of ``context_step``, so that few lengths are compiled), masks built
from positions; every held expert is applied DENSELY to every row and
weighted by ``w_e`` (zero where the token did not choose it), one expert's
float32 matrices at a time; logits come back for the rows ``[rows[0],
rows[0] + rows[1])`` only.  A ``rounding`` other than ``None`` computes the
same pass with every matmul operand rounded to a lower precision: the
control of "How correct is decided", never run by a benchmark run.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmark.families.sparse_moe_lm_reference import (  # noqa: F401
    ROUNDINGS, check_served)

WINDOW, SPARSE = "sliding_attention", "sparse"


def _dot(a, b, rounding):
    r = ROUNDINGS[rounding]
    return r(a) @ r(b)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half RoPE of ``x`` (T, n, d), row ``t`` at position ``t``."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _ffn(x, gate, up, down, rounding):
    return _dot(jax.nn.silu(_dot(x, gate, rounding))
                * _dot(x, up, rounding), down, rounding)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "head_dim", "rope", "theta", "eps",
    "rounding"))
def _project(x, p, *, num_heads, num_kv_heads, head_dim, rope, theta, eps,
             rounding):
    """q (T, H, d), k and v (T, KV, d) of every row."""
    p = _f32(p)
    t = x.shape[0]

    def proj(name, n):
        return _dot(x, p[name]["kernel"], rounding).reshape(t, n, head_dim)

    q = _rms_norm(proj("q", num_heads), p["q_norm"]["scale"], eps)
    k = _rms_norm(proj("k", num_kv_heads), p["k_norm"]["scale"], eps)
    if rope:
        q, k = _rope(q, theta), _rope(k, theta)
    return q, k, proj("v", num_kv_heads)


@functools.partial(jax.jit, static_argnames=("window", "rounding"))
def _attend_rows(q, q_pos, k, v, k_pos, *, window, rounding):
    """Attention output (R, H * d) of query rows at positions ``q_pos``
    against keys at ``k_pos`` (below 0: padding, never visible); ``window``
    0 sees every position not after the query."""
    r = ROUNDINGS[rounding]
    rows, heads, d = q.shape
    kv_heads = k.shape[1]
    gap = q_pos[:, None] - k_pos[None, :]
    see = (gap >= 0) & (k_pos[None, :] >= 0)
    if window:
        see &= gap < window
    qg = r(q.reshape(rows, kv_heads, heads // kv_heads, d))
    a = jnp.einsum("rngd,snd->rngs", qg, r(k)) / jnp.sqrt(jnp.float32(d))
    a = jax.nn.softmax(jnp.where(see[:, None, None], a, -jnp.inf), -1)
    return jnp.einsum("rngs,snd->rngd", r(a), r(v)).reshape(rows, heads * d)


def _attend(q, k, v, *, window, block, reach, rounding):
    """``_attend_rows`` block after block of query rows: a window layer
    against the ``window - 1`` rows before the block and the block, a full
    layer against the positions up to the block's end in steps of
    ``reach``."""
    t, out = q.shape[0], []
    pos = jnp.arange(t, dtype=jnp.int32)
    if window:
        lead = window - 1
        k = jnp.concatenate([jnp.zeros((lead,) + k.shape[1:], k.dtype), k])
        v = jnp.concatenate([jnp.zeros((lead,) + v.shape[1:], v.dtype), v])
        k_pos_all = jnp.arange(-lead, t, dtype=jnp.int32)
    for r0 in range(0, t, block):
        if window:
            span = slice(r0, r0 + block + window - 1)
            keys = (k[span], v[span], k_pos_all[span])
        else:
            n = min(t, -(-(r0 + block) // reach) * reach)
            keys = (k[:n], v[:n], pos[:n])
        out.append(_attend_rows(q[r0:r0 + block], pos[r0:r0 + block], *keys,
                                window=window, rounding=rounding))
    return jnp.concatenate(out)


@functools.partial(jax.jit, static_argnames=("eps", "rounding"))
def _attn_out(x, a, o, norm, *, eps, rounding):
    return x + _rms_norm(_dot(a, o["kernel"].astype(jnp.float32), rounding),
                         norm["scale"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("eps", "rounding"))
def _dense_mlp(x, mlp, norm, *, eps, rounding):
    mlp = _f32(mlp)
    y = _ffn(x, mlp["gate"]["kernel"], mlp["up"]["kernel"],
             mlp["down"]["kernel"], rounding)
    return x + _rms_norm(y, norm["scale"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=(
    "experts_per_token", "first_expert", "scale", "eps", "rounding"))
def _sparse_mlp(x, router, experts, shared, norm, *, experts_per_token,
                first_expert, scale, eps, rounding):
    """The routed layer over the experts HELD (``experts``' first axis, from
    ``first_expert`` of the router's width on) and the shared expert."""
    router, shared = _f32(router), _f32(shared)
    s = jax.nn.sigmoid(_dot(x, router["kernel"], rounding))        # (T, E)
    _, ids = jax.lax.top_k(s + router["bias"], experts_per_token)
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    w = scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    # (T, E): a token's weight for each expert, zero where not chosen
    by_expert = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], ids].set(w)
    held = experts["gate"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(by_expert, first_expert, held, 1)

    def one(acc, e):
        gate, up, down, w_e = e
        return acc + w_e[:, None] * _ffn(
            x, gate.astype(jnp.float32), up.astype(jnp.float32),
            down.astype(jnp.float32), rounding), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        experts["gate"], experts["up"], experts["down"], mine.T))
    y = y + _ffn(x, shared["gate"]["kernel"], shared["up"]["kernel"],
                 shared["down"]["kernel"], rounding)
    return x + _rms_norm(y, norm["scale"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("count", "eps", "rounding"))
def _head(x, start, norm, head, *, count, eps, rounding):
    rows = jax.lax.dynamic_slice_in_dim(x, start, count, 0)
    h = _rms_norm(rows, norm["scale"].astype(jnp.float32), eps)
    return _dot(h, head["kernel"].astype(jnp.float32), rounding)


def window_moe_forward(variables, tokens, *, layer_types: Sequence[str],
                       mlp_layer_types: Sequence[str], num_heads: int,
                       num_kv_heads: int, head_dim: int, window: int,
                       experts_per_token: int, first_expert: int,
                       routed_scale: float, rope_theta: float, eps: float,
                       rows: Optional[Tuple[int, int]] = None,
                       query_block: int = 256, context_step: int = 2048,
                       rounding: Optional[str] = None):
    """Logits in float32 of one sequence of token ids ``(T,)``; row ``t``
    predicts token ``t + 1``.  ``rows = (first, count)`` returns those rows
    only, ``(count, vocab)``.  The sequence is padded with zeros to a whole
    number of query blocks (a causal pass: after everything read)."""
    p = variables["params"]
    first, count = rows if rows is not None else (0, len(tokens))
    block = min(query_block, len(tokens))
    t = -(-len(tokens) // block) * block
    tokens = jnp.zeros(t, jnp.int32).at[:len(tokens)].set(
        jnp.asarray(tokens, jnp.int32))
    how = dict(eps=float(eps), rounding=rounding)
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][tokens].astype(jnp.float32)
        for i, (kind, mlp) in enumerate(zip(layer_types, mlp_layer_types)):
            layer = p[f"layer_{i}"]
            sliding = kind == WINDOW
            q, k, v = _project(
                x, {n: layer[n] for n in ("q", "k", "v", "q_norm", "k_norm")},
                num_heads=num_heads, num_kv_heads=num_kv_heads,
                head_dim=head_dim, rope=sliding, theta=float(rope_theta),
                **how)
            a = _attend(q, k, v, window=int(window) if sliding else 0,
                        block=block, reach=context_step, rounding=rounding)
            del q, k, v
            x = _attn_out(x, a, layer["o"], layer["attn_out_norm"], **how)
            if mlp == SPARSE:
                x = _sparse_mlp(
                    x, layer["router"], layer["experts"], layer["shared"],
                    layer["mlp_out_norm"],
                    experts_per_token=int(experts_per_token),
                    first_expert=int(first_expert),
                    scale=float(routed_scale), **how)
            else:
                x = _dense_mlp(x, layer["mlp"], layer["mlp_out_norm"], **how)
        return _head(x, jnp.int32(first), p["final_norm"], p["head"],
                     count=int(count), **how)
