"""Plain reference for a hybrid decoder of linear-attention (Kimi Delta
Attention) and gated full-attention layers whose routed layers hold one
chip's share of the experts beside a shared expert; the comparison that
decides ``correct`` is ``sparse_moe_lm_reference.check_served`` (the logits
of the rows that produced the served tokens).

``linear_moe_forward`` is the forward pass of Solar Open 2 250B-A15B
(``huggingface.co/upstage/Solar-Open2-250B``, ``config.json``,
``model_type`` ``solar_open2``) over ONE sequence, in plain ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``: no cache, no
batching, no chunk form, no flax module, nothing imported from the program.
Layer ``i``, ``x`` (T, D):

    h = RMSNorm(x)
    layer_types[i] == linear_attention, per head (d_k = d_v = d):
        q, k, v = SiLU(conv4(h Wq)), SiLU(conv4(h Wk)), SiLU(conv4(h Wv))
                  depthwise causal, no bias, zeros before position 0
        q, k = L2norm(q) / sqrt(d), L2norm(k)
        g_t = -exp(A_log) * softplus(h Wf_a Wf_b + dt_bias)   per channel
        beta_t = 2 sigmoid(h Wb)
        S_t = Diag(exp(g_t)) S_{t-1};  S_t = S_t + beta_t k_t (v_t - S_t^T
              k_t)^T;  o_t = S_t^T q_t       position by position (lax.scan)
        y = (RMSNorm_head(o) * sigmoid(h Wg_a Wg_b)) Wo
    layer_types[i] == full_attention:
        q, k, v = h Wq, h Wk, h Wv; no positional encoding, no QK-norm
        a_h = softmax_{s <= t}(q_h . k_{h // (H/KV), s} / sqrt(d)) v
        y = (a * sigmoid(h Wgate)) Wo
    x = x + y
    h = RMSNorm(x)
    s = sigmoid(h Wr) over the router's whole width; T = top-k(s + b), b the
    correction bias (selection only); w_e = scale * s_e / (sum_T s + 1e-20)
    x = x + sum_{e in T, e held} w_e FFN_e(h) + FFN_shared(h)
    logits = RMSNorm(x_L) W_head

What the config has no key for is Kimi Linear's KDA layer (Moonshot AI,
arXiv 2510.26692; fla's ``KimiDeltaAttention``), whose keys
``linear_attn_config`` uses: low-rank decay and output-gate projections
through ``head_dim`` (``kda_use_full_proj`` false), ``A_log`` per head and
``dt_bias`` per channel, L2-normed q and k, ``beta`` doubled
(``kda_allow_neg_eigval``), a gated RMSNorm over each head's output; and
DeepseekV3's pre-norm block and sigmoid router, whose keys the config uses
letter for letter.  The GQA output gate is elementwise (Qwen3-Next's
``attn_output_gate``).

Departures from the published model, each also in the configuration file:
the depth is the configuration's (one whole period, G L L L); the experts
are the HELD ones, ``first_expert ...`` of the router's width (the stacked
matrices' first axis says how many), and what a token's other chosen experts
would add is left out, as on one chip of the eight that share a layer; the
vocabulary is the slice the configuration holds.

It runs layer by layer; the recurrence one position at a time over the whole
sequence from a zero state; full attention in blocks of ``query_block``
rows against the positions up to the block's end (in steps of
``context_step``); every held expert is applied DENSELY to every row and
weighted by ``w_e`` (zero where the token did not choose it), one expert's
float32 matrices at a time; logits come back for the rows ``[rows[0],
rows[0] + rows[1])`` only.  A ``rounding`` other than ``None`` computes the
same pass with every matmul operand of the projections, the attention and
the experts rounded to a lower precision (the recurrence's state stays
float32, as the configuration states it): the control of "How correct is
decided", never run by a benchmark run.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmark.families.sparse_moe_lm_reference import (  # noqa: F401
    check_served)
from benchmark.families.window_moe_lm_reference import (
    _attend, _dot, _f32, _ffn, _head, _rms_norm)

LINEAR = "linear_attention"


def _l2norm(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "eps",
                                             "rounding"))
def _linear_attention(x, p, *, heads, head_dim, eps, rounding):
    """The KDA sublayer's output (T, D) for the normed stream ``x``."""
    p = _f32(p)
    t, d = x.shape[0], head_dim

    def conv(name):
        z = _dot(x, p[name]["kernel"], rounding)
        taps = p[name + "_conv"]["conv"]                  # (K, C)
        K = taps.shape[0]
        z = jnp.concatenate([jnp.zeros((K - 1, z.shape[1])), z])
        return jax.nn.silu(sum(z[i:i + t] * taps[i] for i in range(K)))

    q = _l2norm(conv("q").reshape(t, heads, d)) / jnp.sqrt(jnp.float32(d))
    k = _l2norm(conv("k").reshape(t, heads, d))
    v = conv("v").reshape(t, heads, d)
    f = _dot(_dot(x, p["f_a"]["kernel"], rounding), p["f_b"]["kernel"],
             rounding) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        f.reshape(t, heads, d))
    beta = 2.0 * jax.nn.sigmoid(_dot(x, p["b"]["kernel"], rounding))

    def one(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[:, :, None] * S
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, S))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    _, o = jax.lax.scan(one, jnp.zeros((heads, d, d)), (q, k, v, g, beta))
    gate = _dot(_dot(x, p["g_a"]["kernel"], rounding), p["g_b"]["kernel"],
                rounding)
    o = _rms_norm(o, p["o_norm"]["scale"], eps).reshape(t, heads * d) \
        * jax.nn.sigmoid(gate)
    return _dot(o, p["o"]["kernel"], rounding)


@functools.partial(jax.jit, static_argnames=("num_heads", "num_kv_heads",
                                             "head_dim", "rounding"))
def _project(x, p, *, num_heads, num_kv_heads, head_dim, rounding):
    """The full layer's q (T, H, d), k and v (T, KV, d) and gate (T, H*d)."""
    p = _f32(p)
    t = x.shape[0]

    def proj(name, n):
        return _dot(x, p[name]["kernel"], rounding).reshape(t, n, head_dim)

    return proj("q", num_heads), proj("k", num_kv_heads), \
        proj("v", num_kv_heads), _dot(x, p["gate"]["kernel"], rounding)


@functools.partial(jax.jit, static_argnames=("rounding",))
def _gated_out(a, gate, o, *, rounding):
    return _dot(a * jax.nn.sigmoid(gate), o["kernel"].astype(jnp.float32),
                rounding)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, norm, *, eps):
    return _rms_norm(x, norm["scale"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=(
    "experts_per_token", "first_expert", "scale", "rounding"))
def _moe(x, h, router, experts, shared, *, experts_per_token, first_expert,
         scale, rounding):
    """``x`` plus the routed layer over the experts HELD (``experts``' first
    axis, from ``first_expert`` of the router's width on) and the shared
    expert, both of the normed stream ``h``."""
    router, shared = _f32(router), _f32(shared)
    s = jax.nn.sigmoid(_dot(h, router["kernel"], rounding))        # (T, E)
    _, ids = jax.lax.top_k(s + router["bias"], experts_per_token)
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    w = scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    by_expert = jnp.zeros_like(s).at[
        jnp.arange(h.shape[0])[:, None], ids].set(w)
    held = experts["gate"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(by_expert, first_expert, held, 1)

    def one(acc, e):
        gate, up, down, w_e = e
        return acc + w_e[:, None] * _ffn(
            h, gate.astype(jnp.float32), up.astype(jnp.float32),
            down.astype(jnp.float32), rounding), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        experts["gate"], experts["up"], experts["down"], mine.T))
    return x + y + _ffn(h, shared["gate"]["kernel"], shared["up"]["kernel"],
                        shared["down"]["kernel"], rounding)


def linear_moe_forward(variables, tokens, *, layer_types: Sequence[str],
                       num_heads: int, num_kv_heads: int, head_dim: int,
                       linear_heads: int, linear_head_dim: int,
                       experts_per_token: int, first_expert: int,
                       routed_scale: float, eps: float,
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
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][tokens].astype(jnp.float32)
        for i, kind in enumerate(layer_types):
            layer = p[f"layer_{i}"]
            h = _norm(x, layer["attn_norm"], eps=float(eps))
            if kind == LINEAR:
                x = x + _linear_attention(
                    h, layer["attn"], heads=int(linear_heads),
                    head_dim=int(linear_head_dim), eps=float(eps),
                    rounding=rounding)
            else:
                q, k, v, gate = _project(
                    h, layer["attn"], num_heads=num_heads,
                    num_kv_heads=num_kv_heads, head_dim=head_dim,
                    rounding=rounding)
                a = _attend(q, k, v, window=0, block=block,
                            reach=context_step, rounding=rounding)
                del q, k, v
                x = x + _gated_out(a, gate, layer["attn"]["o"],
                                   rounding=rounding)
            h = _norm(x, layer["mlp_norm"], eps=float(eps))
            x = _moe(x, h, layer["router"], layer["experts"], layer["shared"],
                     experts_per_token=int(experts_per_token),
                     first_expert=int(first_expert),
                     scale=float(routed_scale), rounding=rounding)
        return _head(x, jnp.int32(first), p["final_norm"], p["head"],
                     count=int(count), eps=float(eps), rounding=rounding)
