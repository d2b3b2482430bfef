"""A hybrid decoder of linear-attention and full-attention layers, each with
a routed MLP that holds ONE CHIP'S SHARE of its experts beside a shared
expert, served through the same paged decode path as the other decoders.

Layer ``i`` (``layer_types[i]``), ``x`` (T, D) the float32 residual stream;
a norm on each sublayer's INPUT, none on its output:

    h = RMSNorm(x)
    linear_attention (Kimi Delta Attention), per head, d_k = d_v = d:
        q, k, v = SiLU(conv(h Wq)), SiLU(conv(h Wk)), SiLU(conv(h Wv))
                  (depthwise causal conv of ``conv_kernel`` taps, no bias)
        q, k = L2norm(q) / sqrt(d), L2norm(k)
        g_t = -exp(A_log) * softplus(h Wf_a Wf_b + dt_bias)   (d,) log decay
                                                              per CHANNEL
        beta_t = 2 sigmoid(h Wb)                 in (0, 2): negative
                                                 eigenvalues allowed
        S_t = Diag(exp(g_t)) S_{t-1};  S_t = S_t + beta_t k_t (v_t - S_t^T
              k_t)^T                             S: (d, d), float32
        o_t = S_t^T q_t
        y = (RMSNorm_head(o) * sigmoid(h Wg_a Wg_b)) Wo
    full_attention (gated grouped-query, no positional encoding):
        a = softmax(q k / sqrt(d)) v over every position up to the query's
        y = (a * sigmoid(h Wgate)) Wo            gate elementwise over H x d
    x = x + y
    h = RMSNorm(x)
    s = sigmoid(h Wr) over the PUBLISHED router width; T = top-k(s + b), b a
    correction bias for the selection only; w_e = scale * s_e / sum_T s
    x = x + sum_{e in T, e held here} w_e FFN_e(h) + FFN_shared(h)
    logits = RMSNorm(x_L) W_head                 untied, no bias

The expert layer is ``window_moe``'s (``Router``, ``Experts`` told which
experts it holds, ``GatedMLP`` as the shared expert) and sows the same
``experts_touched`` and ``local_assignments``; the full layer's attention is
``window_moe.full_attention``, the one function every model with a NoPE
full layer calls.

The recurrence in a prefill chunk is the chunkwise form of the gated delta
rule: a ``lax.scan`` over blocks of ``chunk_block`` positions carries ``S``;
within a block, with ``G`` the cumulative log decay (per channel) inside the
block and every decay factor built as ``exp(G_i - G_j)`` for ``i >= j`` (no
exponent is ever positive, so a decay of ``exp(-20)`` a position cannot
overflow where ``(k e^G)(k e^-G)^T`` would),

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)        j < i
    P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)               j <= i
    U = (I + A)^-1 diag(beta) (V - (K exp(G)) S_0)        a triangular solve
    O = (Q exp(G)) S_0 + P U
    S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

with no loop over the chunk's positions.  A decode step applies the update
once.  Rows past a chunk's last real row (``logits_at``) take ``beta = 0``
and ``g = 0``, so they leave ``S`` as that row left it.

Per-slot state (``init_slot_state(rows)``, held by ``PagePool`` beside the
pages of the full layers): per linear layer the recurrent state ``(rows + 1,
heads, d, d)`` in float32 and the conv history ``(rows + 1, conv_kernel -
1, 3 * heads * d)`` (the last inputs of the q, k and v convs) in ``dtype``;
row ``r`` is the engine's slot ``r``, the last row the trash row where a
sequence whose table names no page writes.  RESET BY POSITION: a chunk or
step whose first position is 0 starts from a zero state and a zero history,
chosen inside the program, so a slot that changes hands, a leave and the
stale row of the step in flight need no zeroing and no dispatch of their
own.  ``kv_cache`` is ``(paged, state)``, each a tuple over the layers with
``()`` where a layer has no state of that kind; ``cache_rows`` (B,) names
each sequence's state row (a join prefills one sequence into its slot's
row; without it sequence ``b`` uses row ``b``, as the step does).

Device phases (``jax.named_scope``): ``lm.dense`` (embedding, every
projection, the norms), ``lm.linear_attn`` (conv, gates, L2 norms, the
recurrence or its chunk form, the gated head norm, the state's read and
write), ``lm.full_attn``, ``lm.cache_write``, ``lm.router``, ``lm.experts``,
``lm.shared_expert``, ``lm.head``.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .sparse_moe import F32, Experts, Proj, RMSNorm
from .window_moe import GatedMLP, Router, full_attention

LINEAR, FULL = "linear_attention", "full_attention"
_HI = lax.Precision.HIGHEST


# the pieces of the equations, by name (the tests plant faults through them)

def kda_beta(b):
    """``beta`` from its logits: in (0, 2), so an eigenvalue of the
    transition may be negative."""
    return 2.0 * jax.nn.sigmoid(b)


def kda_decay(f, A_log, dt_bias):
    """The log decay ``g`` (..., H, d) per CHANNEL, never above 0, from the
    low-rank projection ``f`` (..., H * d)."""
    H = A_log.shape[0]
    f = (f + dt_bias.astype(F32)).reshape(f.shape[:-1] + (H, -1))
    return -jnp.exp(A_log.astype(F32))[:, None] * jax.nn.softplus(f)


def output_gate(a, gate):
    """The full layer's output gate, elementwise."""
    return a * jax.nn.sigmoid(gate)


def conv_window(history, x):
    """What the causal conv reads: the history before the chunk, then the
    chunk's own rows."""
    return jnp.concatenate([history.astype(x.dtype), x], axis=1)


def start_from(old, fresh):
    """The state rows ``old`` as a chunk or a step starts from them: zero
    where ``fresh`` (its first position is 0)."""
    return jnp.where(fresh.reshape((-1,) + (1,) * (old.ndim - 1)),
                     jnp.zeros((), old.dtype), old)


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def kda_step(q, k, v, g, beta, S):
    """One position: ``q``, ``k``, ``v``, ``g`` (B, H, d), ``beta`` (B, H),
    ``S`` (B, H, d, d) float32.  Returns ``(o (B, H, d), S)``.  ``S`` is read
    once for both products (``k`` and ``q`` against the decayed state) and
    once for the update: ``o = q^T S' + (q . k) u``."""
    decay = jnp.exp(g)
    kq = jnp.stack([k * decay, q * decay], axis=2)               # (B, H, 2, d)
    kS, qS = jnp.moveaxis(jnp.einsum("bhnk,bhkv->bhnv", kq, S,
                                     precision=_HI), 2, 0)
    u = beta[..., None] * (v - kS)
    o = qS + jnp.sum(q * k, -1, keepdims=True) * u
    return o, decay[..., None] * S + k[..., None] * u[..., None, :]


def kda_chunked(q, k, v, g, beta, S, block: int):
    """The chunk form over ``L`` positions: ``q``, ``k``, ``v``, ``g`` (B, L,
    H, d), ``beta`` (B, L, H), ``S`` (B, H, d, d) float32.  Returns ``(o (B,
    L, H, d), S)``.  A scan over blocks of ``block`` positions (the last
    padded with ``beta = g = 0``, which changes nothing)."""
    B, L, H, d = q.shape
    n = -(-L // block)
    pad = n * block - L

    def blocks(a):                              # (n, B, H, block, ...)
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape((B, n, block) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 3, 2)

    below = jnp.arange(block)[:, None] >= jnp.arange(block)[None, :]
    strict = jnp.arange(block)[:, None] > jnp.arange(block)[None, :]

    def one(S, xs):
        q, k, v, g, beta = xs                   # (B, H, C, d), (B, H, C)
        G = jnp.cumsum(g, axis=2)
        # exp(G_i - G_j) for i >= j, each exponent <= 0; 0 above
        D = jnp.exp(jnp.where(below[..., None],
                              G[:, :, :, None] - G[:, :, None], -jnp.inf))
        kD = k[:, :, None] * D                  # (B, H, C_i, C_j, d)
        A = jnp.where(strict, jnp.sum(k[:, :, :, None] * kD, -1), 0.0) \
            * beta[..., None]
        P = jnp.sum(q[:, :, :, None] * kD, -1)
        eG = jnp.exp(G)
        rhs = beta[..., None] * (v - jnp.einsum(
            "bhcd,bhde->bhce", k * eG, S, precision=_HI))
        U = lax.linalg.triangular_solve(A, rhs, left_side=True, lower=True,
                                        unit_diagonal=True)
        o = jnp.einsum("bhcd,bhde->bhce", q * eG, S, precision=_HI) \
            + jnp.einsum("bhij,bhje->bhie", P, U, precision=_HI)
        last = G[:, :, -1]                      # (B, H, d)
        S = jnp.exp(last)[..., None] * S + jnp.einsum(
            "bhcd,bhce->bhde", k * jnp.exp(last[:, :, None] - G), U,
            precision=_HI)
        return S, o

    S, o = lax.scan(one, S, tuple(blocks(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)                   # (B, n, H, block, d)
    return jnp.moveaxis(o, 3, 2).reshape(B, n * block, H, d)[:, :L], S


class ShortConv(nn.Module):
    """The depthwise causal conv's taps, ``(kernel, channels)``; the last
    tap multiplies the current position."""
    kernel: int
    channels: int

    @nn.compact
    def __call__(self):
        return self.param("conv", nn.initializers.normal(0.5),
                          (self.kernel, self.channels))


class KimiDeltaAttention(nn.Module):
    heads: int
    head_dim: int
    conv_kernel: int
    chunk_block: int
    rms_eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, positions, state=(), page_table=None, rows=None,
                 last_row=None):
        """``h`` (B, L, D) the normed stream; ``state`` this layer's ``(S,
        conv history)`` or ``()``.  Returns ``(y (B, L, D) float32,
        state)``."""
        B, L, D = h.shape
        H, d, K, dt = self.heads, self.head_dim, self.conv_kernel, self.dtype
        C = H * d
        with jax.named_scope("lm.dense"):
            x = jnp.concatenate([Proj(C, dt, name=n)(h).astype(dt)
                                 for n in ("q", "k", "v")], -1)  # (B, L, 3C)
            f = Proj(C, dt, name="f_b")(Proj(d, dt, name="f_a")(h))
            gate = Proj(C, dt, name="g_b")(Proj(d, dt, name="g_a")(h))
            b = Proj(H, dt, name="b")(h)
        A_log = self.param("A_log", nn.initializers.zeros, (H,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (C,))
        with jax.named_scope("lm.linear_attn"):
            taps = jnp.concatenate(
                [ShortConv(K, C, name=f"{n}_conv")() for n in "qkv"], -1)
            last = jnp.full((B,), L - 1, jnp.int32) if last_row is None \
                else last_row
            if state:
                S_buf, conv_buf = state
                # a step reads rows 0 ... B - 1 in place, a join its rows
                old = (S_buf[:B], conv_buf[:B]) if rows is None \
                    else (S_buf[rows], conv_buf[rows])
                fresh = positions[:, 0] == 0
                S, history = (start_from(a, fresh) for a in old)
            else:
                S = jnp.zeros((B, H, d, d), F32)
                history = jnp.zeros((B, K - 1, 3 * C), dt)
            window = conv_window(history, x)               # (B, K - 1 + L, 3C)
            y = jax.nn.silu(sum(
                window[:, i:i + L].astype(F32) * taps[i].astype(F32)
                for i in range(K)))
            q, k, v = (a.reshape(B, L, H, d) for a in jnp.split(y, 3, -1))
            q = _l2norm(q) / jnp.sqrt(F32(d))
            k = _l2norm(k)
            real = (jnp.arange(L) <= last[:, None])[..., None]   # (B, L, 1)
            g = jnp.where(real[..., None], kda_decay(f, A_log, dt_bias), 0.0)
            beta = jnp.where(real, kda_beta(b), 0.0)
            if L == 1:
                o, S = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], S)
                o = o[:, None]
            else:
                o, S = kda_chunked(q, k, v, g, beta, S, self.chunk_block)
            o = RMSNorm(self.rms_eps, name="o_norm")(o).reshape(B, L, C) \
                * jax.nn.sigmoid(gate)
            if state:
                # the history after the last real row: window rows last + 1
                # ... last + K - 1
                at = last[:, None] + 1 + jnp.arange(K - 1)
                history = jnp.take_along_axis(window, at[..., None], axis=1)
                new = (S, history.astype(conv_buf.dtype))
                # a sequence whose table names no page (a pad row, a
                # warm-up dispatch) leaves its row as it was (a step) or
                # writes the trash row (a join)
                live = page_table[:, 0] > 0
                if rows is None:
                    state = tuple(
                        lax.dynamic_update_slice_in_dim(
                            buf, jnp.where(live.reshape(
                                (-1,) + (1,) * (a.ndim - 1)), a, b), 0, 0)
                        for buf, a, b in zip(state, new, old))
                else:
                    to = jnp.where(live, rows, S_buf.shape[0] - 1)
                    state = tuple(buf.at[to].set(a)
                                  for buf, a in zip(state, new))
        with jax.named_scope("lm.dense"):
            return Proj(D, dt, name="o")(o.astype(dt)), state


class GatedAttention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, positions, cache=(), page_table=None):
        """Grouped-query attention without positional encoding over the
        paged cache (``window_moe.full_attention``), its output gated."""
        B, L, D = h.shape
        H, KV, d, dt = self.heads, self.kv_heads, self.head_dim, self.dtype
        with jax.named_scope("lm.dense"):
            # each product as ONE array before its lanes are split into
            # heads (window_moe.py: the chip's compiler otherwise transposes
            # the whole kernel on every step)
            q = lax.optimization_barrier(
                Proj(H * d, dt, name="q")(h)).reshape(B, L, H, d).astype(dt)
            k = lax.optimization_barrier(
                Proj(KV * d, dt, name="k")(h)).astype(dt)
            v = Proj(KV * d, dt, name="v")(h).astype(dt)
            gate = Proj(H * d, dt, name="gate")(h)
        a, cache = full_attention(q, k, v, positions, cache, page_table)
        with jax.named_scope("lm.dense"):
            return Proj(D, dt, name="o")(output_gate(a, gate).astype(dt)), \
                cache


class LinearMoEBlock(nn.Module):
    linear: bool                 # linear attention (else gated full)
    num_heads: int
    num_kv_heads: int
    head_dim: int
    linear_heads: int
    linear_head_dim: int
    conv_kernel: int
    chunk_block: int
    num_experts: int             # the router's width
    experts_per_token: int
    expert_dim: int
    shared_dim: int
    first_expert: int
    experts_held: int
    routed_scale: float
    rms_eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, positions, state=(), page_table=None, rows=None,
                 last_row=None):
        """``x`` (B, L, D) float32; ``state`` this layer's pages or slot
        state, or ``()``.  Returns ``(x, state, experts touched, local
        assignments)``."""
        B, L, D = x.shape
        dt = self.dtype
        with jax.named_scope("lm.dense"):
            h = RMSNorm(self.rms_eps, name="attn_norm")(x)
        if self.linear:
            y, state = KimiDeltaAttention(
                self.linear_heads, self.linear_head_dim, self.conv_kernel,
                self.chunk_block, self.rms_eps, dt, name="attn")(
                    h, positions, state, page_table, rows, last_row)
        else:
            y, state = GatedAttention(
                self.num_heads, self.num_kv_heads, self.head_dim, dt,
                name="attn")(h, positions, state, page_table)
        with jax.named_scope("lm.dense"):
            x = x + y
            h = RMSNorm(self.rms_eps, name="mlp_norm")(x)
        with jax.named_scope("lm.router"):
            ids, w = Router(self.num_experts, self.experts_per_token,
                            self.routed_scale, name="router")(h)
            here = (ids >= self.first_expert) \
                & (ids < self.first_expert + self.experts_held)
        with jax.named_scope("lm.experts"):
            y, touched = Experts(
                self.experts_held, self.expert_dim, dt, self.first_expert,
                name="experts")(h.reshape(B * L, D), ids.reshape(B * L, -1),
                                w.reshape(B * L, -1))
        with jax.named_scope("lm.shared_expert"):
            y = y.reshape(B, L, D) + GatedMLP(self.shared_dim, dt,
                                              name="shared")(h)
        with jax.named_scope("lm.dense"):
            x = x + y
        return x, state, touched, here.sum().astype(jnp.int32)


class LinearMoEDecoder(nn.Module):
    """Causal decoder of one :class:`LinearMoEBlock` a layer, its kinds read
    from ``layer_types``."""

    vocab_size: int
    embed_dim: int = 4096
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (FULL, LINEAR, LINEAR, LINEAR)
    linear_heads: int = 64
    linear_head_dim: int = 128
    conv_kernel: int = 4
    chunk_block: int = 64
    num_experts: int = 320
    experts_per_token: int = 8
    expert_dim: int = 1280
    shared_dim: int = 1280
    first_expert: int = 0
    experts_held: Optional[int] = None        # all of them
    routed_scale: float = 1.0
    rms_eps: float = 1e-5
    max_len: int = 1048576
    dtype: Any = jnp.float32

    def _linear(self):
        for name in self.layer_types:
            if name not in (LINEAR, FULL):
                raise ValueError(f"layer kind {name!r} is not one of "
                                 f"{(LINEAR, FULL)}")
        return [name == LINEAR for name in self.layer_types]

    @nn.compact
    def __call__(self, tokens, positions=None, kv_cache=None,
                 page_table=None, logits_at=None, cache_rows=None):
        """Logits (B, L, vocab) in float32; with ``kv_cache`` = ``(paged,
        state)`` and its ``page_table``, ``(logits, cache)``.  ``logits_at``
        (B,) is each sequence's last real row: the head runs on it alone,
        (B, 1, vocab), and a linear layer's state stops there."""
        B, L = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                         (B, L))
        if kv_cache is not None and page_table is None:
            raise ValueError("LinearMoEDecoder serves the paged layout only: "
                             "give a page_table with the cache")
        held = self.num_experts if self.experts_held is None \
            else self.experts_held
        with jax.named_scope("lm.dense"):
            x = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                         name="embed")(tokens).astype(F32)
        paged, slot = [], []
        touched = local = jnp.int32(0)
        for i, linear in enumerate(self._linear()):
            state = () if kv_cache is None else kv_cache[int(linear)][i]
            x, state, n, m = LinearMoEBlock(
                linear, self.num_heads, self.num_kv_heads, self.head_dim,
                self.linear_heads, self.linear_head_dim, self.conv_kernel,
                self.chunk_block, self.num_experts, self.experts_per_token,
                self.expert_dim, self.shared_dim, self.first_expert, held,
                self.routed_scale, self.rms_eps, self.dtype,
                name=f"layer_{i}")(
                    x, positions, state, page_table, cache_rows, logits_at)
            paged.append(() if linear else state)
            slot.append(state if linear else ())
            touched, local = touched + n, local + m
        self.sow("intermediates", "experts_touched", touched)
        self.sow("intermediates", "local_assignments", local)
        with jax.named_scope("lm.head"):
            if logits_at is not None:
                x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
            logits = Proj(self.vocab_size, self.dtype, name="head")(
                RMSNorm(self.rms_eps, name="final_norm")(x))
        if kv_cache is None:
            return logits
        return logits, (tuple(paged), tuple(slot))

    def init_paged_cache(self, num_pages: int, page_size: int):
        """Zeroed pool slabs of the FULL layers: per layer ``(k, v)`` of
        ``(num_pages, page_size, num_kv_heads * head_dim)``, ``()`` for a
        linear layer.  Page 0 is the trash page."""
        if num_pages < 2:
            raise ValueError(f"num_pages {num_pages} < 2: page 0 is the "
                             "reserved trash page")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        shape = (num_pages, page_size, self.num_kv_heads * self.head_dim)
        return tuple(() if linear else (jnp.zeros(shape, self.dtype),
                                        jnp.zeros(shape, self.dtype))
                     for linear in self._linear())

    def init_slot_state(self, rows: int):
        """The per-slot state ``PagePool`` keeps beside the pages: per
        LINEAR layer the recurrent state ``(rows + 1, linear_heads, d, d)``
        in float32 and the conv history ``(rows + 1, conv_kernel - 1, 3 *
        linear_heads * d)``, the last row the trash row; ``()`` for a full
        layer.  Its size does not depend on how long a sequence may
        grow."""
        if rows < 1:
            raise ValueError(f"rows must be >= 1, got {rows}")
        H, d = self.linear_heads, self.linear_head_dim
        return tuple(
            (jnp.zeros((rows + 1, H, d, d), F32),
             jnp.zeros((rows + 1, self.conv_kernel - 1, 3 * H * d),
                       self.dtype)) if linear else ()
            for linear in self._linear())
