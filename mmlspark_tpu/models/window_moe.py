"""A decoder whose layers differ by kind inside one model, served through the
same paged decode path as ``TransformerEncoder`` and ``SparseMoEDecoder``
(ISSUE 36): window or full attention, a dense or a routed MLP, the routed one
holding ONE CHIP'S SHARE of the experts beside a shared expert.

Layer ``i`` (``layer_types[i]``, ``mlp_layer_types[i]``), ``x`` (T, D) the
float32 residual stream; no norm on a sublayer's input, one on its OUTPUT:

    q, k, v = x Wq, x Wk, x Wv                   grouped-query, no bias
    q, k = RMSNorm_head(q), RMSNorm_head(k)      one scale of head_dim each
    sliding_attention:  q, k = RoPE(q, k), rotate-half; key s visible to
                        query t iff 0 <= t - s < window
    full_attention:     NO RoPE; key s visible iff s <= t
    x = x + RMSNorm(softmax(q k / sqrt(head_dim)) v Wo)
    dense:   x = x + RMSNorm((silu(x Wg) * (x Wu)) Wd)
    sparse:  s = sigmoid(x Wr) over the PUBLISHED router width
             T = top-k of (s + b), b a correction bias for the selection only
             w_e = scale * s_e / (sum_{e' in T} s_e' + 1e-20)        e in T
             y = sum_{e in T, e held here} w_e FFN_e(x) + FFN_shared(x)
             x = x + RMSNorm(y)
    logits = RMSNorm(x_L) W_head                 untied, no bias

The expert layer is told which experts it holds (``first_expert``,
``experts_held``): the router keeps its width and its experts per token, only
assignments that land on held experts are sorted, gathered and multiplied
(``sparse_moe.routed_experts(first=)``), and what the absent experts would
add is left out: the partial sum is what goes on to the next layer, as on a
chip of an expert-parallel deployment before its exchange.  Every token
multiplies the shared expert.  Sown into ``intermediates``, summed over the
layers: ``experts_touched`` (held experts with a token) and
``local_assignments`` (token-expert pairs that landed here).

Cache.  Two kinds of state, both handed out by ``PagePool``:

- full layers: pages, as the other decoders (``init_paged_cache``: K and V
  slabs ``(pages, page_size, num_kv_heads * head_dim)`` addressed through the
  page table, written by ``transformer.paged_write``);
- window layers: a RING of ``window`` positions a sequence
  (``init_window_cache(rows)``: K and V ``(rows + 1, window, C)``; ring row
  ``r`` belongs to the engine's slot ``r`` and the last row is the trash row,
  where sequences whose table names no page write).  Position ``p`` lives at
  ring index ``p % window``, so with the newest position ``last`` index ``i``
  holds position ``last - ((last - i) % window)``: visibility follows from
  positions alone, and a row left by an earlier request maps to a position
  below 0 or is overwritten before it can be seen.  A decode step writes its
  row and reads the slot's ``window`` rows in place, whatever the context
  length; a prefill chunk attends to the ring as earlier chunks and steps
  left it plus its own rows, then leaves its last ``window`` real rows in
  the ring.  The state is bounded by ``window`` a slot a layer whatever
  ``max_prompt_len + max_new_tokens``.

``kv_cache`` is ``(paged, window)``, each a tuple over the layers with ``()``
where a layer has no state of that kind.  ``cache_rows`` (B,) names each
sequence's ring row (a join prefills one sequence into its slot's row);
without it sequence ``b`` uses row ``b``, as the step does.

Device phases (``jax.named_scope``): ``lm.dense`` (embedding, projections,
norms, the dense MLP), ``lm.shared_expert``, ``lm.window_attn``,
``lm.full_attn``, ``lm.cache_write``, ``lm.router``, ``lm.experts``,
``lm.head``.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .sparse_moe import (F32, Experts, Proj, RMSNorm, _rope,
                         masked_attention)
from .transformer import paged_write

WINDOW, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


def ring_positions(last, window: int):
    """The absolute position each ring index holds when the newest position
    written is ``last`` (...,): ``(..., window)``, below 0 where nothing of
    this sequence was written yet."""
    i = jnp.arange(window, dtype=jnp.int32)
    return last[..., None] - (last[..., None] - i) % window


def window_visible(q_pos, k_pos, window: int):
    """Key at ``k_pos`` (B, S) visible to query at ``q_pos`` (B, L):
    ``0 <= t - s < window`` and ``s`` a real position.  (B, L, S)."""
    gap = q_pos[:, :, None] - k_pos[:, None, :]
    return (gap >= 0) & (gap < window) & (k_pos[:, None, :] >= 0)


def step_attention(q, keys, vals, visible, kv_heads: int):
    """One query row a sequence over its context AS THE CACHE HOLDS IT:
    ``q`` (B, H, d) against ``keys``, ``vals`` (B, S, kv_heads * d) where
    ``visible`` (B, S); float32 (B, H * d).

    Splitting the context's lanes into (kv_heads, d) is a relayout of all of
    it on the chip (two copies of 403 MB a step here, PERF.md PR 36; the same
    finding as PR 35's), so the QUERY is laid out instead: ``(C, H)`` with
    head h's d values on the lanes of its own KV head and exact zeros
    elsewhere.  Scores and output are then plain matrix products over the
    context as gathered, and of head h's mix of every lane its KV head's d
    are kept.  Same products, same sums.

    ``transformer.py MultiHeadAttention`` keeps its own inline copy of the
    ``kv_heads == H`` case and does not call this: that copy works in the
    model's dtype throughout (bfloat16 scores, a -1e30 mask, one sum over
    heads) where this one accumulates in float32, so sharing one function
    changes GPT-2 XL's compiled step, whose numbers and ``correct`` limit
    (PR 33 / PR 35) were read on the inline program.  Merging them is a
    change to that cell's program and wants its own parent-against-change
    runs (PERF.md section 7)."""
    B, H, d = q.shape
    C = keys.shape[-1]
    own = jnp.arange(C)[:, None] // d == jnp.arange(H) // (H // kv_heads)
    qc = jnp.where(own, jnp.tile(q.transpose(0, 2, 1), (1, kv_heads, 1)), 0)
    s = jnp.einsum("bsc,bch->bhs", keys, qc,
                   preferred_element_type=F32) / jnp.sqrt(F32(d))
    p = jax.nn.softmax(jnp.where(visible[:, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("bhs,bsc->bhc", p.astype(vals.dtype), vals,
                     preferred_element_type=F32)             # (B, H, C)
    out = jnp.where(own.T, out, 0).reshape(B, H, kv_heads, d).sum(2)
    return out.reshape(B, H * d)


class GatedMLP(nn.Module):
    """``(silu(x Wg) * (x Wu)) Wd``: the dense MLP and the shared expert."""
    features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = jax.nn.silu(Proj(self.features, self.dtype, name="gate")(x)) \
            * Proj(self.features, self.dtype, name="up")(x)
        return Proj(x.shape[-1], self.dtype, name="down")(h)


class Router(nn.Module):
    """Sigmoid scores over the router's whole width, top-k of the scores plus
    a correction bias (selection only), weights normalised over the chosen
    and scaled: ``(ids (T, k), weights (T, k))``, all float32."""
    num_experts: int
    experts_per_token: int
    scale: float

    @nn.compact
    def __call__(self, h):
        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (h.shape[-1], self.num_experts))
        bias = self.param("bias", nn.initializers.zeros, (self.num_experts,))
        s = jax.nn.sigmoid(jnp.dot(h, kernel.astype(F32),
                                   precision=lax.Precision.HIGHEST))
        _, ids = lax.top_k(s + bias.astype(F32), self.experts_per_token)
        w = jnp.take_along_axis(s, ids, axis=-1)
        return ids, self.scale * w / (w.sum(-1, keepdims=True) + 1e-20)


class WindowMoEBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int                  # 0: full attention over the paged cache
    rope: bool                   # rotary positions on q and k
    sparse: bool                 # a routed MLP (else the dense one)
    dense_dim: int
    num_experts: int             # the router's width
    experts_per_token: int
    expert_dim: int
    shared_dim: int
    first_expert: int
    experts_held: int
    routed_scale: float
    rope_theta: float
    rms_eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, positions, cache=(), page_table=None, rows=None,
                 last_row=None):
        """``x`` (B, L, D) float32; ``cache`` this layer's ``(k, v)`` (pages
        or ring) or ``()``.  Returns ``(x, cache, experts touched, local
        assignments)``."""
        B, L, D = x.shape
        H, KV, d, dt = self.num_heads, self.num_kv_heads, self.head_dim, \
            self.dtype
        Wn = self.window

        with jax.named_scope("lm.dense"):
            h = x.astype(dt)
            # the product as ONE (rows, H * d) array before its lanes are
            # split into heads: left to itself the chip's compiler splits
            # them inside the product, and for that transposes the whole
            # kernel on every step (100 MB a layer; PERF.md PR 36)
            q = lax.optimization_barrier(
                Proj(H * d, dt, name="q")(h)).reshape(B, L, H, d)
            k = lax.optimization_barrier(
                Proj(KV * d, dt, name="k")(h)).reshape(B, L, KV, d)
            v = Proj(KV * d, dt, name="v")(h).astype(dt)        # (B, L, KV*d)
            q = RMSNorm(self.rms_eps, name="q_norm")(q)
            k = RMSNorm(self.rms_eps, name="k_norm")(k)
            if self.rope:
                q = _rope(q, positions, self.rope_theta)
                k = _rope(k, positions, self.rope_theta)
            q, k = q.astype(dt), k.astype(dt).reshape(B, L, KV * d)

        if not cache:
            k_pos = positions
            select = window_visible(positions, k_pos, Wn) if Wn else \
                k_pos[:, None, :] <= positions[:, :, None]
        elif not Wn:
            with jax.named_scope("lm.cache_write"):
                ck, cv = (paged_write(slab, new, positions, page_table)
                          for slab, new in zip(cache, (k, v)))
            cache = (ck, cv)
            with jax.named_scope("lm.full_attn"):
                S = page_table.shape[1] * ck.shape[1]
                k, v = (slab[page_table].reshape(B, S, KV * d)
                        for slab in cache)
                # gathered slot s IS absolute position s
                select = jnp.arange(S) <= positions[:, :, None]
        else:
            ck, cv = cache
            own = jnp.arange(B, dtype=jnp.int32) if rows is None else rows
            # a sequence whose table names no page (a pad row, a warm-up
            # dispatch) writes the trash row, as it writes the trash page
            to = jnp.where(page_table[:, 0] > 0, own, ck.shape[0] - 1)
            if L == 1:
                # a step: write this position's row, read the slot's window
                with jax.named_scope("lm.cache_write"):
                    at = positions[:, 0] % Wn
                    ck, cv = ck.at[to, at].set(k[:, 0]), \
                        cv.at[to, at].set(v[:, 0])
                k_pos = ring_positions(positions[:, 0], Wn)
                with jax.named_scope("lm.window_attn"):
                    k, v = (ck[:B], cv[:B]) if rows is None else \
                        (ck[own], cv[own])
            else:
                # a prefill chunk: the ring as earlier chunks left it, then
                # the chunk's own rows; its last ``window`` real rows stay
                with jax.named_scope("lm.window_attn"):
                    old_k, old_v = ck[own], cv[own]
                    k_pos = jnp.concatenate(
                        [ring_positions(positions[:, 0] - 1, Wn), positions],
                        axis=1)
                with jax.named_scope("lm.cache_write"):
                    first = positions[:, 0]
                    last = first + (L - 1 if last_row is None else last_row)
                    src = ring_positions(last, Wn) - first[:, None]  # (B, Wn)
                    fresh = (src >= 0)[..., None]
                    src = jnp.clip(src, 0, L - 1)[..., None]
                    ck, cv = (
                        ring.at[to].set(jnp.where(
                            fresh, jnp.take_along_axis(new, src, axis=1),
                            old))
                        for ring, new, old in ((ck, k, old_k), (cv, v, old_v)))
                with jax.named_scope("lm.window_attn"):
                    k = jnp.concatenate([old_k, k], axis=1)
                    v = jnp.concatenate([old_v, v], axis=1)
            cache = (ck, cv)
            select = window_visible(positions, k_pos, Wn)

        with jax.named_scope("lm.window_attn" if Wn else "lm.full_attn"):
            S = k.shape[1]
            if L == 1:
                a = step_attention(q[:, 0], k, v, select[:, 0], KV)[:, None]
            else:
                a = masked_attention(q, k.reshape(B, S, KV, d),
                                     v.reshape(B, S, KV, d), select)
        with jax.named_scope("lm.dense"):
            x = x + RMSNorm(self.rms_eps, name="attn_out_norm")(
                Proj(D, dt, name="o")(a))

        zero = jnp.int32(0)
        if not self.sparse:
            with jax.named_scope("lm.dense"):
                y = GatedMLP(self.dense_dim, dt, name="mlp")(x)
                x = x + RMSNorm(self.rms_eps, name="mlp_out_norm")(y)
            return x, cache, zero, zero
        with jax.named_scope("lm.router"):
            ids, w = Router(self.num_experts, self.experts_per_token,
                            self.routed_scale, name="router")(x)
            here = (ids >= self.first_expert) \
                & (ids < self.first_expert + self.experts_held)
        with jax.named_scope("lm.experts"):
            y, touched = Experts(
                self.experts_held, self.expert_dim, dt, self.first_expert,
                name="experts")(
                    x.reshape(B * L, D), ids.reshape(B * L, -1),
                    w.reshape(B * L, -1))
        with jax.named_scope("lm.shared_expert"):
            y = y.reshape(B, L, D) + GatedMLP(self.shared_dim, dt,
                                              name="shared")(x)
        with jax.named_scope("lm.dense"):
            x = x + RMSNorm(self.rms_eps, name="mlp_out_norm")(y)
        return x, cache, touched, here.sum().astype(jnp.int32)


class WindowMoEDecoder(nn.Module):
    """Causal decoder of one :class:`WindowMoEBlock` a layer, its kinds read
    from ``layer_types`` and ``mlp_layer_types``."""

    vocab_size: int
    embed_dim: int = 6144
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, FULL)
    mlp_layer_types: Tuple[str, ...] = (DENSE, SPARSE, SPARSE, SPARSE)
    sliding_window: int = 128
    dense_dim: int = 18432
    num_experts: int = 128
    experts_per_token: int = 8
    expert_dim: int = 2048
    shared_dim: int = 2048
    first_expert: int = 0
    experts_held: Optional[int] = None        # all of them
    routed_scale: float = 2.5
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    max_len: int = 262144
    dtype: Any = jnp.float32

    def _kinds(self):
        if len(self.layer_types) != len(self.mlp_layer_types):
            raise ValueError("layer_types and mlp_layer_types differ in "
                             "length")
        for kind, known in ((self.layer_types, (WINDOW, FULL)),
                            (self.mlp_layer_types, (DENSE, SPARSE))):
            for name in kind:
                if name not in known:
                    raise ValueError(f"layer kind {name!r} is not one of "
                                     f"{known}")
        # (window or 0, RoPE, routed): rotary positions go with the window
        return [(self.sliding_window if a == WINDOW else 0, a == WINDOW,
                 m == SPARSE)
                for a, m in zip(self.layer_types, self.mlp_layer_types)]

    @nn.compact
    def __call__(self, tokens, positions=None, kv_cache=None,
                 page_table=None, logits_at=None, cache_rows=None):
        """Logits (B, L, vocab) in float32; with ``kv_cache`` = ``(paged,
        window)`` and its ``page_table``, ``(logits, cache)``.  ``logits_at``
        (B,) is each sequence's last real row: the head runs on it alone,
        (B, 1, vocab), and a window layer leaves the rows up to it in its
        ring."""
        B, L = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                         (B, L))
        if kv_cache is not None and page_table is None:
            raise ValueError("WindowMoEDecoder serves the paged layout only: "
                             "give a page_table with the cache")
        held = self.num_experts if self.experts_held is None \
            else self.experts_held
        with jax.named_scope("lm.dense"):
            x = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                         name="embed")(tokens).astype(F32)
        paged, ring = [], []
        touched = local = jnp.int32(0)
        for i, (window, rope, sparse) in enumerate(self._kinds()):
            state = () if kv_cache is None else kv_cache[1 if window else 0][i]
            x, state, n, m = WindowMoEBlock(
                self.num_heads, self.num_kv_heads, self.head_dim, window,
                rope, sparse, self.dense_dim, self.num_experts,
                self.experts_per_token, self.expert_dim, self.shared_dim,
                self.first_expert, held, self.routed_scale, self.rope_theta,
                self.rms_eps, self.dtype, name=f"layer_{i}")(
                    x, positions, state, page_table, cache_rows, logits_at)
            paged.append(() if window else state)
            ring.append(state if window else ())
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
        return logits, (tuple(paged), tuple(ring))

    def _zero_state(self, shape, windowed: bool):
        return tuple(
            (jnp.zeros(shape, self.dtype), jnp.zeros(shape, self.dtype))
            if bool(window) == windowed else ()
            for window, _, _ in self._kinds())

    def init_paged_cache(self, num_pages: int, page_size: int):
        """Zeroed pool slabs of the FULL layers: per layer ``(k, v)`` of
        ``(num_pages, page_size, num_kv_heads * head_dim)``, ``()`` for a
        window layer.  Page 0 is the trash page, as in
        ``TransformerEncoder.init_paged_cache``."""
        if num_pages < 2:
            raise ValueError(f"num_pages {num_pages} < 2: page 0 is the "
                             "reserved trash page")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        return self._zero_state(
            (num_pages, page_size, self.num_kv_heads * self.head_dim), False)

    def init_window_cache(self, rows: int):
        """Zeroed rings of the WINDOW layers for ``rows`` sequences: per
        layer ``(k, v)`` of ``(rows + 1, sliding_window, num_kv_heads *
        head_dim)``, the last row the trash row; ``()`` for a full layer.
        Its size does not depend on how long a sequence may grow."""
        if rows < 1:
            raise ValueError(f"rows must be >= 1, got {rows}")
        return self._zero_state(
            (rows + 1, self.sliding_window,
             self.num_kv_heads * self.head_dim), True)
