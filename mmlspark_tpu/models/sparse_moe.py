"""A decoder with a routed expert layer and learned sparse attention, served
through the same paged decode path as ``TransformerEncoder`` (ISSUE 34).

One block, every layer the same (sizes are fields, not a class per family):

    x^ = RMSNorm(x)
    q, k, v = x^ Wq, x^ Wk, x^ Wv          grouped-query: ``num_kv_heads`` K/V
    q, k = RoPE(RMSNorm_head(q)), RoPE(RMSNorm_head(k))       rotate-half
    indexer:  qI = RoPE(x^ W_Iq) (index_heads, index_dim);  wI = x^ W_Iw / sqrt(
              index_heads * index_dim);  kI = RoPE(LayerNorm(x^ W_Ik)), one head
              I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s])      for s <= t
              S_t = the ``index_topk`` positions s <= t of largest I[t, s]
    a_h = softmax_{s in S_t}(q_h . k_{h // group, s} / sqrt(head_dim)) v;  x += a Wo
    x^ = RMSNorm(x);  p = softmax(x^ Wr);  T = top-k(p);  w_e = p_e / sum_T p
    x += sum_{e in T} w_e (silu(x^ Wg_e) * (x^ Wu_e)) Wd_e
    logits = RMSNorm(x_L) W_head                     untied, no bias

DeepSeek Sparse Attention's lightning indexer and per-token top-k selection
laid over grouped-query attention, on a block with a routed expert layer and
no dense MLP.  Weights and cache are ``dtype`` (bfloat16 when served); what
decides something is float32: the residual stream, RMSNorm's statistics, the
router's product, softmax and top-k, the indexer's scores and the selection,
attention's softmax.

Cache (``init_paged_cache``): per layer ``(k, v, index_k)`` — K and V as
``(pages, page_size, num_kv_heads * head_dim)`` so that the KV heads are not
a padded tile dimension, and the indexer's keys ``(pages, page_size,
index_dim zero-padded to whole 128-lane rows)``: at 64 lanes the chip's
compiler kept the slab in a layout of its own and copied the WHOLE slab four
times a layer on every join (PERF.md, PR 34).  A decode step scores the
indexer's keys over the whole table width and reads K/V for the selected
positions only.  A prefill chunk (more than one query row) computes the same
selected-set attention as a masked product over its context: the selection
is the same exact top-k (``_topk_mask`` breaks ties at the threshold by
position, as ``lax.top_k`` does), never an approximation.

The expert layer routes over all experts, drops no token whatever the
imbalance, and multiplies only experts that have tokens: assignments are
sorted by expert, each expert's rows run through its three matrices in
blocks of ``block_rows``, and a ``while`` loop of as many turns as there are
non-empty blocks slices one expert's weights a turn.  No capacity factor, no
auxiliary loss.  The number of distinct experts with a token, summed over
the layers, is sown as ``intermediates/experts_touched`` (the decode step
hands it to the engine beside the tokens).

Device phases are ``jax.named_scope``s ``lm.dense`` (embedding, q/k/v/o,
norms), ``lm.indexer``, ``lm.cache_write``, ``lm.select``, ``lm.sparse_attn``,
``lm.router``, ``lm.experts``, ``lm.head``.
"""
from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .transformer import paged_write

F32 = jnp.float32
_NEG = -jnp.inf


def _dot(a, b):
    """``a @ b`` over the last/first axis with a float32 result."""
    return jnp.einsum("...d,df->...f", a, b, preferred_element_type=F32)


def _rms(x, scale, eps):
    """RMSNorm over the last axis, statistics in float32; float32 out."""
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, positions, theta):
    """Rotate-half rotary positions over the last axis of ``x`` (B, L, n, d)
    at absolute ``positions`` (B, L); float32 in and out."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[..., None, None] * inv       # (B, L, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _lanes(n: int) -> int:
    """``n`` rounded up to whole 128-lane rows."""
    return -(-n // 128) * 128


def _to_lanes(x):
    """``x`` with its last axis zero-padded to whole 128-lane rows."""
    pad = _lanes(x.shape[-1]) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def index_scores(q_i, w_i, k_i):
    """The indexer's scores ``I`` (B, L, S) in float32 from its queries
    ``q_i`` (B, L, J, d), head weights ``w_i`` (B, L, J) and keys ``k_i``
    (B, S, d).  One query row a sequence (a decode step) reads the keys
    once; a chunk of rows adds one head at a time, so that no (J, L, S)
    array is ever made."""
    if q_i.shape[1] == 1:
        s = jnp.einsum("bljd,bsd->bljs", q_i, k_i, preferred_element_type=F32)
        return jnp.einsum("blj,bljs->bls", w_i, jax.nn.relu(s))

    def one_head(acc, head):
        q, w = head                                   # (B, L, d), (B, L)
        s = jnp.einsum("bld,bsd->bls", q, k_i, preferred_element_type=F32)
        return acc + w[..., None] * jax.nn.relu(s), None

    acc = jnp.zeros(q_i.shape[:2] + (k_i.shape[1],), F32)
    acc, _ = lax.scan(one_head, acc, (jnp.moveaxis(q_i, 2, 0),
                                      jnp.moveaxis(w_i, 2, 0)))
    return acc


def _kth_largest(x, k):
    """The ``k``-th largest of each row of ``x`` (..., S) float32 (no NaN),
    exactly, by 32 counting passes over an order-preserving integer image of
    the floats: bit after bit, the largest value that at least ``k``
    elements reach.  A prefill chunk needs the selection's THRESHOLD, not
    its order; a sort of 512 x 33,600 scores a layer was three fifths of a
    join (PERF.md, PR 34)."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def one_bit(i, best):
        trial = best | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = (key >= trial[..., None]).sum(-1) >= k
        return jnp.where(enough, trial, best)

    best = lax.fori_loop(0, 32, one_bit, jnp.zeros(x.shape[:-1], jnp.uint32))
    bits = jnp.where(best >> 31 == 1, best & jnp.uint32((1 << 31) - 1), ~best)
    return lax.bitcast_convert_type(bits, F32)[..., None]


def _topk_mask(scores, k):
    """Which of ``scores`` (..., S) are among the ``k`` largest of their row:
    exactly ``lax.top_k``'s set (equal scores go to the lower position),
    as a mask.  Scores of ``-inf`` are never selected."""
    if k >= scores.shape[-1]:
        return scores > _NEG
    scores = scores + 0.0                       # -0.0 and 0.0 are one value
    kth = _kth_largest(scores, k)
    above, level = scores > kth, scores == kth
    room = k - above.sum(-1, keepdims=True)
    rank = jnp.cumsum(level, axis=-1, dtype=jnp.int32)
    return (above | (level & (rank <= room))) & (scores > _NEG)


def masked_attention(q, k, v, select):
    """Attention of ``q`` (B, L, H, d) over ``k``, ``v`` (B, S, KV, d) where
    ``select`` (B, L, S) holds; one K/V head's group of query heads a turn,
    so that the float32 scores held at once are (B, H / KV, L, S)."""
    B, L, H, d = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, L, KV, H // KV, d)

    def groups(qh, kh, vh):         # (B, L, n, G, d), (B, S, n, d) twice
        s = jnp.einsum("blngd,bsnd->bngls", qh, kh,
                       preferred_element_type=F32) / jnp.sqrt(F32(d))
        p = jax.nn.softmax(jnp.where(select[:, None, None], s, _NEG), axis=-1)
        return jnp.einsum("bngls,bsnd->blngd", p.astype(vh.dtype), vh,
                          preferred_element_type=F32)

    if L == 1:
        return groups(qg, k, v).reshape(B, L, H * d)
    _, out = lax.scan(
        lambda _, g: (None, groups(*(a[:, :, None] for a in g))), None,
        tuple(jnp.moveaxis(a, 2, 0) for a in (qg, k, v)))
    return jnp.moveaxis(out[:, :, :, 0], 0, 2).reshape(B, L, H * d)


def routed_experts(x, gate, up, down, expert_ids, weights, block_rows=64,
                   first=0):
    """``sum_k weights[t, k] * FFN_{expert_ids[t, k]}(x[t])`` for ``x`` (T, D):
    float32 (T, D), and the number of experts that had a token.

    Assignments are sorted by expert; each expert's rows go through its
    matrices in blocks of ``block_rows``; the loop takes one turn a
    NON-EMPTY block, so an expert without a token is never read and no
    token is dropped whatever the imbalance.

    The stacked matrices may be ONE CHIP'S SHARE of a wider router (ISSUE
    36): they are experts ``first ... first + E - 1`` and ``expert_ids`` run
    over the router's whole width.  An assignment to an expert not held
    sorts behind every held one, no block serves it and it adds nothing: the
    sum is this share's part of the layer's result, and with no assignment
    landing here no expert is read at all.  With every expert held nothing
    sorts behind and the sums are bit for bit those of the layer before it
    took a share."""
    T, D = x.shape
    k, E = expert_ids.shape[1], gate.shape[0]
    A, Tb = T * k, min(T, block_rows)
    flat = expert_ids.reshape(A) - first
    flat = jnp.where((flat >= 0) & (flat < E), flat, E)      # E: not held
    sizes = jnp.zeros(E + 1, jnp.int32).at[flat].add(1)[:E]
    order = jnp.argsort(flat, stable=True)
    ends = jnp.cumsum(sizes)
    blocks = (sizes + Tb - 1) // Tb                  # of each expert
    block_ends = jnp.cumsum(blocks)
    b = jnp.arange(A // Tb + E)                      # never fewer than there are
    owner = jnp.minimum(jnp.searchsorted(block_ends, b, side="right",
                                         method="compare_all"),
                        E - 1).astype(jnp.int32)
    row0 = ends[owner] - sizes[owner] \
        + (b - (block_ends[owner] - blocks[owner])) * Tb
    xs = jnp.concatenate([x[order // k], jnp.zeros((Tb, D), x.dtype)])

    def one_block(i, ys):
        e, r0 = owner[i], row0[i]
        rows = lax.dynamic_slice(xs, (r0, 0), (Tb, D))
        w_g, w_u, w_d = (lax.dynamic_index_in_dim(w, e, 0, keepdims=False)
                         for w in (gate, up, down))
        h = jax.nn.silu(_dot(rows, w_g)) * _dot(rows, w_u)
        y = _dot(h.astype(x.dtype), w_d)
        mine = (r0 + jnp.arange(Tb) < ends[e])[:, None]
        old = lax.dynamic_slice(ys, (r0, 0), (Tb, D))
        return lax.dynamic_update_slice(ys, jnp.where(mine, y, old), (r0, 0))

    ys = lax.fori_loop(0, block_ends[-1], one_block,
                       jnp.zeros((A + Tb, D), F32))
    per_choice = ys[jnp.argsort(order)].reshape(T, k, D)
    return jnp.einsum("tk,tkd->td", weights.astype(F32), per_choice), \
        (sizes > 0).sum().astype(jnp.int32)


class Proj(nn.Module):
    """``x @ kernel`` with operands in ``dtype`` and a float32 result: no
    bias, and no rounding of the product back to ``dtype`` (a bfloat16
    logit near 4 would move by up to 0.016)."""
    features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (x.shape[-1], self.features))
        return _dot(x.astype(self.dtype), kernel.astype(self.dtype))


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        return _rms(x, self.param("scale", nn.initializers.ones,
                                  (x.shape[-1],)), self.eps)


class Experts(nn.Module):
    """The stacked matrices of the routed experts a layer holds:
    ``num_experts`` of them, from ``first_expert`` of the router's width
    on."""
    num_experts: int
    expert_dim: int
    dtype: Any = jnp.float32
    first_expert: int = 0

    @nn.compact
    def __call__(self, x, expert_ids, weights):
        E, D, Fe = self.num_experts, x.shape[-1], self.expert_dim
        init = nn.initializers.normal(0.02)
        gate = self.param("gate", init, (E, D, Fe))
        up = self.param("up", init, (E, D, Fe))
        down = self.param("down", init, (E, Fe, D))
        return routed_experts(x.astype(self.dtype), gate.astype(self.dtype),
                              up.astype(self.dtype), down.astype(self.dtype),
                              expert_ids, weights, first=self.first_expert)


class SparseMoEBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    experts_per_token: int
    expert_dim: int
    index_heads: int
    index_dim: int
    index_topk: int
    rope_theta: float
    rms_eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, page_table=None):
        """``x`` (B, L, D) float32 residual stream; returns ``(x, cache,
        experts touched)``, ``cache`` as given (``None`` without one)."""
        B, L, D = x.shape
        H, KV, d = self.num_heads, self.num_kv_heads, self.head_dim
        J, di, dt = self.index_heads, self.index_dim, self.dtype

        def dense(n, name):
            return Proj(n, dt, name=name)

        with jax.named_scope("lm.dense"):
            h = RMSNorm(self.rms_eps, name="attn_norm")(x).astype(dt)
            q = dense(H * d, "q")(h).reshape(B, L, H, d)
            k = dense(KV * d, "k")(h).reshape(B, L, KV, d)
            v = dense(KV * d, "v")(h).astype(dt)                # (B, L, KV*d)
            q = _rope(RMSNorm(self.rms_eps, name="q_norm")(q), positions,
                      self.rope_theta).astype(dt)
            k = _rope(RMSNorm(self.rms_eps, name="k_norm")(k), positions,
                      self.rope_theta).astype(dt).reshape(B, L, KV * d)
        with jax.named_scope("lm.indexer"):
            q_i = _rope(dense(J * di, "index_q")(h).reshape(B, L, J, di),
                        positions, self.rope_theta).astype(dt)
            k_i = nn.LayerNorm(epsilon=self.rms_eps, dtype=F32,
                               name="index_k_norm")(dense(di, "index_k")(h))
            k_i = _rope(k_i[:, :, None], positions,
                        self.rope_theta)[:, :, 0].astype(dt)    # (B, L, di)
            w_i = dense(J, "index_w")(h) * (J * di) ** -0.5

        if page_table is not None:
            with jax.named_scope("lm.cache_write"):
                ck, cv, ci = (paged_write(slab, new, positions, page_table)
                              for slab, new in zip(
                                  kv_cache, (k, v, _to_lanes(k_i))))
            kv_cache = (ck, cv, ci)
            W, ps = page_table.shape[1], ck.shape[1]
            with jax.named_scope("lm.indexer"):
                # the keys as the slab holds them, in whole lanes; the
                # queries' padding multiplies the keys' zeros
                ctx_i = ci[page_table].reshape(B, W * ps, ci.shape[-1])
                q_i = _to_lanes(q_i)
        elif kv_cache is not None:
            raise ValueError("SparseMoEDecoder serves the paged layout only: "
                             "give a page_table with the cache")
        else:
            ctx_i = k_i
        # slot s of the context IS absolute position s (the paged read puts
        # pages back in position order), so admissibility is s <= position
        with jax.named_scope("lm.indexer"):
            scores = index_scores(q_i, w_i, ctx_i)               # (B, L, S)
            scores = jnp.where(jnp.arange(ctx_i.shape[1])
                               <= positions[..., None], scores, _NEG)
        topk = min(self.index_topk, ctx_i.shape[1])
        if page_table is not None and L == 1:
            # a decode step: K/V of the selected positions only
            with jax.named_scope("lm.select"):
                best, at = lax.top_k(scores[:, 0], topk)         # (B, topk)
            with jax.named_scope("lm.sparse_attn"):
                phys = jnp.take_along_axis(page_table, at // ps, axis=1)
                ks = ck[phys, at % ps].reshape(B, topk, KV, d)
                vs = cv[phys, at % ps].reshape(B, topk, KV, d)
                a = masked_attention(q, ks, vs, (best > _NEG)[:, None])
        else:
            with jax.named_scope("lm.select"):
                select = _topk_mask(scores, topk)
            with jax.named_scope("lm.sparse_attn"):
                if page_table is not None:
                    k = ck[page_table].reshape(B, W * ps, KV * d)
                    v = cv[page_table].reshape(B, W * ps, KV * d)
                S = k.shape[1]
                a = masked_attention(q, k.reshape(B, S, KV, d),
                                     v.reshape(B, S, KV, d), select)
        with jax.named_scope("lm.dense"):
            x = x + dense(D, "o")(a)
            h = RMSNorm(self.rms_eps, name="mlp_norm")(x)
        with jax.named_scope("lm.router"):
            r = self.param("router", nn.initializers.normal(0.02),
                           (D, self.num_experts))
            p = jax.nn.softmax(jnp.dot(h, r.astype(F32),
                                       precision=lax.Precision.HIGHEST), -1)
            w, ids = lax.top_k(p, self.experts_per_token)        # (B, L, k)
            w = w / w.sum(-1, keepdims=True)
        with jax.named_scope("lm.experts"):
            y, touched = Experts(self.num_experts, self.expert_dim, dt,
                                 name="experts")(
                h.reshape(B * L, D), ids.reshape(B * L, -1),
                w.reshape(B * L, -1))
        return x + y.reshape(B, L, D), kv_cache, touched


class SparseMoEDecoder(nn.Module):
    """Causal decoder of ``num_layers`` :class:`SparseMoEBlock`s."""

    vocab_size: int
    embed_dim: int = 2048
    num_layers: int = 4
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    experts_per_token: int = 8
    expert_dim: int = 768
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048
    rope_theta: float = 1e7
    rms_eps: float = 1e-6
    max_len: int = 262144
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, positions=None, kv_cache=None,
                 page_table=None, logits_at=None):
        """Logits (B, L, vocab) in float32; with ``kv_cache`` (and its
        ``page_table``) ``(logits, cache)``.  ``logits_at`` (B,) applies the
        head to that one row of each sequence: (B, 1, vocab)."""
        B, L = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                         (B, L))
        with jax.named_scope("lm.dense"):
            x = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                         name="embed")(tokens).astype(F32)
        new_cache, touched = [], jnp.int32(0)
        for i in range(self.num_layers):
            x, layer_cache, n = SparseMoEBlock(
                self.num_heads, self.num_kv_heads, self.head_dim,
                self.num_experts, self.experts_per_token, self.expert_dim,
                self.index_heads, self.index_dim, self.index_topk,
                self.rope_theta, self.rms_eps, self.dtype,
                name=f"layer_{i}")(
                    x, positions,
                    None if kv_cache is None else kv_cache[i], page_table)
            new_cache.append(layer_cache)
            touched = touched + n
        self.sow("intermediates", "experts_touched", touched)
        with jax.named_scope("lm.head"):
            if logits_at is not None:
                x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
            logits = Proj(self.vocab_size, self.dtype, name="head")(
                RMSNorm(self.rms_eps, name="final_norm")(x))
        return (logits, tuple(new_cache)) if kv_cache is not None else logits

    def init_paged_cache(self, num_pages: int, page_size: int):
        """Zeroed pool slabs: per layer ``(k, v, index_k)``, K and V
        ``(num_pages, page_size, num_kv_heads * head_dim)`` and the indexer's
        keys ``(num_pages, page_size, index_dim in whole 128-lane rows)``.
        Page 0 is the trash page, as in
        ``TransformerEncoder.init_paged_cache``."""
        if num_pages < 2:
            raise ValueError(f"num_pages {num_pages} < 2: page 0 is the "
                             "reserved trash page")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        kv = (num_pages, page_size, self.num_kv_heads * self.head_dim)
        ix = (num_pages, page_size, _lanes(self.index_dim))
        return tuple((jnp.zeros(kv, self.dtype), jnp.zeros(kv, self.dtype),
                      jnp.zeros(ix, self.dtype))
                     for _ in range(self.num_layers))
