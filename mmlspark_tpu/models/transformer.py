"""Transformer encoder — the long-context model family.

Goes beyond the reference (whose only sequence model is a per-row BiLSTM,
SURVEY.md §5.7): a flax encoder whose attention can run dense, blockwise
(memory-efficient single device), or as ring attention over the ``seq`` mesh
axis for sequences longer than one device's HBM
(``parallel.ring_attention``).

Generative scoring (ISSUE 9): the encoder doubles as a causal LM
(``causal=True, pool="none", num_classes=vocab_size``) with an explicit
KV cache threaded through ``__call__(tokens, positions=..., kv_cache=...)``.
The cache is a plain pytree — one ``(k, v)`` pair of static-shape
``(batch, cache_len, heads, head_dim)`` slots per layer (``init_cache``) —
so prefill and the single-token decode step are ordinary pure functions the
``ModelRunner`` lowers ONCE each: the decode loop re-dispatches one compiled
executable per token instead of recompiling per step (the lower-once/
execute-many contract, PAPERS arxiv 1810.09868; the Gemma-on-TPU serving
comparison in PAPERS.md is the reference point for the shape of the cache).
Per-sequence write positions make ragged prompts exact: each sequence's new
k/v land at ITS next slot, and attention masks keys strictly by absolute
position, so padded prompt tails are overwritten before any real query can
attend to them (see docs/runner.md, "Decode correctness").

Paged decode (ISSUE 12): the same cached attention also runs over a PAGED
cache — pool slabs of ``(num_pages, page_size, heads * head_dim)``
(``init_paged_cache``) addressed through a per-sequence ``page_table``
(B, W) int32, the serving pattern the TPU-vs-GPU Gemma study in PAPERS.md
benchmarks.  Heads and head dimension are ONE minor axis (ISSUE 35): as two
axes, GPT-2 XL's ``(25, 64)`` were tiled to ``(32, 128)`` on the chip, and
every step and every join copied all 96 slabs of the pool into that padded
form and back, 46 ms of a 75.7 ms step on a v5e (PERF.md, PR 35); a row of
``heads * head_dim`` lanes is written in place.  The write scatters into
``(table[pos // ps], pos % ps)``; the read gathers each sequence's pages
back into position order, so gathered slot s is absolute position s and the
SAME strict ``s <= q_pos`` admissibility mask applies — prefill logits are
identical to the dense path.  A prefill splits the heads on that gathered
context; a decode step (one query row a sequence) leaves the context in its
merged lanes and lays the query out block-diagonally instead, because the
split is a relayout of the whole table width on the chip.  Page 0 is the reserved trash page: pad rows and any write whose
logical page is unallocated land there (unallocated table entries are 0)
and no real sequence is ever given it, so garbage writes cannot corrupt
live pages; pad-tail writes into a sequence's own allocated last page are
past its frontier and overwritten by decode steps before they become
admissible, the same argument as dense (docs/runner.md).
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp

from ..parallel import ring_attention as ra


def _cache_update(cache_kv, k_new, v_new, positions):
    """Scatter this call's per-token k/v into the dense cache slots.

    ``cache_kv`` = (k, v) each (B, S, H, D); ``k_new``/``v_new`` (B, L, H, D);
    ``positions`` (B, L) absolute slot per token — per-sequence, so ragged
    batches write each sequence at its own frontier."""
    ck, cv = cache_kv
    bidx = jnp.arange(ck.shape[0])[:, None]            # (B, 1)
    ck = ck.at[bidx, positions].set(k_new.astype(ck.dtype))
    cv = cv.at[bidx, positions].set(v_new.astype(cv.dtype))
    return ck, cv


def paged_write(slab, new, positions, page_table):
    """Scatter this call's per-token rows ``new`` (B, L, C) into the shared
    POOL slab (num_pages, page_size, C): the one paged write of ``models/``
    (keys, values, and ``sparse_moe``'s indexer keys).

    ``page_table`` (B, W) int32 maps a sequence's logical page j (absolute
    positions [j*page_size, (j+1)*page_size)) to its physical pool page.
    Unallocated table entries are 0, the reserved trash page, so pad rows
    and pad-tail prompt positions write garbage into a page no real
    sequence ever reads.

    Offset-prefill contract (ISSUE 20): ``positions`` need not start at 0
    — a prefix-cache hit prefills only the uncached suffix with positions
    offset past the shared prefix, against a table already naming the
    cached pages.  Positions whose logical page falls PAST the table's
    width are routed to the trash page explicitly: a raw gather would
    clamp them to column W-1, and under prefix sharing that column's page
    can be live shared state owned by other sequences.

    ``C`` is ONE minor axis so that the scatter writes a row in place (two
    minor axes had the chip copy the whole slab in and out: see the module
    header)."""
    page_size, W = slab.shape[1], page_table.shape[1]
    bidx = jnp.arange(page_table.shape[0])[:, None]    # (B, 1)
    logical = positions // page_size                   # (B, L) logical page
    phys = jnp.where(logical < W,                      # (B, L) physical page
                     page_table[bidx, jnp.minimum(logical, W - 1)], 0)
    slot = positions % page_size                       # (B, L) slot in page
    return slab.at[phys, slot].set(new.astype(slab.dtype))


class MultiHeadAttention(nn.Module):
    num_heads: int
    head_dim: int
    attention_mode: str = "dense"      # dense | blockwise | ring
    causal: bool = False
    block_size: int = 512
    seq_axis: str = "seq"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, page_table=None):
        B, L, _ = x.shape
        H, D = self.num_heads, self.head_dim
        qkv = nn.Dense(3 * H * D, dtype=self.dtype, name="qkv")(x)
        if kv_cache is not None:
            # KV-cached path (prefill when L = prompt bucket, decode when
            # L = 1).  Dense only: blockwise/ring tile over the query axis
            # and cannot address per-sequence cache slots.
            if self.attention_mode != "dense":
                raise ValueError(
                    "kv_cache requires attention_mode='dense' (got "
                    f"{self.attention_mode!r}); blockwise/ring serve the "
                    "full-sequence paths only")
            if positions is None:
                raise ValueError("kv_cache requires explicit positions")
            q, k, v = jnp.split(qkv.reshape(B, L, 3, H, D), 3, axis=2)
            q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]   # (B, L, H, D)
            # a decode step over the paged cache attends in the slab's own
            # merged lanes (below); every other call splits the heads
            merged = page_table is not None and L == 1
            if page_table is not None:
                # paged path: k/v rows, heads merged into the lanes, land in
                # pool pages addressed through the table; the read gathers
                # each sequence's pages back into (B, W*page_size, C), where
                # gathered slot s IS absolute position s (logical page j
                # covers [j*ps, (j+1)*ps)), so the admissibility mask below
                # is identical to dense.  The heads are split on the
                # GATHERED context, never on the pool.
                ck, cv = (paged_write(slab, new.reshape(B, L, H * D),
                                      positions, page_table)
                          for slab, new in zip(kv_cache, (k, v)))
                S = page_table.shape[1] * ck.shape[1]
                keys, vals = (slab[page_table].reshape(B, S, H * D)
                              for slab in (ck, cv))
                if not merged:
                    keys, vals = (c.reshape(B, S, H, D) for c in (keys, vals))
            else:
                ck, cv = _cache_update(kv_cache, k, v, positions)
                keys, vals = ck, cv
            if merged:
                # one query row a sequence: splitting the (B, S, C) context
                # into heads is a relayout of all of it on the chip (41% of
                # the device's busy time, PERF.md PR 35), so the QUERY is
                # laid out instead, block-diagonally as (C, H): lane c
                # belongs to head c // D, every other entry is an exact
                # zero, and scores and output are plain matrix products
                # over the context as gathered.  Same products, same sums.
                own = jnp.arange(H * D)[:, None] // D == jnp.arange(H)
                s = jnp.einsum("bsc,bch->bhs", keys, jnp.where(
                    own, q.reshape(B, H * D, 1), 0))[:, :, None]
            else:
                s = jnp.einsum("blhd,bshd->bhls", q, keys)
            s = s / jnp.sqrt(D)
            # keys admissible strictly by absolute position: slot s serves
            # query l iff s <= positions[b, l].  Slots past a sequence's
            # frontier hold zeros or stale pad-token k/v, but every decode
            # step writes its token at the frontier BEFORE attending, so
            # admissible slots are always freshly written.  (Paged: slots
            # whose logical page is unallocated sit past every frontier by
            # construction, so the trash page is never admissible.)
            key_pos = jnp.arange(keys.shape[1])[None, None, None, :]
            admissible = key_pos <= positions[:, None, :, None]
            p = nn.softmax(jnp.where(admissible, s, -1e30), axis=-1)
            if merged:
                # (B, H, C): head h's mix of every lane; its own D are kept
                out = jnp.einsum("bhs,bsc->bhc", p[:, :, 0],
                                 vals.astype(p.dtype))
                out = jnp.where(own.T, out, 0).sum(1)[:, None]
            else:
                out = jnp.einsum("bhls,bshd->blhd", p, vals.astype(p.dtype))
                out = out.reshape(B, L, H * D)
            return nn.Dense(x.shape[-1], dtype=self.dtype,
                            name="proj")(out), (ck, cv)
        q, k, v = jnp.split(qkv.reshape(B, L, 3, H, D).transpose(2, 0, 3, 1, 4), 3)
        q, k, v = q[0], k[0], v[0]                    # (B, H, L, D)
        if self.attention_mode == "ring":
            # inside shard_map the seq axis name is live; outside it falls
            # back to blockwise
            try:
                out = ra.ring_attention(q, k, v, axis_name=self.seq_axis,
                                        causal=self.causal)
            except NameError:
                out = ra.blockwise_attention(q, k, v, self.block_size, self.causal)
        elif self.attention_mode == "blockwise":
            out = ra.blockwise_attention(q, k, v, self.block_size, self.causal)
        else:
            s = (q @ k.swapaxes(-1, -2)) / jnp.sqrt(D)
            if self.causal:
                mask = jnp.tril(jnp.ones((L, L), bool))
                s = jnp.where(mask, s, -1e30)
            out = jnp.einsum("bhqk,bhkd->bhqd", nn.softmax(s, axis=-1), v)
        out = out.transpose(0, 2, 1, 3).reshape(B, L, H * D)
        return nn.Dense(x.shape[-1], dtype=self.dtype, name="proj")(out)


class EncoderBlock(nn.Module):
    num_heads: int
    head_dim: int
    mlp_dim: int
    attention_mode: str = "dense"
    causal: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, page_table=None):
        h = nn.LayerNorm(dtype=self.dtype)(x)
        attn = MultiHeadAttention(self.num_heads, self.head_dim,
                                  self.attention_mode, self.causal,
                                  dtype=self.dtype)
        if kv_cache is not None:
            h, kv_cache = attn(h, positions=positions, kv_cache=kv_cache,
                               page_table=page_table)
        else:
            h = attn(h)
        x = x + h
        h = nn.LayerNorm(dtype=self.dtype)(x)
        h = nn.Dense(self.mlp_dim, dtype=self.dtype)(h)
        h = nn.gelu(h)
        h = nn.Dense(x.shape[-1], dtype=self.dtype)(h)
        x = x + h
        return (x, kv_cache) if kv_cache is not None else x


class TransformerEncoder(nn.Module):
    """Token transformer; ``features=True`` returns per-token embeddings."""

    vocab_size: int
    num_classes: int = 2
    embed_dim: int = 256
    num_heads: int = 4
    num_layers: int = 4
    mlp_dim: int = 512
    max_len: int = 32768
    attention_mode: str = "dense"
    causal: bool = False
    pool: str = "mean"                 # mean | none (per-token)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = False, features: bool = False,
                 positions=None, kv_cache=None, page_table=None,
                 logits_at=None):
        B, L = tokens.shape
        x = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype)(tokens)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, self.max_len, self.embed_dim))
        if positions is not None:
            # explicit global positions: required under sequence parallelism
            # (local shard starts at axis_index * L_local) and under KV-cached
            # decode (each sequence's token sits at its own frontier)
            x = x + jnp.take(pos[0], positions, axis=0).astype(self.dtype)
        else:
            x = x + pos[:, :L].astype(self.dtype)
        head_dim = self.embed_dim // self.num_heads
        new_cache = []
        for i in range(self.num_layers):
            block = EncoderBlock(self.num_heads, head_dim, self.mlp_dim,
                                 self.attention_mode, self.causal,
                                 dtype=self.dtype, name=f"block_{i}")
            if kv_cache is not None:
                x, layer_kv = block(x, positions=positions,
                                    kv_cache=kv_cache[i],
                                    page_table=page_table)
                new_cache.append(layer_kv)
            else:
                x = block(x)
        if logits_at is not None:
            # (B,) one row a sequence (a prefill's last real position): the
            # final norm and the head run on it alone, (B, 1, C) out
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        if features:
            x = x.astype(jnp.float32)
            return (x, tuple(new_cache)) if kv_cache is not None else x
        if self.pool == "mean" and kv_cache is None:
            x = x.mean(axis=1)
        logits = nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x)
        logits = logits.astype(jnp.float32)  # (B, C) / (B, L, C) pool="none"
        return (logits, tuple(new_cache)) if kv_cache is not None else logits

    def init_cache(self, batch: int, cache_len: int):
        """Zeroed KV-cache pytree: ``num_layers`` pairs of static-shape
        ``(batch, cache_len, heads, head_dim)`` slots.  Plain data, no
        params — build it host-side once per decode signature and thread it
        through ``__call__(..., kv_cache=...)``.  ``cache_len`` bounds
        prompt + generated tokens and is part of the compile signature."""
        if cache_len > self.max_len:
            raise ValueError(f"cache_len {cache_len} exceeds max_len "
                             f"{self.max_len} (positional table bound)")
        head_dim = self.embed_dim // self.num_heads
        shape = (batch, cache_len, self.num_heads, head_dim)
        return tuple((jnp.zeros(shape, self.dtype), jnp.zeros(shape, self.dtype))
                     for _ in range(self.num_layers))

    def init_paged_cache(self, num_pages: int, page_size: int):
        """Zeroed PAGED KV-cache pytree: ``num_layers`` pairs of
        ``(num_pages, page_size, heads * head_dim)`` pool slabs, shared by
        every sequence through a per-sequence page table (see
        ``models/runner.py::PagePool``).  Heads and head dimension are one
        minor axis, as in ``SparseMoEDecoder.init_paged_cache``, so that
        ``paged_write`` updates a page in place (module header).  Page 0 is
        reserved as the trash page for pad rows and pad-tail prompt writes,
        so a usable pool needs ``num_pages >= 2``.  Unlike ``init_cache``, the pool is sized
        by TOTAL tokens across sequences, not ``batch * cache_len`` — the
        memory model that lets concurrency scale with actual lengths."""
        if num_pages < 2:
            raise ValueError(f"num_pages {num_pages} < 2: page 0 is the "
                             "reserved trash page, so a usable pool needs "
                             "at least one allocatable page")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        head_dim = self.embed_dim // self.num_heads
        shape = (num_pages, page_size, self.num_heads * head_dim)
        return tuple((jnp.zeros(shape, self.dtype), jnp.zeros(shape, self.dtype))
                     for _ in range(self.num_layers))
