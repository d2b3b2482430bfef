"""Plain reference for a decoder with a routed expert layer and learned sparse
attention, and the comparison that decides ``correct`` for one served over
contexts too long for a whole table of logits.

``sparse_moe_forward`` is the forward pass of the language model of
Keye-VL-2.0-30B-A3B (``huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B``,
``config.json``) over ONE sequence, in plain ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no cache, no batching, no flax
module, nothing imported from the program.  Per layer (every layer the same):

    x^ = RMSNorm(x);  q, k, v = x^ Wq, x^ Wk, x^ Wv     (H, d), (KV, d), (KV, d)
    q, k = RoPE(RMSNorm_head(q)), RoPE(RMSNorm_head(k))      rotate-half, theta
    qI = RoPE(x^ W_Iq) (J, dI);  kI = RoPE(LayerNorm(x^ W_Ik)) (dI,)
    wI = (x^ W_Iw) * J^-1/2 * dI^-1/2
    I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s])  for s <= t
    S_t = the topk positions s <= t of largest I[t, s]  (all while t < topk)
    a_h = softmax_{s in S_t}(q_h . k_{h // (H/KV), s} / sqrt(d)) v;  x += a Wo
    x^ = RMSNorm(x);  p = softmax(x^ Wr);  T = top-k(p);  w_e = p_e / sum_T p
    x += sum_{e in T} w_e (silu(x^ Wg_e) * (x^ Wu_e)) Wd_e
    logits = RMSNorm(x_L) W_head

Departures from the published model, each also in the configuration file:
the depth is the configuration's (6 of 48 layers: one chip's stage of an
eight-stage pipeline, with the embedding and the head); there is no vision
tower (text tokens carry equal t/h/w position ids, so the multimodal RoPE
sections ARE one-dimensional RoPE); and the conventions the config leaves
open are the families': per-head RMSNorm on q and k (Qwen3), RoPE on all of
the indexer's dimensions, a LayerNorm with scale and bias on its key, and
the scaling of its head weights (DeepSeek's released indexer).  The
config's ``q_chunk_size`` / ``kv_chunk_size`` are tile sizes and change no
result.

It runs layer by layer with one layer's float32 weights at a time; ``I`` and
attention are evaluated in blocks of ``query_block`` rows against the
positions up to the block's end (in steps of ``context_step``), each row's
selection the exact set ``lax.top_k`` takes (found by counting, ``top_k_set``:
a sort a row does not fit a run) and its attention masked to that set; every
expert is
applied to exactly its tokens (gathered by index; the lists are padded to
one length with a zero row of weight 0); and logits come back for the rows
``[rows[0], rows[0] + rows[1])`` only.  A ``rounding`` other than ``None``
computes the same pass with every matmul operand rounded to a lower
precision: the control of "How correct is decided", never run by a
benchmark run.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _round_fp8(x):
    """``x`` as float8 (e4m3) would hold it under one scale per tensor, back
    in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


ROUNDINGS = {None: lambda x: x, "fp8": _round_fp8}


def _dot(a, b, rounding):
    r = ROUNDINGS[rounding]
    return r(a) @ r(b)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _rope(x, theta):
    """Rotate-half RoPE of ``x`` (T, n, d), row ``t`` at position ``t``."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


_STATIC = ("num_heads", "num_kv_heads", "head_dim", "index_heads",
           "index_dim", "rope_theta", "eps", "rounding")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _project(x, p, *, num_heads, num_kv_heads, head_dim, index_heads,
             index_dim, rope_theta, eps, rounding):
    """Everything of a layer that is a product with ``x^``: q, k, v and the
    indexer's queries, key and head weights, for every row."""
    p = _f32(p)
    t = x.shape[0]
    h = _rms_norm(x, p["attn_norm"]["scale"], eps)

    def proj(name, n, d):
        return _dot(h, p[name]["kernel"], rounding).reshape(t, n, d)

    q = _rope(_rms_norm(proj("q", num_heads, head_dim),
                        p["q_norm"]["scale"], eps), rope_theta)
    k = _rope(_rms_norm(proj("k", num_kv_heads, head_dim),
                        p["k_norm"]["scale"], eps), rope_theta)
    v = proj("v", num_kv_heads, head_dim)
    q_i = _rope(proj("index_q", index_heads, index_dim), rope_theta)
    k_i = _rope(_layer_norm(proj("index_k", 1, index_dim),
                            p["index_k_norm"]["scale"],
                            p["index_k_norm"]["bias"], eps), rope_theta)[:, 0]
    w_i = _dot(h, p["index_w"]["kernel"], rounding) \
        * (index_heads * index_dim) ** -0.5
    return q, k, v, q_i, k_i, w_i


def _kth_largest(x, k):
    """The ``k``-th largest of each row of ``x`` (R, S), exactly and with no
    sort: floats compare as the integers ``key`` below do, and the largest
    integer that at least ``k`` elements of a row reach is found bit by bit,
    32 counts in all.  (A sort of every row of a 33k x 33k table of scores
    took most of this reference's time.)"""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    best = jnp.zeros(x.shape[0], jnp.uint32)
    for bit in range(31, -1, -1):
        trial = best | jnp.uint32(1 << bit)
        best = jnp.where((key >= trial[:, None]).sum(-1) >= k, trial, best)
    bits = jnp.where(best >> 31 == 1, best & jnp.uint32((1 << 31) - 1), ~best)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)[:, None]


def top_k_set(scores, k):
    """The set ``jax.lax.top_k(scores, k)`` selects in each row of ``scores``
    (R, S), as a mask: the elements above the ``k``-th largest value and, of
    those equal to it, the first by position that still fit."""
    scores = scores + 0.0                       # -0.0 and 0.0 are one value
    kth = _kth_largest(scores, k)
    above, level = scores > kth, scores == kth
    first = jnp.cumsum(level, axis=-1) <= k - above.sum(-1, keepdims=True)
    return above | (level & first)


@functools.partial(jax.jit, static_argnames=("topk", "rounding"))
def _attend_rows(q, q_i, w_i, row0, k, v, k_i, *, topk, rounding):
    """Attention output (R, H * d) of the query rows ``row0 ...`` against the
    context ``k``, ``v``, ``k_i`` (every position not after the last of the
    rows, already rounded where ``rounding`` asks): the indexer's scores, the
    exact top-``topk`` of each row among the positions not after it, and
    softmax attention over that set."""
    r = ROUNDINGS[rounding]
    rows, heads, d = q.shape
    t, kv_heads, _ = k.shape
    s = jnp.einsum("rjd,sd->rjs", r(q_i), k_i)               # (R, J, T)
    scores = jnp.einsum("rj,rjs->rs", w_i, jax.nn.relu(s))
    attend = jnp.arange(t)[None, :] <= (row0 + jnp.arange(rows))[:, None]
    if topk < t:
        attend &= top_k_set(jnp.where(attend, scores, -jnp.inf), topk)
    qg = r(q.reshape(rows, kv_heads, heads // kv_heads, d))
    a = jnp.einsum("rngd,snd->rngs", qg, k) / jnp.sqrt(jnp.float32(d))
    a = jax.nn.softmax(jnp.where(attend[:, None, None], a, -jnp.inf), -1)
    return jnp.einsum("rngs,snd->rngd", r(a), v).reshape(rows, heads * d)


@functools.partial(jax.jit,
                   static_argnames=("experts_per_token", "eps", "rounding"))
def _route(x, a, p, *, experts_per_token, eps, rounding):
    """The attention's output projection and residual, then the router:
    ``(x, x^, chosen experts (T, k), their weights (T, k))``."""
    x = x + _dot(a, p["o"]["kernel"].astype(jnp.float32), rounding)
    h = _rms_norm(x, p["mlp_norm"]["scale"].astype(jnp.float32), eps)
    prob = jax.nn.softmax(_dot(h, p["router"].astype(jnp.float32), rounding))
    w, ids = jax.lax.top_k(prob, experts_per_token)
    return x, h, ids, w / w.sum(-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("rounding",))
def _experts(x, h, experts, rows_of, weight_of, *, rounding):
    """``x + sum_e FFN_e`` over exactly the rows routed to each expert:
    ``rows_of`` (E, cap) lists an expert's rows (padded with the index of a
    zero row appended to ``h``), ``weight_of`` their weights (0 on the
    padding)."""
    experts = _f32(experts)
    h0 = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])

    def one(acc, e):
        gate, up, down, rows, w = e
        mine = h0[rows]
        y = _dot(jax.nn.silu(_dot(mine, gate, rounding))
                 * _dot(mine, up, rounding), down, rounding)
        return acc.at[rows].add(w[:, None] * y), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h0), (
        experts["gate"], experts["up"], experts["down"], rows_of, weight_of))
    return x + acc[:-1]


@functools.partial(jax.jit, static_argnames=("count", "eps", "rounding"))
def _head(x, start, norm, head, *, count, eps, rounding):
    rows = jax.lax.dynamic_slice_in_dim(x, start, count, 0)
    h = _rms_norm(rows, norm["scale"].astype(jnp.float32), eps)
    return _dot(h, head["kernel"].astype(jnp.float32), rounding)


def _attend(q, q_i, w_i, k, v, k_i, *, block, reach, **how):
    """``_attend_rows`` block after block of query rows, each against the
    positions up to its own last row, in steps of ``reach`` positions so
    that few lengths are ever compiled (a causal pass: what lies after a
    block's rows is never read)."""
    t, out, context = q.shape[0], [], (0, None)
    for r0 in range(0, t, block):
        n = min(t, -(-(r0 + block) // reach) * reach)
        if n != context[0]:
            context = (n, (k[:n], v[:n], k_i[:n]))
        out.append(_attend_rows(q[r0:r0 + block], q_i[r0:r0 + block],
                                w_i[r0:r0 + block], jnp.int32(r0),
                                *context[1], **how))
    return jnp.concatenate(out)


def _rows_by_expert(ids: np.ndarray, weights: np.ndarray, num_experts: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Each expert's rows and weights from the (T, k) choices, padded to one
    length (a multiple of 1,024, so that few lengths are ever compiled)
    with the zero row ``T`` at weight 0."""
    t, k = ids.shape
    flat = ids.reshape(-1)
    order = np.argsort(flat, kind="stable")
    sizes = np.bincount(flat, minlength=num_experts)
    cap = int(-(-max(sizes.max(), 1) // 1024) * 1024)
    rows = np.full((num_experts, cap), t, np.int32)
    w = np.zeros((num_experts, cap), np.float32)
    start = 0
    for e, n in enumerate(sizes):
        mine = order[start:start + n]
        rows[e, :n] = mine // k
        w[e, :n] = weights.reshape(-1)[mine]
        start += n
    return rows, w


def sparse_moe_forward(variables, tokens, *, num_layers: int, num_heads: int,
                       num_kv_heads: int, head_dim: int,
                       experts_per_token: int, index_heads: int,
                       index_dim: int, index_topk: int, rope_theta: float,
                       eps: float, rows: Optional[Tuple[int, int]] = None,
                       query_block: int = 128, context_step: int = 4096,
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
    sizes = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                 head_dim=head_dim, index_heads=index_heads,
                 index_dim=index_dim, rope_theta=float(rope_theta),
                 eps=float(eps), rounding=rounding)
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][tokens].astype(jnp.float32)
        for i in range(num_layers):
            layer = p[f"layer_{i}"]
            q, k, v, q_i, k_i, w_i = _project(
                x, {n: layer[n] for n in layer if n not in
                    ("experts", "router", "o", "mlp_norm")}, **sizes)
            a = _attend(q, q_i, w_i, *(ROUNDINGS[rounding](c)
                                       for c in (k, v, k_i)),
                        block=block, reach=context_step,
                        topk=int(index_topk), rounding=rounding)
            del q, k, v, q_i, k_i, w_i
            x, h, ids, w = _route(
                x, a, {n: layer[n] for n in ("o", "mlp_norm", "router")},
                experts_per_token=experts_per_token, eps=float(eps),
                rounding=rounding)
            rows_of, weight_of = _rows_by_expert(
                np.asarray(ids), np.asarray(w),
                int(layer["experts"]["gate"].shape[0]))
            x = _experts(x, h, layer["experts"], jnp.asarray(rows_of),
                         jnp.asarray(weight_of), rounding=rounding)
        return _head(x, jnp.int32(first), p["final_norm"], p["head"],
                     count=int(count), eps=float(eps), rounding=rounding)


# ------------------------------------------------- the comparison itself

@jax.jit
def _gaps_below_best(logits, produced):
    got = jnp.take_along_axis(logits, produced[:, None], axis=-1)[:, 0]
    return logits.max(axis=-1) - got


def check_served(forward, variables,
                 requests: List[Tuple[np.ndarray, List[int]]], pad_to: int,
                 rows: int, control: Optional[str] = None) -> Dict[str, Any]:
    """Run ``forward`` once over each request's prompt + served tokens (the
    last one left off: nothing was produced from it), padded with zeros to
    ``pad_to`` positions (a causal pass: the padding is after everything
    read), for the ``rows`` rows from the prompt's last position on: those
    that produced the served tokens.  Over all the served tokens of all the
    requests, of the gap by which a served token's logit lies below the
    reference's best: the mean (``served_gap_mean``, the number that is
    compared), the widest and the share of tokens that are not the
    reference's best.  With ``control``, the same three of the token the
    same pass at that lower precision puts first at the same rows."""
    gaps, control_gaps, seconds = [], [], 0.0
    for prompt, served in requests:
        n = len(served)
        if n > rows:
            raise ValueError(f"{n} served tokens, {rows} rows compared")
        seq = np.zeros(pad_to, np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + n - 1] = served[:-1]
        t0 = time.perf_counter()
        logits = forward(variables, seq, rows=(len(prompt) - 1, rows))
        produced = np.zeros(rows, np.int32)
        produced[:n] = served
        gaps.append(np.asarray(_gaps_below_best(
            logits, jnp.asarray(produced)))[:n].astype(np.float64))
        seconds += time.perf_counter() - t0
        if control is not None:
            low = forward(variables, seq, rows=(len(prompt) - 1, rows),
                          rounding=control)
            first = np.asarray(low.argmax(axis=-1).astype(jnp.int32))
            control_gaps.append(np.asarray(_gaps_below_best(
                logits, jnp.asarray(first)))[:n].astype(np.float64))
    out = {"positions": int(sum(len(g) for g in gaps)),
           "requests": len(requests), "reference_seconds": seconds,
           **_gap_summary("served", gaps)}
    if control is not None:
        out.update(_gap_summary("control", control_gaps))
    return out


def _gap_summary(prefix: str, gaps: Sequence[np.ndarray]) -> Dict[str, float]:
    g = np.concatenate(gaps)
    return {f"{prefix}_gap_mean": float(g.mean()),
            f"{prefix}_gap_max": float(g.max()),
            f"{prefix}_flipped": float((g > 0).mean())}
