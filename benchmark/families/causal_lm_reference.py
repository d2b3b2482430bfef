"""Plain references for the ``causal_lm`` family, and the comparison that
decides ``correct`` for a served language model.

``gpt2_forward`` is the forward pass of GPT-2 (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners"; the layer equations as the
released ``gpt2-xl`` runs them) over ONE sequence, in plain ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``: no cache, no
batching, no flax module, nothing imported from the program.  It reads the
weights the benchmark made (``causal_lm.make_variables``) by the names of the
parameter tree they were made for:

    x = wte[tokens] + wpe[positions]
    per layer:  h = LayerNorm(x);  q, k, v = split(h W_qkv + b_qkv)
                a = softmax(q k^T / sqrt(d_head), causal) v
                x = x + a W_proj + b_proj
                h = LayerNorm(x);  x = x + gelu_tanh(h W_fc + b_fc) W_out + b_out
    logits = LayerNorm(x) W_head + b_head

Departures from the published model, both the program's (``mmlspark_tpu.models.
TransformerEncoder`` has no other form) and both in the configuration file:
the head is an untied matrix with a bias, where GPT-2 reuses ``wte``; the
LayerNorm epsilon is the one the configuration gives (flax's 1e-6, where
GPT-2 has 1e-5).

It runs layer by layer, one jitted block reused for every layer, so that on
the chip a float32 copy of one layer's weights at a time is all it adds to
memory.  A ``rounding`` other than ``None`` computes the same pass with every
matmul operand rounded to a lower precision: the control of "How correct is
decided", never run by a benchmark run.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _round_fp8(x):
    """``x`` as float8 (e4m3) would hold it under one scale per tensor, back
    in float32: three bits of mantissa where bfloat16 has seven."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


ROUNDINGS = {None: lambda x: x, "fp8": _round_fp8}


def _dot(a, b, rounding):
    r = ROUNDINGS[rounding]
    return r(a) @ r(b)


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@jax.jit
def _embed(wte, wpe, tokens):
    t = tokens.shape[0]
    return wte[tokens].astype(jnp.float32) + wpe[0, :t].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("num_heads", "eps", "rounding"))
def _block(x, p, *, num_heads, eps, rounding):
    """One pre-LayerNorm block over one sequence ``x`` (T, width)."""
    p = _f32(p)
    t, w = x.shape
    d = w // num_heads
    attn = p["MultiHeadAttention_0"]
    h = _layer_norm(x, p["LayerNorm_0"]["scale"], p["LayerNorm_0"]["bias"], eps)
    qkv = _dot(h, attn["qkv"]["kernel"], rounding) + attn["qkv"]["bias"]
    q, k, v = (qkv[:, i * w:(i + 1) * w].reshape(t, num_heads, d)
               .transpose(1, 0, 2) for i in range(3))            # (H, T, d)
    r = ROUNDINGS[rounding]
    s = jnp.einsum("htd,hsd->hts", r(q), r(k)) / jnp.sqrt(jnp.float32(d))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    a = jnp.einsum("hts,hsd->htd", r(jax.nn.softmax(s, axis=-1)), r(v))
    a = a.transpose(1, 0, 2).reshape(t, w)
    x = x + _dot(a, attn["proj"]["kernel"], rounding) + attn["proj"]["bias"]
    h = _layer_norm(x, p["LayerNorm_1"]["scale"], p["LayerNorm_1"]["bias"], eps)
    h = _gelu_tanh(_dot(h, p["Dense_0"]["kernel"], rounding)
                   + p["Dense_0"]["bias"])
    return x + _dot(h, p["Dense_1"]["kernel"], rounding) + p["Dense_1"]["bias"]


@functools.partial(jax.jit, static_argnames=("eps", "rounding"))
def _head(x, ln, head, *, eps, rounding):
    ln, head = _f32(ln), _f32(head)
    h = _layer_norm(x, ln["scale"], ln["bias"], eps)
    return _dot(h, head["kernel"], rounding) + head["bias"]


def gpt2_forward(variables, tokens, *, num_heads: int, num_layers: int,
                 eps: float, rounding: Optional[str] = None):
    """Logits ``(T, vocab)`` in float32 of one sequence of token ids
    ``(T,)``; position ``t`` predicts token ``t + 1``."""
    p = variables["params"]
    with jax.default_matmul_precision("highest"):
        x = _embed(p["Embed_0"]["embedding"], p["pos_embed"],
                   jnp.asarray(tokens, jnp.int32))
        for i in range(num_layers):
            x = _block(x, p[f"block_{i}"], num_heads=num_heads, eps=eps,
                       rounding=rounding)
        return _head(x, p["LayerNorm_0"], p["head"], eps=eps,
                     rounding=rounding)


# ------------------------------------------------- the comparison itself

@jax.jit
def _gaps_below_best(logits, produced):
    """Per position ``t``: the best logit less that of token ``produced[t]``."""
    got = jnp.take_along_axis(logits, produced[:, None], axis=-1)[:, 0]
    return logits.max(axis=-1) - got


@jax.jit
def _first(logits):
    return logits.argmax(axis=-1).astype(jnp.int32)


def served_gaps(logits, served: Sequence[int], prompt_len: int) -> np.ndarray:
    """For every served token of one request, how far its logit lies below
    the reference's best at the position that produced it.  ``logits`` is the
    reference's ``(T, vocab)`` over prompt + answer; the answer's token ``j``
    was produced at position ``prompt_len - 1 + j``.  0 where the served
    token IS the reference's best; tokens are never compared for equality,
    because with random weights the best changes on rounding.  One compiled
    shape whatever the lengths: every position is read, the answer's kept."""
    n = len(served)
    produced = np.zeros(logits.shape[0], np.int32)
    produced[prompt_len - 1:prompt_len - 1 + n] = served
    gaps = np.asarray(_gaps_below_best(logits, jnp.asarray(produced)))
    return gaps[prompt_len - 1:prompt_len - 1 + n].astype(np.float64)


def relative_l2(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per position: ``|got - want| / |want|`` over the vocabulary."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def check_served(forward, variables, requests: List[Tuple[np.ndarray, List[int]]],
                 pad_to: int, control: Optional[str] = None
                 ) -> Dict[str, Any]:
    """Run ``forward`` once over each request's prompt + served tokens (the
    last one left off: nothing was produced from it), padded with zeros to
    ``pad_to`` positions so that one compiled pass serves them all (a causal
    pass: the padding is after everything read).  Over all the served tokens
    of all the requests, of the gap by which a served token's logit lies
    below the reference's best: the mean (``served_gap_mean``: the number
    that is compared; a token that IS the reference's best counts 0), the
    widest (``served_gap_max``) and the share of tokens that are not the
    reference's best (``served_flipped``).  With ``control``, the same three
    of the token that the same pass at that lower precision puts first at
    the same positions (``control_gap_mean`` and so on)."""
    gaps, control_gaps = [], []
    for prompt, served in requests:
        seq = np.zeros(pad_to, np.int32)
        n = len(prompt) + len(served) - 1
        seq[:len(prompt)] = prompt
        seq[len(prompt):n] = served[:-1]
        logits = forward(variables, seq)
        gaps.append(served_gaps(logits, served, len(prompt)))
        if control is not None:
            low = forward(variables, seq, rounding=control)
            rows = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
            first = np.asarray(_first(low))[rows]
            control_gaps.append(served_gaps(logits, first, len(prompt)))
    out = {"positions": int(sum(len(g) for g in gaps)),
           "requests": len(requests), **_gap_summary("served", gaps)}
    if control is not None:
        out.update(_gap_summary("control", control_gaps))
    return out


def _gap_summary(prefix: str, gaps: List[np.ndarray]) -> Dict[str, float]:
    g = np.concatenate(gaps)
    return {f"{prefix}_gap_mean": float(g.mean()),
            f"{prefix}_gap_max": float(g.max()),
            f"{prefix}_flipped": float((g > 0).mean())}
