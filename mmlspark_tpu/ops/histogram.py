"""Gradient-histogram builds — the GBDT hot kernel.

Reference: LightGBM's native histogram construction + socket allreduce
(`LGBM_NetworkInit` ring; reference ``TrainUtils.scala:279-295``, C-API calls
in ``LightGBMBooster.scala``).  TPU-native: one fused scatter-add over a
flattened (node, feature, bin) index space, expressed as ``segment_sum`` so
XLA lowers it to a single sorted-scatter per iteration; across data shards the
histograms are combined by ``psum`` over ICI — either inserted automatically
by GSPMD (jit + shardings) or explicitly in ``shard_map`` (see
``lightgbm.core``).

Layout note: the histogram tensor is (nodes, features, bins, 3) holding
(sum_grad, sum_hess, count).  bins=const 256 max keeps the last dim a
multiple of 128 lanes after flattening; counts ride along as a third channel
instead of a separate pass.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..observability.compute import instrumented_jit
from ..utils.device import platform


def build_histograms(binned: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
                     node_ids: jnp.ndarray, num_nodes: int, num_bins: int,
                     sample_weight: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Histograms for every (node, feature, bin) cell in one pass.

    Args:
      binned: (n, F) uint8/int32 feature bins.
      grad, hess: (n,) per-row gradient/hessian.
      node_ids: (n,) int32 current node of each row at this depth, in
        [0, num_nodes); rows with node_id < 0 (masked out by bagging/GOSS)
        are dropped.
      num_nodes, num_bins: static sizes.
      sample_weight: optional (n,) multiplier folded into grad/hess/count.

    Returns:
      (num_nodes, F, num_bins, 3) float32: sums of grad, hess, count.
    """
    n, F = binned.shape
    B = num_bins
    S = num_nodes * F * B
    node = node_ids.astype(jnp.int32)
    g = grad.astype(jnp.float32)
    h = hess.astype(jnp.float32)
    c = jnp.ones_like(g)  # counts stay unweighted (min_data_in_leaf semantics)
    if sample_weight is not None:
        g, h = g * sample_weight, h * sample_weight

    # Row-chunked accumulation keeps the (chunk, F) broadcast small instead of
    # materialising n*F floats (0.8 GB at 1M x 200).  Rows with node < 0
    # (bagging/GOSS-masked or padding) get negative segment ids, which the
    # scatter drops natively.  Three separate f32 scatters measured faster on
    # TPU than channel-windowed or complex-packed variants.
    chunk = max(1024, min(n, (1 << 23) // max(F, 1)))
    n_pad = -n % chunk
    if n_pad:
        node = jnp.concatenate([node, jnp.full((n_pad,), -1, jnp.int32)])
        b_mat = jnp.concatenate([binned, jnp.zeros((n_pad, F), binned.dtype)])
        g = jnp.concatenate([g, jnp.zeros((n_pad,), g.dtype)])
        h = jnp.concatenate([h, jnp.zeros((n_pad,), h.dtype)])
        c = jnp.concatenate([c, jnp.zeros((n_pad,), c.dtype)])
    else:
        b_mat = binned
    R = (n + n_pad) // chunk
    f_idx = jnp.arange(F, dtype=jnp.int32)[None, :]

    def body(acc, args):
        b_c, g_c, h_c, c_c, node_c = args
        seg = ((node_c[:, None] * F + f_idx) * B + b_c.astype(jnp.int32)).reshape(-1)
        sums = [jax.ops.segment_sum(
            jnp.broadcast_to(x[:, None], (chunk, F)).reshape(-1), seg,
            num_segments=S) for x in (g_c, h_c, c_c)]
        return (acc[0] + sums[0], acc[1] + sums[1], acc[2] + sums[2]), None

    init = (jnp.zeros((S,), jnp.float32),) * 3
    (gs, hs, cs), _ = jax.lax.scan(
        body, init,
        (b_mat.reshape(R, chunk, F), g.reshape(R, chunk), h.reshape(R, chunk),
         c.reshape(R, chunk), node.reshape(R, chunk)))
    return jnp.stack([gs, hs, cs], axis=-1).reshape(num_nodes, F, B, 3)


def histogram_subtraction(parent_hist: jnp.ndarray, child_hist: jnp.ndarray) -> jnp.ndarray:
    """Sibling trick: sibling = parent - child (LightGBM's halving of
    histogram work).  parent/child: (nodes_d, F, B, 3) with children of node
    i at 2i, 2i+1 — returns the sibling histograms for the next level."""
    return parent_hist - child_hist


@instrumented_jit(name="ops.bin_matrix", static_argnames=("num_bins",))
def bin_matrix(x: jnp.ndarray, edges: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    """Digitize raw features on device: bin = #edges < x.  edges:
    (F, num_bins-1) ascending with +inf padding.

    Per-feature binary search (vmapped ``searchsorted``), O(n*F*log B) time
    and O(n*F) memory — the old broadcast compare materialized an
    (n, F, B-1) boolean (~50GB logical at 1M x 200 x 255; round-1 weak
    item 10).  NaNs bin to 0, matching the comparison semantics.
    """
    def per_feature(e, xf):
        return jnp.searchsorted(e, xf, side="left")

    bins = jax.vmap(per_feature, in_axes=(0, 1), out_axes=1)(edges, x)
    return jnp.where(jnp.isnan(x), 0, bins).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# MXU histogram backend
# ---------------------------------------------------------------------------

def _layout_scope():
    """The device-phase scope of ``_node_pure_layout``, for the builders that
    call it.  The names live in ``lightgbm.core.DEVICE_PHASES`` alone; the
    import is late because that module imports this one."""
    from ..lightgbm.core import PHASE_LAYOUT
    return jax.named_scope(PHASE_LAYOUT)


def _node_pure_layout(binned, grad, hess, node_ids, num_nodes, R,
                      sample_weight=None, residuals=True, max_rows=None,
                      quantized=False):
    """Shared device prep for the MXU histogram backend: R-row blocks that
    are node-pure, with the bf16x2-decomposed weight channels
    (``residuals=False`` keeps just bf16-rounded grad/hess + count — 3
    channels instead of 5).

    With ``quantized=True``, ``grad``/``hess`` are the pre-quantized int
    gradients and the weight channels come back as **int8**
    (qg, qh, valid) — the packed-histogram operand layout.

    Returns the operands of the builders' block scan: (bb (NB, R, F) u8,
    w_ch (NB, 5 or 3, R), node_blk (NB,) i32).  Masked rows (node < 0) land
    in dummy node P whose buffer is dropped by the caller.

    One stable sort by node, the row index and the weights riding along as
    payloads; after it every block is a CONTIGUOUS run of the sorted order
    (block ``b`` of node ``p`` starts ``b*R - padded_off[p]`` rows into the
    node's run), so the blocks are R-element slices at P+1 offsets — no
    per-row slot, no scatter.  Only the binned rows are gathered, by the
    blocks' sorted row ids.  A build of ONE node with no ``max_rows`` (the
    root of every tree, a leaf-wise ``local_hist``) needs no order at all:
    ``binned`` itself in R-row blocks, masked rows at zero weight.

    ``max_rows`` is a STATIC caller GUARANTEE that at most that many rows
    are unmasked (node >= 0).  It truncates the padded layout — and with it
    the block scan — to ``ceil(max_rows/R) + P + 1`` blocks instead of
    covering all n rows; the dummy node sorts last, so it is surplus masked
    rows that fall off the end.  The level-wise grower uses this with
    LightGBM's smaller-child rule: levels below the root only ever build
    the smaller sibling of each parent (<= n/2 rows total), halving the
    one-hot operand traffic of every build after the root.  If the caller's
    guarantee is violated, UNMASKED rows are silently dropped — callers must
    pass a true bound.
    """
    n, F = binned.shape
    P = num_nodes
    if quantized:
        # ONE int32 payload: |qg| <= 64 and qh <= 127 by the quant_bins cap,
        # so both fit the int8 operand lanes and share a word exactly
        w = ((grad.astype(jnp.int32) << 8) | (hess.astype(jnp.int32) & 255),)
    else:
        g = grad.astype(jnp.float32)
        h = hess.astype(jnp.float32)
        if sample_weight is not None:
            g, h = g * sample_weight, h * sample_weight
        w = (g, h)
    keep = node_ids >= 0

    if P == 1 and max_rows is None:
        NB = -(-n // R)

        def blocks(x):
            x = jnp.pad(x, ((0, NB * R - n),) + ((0, 0),) * (x.ndim - 1))
            return x.reshape((NB, R) + x.shape[1:])

        bb, valid, w = blocks(binned), blocks(keep), [blocks(x) for x in w]
        node_blk = jnp.zeros((NB,), jnp.int32)
    else:
        # R pad keys past the dummy node: no block slice below runs off the
        # sorted arrays, and bounds[P + 1] is the end of the dummy node
        def padded(x, fill):
            return jnp.concatenate([x, jnp.full((R,), fill, x.dtype)])

        node_s = jnp.where(keep, node_ids, P).astype(jnp.int32)
        ks, order, *w = jax.lax.sort(
            (padded(node_s, P + 1), jnp.arange(n + R, dtype=jnp.int32),
             *[padded(x, 0) for x in w]), num_keys=1, is_stable=True)
        bounds = jnp.searchsorted(
            ks, jnp.arange(P + 2, dtype=jnp.int32)).astype(jnp.int32)
        start, counts = bounds[:-1], jnp.diff(bounds)
        # empty nodes get ZERO blocks (their buffer stays at acc0's zeros);
        # node_blk's searchsorted('right')-1 naturally skips past zero-width
        # offsets to the node that actually owns the rows
        padded_counts = -(-counts // R) * R
        padded_off = jnp.cumsum(padded_counts) - padded_counts
        n_cap = n if max_rows is None else min(n, int(max_rows))
        NB = -(-n_cap // R) + P + 1                  # static upper bound
        block_starts = jnp.arange(NB, dtype=jnp.int32) * R
        node_blk = jnp.clip(
            jnp.searchsorted(padded_off, block_starts, side="right")
            .astype(jnp.int32) - 1, 0, P)
        # rows of its node before the block; past a node's real rows (and
        # past every node: those blocks read as the dummy's) nothing is valid
        into = block_starts - padded_off[node_blk]
        src = start[node_blk] + into
        valid = (jnp.arange(R, dtype=jnp.int32)
                 < (counts[node_blk] - into)[:, None])

        def blocks(x):
            return jax.vmap(
                lambda s: jax.lax.dynamic_slice(x, (s,), (R,)))(src)

        w = [blocks(x) for x in w]
        bb = binned[jnp.where(valid, blocks(order), 0)]          # (NB, R, F)

    if quantized:
        # int8 operand lanes: the per-row values are exact; accumulation is
        # int32
        pk = w[0]
        w_ch = jnp.stack([jnp.where(valid, pk >> 8, 0),
                          jnp.where(valid, pk & 255, 0),
                          valid.astype(jnp.int32)], axis=1).astype(jnp.int8)
        return bb, w_ch, node_blk
    # bf16x2 decomposition for the MXU inputs: grad/hess are signed and
    # cancellation-sensitive, so each carries a bf16 residual channel; counts
    # (small ints, unweighted: min_data_in_leaf semantics) are exact in bf16.
    # Accumulation itself is f32 on the MXU.
    gp = jnp.where(valid, w[0], 0.0)
    hp = jnp.where(valid, w[1], 0.0)
    cp = valid.astype(jnp.float32)
    g_hi = gp.astype(jnp.bfloat16).astype(jnp.float32)
    h_hi = hp.astype(jnp.bfloat16).astype(jnp.float32)
    if not residuals:
        return bb, jnp.stack([g_hi, h_hi, cp], axis=1), node_blk
    w5 = jnp.stack([g_hi, gp - g_hi, h_hi, hp - h_hi, cp], axis=1)
    return bb, w5, node_blk


def build_histograms_matmul(binned: jnp.ndarray, grad: jnp.ndarray,
                            hess: jnp.ndarray, node_ids: jnp.ndarray,
                            num_nodes: int, num_bins: int,
                            sample_weight: Optional[jnp.ndarray] = None,
                            block_rows: int = 4096,
                            lo_width: int = 0,
                            residuals: bool = True,
                            max_rows: Optional[int] = None) -> jnp.ndarray:
    """Histogram build as batched one-hot matmuls on the MXU.

    TPU scatter runs ~100M updates/s — far below what the n*F histogram pass
    needs.  This backend reformulates the build so the inner loop is matrix
    multiplication:

    1. rows are sorted by node, once, and every `block_rows` block is a
       node-pure contiguous slice of that order, each node padded to whole
       blocks (``_node_pure_layout``; one node needs no sort at all);
    2. each 8-bit bin splits into hi/lo parts (``lo_width`` lanes wide); a
       block's histogram is the pair of one-hot indicators contracted over
       rows — ``einsum('rfm,rfl->fml', onehot_hi * weight, onehot_lo)`` —
       which XLA lowers to F-batched matmuls on the systolic array;
    3. block results accumulate into per-node buffers in a `lax.scan`.

    Masked rows (node < 0) land in a dummy node whose buffer is dropped.
    Exact: every (row, feature) contributes to exactly one (hi, lo) cell.

    The pass is HBM-bound, not MXU-bound (measured r4): traffic per
    (row, feature) is ``2*(C*HI + LO)`` bytes of materialized bf16 one-hot
    operands plus the per-block f32 accumulator round-trip.  Hence the
    knobs: larger ``block_rows`` cuts accumulator traffic ~linearly;
    ``lo_width=64`` (hi=4) shrinks the weighted operand from 5*16 to 5*4
    channels (the MXU time is invariant to the split — M*N stays C*B);
    ``residuals=False`` drops the two bf16-residual channels (inputs round
    to bf16, accumulation stays exact f32 — LightGBM's own histograms are
    f32) for another ~40% operand-traffic cut; ``max_rows`` (a static caller
    guarantee on the unmasked row count — see ``_node_pure_layout``)
    truncates the scan itself, LightGBM's smaller-child halving.
    """
    import jax
    import jax.numpy as jnp

    n, F = binned.shape
    B = num_bins
    if B > 256:
        raise ValueError("matmul backend supports max_bin <= 256")
    LO = lo_width or 16
    if LO not in (16, 32, 64, 128):
        raise ValueError("lo_width must be one of 16/32/64/128")
    HI = (B + LO - 1) // LO
    shift = LO.bit_length() - 1
    P = num_nodes
    # small inputs: shrink the block so padding (one block minimum per node)
    # stays proportionate
    R = min(block_rows, max(256, 1 << max(0, (n - 1)).bit_length()))

    with _layout_scope():
        bb_all, w_ch, node_blk = _node_pure_layout(
            binned, grad, hess, node_ids, num_nodes, R, sample_weight,
            residuals=residuals, max_rows=max_rows)
    C = w_ch.shape[1]                                # 5 or 3 channels

    hi_iota = jnp.arange(HI, dtype=jnp.int32)
    lo_iota = jnp.arange(LO, dtype=jnp.int32)

    def body(acc, args):
        bb, w, nb = args                             # (R,F) u8, (C,R), ()
        b32 = bb.astype(jnp.int32)
        hi = b32 >> shift
        lo = b32 & (LO - 1)
        onehot_lo = (lo[:, :, None] == lo_iota).astype(jnp.bfloat16)   # (R,F,LO)
        onehot_hi = (hi[:, :, None] == hi_iota).astype(jnp.bfloat16)   # (R,F,HI)
        # channels merged into the matmul M axis: M = C*HI instead of
        # batched M=LO matmuls -> C x less systolic-array padding waste
        a = (onehot_hi[:, :, None, :] *
             w.T[:, None, :, None].astype(jnp.bfloat16))               # (R,F,C,HI)
        a = a.reshape(R, F, C * HI)
        blk = jnp.einsum("rfm,rfl->fml", a, onehot_lo,
                         preferred_element_type=jnp.float32)           # (F,C*HI,LO)
        return acc.at[nb].add(blk), None

    acc0 = jnp.zeros((P + 1, F, C * HI, LO), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (bb_all, w_ch, node_blk))
    acc = acc[:P].reshape(P, F, C, HI, LO)                             # split channels
    if residuals:
        acc3 = jnp.stack([acc[:, :, 0] + acc[:, :, 1],
                          acc[:, :, 2] + acc[:, :, 3], acc[:, :, 4]], axis=0)
    else:
        acc3 = jnp.moveaxis(acc, 2, 0)
    hist = acc3.reshape(3, P, F, HI * LO)[..., :B]                     # (3,P,F,B)
    return jnp.moveaxis(hist, 0, -1)                                    # (P,F,B,3)


# ---------------------------------------------------------------------------
# quantized-gradient packed histograms (LightGBM 4.x quantized training)
# ---------------------------------------------------------------------------
#
# "Quantized Training of Gradient Boosting Decision Trees": per-row grad/hess
# quantize ONCE PER ITERATION to low-bit integers with stochastic rounding and
# per-iteration scale factors; the histogram build then accumulates packed
# integers instead of three f32 channels, and split gains are computed from
# the rescaled integer sums.  Because every level of a tree reuses the SAME
# per-row integers, sibling subtraction (right = parent - left) is EXACT in
# integer space — no f32 cancellation drift between levels.

def global_row_ids(axis_name: Optional[str], n: int):
    """Global ids of this shard's ``n`` contiguous rows, or None when
    unsharded (local ids are already global).  THE formula the elastic
    bit-identity contract rides (ISSUE 14): with contiguous block
    sharding, real rows keep identical ids at ANY shard count, so
    rounding noise keyed on them is width-independent — both growers
    must use this one helper, never a local copy."""
    if axis_name is None:
        return None
    return jax.lax.axis_index(axis_name) * n + jnp.arange(n)


def quantize_gradients(grad, hess, quant_bins: int, seed: int = 0,
                       axis_name: Optional[str] = None,
                       g_scale=None, h_scale=None,
                       row_ids=None, mix=None):
    """Stochastically round per-row grad/hess to small signed/unsigned ints.

    Returns ``(qg, qh, g_scale, h_scale)`` with ``qg`` in
    ``[-quant_bins//2, quant_bins//2]`` (int32), ``qh`` in
    ``[0, quant_bins - 1]`` (int32), and ``E[qg * g_scale] == grad`` /
    ``E[qh * h_scale] == hess`` (stochastic rounding is unbiased:
    ``floor(x + u)``, ``u ~ U[0, 1)``).  Scales are per-call (one boosting
    iteration); with ``axis_name`` they are ``pmax``'d over the mesh so
    every shard quantizes in the SAME units and the psum'd integer
    histograms stay meaningful.

    Passing ``g_scale``/``h_scale`` (both or neither) skips the max pass
    and quantizes in the CALLER's units — the out-of-core tile stream
    computes global maxima in a first pass over every tile, then hands
    each tile the same scales so per-tile integer partial histograms
    accumulate exactly (the tile-level twin of the ``pmax`` contract).
    The values are clipped to the integer caps either way, so a stale
    (too-small) scale degrades resolution, never correctness.

    The rounding noise needs no host RNG plumbing: the PRNG key folds in a
    bitcast of the gradient sum, which changes every iteration (the scores
    moved), decorrelating rounding patterns across iterations while staying
    deterministic and tracer-safe.

    Topology independence (elastic resume, ISSUE 14): with ``row_ids``
    given (the GLOBAL row index of each local row), the per-row noise is
    counter-based — ``u(row) = uniform(fold_in(key, row_id))`` — so a row
    rounds identically no matter which shard or tile holds it.  The key
    itself must then also be topology-free: inside ``shard_map``
    (``axis_name`` set) it folds an exact INTEGER psum of the bitcast
    |grad|/hess magnitudes (integer adds are associative, so 4 shards and
    8 shards fold the same value; |g| zeroes the sign bit so ``-0.0`` pad
    rows cannot skew the count); single-shard callers that stream tiles
    pass ``mix`` (an int32 computed once over the whole row space) for the
    same guarantee.  Without ``row_ids`` the original shape-keyed draw is
    preserved bit-for-bit.
    """
    import jax
    import jax.numpy as jnp
    import jax.random as jrandom

    g = grad.astype(jnp.float32)
    h = hess.astype(jnp.float32)
    qg_cap = max(1, quant_bins // 2)
    qh_cap = max(1, quant_bins - 1)
    if (g_scale is None) != (h_scale is None):
        raise ValueError("pass both g_scale and h_scale or neither")
    if g_scale is None:
        gmax = jnp.max(jnp.abs(g))
        hmax = jnp.max(h)
        if axis_name is not None:
            gmax = jax.lax.pmax(gmax, axis_name)
            hmax = jax.lax.pmax(hmax, axis_name)
        g_scale = jnp.maximum(gmax, 1e-12) / qg_cap
        h_scale = jnp.maximum(hmax, 1e-12) / qh_cap
    else:
        g_scale = jnp.maximum(jnp.asarray(g_scale, jnp.float32), 1e-30)
        h_scale = jnp.maximum(jnp.asarray(h_scale, jnp.float32), 1e-30)
    if mix is None:
        if row_ids is not None and axis_name is not None:
            # exact integer fold: associative across any shard layout
            mix = jax.lax.psum(
                jnp.sum(jax.lax.bitcast_convert_type(jnp.abs(g), jnp.int32))
                + 3 * jnp.sum(jax.lax.bitcast_convert_type(h, jnp.int32)),
                axis_name)
        else:
            mix = jax.lax.bitcast_convert_type(
                jnp.sum(g) + 3.0 * jnp.sum(h), jnp.int32)
    key = jrandom.fold_in(jrandom.PRNGKey(seed),
                          jnp.asarray(mix, jnp.int32))
    if row_ids is not None:
        if g.ndim != 1:
            raise ValueError("row_ids quantization expects 1-d grad/hess "
                             f"(got shape {g.shape})")
        row_keys = jax.vmap(lambda i: jrandom.fold_in(key, i))(
            jnp.asarray(row_ids, jnp.int32))
        u = jnp.moveaxis(
            jax.vmap(lambda k: jrandom.uniform(k, (2,)))(row_keys), -1, 0)
    else:
        u = jrandom.uniform(key, (2,) + g.shape)
    qg = jnp.clip(jnp.floor(g / g_scale + u[0]),
                  -qg_cap, qg_cap).astype(jnp.int32)
    qh = jnp.clip(jnp.floor(h / h_scale + u[1]),
                  0, qh_cap).astype(jnp.int32)
    return qg, qh, g_scale, h_scale


def dequantize_histogram(hist_i32, g_scale, h_scale):
    """(..., 3) int32 [sum_qg, sum_qh, count] -> (..., 3) f32
    [sum_grad, sum_hess, count] — the rescale applied at split-gain time."""
    import jax.numpy as jnp
    f = hist_i32.astype(jnp.float32)
    return jnp.stack([f[..., 0] * g_scale, f[..., 1] * h_scale, f[..., 2]],
                     axis=-1)


def _packed_layout(bound: int, quant_bins: int):
    """Static lane plan for the scatter backend's int32 accumulation.

    ``bound`` is the max rows any single (node, feature, bin) cell can
    receive (== max rows per node).  The widest layout that still fits 31
    bits wins — bit-width WIDENING as node row counts grow:

    - ``all3``: grad, hess AND count share ONE int32 channel
      (1 segment-sum instead of 3 — the deep-level / many-node regime);
    - ``2ch``: grad alone + (hess, count) packed in the hessian lane's
      spare bits (2 segment-sums);
    - ``wide``: three separate int32 channels (root-scale nodes; exact for
      any n with ``n * (quant_bins - 1) < 2**31``).
    """
    qg_cap = max(1, quant_bins // 2)
    qh_cap = max(1, quant_bins - 1)
    cbits = bound.bit_length()
    hbits = (bound * qh_cap).bit_length()
    gbits = (bound * qg_cap).bit_length()
    if cbits + hbits + gbits <= 31:
        return "all3", cbits, hbits
    if cbits + hbits <= 31:
        return "2ch", cbits, hbits
    return "wide", cbits, hbits


def _pack_lanes(qg, qh, mode: str, cbits: int, hbits: int):
    """Per-row packed int32 weight channels for a ``_packed_layout`` plan
    (``_unpack_lanes`` decodes the accumulated sums)."""
    import jax.numpy as jnp
    KC, KH = 1 << cbits, 1 << hbits
    qg = qg.astype(jnp.int32)
    qh = qh.astype(jnp.int32)
    if mode == "all3":
        return [((qg * KH) + qh) * KC + 1]
    if mode == "2ch":
        return [qg, qh * KC + 1]
    return [qg, qh, jnp.ones_like(qg)]


def _unpack_lanes(acc, mode: str, cbits: int, hbits: int):
    """Decode accumulated packed-lane sums -> ``(qg_sum, qh_sum, count)``.
    Elementwise, so it serves any channel shape.  The lane terms are
    multiples of KC/KH, so floor mod/div decode exactly — negative sums
    included."""
    KC, KH = 1 << cbits, 1 << hbits
    if mode == "all3":
        s = acc[0]
        count = s % KC
        s2 = (s - count) // KC
        qh_s = s2 % KH
        qg_s = (s2 - qh_s) // KH
    elif mode == "2ch":
        qg_s = acc[0]
        count = acc[1] % KC
        qh_s = (acc[1] - count) // KC
    else:
        qg_s, qh_s, count = acc[0], acc[1], acc[2]
    return qg_s, qh_s, count


def build_histograms_quantized(binned: jnp.ndarray, qg: jnp.ndarray,
                               qh: jnp.ndarray, node_ids: jnp.ndarray,
                               num_nodes: int, num_bins: int,
                               quant_bins: int = 16,
                               node_rows_bound: Optional[int] = None,
                               max_rows: Optional[int] = None) -> jnp.ndarray:
    """Packed-integer scatter build: one int32 segment-sum pass instead of
    three f32 ones whenever the static ``node_rows_bound`` lets the lanes
    coexist (see ``_packed_layout``).

    Args mirror ``build_histograms`` except grad/hess arrive pre-quantized
    (``quantize_gradients``).  ``node_rows_bound`` is a STATIC caller
    guarantee on the max rows any node receives; like ``max_rows`` it is a
    trace-time contract — a violated bound silently corrupts lanes, so
    callers must pass a true bound (or None for the safe n default).

    Returns (num_nodes, F, B, 3) **int32**: [sum_qg, sum_qh, count].
    """
    import jax
    import jax.numpy as jnp

    n, F = binned.shape
    B = num_bins
    S = num_nodes * F * B
    node = node_ids.astype(jnp.int32)
    qg = qg.astype(jnp.int32)
    qh = qh.astype(jnp.int32)
    bound = max(1, min(n, int(node_rows_bound or n), int(max_rows or n)))
    qh_cap = max(1, quant_bins - 1)
    if n * qh_cap >= (1 << 31):
        raise ValueError("quantized histograms overflow int32 above "
                         f"{(1 << 31) // qh_cap} rows at {quant_bins} bins")
    mode, cbits, hbits = _packed_layout(bound, quant_bins)
    chans = _pack_lanes(qg, qh, mode, cbits, hbits)

    chunk = max(1024, min(n, (1 << 23) // max(F, 1)))
    n_pad = -n % chunk
    if n_pad:
        node = jnp.concatenate([node, jnp.full((n_pad,), -1, jnp.int32)])
        b_mat = jnp.concatenate([binned, jnp.zeros((n_pad, F), binned.dtype)])
        chans = [jnp.concatenate([c, jnp.zeros((n_pad,), jnp.int32)])
                 for c in chans]
    else:
        b_mat = binned
    R = (n + n_pad) // chunk
    f_idx = jnp.arange(F, dtype=jnp.int32)[None, :]
    nc = len(chans)

    def body(acc, args):
        b_c, node_c = args[0], args[-1]
        seg = ((node_c[:, None] * F + f_idx) * B + b_c.astype(jnp.int32)).reshape(-1)
        sums = [jax.ops.segment_sum(
            jnp.broadcast_to(x[:, None], (chunk, F)).reshape(-1), seg,
            num_segments=S) for x in args[1:-1]]
        return tuple(a + s for a, s in zip(acc, sums)), None

    init = (jnp.zeros((S,), jnp.int32),) * nc
    acc, _ = jax.lax.scan(
        body, init,
        (b_mat.reshape(R, chunk, F),
         *[c.reshape(R, chunk) for c in chans],
         node.reshape(R, chunk)))
    qg_s, qh_s, count = _unpack_lanes(acc, mode, cbits, hbits)
    return jnp.stack([qg_s, qh_s, count], axis=-1).reshape(
        num_nodes, F, B, 3)


def build_histograms_matmul_quantized(binned: jnp.ndarray, qg: jnp.ndarray,
                                      qh: jnp.ndarray, node_ids: jnp.ndarray,
                                      num_nodes: int, num_bins: int,
                                      quant_bins: int = 16,
                                      block_rows: int = 4096,
                                      lo_width: int = 0,
                                      max_rows: Optional[int] = None
                                      ) -> jnp.ndarray:
    """Packed-integer MXU build: the bandwidth lever on TPU.

    Same node-pure block layout as ``build_histograms_matmul``, but the
    weighted one-hot operands are **int8** (quantized values fit int8 up to
    128 quantization levels) and the einsum accumulates **int32** on the
    MXU's integer path.  Operand traffic per (row, feature) drops from
    ``2*(5*HI + LO)`` bytes (bf16, residual channels) to ``3*HI + LO``
    bytes — the ~3x hot-kernel bandwidth cut — and per-block integer sums
    are exact, so cross-level sibling subtraction is too.

    Returns (num_nodes, F, B, 3) **int32**: [sum_qg, sum_qh, count].
    """
    import jax
    import jax.numpy as jnp

    n, F = binned.shape
    B = num_bins
    if B > 256:
        raise ValueError("matmul backend supports max_bin <= 256")
    if quant_bins > 128:
        raise ValueError("int8 operand lanes cap num_grad_quant_bins at 128")
    qh_cap = max(1, quant_bins - 1)
    if n * qh_cap >= (1 << 31):
        raise ValueError("quantized histograms overflow int32 above "
                         f"{(1 << 31) // qh_cap} rows at {quant_bins} bins")
    LO = lo_width or 16
    if LO not in (16, 32, 64, 128):
        raise ValueError("lo_width must be one of 16/32/64/128")
    HI = (B + LO - 1) // LO
    shift = LO.bit_length() - 1
    P = num_nodes
    R = min(block_rows, max(256, 1 << max(0, (n - 1)).bit_length()))

    with _layout_scope():
        bb_all, w_ch, node_blk = _node_pure_layout(
            binned, qg, qh, node_ids, num_nodes, R, quantized=True,
            max_rows=max_rows)
    C = 3                                            # qg, qh, count

    hi_iota = jnp.arange(HI, dtype=jnp.int32)
    lo_iota = jnp.arange(LO, dtype=jnp.int32)

    def body(acc, args):
        bb, w, nb = args                             # (R,F) u8, (C,R) i8, ()
        b32 = bb.astype(jnp.int32)
        hi = b32 >> shift
        lo = b32 & (LO - 1)
        onehot_lo = (lo[:, :, None] == lo_iota).astype(jnp.int8)       # (R,F,LO)
        onehot_hi = (hi[:, :, None] == hi_iota).astype(jnp.int8)       # (R,F,HI)
        a = onehot_hi[:, :, None, :] * w.T[:, None, :, None]           # (R,F,C,HI)
        a = a.reshape(R, F, C * HI)
        blk = jnp.einsum("rfm,rfl->fml", a, onehot_lo,
                         preferred_element_type=jnp.int32)             # (F,C*HI,LO)
        return acc.at[nb].add(blk), None

    acc0 = jnp.zeros((P + 1, F, C * HI, LO), jnp.int32)
    acc, _ = jax.lax.scan(body, acc0, (bb_all, w_ch, node_blk))
    acc = acc[:P].reshape(P, F, C, HI, LO)
    hist = jnp.moveaxis(acc, 2, 0).reshape(3, P, F, HI * LO)[..., :B]
    return jnp.moveaxis(hist, 0, -1)                                   # (P,F,B,3)


#: what a caller may pass as ``backend``; ``auto`` resolves by platform
BACKENDS = ("auto", "scatter", "matmul")


def xla_backend(backend: str = "auto") -> str:
    """The builder family, float or quantized: an explicit ``scatter`` or
    ``matmul`` as given; ``auto`` is ``scatter`` on CPU (where one-hot
    matmuls lose) and ``matmul`` (the MXU build) on TPU."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown histogram backend {backend!r}: "
                         f"expected one of {BACKENDS}")
    if backend != "auto":
        return backend
    return "scatter" if platform() == "cpu" else "matmul"


def build_quantized(binned, qg, qh, node_ids, num_nodes, num_bins,
                    quant_bins: int = 16, backend: str = "auto",
                    max_rows=None, node_rows_bound=None):
    """Quantized-path backend dispatcher, mirroring ``build``: 'auto' picks
    the int8 MXU build on TPU and the packed int32 scatter on CPU.  Returns
    int32 (nodes, F, B, 3) [sum_qg, sum_qh, count] — rescale with
    ``dequantize_histogram``."""
    if xla_backend(backend) == "matmul":
        return build_histograms_matmul_quantized(
            binned, qg, qh, node_ids, num_nodes, num_bins,
            quant_bins=quant_bins, max_rows=max_rows)
    return build_histograms_quantized(
        binned, qg, qh, node_ids, num_nodes, num_bins,
        quant_bins=quant_bins, node_rows_bound=node_rows_bound,
        max_rows=max_rows)


def build(binned, grad, hess, node_ids, num_nodes, num_bins,
          sample_weight=None, backend: str = "auto", max_rows=None):
    """Backend dispatcher.  'auto' picks the MXU matmul build on accelerator
    platforms (13x faster than scatter on v5e, measured) and the scatter
    build on CPU (where one-hot matmuls lose)."""
    if xla_backend(backend) == "matmul":
        return build_histograms_matmul(binned, grad, hess, node_ids,
                                       num_nodes, num_bins, sample_weight,
                                       max_rows=max_rows)
    # scatter drops masked rows natively; the max_rows bound is a no-op there
    return build_histograms(binned, grad, hess, node_ids, num_nodes, num_bins,
                            sample_weight)
