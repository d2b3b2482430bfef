"""Fused Pallas GBDT frontier kernel — bin-slot lookup + packed-int
accumulation + integer sibling subtraction + an in-kernel split-gain scan,
one VMEM-resident pass per node-frontier step (ISSUE 8).

Why: PR 5's quantized packed histograms cut hot-kernel operand traffic ~3x,
but the frontier step still runs as separate XLA ops — bin one-hot
materialization, histogram accumulation, sibling subtraction, and the
split-gain cumsum+argmax — with HBM round trips between every stage.  Snap
ML (arXiv:1803.06333) shows hierarchical GBDT training is bandwidth-bound
at exactly this seam.  This kernel streams row tiles through VMEM (the
Pallas grid pipeline double-buffers the HBM->VMEM block fetches, so tile
k+1's DMA rides under tile k's compute) and keeps every intermediate — the
bin-slot lookups, the packed per-tile partials, the assembled children
histograms, the dequantized gain tables — on chip.  Only two tensors ever
reach HBM per step: the ``(nodes, F, B, 3)`` int32 histogram (the next
level's parent / the psum / stored-carry operand, which the growers need
regardless) and a 9-float best-split record per (feature block, node).
The full one-hot operands and gain tables never materialize off-chip.

Layout support matrix (``_packed_layout`` from ``ops.histogram`` decides,
exactly as the scatter builder does):

    layout  in-kernel channels  operand dtype (onehot accum)
    all3    1  (grad+hess+count share one int32 lane)   int32
    2ch     2  (count rides the hessian lane)           int32 / int8*
    wide    3  (separate lanes)                         int8

    * int8 whenever the static lane magnitudes fit; the int8 path is the
      MXU operand contract inherited from ``build_histograms_matmul_quantized``.

Accumulation modes (static, chosen per backend):

- ``scatter`` — per-tile packed-lane scatter-add into the VMEM-resident
  accumulator.  The interpret-mode default: Pallas interpret lowers the
  grid to one compiled ``while_loop`` and the scatter to XLA's native
  scatter-add, which is the fastest CPU formulation (and the one the
  tier-1 bit-exactness gate runs).
- ``onehot`` — the hi/lo one-hot matmul formulation (the in-kernel twin of
  the XLA MXU builder): per feature, ``(N*C*HI, R) @ (R, LO)`` integer
  contractions.  The compiled-TPU default; Mosaic has no vector scatter.

Both modes accumulate exact integers, so outputs are bit-identical to
``build_histograms_quantized`` (tested across layouts, ragged tiles and
streamed per-tile accumulation) — under the Pallas INTERPRETER, which is
the only way this kernel has ever run.

Status on the TPU (v5e, jax 0.9.0, PR 22 chip run): the kernel does not
compile, so it is never an ``auto`` choice (``resolve_quantized_backend``);
an explicit ``backend="pallas"`` on a TPU raises.  The toolchain's words:

- default tiles (R=1024, FB=8): "The Pallas TPU lowering currently
  requires that the last two dimensions of your block shape are divisible
  by 8 and 128 respectively, or be equal to the respective dimensions of
  the overall array" — the ``(R, FB)`` binned block;
- ``feat_block=F`` (full lane dimension), builder mode: "Mosaic failed to
  compile TPU kernel: infer-vector-layout: unsupported shape cast …
  tpu.reshape (vector<1024x32xi1>) -> vector<1024x32x1xi1>";
- fused mode: "Unimplemented primitive in Pallas TPU lowering for
  KernelType.TC: cumsum".

ROADMAP D2 holds the redesign-or-delete decision.

VMEM tile-sizing rule — NOT validated by any compiler: with row tile R,
feature block FB, N frontier nodes and C lane channels, the rule counts
the binned tile (R*FB bytes), the one-hot operands (R*FB*(LO + N*C*HI)
operand bytes), and the accumulator (C*N*FB*B*4 bytes) at R=1024, FB=8.
It undercounts the ``(n_out, FB, B, 3)`` int32 output block: a minor
dimension of 3 is padded to 128 lanes in VMEM, so a (32, 8, 256, 3) block
holds ~33 MB per buffer, not the ~0.8 MB the rule counts.  A redesign
needs bins (not channels) minor.  Interpret mode uses large tiles
(R = (1<<23)/F — the XLA scatter builder's chunk rule, FB=F): the grid is
a while_loop, so fewer/fatter steps win, while the rule keeps the
per-step scatter intermediate at ~32 MB.

Split-gain contract: the in-kernel scan mirrors the growers' gain math
(dequantize -> f32 bin cumsum -> leaf_score with l1/l2 ->
min_data/min_hess/feat-mask/edge-mask validity -> first-max argmax) with
one deliberate difference: node totals come from the EXACT integer bin
sums (scaled once) instead of the f32 cumsum's last element, so totals are
consistent across feature blocks (the XLA path's totals carry cumsum
rounding).  Split decisions agree except at sub-ulp gain ties; the e2e
accuracy gates hold either way (tests/test_pallas_histogram.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.device import platform
from .histogram import _pack_lanes, _packed_layout, _unpack_lanes

_CHANNELS = {"all3": 1, "2ch": 2, "wide": 3}
_LO = 16  # lo one-hot width of the onehot accumulation mode

#: max frontier nodes (the kernel's N) the fused path accepts; the
#: level-wise grower statically falls back to the XLA scan for deeper
#: levels.  Sized from the module docstring's VMEM rule, which no compiler
#: has validated (see "Status on the TPU").
FUSED_MAX_NODES = 16


def builder_node_cap(num_bins: int) -> int:
    """Max ``num_nodes`` the BUILDER path accepts: 12 MiB over the module
    docstring's 36·FB·B bytes-per-node rule at FB=8 (a rule no compiler
    has validated — it ignores the lane padding of the 3-wide minor
    dimension).  ``FUSED_MAX_NODES`` gates the growers' fused-frontier
    calls; this cap gates everything else reaching
    ``build_histograms_pallas`` through the dispatcher (deep-level,
    sharded and streamed builds pass frontier widths up to 2^(D-1) nodes),
    which falls back to the XLA builders above it.  Static and
    platform-independent."""
    return max(1, (12 << 20) // (36 * 8 * num_bins))


def pallas_supported(num_bins: int, quant_bins: int = 16,
                     num_nodes: Optional[int] = None) -> bool:
    """Static support check for the fused kernel: callers fall back to the
    XLA builders (scatter/matmul) when this is False.  Pass ``num_nodes``
    on the builder path — the per-block VMEM resident set scales linearly
    with it (``builder_node_cap``)."""
    if not (2 <= num_bins <= 256 and 2 <= quant_bins <= 128):
        return False
    return num_nodes is None or num_nodes <= builder_node_cap(num_bins)


def _interpret_default() -> bool:
    # the Pallas interpreter (plain XLA) is the CPU path only: on a TPU the
    # kernel is handed to Mosaic, and platform() raises on anything else
    return platform() == "cpu"


def _plan(n: int, F: int, interpret: bool,
          tile_rows: Optional[int], feat_block: Optional[int]) -> Tuple[int, int]:
    """(row tile R, feature block FB) — the VMEM tile-sizing rule."""
    if tile_rows is None:
        if interpret:
            # interpret = one while_loop over the grid: few fat tiles win.
            # Same chunk rule as the XLA scatter builder — the per-step
            # (R*FB,) scatter intermediate stays ~32 MB while the grid
            # degenerates to a single step whenever n fits
            tile_rows = max(1024, (1 << 23) // max(F, 1))
        else:
            tile_rows = 1024
    if feat_block is None:
        feat_block = F if interpret else min(F, 8)
    return max(1, min(int(tile_rows), n)), max(1, min(int(feat_block), F))


def _lane_cap(mode: str, cbits: int, hbits: int, quant_bins: int) -> int:
    """Static max |channel value| — decides the onehot operand dtype."""
    qg_cap = max(1, quant_bins // 2)
    qh_cap = max(1, quant_bins - 1)
    KC, KH = 1 << cbits, 1 << hbits
    if mode == "all3":
        return (qg_cap * KH + qh_cap) * KC + 1
    if mode == "2ch":
        return max(qg_cap, qh_cap * KC + 1)
    return max(qg_cap, qh_cap, 1)


def _make_kernel(*, n, F, B, N, C, mode, cbits, hbits, R, FB, NR, accum,
                 subtract, gains, leaf_gate, l1, l2, min_data, min_hess,
                 op_dtype, HI, shift):
    """Build the kernel body for one static configuration.  Grid is
    (feature blocks, row tiles) with row tiles innermost; the packed
    accumulator lives in VMEM scratch and persists across the row-tile
    sweep of each feature block."""
    S = N * FB * B
    n_out = 2 * N if subtract else N

    def thresh(G):
        return jnp.sign(G) * jnp.maximum(jnp.abs(G) - l1, 0.0)

    def score(G, H):
        return thresh(G) ** 2 / (H + l2)

    def decode(acc):  # (C, N, FB, B) packed lanes -> (N, FB, B, 3) int32
        return jnp.stack(_unpack_lanes(acc, mode, cbits, hbits), axis=-1)

    def kernel(*refs):
        it = iter(refs)
        b_ref = next(it)
        lanes_ref = next(it)
        node_ref = next(it)
        parent_ref = next(it) if subtract else None
        sleft_ref = next(it) if subtract else None
        if gains:
            gsc_ref = next(it)
            hsc_ref = next(it)
            fmask_ref = next(it)
            edge_ref = next(it)
            dok_ref = next(it) if leaf_gate else None
        hist_ref = next(it)
        best_ref = next(it) if gains else None
        acc_ref = next(it)

        j = pl.program_id(0)
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        b32 = b_ref[...].astype(jnp.int32)                       # (R, FB)
        node = node_ref[0, :]                                    # (R,)
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (R, FB), 0)
        f_ids = jax.lax.broadcasted_iota(jnp.int32, (R, FB), 1)
        # ragged edges are masked in-kernel, never padded on the host:
        # rows past n (last row tile) and features past F (last feature
        # block) read block-padding garbage, which must not contribute
        valid = (node[:, None] >= 0) & (row_ids < n - i * R) \
            & (f_ids + j * FB < F)

        if accum == "scatter":
            seg = (node[:, None] * FB + f_ids) * B + b32
            seg = jnp.where(valid, seg, S).reshape(-1)           # OOB drops
            for c in range(C):
                vals = jnp.broadcast_to(lanes_ref[c, :][:, None],
                                        (R, FB)).reshape(-1)
                part = jnp.zeros((S,), jnp.int32).at[seg].add(vals,
                                                              mode="drop")
                acc_ref[c] += part.reshape(N, FB, B)
        else:
            hi = b32 >> shift
            lo = b32 & (_LO - 1)
            node_oh = (node[:, None] ==
                       jax.lax.broadcasted_iota(jnp.int32, (R, N), 1))
            w = jnp.stack([lanes_ref[c, :] for c in range(C)], axis=-1)
            wn = (node_oh[:, :, None] * w[:, None, :]).reshape(R, N * C)
            lo_oh = ((lo[:, :, None] ==
                      jax.lax.broadcasted_iota(jnp.int32, (R, FB, _LO), 2))
                     & valid[..., None]).astype(op_dtype)        # (R,FB,LO)
            hi_oh = (hi[:, :, None] ==
                     jax.lax.broadcasted_iota(jnp.int32, (R, FB, HI), 2))
            a = (hi_oh[:, :, None, :] *
                 wn[:, None, :, None].astype(op_dtype)) \
                .reshape(R, FB, N * C * HI)                      # (R,FB,NCH)
            for f in range(FB):
                m = jax.lax.dot_general(
                    a[:, f, :], lo_oh[:, f, :], (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)            # (NCH, LO)
                m = m.reshape(N, C, HI * _LO)[..., :B]
                acc_ref[:, :, f, :] += jnp.moveaxis(m, 1, 0)

        @pl.when(i == NR - 1)
        def _finish():
            hist_small = decode(acc_ref[...])                    # (N,FB,B,3)
            if subtract:
                parent = parent_ref[...]
                sib = parent - hist_small                        # exact ints
                sl = (sleft_ref[0, :] != 0)[:, None, None, None]
                hist_out = jnp.stack(
                    [jnp.where(sl, hist_small, sib),
                     jnp.where(sl, sib, hist_small)],
                    axis=1).reshape(n_out, FB, B, 3)
            else:
                hist_out = hist_small
            hist_ref[...] = hist_out
            if gains:
                gsc = gsc_ref[0, 0]
                hsc = hsc_ref[0, 0]
                # dequantize then f32 cumsum — the growers' exact op order,
                # so left-side stats match the XLA path bit for bit
                GL = jnp.cumsum(hist_out[..., 0].astype(jnp.float32) * gsc,
                                axis=-1)
                HL = jnp.cumsum(hist_out[..., 1].astype(jnp.float32) * hsc,
                                axis=-1)
                CL = jnp.cumsum(hist_out[..., 2].astype(jnp.float32),
                                axis=-1)
                # node totals from the EXACT integer sums (any one in-range
                # feature column carries every row once) — consistent
                # across feature blocks, unlike an f32 cumsum tail
                tg = jnp.sum(hist_out[:, 0, :, 0],
                             axis=-1).astype(jnp.float32) * gsc
                th = jnp.sum(hist_out[:, 0, :, 1],
                             axis=-1).astype(jnp.float32) * hsc
                tc = jnp.sum(hist_out[:, 0, :, 2],
                             axis=-1).astype(jnp.float32)
                GR = tg[:, None, None] - GL
                HR = th[:, None, None] - HL
                CR = tc[:, None, None] - CL
                gain = (score(GL, HL) + score(GR, HR)
                        - score(tg, th)[:, None, None])
                fcol = jax.lax.broadcasted_iota(jnp.int32, (1, FB, 1), 1) \
                    + j * FB
                ok = ((CL >= min_data) & (CR >= min_data)
                      & (HL >= min_hess) & (HR >= min_hess)
                      & (fmask_ref[0, :] != 0)[None, :, None]
                      & (edge_ref[...] != 0)[None]
                      & (fcol < F))
                if leaf_gate:
                    ok &= dok_ref[0, 0] != 0
                gain = jnp.where(ok, gain, -jnp.inf)
                flat = gain.reshape(n_out, FB * B)
                am = jnp.argmax(flat, axis=1)                    # first max

                def take(X):
                    return jnp.take_along_axis(X.reshape(n_out, FB * B),
                                               am[:, None], axis=1)[:, 0]

                best_ref[0] = jnp.stack(
                    [take(gain),
                     (am // B + j * FB).astype(jnp.float32),
                     (am % B).astype(jnp.float32),
                     take(GL), take(HL), take(CL), tg, th, tc], axis=-1)

    return kernel


def _frontier(binned, qg, qh, node_ids, num_nodes, num_bins, *, quant_bins,
              bound, gains, parent_hist=None, small_left=None, g_scale=None,
              h_scale=None, feat_mask=None, edge_ok=None, depth_ok=None,
              l1=0.0, l2=0.0, min_data=0.0, min_hess=0.0, interpret=None,
              accum=None, tile_rows=None, feat_block=None):
    n, F = binned.shape
    B, N = int(num_bins), int(num_nodes)
    if not pallas_supported(B, quant_bins):
        raise ValueError(f"pallas histogram kernel supports 2 <= num_bins "
                         f"<= 256 and quant_bins <= 128, got ({B}, "
                         f"{quant_bins})")
    if gains and N > FUSED_MAX_NODES:
        # the builder path has its own cap (builder_node_cap)
        raise ValueError(
            f"fused_frontier VMEM node cap exceeded: {N} frontier nodes > "
            f"FUSED_MAX_NODES={FUSED_MAX_NODES} — callers must fall back "
            "to the XLA gain scan (the growers gate per level)")
    qh_cap = max(1, quant_bins - 1)
    if n * qh_cap >= (1 << 31):
        raise ValueError("quantized histograms overflow int32 above "
                         f"{(1 << 31) // qh_cap} rows at {quant_bins} bins")
    interpret = _interpret_default() if interpret is None else bool(interpret)
    accum = accum or ("scatter" if interpret else "onehot")
    if accum not in ("scatter", "onehot"):
        raise ValueError("accum must be scatter|onehot")
    if accum == "scatter" and not interpret:
        # fail at dispatch with a name, not deep inside kernel compilation:
        # Mosaic has no vector scatter, the compiled path must use onehot
        raise ValueError("accum='scatter' is interpret-only (Mosaic has no "
                         "vector scatter) — use accum='onehot' on TPU")
    R, FB = _plan(n, F, interpret, tile_rows, feat_block)
    NR, NFB = pl.cdiv(n, R), pl.cdiv(F, FB)
    mode, cbits, hbits = _packed_layout(bound, quant_bins)
    C = _CHANNELS[mode]
    cap = _lane_cap(mode, cbits, hbits, quant_bins)
    op_dtype = jnp.int8 if (accum == "onehot" and cap <= 127) else jnp.int32
    HI = pl.cdiv(B, _LO)
    shift = _LO.bit_length() - 1

    subtract = parent_hist is not None
    leaf_gate = depth_ok is not None
    n_out = 2 * N if subtract else N

    lanes = jnp.stack(_pack_lanes(qg, qh, mode, cbits, hbits))     # (C, n)
    node2 = node_ids.astype(jnp.int32)[None, :]                    # (1, n)

    inputs = [binned, lanes, node2]
    in_specs = [
        pl.BlockSpec((R, FB), lambda jj, ii: (ii, jj)),
        pl.BlockSpec((C, R), lambda jj, ii: (0, ii)),
        pl.BlockSpec((1, R), lambda jj, ii: (0, ii)),
    ]
    if subtract:
        if small_left is None:
            raise ValueError("subtract mode needs small_left")
        inputs += [parent_hist.astype(jnp.int32),
                   small_left.astype(jnp.int32)[None, :]]
        in_specs += [
            pl.BlockSpec((N, FB, B, 3), lambda jj, ii: (0, jj, 0, 0)),
            pl.BlockSpec((1, N), lambda jj, ii: (0, 0)),
        ]
    if gains:
        if g_scale is None or h_scale is None or feat_mask is None \
                or edge_ok is None:
            raise ValueError("gain scan needs g_scale/h_scale/feat_mask/"
                             "edge_ok")
        inputs += [jnp.asarray(g_scale, jnp.float32).reshape(1, 1),
                   jnp.asarray(h_scale, jnp.float32).reshape(1, 1),
                   feat_mask.astype(jnp.int32)[None, :],
                   edge_ok.astype(jnp.int32)]
        in_specs += [
            pl.BlockSpec((1, 1), lambda jj, ii: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda jj, ii: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, FB), lambda jj, ii: (0, jj)),
            pl.BlockSpec((FB, B), lambda jj, ii: (jj, 0)),
        ]
        if leaf_gate:
            inputs.append(jnp.asarray(depth_ok, jnp.int32).reshape(1, 1))
            in_specs.append(pl.BlockSpec((1, 1), lambda jj, ii: (0, 0),
                                         memory_space=pltpu.SMEM))

    out_shape = [jax.ShapeDtypeStruct((n_out, F, B, 3), jnp.int32)]
    out_specs = [pl.BlockSpec((n_out, FB, B, 3),
                              lambda jj, ii: (0, jj, 0, 0))]
    if gains:
        out_shape.append(jax.ShapeDtypeStruct((NFB, n_out, 9), jnp.float32))
        out_specs.append(pl.BlockSpec((1, n_out, 9),
                                      lambda jj, ii: (jj, 0, 0)))

    kernel = _make_kernel(
        n=n, F=F, B=B, N=N, C=C, mode=mode, cbits=cbits, hbits=hbits, R=R,
        FB=FB, NR=NR, accum=accum, subtract=subtract, gains=gains,
        leaf_gate=leaf_gate, l1=float(l1), l2=float(l2),
        min_data=float(min_data), min_hess=float(min_hess),
        op_dtype=op_dtype, HI=HI, shift=shift)

    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))
    # pallas-site: compiled inside the growers'/bench's instrumented_jit
    # programs — compile booking rides lightgbm.grower/iter/multi_iter
    outs = pl.pallas_call(
        kernel,
        grid=(NFB, NR),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((C, N, FB, B), jnp.int32)],
        interpret=interpret,
        **kw,
    )(*inputs)
    if not gains:
        return outs[0]
    hist, best = outs
    # cross-block reduction: first-max-wins over feature blocks replicates
    # the XLA path's flat argmax ordering (lower feature index wins ties)
    jb = jnp.argmax(best[:, :, 0], axis=0)
    win = jnp.take_along_axis(best, jb[None, :, None], axis=0)[0]
    return hist, (win[:, 0], win[:, 1].astype(jnp.int32),
                  win[:, 2].astype(jnp.int32), win[:, 3:6], win[:, 6:9])


def build_histograms_pallas(binned, qg, qh, node_ids, num_nodes, num_bins,
                            quant_bins: int = 16,
                            node_rows_bound: Optional[int] = None,
                            max_rows: Optional[int] = None,
                            interpret: Optional[bool] = None,
                            accum: Optional[str] = None,
                            tile_rows: Optional[int] = None,
                            feat_block: Optional[int] = None):
    """Drop-in quantized histogram builder on the fused Pallas kernel.

    Same contract as ``ops.histogram.build_histograms_quantized`` — returns
    ``(num_nodes, F, B, 3)`` **int32** ``[sum_qg, sum_qh, count]``, bit-exact
    (integer sums) with the scatter/matmul builders, so it composes with
    the growers' integer sibling subtraction, ``train_streamed``'s per-tile
    partial accumulation, and ``collectives.histogram_psum`` unchanged.
    ``max_rows`` is accepted for signature parity and ignored (masked rows
    drop in-kernel; like the scatter builder, no scan is truncated)."""
    n = binned.shape[0]
    cap = builder_node_cap(num_bins)
    if num_nodes > cap:
        raise ValueError(
            f"pallas builder VMEM node cap exceeded: {num_nodes} nodes > "
            f"{cap} at {num_bins} bins — use the XLA builders "
            "(build_quantized falls back automatically)")
    bound = max(1, min(n, int(node_rows_bound or n), int(max_rows or n)))
    return _frontier(binned, qg, qh, node_ids, num_nodes, num_bins,
                     quant_bins=quant_bins, bound=bound, gains=False,
                     interpret=interpret, accum=accum, tile_rows=tile_rows,
                     feat_block=feat_block)


def fused_frontier(binned, qg, qh, node_ids, num_nodes, num_bins,
                   g_scale, h_scale, feat_mask, edge_ok, *,
                   quant_bins: int = 16, l1: float = 0.0, l2: float = 0.0,
                   min_data: float = 0.0, min_hess: float = 0.0,
                   parent_hist=None, small_left=None, depth_ok=None,
                   node_rows_bound: Optional[int] = None,
                   interpret: Optional[bool] = None,
                   accum: Optional[str] = None,
                   tile_rows: Optional[int] = None,
                   feat_block: Optional[int] = None):
    """One fused frontier step: histogram build (+ optional integer sibling
    subtraction against ``parent_hist``) feeding the in-kernel split-gain
    scan.

    Modes:

    - **direct** (``parent_hist=None``): builds ``num_nodes`` frontier
      histograms and scans their best splits — the root step of both
      growers.
    - **subtract** (``parent_hist`` = ``(num_nodes, F, B, 3)`` int32 parent
      histograms, ``small_left`` = ``(num_nodes,)`` bool): ``node_ids``
      address each parent's SMALLER child; the sibling comes from exact
      integer subtraction in VMEM and both children are emitted interleaved
      ``(2*num_nodes, F, B, 3)`` exactly as the level-wise grower assembles
      them (child ``2k`` is the small child iff ``small_left[k]``).

    ``depth_ok`` (optional traced bool) gates every candidate — the
    leaf-wise grower's depth cap.  Returns ``(hist, (best_gain, best_feat,
    best_bin, left_stats, node_totals))`` with per-node f32 stats; callers
    needing LightGBM's full bookkeeping read left/total (G, H, C) straight
    from the tuple instead of re-scanning the histogram."""
    n = binned.shape[0]
    bound = max(1, min(n, int(node_rows_bound or n)))
    return _frontier(binned, qg, qh, node_ids, num_nodes, num_bins,
                     quant_bins=quant_bins, bound=bound, gains=True,
                     parent_hist=parent_hist, small_left=small_left,
                     g_scale=g_scale, h_scale=h_scale, feat_mask=feat_mask,
                     edge_ok=edge_ok, depth_ok=depth_ok, l1=l1, l2=l2,
                     min_data=min_data, min_hess=min_hess,
                     interpret=interpret, accum=accum, tile_rows=tile_rows,
                     feat_block=feat_block)
