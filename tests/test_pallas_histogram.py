"""Fused Pallas histogram kernel (ISSUE 8): interpret-mode bit-exactness
vs the XLA quantized builders, fused split-gain parity, dispatcher/hatch
semantics, and composition with the streamed and sharded paths.

Contract layers:

1. **Integer exactness** — the kernel accumulates the same packed lanes as
   ``build_histograms_quantized`` and decodes identically, so its
   histograms must match BIT FOR BIT across every lane layout
   (all3/2ch/wide), both accumulation modes (scatter / one-hot matmul),
   ragged last tiles, ragged feature blocks, masked rows, per-tile
   streamed accumulation, and the post-psum sharded build.
2. **Fused frontier parity** — the in-kernel sibling subtraction must
   assemble exactly what the level-wise grower assembles, and the
   in-kernel split-gain scan must pick the same (feature, bin) as the XLA
   ``split_gains`` path (gains agree to f32 tolerance: the fused node
   totals are exact integer sums where the XLA path carries f32 cumsum
   rounding — documented in ops/pallas_histogram.py).
3. **End to end** — training with the pallas backend holds the same
   committed accuracy behavior as the scatter/matmul paths (quick gates in
   tier-1; the full CSV sweeps ride the slow lane), and the streamed
   driver produces the IDENTICAL booster either backend (per-tile integer
   partials are bit-exact, and every split decision is a function of
   them).
"""
import os

import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.core.schema import vector_column

RES = os.path.join(os.path.dirname(__file__), "resources", "benchmarks")


def _hist_inputs(n=5000, f=9, b=255, p=8, seed=0, balanced=False):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    binned = jnp.asarray(rng.integers(0, b, (n, f)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.01, 1, n).astype(np.float32))
    if balanced:
        node = jnp.asarray((np.arange(n) % p).astype(np.int32))
    else:
        node = jnp.asarray(rng.integers(-1, p, n).astype(np.int32))
    return binned, g, h, node


def _gain_reference(hist, gs, hs, fmask, edge_ok, l1, l2, min_data,
                    min_hess):
    """The growers' XLA split-gain scan (non-categorical), inlined."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops.histogram import dequantize_histogram
    hd = dequantize_histogram(hist, gs, hs)
    cum = jnp.cumsum(hd, axis=2)
    tot = cum[:, :1, -1, :]
    GL, HL, CL = cum[..., 0], cum[..., 1], cum[..., 2]
    Gp, Hp, Cp = tot[..., 0], tot[..., 1], tot[..., 2]
    GR, HR, CR = Gp[:, :, None] - GL, Hp[:, :, None] - HL, Cp[:, :, None] - CL

    def score(G, H):
        t = jnp.sign(G) * jnp.maximum(jnp.abs(G) - l1, 0.0)
        return t ** 2 / (H + l2)

    gain = score(GL, HL) + score(GR, HR) - score(Gp, Hp)[:, :, None]
    ok = ((CL >= min_data) & (CR >= min_data) & (HL >= min_hess)
          & (HR >= min_hess) & fmask[None, :, None] & edge_ok[None])
    gain = jnp.where(ok, gain, -jnp.inf)
    B = hist.shape[2]
    flat = gain.reshape(hist.shape[0], -1)
    best = jnp.argmax(flat, axis=1)
    bg = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    return bg, best // B, best % B


# ------------------------------------------------------------ exactness

def test_pallas_build_bit_exact_all_layouts():
    """all3 / 2ch / wide lane layouts (chosen by the static node-row
    bound, same decision table as the scatter builder) must all decode to
    the scatter builder's exact integer sums."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.ops import pallas_histogram as PH
    n, p = 16384, 128
    binned, g, h, node = _hist_inputs(n=n, p=p, balanced=True, seed=1)
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=3)
    # the builders clamp the bound to n, so 'wide' needs the full-n bound
    bounds = {"all3": n // p, "2ch": 4000, "wide": None}
    for want, nb in bounds.items():
        assert H._packed_layout(min(n, nb or n), 16)[0] == want
        ref = H.build_histograms_quantized(binned, qg, qh, node, p, 255,
                                           node_rows_bound=nb)
        got = PH.build_histograms_pallas(binned, qg, qh, node, p, 255,
                                         node_rows_bound=nb)
        assert got.dtype == jnp.int32
        assert bool(jnp.all(ref == got)), want


def test_pallas_build_ragged_tiles_masked_rows_and_feature_blocks():
    """Row tiles and feature blocks are masked in-kernel, never padded on
    the host: ragged last tiles, ragged feature blocks and bagging-masked
    rows (node < 0) must all stay bit-exact."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.ops import pallas_histogram as PH
    binned, g, h, node = _hist_inputs(n=1537, f=10, seed=2)
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=5)
    ref = H.build_histograms_quantized(binned, qg, qh, node, 8, 255)
    for tile_rows, feat_block in ((512, 4), (600, 10), (1537, 3),
                                  (8192, 7)):
        got = PH.build_histograms_pallas(binned, qg, qh, node, 8, 255,
                                         tile_rows=tile_rows,
                                         feat_block=feat_block)
        assert bool(jnp.all(ref == got)), (tile_rows, feat_block)


def test_pallas_onehot_accum_matches_scatter_accum():
    """The one-hot hi/lo matmul accumulation (the compiled-TPU/Mosaic
    formulation) must produce the same exact integers as the scatter
    accumulation the interpreter defaults to — both lane-layout families
    and the int8 operand fast path (wide) included."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.ops import pallas_histogram as PH
    binned, g, h, node = _hist_inputs(n=2048, f=5, b=127, p=4, seed=4)
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=7)
    for nb in (256, None):   # all3-ish packed lanes vs wide int8 operands
        ref = PH.build_histograms_pallas(binned, qg, qh, node, 4, 127,
                                         node_rows_bound=nb,
                                         accum="scatter")
        got = PH.build_histograms_pallas(binned, qg, qh, node, 4, 127,
                                         node_rows_bound=nb, accum="onehot",
                                         tile_rows=512, feat_block=3)
        assert bool(jnp.all(ref == got)), nb
        xla = H.build_histograms_quantized(binned, qg, qh, node, 4, 127,
                                          node_rows_bound=nb)
        assert bool(jnp.all(xla == got)), nb


def test_streamed_tile_accumulation_bit_exact():
    """``train_streamed``'s composition contract: per-tile pallas partials
    built under SHARED quantization scales accumulate bit-exactly to the
    monolithic build — same invariant the XLA builders hold (ISSUE 7)."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.ops import pallas_histogram as PH
    binned, g, h, node = _hist_inputs(n=3000, f=6, seed=6)
    qg, qh, gs, hs = H.quantize_gradients(g, h, 16, seed=9)
    mono = PH.build_histograms_pallas(binned, qg, qh, node, 8, 255)
    for T in (700, 1000, 3000):
        acc = jnp.zeros((8, 6, 255, 3), jnp.int32)
        for lo in range(0, 3000, T):
            hi = min(lo + T, 3000)
            acc = acc + PH.build_histograms_pallas(
                binned[lo:hi], qg[lo:hi], qh[lo:hi], node[lo:hi], 8, 255,
                node_rows_bound=T)
        assert bool(jnp.all(acc == mono)), T
    assert bool(jnp.all(
        mono == H.build_histograms_quantized(binned, qg, qh, node, 8, 255)))


def test_pallas_shard_psum_matches_global_build(mesh8):
    """Multi-host contract: per-shard pallas builds + the packed
    ``histogram_psum`` equal the single-shard build exactly."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.ops import pallas_histogram as PH
    from mmlspark_tpu.parallel.collectives import histogram_psum
    from mmlspark_tpu.parallel.mesh import AXIS_DATA

    n, f, b, p = 800, 4, 63, 4
    binned, g, h, node = _hist_inputs(n=n, f=f, b=b, p=p, seed=2,
                                      balanced=True)
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=1)

    def local_then_psum(bq, qgq, qhq, nq):
        local = PH.build_histograms_pallas(bq, qgq, qhq, nq, p, b,
                                           quant_bins=16)
        return histogram_psum(local, AXIS_DATA, row_bound=n, quant_bins=16)

    sharded = jax.jit(jax.shard_map(
        local_then_psum, mesh=mesh8,
        in_specs=(P(AXIS_DATA),) * 4, out_specs=P(),
        check_vma=False))(binned, qg, qh, node)
    ref = H.build_histograms_quantized(binned, qg, qh, node, p, b,
                                       quant_bins=16)
    assert bool(jnp.all(sharded == ref))


# ------------------------------------------------------- fused frontier

def test_fused_frontier_direct_matches_xla_split():
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.ops import pallas_histogram as PH
    binned, g, h, node = _hist_inputs(seed=0)
    f, b, p = 9, 255, 8
    qg, qh, gs, hs = H.quantize_gradients(g, h, 16, seed=3)
    fmask = jnp.ones((f,), bool)
    edge_ok = jnp.asarray(np.concatenate(
        [np.ones((f, b - 1), bool), np.zeros((f, 1), bool)], axis=1))
    kw = dict(l1=0.0, l2=0.1, min_data=20.0, min_hess=1e-3)
    ref = H.build_histograms_quantized(binned, qg, qh, node, p, b)
    rg, rf, rb = _gain_reference(ref, gs, hs, fmask, edge_ok, **kw)
    # default plan (one feature block) AND a compiled-TPU-shaped plan with
    # ragged feature blocks (9 feats / FB=4) + ragged row tiles — the
    # cross-block winner reduce, the j*FB feature remap and the fcol<F
    # last-block masking all genuinely execute
    for tiles in ({}, dict(tile_rows=1024, feat_block=4)):
        hist, (bg, bf, bb, left3, tot3) = PH.fused_frontier(
            binned, qg, qh, node, p, b, gs, hs, fmask, edge_ok,
            quant_bins=16, **kw, **tiles)
        assert bool(jnp.all(hist == ref)), tiles
        assert bool(jnp.all(bf == rf)) and bool(jnp.all(bb == rb)), tiles
        assert bool(jnp.allclose(bg, rg, rtol=1e-4, atol=1e-6)), tiles
        # left stats at the winner come from the same f32 cumsum the XLA
        # path reads — consistent with the totals (left + right = tot)
        assert bool(jnp.all(left3[:, 2] <= tot3[:, 2] + 1e-4)), tiles


def test_fused_frontier_sibling_subtraction_parity():
    """Subtract mode must assemble EXACTLY what the level-wise grower
    assembles: small child rebuilt, sibling = parent - small (integer
    space), children interleaved by ``small_left``."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.ops import pallas_histogram as PH
    n, f, b, P = 4000, 6, 127, 4
    binned, g, h, _ = _hist_inputs(n=n, f=f, b=b, seed=1)
    rng = np.random.default_rng(11)
    qg, qh, gs, hs = H.quantize_gradients(g, h, 16, seed=5)
    node_parent = jnp.asarray((np.arange(n) % P).astype(np.int32))
    in_small = jnp.asarray(rng.random(n) < 0.45)
    node_small = jnp.where(in_small, node_parent, -1)
    small_left = jnp.asarray(rng.random(P) < 0.5)

    parent = H.build_histograms_quantized(binned, qg, qh, node_parent, P, b)
    hs_small = H.build_histograms_quantized(binned, qg, qh, node_small, P, b)
    sib = parent - hs_small
    sl4 = small_left[:, None, None, None]
    ref = jnp.stack([jnp.where(sl4, hs_small, sib),
                     jnp.where(sl4, sib, hs_small)],
                    axis=1).reshape(2 * P, f, b, 3)

    fmask = jnp.ones((f,), bool)
    edge_ok = jnp.asarray(np.concatenate(
        [np.ones((f, b - 1), bool), np.zeros((f, 1), bool)], axis=1))
    kw = dict(l1=0.05, l2=1.0, min_data=10.0, min_hess=1e-3)
    hist, (bg, bf, bb, left3, tot3) = PH.fused_frontier(
        binned, qg, qh, node_small, P, b, gs, hs, fmask, edge_ok,
        quant_bins=16, parent_hist=parent, small_left=small_left, **kw)
    assert bool(jnp.all(hist == ref))
    rg, rf, rb = _gain_reference(ref, gs, hs, fmask, edge_ok, **kw)
    assert bool(jnp.all(bf == rf)) and bool(jnp.all(bb == rb))
    assert bool(jnp.allclose(bg, rg, rtol=1e-4, atol=1e-6))


def test_fused_frontier_masks_and_depth_gate():
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.ops import pallas_histogram as PH
    binned, g, h, node = _hist_inputs(n=2000, f=6, b=63, p=2, seed=3)
    qg, qh, gs, hs = H.quantize_gradients(g, h, 16, seed=1)
    fmask = jnp.asarray(np.array([1, 0, 1, 0, 1, 0], bool))
    edge_ok = jnp.asarray(np.concatenate(
        [np.ones((6, 62), bool), np.zeros((6, 1), bool)], axis=1))
    kw = dict(quant_bins=16, l1=0.0, l2=1.0, min_data=5.0, min_hess=1e-3)
    _, (bg, bf, bb, _, _) = PH.fused_frontier(
        binned, qg, qh, node, 2, 63, gs, hs, fmask, edge_ok, **kw)
    # winners respect the feature mask and never land on the NaN bin
    assert bool(jnp.all(fmask[bf]))
    assert bool(jnp.all(bb < 62))
    # traced depth gate off -> every candidate invalid, argmax parks at 0
    _, (bg2, bf2, bb2, _, _) = PH.fused_frontier(
        binned, qg, qh, node, 2, 63, gs, hs, fmask, edge_ok,
        depth_ok=jnp.bool_(False), **kw)
    assert bool(jnp.all(jnp.isneginf(bg2)))
    assert bool(jnp.all(bf2 == 0)) and bool(jnp.all(bb2 == 0))


# ------------------------------------------------------------ dispatcher

def test_backend_resolution(monkeypatch):
    from mmlspark_tpu.ops import histogram as H
    monkeypatch.delenv("MMLSPARK_TPU_HIST_BACKEND", raising=False)
    # CPU auto stays on the scatter build; the TPU auto choice is the XLA
    # int8 MXU build — the fused kernel is never chosen automatically (the
    # Pallas TPU lowering refuses it, ROADMAP D2)
    assert H.resolve_quantized_backend("auto") == "scatter"
    monkeypatch.setattr(H, "platform", lambda: "tpu")
    assert H.resolve_quantized_backend("auto") == "matmul"
    # the kernel stays reachable by explicit request, argument or env
    assert H.resolve_quantized_backend("pallas") == "pallas"
    monkeypatch.setenv("MMLSPARK_TPU_HIST_BACKEND", "pallas")
    assert H.resolve_quantized_backend("auto") == "pallas"
    # an explicit argument beats the env
    assert H.resolve_quantized_backend("matmul") == "matmul"


def test_interpreter_is_the_cpu_path_only(monkeypatch):
    """On a TPU the kernel is handed to the compiler (compile or raise,
    never interpret); a third, unknown platform is an error."""
    import jax
    from mmlspark_tpu.ops import pallas_histogram as PH
    from mmlspark_tpu.utils import device
    assert PH._interpret_default() is True            # tier-1 runs on CPU
    monkeypatch.setattr(PH, "platform", lambda: "tpu")
    assert PH._interpret_default() is False
    monkeypatch.undo()
    monkeypatch.setattr(jax, "default_backend", lambda: "mystery")
    with pytest.raises(RuntimeError, match="unsupported JAX platform"):
        device.platform()
    with pytest.raises(RuntimeError, match="unsupported JAX platform"):
        PH._interpret_default()


def test_backend_env_is_part_of_the_jit_cache_key(monkeypatch):
    """Every histogram env knob must key the growers' jit caches — a
    cached program must never keep serving a previously-selected
    configuration (the _resolve_hist_backend contract)."""
    from mmlspark_tpu.lightgbm.core import _resolve_hist_backend
    monkeypatch.delenv("MMLSPARK_TPU_HIST_BACKEND", raising=False)
    base = _resolve_hist_backend()
    monkeypatch.setenv("MMLSPARK_TPU_HIST_BACKEND", "pallas")
    assert _resolve_hist_backend() != base


def test_dispatcher_routes_and_falls_back(monkeypatch):
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    binned, g, h, node = _hist_inputs(n=1200, f=4, b=63, p=3, seed=8)
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=2)
    ref = H.build_histograms_quantized(binned, qg, qh, node, 3, 63)
    got = H.build_quantized(binned, qg, qh, node, 3, 63, backend="pallas")
    assert bool(jnp.all(ref == got))
    # unsupported quantization width -> clean fallback to the XLA builders
    qg2, qh2, _, _ = H.quantize_gradients(g, h, 16, seed=2)
    out = H.build_quantized(binned, qg2, qh2, node, 3, 63,
                            backend="pallas", quant_bins=256)
    assert bool(jnp.all(out == H.build_histograms_quantized(
        binned, qg2, qh2, node, 3, 63, quant_bins=256)))
    # the float dispatcher no longer raises on 'pallas': the integer fused
    # kernel lives on the quantized path, float requests fall back cleanly
    f32 = H.build(binned, g, h, node, 3, 63, backend="pallas")
    assert bool(jnp.allclose(
        f32, H.build(binned, g, h, node, 3, 63, backend="scatter")))


def test_dispatcher_falls_back_above_vmem_node_cap():
    """Deep-level / sharded / streamed builds pass frontier widths up to
    2^(D-1) nodes; the compiled kernel's per-block VMEM resident set
    scales linearly with nodes, so the dispatcher must fall back to the
    XLA builders above builder_node_cap (the direct builder raises)."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.ops import pallas_histogram as P
    b = 63
    cap = P.builder_node_cap(b)
    assert P.pallas_supported(b, 16, num_nodes=cap)
    assert not P.pallas_supported(b, 16, num_nodes=cap + 1)
    assert not P.pallas_supported(256, 16, num_nodes=P.builder_node_cap(256) + 1)
    p = cap + 1
    binned, g, h, node = _hist_inputs(n=4 * p, f=3, b=b, p=p, seed=9)
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=4)
    got = H.build_quantized(binned, qg, qh, node, p, b, backend="pallas")
    ref = H.build_histograms_quantized(binned, qg, qh, node, p, b)
    assert bool(jnp.all(ref == got))
    with pytest.raises(ValueError, match="node cap"):
        P.build_histograms_pallas(binned, qg, qh, node, p, b)
    # the fused path has its own (smaller) cap — an over-wide frontier must
    # fail at dispatch with a name, not as a Mosaic VMEM OOM on chip
    wide = P.FUSED_MAX_NODES + 1
    with pytest.raises(ValueError, match="FUSED_MAX_NODES"):
        P.fused_frontier(binned, qg, qh, node % wide, wide, b,
                         1.0, 1.0, jnp.ones((3,), bool),
                         jnp.ones((3, b), bool))
    # compiled Mosaic has no vector scatter: reject at argument validation
    with pytest.raises(ValueError, match="interpret-only"):
        P.build_histograms_pallas(binned, qg, qh, node % 2, 2, b,
                                  accum="scatter", interpret=False)


# ------------------------------------------------------------ end to end

def _frame(X, y):
    return DataFrame.from_dict({"features": vector_column(list(X)),
                                "label": y.astype(float)}, 2)


def test_e2e_training_parity_and_phase_labels(monkeypatch):
    """Both growers train through the fused frontier path (env-forced
    pallas backend, interpret mode on CPU) and hold the scatter path's
    accuracy; the phase histogram books the 'pallas' backend label."""
    from mmlspark_tpu.lightgbm import GBDTParams, train
    from mmlspark_tpu.observability import get_registry
    rng = np.random.default_rng(7)
    X = rng.normal(size=(2000, 10)).astype(np.float32)
    y = (X[:, 0] * 2 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
         + rng.normal(scale=0.5, size=2000) > 0).astype(np.float32)

    def acc(backend, **kw):
        monkeypatch.setenv("MMLSPARK_TPU_HIST_BACKEND", backend)
        monkeypatch.setenv("MMLSPARK_TPU_HIST_QUANT", "1")
        r = train(X, y, GBDTParams(num_iterations=8, objective="binary",
                                   seed=3, **kw))
        return float(((r.booster.predict(X) > 0.5) == (y > 0)).mean())

    for kw in (dict(max_depth=4),                       # level-wise
               dict(num_leaves=11, min_data_in_leaf=5)):  # leaf-wise
        a_pl = acc("pallas", **kw)
        a_sc = acc("scatter", **kw)
        assert a_pl >= a_sc - 0.02, (kw, a_pl, a_sc)
    fam = get_registry().family("mmlspark_lightgbm_phase_seconds")
    keys = {k for k, _ in fam._snapshot()}
    assert ("histogram_split_update", "pallas", "1") in keys


def test_deep_level_fused_to_xla_handoff(monkeypatch):
    """Deep levels past FUSED_MAX_NODES statically take the XLA branch —
    consuming the prev_hist/small_left the FUSED branch produced at the
    level before.  A handoff bug (wrong child interleaving, stale
    small_left) corrupts every deep tree only when pallas is engaged.
    FUSED_MAX_NODES is lowered to 2 so the crossing happens inside a
    cheap depth-4 program (at the real cap the first XLA level is depth 7
    — a ~20s trace; the grower's gate reads the module attribute at trace
    time, so this exercises the identical branch structure)."""
    from mmlspark_tpu.lightgbm import GBDTParams, train
    from mmlspark_tpu.ops import pallas_histogram as PH
    monkeypatch.setattr(PH, "FUSED_MAX_NODES", 2)
    # depth 4: levels 0-2 run fused (parents 1, 1, 2 <= 2), level 3
    # (8 nodes, 4 parents > 2) takes the XLA branch
    assert 2 ** (4 - 1) // 2 > PH.FUSED_MAX_NODES
    rng = np.random.default_rng(23)
    X = rng.normal(size=(600, 4)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] > 0).astype(np.float32)
    monkeypatch.setenv("MMLSPARK_TPU_HIST_QUANT", "1")

    def acc(backend):
        monkeypatch.setenv("MMLSPARK_TPU_HIST_BACKEND", backend)
        r = train(X, y, GBDTParams(num_iterations=2, max_depth=4,
                                   min_data_in_leaf=2, max_bin=32,
                                   objective="binary", seed=9))
        return float(((r.booster.predict(X) > 0.5) == (y > 0)).mean())

    a_pl, a_sc = acc("pallas"), acc("scatter")
    assert a_pl > 0.8, a_pl
    assert abs(a_pl - a_sc) <= 0.03, (a_pl, a_sc)


def test_float_path_never_labels_pallas(monkeypatch):
    """Incident combo: explicit backend=pallas with quantization forced
    OFF runs the FLOAT builders (build() maps 'pallas' to scatter/matmul
    — the fused kernel is integer-only), so the phase label must name
    what actually ran, not the requested backend."""
    from mmlspark_tpu.lightgbm import GBDTParams, train
    from mmlspark_tpu.observability import get_registry
    monkeypatch.setenv("MMLSPARK_TPU_HIST_BACKEND", "pallas")
    monkeypatch.setenv("MMLSPARK_TPU_HIST_QUANT", "0")
    rng = np.random.default_rng(11)
    X = rng.normal(size=(400, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    train(X, y, GBDTParams(num_iterations=2, max_depth=3, seed=1,
                           objective="binary"))
    fam = get_registry().family("mmlspark_lightgbm_phase_seconds")
    quant0 = {k for k, _ in fam._snapshot() if k[2] == "0"}
    assert quant0, "float run booked no phases"
    assert all(k[1] != "pallas" for k in quant0), quant0


def test_streamed_training_identical_across_backends(monkeypatch):
    """Out-of-core composition: the pallas per-tile builds are bit-exact,
    and every split decision downstream is a pure function of the
    accumulated integers — so the streamed driver must produce the
    IDENTICAL booster with either backend."""
    from mmlspark_tpu.lightgbm import GBDTParams, train_streamed
    rng = np.random.default_rng(17)
    X = rng.normal(size=(3000, 6)).astype(np.float32)
    y = (3 * X[:, 0] - 2 * X[:, 1] + X[:, 2] ** 2
         + rng.normal(scale=0.3, size=3000)).astype(np.float32)
    boosters = {}
    monkeypatch.setenv("MMLSPARK_TPU_HIST_QUANT", "1")
    for backend in ("scatter", "pallas"):
        monkeypatch.setenv("MMLSPARK_TPU_HIST_BACKEND", backend)
        r = train_streamed(X, y, GBDTParams(num_iterations=4, max_depth=4,
                                            objective="regression", seed=3),
                           tile_rows=700)
        boosters[backend] = r.booster
    a, b = boosters["scatter"], boosters["pallas"]
    np.testing.assert_array_equal(a.split_feature, b.split_feature)
    np.testing.assert_array_equal(a.threshold_bin, b.threshold_bin)
    np.testing.assert_array_equal(a.leaf_value, b.leaf_value)


def _split(X, y, seed=5):
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(y))
    cut = int(len(y) * 0.75)
    tr, te = order[:cut], order[cut:]
    return X[tr], X[te], y[tr], y[te]


@pytest.mark.slow
def test_pallas_classifier_holds_committed_benchmarks(monkeypatch):
    """The committed benchmarks_VerifyLightGBMClassifier sweep with the
    fused pallas backend forced must hold the SAME baselines at the SAME
    precisions — the ISSUE 8 accuracy acceptance gate."""
    from mmlspark_tpu.testing import Benchmarks
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    from tests.test_benchmark_regression import (MODES,
                                                 _datasets_classification)
    monkeypatch.setenv("MMLSPARK_TPU_HIST_BACKEND", "pallas")
    monkeypatch.setenv("MMLSPARK_TPU_HIST_QUANT", "1")
    bench = Benchmarks(os.path.join(
        RES, "benchmarks_VerifyLightGBMClassifier.csv"))
    if not os.path.exists(bench.baseline_path):
        pytest.skip("no committed classifier baseline to hold")
    for ds_name, (X, y) in _datasets_classification().items():
        for mode in MODES:
            clf = LightGBMClassifier().set_params(
                num_iterations=30, min_data_in_leaf=5, boosting_type=mode,
                seed=42, use_quantized_grad=True)
            Xtr, Xte, ytr, yte = _split(X, y)
            model = clf.fit(_frame(Xtr, ytr))
            pred = model.transform(_frame(Xte, yte)).collect()["prediction"]
            bench.add(f"LightGBMClassifier_{ds_name}_{mode}",
                      float((pred == yte).mean()), 0.07, True)
    bench.verify()


@pytest.mark.slow
def test_pallas_regressor_holds_committed_benchmarks(monkeypatch):
    from mmlspark_tpu.testing import Benchmarks
    from mmlspark_tpu.lightgbm import LightGBMRegressor
    from tests.test_benchmark_regression import _datasets_regression
    monkeypatch.setenv("MMLSPARK_TPU_HIST_BACKEND", "pallas")
    monkeypatch.setenv("MMLSPARK_TPU_HIST_QUANT", "1")
    bench = Benchmarks(os.path.join(
        RES, "benchmarks_VerifyLightGBMRegressor.csv"))
    if not os.path.exists(bench.baseline_path):
        pytest.skip("no committed regressor baseline to hold")
    for ds_name, (X, y) in _datasets_regression().items():
        for mode in ["gbdt", "rf", "dart", "goss"]:
            reg = LightGBMRegressor().set_params(
                num_iterations=30, min_data_in_leaf=5, boosting_type=mode,
                seed=42, use_quantized_grad=True)
            Xtr, Xte, ytr, yte = _split(X, y)
            model = reg.fit(_frame(Xtr, ytr))
            pred = model.transform(_frame(Xte, yte)).collect()["prediction"]
            bench.add(f"LightGBMRegressor_{ds_name}_{mode}",
                      float(np.mean((pred - yte) ** 2)), 1.0, False)
    bench.verify()
