"""Quantized-gradient packed histograms: exactness + accuracy parity.

Two layers of guarantees (ISSUE 5 / LightGBM 4.x "Quantized Training of
Gradient Boosting Decision Trees"):

1. **Integer exactness** — given the same quantized per-row gradients, the
   packed scatter and packed int8-matmul builders must agree BIT-FOR-BIT
   (they accumulate exact integers), every lane-packing layout
   (all3/2ch/wide, chosen by the static node-row bound) must decode to the
   same sums, the packed shard_map allreduce must equal the single-shard
   build, and sibling subtraction (parent - left == right) must hold
   EXACTLY in integer space — the property that lets the growers reuse
   LightGBM's histogram-halving without f32 cancellation drift.
2. **Accuracy parity** — stochastic rounding is unbiased, so quantized
   training must match float training within the repo's committed gates:
   the quick checks here, and (slow lane) the benchmarks_VerifyLightGBM*
   CSV sweeps re-run with ``use_quantized_grad=True`` against the SAME
   committed baselines and precisions (PARITY.md's contract).
"""
import os

import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.core.schema import vector_column

RES = os.path.join(os.path.dirname(__file__), "resources", "benchmarks")


def _hist_inputs(n=4000, f=6, b=255, p=8, seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    binned = jnp.asarray(rng.integers(0, b, (n, f)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.01, 1, n).astype(np.float32))
    node = jnp.asarray(rng.integers(-1, p, n).astype(np.int32))
    return binned, g, h, node


# ------------------------------------------------------------ kernel layer

def test_quantizer_is_unbiased_and_bounded():
    import jax.numpy as jnp
    from mmlspark_tpu.ops.histogram import quantize_gradients
    _, g, h, _ = _hist_inputs(n=20000)
    for bins in (4, 16, 64):
        qg, qh, gs, hs = quantize_gradients(g, h, bins, seed=7)
        assert int(qg.min()) >= -(bins // 2) and int(qg.max()) <= bins // 2
        assert int(qh.min()) >= 0 and int(qh.max()) <= bins - 1
        # stochastic rounding: per-row error < 1 quantum, mean error ~ 0
        assert float(jnp.max(jnp.abs(qg * gs - g))) <= float(gs) + 1e-6
        assert abs(float(jnp.mean(qg * gs - g))) < 3 * float(gs) / np.sqrt(len(g))
        assert abs(float(jnp.mean(qh * hs - h))) < 3 * float(hs) / np.sqrt(len(g))


def test_packed_backends_agree_bit_for_bit():
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    binned, g, h, node = _hist_inputs()
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=3)
    p, b = 8, 255
    sc = H.build_histograms_quantized(binned, qg, qh, node, p, b)
    mm = H.build_histograms_matmul_quantized(binned, qg, qh, node, p, b,
                                             block_rows=256)
    assert sc.dtype == jnp.int32 and mm.dtype == jnp.int32
    assert bool(jnp.all(sc == mm))
    # and both equal the f32 reference run over the SAME integer gradients
    # (small ints are exact in f32 at this n)
    ref = H.build_histograms(binned, qg.astype(jnp.float32),
                             qh.astype(jnp.float32), node, p, b)
    assert float(jnp.max(jnp.abs(ref - sc.astype(jnp.float32)))) == 0.0


def test_packed_lane_layouts_decode_identically():
    """all3 (one segment-sum) / 2ch / wide must be indistinguishable in
    output — the bit-width widening is a pure layout decision."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    n, f, b, p = 4096, 5, 255, 32
    rng = np.random.default_rng(1)
    binned = jnp.asarray(rng.integers(0, b, (n, f)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.01, 1, n).astype(np.float32))
    node = jnp.asarray((np.arange(n) % p).astype(np.int32))  # balanced
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=5)
    bound = n // p                                           # 128 rows/node
    assert H._packed_layout(bound, 16)[0] == "all3"
    assert H._packed_layout(4000, 16)[0] == "2ch"
    assert H._packed_layout(10_000_000, 16)[0] == "wide"
    outs = [H.build_histograms_quantized(binned, qg, qh, node, p, b,
                                         node_rows_bound=nb)
            for nb in (bound, 4000, None)]                   # all3/2ch/wide
    assert bool(jnp.all(outs[0] == outs[1]))
    assert bool(jnp.all(outs[1] == outs[2]))
    # count channel is the true row count
    cnt = H.build_histograms(binned, jnp.ones((n,)), jnp.ones((n,)),
                             node, p, b)[..., 2]
    assert bool(jnp.all(outs[0][..., 2] == cnt.astype(jnp.int32)))


def test_sibling_subtraction_exact_in_integer_space():
    """parent - left == right, bit-for-bit, across both packed builders —
    the invariant the growers' histogram-halving rests on."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    binned, g, h, _ = _hist_inputs(n=6000, p=1)
    n = binned.shape[0]
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=9)
    rng = np.random.default_rng(4)
    go_left = jnp.asarray(rng.random(n) < 0.37)
    root = jnp.zeros((n,), jnp.int32)
    left = jnp.where(go_left, 0, -1)
    right = jnp.where(go_left, -1, 0)
    for build in (H.build_histograms_quantized,
                  lambda *a, **k: H.build_histograms_matmul_quantized(
                      *a, block_rows=256, **k)):
        hp = build(binned, qg, qh, root, 1, 255)
        hl = build(binned, qg, qh, left, 1, 255)
        hr = build(binned, qg, qh, right, 1, 255)
        assert bool(jnp.all(hp - hl == hr)), build


def test_packed_histogram_psum_matches_global_build(mesh8):
    """The packed int32 allreduce (grad+hess lanes share one channel when
    the global row bound allows) must equal the single-shard build."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.parallel.collectives import histogram_psum
    from mmlspark_tpu.parallel.mesh import AXIS_DATA

    n, f, b, p = 800, 4, 63, 4                 # 800 * 15 < 2**14: packs
    rng = np.random.default_rng(2)
    binned = jnp.asarray(rng.integers(0, b, (n, f)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.01, 1, n).astype(np.float32))
    node = jnp.asarray(rng.integers(0, p, n).astype(np.int32))
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=1)

    def local_then_psum(bq, qgq, qhq, nq):
        local = H.build_histograms_quantized(bq, qgq, qhq, nq, p, b,
                                             quant_bins=16)
        return histogram_psum(local, AXIS_DATA, row_bound=n, quant_bins=16)

    sharded = jax.jit(jax.shard_map(
        local_then_psum, mesh=mesh8,
        in_specs=(P(AXIS_DATA), P(AXIS_DATA), P(AXIS_DATA), P(AXIS_DATA)),
        out_specs=P(), check_vma=False))(binned, qg, qh, node)
    ref = H.build_histograms_quantized(binned, qg, qh, node, p, b,
                                       quant_bins=16)
    assert bool(jnp.all(sharded == ref))


# ----------------------------------------------------------- training layer

def _frame(X, y):
    return DataFrame.from_dict({"features": vector_column(list(X)),
                                "label": y.astype(float)}, 2)


def test_quantized_classifier_parity_quick():
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    rng = np.random.default_rng(7)
    X = rng.normal(size=(2000, 10))
    y = (X[:, 0] * 2 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
         + rng.normal(scale=0.5, size=2000) > 0).astype(float)
    accs = {}
    for quant in (False, True):
        clf = LightGBMClassifier().set_params(
            num_iterations=40, max_depth=5, min_data_in_leaf=5, seed=3,
            use_quantized_grad=quant)
        model = clf.fit(_frame(X, y))
        out = model.transform(_frame(X, y)).collect()
        accs[quant] = float((np.asarray(out["prediction"]) == y).mean())
    assert accs[True] >= accs[False] - 0.02, accs


def test_quantized_regressor_parity_quick():
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(17)
    X = rng.normal(size=(2000, 8)).astype(np.float32)
    y = (3 * X[:, 0] - 2 * X[:, 1] + X[:, 2] ** 2
         + rng.normal(scale=0.3, size=2000)).astype(np.float32)
    mses = {}
    for quant in (False, True):
        r = train(X, y, GBDTParams(num_iterations=50, max_depth=5,
                                   objective="regression", seed=3,
                                   use_quantized_grad=quant))
        mses[quant] = float(np.mean((r.booster.predict(X) - y) ** 2))
    assert mses[True] <= mses[False] * 1.35 + 0.05, mses


def test_quant_env_hatch_and_phase_labels(monkeypatch):
    """MMLSPARK_TPU_HIST_QUANT overrides the param in BOTH directions, and
    the phase histogram books attributable (backend, quantized) children."""
    from mmlspark_tpu.lightgbm import GBDTParams, train
    from mmlspark_tpu.observability import get_registry
    rng = np.random.default_rng(0)
    X = rng.normal(size=(600, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)

    monkeypatch.setenv("MMLSPARK_TPU_HIST_QUANT", "1")
    train(X, y, GBDTParams(num_iterations=3, max_depth=3, objective="binary"))
    fam = get_registry().family("mmlspark_lightgbm_phase_seconds")
    assert fam.label_names == ("phase", "backend", "quantized")
    keys = {k for k, _ in fam._snapshot()}
    assert ("histogram_split_update", "scatter", "1") in keys
    # env=0 beats an explicit param True (operational kill switch), and
    # the comparison is case-insensitive — QUANT=OFF must never fail open
    # into force-enabling the feature
    for off_token in ("0", "OFF", " False "):
        monkeypatch.setenv("MMLSPARK_TPU_HIST_QUANT", off_token)
        train(X, y, GBDTParams(num_iterations=3, max_depth=3,
                               objective="binary", use_quantized_grad=True))
        keys = {k for k, _ in fam._snapshot()}
        assert ("histogram_split_update", "scatter", "0") in keys, off_token


def test_matmul_and_scatter_backends_grow_the_same_trees(monkeypatch):
    """Integer histograms are exact in both builders, so a quantized fit
    through the int8 matmul build (one node unsorted at the root, sorted
    block slices below) takes the same splits as through the packed
    scatter."""
    from mmlspark_tpu.lightgbm import GBDTParams, core, train
    rng = np.random.default_rng(5)
    X = rng.normal(size=(700, 6)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.normal(size=700) > 0)
    params = GBDTParams(num_iterations=4, max_depth=3, objective="binary",
                        min_data_in_leaf=5, use_quantized_grad=True,
                        bagging_fraction=0.8, bagging_freq=1)

    def fit(backend):
        monkeypatch.setenv("MMLSPARK_TPU_HIST_BACKEND", backend)
        b = train(X, y.astype(np.float32), params).booster
        return {k: np.asarray(getattr(b, k)) for k in (
            "split_feature", "threshold_bin", "threshold", "leaf_value",
            "internal_count", "leaf_count")}

    mm, sc = fit("matmul"), fit("scatter")
    assert (mm["split_feature"] >= 0).sum() >= 4 * 3
    for key in mm:
        np.testing.assert_array_equal(mm[key], sc[key], err_msg=key)


def test_sharded_overflow_guard_uses_global_row_bound():
    """The builders' int32 guard sees only the local shard; the grower must
    reject a GLOBAL row bound that would wrap the hessian lane after the
    psum (review finding)."""
    from mmlspark_tpu.lightgbm.core import (GBDTParams, make_tree_grower,
                                            make_leafwise_grower)
    p = GBDTParams(use_quantized_grad=True, num_grad_quant_bins=128,
                   max_depth=3).resolve()
    huge = (1 << 31) // 127 + 1          # global rows x qh_cap wraps int32
    with pytest.raises(ValueError, match="cross-shard psum"):
        make_tree_grower(3, 4, 63, p, axis_name="data",
                         psum_row_bound=huge)
    pl = GBDTParams(use_quantized_grad=True, num_grad_quant_bins=128,
                    num_leaves=4).resolve()
    with pytest.raises(ValueError, match="cross-shard psum"):
        make_leafwise_grower(4, 0, 4, 63, pl, axis_name="data",
                             psum_row_bound=huge)
    # same bound single-shard (no axis) or float-mode is fine
    make_tree_grower(3, 4, 63, p, psum_row_bound=huge)
    make_tree_grower(3, 4, 63, GBDTParams(max_depth=3).resolve(),
                     axis_name="data", psum_row_bound=huge)


def test_quantized_sharded_training_learns(mesh8):
    """shard_rows + quantization: per-shard quantization under pmax'd
    scales + the packed psum must still train a usable model."""
    from mmlspark_tpu.lightgbm import LightGBMRegressor
    from mmlspark_tpu.parallel import active_mesh
    rng = np.random.default_rng(6)
    X = rng.normal(size=(320, 5))
    y = 2 * X[:, 0] - X[:, 3]
    with active_mesh(mesh8):
        m = LightGBMRegressor().set_params(
            num_iterations=10, min_data_in_leaf=5, shard_rows=True,
            use_quantized_grad=True).fit(_frame(X, y))
    mse = float(np.mean((m.booster.predict(X) - y) ** 2))
    assert mse < float(np.var(y)) * 0.3, mse


def test_num_grad_quant_bins_validation():
    from mmlspark_tpu.lightgbm import GBDTParams
    with pytest.raises(ValueError, match="num_grad_quant_bins"):
        GBDTParams(num_grad_quant_bins=2).resolve()
    with pytest.raises(ValueError, match="num_grad_quant_bins"):
        GBDTParams(num_grad_quant_bins=256).resolve()


# --------------------------------------- committed accuracy gates, quant ON

def _split(X, y, seed=5):
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(y))
    cut = int(len(y) * 0.75)
    tr, te = order[:cut], order[cut:]
    return X[tr], X[te], y[tr], y[te]


@pytest.mark.slow  # mirrors test_benchmark_regression timing (~160 s)
def test_quantized_classifier_holds_committed_benchmarks():
    """The full benchmarks_VerifyLightGBMClassifier sweep with quantization
    ON must hold the SAME committed baselines within the SAME precisions —
    PARITY.md's quantized-training accuracy contract."""
    from mmlspark_tpu.testing import Benchmarks
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    from tests.test_benchmark_regression import (MODES,
                                                 _datasets_classification)
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


@pytest.mark.slow  # mirrors test_benchmark_regression timing (~70 s)
def test_quantized_regressor_holds_committed_benchmarks():
    from mmlspark_tpu.testing import Benchmarks
    from mmlspark_tpu.lightgbm import LightGBMRegressor
    from tests.test_benchmark_regression import _datasets_regression
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
