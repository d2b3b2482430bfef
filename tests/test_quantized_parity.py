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


def _int8_matmul_cases():
    """Inputs the chip's builder (``build_histograms_matmul_quantized``) must
    take to the packed scatter's exact integers: name -> (n, f, bins, nodes,
    quant_bins, block_rows, how the node ids are made)."""
    def uniform(rng, n, p):
        return rng.integers(-1, p, n)

    def all_masked(rng, n, p):
        return np.full(n, -1)

    def first_node_empty(rng, n, p):
        return rng.integers(1, p, n)

    return {
        # 1537 = 3 * 512 + 1: the last block holds one row
        "ragged_last_block": (1537, 10, 255, 8, 16, 512, uniform),
        # fewer rows than the smallest block the builder makes (256)
        "rows_under_one_block": (100, 3, 63, 2, 16, 4096, uniform),
        # a bagged-out level: every row carries node_id = -1
        "every_row_masked": (700, 4, 63, 4, 16, 256, all_masked),
        # the level-1 shape with no row in the left child
        "empty_first_node": (900, 5, 63, 2, 16, 256, first_node_empty),
        "one_feature": (1000, 1, 255, 4, 16, 256, uniform),
        # 256 bins: the whole uint8 range, 16 hi x 16 lo with no remainder
        "bins_256": (2000, 6, 256, 4, 16, 256, uniform),
        # 17 bins: a second hi group of one bin
        "bins_17": (1200, 7, 17, 4, 16, 256, uniform),
        "quant_bins_4": (2000, 6, 255, 8, 4, 256, uniform),
        "quant_bins_128": (2000, 6, 255, 8, 128, 256, uniform),
    }


@pytest.mark.parametrize("case", sorted(_int8_matmul_cases()))
def test_int8_matmul_build_matches_packed_scatter(case):
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    n, f, b, p, qb, block_rows, nodes_of = _int8_matmul_cases()[case]
    rng = np.random.default_rng(len(case))
    binned = jnp.asarray(rng.integers(0, b, (n, f)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.01, 1, n).astype(np.float32))
    node = jnp.asarray(nodes_of(rng, n, p).astype(np.int32))
    qg, qh, _, _ = H.quantize_gradients(g, h, qb, seed=3)
    sc = H.build_histograms_quantized(binned, qg, qh, node, p, b,
                                      quant_bins=qb)
    mm = H.build_histograms_matmul_quantized(binned, qg, qh, node, p, b,
                                             quant_bins=qb,
                                             block_rows=block_rows)
    assert mm.dtype == jnp.int32 and mm.shape == (p, f, b, 3)
    assert bool(jnp.all(sc == mm))
    kept = int((np.asarray(node) >= 0).sum())
    assert int(mm[..., 2].sum()) == kept * f
    if case == "bins_256":
        assert int(mm[:, :, 255, 2].sum()) > 0      # the last bin is used


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


@pytest.mark.parametrize("builder", ["scatter", "matmul"])
def test_packed_histogram_psum_matches_global_build(mesh8, builder):
    """The packed int32 allreduce (grad+hess lanes share one channel when
    the global row bound allows) must equal the single-shard build, from
    either builder's per-shard histograms."""
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
        local = H.build_quantized(bq, qgq, qhq, nq, p, b, quant_bins=16,
                                  backend=builder)
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


def _trees(booster):
    return {k: np.asarray(getattr(booster, k)) for k in (
        "split_feature", "threshold_bin", "threshold", "split_gain",
        "leaf_value", "internal_count", "leaf_count")}


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


def test_quantization_follows_the_param_and_phase_labels(as_platform):
    """``use_quantized_grad`` decides in BOTH directions whatever the
    platform's own choice is (unset, the platform decides), and the phase
    histogram books attributable (backend, quantized) children."""
    from mmlspark_tpu.lightgbm import GBDTParams, train
    from mmlspark_tpu.observability import get_registry
    rng = np.random.default_rng(0)
    X = rng.normal(size=(600, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)

    def counts():
        fam = get_registry().family("mmlspark_lightgbm_phase_seconds")
        return {k[1:]: child.count for k, child in fam._snapshot()
                if k[0] == "histogram_split_update"} if fam else {}

    def booked(**kw):
        before = counts()
        train(X, y, GBDTParams(num_iterations=3, max_depth=3,
                               objective="binary", **kw))
        return {k for k, c in counts().items() if c != before.get(k, 0)}

    assert booked() == {("scatter", "0")}                 # the CPU's own
    assert booked(use_quantized_grad=True) == {("scatter", "1")}
    as_platform("tpu")
    assert booked() == {("matmul", "1")}                  # the chip's own
    assert booked(use_quantized_grad=False) == {("matmul", "0")}
    fam = get_registry().family("mmlspark_lightgbm_phase_seconds")
    assert fam.label_names == ("phase", "backend", "quantized")


def _fit_recording_leaves(monkeypatch, X, y, params, mesh=None):
    """Trees of a fit, and the ``leaf_of_row`` of every tree as its grower
    returned it, one list a shard in the order grown."""
    import jax
    from mmlspark_tpu.lightgbm import core, train
    from mmlspark_tpu.parallel import active_mesh
    leaves = {}
    make = core._make_grower

    def recording(p, F, B, axis_name=None, **kw):
        grow = make(p, F, B, axis_name=axis_name, **kw)

        def grow_and_record(*args):
            out = grow(*args)
            shard = 0 if axis_name is None else jax.lax.axis_index(axis_name)
            jax.debug.callback(lambda s, leaf: leaves.setdefault(
                int(s), []).append(np.asarray(leaf)), shard, out[-1])
            return out
        return grow_and_record

    core._JIT_CACHE.clear()          # no program grown without the recorder
    with monkeypatch.context() as mp:
        mp.setattr(core, "_make_grower", recording)
        if mesh is None:
            booster = train(X, y, params).booster
        else:
            with active_mesh(mesh):
                booster = train(X, y, params, shard_rows=True).booster
    core._JIT_CACHE.clear()
    return _trees(booster), leaves


def _categorical_column(n_codes, n=1500):
    """``x0`` a category code (``y`` follows a random half of the codes),
    ``x1``-``x3`` normal."""
    rng = np.random.default_rng(11)
    codes = rng.integers(0, n_codes, size=n)
    in_set = rng.permutation(n_codes) < n_codes // 2
    X = np.column_stack([codes, rng.normal(size=(n, 3))]).astype(np.float32)
    y = in_set[codes] ^ (X[:, 1] + 0.3 * rng.normal(size=n) > 1)
    return X, y, dict(categorical_features=(0,))


def _plain_columns(n=700, F=6):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.normal(size=n) > 0
    return X, y, {}


@pytest.mark.parametrize("data,over,sharded", [
    (_plain_columns, {}, False),
    # 16 nodes on the last level, the widest route
    (lambda: _plain_columns(n=3000, F=20), dict(max_depth=5), False),
    (_plain_columns, dict(use_quantized_grad=False), False),
    (lambda: _categorical_column(4), {}, False),          # one-vs-rest
    (lambda: _categorical_column(24), {}, False),         # sorted subset
    (_plain_columns, {}, True)],                           # 8 CPU shards
    ids=["depth3", "depth5", "float", "cat-onehot", "cat-subset", "sharded"])
def test_matmul_and_scatter_backends_grow_the_same_trees(
        as_platform, monkeypatch, mesh8, data, over, sharded):
    """Integer histograms are exact in both builders, so a quantized fit
    through the int8 matmul build (one node unsorted at the root, sorted
    block slices below) takes the same splits as through the packed
    scatter (a float fit the same splits, and gains and values to
    rounding); and the route, a product with the level's split columns on
    the matmul side and a per-row gather on the scatter side, sends every
    row to the same leaf."""
    from mmlspark_tpu.lightgbm import GBDTParams
    X, y, cat = data()
    kw = dict(num_iterations=4, max_depth=3, objective="binary",
              min_data_in_leaf=5, use_quantized_grad=True,
              bagging_fraction=0.8, bagging_freq=1)
    params = GBDTParams(**{**kw, **cat, **over})

    def fit(platform):
        as_platform(platform)
        return _fit_recording_leaves(monkeypatch, X, y.astype(np.float32),
                                     params, mesh8 if sharded else None)

    (mm, mm_leaves), (sc, sc_leaves) = fit("tpu"), fit("cpu")
    assert (mm["split_feature"] >= 0).sum() >= 4 * 3
    if cat:
        assert (mm["split_feature"] == 0).any()
    for key in mm:
        if key in ("split_gain", "leaf_value") and not params.use_quantized_grad:
            # float sums: the two builders add in different orders
            np.testing.assert_allclose(mm[key], sc[key], rtol=1e-4,
                                       err_msg=key)
        else:
            np.testing.assert_array_equal(mm[key], sc[key], err_msg=key)
    assert sorted(mm_leaves) == sorted(sc_leaves) == \
        list(range(8 if sharded else 1))
    for shard in mm_leaves:
        assert len(mm_leaves[shard]) == 4
        np.testing.assert_array_equal(np.stack(mm_leaves[shard]),
                                      np.stack(sc_leaves[shard]))


def _train_span_facts(trace_id):
    from mmlspark_tpu.observability.collector import get_collector
    (span,) = [s for s in get_collector().trace(trace_id)
               if s.name == "lightgbm.train"]
    return {k: span.attributes[k] for k in ("hist_backend", "quantized",
                                            "chunk")}


@pytest.mark.parametrize("platform,rows,facts", [
    ("cpu", 600, dict(hist_backend="scatter", quantized=False, chunk=1)),
    ("tpu", 600, dict(hist_backend="matmul", quantized=True, chunk=1)),
    # what BENCHMARK.json's GBDT cells expect of a one-chip fit
    ("tpu", 50_000, dict(hist_backend="matmul", quantized=True, chunk=4))])
def test_the_platform_chooses_the_path_and_the_span_says_which(
        as_platform, platform, rows, facts):
    """The builder family is a function of the platform alone and is decided
    in ``xla_backend``; ``train()`` writes the path it took on its span,
    where ``chip_smoke.py`` and ``benchmark/families/gbdt.py`` read it."""
    from mmlspark_tpu.lightgbm import GBDTParams, train
    from mmlspark_tpu.observability.tracing import trace_span
    from mmlspark_tpu.ops import histogram as H
    as_platform(platform)
    assert H.xla_backend() == H.xla_backend("auto") == facts["hist_backend"]
    assert H.xla_backend("scatter") == "scatter"
    assert H.xla_backend("matmul") == "matmul"
    rng = np.random.default_rng(1)
    X = rng.normal(size=(rows, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    with trace_span("test.path") as sp:
        r = train(X, y, GBDTParams(num_iterations=8, max_depth=3, max_bin=63,
                                   objective="binary"))
    assert _train_span_facts(sp.trace_id) == facts
    assert ((r.booster.predict(X) > 0.5) == y).mean() > 0.9


def _gbdt_cache_entries():
    from mmlspark_tpu.lightgbm import core
    return set(core._JIT_CACHE)


def test_two_platforms_in_one_process_share_no_program(as_platform):
    """The resolved backend is part of the jit-cache key: a fit told it
    runs on a TPU and a plain one, at the same shape and with the SAME
    explicit params, get a program each, and each grows its own builder's
    trees.  (Keyed on the environment alone, the second was served the
    first one's program.)"""
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(9)
    X = rng.normal(size=(900, 6)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 2] > 0).astype(np.float32)
    params = GBDTParams(num_iterations=3, max_depth=3, objective="binary",
                        use_quantized_grad=False, seed=4)
    before = _gbdt_cache_entries()
    as_platform("tpu")
    b_tpu = train(X, y, params).booster
    on_tpu = _gbdt_cache_entries() - before
    as_platform("cpu")
    b_cpu = train(X, y, params).booster
    on_cpu = _gbdt_cache_entries() - before - on_tpu

    def keyed_on(entries, backend):
        return {k for k in entries if repr(backend) in repr(k)}

    # (the tree walker and the valid-score update depend on no builder)
    assert keyed_on(on_tpu, "matmul") and not keyed_on(on_tpu, "scatter")
    assert keyed_on(on_cpu, "scatter") and not keyed_on(on_cpu, "matmul")
    assert len(keyed_on(on_cpu, "scatter")) == len(keyed_on(on_tpu, "matmul"))
    # the float builders round differently, so shared programs would show
    # as identical gains: same splits, gains that differ in the last bits
    t, c = _trees(b_tpu), _trees(b_cpu)
    np.testing.assert_array_equal(t["split_feature"], c["split_feature"])
    assert not np.array_equal(t["split_gain"], c["split_gain"])
    np.testing.assert_allclose(t["split_gain"], c["split_gain"], rtol=1e-3)


def test_the_former_histogram_switches_change_nothing(monkeypatch):
    """MMLSPARK_TPU_HIST_BACKEND / _BLOCK_ROWS / _LO / _RESID / _QUANT /
    _STORE16 chose the builder once and were keyed into every jit cache.
    Nothing reads them now: set to nonsense, a fit returns the same trees
    bit for bit from the same cached programs."""
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(13)
    X = rng.normal(size=(800, 5)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    fits = [dict(max_depth=3, use_quantized_grad=True),
            dict(num_leaves=7, use_quantized_grad=True),
            dict(max_depth=3)]

    def run():
        return [_trees(train(X, y, GBDTParams(
            num_iterations=3, objective="binary", seed=2, **kw)).booster)
            for kw in fits]

    plain = run()
    entries = _gbdt_cache_entries()
    for name in ("BACKEND", "BLOCK_ROWS", "LO", "RESID", "QUANT", "STORE16"):
        monkeypatch.setenv("MMLSPARK_TPU_HIST_" + name, "nonsense")
    again = run()
    assert _gbdt_cache_entries() == entries
    for a, b in zip(plain, again):
        for key in a:
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)


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
