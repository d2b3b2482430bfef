import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame, save, load


def make_classification(n=600, f=10, seed=0, classes=2):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    if classes == 2:
        logit = X[:, 0] * 2 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
        y = (logit + rng.normal(scale=0.5, size=n) > 0).astype(float)
    else:
        score = X[:, :classes] + rng.normal(scale=0.3, size=(n, classes))
        y = score.argmax(axis=1).astype(float)
    return X, y


def frame_of(X, y, parts=2, **extra):
    from mmlspark_tpu.core.schema import vector_column
    cols = {"features": vector_column(list(X)), "label": y}
    cols.update(extra)
    return DataFrame.from_dict(cols, num_partitions=parts)


def accuracy(model, X, y):
    df = frame_of(X, y, 1)
    out = model.transform(df).collect()
    return float((out["prediction"] == y).mean())


def test_binary_classifier_learns():
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    X, y = make_classification(800, 10)
    clf = LightGBMClassifier().set_params(num_iterations=40, learning_rate=0.15,
                                          min_data_in_leaf=5)
    model = clf.fit(frame_of(X, y))
    acc = accuracy(model, X, y)
    assert acc > 0.92, f"train accuracy {acc}"
    out = model.transform(frame_of(X, y, 1)).collect()
    prob = out["probability"][0]
    assert len(prob) == 2 and abs(prob.sum() - 1) < 1e-6


def test_multiclass_classifier():
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    X, y = make_classification(900, 8, classes=3)
    clf = LightGBMClassifier().set_params(num_iterations=30, min_data_in_leaf=5)
    model = clf.fit(frame_of(X, y))
    acc = accuracy(model, X, y)
    assert acc > 0.85, f"train accuracy {acc}"
    prob = model.transform(frame_of(X, y, 1)).collect()["probability"][0]
    assert len(prob) == 3


def test_regressor_modes():
    from mmlspark_tpu.lightgbm import LightGBMRegressor
    rng = np.random.default_rng(0)
    X = rng.normal(size=(500, 6))
    y = 3 * X[:, 0] - 2 * X[:, 1] + X[:, 2] ** 2 + rng.normal(scale=0.1, size=500)
    base_mse = float(np.var(y))
    for boosting in ("gbdt", "goss", "dart", "rf"):
        reg = LightGBMRegressor().set_params(num_iterations=30, min_data_in_leaf=5,
                                             boosting_type=boosting, seed=1)
        model = reg.fit(frame_of(X, y))
        pred = model.transform(frame_of(X, y, 1)).collect()["prediction"]
        mse = float(np.mean((pred - y) ** 2))
        assert mse < base_mse * 0.5, f"{boosting}: mse {mse} vs var {base_mse}"


def test_model_string_roundtrip_and_warm_start():
    from mmlspark_tpu.lightgbm import LightGBMClassifier, LightGBMRegressor
    from mmlspark_tpu.models.gbdt import GBDTBooster
    X, y = make_classification(400, 6)
    m1 = LightGBMClassifier().set_params(num_iterations=10, min_data_in_leaf=5) \
        .fit(frame_of(X, y))
    s = m1.get_model_string()
    b2 = GBDTBooster.from_string(s)
    p1 = m1.booster.predict(X)
    assert np.allclose(p1, b2.predict(X), atol=1e-6)
    # warm start continues training
    m2 = LightGBMClassifier().set_params(num_iterations=10, min_data_in_leaf=5,
                                         model_string=s).fit(frame_of(X, y))
    assert m2.booster.num_trees == 20


def test_save_load_model(tmp_path):
    from mmlspark_tpu.lightgbm import LightGBMRegressor
    rng = np.random.default_rng(2)
    X = rng.normal(size=(300, 5))
    y = X[:, 0] + 0.5 * X[:, 1]
    model = LightGBMRegressor().set_params(num_iterations=15, min_data_in_leaf=5) \
        .fit(frame_of(X, y))
    p = str(tmp_path / "lgbm")
    save(model, p)
    model2 = load(p)
    a = model.transform(frame_of(X, y, 1)).collect()["prediction"]
    b = model2.transform(frame_of(X, y, 1)).collect()["prediction"]
    assert np.allclose(a, b)


def test_early_stopping_and_validation():
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    X, y = make_classification(600, 8, seed=3)
    vmask = np.zeros(600, bool)
    vmask[::5] = True
    clf = LightGBMClassifier().set_params(num_iterations=200, learning_rate=0.3,
                                          min_data_in_leaf=5,
                                          early_stopping_round=5,
                                          validation_indicator_col="is_valid")
    model = clf.fit(frame_of(X, y, 2, is_valid=vmask))
    assert model.booster.num_trees < 200  # stopped early


def test_feature_importance_and_leaf_contrib():
    from mmlspark_tpu.lightgbm import LightGBMRegressor
    rng = np.random.default_rng(4)
    X = rng.normal(size=(400, 5))
    y = 5 * X[:, 2] + rng.normal(scale=0.1, size=400)
    model = LightGBMRegressor().set_params(num_iterations=20, min_data_in_leaf=5) \
        .fit(frame_of(X, y))
    imp = model.get_feature_importances("split")
    assert imp.argmax() == 2
    gains = model.get_feature_importances("gain")
    assert gains.argmax() == 2
    # leaf predictions + contribs
    df = frame_of(X[:20], y[:20], 1)
    leaves = model.predict_leaf(df).collect()["leaf_prediction"]
    assert len(leaves[0]) == model.booster.num_trees
    contrib = model.predict_contrib(df).collect()["features_shap"]
    raw = model.booster.raw_scores(X[:20])[:, 0]
    assert np.allclose([c.sum() for c in contrib], raw, atol=1e-4)


def test_ranker_improves_ndcg():
    from mmlspark_tpu.lightgbm import LightGBMRanker
    rng = np.random.default_rng(5)
    n_q, per_q = 40, 10
    X = rng.normal(size=(n_q * per_q, 6))
    rel = np.clip((X[:, 0] * 2 + rng.normal(scale=0.3, size=n_q * per_q)), 0, None)
    y = np.digitize(rel, [0.5, 1.5, 2.5]).astype(float)
    groups = np.repeat(np.arange(n_q), per_q)
    df = frame_of(X, y, 2, group=groups)
    rk = LightGBMRanker().set_params(num_iterations=30, min_data_in_leaf=3)
    model = rk.fit(df)
    pred = model.transform(frame_of(X, y, 1, group=groups)).collect()["prediction"]
    # spearman-ish check: predictions correlate with relevance
    corr = np.corrcoef(pred, y)[0, 1]
    assert corr > 0.5, corr


def test_sharded_training_matches(mesh8):
    from mmlspark_tpu.lightgbm import LightGBMRegressor
    from mmlspark_tpu.parallel import active_mesh
    rng = np.random.default_rng(6)
    X = rng.normal(size=(320, 5))
    y = 2 * X[:, 0] - X[:, 3]
    with active_mesh(mesh8):
        m_sharded = LightGBMRegressor().set_params(num_iterations=10, min_data_in_leaf=5,
                                                   shard_rows=True).fit(frame_of(X, y))
    m_local = LightGBMRegressor().set_params(num_iterations=10, min_data_in_leaf=5) \
        .fit(frame_of(X, y))
    a = m_sharded.booster.predict(X)
    b = m_local.booster.predict(X)
    assert np.allclose(a, b, atol=1e-4), np.abs(a - b).max()


@pytest.mark.parametrize("p", [1, 4])
def test_histogram_backends_agree(p):
    import jax.numpy as jnp
    from mmlspark_tpu.ops.histogram import build_histograms, build_histograms_matmul
    # one node takes the matmul backend's unsorted root layout, four its
    # sorted block slices: both must match the scatter build
    rng = np.random.default_rng(7)
    n, f, b = 3000, 9, 255
    binned = jnp.asarray(rng.integers(0, b, (n, f)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, n).astype(np.float32))
    node = jnp.asarray(rng.integers(-1, p, n).astype(np.int32))
    a = build_histograms(binned, g, h, node, p, b)
    m = build_histograms_matmul(binned, g, h, node, p, b, block_rows=256)
    assert float(jnp.max(jnp.abs(a - m))) < 1e-3
    # count channel must be exactly integral
    assert float(jnp.max(jnp.abs(m[..., 2] - jnp.round(m[..., 2])))) == 0.0
    # every combination of the float builder's arguments must agree too:
    # residual channels keep f32-exactness, bf16-rounded inputs bound 2e-3
    for lo in (32, 64, 128):
        for resid, tol in ((True, 1e-3), (False, 2e-3)):
            m2 = build_histograms_matmul(binned, g, h, node, p, b,
                                         block_rows=1024, lo_width=lo,
                                         residuals=resid)
            scale = float(jnp.max(jnp.abs(a)))
            err = float(jnp.max(jnp.abs(a - m2))) / max(scale, 1.0)
            assert err < tol, (lo, resid, err)
            assert float(jnp.max(jnp.abs(
                m2[..., 2] - jnp.round(m2[..., 2])))) == 0.0


def test_histogram_max_rows_compaction_exact():
    """The smaller-child static bound (max_rows) must be exact whenever the
    caller's guarantee holds — including at the boundary and with heavily
    masked inputs (the level-wise grower's smaller-child builds)."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops.histogram import build_histograms, build_histograms_matmul
    rng = np.random.default_rng(3)
    n, f, b, p = 4000, 7, 255, 8
    binned = jnp.asarray(rng.integers(0, b, (n, f)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, n).astype(np.float32))
    # mask ~70% of rows out: unmasked count <= n//2 like a smaller-child pass
    node_np = rng.integers(0, p, n).astype(np.int32)
    keep = rng.uniform(size=n) < 0.3
    node_np[~keep] = -1
    unmasked = int((node_np >= 0).sum())
    node = jnp.asarray(node_np)
    ref = build_histograms(binned, g, h, node, p, b)
    for cap in (unmasked, unmasked + 1, n // 2, n):
        m = build_histograms_matmul(binned, g, h, node, p, b,
                                    block_rows=256, max_rows=cap)
        assert float(jnp.max(jnp.abs(ref - m))) < 1e-3, cap


def _layout_reference(node, R, P, NB):
    """Plain numpy: stable sort by node (masked rows last, as node P), each
    node padded to a multiple of R; the first NB blocks.  Returns the row id
    of every slot (-1 = padding) and the node of every block that has rows."""
    node_s = np.where(node < 0, P, node)
    order = np.argsort(node_s, kind="stable")
    slots, owner = [], []
    for q in range(P + 1):
        rows = order[node_s[order] == q]
        nblk = -(-len(rows) // R)
        slots.append(np.concatenate(
            [rows, np.full(nblk * R - len(rows), -1, np.int64)]))
        owner += [q] * nblk
    slots = np.concatenate(slots)
    slots = np.concatenate([slots, np.full(NB * R, -1, np.int64)])[:NB * R]
    return slots.reshape(NB, R), np.asarray(owner[:NB])


def _layout_cases():
    rng = np.random.default_rng(21)
    R = 256

    def mixed(n, P, masked=0.15, empty=None, exact=None):
        node = rng.integers(0, P, n)
        if empty is not None:                 # a node no row reaches
            node[node == empty] = (empty + 1) % P
        if exact is not None:                 # a node of exactly 2 * R rows
            node[node == exact] = (exact + 1) % P
            node[rng.permutation(n)[:2 * R]] = exact
        node[rng.uniform(size=n) < masked] = -1
        return node.astype(np.int32)

    heavy = mixed(4000, 8, masked=0.7)
    unmasked = int((heavy >= 0).sum())
    return {
        "masked_rows": (mixed(3000, 4), 4, R, None),
        "empty_node": (mixed(3000, 5, empty=2), 5, R, None),
        "exact_multiple_of_R": (mixed(3000, 4, masked=0.0, exact=1), 4, R,
                                None),
        "n_not_multiple_of_R": (mixed(2999, 3), 3, R, None),
        "max_rows_at_boundary": (heavy, 8, R, unmasked),
        "max_rows_70pct_masked": (heavy, 8, R, len(heavy) // 2),
        "one_node_with_max_rows": (mixed(3000, 1, masked=0.6), 1, R, 1500),
    }


@pytest.mark.parametrize("case", sorted(_layout_cases()))
def test_node_pure_layout_blocks_match_numpy_reference(case):
    """Block for block: the same rows in the same order as "stable sort by
    node, pad each node to a multiple of R", weights riding along."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops.histogram import _node_pure_layout
    node, P, R, max_rows = _layout_cases()[case]
    n, F = len(node), 5
    rng = np.random.default_rng(22)
    # every row's bins spell its row id, so a block's rows can be read back
    binned = np.stack([(np.arange(n) >> (8 * k)) & 255
                       for k in range(2)] + [rng.integers(0, 255, n)] * 3,
                      axis=1).astype(np.uint8)
    qg = rng.integers(-8, 9, n).astype(np.int32)
    qh = rng.integers(0, 16, n).astype(np.int32)
    bb, w, node_blk = _node_pure_layout(
        jnp.asarray(binned), jnp.asarray(qg), jnp.asarray(qh),
        jnp.asarray(node), P, R, quantized=True, max_rows=max_rows)
    bb, w, node_blk = np.asarray(bb), np.asarray(w), np.asarray(node_blk)
    NB = bb.shape[0]
    n_cap = n if max_rows is None else min(n, max_rows)
    assert NB == -(-n_cap // R) + P + 1 and bb.shape == (NB, R, F)
    assert w.shape == (NB, 3, R) and w.dtype == np.int8
    slots, owner = _layout_reference(node, R, P, NB)
    valid = slots >= 0
    np.testing.assert_array_equal(w[:, 2, :], valid)
    rows = bb[..., 0].astype(np.int64) | (bb[..., 1].astype(np.int64) << 8)
    np.testing.assert_array_equal(rows[valid], slots[valid])
    np.testing.assert_array_equal(w[:, 0, :], np.where(valid, qg[slots], 0))
    np.testing.assert_array_equal(w[:, 1, :], np.where(valid, qh[slots], 0))
    # a block with rows belongs to their node; every unmasked row is there
    has_rows = valid.any(axis=1)
    np.testing.assert_array_equal(node_blk[has_rows][:len(owner)],
                                  owner[:has_rows.sum()])
    kept = slots[valid & (node_blk[:, None] < P)]
    np.testing.assert_array_equal(np.sort(kept), np.flatnonzero(node >= 0))


def test_root_layout_is_taken_for_one_node_without_max_rows_only():
    """One node and no bound: ``binned`` itself in R-row blocks, in row
    order, masked rows at zero weight.  With ``max_rows`` the sorted layout
    runs (its truncation is what the bound is for)."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    rng = np.random.default_rng(23)
    n, F, R, b = 1500, 4, 256, 255
    binned = jnp.asarray(rng.integers(0, b, (n, F)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, n).astype(np.float32))
    node_np = np.where(rng.uniform(size=n) < 0.4, -1, 0).astype(np.int32)
    node = jnp.asarray(node_np)
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=2)
    bb, w, node_blk = H._node_pure_layout(binned, qg, qh, node, 1, R,
                                          quantized=True)
    NB = -(-n // R)
    assert bb.shape == (NB, R, F) and not np.asarray(node_blk).any()
    np.testing.assert_array_equal(np.asarray(bb).reshape(-1, F)[:n], binned)
    np.testing.assert_array_equal(np.asarray(w)[:, 2, :].reshape(-1)[:n],
                                  node_np >= 0)
    bb2, _, _ = H._node_pure_layout(binned, qg, qh, node, 1, R,
                                    quantized=True, max_rows=n)
    assert bb2.shape[0] == NB + 2
    # bit for bit on the quantized path, within 1e-3 on the float path
    sc = H.build_histograms_quantized(binned, qg, qh, node, 1, b)
    mm = H.build_histograms_matmul_quantized(binned, qg, qh, node, 1, b,
                                             block_rows=R)
    assert bool(jnp.all(sc == mm))
    ref = H.build_histograms(binned, g, h, node, 1, b)
    fm = H.build_histograms_matmul(binned, g, h, node, 1, b, block_rows=R)
    assert float(jnp.max(jnp.abs(ref - fm))) < 1e-3


@pytest.mark.parametrize("p", [33, 63])
def test_wide_node_buffers_agree_with_scatter(p):
    """The leaf-wise regime: more nodes than the level-wise grower ever
    has, some of them empty."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    rng = np.random.default_rng(p)
    n, f, b = 5000, 4, 255
    binned = jnp.asarray(rng.integers(0, b, (n, f)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, n).astype(np.float32))
    node_np = rng.integers(-1, p, n).astype(np.int32)
    node_np[node_np == 7] = 8
    node = jnp.asarray(node_np)
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=4)
    sc = H.build_histograms_quantized(binned, qg, qh, node, p, b)
    mm = H.build_histograms_matmul_quantized(binned, qg, qh, node, p, b,
                                             block_rows=256)
    assert bool(jnp.all(sc == mm))
    assert not np.asarray(mm[7]).any()
    ref = H.build_histograms(binned, g, h, node, p, b)
    fm = H.build_histograms_matmul(binned, g, h, node, p, b, block_rows=256)
    assert float(jnp.max(jnp.abs(ref - fm))) < 1e-3


def test_float_matmul_builder_drives_training(as_platform):
    # the float matmul build (the chip's builder where a caller turns
    # quantization off) must produce an equivalent booster through the
    # full train() flow
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(11)
    X = rng.normal(size=(2000, 8)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    accs = {}
    for platform, backend in (("tpu", "matmul"), ("cpu", "scatter")):
        as_platform(platform)
        r = train(X, y, GBDTParams(num_iterations=5, max_depth=4,
                                   objective="binary",
                                   use_quantized_grad=False))
        accs[backend] = ((r.booster.predict(X) > 0.5) == y).mean()
    assert accs["matmul"] > 0.9, accs
    assert abs(accs["matmul"] - accs["scatter"]) <= 0.01, accs


def test_chunked_training_matches_unchunked(monkeypatch):
    """The scan-chunked path must produce the same boosting trajectory shape
    and comparable accuracy as per-iteration dispatch."""
    import importlib
    monkeypatch.setenv("MMLSPARK_TPU_GBDT_CHUNK", "4")
    from mmlspark_tpu.lightgbm import core as gbdt_core
    X, y = make_classification(600, 8, seed=9)
    p = gbdt_core.GBDTParams(num_iterations=12, objective="binary",
                             max_depth=4, min_data_in_leaf=5, seed=3)
    # force-eligible despite small n by lowering the gate via monkeypatch of n
    # threshold is internal; instead test the multi-iter machinery on a
    # synthetic large-enough frame
    Xl = np.tile(X, (100, 1))
    yl = np.tile(y, 100)
    res_chunked = gbdt_core.train(Xl, yl, p)
    monkeypatch.setenv("MMLSPARK_TPU_GBDT_CHUNK", "1")
    res_plain = gbdt_core.train(Xl, yl, p)
    assert res_chunked.booster.num_trees == res_plain.booster.num_trees == 12
    acc_c = ((res_chunked.booster.predict(X) > 0.5) == y).mean()
    acc_p = ((res_plain.booster.predict(X) > 0.5) == y).mean()
    assert acc_c > 0.9 and acc_p > 0.9, (acc_c, acc_p)


def test_chunked_program_is_not_stale_across_train_calls(monkeypatch):
    """The chunked program is cached by (params, shape): a second train()
    at the same shape must learn ITS data, not the first call's (the data
    used to be closed over and baked into the cached program — the TPU
    default path, found by PR 22's chip bring-up)."""
    monkeypatch.setenv("MMLSPARK_TPU_GBDT_CHUNK", "4")
    from mmlspark_tpu.lightgbm import core as gbdt_core
    rng = np.random.default_rng(0)
    n, f = 50_000, 6
    X1 = rng.standard_normal((n, f)).astype(np.float32)
    X2 = rng.standard_normal((n, f)).astype(np.float32)
    y1 = (X1[:, 0] > 0).astype(np.float32)
    y2 = (X2[:, 3] > 0).astype(np.float32)      # a different signal feature
    p = gbdt_core.GBDTParams(num_iterations=8, objective="binary",
                             max_depth=3, seed=3)
    gbdt_core.train(X1, y1, p)
    second = gbdt_core.train(X2, y2, p).booster
    assert (second.split_feature[:, 0] == 3).all(), second.split_feature[:, 0]
    assert ((second.predict(X2) > 0.5) == (y2 > 0.5)).mean() > 0.95


def test_tree_shap_exact_vs_bruteforce():
    """Path-dependent TreeSHAP must match brute-force Shapley values computed
    from the tree's conditional expectations over all feature subsets."""
    from itertools import combinations
    from math import factorial
    from mmlspark_tpu.lightgbm import LightGBMRegressor
    from mmlspark_tpu.models.gbdt import tree_shap

    rng = np.random.default_rng(11)
    F = 4
    X = rng.normal(size=(200, F))
    y = 2 * X[:, 0] - 1.5 * X[:, 1] + X[:, 0] * X[:, 2]
    model = LightGBMRegressor().set_params(num_iterations=3, max_depth=3,
                                           min_data_in_leaf=2).fit(frame_of(X, y))
    b = model.booster
    I = 2 ** b.max_depth - 1

    def cond_exp(t, x, S):
        """Path-dependent expectation following x on S, covers elsewhere."""
        def rec(j):
            if j >= I:
                return float(b.leaf_value[t, j - I])
            f = int(b.split_feature[t, j])
            l, r = 2 * j + 1, 2 * j + 2
            if f < 0:
                return rec(l)
            if f in S:
                return rec(l) if not (x[f] > b.threshold[t, j]) else rec(r)
            def cov(k):
                return float(b.internal_count[t, k]) if k < I else \
                    float(b.leaf_count[t, k - I])
            cl, cr = cov(l), cov(r)
            tot = max(cl + cr, 1e-12)
            return (cl * rec(l) + cr * rec(r)) / tot
        return rec(0)

    x = X[0]
    # brute-force Shapley per tree, summed
    phi_brute = np.zeros(F + 1)
    for t in range(b.num_trees):
        for i in range(F):
            others = [f for f in range(F) if f != i]
            for k in range(F):
                for S in combinations(others, k):
                    wgt = factorial(len(S)) * factorial(F - len(S) - 1) / factorial(F)
                    phi_brute[i] += wgt * (cond_exp(t, x, set(S) | {i}) -
                                           cond_exp(t, x, set(S)))
        phi_brute[F] += cond_exp(t, x, set())
    phi_brute[F] += b.init_score

    phi = tree_shap(b, x[None, :])[0]
    assert np.allclose(phi, phi_brute, atol=1e-4), np.abs(phi - phi_brute).max()
    # additivity: contributions sum to the raw score
    raw = b.raw_scores(x[None, :])[0, 0]
    assert abs(phi.sum() - raw) < 1e-4


def test_bin_matrix_matches_host_binning():
    """Device digitize (vmapped searchsorted, O(n*F) memory) must agree with
    the host BinMapper, including tie-on-edge and NaN rows."""
    import jax.numpy as jnp
    from mmlspark_tpu.lightgbm.binning import BinMapper
    from mmlspark_tpu.ops.histogram import bin_matrix

    rng = np.random.default_rng(0)
    X = rng.normal(size=(500, 7)).astype(np.float32)
    X[::50, 0] = np.nan
    mapper = BinMapper(31).fit(np.nan_to_num(X, nan=0.0))
    X[5, 1] = mapper.edges[1][3]  # exact tie on an edge
    host = mapper.transform(np.nan_to_num(X, nan=np.nan))
    dev = np.asarray(bin_matrix(jnp.asarray(X), jnp.asarray(mapper.edges),
                                mapper.num_bins))
    finite = ~np.isnan(X)
    np.testing.assert_array_equal(dev[finite], host[finite])
    assert (dev[~finite] == 0).all()


def test_native_binning_matches_numpy():
    """C++ data-plane binning (mm_bin_edges/mm_bin_apply) must byte-match
    the numpy path, NaN and few-distinct features included."""
    from mmlspark_tpu.utils.native_loader import (bin_apply_native,
                                                  bin_edges_native,
                                                  load_native)
    if load_native() is None:
        import pytest
        pytest.skip("no native toolchain")
    from mmlspark_tpu.lightgbm.binning import BinMapper

    rng = np.random.default_rng(3)
    X = rng.normal(size=(5000, 12)).astype(np.float32)
    X[::31, 2] = np.nan
    X[:, 5] = np.round(X[:, 5])          # few distinct values
    X[:, 9] = 1.25                        # constant feature
    B = 31
    nat_edges = bin_edges_native(X, B)
    m = BinMapper(B)
    # numpy reference path (force it regardless of core count)
    n, F = X.shape
    edges = np.full((F, B - 1), np.inf, np.float32)
    qs = np.linspace(0, 1, B + 1)[1:-1]
    for f in range(F):
        col = X[:, f]
        col = col[~np.isnan(col)]
        uniq = np.unique(col)
        if uniq.size <= 1:
            continue
        if uniq.size <= B:
            mids = (uniq[:-1] + uniq[1:]) / 2.0
            edges[f, :mids.size] = mids
        else:
            e = np.unique(np.quantile(col, qs).astype(np.float32))
            edges[f, :e.size] = e
    np.testing.assert_allclose(np.nan_to_num(nat_edges, posinf=1e30),
                               np.nan_to_num(edges, posinf=1e30), atol=1e-5)
    nat_bins = bin_apply_native(X, edges, B)
    host = np.empty(X.shape, np.uint8)
    for f in range(F):
        fe = edges[f][np.isfinite(edges[f])]
        host[:, f] = np.searchsorted(fe, np.nan_to_num(X[:, f], nan=-np.inf),
                                     side="left")
    np.testing.assert_array_equal(nat_bins, host)


def test_lambdarank_uncovered_rows_are_inert():
    """Rows outside group_ptr must receive zero gradients (the old scatter
    unpack left them at zero; the gather unpack must mask them), so a
    group_ptr that doesn't cover the tail doesn't skew training."""
    from mmlspark_tpu.lightgbm.core import lambdarank_grads
    rng = np.random.default_rng(0)
    n, g_sz = 103, 25  # 4 groups of 25 + 3 uncovered tail rows
    scores = rng.normal(size=(n, 1)).astype(np.float32)
    y = rng.integers(0, 3, n).astype(np.float32)
    gp = np.arange(0, 101, g_sz)  # covers rows [0, 100)
    g, h = lambdarank_grads(scores, y, gp)
    assert np.all(g[100:] == 0.0), g[100:]
    assert np.all(h[100:] <= 1e-10)
    assert np.abs(g[:100]).sum() > 0


def test_categorical_one_vs_rest_splits():
    """Categorical features split as code == c vs rest (the reference's
    categorical support, getCategoricalIndexes LightGBMBase.scala:168).
    Membership in a scattered code set is learnable at a depth where
    numerical thresholds on the same codes are not."""
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(0)
    n = 3000
    codes = rng.integers(0, 24, n).astype(np.float32)
    noise = rng.normal(size=(n, 2)).astype(np.float32)
    X = np.column_stack([codes, noise])
    hot = {2.0, 7.0, 11.0, 19.0}
    y = np.isin(codes, list(hot)).astype(np.float32)

    p_cat = GBDTParams(num_iterations=12, objective="binary", max_depth=3,
                       min_data_in_leaf=5, categorical_features=(0,))
    res_cat = train(X, y, p_cat)
    acc_cat = float(((res_cat.booster.predict(X) > 0.5) == y).mean())

    p_num = GBDTParams(num_iterations=12, objective="binary", max_depth=3,
                       min_data_in_leaf=5)
    acc_num = float(((train(X, y, p_num).booster.predict(X) > 0.5) == y).mean())
    assert acc_cat > 0.97, acc_cat
    assert acc_cat > acc_num + 0.01, (acc_cat, acc_num)

    b = res_cat.booster
    # the model must actually use == splits on the categorical feature, with
    # thresholds that ARE category codes
    cat_splits = b.split_feature == 0  # -1 sentinel excluded by ==
    assert cat_splits.any()
    thr = b.threshold[cat_splits]
    assert np.allclose(thr, np.round(thr))
    assert set(np.unique(thr)) <= set(np.arange(24, dtype=np.float32))

    # serde round-trips the categorical metadata and predictions
    from mmlspark_tpu.models.gbdt import GBDTBooster
    b2 = GBDTBooster.from_string(b.to_string())
    assert b2.categorical_features == [0]
    np.testing.assert_allclose(b2.predict(X[:100]), b.predict(X[:100]),
                               rtol=1e-6)

    # TreeSHAP stays additive with categorical splits
    contrib = b.predict_contrib(X[:20])
    raw = b.raw_scores(X[:20])[:, 0]
    np.testing.assert_allclose(contrib.sum(axis=1), raw, atol=1e-4)


def test_categorical_estimator_surface():
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.core.schema import vector_column
    rng = np.random.default_rng(1)
    codes = rng.integers(0, 8, 400).astype(np.float64)
    y = np.isin(codes, [1, 4, 6]).astype(np.float64)
    X = np.column_stack([codes, rng.normal(size=400)])
    df = DataFrame.from_dict({"features": vector_column(list(X)), "label": y})
    clf = LightGBMClassifier().set_params(num_iterations=10, max_depth=3,
                                          min_data_in_leaf=3,
                                          categorical_features=[0])
    model = clf.fit(df)
    pred = model.transform(df).collect()["prediction"]
    assert float((pred == y).mean()) > 0.97


def test_categorical_nan_and_validation():
    """NaN categorical values bin to the reserved last bin, never become a
    split code, and route RIGHT consistently at train and predict time;
    out-of-range categorical indices raise."""
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(2)
    n = 1200
    codes = rng.integers(0, 6, n).astype(np.float32)
    codes[::7] = np.nan  # missingness correlates with the label
    y = (np.nan_to_num(codes, nan=99) == 3).astype(np.float32)
    X = np.column_stack([codes, rng.normal(size=n).astype(np.float32)])
    p = GBDTParams(num_iterations=8, objective="binary", max_depth=3,
                   min_data_in_leaf=3, max_bin=16, categorical_features=(0,))
    res = train(X, y, p)
    b = res.booster
    cat_thr = b.threshold[b.split_feature == 0]
    assert not np.any(cat_thr == 15), "reserved NaN bin must never be a code"
    # training-time fit and predict-time walk agree on the NaN rows
    pred = b.predict(X)
    acc = float(((pred > 0.5) == y).mean())
    assert acc > 0.95, acc
    # non-integer codes round consistently with binning
    Xq = X.copy()
    Xq[:, 0] = np.where(np.isnan(Xq[:, 0]), np.nan, Xq[:, 0] + 0.001)
    np.testing.assert_allclose(b.predict(Xq), pred, rtol=1e-6)

    import pytest as _pt
    with _pt.raises(ValueError, match="out of range"):
        train(X, y, GBDTParams(num_iterations=1, objective="binary",
                               categorical_features=(-1,)))


def test_categorical_negative_codes_raise():
    from mmlspark_tpu.lightgbm import GBDTParams, train
    X = np.array([[-1.0, 0.5], [2.0, 0.1], [1.0, 0.3]] * 20, np.float32)
    y = np.array([0, 1, 0] * 20, np.float32)
    import pytest as _pt
    with _pt.raises(ValueError, match="negative codes"):
        train(X, y, GBDTParams(num_iterations=1, objective="binary",
                               min_data_in_leaf=1, categorical_features=(0,)))


def test_poisson_and_tweedie_objectives():
    """Log-link objectives (native-LightGBM parity: the reference passes
    objective strings straight through): predictions come back on the MEAN
    scale and beat the constant-mean baseline on count data."""
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(0)
    n = 2000
    X = rng.normal(size=(n, 6)).astype(np.float32)
    lam = np.exp(0.8 * X[:, 0] - 0.5 * X[:, 1])
    y = rng.poisson(lam).astype(np.float32)

    for obj in ("poisson", "tweedie"):
        res = train(X, y, GBDTParams(num_iterations=40, objective=obj,
                                     max_depth=4, min_data_in_leaf=10,
                                     learning_rate=0.1))
        pred = res.booster.predict(X)
        assert (pred >= 0).all(), obj  # mean scale, never negative
        dev = float(np.mean((pred - lam) ** 2))
        base = float(np.mean((y.mean() - lam) ** 2))
        assert dev < base * 0.35, (obj, dev, base)

    # estimator surface
    from mmlspark_tpu.lightgbm import LightGBMRegressor
    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.core.schema import vector_column
    df = DataFrame.from_dict({"features": vector_column(list(X)),
                              "label": y.astype(np.float64)})
    m = LightGBMRegressor().set_params(objective="poisson", num_iterations=20,
                                       min_data_in_leaf=10).fit(df)
    p2 = m.transform(df).collect()["prediction"]
    assert (np.asarray(p2) >= 0).all()


def test_poisson_rejects_negative_labels_and_tweedie_early_stops():
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(1)
    X = rng.normal(size=(200, 4)).astype(np.float32)
    import pytest as _pt
    with _pt.raises(ValueError, match="non-negative"):
        train(X, rng.normal(size=200).astype(np.float32),
              GBDTParams(num_iterations=1, objective="poisson"))
    # tweedie early stopping evaluates on the MEAN scale (tweedie_nll)
    lam = np.exp(0.6 * X[:, 0])
    y = rng.poisson(lam).astype(np.float32)
    res = train(X[:150], y[:150],
                GBDTParams(num_iterations=60, objective="tweedie", max_depth=3,
                           min_data_in_leaf=5, early_stopping_round=5),
                valid=(X[150:], y[150:]))
    assert res.evals and "tweedie_nll" in res.evals[0]
    vals = [e["tweedie_nll"] for e in res.evals]
    assert vals[min(len(vals) - 1, 5)] <= vals[0]  # the metric improves


def test_tweedie_metric_fallback_and_rho_validation():
    from mmlspark_tpu.lightgbm import GBDTParams, train
    from mmlspark_tpu.lightgbm.core import resolve_metric
    rng = np.random.default_rng(2)
    X = rng.normal(size=(150, 3)).astype(np.float32)
    y = rng.poisson(1.0, 150).astype(np.float32)
    # unknown metric name with tweedie falls back instead of KeyError
    p = GBDTParams(num_iterations=2, objective="tweedie", metric="logloss",
                   min_data_in_leaf=5)
    fn, lb = resolve_metric("logloss", p)
    assert lb is False and np.isfinite(fn(y, np.zeros((150, 1))))
    train(X[:100], y[:100], p, valid=(X[100:], y[100:]))  # no crash
    import pytest as _pt
    with _pt.raises(ValueError, match="tweedie_variance_power"):
        train(X, y, GBDTParams(num_iterations=1, objective="tweedie",
                               tweedie_variance_power=1.0))


def test_gamma_objective_and_pinball_metric():
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(3)
    n = 1500
    X = rng.normal(size=(n, 5)).astype(np.float32)
    mu = np.exp(0.6 * X[:, 0])
    y = rng.gamma(shape=2.0, scale=mu / 2.0, size=n).astype(np.float32) + 1e-3
    res = train(X, y, GBDTParams(num_iterations=40, objective="gamma",
                                 max_depth=4, min_data_in_leaf=10))
    pred = res.booster.predict(X)
    assert (pred > 0).all()
    assert float(np.mean((pred - mu) ** 2)) < float(np.mean((y.mean() - mu) ** 2)) * 0.4
    import pytest as _pt
    with _pt.raises(ValueError, match="strictly positive"):
        train(X, np.zeros(n, np.float32),
              GBDTParams(num_iterations=1, objective="gamma"))

    # quantile objective now early-stops on its own pinball loss
    yq = (2 * X[:, 0] + rng.normal(scale=0.5, size=n)).astype(np.float32)
    res_q = train(X[:1200], yq[:1200],
                  GBDTParams(num_iterations=30, objective="quantile",
                             alpha=0.9, max_depth=3, min_data_in_leaf=10),
                  valid=(X[1200:], yq[1200:]))
    assert res_q.evals and "pinball" in res_q.evals[0]
    # alpha=0.9 predictions skew toward the upper conditional percentile
    # (well above the ~0.5 coverage a median/L2 fit would give; exact 0.9
    # needs more iterations than this smoke budget)
    frac_below = float((yq <= res_q.booster.predict(X)).mean())
    assert 0.7 < frac_below <= 1.0, frac_below
