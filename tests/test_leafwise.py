"""Leaf-wise (best-first) growth — LightGBM's defining algorithm.

Reference: ``numLeaves`` default 31 with best-gain leaf growth
(``lightgbm/src/main/scala/.../params/LightGBMParams.scala:331-332``); the
round-2 rebuild silently rewrote num_leaves into a perfect-tree depth, which
changes the model class.  These tests pin the num_leaves-true semantics.
"""
import numpy as np
import pytest

from mmlspark_tpu.lightgbm import GBDTParams, train
from mmlspark_tpu.lightgbm.estimators import (LightGBMClassifier,
                                              LightGBMRegressor)
from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.core.schema import vector_column
from mmlspark_tpu.models.gbdt import GBDTBooster, children_depth_bound


def _xor_data(n=2000, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 10)).astype(np.float32)
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0)).astype(np.float32)
    return X, y


def _rings_data(n=2400, seed=1):
    rng = np.random.default_rng(seed)
    r = np.sqrt(rng.uniform(0, 4, n))
    th = rng.uniform(0, 2 * np.pi, n)
    X = np.stack([r * np.cos(th), r * np.sin(th)], axis=1).astype(np.float32)
    X = np.concatenate([X, rng.normal(size=(n, 6)).astype(np.float32)], axis=1)
    y = (r.astype(np.float32) % 1.0 > 0.5).astype(np.float32)
    return X, y


def test_leafwise_beats_depth_capped_on_xor_and_rings():
    """VERDICT r2 gate: LightGBMClassifier(num_leaves=31) must beat the old
    depth-capped model on the xor/rings gates."""
    for maker in (_xor_data, _rings_data):
        X, y = maker()
        leaf = train(X, y, GBDTParams(num_iterations=25, objective="binary",
                                      num_leaves=31, min_data_in_leaf=5))
        level = train(X, y, GBDTParams(num_iterations=25, objective="binary",
                                       max_depth=5, min_data_in_leaf=5))
        acc_leaf = ((leaf.booster.predict(X) > 0.5) == y).mean()
        acc_level = ((level.booster.predict(X) > 0.5) == y).mean()
        assert acc_leaf >= acc_level, (maker.__name__, acc_leaf, acc_level)
        assert acc_leaf > 0.9, (maker.__name__, acc_leaf)


def test_num_leaves_is_honoured_exactly():
    """num_leaves=100 must NOT become a 128-leaf perfect tree (the round-2
    silent rewrite)."""
    X, y = _xor_data(4000)
    res = train(X, y, GBDTParams(num_iterations=3, objective="binary",
                                 num_leaves=100, min_data_in_leaf=1))
    b = res.booster
    assert b.num_leaves == 100
    populated = (b.leaf_count > 0).sum(axis=1)
    assert populated.max() <= 100
    # enough signal to actually use the leaf budget
    assert populated.max() > 64


def test_leafwise_respects_max_depth_cap():
    X, y = _xor_data(3000)
    res = train(X, y, GBDTParams(num_iterations=5, objective="binary",
                                 num_leaves=31, max_depth=3,
                                 min_data_in_leaf=2))
    b = res.booster
    assert children_depth_bound(b.left_child, b.right_child) <= 3
    # and the cap binds: uncapped growth goes deeper
    free = train(X, y, GBDTParams(num_iterations=5, objective="binary",
                                  num_leaves=31, min_data_in_leaf=2))
    assert children_depth_bound(free.booster.left_child,
                                free.booster.right_child) > 3


def test_leafwise_serde_and_shap_roundtrip():
    X, y = _xor_data(1500)
    b = train(X, y, GBDTParams(num_iterations=8, objective="binary",
                               num_leaves=15, min_data_in_leaf=5)).booster
    b2 = GBDTBooster.from_string(b.to_string())
    np.testing.assert_allclose(b2.predict(X), b.predict(X), rtol=1e-6)
    Xs = X[:16]
    raw = b.raw_scores(Xs)[:, 0]
    shap = b.predict_contrib(Xs)
    np.testing.assert_allclose(shap.sum(axis=1), raw, rtol=1e-4, atol=1e-4)
    sab = b.predict_contrib(Xs, method="saabas")
    np.testing.assert_allclose(sab.sum(axis=1), raw, rtol=1e-4, atol=1e-4)


def test_leafwise_warm_start_continues_training():
    X, y = _xor_data(1500, seed=5)
    p = GBDTParams(num_iterations=5, objective="binary", num_leaves=15,
                   min_data_in_leaf=5)
    first = train(X, y, p).booster
    cont = train(X, y, p, init_booster=first).booster
    assert cont.num_trees == 10
    from mmlspark_tpu.lightgbm.core import resolve_metric
    mfn, _ = resolve_metric("binary_logloss", p)
    ll_first = mfn(y, first.raw_scores(X))
    ll_cont = mfn(y, cont.raw_scores(X))
    assert ll_cont < ll_first


def test_leafwise_pretty_old_artifact_migration():
    """Pre-round-3 JSON artifacts (no child arrays) must still load as
    perfect trees."""
    X, y = _xor_data(800)
    b = train(X, y, GBDTParams(num_iterations=3, objective="binary",
                               max_depth=3, min_data_in_leaf=5)).booster
    import json
    d = json.loads(b.to_string())
    del d["arrays"]["left_child"], d["arrays"]["right_child"]
    b2 = GBDTBooster.from_string(json.dumps(d))
    np.testing.assert_allclose(b2.predict(X), b.predict(X), rtol=1e-6)


def test_estimator_default_is_leafwise_31():
    X, y = _xor_data(1200)
    df = DataFrame.from_dict({"features": vector_column(list(X)),
                              "label": y.astype(float)})
    model = LightGBMClassifier().set_params(num_iterations=10,
                                            min_data_in_leaf=5).fit(df)
    b = model.booster
    assert b.num_leaves == 31                      # LightGBM default
    # explicit max_depth alone still selects the level-wise fast path
    model2 = LightGBMRegressor().set_params(num_iterations=3,
                                            max_depth=3).fit(df)
    assert model2.booster.num_leaves == 8          # perfect depth-3 tree


def test_leafwise_sharded_matches_single_device():
    """Row-sharded leaf-wise growth (histogram psum per split step) must
    reproduce the single-device tree structure."""
    from mmlspark_tpu.parallel import active_mesh, make_mesh

    rng = np.random.default_rng(3)
    X = rng.normal(size=(512, 10)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 3] > 0).astype(np.float32)
    base = dict(num_iterations=3, objective="binary", num_leaves=8,
                min_data_in_leaf=2)
    single = train(X, y, GBDTParams(**base))
    mesh = make_mesh({"data": 8})
    with active_mesh(mesh):
        sharded = train(X, y, GBDTParams(**base), shard_rows=True)
    np.testing.assert_array_equal(sharded.booster.split_feature[0],
                                  single.booster.split_feature[0])
    np.testing.assert_array_equal(sharded.booster.left_child[0],
                                  single.booster.left_child[0])
    np.testing.assert_allclose(sharded.booster.raw_scores(X),
                               single.booster.raw_scores(X), atol=5e-3)


def test_leafwise_voting_parallel_matches_full_psum():
    """voting_parallel under leaf-wise growth: with 2k >= F every feature is
    selected, so trees must match the full-psum path."""
    from mmlspark_tpu.parallel import active_mesh, make_mesh

    rng = np.random.default_rng(4)
    X = rng.normal(size=(512, 10)).astype(np.float32)
    y = (X[:, 1] - 0.5 * X[:, 6] > 0).astype(np.float32)
    base = dict(num_iterations=2, objective="binary", num_leaves=8,
                min_data_in_leaf=2)
    mesh = make_mesh({"data": 8})
    with active_mesh(mesh):
        full = train(X, y, GBDTParams(**base), shard_rows=True)
        vote = train(X, y, GBDTParams(**base, voting_k=5), shard_rows=True)
    np.testing.assert_array_equal(vote.booster.split_feature[0],
                                  full.booster.split_feature[0])
    np.testing.assert_array_equal(vote.booster.threshold_bin[0],
                                  full.booster.threshold_bin[0])
    agree = float(((vote.booster.predict(X) > 0.5)
                   == (full.booster.predict(X) > 0.5)).mean())
    assert agree > 0.999, agree


def test_warm_start_deeper_trees_than_continuation_bound():
    """Code-review r3: replaying a warm-start booster whose trees are DEEPER
    than the continuation run's depth bound must walk them fully (a
    truncated walk gathers from a negative pseudo-leaf and corrupts
    scores)."""
    X, y = _xor_data(2500, seed=9)
    deep = train(X, y, GBDTParams(num_iterations=10, objective="binary",
                                  num_leaves=31, min_data_in_leaf=2)).booster
    assert deep.max_depth > 3  # premise: warm-start trees really are deeper
    capped = GBDTParams(num_iterations=5, objective="binary", num_leaves=31,
                        max_depth=3, min_data_in_leaf=2)
    cont = train(X, y, capped, init_booster=deep).booster
    from mmlspark_tpu.lightgbm.core import resolve_metric
    mfn, _ = resolve_metric("binary_logloss", capped)
    # continued training must improve on the warm start, which only happens
    # if the replayed scores were computed from correctly-walked leaves
    assert mfn(y, cont.raw_scores(X)) < mfn(y, deep.raw_scores(X))


def test_estimator_num_leaves_with_max_depth_stays_leafwise():
    """Code-review r3: set_params(num_leaves=20, max_depth=4) must run
    leaf-wise with 20 leaves capped at depth 4 — not level-wise with 16."""
    X, y = _xor_data(2000)
    df = DataFrame.from_dict({"features": vector_column(list(X)),
                              "label": y.astype(float)})
    model = LightGBMClassifier().set_params(num_iterations=5, num_leaves=20,
                                            max_depth=4,
                                            min_data_in_leaf=2).fit(df)
    b = model.booster
    assert b.num_leaves == 20
    assert children_depth_bound(b.left_child, b.right_child) <= 4


def test_levelwise_continuation_of_deeper_leafwise_booster_predicts_right():
    """Code-review r3: the merged booster's max_depth (walk bound) must
    resolve warm-start trees deeper than the continuation's depth."""
    X, y = _xor_data(2500, seed=11)
    deep = train(X, y, GBDTParams(num_iterations=8, objective="binary",
                                  num_leaves=32, min_data_in_leaf=2)).booster
    assert deep.max_depth > 5
    cont = train(X, y, GBDTParams(num_iterations=3, objective="binary",
                                  growth="level", max_depth=5,
                                  min_data_in_leaf=2),
                 init_booster=deep).booster
    assert cont.max_depth >= deep.max_depth
    # replayed + new trees must at least not regress vs the warm start
    from mmlspark_tpu.lightgbm.core import resolve_metric
    mfn, _ = resolve_metric("binary_logloss", GBDTParams(objective="binary"))
    assert mfn(y, cont.raw_scores(X)) <= mfn(y, deep.raw_scores(X)) + 1e-9


def test_estimator_growth_level_with_explicit_num_leaves():
    """Code-review r3: growth='level' + num_leaves=64 must give depth-6
    trees (64 leaves), matching GBDTParams semantics."""
    X, y = _xor_data(1500)
    df = DataFrame.from_dict({"features": vector_column(list(X)),
                              "label": y.astype(float)})
    m = LightGBMClassifier().set_params(num_iterations=2, growth="level",
                                        num_leaves=64,
                                        min_data_in_leaf=2).fit(df)
    assert m.booster.num_leaves == 64


def test_leafwise_matmul_backend_agrees(as_platform):
    """Leaf-wise growth through the float MXU matmul histogram backend
    (the accelerator's, with quantization turned off) must match the
    scatter build."""
    import numpy as np
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(17)
    X = rng.normal(size=(3000, 8)).astype(np.float32)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0.2).astype(np.float32)
    params = GBDTParams(num_iterations=6, num_leaves=15, min_data_in_leaf=5,
                        use_quantized_grad=False)
    as_platform("tpu")
    r_m = train(X, y, params)
    as_platform("cpu")
    r_s = train(X, y, params)
    a, b = r_m.booster.predict(X), r_s.booster.predict(X)
    assert np.allclose(a, b, atol=5e-4), float(np.abs(a - b).max())
