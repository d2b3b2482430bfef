"""The plain references agree with the program where the program is right,
and the checks built on them refuse what is wrong."""
import numpy as np
import pytest

from benchmark.families import gbdt_reference as gref


def _brute_force_gains(binned, y, bins, min_data, min_hess):
    n, features = binned.shape
    p = y.mean()
    g, h = p - y, np.full(n, p * (1 - p))
    out = np.full((features, bins - 1), -np.inf)
    for f in range(features):
        for b in range(bins - 1):
            left = binned[:, f] <= b
            if left.sum() < min_data or (~left).sum() < min_data \
                    or h[left].sum() < min_hess or h[~left].sum() < min_hess:
                continue
            out[f, b] = g[left].sum() ** 2 / h[left].sum() \
                + g[~left].sum() ** 2 / h[~left].sum()
    return out


def test_root_split_gains_equal_a_brute_force_search():
    rng = np.random.default_rng(0)
    binned = rng.integers(0, 12, (600, 5)).astype(np.uint8)
    y = ((binned[:, 2] > 5) ^ (rng.random(600) < 0.1)).astype(np.float32)
    got = gref.root_split_gains(binned, y, 12, 20, 1e-3, threads=2)
    want = _brute_force_gains(binned, y.astype(np.float64), 12, 20, 1e-3)
    assert got.shape == (5, 11)
    assert np.array_equal(np.isfinite(got), np.isfinite(want))
    ok = np.isfinite(want)
    assert np.allclose(got[ok], want[ok], rtol=1e-6)
    assert np.unravel_index(np.argmax(got), got.shape) == (2, 5)


@pytest.mark.parametrize("feature,bin_,ok", [
    (2, 5, True), (2, 6, True), (2, 4, True),      # the bin or a neighbour
    (2, 9, False),                                 # far off and much worse
    (1, 5, False),                                 # the wrong feature
    (2, 99, False),                                # not a bin at all
])
def test_check_root_split(feature, bin_, ok):
    gains = np.full((4, 11), 1.0)
    gains[2] = [1, 2, 4, 8, 9.0, 10.0, 9.0, 8, 4, 2, 1]
    assert gref.check_root_split(gains, feature, bin_)[0] is ok


def test_a_split_on_the_gain_plateau_passes():
    gains = np.full((2, 11), 1.0)
    gains[0, 3:8] = [9.9995, 9.9999, 10.0, 9.9998, 9.9]
    assert gref.check_root_split(gains, 0, 3)[0]          # within 0.1%
    assert not gref.check_root_split(gains, 0, 7)[0]      # 1% off, two bins


def test_the_tree_walk_agrees_with_the_programs_own_predict():
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(1)
    X = rng.standard_normal((3000, 6)).astype(np.float32)
    y = (X[:, 0] - X[:, 3] + 0.3 * rng.standard_normal(3000) > 0) \
        .astype(np.float32)
    for params in (GBDTParams(objective="binary", max_depth=4,
                              num_iterations=5),
                   GBDTParams(objective="binary", num_leaves=7,
                              num_iterations=3)):        # leaf-wise, ragged
        booster = train(X, y, params).booster
        got = gref.predict_proba(gref.booster_arrays(booster), X[:500])
        assert np.allclose(got, booster.predict(X[:500]), atol=1e-6)
        acc = gref.holdout_accuracy(gref.booster_arrays(booster), X, y)
        assert acc > 0.85


def test_a_walk_that_cannot_end_is_an_error():
    tree = {"split_feature": np.array([[0]]), "threshold": np.array([[0.0]]),
            "left_child": np.array([[0]]), "right_child": np.array([[0]]),
            "leaf_value": np.array([[1.0, 2.0]]), "tree_weight": np.array([1.0]),
            "init_score": 0.0, "sigmoid": 1.0}
    with pytest.raises(ValueError):
        gref.predict_proba(tree, np.zeros((3, 1), np.float32))


def test_the_resnet_reference_is_the_modules_forward_pass_in_float32():
    import jax
    import jax.numpy as jnp
    from benchmark.families import image_featurizer as fam
    from benchmark.families import image_featurizer_reference as iref
    from mmlspark_tpu.models import resnet50
    from mmlspark_tpu.ops import image as image_ops
    module = resnet50(num_classes=10, dtype=jnp.float32)
    variables = fam.make_variables(module, seed=3, init_size=32)
    again = fam.make_variables(module, seed=3, init_size=32)
    other = fam.make_variables(module, seed=4, init_size=32)
    flat = jax.tree_util.tree_leaves_with_path(variables)
    assert all(np.array_equal(a, b) for (_, a), b in
               zip(flat, jax.tree_util.tree_leaves(again)))
    assert not all(np.array_equal(a, b) for (_, a), b in
                   zip(flat, jax.tree_util.tree_leaves(other)))
    # nothing is left inert: no batch-norm scale is zero, no variance one
    for path, leaf in flat:
        if getattr(path[-1], "key", "") in ("scale", "var"):
            assert float(jnp.min(leaf)) >= 0.5 and float(jnp.std(leaf)) > 0.1
    imgs = np.random.default_rng(0).integers(0, 256, (2, 48, 48, 3)) \
        .astype(np.float32)
    want = module.apply(variables, image_ops.normalize(jnp.asarray(imgs)),
                        features=True)
    got = jax.jit(iref.forward)(variables, imgs)
    assert got.shape == (2, 2048)
    assert iref.relative_l2(got, want).max() < 1e-4
    # and the comparison sees a model that computes something else
    broken = jax.tree_util.tree_map(lambda a: a, variables)
    broken["params"]["BottleneckBlock_7"]["Conv_1"]["kernel"] = \
        broken["params"]["BottleneckBlock_7"]["Conv_1"]["kernel"] * 0.5
    off = module.apply(broken, image_ops.normalize(jnp.asarray(imgs)),
                       features=True)
    assert iref.relative_l2(off, got).max() > 0.02


@pytest.mark.parametrize("spoil,wrong", [(None, 0), (2, 1)])
def test_sampled_replies_are_held_to_the_stages_transform_in_warmed_batches(
        spoil, wrong):
    """After the window the served stage transforms the sampled images
    itself, in the batch sizes the warm-up went through, and a reply that
    is another image's features is a failed request."""
    import json
    from types import SimpleNamespace
    from benchmark.families import image_featurizer as fam
    system = object.__new__(fam.FeaturizerSystem)
    notes, failures, batches = [], [], []
    system.cfg = {"reference": {"relative_l2_tolerance": 0.02}}
    system.run = SimpleNamespace(mix={"warm_batches": [1, 2, 4]},
                                 note=notes.append, fail=failures.append)
    system.pool = np.arange(1.0, 11.0)[:, None] * np.ones((10, 3))

    def featurize(images):
        batches.append(len(images))
        return np.asarray(images, np.float32) * 2.0
    system._featurize = featurize
    picks = [3, 1, 4, 1, 5, 9]
    sampled = [(p, json.dumps((system.pool[p] * 2.0).tolist()).encode())
               for p in picks]
    if spoil is not None:
        sampled[spoil] = (picks[spoil], sampled[0][1])
    assert system.wrong_replies(sampled) == wrong
    assert batches == [4, 2] and len(failures) == wrong and len(notes) == 1
