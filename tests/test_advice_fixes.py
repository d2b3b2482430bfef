"""Regression tests for the round-1 advisor findings (ADVICE.md)."""
import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.lightgbm import GBDTParams, train
from mmlspark_tpu.vw import VowpalWabbitClassifier, VowpalWabbitRegressor
from mmlspark_tpu.vw.featurizer import VowpalWabbitFeaturizer


def _sparse_frame(n=200, seed=0):
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    y = (x1 + 0.5 * x2 > 0).astype(np.float64)
    df = DataFrame.from_dict({"a_num": x1, "b_num": x2, "label": y})
    feats = VowpalWabbitFeaturizer(input_cols=["a_num", "b_num"],
                                   output_col="features")
    return feats.transform(df)


def test_vw_loss_function_arg_is_per_instance():
    """ADVICE #2: ``--loss_function`` must set the instance Param; parsing on
    one estimator must not leak into other instances of the class."""
    df = _sparse_frame()
    hinge = VowpalWabbitClassifier().set_params(args="--loss_function hinge", num_passes=2)
    plain = VowpalWabbitClassifier().set_params(num_passes=2)
    m_hinge = hinge.fit(df)
    assert hinge.get("loss_function") == "hinge"
    # the second instance is untouched by the first instance's arg parsing
    assert plain.get("loss_function") == "logistic"
    m_plain = plain.fit(df)
    # and the parsed loss actually changes training
    assert not np.allclose(m_hinge.weights, m_plain.weights)


def test_vw_args_power_t_and_interactions():
    """``-q ab`` crosses namespace (sparse featurizer output) columns whose
    names start with 'a' and 'b' — VW's first-letter namespace matching."""
    rng = np.random.default_rng(0)
    x1, x2 = rng.normal(size=200), rng.normal(size=200)
    y = x1 * x2  # pure interaction target: only -q can fit this
    df = DataFrame.from_dict({"a_num": x1, "b_num": x2, "label": y})
    for cols, out in ((["a_num"], "a_ns"), (["b_num"], "b_ns"),
                      (["a_num", "b_num"], "features")):
        df = VowpalWabbitFeaturizer(input_cols=cols, output_col=out).transform(df)

    est = VowpalWabbitRegressor().set_params(args="--power_t 0.3 -q ab",
                                             label_col="label", num_passes=4)
    est._parse_args()
    assert est.get("power_t") == 0.3
    assert est.get("interactions") == ["ab"]
    model = est.fit(df)
    assert model.get("interactions") == ["ab"]
    out = model.transform(df).to_pandas()
    assert len(out["prediction"]) == 200
    # interactions add crossed feature mass: weights differ from a plain fit
    plain = VowpalWabbitRegressor().set_params(label_col="label",
                                               num_passes=4).fit(df)
    assert not np.allclose(model.weights, plain.weights)
    # and the crossed features actually capture the x1*x2 structure better
    err_q = float(np.mean((out["prediction"] - y) ** 2))
    pred_plain = plain.transform(df).to_pandas()["prediction"]
    err_plain = float(np.mean((pred_plain - y) ** 2))
    assert err_q < err_plain


def test_gbdt_warm_start_bagging_off_schedule():
    """ADVICE #4: warm start beginning on an iteration where
    ``it % bagging_freq != 0`` must not raise UnboundLocalError."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(400, 8)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    p = GBDTParams(num_iterations=3, objective="binary", max_depth=3,
                   bagging_freq=2, bagging_fraction=0.5, seed=7)
    r1 = train(X, y, p)
    assert r1.booster.num_trees == 3
    # continue from iteration 3 (3 % 2 != 0): first loop pass must resample
    p2 = GBDTParams(num_iterations=2, objective="binary", max_depth=3,
                    bagging_freq=2, bagging_fraction=0.5, seed=7)
    r2 = train(X, y, p2, init_booster=r1.booster)
    assert r2.booster.num_trees == 5


def test_gbdt_warm_start_respects_init_score_shift():
    """ADVICE #1: continuing training on data with a different base score
    must anchor the replayed scores at the INIT booster's init_score, so the
    returned booster's predictions match the new data."""
    rng = np.random.default_rng(1)
    X1 = rng.normal(size=(500, 6)).astype(np.float32)
    y1 = (0.05 * X1[:, 0]).astype(np.float32)          # mean ~ 0
    r1 = train(X1, y1, GBDTParams(num_iterations=2, objective="regression",
                                  max_depth=3, learning_rate=0.2))
    assert abs(r1.booster.init_score) < 0.5
    X2 = rng.normal(size=(500, 6)).astype(np.float32)
    y2 = (10.0 + 0.05 * X2[:, 0]).astype(np.float32)   # mean ~ 10
    r2 = train(X2, y2, GBDTParams(num_iterations=40, objective="regression",
                                  max_depth=3, learning_rate=0.3),
               init_booster=r1.booster)
    pred = r2.booster.predict(X2)
    # with the old no-op delta the booster predicted ~0 here (off by ~10)
    assert abs(float(np.mean(pred)) - 10.0) < 1.0


def test_safe_load_refuses_pickle_and_foreign_classes(tmp_path):
    """ADVICE #5: opt-in safe mode blocks the two code-execution paths."""
    from mmlspark_tpu.core import serialize
    from mmlspark_tpu.stages import Lambda

    stage = Lambda(fn=lambda p: p)  # closure payload -> pickle fallback
    path = str(tmp_path / "lam")
    serialize.save(stage, path)
    loaded = serialize.load(path)  # trusted path: works
    assert isinstance(loaded, Lambda)
    with pytest.raises(PermissionError):
        serialize.load(path, safe=True)

    class NotOurs(Lambda):
        pass

    p2 = str(tmp_path / "foreign")
    serialize.save(NotOurs(fn=lambda p: p), p2)
    with pytest.raises(PermissionError):
        serialize.load(p2, safe=True)
    serialize.register_loadable_prefix("tests.")
    try:
        with pytest.raises(PermissionError):  # still pickled payload inside
            serialize.load(p2, safe=True)
    finally:
        serialize._TRUSTED_PREFIXES.discard("tests.")


# ---------------------------------------------------------------------------
# round-2 advisor findings
# ---------------------------------------------------------------------------

def test_load_dataframe_honours_safe_load_env(tmp_path, monkeypatch):
    """ADVICE r2 (medium): direct load_dataframe() must resolve
    MMLSPARK_TPU_SAFE_LOAD like load_stage/load do."""
    from mmlspark_tpu.core.serialize import load_dataframe, save_dataframe

    df = DataFrame.from_dict({"x": np.arange(4, dtype=np.float64)})
    obj_col = np.empty(4, dtype=object)
    for i in range(4):
        obj_col[i] = {"i": i}
    df = df.with_column("obj", obj_col)
    path = str(tmp_path / "frame")
    save_dataframe(df, path)
    monkeypatch.setenv("MMLSPARK_TPU_SAFE_LOAD", "1")
    with pytest.raises(ValueError):
        load_dataframe(path)                     # env opt-in now applies
    monkeypatch.delenv("MMLSPARK_TPU_SAFE_LOAD")
    out = load_dataframe(path)                   # default stays permissive
    assert out.collect()["obj"][2]["i"] == 2


def test_onnx_lstm_peephole_raises():
    """ADVICE r2: LSTM peephole weights must raise, not silently drop."""
    from mmlspark_tpu.dl.onnx_import import onnx_to_jax
    from mmlspark_tpu.dl.onnx_wire import build_model, encode_node

    seq, batch, inp, H = 3, 2, 4, 5
    rng = np.random.default_rng(0)
    nodes = [encode_node("LSTM", ["x", "W", "R", "B", "", "", "", "P"],
                         ["Y"], hidden_size=H)]
    init = {"W": rng.normal(size=(1, 4 * H, inp)).astype(np.float32),
            "R": rng.normal(size=(1, 4 * H, H)).astype(np.float32),
            "B": np.zeros((1, 8 * H), np.float32),
            "P": np.zeros((1, 3 * H), np.float32)}
    data = build_model(nodes, init, [("x", [seq, batch, inp])],
                       [("Y", [seq, 1, batch, H])])
    with pytest.raises(NotImplementedError, match="peephole"):
        apply_fn, variables = onnx_to_jax(data)
        apply_fn(variables, np.zeros((seq, batch, inp), np.float32))


def test_checkpoint_backend_marker_beats_mtime(tmp_path):
    """ADVICE r2: when both backends wrote, the marker (not cp/rsync-fragile
    mtimes) decides; explicit backend= wins over everything."""
    import jax.numpy as jnp
    import optax
    from mmlspark_tpu.parallel.checkpoint import (load_train_state,
                                                  save_train_state)
    from mmlspark_tpu.parallel.trainer import TrainState

    params = {"w": jnp.arange(4, dtype=jnp.float32)}
    opt = optax.sgd(0.1)
    state_a = TrainState(params=params, opt_state=opt.init(params), step=1)
    state_b = TrainState(params={"w": jnp.arange(4, dtype=jnp.float32) + 10},
                         opt_state=opt.init(params), step=2)
    path = str(tmp_path / "ckpt")
    save_train_state(state_a, path, backend="orbax")
    save_train_state(state_b, path, backend="npz")   # npz wrote LAST
    # adversarial mtime: touch the orbax dir newer than the npz
    import os, time
    os.utime(os.path.join(path, "orbax"))
    restored = load_train_state(path)
    assert int(restored.step) == 2                   # marker wins
    template = TrainState(params=params, opt_state=opt.init(params), step=0)
    forced = load_train_state(path, template=template, backend="orbax")
    assert int(forced.step) == 1                     # explicit wins


def test_histogram_explicit_backend_honoured_unknown_raises(as_platform):
    """An explicit backend is what runs whatever the platform's own choice
    would be, and a name that is no backend raises: it is outside input,
    and used to mean scatter silently."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as hist_ops

    rng = np.random.default_rng(0)
    binned = jnp.asarray(rng.integers(0, 8, size=(64, 3)).astype(np.int32))
    g = jnp.asarray(rng.normal(size=64).astype(np.float32))
    h = jnp.ones(64, jnp.float32)
    node = jnp.zeros(64, jnp.int32)

    def dots(backend):          # the matmul builders contract, scatter never
        jaxpr = jax.make_jaxpr(lambda: hist_ops.build(
            binned, g, h, node, 1, 8, backend=backend))()
        return "dot_general" in str(jaxpr)

    assert not dots("auto") and not dots("scatter") and dots("matmul")
    as_platform("tpu")
    assert dots("auto") and not dots("scatter") and dots("matmul")
    out = hist_ops.build(binned, g, h, node, 1, 8, backend="scatter")
    assert out.shape == (1, 3, 8, 3)
    out2 = hist_ops.build(binned, g, h, node, 1, 8, backend="auto")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-4)
    qg = jnp.ones(64, jnp.int32)
    for name in ("bogus_backend", "pallas", ""):
        with pytest.raises(ValueError, match="auto.*scatter.*matmul"):
            hist_ops.build(binned, g, h, node, 1, 8, backend=name)
        with pytest.raises(ValueError, match="auto.*scatter.*matmul"):
            hist_ops.build_quantized(binned, qg, qg, node, 1, 8,
                                     backend=name)


def test_vw_bfgs_stats_count_packed_nnz_per_partition():
    """ADVICE r2: features_per_example counts pre-padding nnz (explicit
    zeros included), with true partition ids."""
    rng = np.random.default_rng(1)
    parts = []
    for pid in range(2):
        n = 50
        feats = np.empty(n, dtype=object)
        for i in range(n):
            feats[i] = {"indices": np.asarray([0, 5, 9]),
                        "values": np.asarray([1.0, 0.0, 2.0])}  # explicit 0
        y = rng.integers(0, 2, n).astype(np.float64)
        parts.append({"features": feats, "label": y})
    from mmlspark_tpu.core.schema import ColumnType, Schema
    df = DataFrame(parts, schema=Schema({"features": ColumnType.STRUCT,
                                         "label": ColumnType.DOUBLE}))
    reg = VowpalWabbitRegressor().set_params(args="--bfgs", num_passes=3)
    model = reg.fit(df)
    stats = model.get_performance_statistics().collect()
    assert sorted(stats["partition_id"].tolist()) == [0, 1]
    for fpe in stats["features_per_example"]:
        assert fpe == pytest.approx(3.0)  # not 2.0 (explicit zero counts)


def test_vw_classifier_extreme_margin_no_overflow():
    """ADVICE r2 / VERDICT weak #8: the predict sigmoid must not overflow on
    extreme raw margins."""
    import warnings
    df = _sparse_frame(300, seed=7)
    scaled = df.map_partitions(
        lambda p: {**p, "features": np.asarray(
            [{"indices": v["indices"], "values": v["values"] * 1e4}
             for v in p["features"]], dtype=object)})
    model = VowpalWabbitClassifier().set_params(num_passes=3,
                                                learning_rate=5.0).fit(scaled)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        out = model.transform(scaled).collect()
    probs = np.stack(list(out["probability"]))
    assert np.isfinite(probs).all()


def test_domain_specific_content_url_resolved_lazily():
    """ADVICE r4: set('model', ...) AFTER set_location must not leave a stale
    'celebrities' endpoint — the URL is resolved at request-build time."""
    from mmlspark_tpu.cognitive.services import RecognizeDomainSpecificContent
    t = RecognizeDomainSpecificContent()
    t.set_location("eastus")
    t.set("model", "landmarks")
    url = t._base_url()
    assert "/models/landmarks/analyze" in url, url
    assert "celebrities" not in url
    # explicit url always wins over location
    t2 = RecognizeDomainSpecificContent()
    t2.set("url", "https://custom.example/v1")
    t2.set_location("eastus")
    assert t2._base_url() == "https://custom.example/v1"
