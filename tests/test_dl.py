import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame, save, load


def small_images(n=6, h=8, w=8, c=3, seed=0):
    rng = np.random.default_rng(seed)
    col = np.empty(n, dtype=object)
    for i in range(n):
        col[i] = rng.uniform(0, 255, (h, w, c)).astype(np.float32)
    return DataFrame.from_dict({"image": col}, num_partitions=2)


def test_jax_model_mlp_vectors():
    import jax
    import jax.numpy as jnp
    import flax.linen as nn
    from mmlspark_tpu.dl import JaxModel

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(nn.relu(nn.Dense(8)(x)))

    mod = MLP()
    variables = mod.init(jax.random.PRNGKey(0), jnp.zeros((1, 5)))
    df = DataFrame.from_dict({"feats": np.random.default_rng(1).normal(size=(11, 5))}, 2)
    m = JaxModel().set_model(module=mod, variables=variables)
    m.set("input_col", "feats").set("output_col", "out").set("batch_size", 4)
    out = m.transform(df)
    col = out.collect()["out"]
    assert len(col) == 11 and col[0].shape == (4,)
    # determinism across batch-size padding
    m2 = JaxModel().set_model(module=mod, variables=variables)
    m2.set("input_col", "feats").set("output_col", "out").set("batch_size", 64)
    col2 = m2.transform(df).collect()["out"]
    assert np.allclose(np.stack(list(col)), np.stack(list(col2)), atol=1e-5)


def test_jax_model_save_load(tmp_path):
    import jax
    import jax.numpy as jnp
    import flax.linen as nn
    from mmlspark_tpu.dl import JaxModel

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(2)(x)

    mod = Tiny()
    variables = mod.init(jax.random.PRNGKey(0), jnp.zeros((1, 3)))
    m = JaxModel().set_model(module=mod, variables=variables)
    m.set("input_col", "x").set("output_col", "y")
    path = str(tmp_path / "jaxmodel")
    save(m, path)
    m2 = load(path)
    df = DataFrame.from_dict({"x": np.ones((5, 3))})
    a = np.stack(list(m.transform(df).collect()["y"]))
    b = np.stack(list(m2.transform(df).collect()["y"]))
    assert np.allclose(a, b, atol=1e-6)


def test_image_featurizer_resnet18_small():
    from mmlspark_tpu.dl import ImageFeaturizer, ModelDownloader
    payload = ModelDownloader().download_by_name("ResNet18", num_classes=10)
    feat = ImageFeaturizer()
    feat.set("model", payload)
    feat.set_params(input_col="image", output_col="features",
                    height=32, width=32, batch_size=4)
    df = small_images(5)
    out = feat.transform(df)
    col = out.collect()["features"]
    assert len(col) == 5
    assert col[0].shape == (512,)  # resnet18 penultimate width
    # cut_output_layers=0 -> logits head
    logits = ImageFeaturizer()
    logits.set("model", payload)
    logits.set_params(input_col="image", output_col="logits", height=32, width=32,
                      batch_size=4, cut_output_layers=0)
    lcol = logits.transform(df).collect()["logits"]
    assert lcol[0].shape == (10,)


def _pixels(n, h, w, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (n, h, w, 3), np.uint8)


def _object_column(rows):
    col = np.empty(len(rows), dtype=object)
    for i, r in enumerate(rows):
        col[i] = r
    return col


def _mixed_dtype_column(px):
    return _object_column([r if i % 2 else r.astype(np.float32)
                           for i, r in enumerate(px)])


def _image_featurizer(**params):
    """ResNet-free featurizer over a tiny conv net, so bit-equality is
    about the input path and not about a deep backbone's run time."""
    import jax
    import jax.numpy as jnp
    import flax.linen as nn
    from mmlspark_tpu.dl import ImageFeaturizer

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Conv(4, (3, 3))(x))
            return nn.Dense(6)(x.mean(axis=(1, 2)))

    mod = Net()
    variables = mod.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)))
    kw = dict(input_col="image", output_col="features", height=8, width=8,
              batch_size=4)
    kw.update(params)
    feat = ImageFeaturizer(**kw)
    return feat.set_model(apply_fn=mod.apply, variables=variables)


def _spy_on_apply_batch(feat):
    """Wrap the bound ``apply_batch`` on the featurizer's runner INSTANCE,
    as ``benchmark/families/image_featurizer.py`` does for its span: the
    stage must look it up there at call time.  Returns the arrays seen."""
    runner = feat._build_runner().runner()
    inner, seen = runner.apply_batch, []

    def apply_batch(x, *args, **kwargs):
        seen.append(x)
        return inner(x, *args, **kwargs)
    runner.apply_batch = apply_batch
    return seen


# (pixels shape (n, h, w); caller's column from the uint8 pixels; stage
# params; the dtype the runner must be handed)
_INPUT_PATH_CASES = {
    "uint8_object_column": ((6, 8, 8), _object_column, {}, np.uint8),
    "uint8_dense_column": ((6, 8, 8), lambda px: px, {}, np.uint8),
    "float32_object_column":
        ((6, 8, 8), lambda px: _object_column(px.astype(np.float32)), {},
         np.float32),
    "float64_dense_column":
        ((6, 8, 8), lambda px: px.astype(np.float64), {}, np.float32),
    "unrolled_uint8_rows":
        ((6, 8, 8), lambda px: _object_column(px.reshape(len(px), -1)), {},
         np.uint8),
    "unrolled_dense_vectors":
        ((6, 8, 8), lambda px: px.reshape(len(px), -1).astype(np.float64), {},
         np.float32),
    "mixed_dtype_partition": ((6, 8, 8), _mixed_dtype_column, {}, np.float32),
    # 7 rows at batch_size 4: a chunk of 3 padded to its bucket of 4
    "partial_last_batch": ((7, 8, 8), _object_column, {}, np.uint8),
    "auto_convert_off":
        ((6, 8, 8), _object_column, {"auto_convert": False}, np.uint8),
    "resize_branch": ((6, 12, 12), _object_column, {}, np.uint8),
}


@pytest.mark.parametrize("case", sorted(_INPUT_PATH_CASES))
def test_image_featurizer_input_path_matches_float32(case):
    """PR 30: pixels reach the device in the table's dtype.  Whatever the
    column holds, the features are those of the same pixels handed to the
    same program as float32 (what the host used to make, image by image):
    bit-equal, since uint8 -> float32 is exact and every later operation
    is the same."""
    (n, h, w), make_column, params, dtype = _INPUT_PATH_CASES[case]
    px = _pixels(n, h, w)
    feat = _image_featurizer(**params)
    want = feat._build_runner().runner().apply_batch(
        px.astype(np.float32), batch_size=4)
    seen = _spy_on_apply_batch(feat)
    df = DataFrame.from_dict({"image": make_column(px)})
    got = np.stack(list(feat.transform(df).collect()["features"]))
    assert [x.dtype for x in seen] == [dtype]
    assert seen[0].shape == px.shape
    assert got.shape == (n, 6) and np.array_equal(got, want)


def test_image_featurizer_keeps_callers_image_column():
    """The output table carries the caller's own image objects, not
    widened copies, and one scorer (one ModelRunner) serves every dtype."""
    px = _pixels(5, 8, 8)
    col = _object_column(px)
    feat = _image_featurizer()
    scorer = feat._build_runner()
    out = feat.transform(DataFrame.from_dict({"image": col}, 2)).collect()
    assert all(a is b for a, b in zip(out["image"], col))
    feat.transform(DataFrame.from_dict({"image": px.astype(np.float32)}))
    assert feat._build_runner() is scorer
    assert scorer.runner() is feat._build_runner().runner()


def test_image_featurizer_mixed_shapes_fail_in_the_stack():
    rows = [np.zeros((8, 8, 3), np.uint8), np.zeros((8, 1, 3), np.uint8)]
    with pytest.raises(ValueError, match="shape"):
        _image_featurizer().transform(
            DataFrame.from_dict({"image": _object_column(rows)}))


@pytest.mark.parametrize("dtype,bytes_per_row",
                         [(np.uint8, 150_528), (np.float32, 602_112)])
def test_runner_input_bytes_counter_counts_padded_rows(dtype, bytes_per_row):
    """``mmlspark_runner_input_bytes_total`` is the witness of the input
    path: bytes a row as the device receives them, over the PADDED rows."""
    from mmlspark_tpu.observability import get_registry
    feat = _image_featurizer(height=224, width=224)
    px = _pixels(3, 224, 224).astype(dtype)
    feat._build_runner()                  # the runner registers the family
    fam = get_registry().family("mmlspark_runner_input_bytes_total")
    labels = dict(runner="dl.jax_model", front="transform")
    before = fam.value(**labels)
    feat.transform(DataFrame.from_dict({"image": _object_column(px)}))
    # 3 rows at batch_size 4 -> one chunk in its bucket of 4
    assert fam.value(**labels) - before == 4 * bytes_per_row


def test_bilstm_tagger_shapes():
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import BiLSTMTagger

    mod = BiLSTMTagger(vocab_size=100, num_tags=7, embed_dim=8, hidden=16, num_layers=1)
    toks = jnp.array(np.random.default_rng(0).integers(0, 100, (2, 12)), jnp.int32)
    variables = mod.init(jax.random.PRNGKey(0), toks)
    logits = mod.apply(variables, toks)
    assert logits.shape == (2, 12, 7)


def test_minibatch_roundtrip():
    from mmlspark_tpu.stages import FixedMiniBatchTransformer, FlattenBatch
    df = DataFrame.from_dict({"a": np.arange(10), "s": np.array([f"r{i}" for i in range(10)], dtype=object)}, 2)
    batched = FixedMiniBatchTransformer().set("batch_size", 3).transform(df)
    assert batched.count() == 4  # 5+5 rows per part -> 2+2 batches
    flat = FlattenBatch().transform(batched)
    assert flat.count() == 10
    assert np.array_equal(np.sort(np.asarray(flat.collect()["a"], dtype=int)), np.arange(10))


def test_torch_import_matches_torch():
    torch = pytest.importorskip("torch")
    import torch.nn as tnn
    from mmlspark_tpu.dl.torch_import import torch_to_jax, torch_to_jax_model

    torch.manual_seed(0)
    mlp = tnn.Sequential(tnn.Linear(6, 16), tnn.ReLU(), tnn.Linear(16, 3))
    x = np.random.default_rng(0).normal(size=(9, 6)).astype(np.float32)
    ref = mlp(torch.from_numpy(x)).detach().numpy()
    apply_fn, variables = torch_to_jax(mlp)
    got = np.asarray(apply_fn(variables, x))
    assert np.allclose(got, ref, atol=1e-5)

    conv = tnn.Sequential(
        tnn.Conv2d(3, 4, 3, stride=1, padding=1), tnn.BatchNorm2d(4),
        tnn.ReLU(), tnn.MaxPool2d(2), tnn.AdaptiveAvgPool2d(1),
        tnn.Flatten(), tnn.Linear(4, 2)).eval()
    xi = np.random.default_rng(1).normal(size=(2, 3, 8, 8)).astype(np.float32)
    ref2 = conv(torch.from_numpy(xi)).detach().numpy()
    apply2, vars2 = torch_to_jax(conv)
    got2 = np.asarray(apply2(vars2, np.transpose(xi, (0, 2, 3, 1))))  # NHWC in
    assert np.allclose(got2, ref2, atol=1e-4), np.abs(got2 - ref2).max()

    # end-to-end through JaxModel
    jm = torch_to_jax_model(mlp, input_col="f", output_col="o", batch_size=4)
    df = DataFrame.from_dict({"f": np.asarray(x, np.float64)})
    out = jm.transform(df).collect()["o"]
    assert np.allclose(np.stack(list(out)), ref, atol=1e-4)


def test_jax_model_single_row_uses_small_bucket():
    """Round-1 weak item 9: a 1-row request must not pad to batch_size=64 —
    it compiles/uses the 1-row bucket (latency path).  The buckets now live
    in the stage's ModelRunner (ISSUE 9), keyed (kind, devices, bucket,
    feat shape)."""
    import jax.numpy as jnp
    from mmlspark_tpu.dl import JaxModel

    jm = JaxModel()
    jm.set_model(apply_fn=lambda v, x: x * 2.0, variables={})
    jm.set_params(input_col="input", output_col="out", batch_size=64)
    one = np.empty(1, dtype=object)
    one[0] = np.asarray([1.0, 2.0], np.float32)
    out = jm.transform(DataFrame.from_dict({"input": one})).collect()["out"]
    np.testing.assert_allclose(np.asarray(out[0]), [2.0, 4.0])

    def buckets():
        return {k[2] for k in jm.runner()._executables if k[0] == "apply"}

    assert 1 in buckets(), buckets()
    # 3 rows -> bucket 4; full batches still use batch_size
    three = np.empty(3, dtype=object)
    for i in range(3):
        three[i] = np.asarray([float(i), 1.0], np.float32)
    jm.transform(DataFrame.from_dict({"input": three}))
    assert 4 in buckets()
    assert 64 not in buckets()
