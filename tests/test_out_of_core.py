"""Out-of-core chunked training (ISSUE 7): double-buffered host->device
prefetch streaming datasets larger than HBM through the GBDT stack.

Guarantee layers, mirroring test_quantized_parity's structure:

1. **Plumbing** — ChunkedDataset tile geometry/budget resolution, the
   streaming quantile sketch's exact parity with the in-memory edge fit,
   and the TilePrefetcher's wait/compute accounting on FakeClock (wait is
   booked ONLY when compute outruns transfer).
2. **Integer exactness** — per-tile quantized int32 histogram partials
   accumulated across tiles are BIT-FOR-BIT the monolithic build (same
   quantized gradients), single-shard and composed with the packed
   allreduce on mesh8 (``histogram_psum(num_tiles=)``).
3. **End-to-end** — streamed training (both grower families) matches
   in-memory training within the committed quick-parity precisions, and a
   dataset exceeding a configured device-memory budget trains through
   forced small tiles with the transfer/overlap telemetry booked.
4. **Leaf-wise int16 storage** — the narrowed stored-histogram carry is
   lossless: bit-identical boosters with an int16 and an int32 carry.
"""
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.io.chunked import (ChunkedDataset, TilePrefetcher,
                                     pad_tile, resolve_tile_rows)
from mmlspark_tpu.utils.resilience import FakeClock


# --------------------------------------------------------------- plumbing

def test_resolve_tile_rows_budget_and_env(monkeypatch):
    # two tiles must fit the budget (one training, one in flight)
    assert resolve_tile_rows(10_000, bytes_per_row=100,
                             memory_budget_bytes=200_000) == 1000
    # explicit tile_rows wins over nothing, clamps to n
    assert resolve_tile_rows(500, 100, tile_rows=2000) == 500
    # no sizing: one tile (the in-memory degenerate case)
    assert resolve_tile_rows(500, 100) == 500
    # floor: tiny budgets round up to the minimum useful tile — but the
    # floored tiles exceed the caller's budget, so it must say so
    with pytest.warns(RuntimeWarning, match="exceeding the budget"):
        assert resolve_tile_rows(10_000, 100,
                                 memory_budget_bytes=4_000) == 256
    # env override beats everything
    monkeypatch.setenv("MMLSPARK_TPU_TILE_ROWS", "333")
    assert resolve_tile_rows(10_000, 100, tile_rows=50,
                             memory_budget_bytes=1) == 333
    monkeypatch.delenv("MMLSPARK_TPU_TILE_ROWS")
    with pytest.raises(ValueError):
        resolve_tile_rows(10, 100, tile_rows=0)


def test_chunked_dataset_geometry_and_padding():
    X = np.arange(25 * 3, dtype=np.float32).reshape(25, 3)
    y = np.arange(25, dtype=np.float32)
    cd = ChunkedDataset(X, y=y, tile_rows=10)
    assert (cd.num_tiles, cd.tile_rows) == (3, 10)
    assert cd.tile_slice(2) == (20, 25)
    assert cd.tile_valid_rows(2) == 5
    t = cd.tile(2, ("X", "y"))
    assert t["X"].shape == (10, 3) and t["y"].shape == (10,)
    assert np.all(t["X"][:5] == X[20:25]) and np.all(t["X"][5:] == 0)
    # full tiles come back as views (no copy)
    assert cd.tile(0, ("X",))["X"].base is not None
    # fill value is honoured (the -1 node-id pad)
    padded = pad_tile(np.zeros(25, np.int32), 20, 25, 10, fill=-1)
    assert np.all(padded[5:] == -1)
    with pytest.raises(ValueError):
        cd.add_column("bad", np.zeros(7))


def test_streaming_sketch_matches_in_memory_fit():
    from mmlspark_tpu.lightgbm import BinMapper
    rng = np.random.default_rng(0)
    X = rng.normal(size=(5000, 6)).astype(np.float32)
    X[::17, 2] = np.nan                      # NaN lane survives streaming
    chunks = [X[i:i + 700] for i in range(0, 5000, 700)]
    m_stream = BinMapper(63).fit_streaming(iter(chunks))
    m_mono = BinMapper(63).fit(X)
    # the stream fits the reservoir -> identical edges, bit for bit
    assert np.array_equal(m_stream.edges, m_mono.edges, equal_nan=True)
    # and the binned matrices agree everywhere
    assert np.array_equal(m_stream.transform(X), m_mono.transform(X))
    # above the cap: still a valid mapper (every feature gets finite edges)
    m_small = BinMapper(15).fit_streaming(iter(chunks), sample_cnt=900)
    assert np.isfinite(m_small.edges).any(axis=1).all()
    with pytest.raises(ValueError, match="empty"):
        BinMapper(15).fit_streaming(iter([]))


def _fake_prefetcher(n_tiles, load_fn, clock):
    from mmlspark_tpu.observability import MetricsRegistry
    return TilePrefetcher(range(n_tiles), load_fn, clock=clock,
                          registry=MetricsRegistry(), site="test")


def test_prefetch_books_no_wait_when_transfer_hides(rng):
    """Transfer faster than compute: the consumer never blocks -> zero
    wait booked, overlap 100%.  Deterministic on FakeClock: the consumer
    only asks for a tile it can SEE is already loaded."""
    clock = FakeClock()

    def load(i):
        clock.advance(0.2)                   # the transfer cost
        return i

    pf = _fake_prefetcher(3, load, clock)
    it = iter(pf)
    got = []
    for _ in range(3):
        deadline = time.time() + 10
        while pf._q.empty():                 # tile visibly resident first
            assert time.time() < deadline, "prefetch worker stalled"
            time.sleep(0.001)
        got.append(next(it))
        clock.advance(1.0)                   # compute outlasts transfer
    with pytest.raises(StopIteration):
        next(it)
    assert got == [0, 1, 2]
    assert pf.wait_s == 0.0                  # every transfer fully hidden
    assert pf.overlap_stats()["overlap_pct"] == 100.0
    assert pf.tiles_served == 3


def test_prefetch_books_wait_when_compute_outruns_transfer():
    """Compute faster than transfer: every tile take blocks for the
    remaining transfer time, booked as prefetch wait.  The loader gates on
    the prefetcher's ``waiting`` seam so the FakeClock sequencing is
    deterministic: the consumer is provably blocked before the transfer
    'runs', so the booked wait is exactly the transfer time."""
    clock = FakeClock()
    holder = []

    def load(i):
        while not holder:                    # construction race guard
            time.sleep(0.001)
        assert holder[0].waiting.wait(10), "consumer never blocked"
        clock.advance(0.7)                   # transfer the compute can't hide
        return i

    pf = _fake_prefetcher(3, load, clock)
    holder.append(pf)
    for _ in pf:
        clock.advance(0.1)                   # compute far below transfer
    assert pf.wait_s == pytest.approx(3 * 0.7)
    stats = pf.overlap_stats()
    assert stats["overlap_pct"] < 15.0       # mostly stalled, as designed
    assert stats["tiles"] == 3.0


def test_prefetch_propagates_worker_errors_and_is_single_pass():
    def load(i):
        if i == 1:
            raise RuntimeError("tile exploded")
        return i

    pf = _fake_prefetcher(3, load, FakeClock())
    with pytest.raises(RuntimeError, match="tile exploded"):
        list(pf)
    pf2 = _fake_prefetcher(1, lambda i: i, FakeClock())
    assert list(pf2) == [0]
    with pytest.raises(RuntimeError, match="single-pass"):
        list(pf2)


def test_prefetch_early_exit_retires_worker():
    """A consumer that bails mid-stream (break or raise) must not strand
    the worker thread: the terminal _DONE put is not token-guarded, so the
    queue needs slack for it even with the last tile still untaken —
    otherwise the thread leaks and pins a device tile for the process
    lifetime."""
    # break after the FIRST of many tiles (worker mid-pipeline)
    pf = _fake_prefetcher(10, lambda i: i, FakeClock())
    for tile in pf:
        break
    pf._thread.join(timeout=10)
    assert not pf._thread.is_alive(), "worker stranded after consumer break"

    # break with the FINAL tile loaded but never taken: the worker is past
    # the token gate, blocked only on the sentinel put
    pf2 = _fake_prefetcher(2, lambda i: i, FakeClock())
    it = iter(pf2)
    next(it)                                # take tile 0; tile 1 loads
    it.close()                              # consumer gives up
    pf2._thread.join(timeout=10)
    assert not pf2._thread.is_alive(), "worker stranded on terminal put"


# ------------------------------------------------------ integer exactness

@pytest.mark.parametrize("builder,T", [
    ("scatter", 1100),                       # 4000 % 1100 != 0
    ("matmul", 4000), ("matmul", 1000), ("matmul", 1100)])
def test_tile_partial_accumulation_is_bit_exact(builder, T):
    """Sum over per-tile quantized builds == the monolithic quantized build
    (same integer gradients) over one tile, even tiles and an uneven final
    tile — the property the streamed driver's histogram accumulation rests
    on, for the packed scatter and for the chip's int8 matmul build."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    n, f, b, p = 4000, 5, 127, 8
    rng = np.random.default_rng(3)
    binned = jnp.asarray(rng.integers(0, b, (n, f)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.01, 1, n).astype(np.float32))
    node = jnp.asarray(rng.integers(-1, p, n).astype(np.int32))
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=11)
    mono = H.build_histograms_quantized(binned, qg, qh, node, p, b)
    acc = jnp.zeros_like(mono)
    for lo in range(0, n, T):
        hi = min(lo + T, n)
        acc = acc + H.build_quantized(
            binned[lo:hi], qg[lo:hi], qh[lo:hi], node[lo:hi], p, b,
            backend=builder, node_rows_bound=hi - lo)
    assert acc.dtype == jnp.int32
    assert bool(jnp.all(acc == mono))


def test_quantize_with_explicit_scales_matches_and_validates():
    """Handing the quantizer precomputed (global) scales must reproduce the
    internal-scale result exactly — the tile stream's 'identical units'
    contract — and half-passed scales are an error."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops.histogram import quantize_gradients
    rng = np.random.default_rng(5)
    g = jnp.asarray(rng.normal(size=3000).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.01, 1, 3000).astype(np.float32))
    qg0, qh0, gs, hs = quantize_gradients(g, h, 16, seed=2)
    qg1, qh1, gs1, hs1 = quantize_gradients(g, h, 16, seed=2,
                                            g_scale=gs, h_scale=hs)
    assert bool(jnp.all(qg0 == qg1)) and bool(jnp.all(qh0 == qh1))
    assert float(gs1) == float(gs) and float(hs1) == float(hs)
    with pytest.raises(ValueError, match="both"):
        quantize_gradients(g, h, 16, g_scale=1.0)


def test_tile_accumulation_composes_with_packed_psum_on_mesh8(mesh8):
    """The multi-host composition: each shard accumulates TWO per-tile
    int32 partials, then the packed allreduce with the global row bound =
    sum over shards AND tiles must equal the monolithic build — in the
    packed-lane regime and above it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.parallel.collectives import histogram_psum
    from mmlspark_tpu.parallel.mesh import AXIS_DATA

    n, f, b, p = 800, 4, 63, 4
    rng = np.random.default_rng(8)
    binned = jnp.asarray(rng.integers(0, b, (n, f)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.01, 1, n).astype(np.float32))
    node = jnp.asarray(rng.integers(0, p, n).astype(np.int32))
    qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=4)
    ref = H.build_histograms_quantized(binned, qg, qh, node, p, b,
                                       quant_bins=16)

    def tiles_then_psum(row_bound, num_tiles):
        def fn(bq, qgq, qhq, nq):
            half = bq.shape[0] // 2           # two tiles per shard
            acc = H.build_histograms_quantized(
                bq[:half], qgq[:half], qhq[:half], nq[:half], p, b,
                quant_bins=16, node_rows_bound=half)
            acc = acc + H.build_histograms_quantized(
                bq[half:], qgq[half:], qhq[half:], nq[half:], p, b,
                quant_bins=16, node_rows_bound=half)
            return histogram_psum(acc, AXIS_DATA, row_bound=row_bound,
                                  quant_bins=16, num_tiles=num_tiles)
        return jax.jit(jax.shard_map(     # raw-jit: test-local harness
            fn, mesh=mesh8,
            in_specs=(P(AXIS_DATA),) * 4, out_specs=P(), check_vma=False))

    # packed regime: 400 rows/tile globally x 2 tiles x 15 = 12000 < 2^14
    packed = tiles_then_psum(n // 2, 2)(binned, qg, qh, node)
    assert bool(jnp.all(packed == ref))
    # above the packing bound the plain int32 psum path must also be exact
    wide = tiles_then_psum(n * 8, 2)(binned, qg, qh, node)
    assert bool(jnp.all(wide == ref))


# ------------------------------------------------------------- end to end

def _parity_data(seed=7, n=2000):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    y = (X[:, 0] * 2 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
         + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    return X, y


def _acc(res, X, y):
    raw = np.asarray(res.booster.predict(X)).reshape(len(y), -1)[:, 0]
    return float(((raw > 0.5) == (y > 0)).mean())


def test_streamed_classifier_parity_quick():
    from mmlspark_tpu.lightgbm import GBDTParams, train, train_streamed
    X, y = _parity_data()
    pkw = dict(num_iterations=25, max_depth=4, objective="binary", seed=3,
               min_data_in_leaf=5, use_quantized_grad=True)
    r_mem = train(X, y, GBDTParams(**pkw))
    r_str = train_streamed(X, y, GBDTParams(**pkw), tile_rows=450)
    assert r_str.extras["num_tiles"] == 5.0
    assert _acc(r_str, X, y) >= _acc(r_mem, X, y) - 0.02
    # valid + early stopping ride the streamed loop too
    r_es = train_streamed(X[:1500], y[:1500],
                          GBDTParams(**{**pkw, "early_stopping_round": 3,
                                        "num_iterations": 40}),
                          valid=(X[1500:], y[1500:]), tile_rows=400)
    assert r_es.evals and r_es.booster.best_iteration >= 0


def test_streamed_regressor_parity_quick():
    from mmlspark_tpu.lightgbm import GBDTParams, train, train_streamed
    rng = np.random.default_rng(17)
    X = rng.normal(size=(2000, 8)).astype(np.float32)
    y = (3 * X[:, 0] - 2 * X[:, 1] + X[:, 2] ** 2
         + rng.normal(scale=0.3, size=2000)).astype(np.float32)
    pkw = dict(num_iterations=40, max_depth=4, objective="regression",
               seed=3, use_quantized_grad=True)
    mses = {}
    for name, res in (
            ("mem", train(X, y, GBDTParams(**pkw))),
            ("str", train_streamed(X, y, GBDTParams(**pkw), tile_rows=512))):
        pred = np.asarray(res.booster.predict(X)).reshape(len(y), -1)[:, 0]
        mses[name] = float(np.mean((pred - y) ** 2))
    assert mses["str"] <= mses["mem"] * 1.35 + 0.05, mses


def test_streamed_leafwise_parity_quick():
    """The second grower family: streamed best-first growth (stored
    histograms host-side, sibling by exact integer subtraction)."""
    from mmlspark_tpu.lightgbm import GBDTParams, train, train_streamed
    X, y = _parity_data(seed=23)
    pkw = dict(num_iterations=15, num_leaves=15, objective="binary", seed=3,
               min_data_in_leaf=5, use_quantized_grad=True)
    r_mem = train(X, y, GBDTParams(**pkw))
    r_str = train_streamed(X, y, GBDTParams(**pkw), tile_rows=700)
    assert r_str.extras["num_tiles"] == 3.0
    assert _acc(r_str, X, y) >= _acc(r_mem, X, y) - 0.02


@pytest.mark.parametrize("growth", [dict(max_depth=4),
                                    dict(num_leaves=11, min_data_in_leaf=5)],
                         ids=["level", "leaf"])
def test_streamed_training_identical_across_backends(as_platform, growth):
    """The per-tile integer partials are bit-exact in both builders, and
    every split decision downstream is a pure function of the accumulated
    integers — so the streamed driver must produce the IDENTICAL booster
    through the packed scatter and through the chip's int8 matmul build."""
    from mmlspark_tpu.lightgbm import GBDTParams, train_streamed
    rng = np.random.default_rng(17)
    X = rng.normal(size=(3000, 6)).astype(np.float32)
    y = (3 * X[:, 0] - 2 * X[:, 1] + X[:, 2] ** 2
         + rng.normal(scale=0.3, size=3000)).astype(np.float32)
    boosters = {}
    for platform in ("cpu", "tpu"):
        as_platform(platform)
        boosters[platform] = train_streamed(
            X, y, GBDTParams(num_iterations=4, objective="regression",
                             seed=3, use_quantized_grad=True, **growth),
            tile_rows=700).booster
    a, b = boosters["cpu"], boosters["tpu"]
    assert (a.split_feature >= 0).sum() >= 4 * 5
    for key in ("split_feature", "threshold_bin", "left_child",
                "right_child", "leaf_value", "leaf_count"):
        np.testing.assert_array_equal(getattr(a, key), getattr(b, key),
                                      err_msg=key)


def test_dataset_larger_than_device_budget_trains():
    """ISSUE 7 acceptance: a dataset exceeding a configured device-memory
    budget trains through forced small tiles, with the transfer counters
    and the prefetch seam booked on the global registry."""
    from mmlspark_tpu.lightgbm import GBDTParams, train_streamed
    from mmlspark_tpu.observability import get_registry
    rng = np.random.default_rng(9)
    n = 20_000
    X = rng.normal(size=(n, 12)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] > 0).astype(np.float32)
    # bytes/row = 12*4 + 16 = 64; the dataset 'needs' 1.28 MB, the budget
    # holds two 160 KB tiles -> 2500-row tiles, 8 of them
    budget = 2 * 2500 * 64
    res = train_streamed(X, y, GBDTParams(num_iterations=8, max_depth=4,
                                          objective="binary", seed=1),
                         memory_budget_bytes=budget)
    assert res.extras["num_tiles"] == 8.0
    assert res.extras["tile_rows"] == 2500.0
    assert res.extras["prefetch_overlap_pct"] > 0.0
    assert _acc(res, X, y) > 0.8
    reg = get_registry()
    fam = reg.family("mmlspark_device_transfer_bytes_total")
    sites = {k[0]: child.value for k, child in fam._snapshot()}
    assert sites.get("lightgbm.ooc_tile", 0) > n * 12  # binned tiles moved
    for metric in ("mmlspark_prefetch_wait_seconds",
                   "mmlspark_tile_compute_seconds"):
        assert reg.family(metric) is not None, metric


def test_streamed_rejects_unsupported_configs():
    from mmlspark_tpu.lightgbm import GBDTParams, train_streamed
    X = np.zeros((50, 3), np.float32)
    y = np.zeros(50, np.float32)
    with pytest.raises(ValueError, match="multiclass"):
        train_streamed(X, y, GBDTParams(objective="multiclass", num_class=3))
    with pytest.raises(ValueError, match="boosting_type"):
        train_streamed(X, y, GBDTParams(boosting_type="dart"))
    with pytest.raises(ValueError, match="categorical"):
        train_streamed(X, y, GBDTParams(categorical_features=(0,)))
    with pytest.raises(ValueError, match="tile sizing"):
        train_streamed(ChunkedDataset(X, y=y, tile_rows=10),
                       params=GBDTParams(), tile_rows=5)
    with pytest.raises(ValueError, match="labels"):
        train_streamed(ChunkedDataset(X), params=GBDTParams())
    # a dataset 'w' column + explicit sample_weight is the same ambiguity
    # as the tile-sizing args: raise, never silently prefer one
    with pytest.raises(ValueError, match="sample weights"):
        train_streamed(ChunkedDataset(X, y=y,
                                      sample_weight=np.ones(50, np.float32)),
                       params=GBDTParams(),
                       sample_weight=np.ones(50, np.float32))


# -------------------------------------------- leaf-wise int16 stored carry

def test_leafwise_store_dtype_gate():
    import jax.numpy as jnp
    from mmlspark_tpu.lightgbm.core import leafwise_store_dtype
    # 2000 rows x 15 (qh cap at 16 bins) = 30000 < 2^15 -> int16
    assert leafwise_store_dtype(2000, True, 16) == jnp.int16
    # 4-bin gradients stretch the window (cap 3): 10000 x 3 < 2^15
    assert leafwise_store_dtype(10_000, True, 4) == jnp.int16
    assert leafwise_store_dtype(11_000, True, 4) == jnp.int32
    assert leafwise_store_dtype(1_000_000, True, 16) == jnp.int32
    assert leafwise_store_dtype(None, True, 16) == jnp.int32
    assert leafwise_store_dtype(2000, False, 16) == jnp.float32


def test_leafwise_int16_storage_is_lossless(monkeypatch):
    """int16 vs int32 stored carry must be indistinguishable in output —
    the narrowing is storage-only (arithmetic stays int32)."""
    from mmlspark_tpu.lightgbm import GBDTParams, train
    import jax.numpy as jnp
    from mmlspark_tpu.lightgbm import core
    X, y = _parity_data(seed=31, n=1500)   # 1500*15 < 2^15: int16 engages
    assert core.leafwise_store_dtype(len(y), True, 16) == jnp.int16
    boosters, asked = {}, []
    for carry in ("int16", "int32"):
        if carry == "int32":
            monkeypatch.setattr(core, "leafwise_store_dtype",
                                lambda *a: asked.append(a) or jnp.int32)
            core._JIT_CACHE.clear()     # the dtype is no part of the key
        r = train(X, y, GBDTParams(num_iterations=8, num_leaves=15,
                                   objective="binary", seed=3,
                                   min_data_in_leaf=5,
                                   use_quantized_grad=True))
        boosters[carry] = r.booster
    core._JIT_CACHE.clear()
    assert asked == [(len(y), True, 16)]
    a, b = boosters["int16"], boosters["int32"]
    for key in ("split_feature", "threshold_bin", "left_child",
                "right_child", "leaf_value", "leaf_count", "split_gain"):
        assert np.array_equal(getattr(a, key), getattr(b, key)), key
