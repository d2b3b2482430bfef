"""The benchmark's metric arithmetic, data generation and shape functions."""
import json
import math

import numpy as np
import pytest

from benchmark import datagen, measure, shapes
from benchmark.peaks import peaks_for


# ----------------------------------------------------------------- rates

def test_rate_counts_whole_operations_over_the_time_the_last_returned():
    # 3 fits of 1M rows x 16 iterations, the last returning at 9.6 s
    assert measure.rate(3 * 1_000_000 * 16, 9.6) == pytest.approx(5_000_000)
    with pytest.raises(ValueError):
        measure.rate(1.0, 0.0)


@pytest.mark.parametrize("elapsed,last,done,want", [
    (0.0, 0.0, 0, True),        # the first always runs
    (9.0, 9.0, 1, True),        # at least two always run
    (18.0, 9.0, 2, False),      # a third would end past the 20 s window
    (10.0, 5.0, 2, True),       # fits: 10 + 5 <= 20
    (15.1, 5.0, 3, False),
])
def test_start_rule(elapsed, last, done, want):
    assert measure.may_start(elapsed, last, 20.0, done, at_least=2) is want


@pytest.mark.parametrize("builds,misses,per_op,ops,unexpected", [
    (0, 0, 0, 3, 0),       # a warmed program builds nothing
    (1, 0, 0, 12, 1),      # a shape the warm-up missed, loaded from the cache
    (1, 1, 0, 12, 1),      # the same on a cold cache
    (4, 0, 2, 2, 0),       # the sharded trainer's objective, twice a fit
    (5, 0, 2, 2, 1),       # one more than the configuration covers
    (4, 1, 2, 2, 1),       # nothing covers a compilation
])
def test_builds_inside_the_window_beyond_the_configurations_are_unexpected(
        builds, misses, per_op, ops, unexpected):
    assert measure.unexpected_builds(builds, misses, per_op, ops) == unexpected


# ------------------------------------------------------------ percentiles

@pytest.mark.parametrize("q", [0, 25, 50, 95, 99, 100])
def test_percentile_is_numpys_linear_rule(q):
    xs = np.random.default_rng(q).exponential(10.0, 257)
    assert measure.percentile(list(xs), q) == pytest.approx(
        float(np.percentile(xs, q)))


def test_a_stall_spoils_its_slice_and_not_the_median_of_the_slices():
    """Nine slices of 5 s, 100 requests each at 10..19.9 ms; a stall puts
    every request of one slice at 200 ms."""
    at = [k * 5.0 + i * 0.05 for k in range(9) for i in range(100)]
    calm = [10.0 + (i % 100) * 0.1 for i in range(900)]
    stalled = [200.0 if 15.0 <= t < 20.0 else v for t, v in zip(at, calm)]
    for values in (calm, stalled):
        p95, slices = measure.sliced_percentile(at, values, 95, 5.0, 45.0)
        assert len(slices) == 9 and p95 == pytest.approx(19.405)
    assert slices[3] == 200.0
    # one percentile over the whole window moves with the stall
    assert measure.percentile(stalled, 95) == 200.0
    assert measure.sliced_percentile(at, stalled, 95, 0.0, 45.0) == \
        (200.0, [200.0])


@pytest.mark.parametrize("seconds,slice_s,sizes", [
    (45.0, 5.0, [5] * 9),            # whole slices
    (51.0, 5.0, [5] * 9 + [6]),      # the last takes what is left over
    (3.0, 5.0, [3]),                 # a window shorter than a slice is one
    (10.0, 0.0, [10]),               # no slicing
])
def test_slices_cover_the_window_once(seconds, slice_s, sizes):
    at = [i + 0.5 for i in range(int(seconds))]          # one a second
    values = list(range(len(at)))
    got, slices = measure.sliced_percentile(at, values, 100, slice_s, seconds)
    ends, edge = [], 0
    for n in sizes:
        edge += n
        ends.append(edge - 1)
    assert slices == ends and got == measure.percentile(ends, 50)
    # a slice no request fell due in is left out
    assert measure.sliced_percentile([0.5, 12.0], [1.0, 3.0], 50, 5.0,
                                     15.0) == (2.0, [1.0, 3.0])


def test_bucket_percentile_interpolates_inside_the_bucket():
    buckets = [(1.0, 10), (2.0, 80), (4.0, 10), (math.inf, 0)]
    assert measure.bucket_percentile(buckets, 50) == pytest.approx(1.5)
    assert measure.bucket_percentile(buckets, 5) == pytest.approx(0.5)
    assert measure.bucket_percentile(buckets, 95) == pytest.approx(3.0)
    # the overflow bucket reports its lower edge
    assert measure.bucket_percentile([(1.0, 1), (math.inf, 9)], 99) == 1.0


# ------------------------------------------------- the program's counters

def test_counter_and_histogram_deltas_over_a_window():
    from mmlspark_tpu.observability.metrics import MetricsRegistry
    reg = MetricsRegistry()
    c = reg.counter("mmlspark_t_rows_total", "", labels=("runner", "front"))
    h = reg.histogram("mmlspark_t_phase_seconds", "", labels=("phase",))
    c.labels(runner="a", front="x").inc(5)
    h.labels(phase="queue").observe(0.5)
    before = measure.snapshot_registry(reg)
    c.labels(runner="a", front="x").inc(7)
    c.labels(runner="a", front="y").inc(1)
    c.labels(runner="b", front="x").inc(100)
    for v in (0.001, 0.001, 0.01):
        h.labels(phase="queue").observe(v)
    h.labels(phase="score").observe(3.0)
    after = measure.snapshot_registry(reg)
    assert measure.counter_delta(before, after, "mmlspark_t_rows_total",
                                 runner="a") == 8
    assert measure.counter_delta(before, after, "mmlspark_t_rows_total") == 108
    assert measure.counter_delta(before, after, "no_such_total") is None
    d = measure.histogram_delta(before, after, "mmlspark_t_phase_seconds",
                                phase="queue")
    assert d["count"] == 3 and d["sum"] == pytest.approx(0.012)
    assert sum(n for _, n in d["buckets"]) == 3
    assert measure.bucket_percentile(d["buckets"], 50) < 0.002
    assert measure.histogram_delta(before, before, "mmlspark_t_phase_seconds",
                                   phase="queue") is None


def test_compile_clock_tells_a_compile_from_a_rebuild():
    import jax
    import jax.numpy as jnp
    clock = measure.CompileClock()
    x = jnp.arange(7.0)
    jax.jit(lambda v: v * 3 + 1)(x).block_until_ready()
    assert clock.builds >= 1 and clock.compile_s > 0
    builds = clock.builds
    f = jax.jit(lambda v: v * 5 + 2)
    f(x).block_until_ready()
    first = clock.builds
    f(x).block_until_ready()                  # in memory: nothing is built
    assert first > builds and clock.builds == first
    assert set(clock.snapshot()) == {"compile_s", "builds", "cache_hits",
                                     "cache_misses"}


def test_spans_total_only_what_began_in_the_window():
    spans = measure.Spans()
    spans.records += [("fit", 0.0, 1.0), ("fit", 2.0, 3.5), ("fit", 9.0, 9.5),
                      ("other", 2.0, 9.0)]
    assert spans.total("fit", 1.5, 8.0) == pytest.approx(1.5)
    with spans.span("live"):
        pass
    assert spans.records[-1][0] == "live" and spans.total("live") >= 0


# -------------------------------------------------------------- the inputs

def test_tabular_is_seeded_and_its_blocks_are_distinct_rows():
    X, y, Xh, yh = datagen.tabular(3, 6000, 8, 2000, 500, 0.3, threads=2)
    X2, y2, _, _ = datagen.tabular(3, 6000, 8, 2000, 500, 0.3, threads=2)
    X3, _, _, _ = datagen.tabular(4, 6000, 8, 2000, 500, 0.3, threads=2)
    assert X.shape == (6000, 8) and Xh.shape == (500, 8) and yh.shape == (500,)
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    assert not np.array_equal(X, X3)
    # block k is block 0 with columns rolled by k and rows rotated
    assert sorted(X[2000:4000, 1].tolist()) == sorted(X[:2000, 0].tolist())
    assert len({r.tobytes() for r in X}) == 6000
    # the label is one function of a row's own features
    clean = (X[:, 0] + 0.5 * X[:, 1] > 0)
    assert 0.85 < (clean == (y > 0.5)).mean() < 0.97
    assert abs(X.mean()) < 0.05 and abs(X.std() - 1) < 0.05
    with pytest.raises(ValueError):
        datagen.tabular(0, 5000, 8, 2000, 10, 0.3)


def test_flip_labels_inverts_exactly_that_many_and_differs_per_fit():
    y = (np.arange(1000) % 2).astype(np.float32)
    a, b = datagen.flip_labels(y, 0, 0, 64), datagen.flip_labels(y, 0, 1, 64)
    assert (a != y).sum() == 64 and (b != y).sum() == 64
    assert not np.array_equal(a, b)
    assert np.array_equal(a, datagen.flip_labels(y, 0, 0, 64))
    assert set(np.unique(a)) == {0.0, 1.0}


def test_arrivals_are_a_fixed_count_sorted_inside_the_window():
    due = datagen.poisson_arrivals(5, 80.0, 10.0)
    assert len(due) == 800 and (np.diff(due) >= 0).all()
    assert 0 <= due[0] and due[-1] < 10.0
    assert np.array_equal(due, datagen.poisson_arrivals(5, 80.0, 10.0))
    assert not np.array_equal(due, datagen.poisson_arrivals(6, 80.0, 10.0))
    gaps = np.diff(due)            # exponential gaps: cv about 1
    assert 0.8 < gaps.std() / gaps.mean() < 1.2
    picks = datagen.picks(5, 800, 64)
    assert picks.min() >= 0 and picks.max() < 64 and len(set(picks)) > 32


def test_images_are_uint8_hwc_and_seeded():
    a = datagen.images(1, 3, 16)
    assert a.shape == (3, 16, 16, 3) and a.dtype == np.uint8
    assert np.array_equal(a, datagen.images(1, 3, 16))
    assert a.max() > 200 and a.min() < 50


# ------------------------------------------------------- shapes and peaks

def test_resnet50_needs_what_the_paper_and_torchvision_say():
    assert shapes.resnet_forward_macs(224, num_classes=1000) == pytest.approx(
        4.089e9, rel=1e-3)
    # the features-only pass leaves out the 2048 x 1000 classifier
    assert shapes.resnet_forward_macs(224, num_classes=1000) \
        - shapes.resnet_forward_macs(224) == 2048 * 1000
    need = shapes.resnet_forward_need(256, 224)
    assert need["flops"] == pytest.approx(2 * 256 * 4.087e9, rel=1e-3)
    least, bound = shapes.resnet_forward_least_s(need, peaks_for("TPU v5 lite"))
    assert bound == "bf16 compute"
    assert least == pytest.approx(need["flops"] / 197e12)


def test_gbdt_iteration_need_and_which_bound_binds():
    need = shapes.gbdt_iteration_need(1_000_000, 200, 256, 5)
    assert need["hbm_bytes"] == 5 * 1_000_000 * 200
    assert need["int8_ops"] == 2 * 5 * 1_000_000 * 200 * 256 * 3
    least, bound = shapes.gbdt_iteration_least_s(need, peaks_for("TPU v5 lite"))
    assert bound == "int8 compute" and least == pytest.approx(3.908e-3, rel=1e-3)
    # a chip with a tenth of the bandwidth would be bound by it
    slow = dict(peaks_for("TPU v5 lite"), hbm_bytes_per_s=81.9e9)
    assert shapes.gbdt_iteration_least_s(need, slow)[1] == "HBM bandwidth"


def test_peaks_are_the_published_v5e_numbers_and_no_device_is_assumed():
    p = peaks_for("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["int8_ops_per_s"], p["hbm_bytes_per_s"],
            p["ici_bits_per_s"]) == (197e12, 393e12, 819e9, 1600e9)
    with pytest.raises(KeyError):
        peaks_for("cpu")
    with open(peaks_for.__globals__["_TABLE"]) as f:
        assert "Google Cloud" in json.load(f)["source"]
