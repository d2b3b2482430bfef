"""The lap clock (``observability/tracing.py LapClock`` / ``PhaseLog``) and the
two loops that lap it: ``ContinuousDecoder``'s round under both of its
drivers, and ``ModelRunner.apply_batch``'s chunks."""
import threading

import numpy as np
import pytest

from mmlspark_tpu.models.runner import BATCH_LAPS, DECODE_LAPS, ModelRunner
from mmlspark_tpu.observability import MetricsRegistry
from mmlspark_tpu.observability.profiling import SamplingProfiler
from mmlspark_tpu.observability.tracing import (LapClock, PhaseLog,
                                                ambient_phase, phase_log,
                                                thread_phases)


def _clock(reg, loop="loop", names=("a", "b", "c")):
    hist = reg.histogram("test_lap_seconds", "laps", labels=("phase",))
    children = {n: hist.labels(phase=n) for n in names}
    return LapClock(loop, "the.span", children, reg), children


def _records(reg, loop=None):
    return [r for r in phase_log(reg).snapshot()["records"]
            if loop is None or r[0] == loop]


# ------------------------------------------------------------------ the clock

def test_the_phases_of_one_thread_are_flat_and_contiguous():
    reg = MetricsRegistry()
    clock, _ = _clock(reg)
    t_a = clock.lap("a")
    t_b = clock.lap("b")
    t_c = clock.lap("c")
    clock.lap("a")
    clock.stop()
    recs = _records(reg)
    assert [(r[0], r[1]) for r in recs] == [("loop", n) for n in "abca"]
    assert [r[2] for r in recs[:3]] == [t_a, t_b, t_c]   # lap() returns it
    for before, after in zip(recs, recs[1:]):
        assert before[3] == after[2]        # nothing uncovered, no overlap
    assert all(r[3] >= r[2] for r in recs)
    clock.stop()                            # in no phase: nothing to end
    assert len(_records(reg)) == 4


def test_a_lap_into_the_running_phase_lets_it_run_on():
    reg = MetricsRegistry()
    clock, children = _clock(reg)
    clock.lap("a")
    clock.lap("a")
    clock.lap("b")
    clock.stop()
    assert [r[1] for r in _records(reg)] == ["a", "b"]
    assert children["a"].count == 1


def test_the_side_table_shows_the_lap_and_stop_restores_it():
    reg = MetricsRegistry()
    clock, _ = _clock(reg)
    tid = threading.get_ident()
    assert tid not in thread_phases()
    clock.lap("a")
    assert thread_phases()[tid] == "the.span/a"
    clock.stop()
    assert tid not in thread_phases()
    with ambient_phase("outer"):
        clock.lap("a")
        clock.lap("b")
        assert thread_phases()[tid] == "the.span/b"
        clock.stop()
        assert thread_phases()[tid] == "outer"    # restored, not popped
    assert tid not in thread_phases()


def test_an_ended_phase_is_observed_on_the_child_bound_to_its_name():
    reg = MetricsRegistry()
    clock, children = _clock(reg)
    for name in "abab":
        clock.lap(name)
    clock.stop()
    assert [children[n].count for n in "abc"] == [2, 2, 0]
    for n in "ab":
        spent = sum(r[3] - r[2] for r in _records(reg) if r[1] == n)
        assert children[n].sum == pytest.approx(spent)
    with pytest.raises(KeyError):
        clock.lap("no such phase")


def test_the_ring_is_bounded_and_says_what_it_dropped():
    log = PhaseLog(capacity=4)
    assert log.snapshot() == {"records": [], "capacity": 4, "dropped": 0}
    for i in range(10):
        log.record("loop", f"p{i}", float(i), i + 0.5)
    snap = log.snapshot()
    assert snap["dropped"] == 6 and snap["capacity"] == 4
    assert snap["records"] == [("loop", f"p{i}", float(i), i + 0.5)
                               for i in range(6, 10)]
    reg = MetricsRegistry()
    assert phase_log(reg) is phase_log(reg)
    assert phase_log(reg) is not phase_log(MetricsRegistry())
    assert phase_log(reg).capacity >= 32768


def test_each_thread_has_its_own_phase_on_one_clock():
    reg = MetricsRegistry()
    clock, _ = _clock(reg)
    gate = threading.Barrier(3)
    seen = {}

    def work(name):
        clock.lap(name)
        gate.wait(10)
        seen[name] = thread_phases()[threading.get_ident()]
        gate.wait(10)
        clock.stop()

    threads = [threading.Thread(target=work, args=(n,)) for n in "ab"]
    for t in threads:
        t.start()
    gate.wait(10)
    gate.wait(10)
    for t in threads:
        t.join(10)
    assert seen == {"a": "the.span/a", "b": "the.span/b"}
    assert sorted(r[1] for r in _records(reg)) == ["a", "b"]
    assert not any(p.startswith("the.span") for p in thread_phases().values())


def test_many_threads_on_one_clock_and_ring_lose_no_record():
    """More threads than cores at a 10 us switch interval: every ended phase
    is in the ring or counted as dropped, and observed under its own name."""
    import sys
    reg = MetricsRegistry()
    names = [f"t{i}" for i in range(16)]
    clock, children = _clock(reg, names=names + ["rest"])
    laps = 400
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work(name):
            for _ in range(laps):
                clock.lap(name)
                clock.lap("rest")
            clock.stop()
        threads = [threading.Thread(target=work, args=(n,)) for n in names]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    snap = phase_log(reg).snapshot()
    assert len(snap["records"]) + snap["dropped"] == 2 * laps * len(names)
    assert snap["dropped"] == 0
    assert [children[n].count for n in names] == [laps] * len(names)
    assert children["rest"].count == laps * len(names)
    assert not any(p.startswith("the.span") for p in thread_phases().values())


def test_the_profiler_rolls_a_lap_up_to_its_span_and_keeps_it_on_the_stack():
    import sys
    prof = SamplingProfiler(registry=MetricsRegistry())
    frame = sys._getframe()
    prof.sample_once(frames={1: frame, 2: frame},
                     phases={1: "runner.decode.step/fetch",
                             2: "runner.decode.step"})
    rep = prof.report()
    assert rep["by_span"] == {"runner.decode.step": 2}
    assert sorted(s["span"] for s in rep["stacks"]) == [
        "runner.decode.step", "runner.decode.step/fetch"]


# ----------------------------------------------------------- the decode round

def _engine(name, slots=4):
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import TransformerEncoder
    module = TransformerEncoder(
        vocab_size=48, num_classes=48, embed_dim=32, num_heads=2,
        num_layers=1, mlp_dim=64, max_len=64, causal=True, pool="none")
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    reg = MetricsRegistry()
    runner = ModelRunner(module=module, variables=variables, name=name,
                         registry=reg)
    dec = runner.decode_stream(slots=slots, prompt_bucket=8,
                               max_new_tokens=12, page_size=4)
    dec.warmup()
    return reg, runner, dec


def _phase_count(runner, phase):
    return runner.registry.family("mmlspark_runner_decode_phase_seconds") \
        .labels(runner=runner.name, phase=phase).count


@pytest.mark.parametrize("on_thread", [True, False],
                         ids=["start_thread", "step_by_hand"])
def test_both_drivers_of_the_round_record_the_same_phases(on_thread):
    reg, runner, dec = _engine(f"laps.{int(on_thread)}")
    rng = np.random.default_rng(5)
    handles = [dec.submit(rng.integers(0, 48, 5).astype(np.int32),
                          max_new_tokens=n) for n in (12, 8, 5, 3)]
    if on_thread:
        dec.start()
        assert all(h.done.wait(60) for h in handles)
    else:
        while dec.step():
            pass
    dec.close()
    recs = _records(reg, "decode")
    assert {r[1] for r in recs} == set(DECODE_LAPS)
    assert not _records(reg, "batch")
    count = {p: sum(1 for r in recs if r[1] == p) for p in DECODE_LAPS}
    # one dispatch and one fetch a step, one fetch and one splice a join, a
    # notify for every round that had a leaver; the histogram saw them all
    assert count["dispatch"] == count["fetch"] == dec.steps == 11
    assert count["join_fetch"] == count["join_splice"] == 4
    assert 1 <= count["notify"] <= 4
    for p in DECODE_LAPS:
        assert _phase_count(runner, p) == count[p]
    # one thread wrote them: by start, none begins before the last ended
    recs.sort(key=lambda r: r[2])
    for before, after in zip(recs, recs[1:]):
        assert after[2] >= before[3]
    # and the driver ends in no phase, under no span
    assert not any(p.startswith("runner.decode.step")
                   for p in thread_phases().values())


def _rounds(dec, n=1):
    for _ in range(n):
        with dec._engine_work() as leavers:
            dec._round(leavers)


def test_a_join_behind_a_step_in_flight_retires_it_between_two_prefills():
    reg, runner, dec = _engine("laps.join")
    p = np.asarray([5, 7, 11], np.int32)
    dec.submit(p)
    _rounds(dec)                              # joins; step 1 left in flight
    assert dec._in_flight is not None
    assert [r[1] for r in _records(reg, "decode")] == [
        "join_prefill", "join_fetch", "join_splice", "prepare", "dispatch",
        "book"]
    before = len(_records(reg, "decode"))
    dec.submit(p + 1)
    _rounds(dec)        # the join's prefill queues behind step 1, retires it
    names = [r[1] for r in _records(reg, "decode")][before:]
    assert names == ["join_prefill", "fetch", "book", "join_prefill",
                     "join_fetch", "join_splice", "prepare", "dispatch",
                     "book"]
    recs = _records(reg, "decode")
    for a, b in zip(recs[before:], recs[before + 1:]):
        assert a[3] == b[2]                   # flat through the retirement
    dec.close()


def test_a_round_that_fails_still_ends_in_no_phase():
    reg, runner, dec = _engine("laps.boom")
    dec.submit(np.asarray([5, 7, 11], np.int32))

    def boom(*a):
        raise RuntimeError("step executable poisoned")

    dec._step = boom
    with pytest.raises(RuntimeError, match="poisoned"):
        _rounds(dec)
    assert threading.get_ident() not in thread_phases()
    assert [r[1] for r in _records(reg, "decode")][-2:] == [
        "prepare", "dispatch"]
    dec._abort()
    dec.close()


def test_a_callback_that_ends_the_thread_leaves_no_phase_behind():
    """``_finish`` swallows an ``Exception`` of ``on_done``; what is not one
    (an interpreter's exit) passes through the bracket, which must still end
    the ``notify`` lap and clear the side table."""
    reg, runner, dec = _engine("laps.exit")

    def leave(handle):
        raise SystemExit("the reply's thread was told to go")

    dec.submit(np.asarray([5, 7, 11], np.int32), max_new_tokens=1,
               on_done=leave)
    with pytest.raises(SystemExit):
        _rounds(dec, 3)
    assert threading.get_ident() not in thread_phases()
    assert _records(reg, "decode")[-1][1] == "notify"
    dec._abort()
    dec.close()


def _aligned_like(a):
    """A copy of ``a`` in memory aligned to 64 bytes: what the CPU backend
    aliases and does not copy when ``jnp.asarray`` takes it."""
    buf = np.zeros(a.nbytes + 64, np.uint8)
    off = -buf.ctypes.data % 64
    out = buf[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


def test_the_step_in_flight_owns_its_table_tokens_and_mask():
    """The host edits ``_table`` / ``_tok`` / ``_fin`` while the step it
    dispatched may still be waiting to run (a join's table row, a leave's
    zeroes).  On the CPU ``jnp.asarray`` aliases a small aligned buffer: a
    pad row of the step in flight then wrote position 0 through the row a
    join had just filled, into the first page of a shared prefix (the tiny
    Keye cell served wrong tokens in 4 of 12 runs under load).  What is
    uploaded is a copy."""
    reg, runner, dec = _engine("laps.alias")
    dec._table = _aligned_like(dec._table)
    dec._tok = _aligned_like(dec._tok)
    dec._fin = _aligned_like(dec._fin)
    dec.submit(np.asarray([5, 7, 11], np.int32))
    _rounds(dec)                              # joins; step 1 left in flight
    assert dec._in_flight is not None
    table_before = np.array(dec._table_dev)
    assert not np.shares_memory(np.asarray(dec._table_dev), dec._table)
    dec._table[:] = 0                         # what a leave does to its row
    np.testing.assert_array_equal(np.asarray(dec._table_dev), table_before)
    _rounds(dec, 2)
    assert dec.steps >= 2
    dec._abort()
    dec.close()


# ---------------------------------------------------------------- apply_batch

CHUNK = ["stage", "dispatch"]
RETIRE = ["wait", "fetch"]


@pytest.mark.parametrize("rows,expected", [
    (3, CHUNK + RETIRE + ["concat"]),
    (7, CHUNK * 2 + RETIRE * 2 + ["concat"]),
    (10, CHUNK * 2 + RETIRE + CHUNK + RETIRE * 2 + ["concat"]),
], ids=["one_chunk", "two_chunks", "three_chunks"])
def test_apply_batch_laps_its_chunks_in_order_and_ends_in_no_phase(
        rows, expected):
    reg = MetricsRegistry()
    runner = ModelRunner(apply_fn=lambda v, x: x * 2.0, variables={},
                         name="laps.batch", registry=reg, batch_size=4)
    x = np.arange(rows * 3, dtype=np.float32).reshape(rows, 3)
    with ambient_phase("the.caller"):
        out = runner.apply_batch(x)
        assert thread_phases()[threading.get_ident()] == "the.caller"
    np.testing.assert_array_equal(out, x * 2.0)
    recs = _records(reg)
    assert [r[0] for r in recs] == ["batch"] * len(expected)
    assert [r[1] for r in recs] == expected
    assert set(expected) == set(BATCH_LAPS)
    for a, b in zip(recs, recs[1:]):
        assert a[3] == b[2]
    fam = reg.family("mmlspark_runner_batch_phase_seconds")
    for p in BATCH_LAPS:
        assert fam.labels(runner="laps.batch", front="transform",
                          phase=p).count == expected.count(p)
    assert fam.labels(runner="laps.batch", front="serving",
                      phase="stage").count == 0
    assert runner.apply_batch(x[:0]).shape == (0,)      # no lap at all
    assert len(_records(reg)) == len(expected)
