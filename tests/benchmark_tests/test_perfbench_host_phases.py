"""``benchmark/host_phases.py``, its nine readers and ``tools/line_check.py``:
the arithmetic on hand-made gaps and phases (no trace file), the readers'
promise of a number for every traced run, the nine entries in the manifest,
and the tiny traced cells that print every one of them."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import host_phases
from benchmark.harness import Run, run_cell
from benchmark.manifest import Manifest
from benchmark.tools import line_check

from perfbench_tiny import REPO, copy_benchmark, tiny_root
from test_perfbench_causal_lm import tiny_lm_root

DECODE_CELLS = ["gpt2xl-generate-backlog", "keye-docqa-backlog",
                "kexaone-reasoning-backlog"]
DECODE_IDLE = ["decode.idle_prepare_ms_per_step",
               "decode.idle_join_ms_per_step",
               "decode.idle_retire_ms_per_step",
               "decode.idle_unphased_ms_per_step"]
BATCH_IDLE = ["runner.idle_stage_ms_per_batch",
              "runner.idle_dispatch_ms_per_batch",
              "runner.idle_drain_ms_per_batch",
              "runner.idle_outside_ms_per_batch"]
NINE = DECODE_IDLE + ["decode.host_work_ms_per_step"] + BATCH_IDLE
MS = 1e6                                      # ns


@pytest.fixture(autouse=True)
def _leave_the_process_as_found():
    import jax
    from mmlspark_tpu.parallel import get_active_mesh, set_active_mesh
    mesh = get_active_mesh()
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    set_active_mesh(mesh)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


def _read(name, run):
    return Manifest(REPO).module("layer_metrics", name).read(run)


# ------------------------------------------------------------- the arithmetic

def test_a_gap_across_three_phases_is_split_by_overlap():
    phases = host_phases.in_order([
        ("prepare", 2 * MS, 3 * MS), ("book", 0, 2 * MS),
        ("fetch", 6 * MS, 20 * MS), ("dispatch", 3 * MS, 6 * MS)])
    # a 5 ms gap: 1 ms of prepare, 3 of dispatch, 1 of fetch.  The middle of
    # it lies in dispatch, which the harness's rule would book all 5 ms to
    under, longest = host_phases.split([(2 * MS, 7 * MS)], phases)
    assert under == {"prepare": 1 * MS, "dispatch": 3 * MS, "fetch": 1 * MS,
                     host_phases.NO_PHASE: 0.0}
    assert longest == (5 * MS, "dispatch")
    # what no phase covers goes to (no phase); several gaps add up
    under, longest = host_phases.split(
        [(-4 * MS, 1 * MS), (19 * MS, 30 * MS)], phases)
    assert under == {"book": 1 * MS, "fetch": 1 * MS,
                     host_phases.NO_PHASE: 14 * MS}
    assert longest == (11 * MS, host_phases.NO_PHASE)
    assert sum(under.values()) == 16 * MS
    assert host_phases.split([], phases) == ({host_phases.NO_PHASE: 0.0},
                                             (0.0, host_phases.NO_PHASE))


def test_phases_come_in_order_and_two_that_overlap_are_refused():
    assert host_phases.in_order([("b", 6, 9), ("a", 0, 6), ("c", 9, 9)]) == [
        ("a", 0, 6), ("b", 6, 9), ("c", 9, 9)]
    with pytest.raises(ValueError, match="more than one thread"):
        host_phases.in_order([("b", 5, 9), ("a", 0, 6)])
    assert host_phases.clipped_seconds(
        [("a", 0, 4e9), ("b", 4e9, 6e9), ("a", 6e9, 9e9)], 1e9, 7e9) == {
            "a": 4.0, "b": 2.0}


def test_the_clock_check_is_signed_and_pairs_a_fetch_with_the_nearest_step():
    steps = [10 * MS, 20 * MS, 30 * MS]
    assert host_phases.clock_lag_ms([10.2 * MS, 20.1 * MS, 30.3 * MS],
                                    steps) == pytest.approx(0.2)
    # the host's clock 1 ms behind the trace's: it reads negative
    assert host_phases.clock_lag_ms([9.2 * MS, 19.1 * MS, 29.3 * MS],
                                    steps) == pytest.approx(-0.8)
    # one fetch that came late to its step lies nearer to the next step's
    # end: the median does not follow it
    assert host_phases.clock_lag_ms(
        [10.2 * MS, 16.0 * MS, 20.1 * MS, 30.3 * MS, 30.3 * MS],
        steps) == pytest.approx(0.2)
    assert host_phases.clock_lag_ms([], steps) is None
    assert host_phases.clock_lag_ms([1.0], []) is None


# ---------------------------------------- the readers on a hand-made traced run

def _traced_run(monkeypatch, gaps, records, steps=10.0, batches=4.0,
                window=(100.0, 101.0), capacity=None, programs=()):
    """A ``Run`` as the readers see it, traced, whose trace holds ``gaps``
    (ms from the window's opening) and the ends of ``programs`` ``(name, ms)``
    and whose registry's ring holds ``records`` ``(loop, name, start ms,
    end ms)``."""
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.observability.tracing import PhaseLog, phase_log
    reg = MetricsRegistry()
    if capacity:
        reg._phase_log = PhaseLog(capacity)
    for loop, name, t0, t1 in records:
        phase_log(reg).record(loop, name, window[0] + t0 / 1e3,
                              window[0] + t1 / 1e3)
    lo = 5e9                                  # the window on the trace's clock
    hi = lo + (window[1] - window[0]) * 1e9
    monkeypatch.setattr(
        host_phases, "_trace_gaps",
        lambda run: (lo, hi, [(lo + a * MS, lo + b * MS) for a, b in gaps],
                     [(n, lo + e * MS) for n, e in programs]))
    counters = {
        "mmlspark_runner_decode_steps_total": {(("runner", "lm"),): steps},
        "mmlspark_runner_batches_total": {
            (("runner", "dl.jax_model"), ("front", "transform")): batches}}
    run = types.SimpleNamespace(
        trace_summary=object(), registry=reg, facts={}, failures=[],
        notes=[], window_start_s=window[0], window_end_s=window[1],
        counters_before={f: {k: 0.0 for k in v} for f, v in counters.items()},
        counters_after=counters, platform="cpu")
    run.counter = types.MethodType(Run.counter, run)
    run.note = run.notes.append
    run.fail = run.failures.append
    return run


ROUND = [("decode", "prepare", 0, 1), ("decode", "dispatch", 1, 4),
         ("decode", "book", 4, 4.5), ("decode", "fetch", 4.5, 10),
         ("decode", "book", 10, 11), ("decode", "notify", 11, 11.5)]
JOIN = [("decode", "join_prefill", 20, 23), ("decode", "join_fetch", 23, 40),
        ("decode", "join_splice", 40, 41)]


def test_no_gap_under_a_join_reads_zero_and_not_none(monkeypatch):
    """PR 38's refusal: on the chip a join's prefill queues behind the step
    in flight, so the device may be busy right through every join."""
    run = _traced_run(monkeypatch, gaps=[(0.5, 5.0)], records=ROUND + JOIN,
                      programs=[("jit__step(1)", 9.75), ("jit__prefill(2)", 9.9)])
    assert _read("decode.idle_join_ms_per_step", run) == 0.0
    assert _read("decode.idle_prepare_ms_per_step", run) == \
        pytest.approx((0.5 + 3.0) / 10)
    assert _read("decode.idle_retire_ms_per_step", run) == \
        pytest.approx((0.5 + 0.5) / 10)
    assert _read("decode.idle_unphased_ms_per_step", run) == 0.0
    assert not run.failures
    assert any("clock check: a fetch phase of the decode loop ends 0.2500 ms"
               in n for n in run.notes), run.notes
    assert any("longest idle gap 4.500 ms, most of it under dispatch" in n
               for n in run.notes)


def test_no_idle_at_all_reads_four_zeros_and_the_work_stays(monkeypatch):
    run = _traced_run(monkeypatch, gaps=[], records=ROUND + JOIN)
    assert [_read(n, run) for n in DECODE_IDLE] == [0.0] * 4
    # every phase but the two blocked ones: 41 + 11.5 - 20 - 5.5 - 17 = 10 ms
    assert _read("decode.host_work_ms_per_step", run) == pytest.approx(1.0)
    # and a loop that never ran: zeros too, for a traced run with batches
    assert [_read(n, run) for n in BATCH_IDLE] == [0.0] * 4


def test_the_four_parts_add_up_to_the_whole(monkeypatch):
    gaps = [(-0.0, 0.7), (3.0, 12.5), (15.0, 16.0), (22.0, 39.0),
            (900.0, 1000.0)]
    run = _traced_run(monkeypatch, gaps=gaps, records=ROUND + JOIN, steps=7.0)
    parts = [_read(n, run) for n in DECODE_IDLE]
    assert all(isinstance(p, float) and p > 0 for p in parts)
    assert sum(parts) == pytest.approx(sum(b - a for a, b in gaps) / 7.0)
    idle = host_phases.idle_by_phase(run, "decode")
    assert idle["join_fetch"] == pytest.approx(0.016)
    assert idle[host_phases.NO_PHASE] == pytest.approx(0.001 + 0.001 + 0.1)

    batch = [("batch", "stage", 0, 30), ("batch", "dispatch", 30, 32),
             ("batch", "stage", 32, 60), ("batch", "dispatch", 60, 62),
             ("batch", "wait", 62, 400), ("batch", "fetch", 400, 403),
             ("batch", "wait", 403, 600), ("batch", "fetch", 600, 603),
             ("batch", "concat", 603, 610)]
    gaps = [(0.0, 31.5), (599.0, 640.0)]
    run = _traced_run(monkeypatch, gaps=gaps, records=batch, batches=2.0,
                      programs=[("jit_apply(3)", 399.9), ("jit_apply(3)", 599.9)])
    parts = dict(zip(BATCH_IDLE, (_read(n, run) for n in BATCH_IDLE)))
    assert any("clock check: a wait phase of the batch loop ends 0.1000 ms"
               in n for n in run.notes), run.notes
    assert parts == {"runner.idle_stage_ms_per_batch": pytest.approx(15.0),
                     "runner.idle_dispatch_ms_per_batch": pytest.approx(0.75),
                     "runner.idle_drain_ms_per_batch": pytest.approx(5.5),
                     "runner.idle_outside_ms_per_batch": pytest.approx(15.0)}
    assert sum(parts.values()) == pytest.approx((31.5 + 41.0) / 2)
    # the decode loop's readers do not read the batch loop's phases
    assert _read("decode.idle_unphased_ms_per_step", run) == \
        pytest.approx(72.5 / 10)


def test_the_readers_groups_cover_every_lap_of_the_two_loops():
    """A lap the program adds and no reader lists would fall out of the sum."""
    from mmlspark_tpu.models.runner import BATCH_LAPS, DECODE_LAPS
    named = {"decode": set(), "batch": set()}

    class Spy:
        NO_PHASE = host_phases.NO_PHASE

        def idle_ms_per_step(self, run, *names):
            named["decode"].update(names)

        def idle_ms_per_batch(self, run, *names):
            named["batch"].update(names)

    manifest = Manifest(REPO)
    for name in DECODE_IDLE + BATCH_IDLE:
        mod = manifest.module("layer_metrics", name)
        mod.host_phases = Spy()
        mod.read(None)
    assert named["decode"] == set(DECODE_LAPS) | {host_phases.NO_PHASE}
    assert named["batch"] == set(BATCH_LAPS) | {host_phases.NO_PHASE}


def test_a_ring_that_wrapped_inside_the_window_fails_the_run(monkeypatch):
    run = _traced_run(monkeypatch, gaps=[(0.5, 5.0)], records=ROUND + JOIN,
                      capacity=4)
    assert _read("decode.idle_join_ms_per_step", run) is not None
    assert len(run.failures) == 1 and "dropped 5" in run.failures[0]
    # wrapped, but every dropped record is older than the window: sound
    old = [("decode", "book", -50 - i, -49.5 - i) for i in range(6)]
    run = _traced_run(monkeypatch, gaps=[(0.5, 5.0)],
                      records=old[::-1] + ROUND, capacity=8)
    assert _read("decode.idle_prepare_ms_per_step", run) == \
        pytest.approx(0.35)
    assert not run.failures


def test_an_untraced_run_and_a_window_without_steps_read_none(monkeypatch):
    run = _traced_run(monkeypatch, gaps=[(0.5, 5.0)], records=ROUND)
    run.trace_summary = None
    assert [_read(n, run) for n in NINE] == [None] * 9
    run = _traced_run(monkeypatch, gaps=[(0.5, 5.0)], records=ROUND,
                      steps=0.0, batches=0.0)
    assert [_read(n, run) for n in NINE] == [None] * 9


def test_a_program_without_a_phase_ring_reads_none(monkeypatch):
    """The parent commit under this PR's benchmark files: nothing to read,
    nothing raised."""
    from mmlspark_tpu.observability import tracing
    run = _traced_run(monkeypatch, gaps=[(0.5, 5.0)], records=ROUND)
    monkeypatch.delattr(tracing, "phase_log")
    assert [_read(n, run) for n in NINE] == [None] * 9


# ------------------------------------------------- the manifest and the tool

def assert_the_manifest_lists_the_nine(manifest):
    """Each of the nine, found by its name, with its unit, source, layer and
    the end-to-end metric it moves; the decode entries list the three decode
    cells among their cells (a later decode cell may join them), the batch
    entries the bulk cell."""
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    for name in NINE:
        m, decode = by_name[name], name.startswith("decode.")
        assert (m["unit"], m["better"], m["source"]) == (
            "ms", "lower", "program_span")
        cells = set(m["workloads"])
        if decode:
            assert set(DECODE_CELLS) <= cells and "resnet50-bulk" not in cells
        else:
            assert "resnet50-bulk" in cells and not cells & set(DECODE_CELLS)
        assert m["moves"] == ("tokens_per_s" if decode else "images_per_s")
        assert m["layer"] == {"decode.idle_unphased_ms_per_step": "scheduler",
                              "runner.idle_outside_ms_per_batch": "pipeline"
                              }.get(name, "model_runner")
        assert callable(manifest.module("layer_metrics", name).read)
    for cell in DECODE_CELLS + ["resnet50-bulk"]:
        listed = {m["name"] for m in manifest.metrics_for("per_layer", cell)}
        assert listed & set(NINE) == {
            n for n in NINE
            if n.startswith("decode.") == (cell != "resnet50-bulk")}


def test_the_nine_are_held_back_and_fit_the_manifest_by_name():
    """The nine idle-by-phase entries stand in ``BENCHMARK.json``, each found
    by its name, and the manifest holds to the contract."""
    manifest = Manifest(REPO)
    assert manifest.problems() == []
    assert_the_manifest_lists_the_nine(manifest)


def test_line_check_names_what_a_line_lacks(tmp_path):
    root = copy_benchmark(tmp_path)
    manifest = Manifest(root)
    cell = "gpt2xl-generate-backlog"
    traced = {"device": {"window_s": 8.0, "busy_s": 7.0}, "metrics": {
        m["name"]: {"value": 1.0, "unit": m["unit"]}
        for m in manifest.metrics_for("per_layer", cell)}}
    assert line_check.missing(cell, traced, manifest) == []
    del traced["metrics"]["decode.idle_join_ms_per_step"]
    assert line_check.missing(cell, traced, manifest) == [
        "decode.idle_join_ms_per_step"]
    untraced = {"device": {}, "metrics": {"tokens_per_s": {"value": 1.0}}}
    assert line_check.missing(cell, untraced, manifest) == ["setup_s"]
    # the two sums: the parts against the number the line already holds
    assert line_check.sums(traced) == [("decode.idle_*", 3.0, 1.0)]
    bulk = {"device": {"window_s": 8.0, "busy_s": 6.0}, "metrics": dict(
        {n: {"value": 10.0} for n in BATCH_IDLE},
        **{"runner.device_ms_per_batch": {"value": 150.0}})}
    assert line_check.sums(bulk) == [("runner.idle_*", 40.0, 50.0)]
    # as a command: the last line of a file, exit 1 where a name is missing
    out = tmp_path / "run.out"
    out.write_text("an earlier line\n" + json.dumps(traced) + "\n")
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools",
                                      "line_check.py"), cell, str(out)],
        capture_output=True, text=True)
    assert done.returncode == 1
    assert "lacks ['decode.idle_join_ms_per_step']" in done.stdout


# ------------------------------------------------ the two tiny traced cells

def _lacks_only_device_metrics(cell, line, root):
    """``line_check`` on a CPU line: what is missing may only be what reads
    the device's own trace (program times, scopes, peaks)."""
    manifest = Manifest(root)
    lacks = line_check.missing(cell, line, manifest)
    source = {m["name"]: m["source"] for m in manifest.data["per_layer"]}
    assert all(source[n] == "device_trace" for n in lacks), lacks


def test_the_tiny_traced_decode_cell_prints_the_five_and_they_add_up(
        tmp_path):
    cell, seed = "gpt2xl-generate-backlog", 2**31 + 39
    root = tiny_lm_root(tmp_path)
    line = json.loads(json.dumps(run_cell(
        root, cell, seed=seed, seconds=1.0, trace=True, platform="cpu")))
    assert line["correct"] is True, line
    m = {n: v["value"] for n, v in line["metrics"].items()}
    _lacks_only_device_metrics(cell, line, root)
    assert all(isinstance(m[n], float) and m[n] >= 0 for n in NINE[:5])
    assert sum(m[n] for n in DECODE_IDLE) == \
        pytest.approx(m["decode.host_ms_per_step"], rel=0.01)
    assert 0 < m["decode.host_work_ms_per_step"]
    assert line_check.sums(line)[0][0] == "decode.idle_*"


def test_the_tiny_traced_bulk_cell_prints_the_four_and_they_add_up(tmp_path):
    root = tiny_root(tmp_path)
    cell = "resnet50-bulk"
    line = json.loads(json.dumps(run_cell(
        root, cell, seed=39, seconds=1.0, trace=True, platform="cpu")))
    assert line["correct"] is True, line
    m = {n: v["value"] for n, v in line["metrics"].items()}
    _lacks_only_device_metrics(cell, line, root)
    assert all(isinstance(m[n], float) and m[n] >= 0 for n in BATCH_IDLE)
    (what, parts, whole), = line_check.sums(line)
    assert what == "runner.idle_*" and parts == pytest.approx(whole, rel=0.01)
    assert parts == pytest.approx(sum(m[n] for n in BATCH_IDLE))
