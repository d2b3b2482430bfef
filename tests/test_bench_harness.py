"""The bench parent's streaming collector is load-bearing for the run's
result line, so its failure modes are CI-covered: partial lines must not
disable the deadline checks, silence must kill, markers must parse from
interleaved/merged output.  The parent itself must stay off JAX (one
process per chip) and exit non-zero when a device phase fails."""
import os
import subprocess
import sys
import time

import bench


def _child(code: str) -> subprocess.Popen:
    return subprocess.Popen([sys.executable, "-u", "-c", code],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)


def test_markers_parse_from_merged_output():
    proc = _child(
        "import sys\n"
        "print('noise line')\n"
        "sys.stderr.write('stderr noise\\n')\n"
        "print('MARK_A 1.5 2.5')\n"
        "print('MARK_B {\"nproc\": 1}')\n")
    got = bench._collect_multi(proc, ("MARK_A", "MARK_B"), idle=10, hard=20)
    assert got["MARK_A"] == [1.5, 2.5]
    assert got["MARK_B"] == '{"nproc": 1}'


def test_partial_line_does_not_disable_deadlines():
    # child writes a marker, then a PARTIAL line (no newline) and hangs:
    # a buffered readline() would block forever; the raw-fd reader must
    # still enforce the idle deadline and salvage the completed marker
    proc = _child(
        "import sys, time\n"
        "print('MARK_A 7.0')\n"
        "sys.stdout.write('partial-with-no-newline')\n"
        "sys.stdout.flush()\n"
        "time.sleep(600)\n")
    t0 = time.perf_counter()
    got = bench._collect_multi(proc, ("MARK_A",), idle=12, hard=60)
    took = time.perf_counter() - t0
    assert got.get("MARK_A") == [7.0]
    assert took < 50, f"idle kill did not fire ({took:.0f}s)"
    assert proc.poll() is not None


def test_silent_child_killed_at_idle_window():
    proc = _child("import time; time.sleep(600)")
    t0 = time.perf_counter()
    got = bench._collect_multi(proc, ("NOPE",), idle=12, hard=60)
    assert got == {}
    assert time.perf_counter() - t0 < 50
    assert proc.poll() is not None


def test_trailing_line_without_newline_is_still_parsed():
    proc = _child(
        "import sys\n"
        "sys.stdout.write('MARK_A 3.25')\n"  # no trailing newline, then exit
        "sys.stdout.flush()\n")
    got = bench._collect_multi(proc, ("MARK_A",), idle=10, hard=20)
    assert got.get("MARK_A") == [3.25]


def test_hist_ab_markers_fold_into_extras():
    proc = _child(
        "print('HIST_AB_RATES 1000.0 2500.0 2.5')\n"
        "print('HIST_AB_MODE tpu_matmul 1000000 200')\n")
    got = bench._collect_multi(proc, ("HIST_AB_RATES", "HIST_AB_MODE"),
                               idle=10, hard=20)
    bench.RESULT["extras"].clear()
    try:
        assert bench._record_hist_ab(got)
        ex = bench.RESULT["extras"]
        assert ex["hist_ab_packed_speedup"] == 2.5
        assert ex["hist_ab_f32_rows_per_sec"] == 1000.0
        assert ex["hist_ab_mode"] == "tpu_matmul"
        assert ex["hist_ab_shape"] == "1000000x200"
        assert not bench._record_hist_ab({})   # absent markers -> False
    finally:
        bench.RESULT["extras"].clear()


def test_ooc_ckpt_marker_folds_into_extras():
    """ISSUE 10: the checkpoint-overhead arm rides the ooc child — its
    OOC_CKPT marker must fold into extras (and stay optional, so an older
    child without the arm still folds its OOC_AB)."""
    proc = _child(
        "print('OOC_AB 1000.0 1200.0 1.2 99.5 4')\n"
        "print('OOC_CKPT 1160.0 3.33 2')\n")
    got = bench._collect_multi(proc, ("OOC_AB", "OOC_CKPT"), idle=10, hard=20)
    bench.RESULT["extras"].clear()
    try:
        assert bench._record_ooc(got)
        ex = bench.RESULT["extras"]
        assert ex["ooc_streamed_rows_per_sec"] == 1200.0
        assert ex["ooc_ckpt_streamed_rows_per_sec"] == 1160.0
        assert ex["ckpt_overhead_pct"] == 3.33
        assert ex["ooc_ckpt_every"] == 2
    finally:
        bench.RESULT["extras"].clear()
    # OOC_CKPT is optional: a child without the arm still folds OOC_AB
    bench.RESULT["extras"].clear()
    try:
        assert bench._record_ooc({"OOC_AB": [1000.0, 1200.0, 1.2, 99.5, 4]})
        assert "ckpt_overhead_pct" not in bench.RESULT["extras"]
    finally:
        bench.RESULT["extras"].clear()


def test_runner_markers_fold_into_extras():
    """ISSUE 9: the runner A/B + decode markers must fold (and note a
    below-gate overhead ratio); the decode arm is additive."""
    proc = _child(
        "print('RUNNER_AB 1000.0 980.0 0.98')\n"
        "print('RUNNER_DECODE 512.5 8 32')\n")
    got = bench._collect_multi(proc, ("RUNNER_AB", "RUNNER_DECODE"),
                               idle=10, hard=20)
    bench.RESULT["extras"].clear()
    try:
        assert bench._record_runner(got)
        ex = bench.RESULT["extras"]
        assert ex["runner_ab_legacy_rows_per_sec"] == 1000.0
        assert ex["runner_ab_runner_rows_per_sec"] == 980.0
        assert ex["runner_vs_legacy"] == 0.98
        assert ex["runner_decode_tokens_per_sec"] == 512.5
        assert ex["runner_decode_shape"] == "b8xt32"
        assert "runner" not in ex.get("phase_notes", {})
        assert not bench._record_runner({})    # absent markers -> False
    finally:
        bench.RESULT["extras"].clear()


def test_runner_paged_marker_folds_with_gate():
    """ISSUE 12: the paged-vs-dense decode A/B folds its tokens/sec pair,
    occupancy, and HBM-per-seq extras; the on-chip 1.2x gate notes a
    miss."""
    proc = _child(
        "print('RUNNER_PAGED 500.0 650.0 1.3 62.5 8192.0')\n")
    got = bench._collect_multi(proc, ("RUNNER_PAGED",), idle=10, hard=20)
    bench.RESULT["extras"].clear()
    try:
        assert bench._record_runner(got)
        ex = bench.RESULT["extras"]
        assert ex["decode_dense_tokens_per_sec"] == 500.0
        assert ex["decode_paged_tokens_per_sec"] == 650.0
        assert ex["decode_paged_vs_dense"] == 1.3
        assert ex["decode_page_occupancy_pct"] == 62.5
        assert ex["decode_hbm_bytes_per_seq"] == 8192.0
        assert "runner" not in ex.get("phase_notes", {})
    finally:
        bench.RESULT["extras"].clear()
    # below the on-chip gate -> attributable note
    bench.RESULT["extras"].clear()
    try:
        assert bench._record_runner(
            {"RUNNER_PAGED": [500.0, 550.0, 1.1, 60.0, 8192.0]})
        note = bench.RESULT["extras"]["phase_notes"]["runner"]
        assert "1.2x" in note
    finally:
        bench.RESULT["extras"].clear()


def test_runner_cont_marker_folds_with_gate_parity_and_compile_checks():
    """ISSUE 13: the continuous-vs-ticked A/B folds its tokens/sec pair +
    ratio, the parity and join-compile counter checks note failures
    attributably, and the on-chip 1.5x gate notes a miss.  The marker is
    additive — an older child without it still folds the other runner
    markers."""
    proc = _child(
        "print('RUNNER_CONT 82.0 140.0 1.707 1 0')\n")
    got = bench._collect_multi(proc, ("RUNNER_CONT",), idle=10, hard=20)
    bench.RESULT["extras"].clear()
    try:
        assert bench._record_runner(got)
        ex = bench.RESULT["extras"]
        assert ex["decode_ticked_tokens_per_sec"] == 82.0
        assert ex["decode_cont_tokens_per_sec"] == 140.0
        assert ex["decode_cont_vs_ticked"] == 1.707
        assert ex["decode_cont_parity"] == "ok"
        assert ex["decode_cont_join_step_compiles"] == 0
        assert "runner" not in ex.get("phase_notes", {})
    finally:
        bench.RESULT["extras"].clear()
    # below the on-chip gate -> attributable note
    try:
        assert bench._record_runner(
            {"RUNNER_CONT": [100.0, 120.0, 1.2, 1, 0]})
        assert "1.5x" in bench.RESULT["extras"]["phase_notes"]["runner"]
    finally:
        bench.RESULT["extras"].clear()
    # parity mismatch leaves its note (and the extra says MISMATCH)
    try:
        assert bench._record_runner(
            {"RUNNER_CONT": [100.0, 180.0, 1.8, 0, 0]})
        ex = bench.RESULT["extras"]
        assert ex["decode_cont_parity"] == "MISMATCH"
        assert "DIVERGED" in ex["phase_notes"]["runner"]
    finally:
        bench.RESULT["extras"].clear()
    # a join-minted step compile leaves its note
    try:
        assert bench._record_runner(
            {"RUNNER_CONT": [100.0, 180.0, 1.8, 1, 2]})
        ex = bench.RESULT["extras"]
        assert ex["decode_cont_join_step_compiles"] == 2
        assert "compile" in ex["phase_notes"]["runner"]
    finally:
        bench.RESULT["extras"].clear()
    # marker-optional back-compat: RUNNER_AB alone still folds
    try:
        assert bench._record_runner({"RUNNER_AB": [1000.0, 980.0, 0.98]})
        assert "decode_cont_vs_ticked" not in bench.RESULT["extras"]
    finally:
        bench.RESULT["extras"].clear()


def test_serving_profiler_marker_folds_with_gate():
    """ISSUE 15: the echo-serving profiler overhead A/B rides the serving
    child — its SERVING_PROFILER marker must fold into extras, a >3%
    overhead must leave a phase note (the gate), and a within-gate run
    must not."""
    proc = _child("print('SERVING_PROFILER 1.441 1.462 1.5')\n")
    got = bench._collect_multi(proc, ("SERVING_PROFILER",), idle=10, hard=20)
    bench.RESULT["extras"].clear()
    try:
        assert bench._record_serving_profiler(got)
        ex = bench.RESULT["extras"]
        assert ex["serving_echo_p50_ms"] == 1.441
        assert ex["serving_echo_profiled_p50_ms"] == 1.462
        assert ex["profiler_overhead_pct"] == 1.5
        assert "serving" not in ex.get("phase_notes", {})
        assert not bench._record_serving_profiler({})  # absent -> False
    finally:
        bench.RESULT["extras"].clear()
    # over-gate run: the number still folds, the note names the miss
    bench.RESULT["extras"].clear()
    try:
        assert bench._record_serving_profiler(
            {"SERVING_PROFILER": [1.441, 1.513, 5.0]})
        ex = bench.RESULT["extras"]
        assert ex["profiler_overhead_pct"] == 5.0
        assert "3% echo-microbench gate" in ex["phase_notes"]["serving"]
    finally:
        bench.RESULT["extras"].clear()


def test_phase_metrics_snapshot_folds_into_extras():
    """ISSUE 11: each phase child prints a bounded PHASE_METRICS registry
    snapshot; the parent folds it under extras.phase_metrics so bench
    regressions diagnose from counters instead of reruns.  Garbled or
    absent markers fold nothing."""
    proc = _child(
        "print('GBDT_RPS 123.0')\n"
        "print('PHASE_METRICS {\"mmlspark_x_total\": {\"type\": "
        "\"counter\", \"samples\": [{\"labels\": {}, \"value\": 7}]}}')\n")
    got = bench._collect_multi(proc, ("GBDT_RPS", "PHASE_METRICS"),
                               idle=10, hard=20)
    bench.RESULT["extras"].clear()
    try:
        assert bench._record_phase_metrics("gbdt", got)
        snap = bench.RESULT["extras"]["phase_metrics"]["gbdt"]
        assert snap["mmlspark_x_total"]["samples"][0]["value"] == 7
        assert not bench._record_phase_metrics("ooc", {})          # absent
        assert not bench._record_phase_metrics(
            "ooc", {"PHASE_METRICS": "not json"})                  # garbled
        assert not bench._record_phase_metrics(
            "ooc", {"PHASE_METRICS": [1.0]})            # parsed as floats
        assert list(bench.RESULT["extras"]["phase_metrics"]) == ["gbdt"]
    finally:
        bench.RESULT["extras"].clear()


def test_phase_metrics_snapshot_is_bounded_and_names_dropped_families():
    """The snapshot must stay a single bounded line: oversized registries
    drop their largest families and NAME them — truncation is
    attributable, never silent — and exemplars (trace ids) are stripped."""
    import json

    from mmlspark_tpu.observability import MetricsRegistry, set_registry

    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        big = reg.counter("mmlspark_bulk_total", "bulk", labels=("k",))
        for i in range(200):
            big.inc(k=f"series-{i}")
        reg.counter("mmlspark_tiny_total", "tiny").inc(3)
        h = reg.histogram("mmlspark_lat_seconds", "lat")
        h.observe(0.01, trace_id="deadbeef")  # exemplar must not leak
        out = bench._metrics_snapshot_json(max_bytes=2048)
        assert len(out) <= 2048
        snap = json.loads(out)
        assert "mmlspark_bulk_total" in snap["_dropped_families"]
        assert snap["mmlspark_tiny_total"]["samples"][0]["value"] == 3
        assert "deadbeef" not in out and "exemplars" not in out
        # comfortably-sized registries pass through whole
        small = json.loads(bench._metrics_snapshot_json(max_bytes=1 << 20))
        assert "_dropped_families" not in small
        assert "mmlspark_bulk_total" in small
    finally:
        set_registry(prev)


def test_phase_children_emit_the_metrics_marker():
    """The dispatcher (not each phase body) prints PHASE_METRICS after
    every phase, so a new phase cannot forget the snapshot."""
    import inspect

    src = open(bench.__file__).read()
    assert "_emit_phase_metrics()" in src
    # and the parent folds it for every measured phase
    fold_src = inspect.getsource(bench.main)
    for phase in ("gbdt", "ooc", "hist_ab", "runner", "serving", "cpu"):
        assert f'_record_phase_metrics("{phase}"' in fold_src, \
            f"phase {phase} snapshot is no longer folded"
    assert 'phase="ranker"' in fold_src and 'phase="resnet"' in fold_src


def test_runner_below_gate_ratio_leaves_a_note():
    bench.RESULT["extras"].clear()
    try:
        assert bench._record_runner({"RUNNER_AB": [1000.0, 500.0, 0.5]})
        assert bench.RESULT["extras"]["runner_vs_legacy"] == 0.5
        assert "0.9x" in bench.RESULT["extras"]["phase_notes"]["runner"]
    finally:
        bench.RESULT["extras"].clear()


def test_runner_prefix_marker_folds_with_gate_parity_and_compile_checks():
    """ISSUE 20: the prefix-cache cached-vs-cold TTFT A/B folds its p99
    pair + ratio + hit rate, the parity and compile counter checks note
    failures attributably, a zero hit rate notes the broken trace, and the
    on-chip 1.3x gate notes a miss.  The marker is additive — an older
    child without it still folds the other runner markers."""
    proc = _child(
        "print('RUNNER_PREFIX 20.0 12.0 1.667 75.0 1 0')\n")
    got = bench._collect_multi(proc, ("RUNNER_PREFIX",), idle=10, hard=20)
    bench.RESULT["extras"].clear()
    try:
        assert bench._record_runner(got)
        ex = bench.RESULT["extras"]
        assert ex["decode_prefix_cold_ttft_p99_ms"] == 20.0
        assert ex["decode_prefix_ttft_p99_ms"] == 12.0
        assert ex["decode_prefix_vs_nocache"] == 1.667
        assert ex["decode_prefix_hit_rate_pct"] == 75.0
        assert ex["decode_prefix_parity"] == "ok"
        assert ex["decode_prefix_hit_compiles"] == 0
        assert "runner" not in ex.get("phase_notes", {})
    finally:
        bench.RESULT["extras"].clear()
    # below the on-chip gate -> attributable note
    try:
        assert bench._record_runner(
            {"RUNNER_PREFIX": [20.0, 18.0, 1.111, 75.0, 1, 0]})
        assert "1.3x" in bench.RESULT["extras"]["phase_notes"]["runner"]
    finally:
        bench.RESULT["extras"].clear()
    # parity mismatch leaves its note (and the extra says MISMATCH)
    try:
        assert bench._record_runner(
            {"RUNNER_PREFIX": [20.0, 12.0, 1.667, 75.0, 0, 0]})
        ex = bench.RESULT["extras"]
        assert ex["decode_prefix_parity"] == "MISMATCH"
        assert "DIVERGED" in ex["phase_notes"]["runner"]
    finally:
        bench.RESULT["extras"].clear()
    # a hit-minted compile leaves its note
    try:
        assert bench._record_runner(
            {"RUNNER_PREFIX": [20.0, 12.0, 1.667, 75.0, 1, 3]})
        ex = bench.RESULT["extras"]
        assert ex["decode_prefix_hit_compiles"] == 3
        assert "compile" in ex["phase_notes"]["runner"]
    finally:
        bench.RESULT["extras"].clear()
    # a zero hit rate means the template-sharing trace never hit
    try:
        assert bench._record_runner(
            {"RUNNER_PREFIX": [20.0, 14.0, 1.43, 0.0, 1, 0]})
        assert "ZERO hit rate" in bench.RESULT["extras"]["phase_notes"]["runner"]
    finally:
        bench.RESULT["extras"].clear()
    # marker-optional back-compat: RUNNER_AB alone still folds
    try:
        assert bench._record_runner({"RUNNER_AB": [1000.0, 980.0, 0.98]})
        assert "decode_prefix_vs_nocache" not in bench.RESULT["extras"]
    finally:
        bench.RESULT["extras"].clear()


def test_parent_stays_off_jax():
    """A parent that has touched JAX holds the chip and starves every
    device child: importing bench (what the parent does) must not import
    jax."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, bench; assert 'jax' not in sys.modules, 'jax imported'"],
        cwd=os.path.dirname(bench.__file__), capture_output=True, text=True,
        timeout=60)
    assert proc.returncode == 0, proc.stderr[-500:]


def test_device_phase_fails_without_a_tpu():
    """No CPU fallback under a device metric's name: a device phase on a
    machine without the chip exits non-zero and prints no marker."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, bench.__file__, "--phase", "hist_ab"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "HIST_AB_RATES" not in proc.stdout


def test_main_exits_nonzero_when_a_device_phase_fails(monkeypatch):
    """The run's exit code carries device-phase failure; host cells alone
    (CPU baseline, serving) cannot turn it into a pass."""
    def fake_spawn(phase, env, extra_args=()):
        host = {"cpu": "print('CPU_RPS 1000.0')",
                "serving": "print('SERVING_P50_MS 1.0 2.0')"}
        return _child(host.get(phase, "raise SystemExit(1)"))

    monkeypatch.setattr(bench, "_spawn", fake_spawn)
    bench.RESULT["extras"].clear()
    try:
        assert bench.main() == 1
        failed = bench.RESULT["extras"]["failed_device_phases"]
        assert set(failed) == {"gbdt", "ooc", "hist_ab", "ranker", "resnet",
                               "runner"}
        assert bench.RESULT["value"] is None
    finally:
        bench.RESULT["extras"].clear()
