"""Flight recorder + on-demand profiling plane (ISSUE 15).

The contracts this file pins, host-only (the jax-side acceptance — a
dispatch-heavy decode stream attributing >= half its samples to the
decode-step span over ``GET /debug/profile``, and the mid-stream
preemption dump carrying the live slot table — rides
``test_continuous_batching.py`` where the compiled runner is shared):

- sampler: span attribution through the thread-phase side table, bounded
  folded-stack aggregation (drops counted, never grown), idle-thread
  exclusion by default with ``idle_samples`` accounting;
- one profile window at a time (409 over HTTP), param clamps, and the
  jax-trace hatch degrading to host-only sampling on ANY capture failure;
- recorder: every section individually guarded, counter DELTAS between
  snapshots, atomic keep-last-K dump files, and a dump on each trigger —
  ``sys.excepthook`` / ``threading.excepthook`` (chained, shutdown not
  deadlocked), ``request_preemption``, the SLO burning EDGE (one dump per
  edge, not per evaluate), ``GET /debug/dump``, and the deadline-bounded
  ``GET /fleet/dump`` fan-out serving PARTIAL results past a dead worker.
"""
import json
import os
import sys
import threading
import time
import urllib.request

import pytest

from mmlspark_tpu.core.logging import recent_events
from mmlspark_tpu.observability import MetricsRegistry
from mmlspark_tpu.observability.flightrecorder import (FlightRecorder,
                                                       get_flight_recorder)
from mmlspark_tpu.observability.profiling import (MAX_HZ, UNATTRIBUTED,
                                                  ProfilerBusy,
                                                  SamplingProfiler,
                                                  profile_window)
from mmlspark_tpu.observability.tracing import (ambient_phase, thread_phases,
                                                trace_span)
from tests.serving_helpers import Doubler


def _largest_attributed_span(rep):
    """The span with the most samples in a window's report, leaving out
    what no span claimed: whatever other threads the process runs (a test
    runner's workers among them) are sampled too, and carry no phase."""
    attributed = {span: count for span, count in rep["by_span"].items()
                  if span != UNATTRIBUTED}
    return max(attributed, key=attributed.get) if attributed else None


def _frame_of(fn):
    """A real frame whose fold is distinct per ``fn``."""
    out = {}

    def capture():
        out["f"] = sys._getframe()

    fn(capture)
    return out["f"]


# ---------------------------------------------------------------------------
# sampler: attribution, bounds, idle exclusion
# ---------------------------------------------------------------------------

def test_sampler_attributes_injected_frames_to_phases():
    reg = MetricsRegistry()
    p = SamplingProfiler(hz=50, registry=reg)
    f = sys._getframe()
    own = threading.get_ident()
    assert p.sample_once(frames={own + 1: f, own + 2: f, own: f},
                         phases={own + 1: "phase.a"}) == 2  # own excluded
    rep = p.report()
    assert rep["by_span"] == {"phase.a": 1, "unattributed": 1}
    assert rep["samples"] == 2 and rep["stacks_dropped"] == 0
    # stop() books the per-span counters
    p.stop()
    fam = reg.family("mmlspark_profiler_samples_total")
    assert fam.value(span="phase.a") == 1
    assert fam.value(span="unattributed") == 1


def test_sampler_bounded_aggregation_drops_stacks_not_spans():
    """Past ``max_stacks`` distinct folds the sample still counts toward
    its span — only the per-stack detail is dropped, and the drop is
    booked (never silent)."""
    reg = MetricsRegistry()
    p = SamplingProfiler(hz=50, registry=reg, max_stacks=2)

    def lvl_a(fn):
        fn()

    def lvl_b(fn):
        fn()

    def lvl_c(fn):
        fn()

    own = threading.get_ident()
    for i, mk in enumerate((lvl_a, lvl_b, lvl_c)):
        p.sample_once(frames={own + 1: _frame_of(mk)},
                      phases={own + 1: "spam"})
    rep = p.report()
    assert rep["by_span"] == {"spam": 3}          # every sample attributed
    assert rep["distinct_stacks"] == 2            # the bound held
    assert rep["stacks_dropped"] == 1
    assert reg.family(
        "mmlspark_profiler_stacks_dropped_total").value() == 1


def test_sampler_excludes_idle_threads_by_default():
    """A thread parked in a stdlib wait wrapper is blocked in a C wait
    with the GIL released — by default it lands in ``idle_samples``, not
    the by-span rollup (else parked handler threads dilute every busy
    phase); ``include_idle=True`` restores wall-clock attribution."""
    ev = threading.Event()
    t = threading.Thread(target=ev.wait, daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 5
        frame = None
        while time.monotonic() < deadline:
            frame = sys._current_frames().get(t.ident)
            if frame is not None and \
                    frame.f_code.co_filename.endswith("threading.py"):
                break
            time.sleep(0.01)
        assert frame is not None
        reg = MetricsRegistry()
        p = SamplingProfiler(hz=50, registry=reg)
        p.sample_once(frames={t.ident: frame},
                      phases={t.ident: "waiting.phase"})
        rep = p.report()
        assert rep["idle_samples"] == 1 and rep["by_span"] == {}
        p2 = SamplingProfiler(hz=50, registry=reg, include_idle=True)
        p2.sample_once(frames={t.ident: frame},
                       phases={t.ident: "waiting.phase"})
        rep2 = p2.report()
        assert rep2["by_span"] == {"waiting.phase": 1}
        assert rep2["idle_samples"] == 0
    finally:
        ev.set()
        t.join(timeout=5)


def test_trace_span_and_ambient_phase_maintain_thread_table():
    tid = threading.get_ident()
    assert tid not in thread_phases()
    with trace_span("outer.span", registry=MetricsRegistry()):
        assert thread_phases()[tid] == "outer.span"
        with ambient_phase("inner.phase"):
            assert thread_phases()[tid] == "inner.phase"
        assert thread_phases()[tid] == "outer.span"   # restored, not popped
    assert tid not in thread_phases()


def test_profile_window_attributes_busy_thread_and_rejects_concurrent():
    """The worked contract at module level: a busy thread under an
    ambient phase is the largest attributed span of the window's by-span
    rollup (the window's own sleeping caller is idle-excluded), and a
    second concurrent window is refused (two samplers would double the
    overhead both measure)."""
    reg = MetricsRegistry()
    stop = threading.Event()

    def busy():
        with ambient_phase("busy.phase"):
            x = 0
            while not stop.is_set():
                x += 1

    t = threading.Thread(target=busy, daemon=True)
    t.start()
    try:
        rep = profile_window(seconds=0.3, hz=200, registry=reg)
    finally:
        stop.set()
        t.join(timeout=5)
    assert rep["samples"] > 0
    assert _largest_attributed_span(rep) == "busy.phase"
    assert rep["requested_seconds"] == 0.3
    assert any(e["span"] == "busy.phase" for e in rep["stacks"])
    # concurrency: hold the window lock, the next window must refuse
    from mmlspark_tpu.observability import profiling as prof_mod
    assert prof_mod._WINDOW_LOCK.acquire(blocking=False)
    try:
        with pytest.raises(ProfilerBusy):
            profile_window(seconds=0.05, registry=reg)
    finally:
        prof_mod._WINDOW_LOCK.release()
    assert reg.family("mmlspark_profiler_runs_total").value(
        result="busy") == 1


def test_sampler_clamps_hz_and_window_clamps_seconds():
    assert SamplingProfiler(hz=10 ** 9).hz == MAX_HZ
    with pytest.raises(ValueError):
        SamplingProfiler(hz=0)
    rep = profile_window(seconds=-3, hz=0.25, registry=MetricsRegistry())
    assert rep["requested_seconds"] == 0.01 and rep["hz"] == 1.0


def test_jax_trace_hatch_degrades_to_host_only(monkeypatch, tmp_path):
    """ANY device-capture failure must cost only the capture: the report
    records the error and the host samples still serve."""
    import types

    from mmlspark_tpu.observability.profiling import JAX_TRACE_DIR_ENV

    class _BoomProfiler:
        @staticmethod
        def trace(_dir):
            raise RuntimeError("no profiler on this backend")

    monkeypatch.setitem(sys.modules, "jax",
                        types.SimpleNamespace(profiler=_BoomProfiler))
    monkeypatch.setenv(JAX_TRACE_DIR_ENV, str(tmp_path / "traces"))
    rep = profile_window(seconds=0.05, registry=MetricsRegistry())
    assert rep["jax_trace"]["ok"] is False
    assert "no profiler" in rep["jax_trace"]["error"]
    assert rep["samples"] >= 0 and "by_span" in rep


# ---------------------------------------------------------------------------
# flight recorder: snapshot, dumps, triggers
# ---------------------------------------------------------------------------

def test_recorder_dump_files_are_atomic_parseable_and_pruned(tmp_path):
    reg = MetricsRegistry()
    reg.counter("mmlspark_probe_total", "p").inc(3)
    rec = FlightRecorder(registry=reg, dump_dir=str(tmp_path), keep_last=2)
    try:
        paths = [rec.dump(trigger="demand") for _ in range(3)]
        assert all(p is not None for p in paths)
        names = sorted(os.listdir(tmp_path))
        assert len(names) == 2, "keep-last pruning failed"
        assert not any(".tmp" in n for n in names), "torn temp file leaked"
        data = json.load(open(paths[-1]))
        for section in ("ring_events", "slow_spans", "compile", "metrics",
                        "decode_streams", "runners", "membership"):
            assert section in data, f"dump lost the {section} section"
        assert data["trigger"] == "demand" and data["pid"] == os.getpid()
        fam = reg.family("mmlspark_flightrecorder_dumps_total")
        assert fam.value(trigger="demand", result="ok") == 3
        age = reg.family("mmlspark_flightrecorder_last_dump_age_seconds")
        assert age.value(recorder=rec._label) < 60.0
    finally:
        rec.close()


def test_recorder_metric_section_reports_deltas_and_bounds(tmp_path):
    reg = MetricsRegistry()
    c = reg.counter("mmlspark_probe_total", "p", labels=("k",))
    c.inc(5, k="a")
    rec = FlightRecorder(registry=reg, dump_dir=str(tmp_path),
                         max_metric_entries=1)
    try:
        snap1 = rec.snapshot()
        assert snap1["metrics"]["counter_deltas"][
            'mmlspark_probe_total{k="a"}'] == {"delta": 5.0, "total": 5.0}
        c.inc(2, k="a")
        c.inc(1, k="b")
        snap2 = rec.snapshot()
        deltas = snap2["metrics"]["counter_deltas"]
        # bounded to the single largest mover, the cut is counted
        assert len(deltas) == 1
        assert snap2["metrics"]["truncated"]["counters"] == 1
        assert deltas['mmlspark_probe_total{k="a"}']["delta"] == 2.0
    finally:
        rec.close()


def test_recorder_without_dump_dir_books_no_dir_and_keeps_snapshot():
    reg = MetricsRegistry()
    rec = FlightRecorder(registry=reg)   # no dir param, env unset in tests
    try:
        assert rec.dump_dir is None
        assert rec.dump(trigger="demand") is None
        assert rec.last_snapshot is not None
        assert reg.family("mmlspark_flightrecorder_dumps_total").value(
            trigger="demand", result="no_dir") == 1
    finally:
        rec.close()


def test_recorder_write_failure_books_error_not_raise(tmp_path):
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("file, not dir")
    reg = MetricsRegistry()
    rec = FlightRecorder(registry=reg, dump_dir=str(blocker / "sub"))
    try:
        assert rec.dump(trigger="demand") is None
        assert rec.last_snapshot is not None  # snapshot survived the I/O
        assert reg.family("mmlspark_flightrecorder_dumps_total").value(
            trigger="demand", result="error") == 1
    finally:
        rec.close()


def test_recorder_raising_source_costs_its_row_not_the_dump(tmp_path):
    rec = FlightRecorder(registry=MetricsRegistry(), dump_dir=str(tmp_path))
    try:
        rec.add_source("good", lambda: {"v": 1})
        rec.add_source("bad", lambda: 1 / 0)
        path = rec.dump(trigger="demand")
        data = json.load(open(path))
        assert data["source.good"] == {"v": 1}
        assert "ZeroDivisionError" in data["source.bad"]["error"]
    finally:
        rec.close()


def test_crash_hooks_chain_dump_and_uninstall(tmp_path):
    """A crashing thread produces a dump via ``threading.excepthook``
    WITHOUT deadlocking shutdown, the previous hooks still run (chained,
    never replaced), and uninstall restores exactly what install saved."""
    seen = {"sys": None, "thread": None}
    prev_sys = sys.excepthook
    prev_thread = threading.excepthook
    sys.excepthook = lambda *a: seen.__setitem__("sys", a[0])
    threading.excepthook = lambda args: seen.__setitem__(
        "thread", args.exc_type)
    reg = MetricsRegistry()
    rec = FlightRecorder(registry=reg, dump_dir=str(tmp_path))
    try:
        rec.install()
        rec.install()                     # idempotent

        def boom():
            raise ValueError("scorer thread died")

        t = threading.Thread(target=boom)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive(), "excepthook dump deadlocked the thread"
        assert seen["thread"] is ValueError, "previous hook not chained"
        # the sys hook path, driven directly (a real one ends the process)
        try:
            raise RuntimeError("driver died")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
        assert seen["sys"] is RuntimeError
        dumps = sorted(os.listdir(tmp_path))
        assert len(dumps) == 2 and all("crash" in n for n in dumps)
        assert json.load(open(tmp_path / dumps[0]))["trigger"] == "crash"
        assert reg.family("mmlspark_flightrecorder_dumps_total").value(
            trigger="crash", result="ok") == 2
    finally:
        rec.close()
        assert sys.excepthook is not rec._sys_hook
        assert threading.excepthook is not rec._threading_hook
        sys.excepthook = prev_sys
        threading.excepthook = prev_thread


def test_request_preemption_triggers_dump_with_ring_tail(tmp_path):
    """The membership-shrink path: a programmatic ``request_preemption``
    reaching an active scope dumps the black box BEFORE the final
    checkpoint-and-exit, and the dump's ring tail includes the very
    preemption event it records."""
    from mmlspark_tpu.utils.resilience import (preemption_scope,
                                               request_preemption)

    reg = MetricsRegistry()
    rec = FlightRecorder(registry=reg, dump_dir=str(tmp_path), install=True)
    try:
        with preemption_scope() as token:
            assert request_preemption("shrink-drill") == 1
            assert token.requested
        names = os.listdir(tmp_path)
        assert len(names) == 1 and "preemption" in names[0]
        data = json.load(open(tmp_path / names[0]))
        assert data["trigger"] == "preemption"
        assert any(e.get("event") == "preemption_requested"
                   and e.get("reason") == "shrink-drill"
                   for e in data["ring_events"]), \
            "ring tail lost the preemption event that triggered the dump"
        assert reg.family("mmlspark_flightrecorder_dumps_total").value(
            trigger="preemption", result="ok") == 1
    finally:
        rec.close()
    # closed recorder: a later preemption no longer dumps
    with preemption_scope():
        request_preemption("after-close")
    assert len(os.listdir(tmp_path)) == 1


def test_slo_burn_edge_dumps_once_per_edge(tmp_path):
    """The burning EDGE dumps exactly once — a sustained burn costs one
    artifact, not one per evaluate pass."""
    from mmlspark_tpu.observability import FleetView, SLOEngine
    from mmlspark_tpu.utils.resilience import FakeClock

    def lat_view(values):
        r = MetricsRegistry()
        h = r.histogram("mmlspark_t_lat_seconds", "l",
                        buckets=(0.001, 0.01, 0.1))
        for v in values:
            h.observe(v)
        return FleetView.from_texts({"w0": r.to_prometheus()})

    clk = FakeClock()
    reg = MetricsRegistry()
    rec = FlightRecorder(registry=reg, dump_dir=str(tmp_path))
    reg._flight_recorder = rec
    try:
        eng = SLOEngine(["p99(mmlspark_t_lat_seconds) <= 0.01"],
                        registry=reg, clock=clk,
                        fast_window_s=300.0, slow_window_s=3600.0)
        history = [0.001] * 50
        eng.evaluate(lat_view(history))
        clk.advance(60)
        history += [0.5] * 10
        assert eng.evaluate(lat_view(history))["slos"][0]["burning"]
        assert len(os.listdir(tmp_path)) == 1, "burn edge must dump once"
        clk.advance(30)
        history += [0.5] * 5
        assert eng.evaluate(lat_view(history))["slos"][0]["burning"]
        assert len(os.listdir(tmp_path)) == 1, \
            "sustained burn must not dump per evaluate"
        name = os.listdir(tmp_path)[0]
        assert "slo_burn" in name
        assert json.load(open(tmp_path / name))["trigger"] == "slo_burn"
    finally:
        rec.close()


def test_get_flight_recorder_is_per_registry_singleton():
    reg = MetricsRegistry()
    prev_sys, prev_thread = sys.excepthook, threading.excepthook
    rec = get_flight_recorder(reg)
    try:
        assert get_flight_recorder(reg) is rec
        # bound-method equality (`is` builds a fresh object per access)
        assert sys.excepthook == rec._sys_hook, \
            "first use must install the crash hooks"
    finally:
        rec.close()
        assert sys.excepthook is prev_sys
        assert threading.excepthook is prev_thread
    rec2 = get_flight_recorder(reg)
    try:
        assert rec2 is not rec, "close() must clear the registry slot"
    finally:
        rec2.close()


# ---------------------------------------------------------------------------
# HTTP surfaces: /debug/profile, /debug/dump, /fleet/dump
# ---------------------------------------------------------------------------

def _get(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.load(r)


def test_debug_profile_endpoint_reports_clamps_and_409():
    import jax
    jax.devices()  # else the server's start-time environment pivot runs
    # jax backend init (plugin discovery over importlib.metadata) on ITS
    # daemon thread and that churn dominates the short window as
    # unattributed busy samples

    from mmlspark_tpu.observability import profiling as prof_mod
    from mmlspark_tpu.serving import PipelineServer

    reg = MetricsRegistry()
    srv = PipelineServer(Doubler(), port=0, registry=reg).start()
    stop = threading.Event()

    def busy():
        with ambient_phase("echo.busy"):
            x = 0
            while not stop.is_set():
                x += 1

    t = threading.Thread(target=busy, daemon=True)
    t.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        status, rep = _get(base + "/debug/profile?seconds=0.3&hz=200")
        assert status == 200
        assert _largest_attributed_span(rep) == "echo.busy"
        assert any(e["span"] == "echo.busy" for e in rep["stacks"])
        # bad params reply 400, a held window replies 409
        req = urllib.request.Request(base + "/debug/profile?seconds=abc")
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=10)
        assert err.value.code == 400
        assert prof_mod._WINDOW_LOCK.acquire(blocking=False)
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(
                    base + "/debug/profile?seconds=0.05", timeout=10)
            assert err.value.code == 409
        finally:
            prof_mod._WINDOW_LOCK.release()
    finally:
        stop.set()
        t.join(timeout=5)
        srv.stop()
        reg._flight_recorder.close()


def test_debug_dump_endpoint_serves_snapshot_and_writes_file(
        monkeypatch, tmp_path):
    from mmlspark_tpu.observability.flightrecorder import DUMP_DIR_ENV
    from mmlspark_tpu.serving import PipelineServer

    monkeypatch.setenv(DUMP_DIR_ENV, str(tmp_path))
    reg = MetricsRegistry()
    srv = PipelineServer(Doubler(), port=0, registry=reg).start()
    try:
        status, snap = _get(f"http://127.0.0.1:{srv.port}/debug/dump")
        assert status == 200
        for section in ("ring_events", "slow_spans", "compile", "metrics"):
            assert section in snap
        assert snap["dump_path"] is not None
        on_disk = json.load(open(snap["dump_path"]))
        assert on_disk["trigger"] == "http"
        assert reg.family("mmlspark_flightrecorder_dumps_total").value(
            trigger="http", result="ok") == 1
    finally:
        srv.stop()
        reg._flight_recorder.close()


def test_fleet_dump_serves_partial_results_past_a_dead_worker():
    """The endpoint exists FOR fleets with a dead worker: one refused
    connection is an error row + open breaker, never a blind fleet."""
    from mmlspark_tpu.serving import PipelineServer, TopologyService

    reg = MetricsRegistry()
    svc = TopologyService(registry=reg, probe_interval_s=None,
                          fleet_slow_deadline_s=10.0).start()
    wreg = MetricsRegistry()
    w = PipelineServer(Doubler(), port=0, registry=wreg).start()
    try:
        for sid, port in (("w1", w.port), ("dead", 9)):
            req = urllib.request.Request(
                svc.address + "/register",
                json.dumps({"server_id": sid, "host": "127.0.0.1",
                            "port": port}).encode(),
                {"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=10)
        status, out = _get(svc.address + "/fleet/dump?deadline_ms=8000")
        assert status == 200
        assert out["workers"]["w1"] == {"ok": True}
        assert "error" in out["workers"]["dead"]
        assert "dead" not in out["dumps"]
        snap = out["dumps"]["w1"]
        for section in ("ring_events", "slow_spans", "compile", "metrics"):
            assert section in snap
        # the driver's own membership section sees the fleet epoch
        assert reg._flight_recorder.snapshot()["membership"][0]["epoch"] >= 2
        fam = reg.family("mmlspark_flightrecorder_dumps_total")
        assert fam.value(trigger="fleet", result="ok") == 1
        assert fam.value(trigger="fleet", result="error") == 1
        # malformed deadline rejects like every fleet endpoint
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                svc.address + "/fleet/dump?deadline_ms=nope", timeout=10)
        assert err.value.code == 400
    finally:
        w.stop()
        svc.stop()
        reg._flight_recorder.close()
        wreg._flight_recorder.close()
