"""One run of one cell: set-up, a measured window, checks, metrics.

    load the cell's files -> take the device -> traffic.run(run)
      (which builds the system, warms it, opens the window, drives it,
       closes the window and checks the outputs)
    -> reduce the trace, read the per-layer metrics -> the result line

``run_cell`` returns the result object; ``benchmark/run.py`` prints it.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import time
from typing import Any, Dict, List, Optional

from . import measure
from .manifest import Manifest

#: where a traced run keeps its profile, inside the checkout (git-ignored)
TRACE_DIR = ".bench_trace"


class Run:
    """Everything one run knows.  The traffic kind and the family fill it
    in; the per-layer metric readers only read it."""

    def __init__(self, manifest: Manifest, cell: Dict[str, Any], seed: int,
                 seconds: float, trace: bool, process_start_s: float,
                 platform: str = "tpu"):
        self.manifest = manifest
        self.platform = platform
        self.cell = cell
        self.config = manifest.config(cell["config"])
        self.mix = manifest.mix(cell["traffic"])
        self.seed = int(seed)
        self.trace = bool(trace)
        #: a traced run measures a short window: traces are large
        self.seconds = min(float(seconds), float(self.mix["trace_seconds"])) \
            if trace else float(seconds)
        self.process_start_s = process_start_s
        self.spans = measure.Spans()
        self.clock: Optional[measure.CompileClock] = None
        self.registry = None
        self.devices: List[Any] = []
        self.peaks: Optional[Dict[str, float]] = None
        self.failures: List[str] = []
        #: every number a check compared, beside its limit, by a short name
        self.checks: Dict[str, Dict[str, float]] = {}
        self._memory_peak: Optional[int] = None
        #: facts the family and the traffic kind record for the readers
        self.facts: Dict[str, Any] = {}
        self.setup_s: Optional[float] = None
        self.window_start_s = self.window_end_s = 0.0
        #: wall-clock ns at ``window_start_s``: lays a reading of the trace
        #: over the window
        self.window_start_wall_ns = 0
        self.counters_before: Dict = {}
        self.counters_after: Dict = {}
        self.builds_before = self.builds_after = 0
        self.misses_before = self.misses_after = 0
        self.trace_summary = None          # trace_reduce.TraceSummary

    # -------------------------------------------------------------- notes
    def note(self, text: str) -> None:
        print(f"[{self.cell['name']}] {text}", flush=True)

    def fail(self, text: str) -> None:
        """A check that did not hold: the run prints ``correct: false``."""
        self.failures.append(text)
        self.note("CHECK FAILED: " + text)

    def check(self, name: str, value: float, limit: float) -> bool:
        """Hold ``value`` to ``limit`` (at most it; a NaN fails), keep both
        for the result line, and fail the run where it does not hold."""
        value, limit = float(value), float(limit)
        self.checks[name] = {"value": value, "limit": limit}
        ok = value <= limit
        if not ok:
            self.fail(f"{name} = {value!r} over its limit {limit!r}")
        return ok

    def memory_peak_bytes(self) -> int:
        """``_memory_peak_bytes`` of this run, read once: a traffic kind
        whose check runs a reference on the device after the window reads
        it before that, so that the reference is not counted."""
        if self._memory_peak is None:
            self._memory_peak = _memory_peak_bytes(self)
        return self._memory_peak

    # ------------------------------------------------------------- window
    def setup_done(self) -> None:
        """Called by the traffic kind when everything is loaded and warm:
        what follows is the first measured operation."""
        self.setup_s = time.perf_counter() - self.process_start_s

    @contextlib.contextmanager
    def window(self):
        """The measured window.  With ``--trace 1`` the profiler runs for
        exactly this window; starting and stopping it stay outside."""
        import jax
        trace_dir = None
        if self.trace:
            trace_dir = self.manifest.path(TRACE_DIR, self.cell["name"])
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            # on a TPU the device alone is traced: the host tracer also logs
            # every chunk of the runtime's host-side layout transposes, which
            # with the uploads of these cells was millions of events, a 0.5 GB
            # trace and a window three times as slow (PR 23).  Idle gaps are
            # named by the harness's own spans.  On the CPU (the tests) the
            # operations themselves are host events.
            opts.host_tracer_level = 1 if self.platform == "cpu" else 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # read after the profiler has started (which takes a while) and
        # before it stops: where an engine runs on through both, what it
        # counts in the meantime is not the window's
        self.counters_before = measure.snapshot_registry(self.registry)
        self.builds_before = self.clock.builds
        self.misses_before = self.clock.misses
        # the same instant on the two clocks: spans are on perf_counter, the
        # trace counts from its start on the wall clock
        self.window_start_s, self.window_start_wall_ns = \
            time.perf_counter(), time.time_ns()
        try:
            yield
        finally:
            self.window_end_s = time.perf_counter()
            self.builds_after = self.clock.builds
            self.misses_after = self.clock.misses
            self.counters_after = measure.snapshot_registry(self.registry)
            if self.trace:
                jax.profiler.stop_trace()
        if self.trace:
            from . import trace_reduce
            files = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if len(files) != 1:
                raise RuntimeError(f"expected one trace file, found {files}")

            def wall_ns(t_s: float) -> float:
                return self.window_start_wall_ns \
                    + (t_s - self.window_start_s) * 1e9
            self.trace_summary = trace_reduce.reduce_file(
                files[0],
                window_wall_ns=(wall_ns(self.window_start_s),
                                wall_ns(self.window_end_s)),
                wall_spans=[(n, wall_ns(t0), wall_ns(t1))
                            for n, t0, t1 in self.spans.records
                            if t1 >= self.window_start_s],
                platform=self.platform)

    @property
    def window_s(self) -> float:
        return self.window_end_s - self.window_start_s

    # ------------------------------------------------------ reader helpers
    def counter(self, family: str, **labels: str) -> Optional[float]:
        return measure.counter_delta(self.counters_before,
                                     self.counters_after, family, **labels)

    def histogram(self, family: str, **labels: str):
        return measure.histogram_delta(self.counters_before,
                                       self.counters_after, family, **labels)

    def device_busy_s(self) -> Optional[float]:
        """Seconds an operation ran on the device in the window, averaged
        over the chips the cell uses; ``None`` without a trace."""
        if self.trace_summary is None:
            return None
        return self.trace_summary.busy_s

    def device_ms_per(self, count: Optional[float]) -> Optional[float]:
        """Device busy milliseconds of the window per one of ``count``
        things done in it; ``None`` without a trace or with none done."""
        busy = self.device_busy_s()
        if busy is None or not count:
            return None
        return busy * 1e3 / count


def _take_devices(run: Run, platform: str) -> Dict[str, Any]:
    """The device stamp, after holding the run to its platform and chips."""
    import jax
    from mmlspark_tpu.parallel import data_parallel_mesh, set_active_mesh
    from mmlspark_tpu.utils.device import device_stamp
    stamp = device_stamp()
    if stamp["platform"] != platform:
        raise RuntimeError(f"the benchmark runs on {platform!r}; JAX reports "
                           f"{stamp['platform']!r} ({stamp['kind']})")
    chips = int(run.cell["chips"])
    if stamp["count"] < chips:
        raise RuntimeError(f"cell {run.cell['name']} needs {chips} chips; "
                           f"JAX finds {stamp['count']}")
    run.devices = jax.devices()[:chips]
    # the program's default mesh spans every visible device: a one-chip
    # cell on a larger machine must not spread over it
    set_active_mesh(data_parallel_mesh(chips))
    return stamp


def _memory_peak_bytes(run: Run) -> int:
    """Peak bytes held on the fullest chip, as an upper bound.  The TPU
    runtime keeps two accounts that add up, with the free bytes, to the
    chip's limit: buffers (``peak_bytes_in_use``) and what it reserves for
    the scratch of loaded programs (``peak_bytes_reserved``), which the first
    leaves out.  A program's temporaries are most of what the trainer and
    the featurizer hold, so the two are added.  Each is a peak over the whole
    process, set-up included, and the two need not fall at the same instant;
    the runtime reports no joint peak.  Both parts go on an earlier line."""
    peak = 0
    for d in run.devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    if run.devices:
        run.note(f"memory_stats of {run.devices[0]}: "
                 f"{run.devices[0].memory_stats()}")
    return peak


def prepare(root: str, workload: str, seed: int, seconds: float, trace: bool,
            process_start_s: float, platform: str = "tpu"):
    """Load the cell's files, take the device, turn on the compile cache and
    the clocks.  Returns ``(run, device stamp)``."""
    manifest = Manifest(root)
    run = Run(manifest, manifest.cell(workload), seed, seconds, trace,
              process_start_s, platform)
    import jax
    from mmlspark_tpu.observability import get_registry
    from mmlspark_tpu.utils.device import enable_compilation_cache
    stamp = _take_devices(run, platform)
    cache_dir = enable_compilation_cache()
    # sub-second programs recompile in every process under the program's
    # one-second floor (PR 22); a run is a new process, so cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run.clock = measure.CompileClock()
    run.registry = get_registry()
    if platform == "tpu":
        from .peaks import peaks_for
        run.peaks = peaks_for(stamp["kind"])
    run.note(f"device {stamp} compile_cache={cache_dir} seed={run.seed} "
             f"seconds={run.seconds} trace={int(run.trace)}")
    return run, stamp


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             process_start_s: Optional[float] = None,
             platform: str = "tpu") -> Dict[str, Any]:
    """Run one cell and return its result line as an object.  ``platform``
    is ``"tpu"`` for every real run; only the tests pass ``"cpu"``, at tiny
    sizes, and no command line reaches it."""
    if process_start_s is None:
        process_start_s = time.perf_counter()
    run, stamp = prepare(root, workload, seed, seconds, trace,
                         process_start_s, platform)
    manifest = run.manifest

    traffic = manifest.module("traffic", run.mix["kind"])
    family = manifest.module("families", run.config["family"])
    outcome = traffic.run(run, family)
    if run.setup_s is None:
        raise RuntimeError("the traffic kind never called run.setup_done()")

    # No program is built inside the window: the warm-up has been through
    # every shape, and a shape it missed is a build there even when the
    # compile cache holds the program.  Where the program itself makes a new
    # jitted function in every operation, the configuration says how many
    # (``rebuilds_per_operation``) and why: the rate pays for those.
    builds = run.builds_after - run.builds_before
    misses = run.misses_after - run.misses_before
    allowed = int(run.config.get("rebuilds_per_operation", 0))
    run.facts["rebuilds_in_window"] = builds
    run.facts["compiles_in_window"] = measure.unexpected_builds(
        builds, misses, allowed, int(outcome["attempted"]))
    if run.facts["compiles_in_window"]:
        run.fail(f"{builds} programs built inside the measured window, "
                 f"{misses} of them compiled; the configuration allows "
                 f"{allowed} per operation and {outcome['attempted']} ran")
    snap = run.clock.snapshot()
    run.note(f"setup_s={run.setup_s:.2f} compile_s={snap['compile_s']:.2f} "
             f"builds={snap['builds']} cache_hits={snap['cache_hits']} "
             f"cache_misses={snap['cache_misses']} rebuilds_in_window="
             f"{run.facts['rebuilds_in_window']} window_s={run.window_s:.3f}"
             f" attempted={outcome['attempted']} failed={outcome['failed']}")

    metrics: Dict[str, Dict[str, Any]] = {}
    if run.trace:
        for m in manifest.metrics_for("per_layer", workload):
            value = manifest.module("layer_metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = dict(outcome["end_to_end"], setup_s=run.setup_s)
        for m in manifest.metrics_for("end_to_end", workload):
            if m["name"] not in values:
                raise RuntimeError(f"traffic kind {run.mix['kind']!r} gave no "
                                   f"{m['name']} for cell {workload}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}

    device = dict(stamp, memory_peak_bytes=run.memory_peak_bytes())
    result = {"correct": not run.failures,
              "attempted": int(outcome["attempted"]),
              "failed": int(outcome["failed"]),
              "metrics": metrics, "device": device}
    if run.trace:
        ts = run.trace_summary
        device["busy_s"] = ts.busy_s
        device["window_s"] = ts.window_s
        result["breakdown"] = {"device_ops": ts.top_ops(10),
                               "idle_gaps": ts.top_gaps(10)}
    if run.failures:
        result["failures"] = run.failures
    if run.checks:
        # last, where the driver's record of a run at fault keeps it
        result["checks"] = run.checks
    return result
