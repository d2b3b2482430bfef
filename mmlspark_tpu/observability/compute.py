"""Compute-plane telemetry — the half of observability that lives below jit.

PRs 2 and 4 made the HOST side legible (queue-vs-score splits, spans,
exemplars), but once execution enters XLA the system was dark: a recompile
storm, an HBM high-water creep, or a host->device transfer stall all looked
identical ("score phase got slow").  This module instruments the compile/
device boundary itself:

- :func:`instrumented_jit` — drop-in for ``jax.jit`` (including
  ``jax.jit(jax.shard_map(...))`` composites).  Every call resolves the
  arguments' *abstract shape signature*; a signature hit is a dict lookup
  straight into the compiled executable, a miss books one compilation:
  ``mmlspark_jit_compile_total{fn}`` / ``mmlspark_jit_compile_seconds{fn}``,
  the compile's ``cost_analysis()`` (FLOPs / bytes-accessed gauges, so a
  bench rows/sec can be read as %% of achievable utilization), and — when a
  single function crosses ``storm_signatures`` distinct signatures — a
  *recompile-storm* warning event plus
  ``mmlspark_jit_recompile_storm_total{fn}``, the classic silent TPU
  production killer.  All booking happens on the HOST side of the cache
  miss, never inside traced code (tracer-safe by construction; graft-lint
  TRC treats ``instrumented_jit`` as a tracing entry point so the wrapped
  functions keep their tracer-safety coverage).
- device-memory gauges — ``mmlspark_device_bytes_in_use{device}`` /
  ``mmlspark_device_peak_bytes_in_use{device}`` sampled from
  ``device.memory_stats()`` at scrape time (callback gauges; platforms
  without memory introspection — CPU — simply don't register the series).
- :func:`device_put` — drop-in for ``jax.device_put`` booking
  ``mmlspark_device_transfer_bytes_total{site}``: the host->device feed the
  billion-row out-of-core item needs visible before it lands.
- :func:`compile_report` — the JSON behind ``GET /debug/compile`` on
  ``PipelineServer``: per-function compile counts, the signatures seen, and
  the last cost analysis.
- :func:`ensure_build_info` — ``mmlspark_build_info`` gauge (jax version /
  backend / device kind / device count labels) so scraped dashboards can
  pivot every series by environment.

``jax`` is imported lazily inside functions: graft-lint environments import
this package without jax (PR 3 contract).
"""
from __future__ import annotations

import functools
import inspect
import os
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

from .metrics import MetricsRegistry, get_registry

__all__ = ["InstrumentedJit", "instrumented_jit", "device_put",
           "transfer_nbytes", "compile_report",
           "ensure_device_memory_gauges", "ensure_build_info",
           "STORM_SIGNATURES_ENV", "DEFAULT_STORM_SIGNATURES"]

#: env override for the recompile-storm threshold (distinct signatures one
#: function may compile before each further signature books a storm trip)
STORM_SIGNATURES_ENV = "MMLSPARK_TPU_JIT_STORM_SIGS"
DEFAULT_STORM_SIGNATURES = 8

#: env hatch disabling the AOT executable cache (the wrapper then books
#: compiles by signature but dispatches through plain ``jax.jit``)
AOT_ENV = "MMLSPARK_TPU_JIT_AOT"


def _storm_threshold() -> int:
    raw = os.environ.get(STORM_SIGNATURES_ENV, "")
    try:
        return max(2, int(raw)) if raw.strip() else DEFAULT_STORM_SIGNATURES
    except ValueError:
        return DEFAULT_STORM_SIGNATURES


# ---------------------------------------------------------------------------
# abstract shape signatures
# ---------------------------------------------------------------------------

_DTYPE_SHORT = {"float32": "f32", "float64": "f64", "float16": "f16",
                "bfloat16": "bf16", "int32": "i32", "int64": "i64",
                "int16": "i16", "int8": "i8", "uint8": "u8",
                "uint16": "u16", "uint32": "u32", "bool": "b1"}


def _leaf_sig(leaf) -> Tuple:
    """One leaf's cache identity, mirroring jax.jit's semantics: arrays key
    on (shape, dtype, weak_type, sharding) — placement included because an
    AOT executable is specialized to its inputs' shardings exactly like
    jit's own cache; python scalars key on their TYPE only (jit traces them
    weak-typed, so a new float VALUE is not a recompile)."""
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        # the dtype OBJECT keys the signature (hashable, equality-correct);
        # stringification happens only at render time — str(np.dtype) walks
        # numpy's uncached name machinery and costs ~10us per leaf per call
        return ("a", tuple(leaf.shape), leaf.dtype,
                bool(getattr(leaf, "weak_type", False)),
                getattr(leaf, "sharding", None))
    return ("py", type(leaf).__name__)


def _render_leaf(sig: Tuple) -> str:
    if sig[0] == "a":
        name = str(sig[2])
        dt = _DTYPE_SHORT.get(name, name)
        out = f"{dt}[{','.join(str(d) for d in sig[1])}]"
        spec = getattr(sig[4], "spec", None) if len(sig) > 4 else None
        if spec is not None:  # NamedSharding: show the partitioning
            out += f"@{spec}"
        return out
    if sig[0] == "py":
        return f"py:{sig[1]}"
    return f"static:{sig[1]!r}"


def _extract_cost(analysis) -> Optional[Dict[str, float]]:
    """Reduce ``Compiled.cost_analysis()``'s dict to {flops,
    bytes_accessed}."""
    if not isinstance(analysis, dict):
        return None
    out = {}
    if "flops" in analysis:
        out["flops"] = float(analysis["flops"])
    if "bytes accessed" in analysis:
        out["bytes_accessed"] = float(analysis["bytes accessed"])
    return out or None


class _SigEntry:
    """One compiled signature: the executable (or None when the AOT path
    was not viable and dispatch stays on plain jit) plus its book-keeping."""

    __slots__ = ("compiled", "rendered", "compile_s", "cost")

    def __init__(self, rendered: str):
        self.compiled = None
        self.rendered = rendered
        self.compile_s = 0.0
        self.cost: Optional[Dict[str, float]] = None


class InstrumentedJit:
    """``jax.jit`` with compile-boundary telemetry.

    Dispatch: the arguments' abstract signature indexes a dict of compiled
    executables — the steady-state path is one signature build + dict hit,
    with zero metric writes.  A miss lowers + compiles once (AOT), books the
    compile counter/histogram, captures ``cost_analysis()``, and checks the
    recompile-storm threshold.  Any AOT failure (exotic argument placement,
    jax version quirk) falls back to the plain jitted callable for that
    signature — semantics are never worse than ``jax.jit``.
    """

    def __init__(self, fn: Callable, *, name: Optional[str] = None,
                 registry: Optional[MetricsRegistry] = None,
                 storm_signatures: Optional[int] = None,
                 static_argnums=(), static_argnames=(), **jit_kwargs):
        import jax
        self._fn = fn
        self.name = name or getattr(fn, "__name__", None) or "anonymous"
        self.registry = registry if registry is not None else get_registry()
        self.storm_signatures = storm_signatures or _storm_threshold()
        # an explicit empty static_argnums would DISABLE jax's inference of
        # positions from static_argnames — only forward what was given
        static_kw = {}
        if static_argnums not in ((), None):
            static_kw["static_argnums"] = static_argnums
        if static_argnames not in ((), None, ""):
            static_kw["static_argnames"] = static_argnames
        self._jitted = jax.jit(fn, **static_kw, **jit_kwargs)
        self._aot = os.environ.get(AOT_ENV, "1").strip().lower() \
            not in ("0", "false", "off", "no")
        # static-argument plan: the AOT executable takes only dynamic args,
        # so static positions must be resolvable — by number directly, and
        # by name through the function signature (static_argnames may be
        # passed positionally at call sites)
        self._static_nums = set(int(i) for i in (
            (static_argnums,) if isinstance(static_argnums, int)
            else static_argnums))
        self._static_names = set((static_argnames,) if isinstance(
            static_argnames, str) else static_argnames)
        if self._static_names:
            try:
                params = list(inspect.signature(fn).parameters)
                for nm in self._static_names:
                    if nm in params:
                        self._static_nums.add(params.index(nm))
            except (TypeError, ValueError):
                self._aot = False  # cannot split positionally-passed statics
        self._has_static = bool(self._static_nums or self._static_names)
        self._entries: Dict[Tuple, _SigEntry] = {}
        self._lock = threading.Lock()
        self._storm_tripped = False
        self.last_compile_s = 0.0
        # metric children bound once (the miss path is rare but the labels
        # must not be resolved per compile inside any lock)
        reg = self.registry
        self._c_compile = reg.counter(
            "mmlspark_jit_compile_total",
            "XLA compilations by instrumented function",
            labels=("fn",)).labels(fn=self.name)
        self._h_compile = reg.histogram(
            "mmlspark_jit_compile_seconds",
            "lower+compile wall time per new abstract signature",
            labels=("fn",)).labels(fn=self.name)
        self._c_storm = reg.counter(
            "mmlspark_jit_recompile_storm_total",
            "signatures compiled at/over the recompile-storm threshold",
            labels=("fn",)).labels(fn=self.name)
        self._g_flops = reg.gauge(
            "mmlspark_jit_flops",
            "cost_analysis FLOPs of the last compile",
            labels=("fn",))
        self._g_bytes = reg.gauge(
            "mmlspark_jit_bytes_accessed",
            "cost_analysis bytes accessed of the last compile",
            labels=("fn",))
        table = getattr(reg, "_jit_wrappers", None)
        if table is None:
            table = reg._jit_wrappers = {}
        table.setdefault(self.name, weakref.WeakSet()).add(self)

    # ------------------------------------------------------------- dispatch
    def _signature(self, args, kwargs) -> Tuple:
        import jax
        sig: List = []
        for i, a in enumerate(args):
            if i in self._static_nums:
                sig.append(("static", a))
                continue
            leaves, treedef = jax.tree_util.tree_flatten(a)
            sig.append((treedef, tuple(_leaf_sig(l) for l in leaves)))
        for k in sorted(kwargs):
            if k in self._static_names:
                sig.append((k, ("static", kwargs[k])))
                continue
            leaves, treedef = jax.tree_util.tree_flatten(kwargs[k])
            sig.append((k, treedef, tuple(_leaf_sig(l) for l in leaves)))
        return tuple(sig)

    def _render(self, args, kwargs) -> str:
        parts: List[str] = []
        import jax
        for i, a in enumerate(args):
            if i in self._static_nums:
                parts.append(f"static:{a!r}")
            else:
                leaves, _ = jax.tree_util.tree_flatten(a)
                parts.append("/".join(_render_leaf(_leaf_sig(l))
                                      for l in leaves) or "()")
        for k in sorted(kwargs):
            if k in self._static_names:
                parts.append(f"{k}=static:{kwargs[k]!r}")
            else:
                leaves, _ = jax.tree_util.tree_flatten(kwargs[k])
                parts.append(f"{k}=" + ("/".join(
                    _render_leaf(_leaf_sig(l)) for l in leaves) or "()"))
        return ", ".join(parts)

    def _call_compiled(self, compiled, args, kwargs):
        if not self._has_static:
            return compiled(*args, **kwargs)
        dyn_args = tuple(a for i, a in enumerate(args)
                         if i not in self._static_nums)
        dyn_kwargs = {k: v for k, v in kwargs.items()
                      if k not in self._static_names}
        return compiled(*dyn_args, **dyn_kwargs)

    def __call__(self, *args, **kwargs):
        sig = self._signature(args, kwargs)
        entry = self._entries.get(sig)  # GIL-atomic read; hot path
        if entry is not None:
            if entry.compiled is not None:
                return self._call_compiled(entry.compiled, args, kwargs)
            return self._jitted(*args, **kwargs)
        return self._compile_miss(sig, args, kwargs)

    def _compile_miss(self, sig, args, kwargs):
        """Cache miss: compile (AOT when possible), book, then execute.
        Serialized per wrapper so concurrent first calls book one compile."""
        # environment/device gauges ride the first compile, NOT wrapper
        # construction: module-level `@instrumented_jit` must never
        # initialize the jax backend at import time (both are idempotent)
        ensure_build_info(self.registry)
        ensure_device_memory_gauges(self.registry)
        with self._lock:
            entry = self._entries.get(sig)
            if entry is None:
                entry = self._do_compile(sig, args, kwargs)
        # execution happens OUTSIDE the wrapper lock
        if entry.compiled is not None:
            try:
                return self._call_compiled(entry.compiled, args, kwargs)
            except TypeError:
                # Compiled raises TypeError for call-shape mismatches
                # (pytree drift, tracer args) BEFORE executing — safe to
                # fall back to plain jit.  Anything else is a real runtime
                # failure and must propagate: re-executing would double-run
                # side effects and crash on donated (consumed) buffers.
                entry.compiled = None  # permanent fallback for this sig
        return self._jitted(*args, **kwargs)

    def _do_compile(self, sig, args, kwargs) -> _SigEntry:
        entry = _SigEntry(self._render(args, kwargs))
        t0 = time.perf_counter()
        try:
            lowered = self._jitted.lower(*args, **kwargs)
            compiled = lowered.compile()
        except Exception:  # noqa: BLE001 — fall back to plain jit dispatch
            compiled = None
        entry.compile_s = time.perf_counter() - t0
        if compiled is not None and self._aot:
            entry.compiled = compiled
        if compiled is not None:
            try:
                entry.cost = _extract_cost(compiled.cost_analysis())
            except Exception:  # noqa: BLE001 — cost analysis is best-effort
                entry.cost = None
        self._entries[sig] = entry
        self.last_compile_s = entry.compile_s
        self._book_compile(entry, len(self._entries))
        return entry

    def _book_compile(self, entry: _SigEntry, n_sigs: int) -> None:
        # all booking is host-side, after compile, before execution — a
        # compile that produces a failing program is still a compile
        self._c_compile.inc()
        self._h_compile.observe(entry.compile_s)
        if entry.cost:
            if "flops" in entry.cost:
                self._g_flops.set(entry.cost["flops"], fn=self.name)
            if "bytes_accessed" in entry.cost:
                self._g_bytes.set(entry.cost["bytes_accessed"], fn=self.name)
        if n_sigs >= self.storm_signatures:
            self._c_storm.inc()
            if not self._storm_tripped:
                self._storm_tripped = True
                from ..core.logging import log_event  # lazy: import cycle
                log_event({"event": "recompile_storm",
                           "className": "InstrumentedJit", "fn": self.name,
                           "distinct_signatures": n_sigs,
                           "threshold": self.storm_signatures,
                           "last_signature": entry.rendered})

    # --------------------------------------------------------------- report
    @property
    def compiles(self) -> int:
        return len(self._entries)

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able state for ``/debug/compile``."""
        with self._lock:
            entries = list(self._entries.values())
            tripped = self._storm_tripped
        last_cost = None
        sigs = []
        for e in entries:
            sigs.append({"signature": e.rendered,
                         "compile_seconds": round(e.compile_s, 6),
                         "cost_analysis": e.cost,
                         "aot": e.compiled is not None})
            if e.cost is not None:
                last_cost = e.cost
        return {"fn": self.name, "compiles": len(entries),
                "storm_threshold": self.storm_signatures,
                "storm_tripped": tripped,
                "signatures": sigs, "last_cost_analysis": last_cost}

    # a drop-in must still expose the AOT entry point some callers use
    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def clear_cache(self) -> None:
        """``jax.jit``'s ``clear_cache()``: release every compiled
        executable this wrapper holds (the next call recompiles and books
        the compile again)."""
        with self._lock:
            self._entries.clear()
        self._jitted.clear_cache()

    def __repr__(self):
        return (f"InstrumentedJit({self.name!r}, "
                f"signatures={len(self._entries)})")


def instrumented_jit(fn: Optional[Callable] = None, *,
                     name: Optional[str] = None,
                     registry: Optional[MetricsRegistry] = None,
                     storm_signatures: Optional[int] = None,
                     static_argnums=(), static_argnames=(), **jit_kwargs):
    """Drop-in ``jax.jit`` replacement with compile-plane telemetry; usable
    as ``instrumented_jit(fn, name=...)`` or ``@instrumented_jit(name=...)``.
    See :class:`InstrumentedJit`."""
    if fn is None:
        return functools.partial(
            instrumented_jit, name=name, registry=registry,
            storm_signatures=storm_signatures, static_argnums=static_argnums,
            static_argnames=static_argnames, **jit_kwargs)
    return InstrumentedJit(fn, name=name, registry=registry,
                           storm_signatures=storm_signatures,
                           static_argnums=static_argnums,
                           static_argnames=static_argnames, **jit_kwargs)


def compile_report(registry: Optional[MetricsRegistry] = None) -> Dict[str, Any]:
    """Aggregated per-function compile state — ``GET /debug/compile``.

    Wrappers sharing a ``name`` (e.g. one per jit-cache key) merge into one
    entry; functions whose wrappers were garbage-collected drop out."""
    reg = registry if registry is not None else get_registry()
    table: Dict[str, Any] = getattr(reg, "_jit_wrappers", {})
    functions: Dict[str, Any] = {}
    for name in sorted(table):
        wrappers = [w for w in table[name]]
        if not wrappers:
            continue
        snaps = [w.snapshot() for w in wrappers]
        functions[name] = {
            "compiles": sum(s["compiles"] for s in snaps),
            "storm_threshold": min(s["storm_threshold"] for s in snaps),
            "storm_tripped": any(s["storm_tripped"] for s in snaps),
            "signatures": [sig for s in snaps for sig in s["signatures"]],
            "last_cost_analysis": next(
                (s["last_cost_analysis"] for s in reversed(snaps)
                 if s["last_cost_analysis"] is not None), None),
        }
    return {"functions": functions,
            "storm_threshold_default": _storm_threshold()}


# ---------------------------------------------------------------------------
# device-memory gauges
# ---------------------------------------------------------------------------

def _mem_stat(device, key: str) -> float:
    stats = device.memory_stats()
    if not stats:
        return float("nan")
    return float(stats.get(key, float("nan")))


def ensure_device_memory_gauges(registry: Optional[MetricsRegistry] = None,
                                devices=None) -> bool:
    """Register per-local-device callback gauges sampled from
    ``device.memory_stats()`` at scrape time:

    - ``mmlspark_device_bytes_in_use{device}``
    - ``mmlspark_device_peak_bytes_in_use{device}``

    Idempotent per registry.  Platforms without memory introspection (CPU
    returns None) register nothing — a dashboard should see no series, not
    a wall of NaN.  Returns True when the gauges are live."""
    reg = registry if registry is not None else get_registry()
    state = getattr(reg, "_device_mem_gauges", None)
    if state:
        return True
    # a cached negative verdict short-circuits only the ambient path —
    # explicit devices= (tests, late-attached accelerators) re-evaluate
    if state is False and devices is None:
        return False
    if devices is None:
        try:
            import jax
            devices = jax.local_devices()
        except Exception:  # noqa: BLE001 — no jax / backend unreachable:
            return False   # transient — no verdict cached, retried next
                           # compile (misses are rare by construction)
    live = []
    for d in devices:
        try:
            if d.memory_stats():
                live.append(d)
        except Exception:  # noqa: BLE001 — introspection unsupported
            continue
    if not live:
        reg._device_mem_gauges = False
        return False
    g_use = reg.gauge("mmlspark_device_bytes_in_use",
                      "live allocated bytes per local device (sampled from "
                      "memory_stats at scrape time)", labels=("device",))
    g_peak = reg.gauge("mmlspark_device_peak_bytes_in_use",
                       "high-water allocated bytes per local device",
                       labels=("device",))
    for d in live:
        label = f"{d.platform}:{d.id}"
        g_use.set_function(
            functools.partial(_mem_stat, d, "bytes_in_use"), device=label)
        g_peak.set_function(
            functools.partial(_mem_stat, d, "peak_bytes_in_use"),
            device=label)
    reg._device_mem_gauges = True
    return True


# ---------------------------------------------------------------------------
# host->device transfer accounting
# ---------------------------------------------------------------------------

def transfer_nbytes(x) -> int:
    """Total buffer bytes in a pytree (what a device_put will move or, for
    already-resident arrays, re-place)."""
    import jax
    leaves, _ = jax.tree_util.tree_flatten(x)
    return sum(int(getattr(l, "nbytes", 0)) for l in leaves)


def _transfer_child(site: str, reg: MetricsRegistry):
    cache = getattr(reg, "_transfer_children", None)
    if cache is None:
        cache = reg._transfer_children = {}
    child = cache.get(site)
    if child is None:
        child = cache[site] = reg.counter(
            "mmlspark_device_transfer_bytes_total",
            "bytes offered to device_put by call site (host->device feed; "
            "already-resident arrays count as placement)",
            labels=("site",)).labels(site=site)
    return child


def device_put(x, device=None, *, site: str = "unlabeled",
               registry: Optional[MetricsRegistry] = None, **kw):
    """Drop-in ``jax.device_put`` that books
    ``mmlspark_device_transfer_bytes_total{site}`` before the transfer.
    The byte count is computed host-side from the input leaves, so the
    booking adds no device sync."""
    import jax
    reg = registry if registry is not None else get_registry()
    _transfer_child(site, reg).inc(transfer_nbytes(x))
    if device is None:
        return jax.device_put(x, **kw)
    return jax.device_put(x, device, **kw)


# ---------------------------------------------------------------------------
# build info
# ---------------------------------------------------------------------------

def ensure_build_info(registry: Optional[MetricsRegistry] = None) -> bool:
    """Register the ``mmlspark_build_info`` gauge (constant 1) labelled with
    the jax version, backend, device kind, and local device count — the
    pivot every scraped dashboard needs to split series by environment.
    Idempotent per registry; a jax-less environment registers nothing."""
    reg = registry if registry is not None else get_registry()
    state = getattr(reg, "_build_info_done", None)
    if state is not None:
        return state
    try:
        import jax
        devices = jax.local_devices()
        backend = jax.default_backend()
        kind = devices[0].device_kind if devices else "unknown"
        reg.gauge("mmlspark_build_info",
                  "constant 1; labels identify the compute environment",
                  labels=("jax", "backend", "device_kind", "device_count")
                  ).set(1.0, jax=jax.__version__, backend=backend,
                        device_kind=kind, device_count=str(len(devices)))
    except Exception:  # noqa: BLE001 — no jax / no backend: skip quietly
        reg._build_info_done = False
        return False
    reg._build_info_done = True
    return True
