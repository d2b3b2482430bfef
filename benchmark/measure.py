"""Clocks, spans, counters and the metric arithmetic of the benchmark.

Nothing here imports the program: a later PR that changes the program cannot
change how a number is computed.
"""
from __future__ import annotations

import contextlib
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple


class CompileClock:
    """Process-wide compile seconds and persistent-cache hits and misses,
    read from ``jax.monitoring`` (every compile in the process).  Copied from
    ``chip_smoke.CompileClock``.  ``builds`` counts every program JAX had to
    build: a trace, a lowering, and then either an XLA compilation (a
    ``miss`` of the persistent cache, which the harness keeps with no floor
    on compile time) or the load of a cached executable (a ``hit``)."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _DURATIONS = ("/jax/core/compile/jaxpr_to_mlir_module_duration", _BACKEND)

    def __init__(self):
        from jax import monitoring
        self.compile_s = 0.0
        self.builds = 0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event in self._DURATIONS:
            self.compile_s += duration
        if event == self._BACKEND:
            self.builds += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.compile_s, "builds": self.builds,
                "cache_hits": self.hits, "cache_misses": self.misses}


class Spans:
    """The harness's own spans around the calls into each layer, kept in
    memory: ``(name, start_s, end_s)`` on ``time.perf_counter``."""

    def __init__(self):
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str, start_s: float = -math.inf,
              end_s: float = math.inf) -> float:
        """Seconds inside spans called ``name`` that began in the window."""
        return sum(t1 - t0 for n, t0, t1 in self.records
                   if n == name and start_s <= t0 < end_s)


# ------------------------------------------------------------- arithmetic

def rate(units: float, elapsed_s: float) -> float:
    """Work completed per second; ``elapsed_s`` is read when the last whole
    operation returned, so nothing is lost to an operation cut in half."""
    if elapsed_s <= 0:
        raise ValueError(f"elapsed {elapsed_s} s")
    return units / elapsed_s


def may_start(elapsed_s: float, last_op_s: float, seconds: float,
              done: int, at_least: int) -> bool:
    """The start rule of a back-to-back loop: another whole operation starts
    only while it is expected to end inside the window, and ``at_least``
    always run."""
    return done < at_least or elapsed_s + last_op_s <= seconds


def unexpected_builds(builds: int, misses: int, per_operation: int,
                      operations: int) -> int:
    """Programs built inside a window that should not have been.  ``builds``
    counts every program JAX built there (a trace, a lowering, then a
    compilation or a load from the compile cache), ``misses`` those of them
    that the cache did not hold.  A configuration may say that its program
    builds ``per_operation`` again in every steady-state operation; nothing
    covers a compilation."""
    return max(misses, builds - per_operation * operations, 0)


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between order
    statistics, numpy's default rule, written out so the rule is on record."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def sliced_percentile(at_s: Sequence[float], values: Sequence[float],
                      q: float, slice_s: float, seconds: float
                      ) -> Tuple[float, List[float]]:
    """The median, over the slices of a window, of each slice's ``q``-th
    percentile, and the slices' percentiles.  Value ``i`` belongs to the
    slice of ``slice_s`` seconds that ``at_s[i]`` falls in; a window of
    ``seconds`` has ``floor(seconds / slice_s)`` slices, the last taking what
    is left over, and one slice when ``slice_s`` is 0 or the window shorter.
    A stall of the host spoils the slices it falls in and leaves the median
    of the slices alone, where one percentile over the whole window moves
    with every stall."""
    n = max(1, int(seconds // slice_s)) if slice_s > 0 else 1
    slices: List[List[float]] = [[] for _ in range(n)]
    for t, v in zip(at_s, values):
        slices[min(int(t // slice_s), n - 1) if n > 1 else 0].append(v)
    per_slice = [percentile(vs, q) for vs in slices if vs]
    return percentile(per_slice, 50), per_slice


# ------------------------------------------------- the program's counters

def snapshot_registry(registry) -> Dict[str, Dict[Tuple[Tuple[str, str], ...], object]]:
    """Every counter value and histogram (``sum``, ``count``, cumulative
    buckets) of the program's metrics registry, keyed by family name and
    label set.  Taken at both ends of the window; readers use the difference.
    """
    out: Dict[str, Dict] = {}
    for fam in registry.families():
        if fam.kind not in ("counter", "histogram"):
            continue
        samples = {}
        for key, child in fam._snapshot():
            labels = tuple(zip(fam.label_names, key))
            if fam.kind == "counter":
                samples[labels] = float(child.value)
            else:
                samples[labels] = {"sum": child.sum, "count": child.count,
                                   "cumulative": child.cumulative()}
        out[fam.name] = samples
    return out


def _matching(samples: Dict, want: Dict[str, str]):
    for labels, value in samples.items():
        have = dict(labels)
        if all(have.get(k) == v for k, v in want.items()):
            yield value


def counter_delta(before: Dict, after: Dict, family: str,
                  **labels: str) -> Optional[float]:
    """Growth of a counter over the window, summed over the children whose
    labels match; ``None`` when the program has no such counter."""
    if family not in after:
        return None
    end = sum(_matching(after[family], labels))
    start = sum(_matching(before.get(family, {}), labels))
    return end - start


def histogram_delta(before: Dict, after: Dict, family: str,
                    **labels: str) -> Optional[Dict[str, object]]:
    """What a histogram gathered inside the window: ``sum``, ``count`` and
    per-bucket counts ``[(upper_bound, n), ...]``, summed over matching
    children; ``None`` when there is no such histogram or it saw nothing."""
    if family not in after:
        return None
    total = {"sum": 0.0, "count": 0, "buckets": {}}

    def add(sample, sign):
        total["sum"] += sign * sample["sum"]
        total["count"] += sign * sample["count"]
        prev = 0
        for ub, cum in sample["cumulative"]:
            total["buckets"][ub] = total["buckets"].get(ub, 0) \
                + sign * (cum - prev)
            prev = cum

    for s in _matching(after[family], labels):
        add(s, +1)
    for s in _matching(before.get(family, {}), labels):
        add(s, -1)
    if total["count"] <= 0:
        return None
    total["buckets"] = sorted(total["buckets"].items())
    return total


def bucket_percentile(buckets: Sequence[Tuple[float, int]], q: float) -> float:
    """Percentile of a bucketed histogram by linear interpolation inside the
    bucket that holds the rank (the first bucket starts at 0; the overflow
    bucket reports its lower edge).  The program's phase histograms have four
    buckets per decade, so this is good to about a quarter of a decade."""
    count = sum(n for _, n in buckets)
    if count <= 0:
        raise ValueError("percentile of an empty histogram")
    rank = q / 100.0 * count
    cum, lower = 0.0, 0.0
    for ub, n in buckets:
        if n and cum + n >= rank:
            if math.isinf(ub):
                return lower
            return lower + (ub - lower) * (rank - cum) / n
        cum += n
        if not math.isinf(ub):
            lower = ub
    return lower
