"""Unified model runner — lower-once StableHLO execution for every model.

The paper's second capability pillar (ROADMAP "Unified StableHLO model
runner"): one subsystem that takes an in-tree model (resnet, transformer,
bilstm), an ONNX import (``dl/onnx_import.py``), or any pure
``apply(variables, batch)`` callable, lowers it **once per (local device
set, bucketed batch shape)** through ``instrumented_jit`` into a cached
executable, and serves it behind two fronts:

- **batch transform** — :meth:`ModelRunner.apply_batch` owns the padding/
  bucketing/unpadding that ``dl/jax_model.py``, ``dl/image_featurizer.py``
  and the serving scorers each hand-rolled before this PR (power-of-two
  latency buckets: a 1-row request pads to 1, not ``batch_size``);
- **low-latency serving** — :meth:`ModelRunner.scorer` returns a
  ``Transformer`` that ``PipelineServer`` (and the streaming facade) score
  through: the server's continuous-mode drain admits requests into one
  in-flight batch, and the runner buckets that batch onto an already-lowered
  executable, so steady-state latency never pays a compile.

On top of it, generative scoring is a first-class workload:
:meth:`ModelRunner.decode` runs a KV-cached batched decode loop — one
prefill executable per (batch bucket, prompt bucket, cache geometry) plus
ONE single-token step executable re-dispatched every token, with
per-sequence lengths so ragged prompts decode exactly
(``models/transformer.py`` owns the cache math; docs/runner.md states the
correctness argument).  ISSUE 12 rebuilt the decode memory model: the step
executables DONATE the cache (and finished-mask) buffers so per-token
dispatch updates slots in place instead of allocating a fresh cache per
layer per token; the default greedy/eos path samples + freezes on device
(one (B,) token fetch per step, never the (B, V) logits); and
``kv_layout="paged"`` replaces the dense per-sequence reservation with
fixed-size pages from a shared :class:`PagePool` plus a per-sequence page
table, so hundreds of concurrent sequences share cache HBM by ACTUAL
length — the serving pattern the TPU-vs-GPU Gemma study in PAPERS.md
benchmarks, and the memory substrate the continuous-batching ROADMAP item
admits requests into.  The paged step is keyed on (batch bucket, page
size, table width): cache length stops being a compile key, collapsing the
per-``cache_len`` executable fan-out.

Lowering contract (the lower-once/execute-many precedent is the Julia→TPU
full-compilation work, PAPERS arxiv 1810.09868): every executable is keyed
by (device set, bucket shape) and built exactly once; compile counts ride
``mmlspark_jit_compile_total{fn="runner.<name>*"}`` so a recompile storm
across ragged batch sizes is impossible by construction and visible on
``/debug/compile`` if an input ever escapes the buckets.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core import DataFrame, Transformer
from ..utils.concurrency import make_condition, make_lock
from ..core.schema import ColumnType

__all__ = ["ModelRunner", "DecodeResult", "PagePool", "ContinuousDecoder",
           "StreamHandle", "PagePoolExhausted", "SlotsExhausted", "ShedReply",
           "RowSource", "bucket_rows"]

#: fronts a batch can arrive through; metric label values
FRONTS = ("transform", "serving", "decode")

#: the laps of :meth:`ModelRunner.apply_batch`, the ``phase`` values of
#: ``mmlspark_runner_batch_phase_seconds``: a chunk filled into its staging
#: buffer (or a dense input's slice padded), the jitted call with the ask
#: for its output's copy, the wait for the oldest chunk's output, its fetch,
#: the final concatenation.  An upload has no lap: the call returns while it
#: runs, so the device's idle time under an upload is the lap's the host is in
BATCH_LAPS = ("stage", "dispatch", "wait", "fetch", "concat")
#: the laps of the continuous engine's round (``ContinuousDecoder``), ``phase``
#: values of ``mmlspark_runner_decode_phase_seconds`` beside ``device``
DECODE_LAPS = ("join_prefill", "join_fetch", "join_splice", "prepare",
               "dispatch", "fetch", "book", "notify")


class PagePoolExhausted(RuntimeError):
    """The page pool cannot cover an allocation — admission control, not a
    crash.  ``shed`` duck-types the serving layer's shed path (serving maps
    it to 503 + Retry-After without importing this module)."""
    shed = True


class SlotsExhausted(RuntimeError):
    """No free decode slot for a new arrival — the continuous engine's
    admission-control twin of :class:`PagePoolExhausted`."""
    shed = True


class EngineDraining(RuntimeError):
    """The decoder is draining (graceful shutdown, ISSUE 16): no new
    joins — existing slots run to eos/budget, arrivals shed retryably."""
    shed = True
    shed_reason = "draining"


class EngineUnavailable(RuntimeError):
    """The continuous decode engine cannot take this request right now —
    restart backoff in progress, or the runner is quarantined after
    repeated stalls (ISSUE 16).  A retryable shed (another worker can
    serve it), not a failure: ``shed`` duck-types the serving 503 path."""
    shed = True

    def __init__(self, msg: str, reason: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.shed_reason = reason
        self.retry_after_s = float(retry_after_s)


class ShedReply:
    """Per-row shed sentinel: a scorer that must refuse ONE row of a batch
    (mid-decode page denial) returns this in the reply column, and the
    serving layer maps it to 503 + Retry-After.  Duck-typed on
    ``shed_reason`` so serving never imports the models package."""

    __slots__ = ("shed_reason", "retry_after_s")

    def __init__(self, reason: str, retry_after_s: Optional[float] = None):
        self.shed_reason = reason
        self.retry_after_s = retry_after_s


def bucket_rows(m: int, batch_size: int) -> int:
    """Power-of-two latency bucket for an ``m``-row chunk: a 1-row serving
    request pads to 1, not ``batch_size``; full chunks use ``batch_size``
    itself.  Each bucket lowers once and is cached."""
    if m >= batch_size:
        return batch_size
    return min(batch_size, 1 << (max(1, m) - 1).bit_length())


def _pad_rows(x: np.ndarray, target: int) -> np.ndarray:
    """Pad the leading dim to ``target`` by repeating the last row (cheap,
    and keeps the padded rows numerically tame for any model)."""
    m = x.shape[0]
    if m == target:
        return x
    pad = np.repeat(x[-1:], target - m, axis=0)
    return np.concatenate([x, pad], axis=0)


class RowSource:
    """Rows a caller has NOT stacked: ``shape`` (``(n, *row_shape)``) and
    ``dtype`` are known up front, the pixels are copied only when
    :meth:`ModelRunner.apply_batch` asks for rows ``start:stop``, chunk by
    chunk into its own staging buffers.  ``as_row`` views one element of
    ``rows`` as an array of ``row_shape`` (no copy wanted: :meth:`fill`
    makes the one copy, casting to ``dtype``).  ``apply_batch`` reads
    ``shape``, ``dtype`` and ``fill`` and nothing else, so any object with
    those three is a row source."""

    def __init__(self, rows, row_shape: Tuple[int, ...], dtype,
                 as_row: Callable[[Any], np.ndarray] = np.asarray):
        self.rows = rows
        self.shape = (len(rows), *row_shape)
        self.dtype = np.dtype(dtype)
        self.as_row = as_row

    def fill(self, out: np.ndarray, start: int, stop: int) -> None:
        """Copy rows ``start:stop`` into ``out[:stop - start]``."""
        row_shape = self.shape[1:]
        for j, i in enumerate(range(start, stop)):
            r = self.as_row(self.rows[i])
            if r.shape != row_shape:        # the assignment would broadcast
                raise ValueError(f"row {i} has shape {r.shape}, the "
                                 f"partition's first has {row_shape}")
            out[j] = r


def _greedy_freeze(logits, finished, eos_id):
    """On-device greedy sampling + eos freeze — the ONE copy of the rule
    shared by the fused decode step and the prefill sampler: frozen
    sequences keep emitting ``eos_id``, and emitting it freezes."""
    import jax.numpy as jnp
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if eos_id is not None:
        tok = jnp.where(finished, eos_id, tok)
        finished = finished | (tok == eos_id)
    return tok, finished


def _keeps_slot_state(module) -> bool:
    """Whether ``module`` keeps per-slot state beside its pages
    (``init_slot_state(rows)``; see :class:`PagePool`)."""
    return hasattr(module, "init_slot_state")


def _refuse_slot_state_prefix(module) -> None:
    """Prefix sharing over per-slot state is not built: refuse it by name
    rather than serve a hit whose state never saw the prefix."""
    if _keeps_slot_state(module):
        raise ValueError(
            "prefix_cache=True is not supported for a module that keeps "
            f"per-slot state beside its pages ({type(module).__name__}): "
            "the prefix index shares PAGES, and a hit would need a window "
            "layer's ring or a linear-attention layer's recurrent state as "
            "it stood at the prefix's end, which no page holds")


def _cached_apply(module, variables, toks, positions, table, cache,
                  logits_at=None, rows=None):
    """One call shape for every decode executable: ``table`` is ``None`` on
    the dense layout (an empty pytree — part of the jit signature, no
    tracing cost) and the kwarg is withheld so modules that only know
    ``init_cache`` keep working.  ``logits_at`` (B,) has the module apply
    its head to that one row of each sequence (prefill: the last real
    position), so the (B, P, vocab) logits are never built.  ``rows`` (B,)
    names each sequence's row of the per-slot state a module with
    ``init_slot_state`` keeps beside its pages (a join prefills ONE
    sequence into its slot's row; without it sequence b uses row b).  Returns
    ``(logits, cache, sown)``: ``sown`` is what the module sowed into
    ``intermediates`` (a routed model's ``experts_touched``) and ``{}`` —
    no output of the compiled program — for a module that sows nothing."""
    kw = {} if table is None else {"page_table": table}
    if logits_at is not None:
        kw["logits_at"] = logits_at
    if rows is not None:
        kw["cache_rows"] = rows
    (logits, cache), sown = module.apply(
        variables, toks, positions=positions, kv_cache=cache,
        mutable=["intermediates"], **kw)
    return logits, cache, dict(sown.get("intermediates", {}))


@dataclass
class DecodeResult:
    """One batched decode: ``tokens[b, t]`` is the t-th generated token of
    sequence b; ``logits`` (collect_logits=True) holds the distribution
    that produced each token; ``steps`` counts device dispatches (prefill
    excluded); ``lengths`` echoes the prompt lengths the loop honoured;
    ``extras`` surfaces the resolved cache geometry — kv_layout,
    real_tokens (unfrozen steps only), cache_bytes_per_seq, and for the
    paged layout page_size / table_width / pages_peak /
    page_occupancy_pct — so callers (``mixed_load``'s decode class, the
    bench A/B) can report tokens/sec against the memory the decode
    actually held."""
    tokens: np.ndarray                 # (B, T) int32
    lengths: np.ndarray                # (B,) prompt lengths
    steps: int
    logits: Optional[np.ndarray] = None  # (B, T, V) float32
    extras: Optional[Dict[str, Any]] = None


class PagePool:
    """Fixed-size KV-cache page allocator — the shared-HBM memory model
    behind ``ModelRunner.decode(kv_layout="paged")`` (ISSUE 12 tentpole).

    The pool owns ``num_pages`` pages of ``page_size`` token slots each,
    materialized on device as the slabs ``module.init_paged_cache``
    returns (for ``TransformerEncoder`` a k and a v of ``(num_pages,
    page_size, heads * head_dim)`` per layer), plus the host-side free list
    that hands pages to sequences: allocate by TRUE prompt length at
    prefill, extend one page at a time when a decode frontier crosses a
    page boundary, free on eos/completion.  Page 0 is
    the reserved trash page (pad rows and unallocated table entries point
    there; it is never handed out), so ``capacity == num_pages - 1``.
    Sequences therefore share cache HBM by actual length instead of
    reserving ``batch × max_len`` slots each — the occupancy and
    high-water gauges make the claim observable on ``/metrics``.

    The device slabs are BORROWED by one decode loop at a time (the step
    executables donate them in place, so two concurrent borrowers would
    consume each other's buffers); :meth:`borrow_cache` blocks until the
    previous borrower returns.  The accounting half (allocate/extend/free/
    occupancy) is lock-protected and usable standalone — sizing studies
    never have to build device slabs.

    Per-slot state: a module some of whose layers keep a fixed-size state
    a SLOT instead of pages (it has ``init_slot_state(rows)``: a window
    layer's ring of positions, a linear-attention layer's recurrent state
    and conv history) gets that state from this pool too, built for the
    borrower's row count and handed out and returned WITH the slabs as
    ``(slabs, slot state)``; row ``r`` is slot ``r``'s and the last row is
    the trash row.  Pages keep their meaning: capacity, occupancy,
    ``page_nbytes`` and the ``mmlspark_runner_page_*`` series count pages
    of the paged layers only; the slot state's bytes are
    :meth:`slot_state_nbytes` and ``mmlspark_runner_slot_state_bytes``.  It
    needs no allocation, no free and no zeroing: whoever holds a slot
    rewrites its row, a ring's rows are read by position alone, and a
    recurrent layer starts from zero where a chunk's first position is 0.
    """

    #: booking ops — each books pages moved, not call count ("denied"
    #: books pages REFUSED: the admission-control outcome, ISSUE 13).
    #: "pin" books refcount increments on shared pages (prefix hits),
    #: "cow" books private copies minted by copy-on-write splits (ISSUE 20)
    OPS = ("allocate", "extend", "free", "denied", "pin", "cow")

    def __init__(self, module=None, num_pages: int = 0, page_size: int = 64,
                 *, name: str = "pool", registry=None,
                 clock: Callable[[], float] = time.monotonic):
        if num_pages < 2:
            raise ValueError(f"num_pages {num_pages} < 2: page 0 is the "
                             "reserved trash page, so a usable pool needs "
                             "at least one allocatable page")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.module = module
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._name = name
        #: free physical pages; page 0 (trash) is never in this list
        self._free = list(range(self.num_pages - 1, 0, -1))
        #: per-page refcounts (ISSUE 20): a page is on exactly one side —
        #: in ``_free`` with no entry here, or held with refcount >= 1.
        #: ``free()`` decrements and only returns the page at zero, so a
        #: prefix-shared page survives any one holder's release
        self._ref: Dict[int, int] = {}
        #: the prefix index retaining pages in this pool, if any (set by
        #: ``ModelRunner.prefix_cache``); ``resized()`` flushes it so a
        #: successor pool can never be handed a dangling page id
        self.prefix_index = None
        self._cond = make_condition("PagePool._cond")
        self._cache = None          # built lazily, rebuilt if dropped
        self._cache_nbytes = 0
        #: rows (slots) the slot state in ``_cache`` was built for, and
        #: its bytes; 0 for a module without ``init_slot_state``
        self._state_rows = 0
        self._state_nbytes = 0
        self._borrowed = False
        self.high_water = 0
        #: True when the owning runner sized this pool implicitly (from a
        #: decode's worst case) — such pools may be grown for a larger
        #: batch; an explicitly budgeted pool is never resized behind the
        #: caller's back
        self.auto_sized = False
        from ..observability import get_registry
        reg = registry if registry is not None else get_registry()
        self._registry = reg
        # page_size is in the label set because one runner keeps a pool
        # PER page size — without it the pools would stomp one another's
        # occupancy series
        ops = reg.counter(
            "mmlspark_runner_page_ops_total",
            "KV page-pool pages moved by op (allocate/extend/free)",
            labels=("runner", "page_size", "op"))
        self._c_ops = {op: ops.labels(runner=name,
                                      page_size=str(self.page_size), op=op)
                       for op in self.OPS}
        self._g_used = reg.gauge(
            "mmlspark_runner_page_pool_used_pages",
            "KV pages currently held by live sequences",
            labels=("runner", "page_size"))
        self._g_hw = reg.gauge(
            "mmlspark_runner_page_pool_high_water_pages",
            "max KV pages ever simultaneously held",
            labels=("runner", "page_size"))
        self._g_state = reg.gauge(
            "mmlspark_runner_slot_state_bytes",
            "device bytes of the per-slot state (window rings, recurrent "
            "state) kept beside the pages (0 for a module all of whose "
            "layers are paged)", labels=("runner", "page_size"))
        # page-seconds integral (ISSUE 17): pages held x wall time,
        # integrated exactly at the alloc/extend/free edges — the memory
        # half of the per-request cost ledger, and the pool-level total
        # the per-request integrals must sum to
        self._clock = clock
        self._page_seconds = 0.0
        self._t_integral = self._clock()
        self._c_pagesec = reg.counter(
            "mmlspark_runner_page_seconds_total",
            "KV page-seconds consumed (pages held x wall time, integrated "
            "at pool-op edges)", labels=("runner", "page_size")).labels(
                runner=name, page_size=str(self.page_size))
        self._book("allocate", 0)   # gauges live from construction

    # ---------------------------------------------------------- accounting
    @property
    def capacity(self) -> int:
        """Allocatable pages (the trash page is not allocatable)."""
        return self.num_pages - 1

    def token_capacity(self) -> int:
        """Total token slots the pool can hold across all sequences."""
        return self.capacity * self.page_size

    def pages_in_use(self) -> int:
        return self.capacity - len(self._free)

    def occupancy_pct(self) -> float:
        return 100.0 * self.pages_in_use() / max(self.capacity, 1)

    def _integrate_locked(self) -> None:
        """Advance the page-seconds integral to now (called under the pool
        lock, BEFORE the free-list mutation — the interval just ended was
        held at the pre-edge page count)."""
        now = self._clock()
        delta = self.pages_in_use() * max(0.0, now - self._t_integral)
        self._t_integral = now
        if delta > 0:
            self._page_seconds += delta
            self._c_pagesec.inc(delta)

    def page_seconds(self) -> float:
        """Cumulative pages-held x wall-time integral, current to now."""
        with self._cond:
            self._integrate_locked()
            return self._page_seconds

    def _book(self, op: str, n: int) -> None:
        """Book one pool operation: the op counter plus the occupancy and
        high-water gauges (called under the pool lock)."""
        used = self.pages_in_use()
        if used > self.high_water:
            self.high_water = used
        self._c_ops[op].inc(n)
        ps = str(self.page_size)
        self._g_used.set(float(used), runner=self._name, page_size=ps)
        self._g_hw.set(float(self.high_water), runner=self._name,
                       page_size=ps)

    def allocate(self, n: int, op: str = "allocate", shared=None):
        """Hand out ``n`` fresh pages (prefill sizing: ``ceil(true_len /
        page_size)`` per sequence).  ``shared`` (ISSUE 20) names already-
        resident pages to PIN instead of copy — each gains a refcount and
        rides ahead of the fresh pages in the returned list, so a prefix
        hit allocates only its suffix.  Atomic: a refused fresh allocation
        unpins ``shared`` before raising.  Raises when the budget is
        exhausted — admission control, not silent overcommit."""
        shared = [int(p) for p in shared] if shared else []
        with self._cond:
            self._integrate_locked()
            if n > len(self._free):
                # book the refusal before raising: the denied outcome is
                # the admission-control signal dashboards alert on
                self._book("denied", n)
                raise PagePoolExhausted(
                    f"page pool exhausted: need {n} page(s), "
                    f"{len(self._free)} free of {self.capacity} "
                    f"(page_size={self.page_size}) — free finished "
                    "sequences, shrink the batch, or size the pool larger")
            if shared:
                self._pin_locked(shared)
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._ref[p] = 1
            self._book(op, n)
            return shared + pages

    def extend(self, n: int = 1):
        """Allocate at a decode page-boundary crossing (same free list,
        booked as ``op="extend"`` so growth is attributable)."""
        return self.allocate(n, op="extend")

    def _pin_locked(self, pages) -> None:
        for p in pages:
            r = self._ref.get(p)
            if r is None:
                raise ValueError(f"pin of page {p} which is not allocated")
            self._ref[p] = r + 1
        self._book("pin", len(pages))

    def pin(self, pages) -> None:
        """Add a reference to already-resident pages (prefix-cache hit):
        the pinned pages are shared, and ``free()`` from any one holder
        only drops that holder's reference."""
        pages = [int(p) for p in pages]
        with self._cond:
            self._integrate_locked()
            self._pin_locked(pages)

    def refcount(self, page: int) -> int:
        """Current reference count of ``page`` (0 when free)."""
        with self._cond:
            return self._ref.get(int(page), 0)

    def shortfall(self, n: int) -> int:
        """Free-list deficit for an ``n``-page allocation (0 when it would
        succeed) — no booking, no side effects.  Callers use it to evict
        refcount-0 prefix retentions BEFORE an allocate, keeping the
        index-lock -> pool-lock order deadlock-free."""
        with self._cond:
            return max(0, int(n) - len(self._free))

    def free(self, pages) -> None:
        """Drop one reference per page (eos/completion); a page returns to
        the free list only at refcount zero, so freeing a prefix-shared
        page never yanks it from the other holders.  Freed pages are not
        zeroed: stale k/v in a reused page sits past the new owner's
        frontier until overwritten, so it is never admissible."""
        pages = [int(p) for p in pages]
        if any(p <= 0 or p >= self.num_pages for p in pages):
            raise ValueError(f"free() of invalid page in {pages} "
                             "(page 0 is the reserved trash page)")
        with self._cond:
            self._integrate_locked()
            for p in pages:
                r = self._ref.get(p)
                if r is None:
                    raise ValueError(f"double free of page {p}")
                if r > 1:
                    self._ref[p] = r - 1
                else:
                    del self._ref[p]
                    self._free.append(p)
            self._book("free", len(pages))

    # ------------------------------------------------------- device slabs
    def page_nbytes(self) -> int:
        """Device bytes per page across all layers (0 until slabs built)."""
        return self._cache_nbytes // self.num_pages if self._cache_nbytes \
            else 0

    def slot_state_nbytes(self) -> int:
        """Device bytes of the per-slot state (0 until built, and for a
        module that keeps none): fixed by the borrower's rows, whatever the
        pool's pages."""
        return self._state_nbytes

    #: the name the window cell's benchmark family reads
    window_nbytes = slot_state_nbytes

    def borrow_cache(self, state_rows: int = 0):
        """Take exclusive ownership of the device slabs (building them on
        first use), blocking while another decode holds them — the step
        executables donate the buffers, so exactly one loop may own them.
        For a module with ``init_slot_state`` the result is ``(slabs,
        slot state)``, the state built for ``state_rows`` sequences (the
        borrower's slots); it is rebuilt when the row count changes, and
        what it held is nobody's: no request outlives a borrow.  A borrower
        that names no rows (one that only drops the slabs) gets the state as
        it stands, ``None`` if none was built."""
        if self.module is None:
            raise TypeError("this PagePool was built without a module — "
                            "accounting only, no device slabs")
        with self._cond:
            while self._borrowed:
                self._cond.wait()
            self._borrowed = True
            cache = self._cache
            self._cache = None
        stateful = _keeps_slot_state(self.module)
        try:
            import jax

            def nbytes(tree):
                return sum(int(l.nbytes)
                           for l in jax.tree_util.tree_leaves(tree))
            if cache is None:
                slabs = self.module.init_paged_cache(self.num_pages,
                                                     self.page_size)
                self._cache_nbytes = nbytes(slabs)
                cache = (slabs, None) if stateful else slabs
                self._state_rows = 0
            if stateful and state_rows \
                    and self._state_rows != int(state_rows):
                state = self.module.init_slot_state(int(state_rows))
                cache = (cache[0], state)
                self._state_rows = int(state_rows)
                self._state_nbytes = nbytes(state)
                self._g_state.set(float(self._state_nbytes),
                                  runner=self._name,
                                  page_size=str(self.page_size))
        except Exception:
            # a failed slab build (HBM exhaustion) must not leave the
            # pool borrowed forever — every later borrower would block
            self.return_cache(None)
            raise
        return cache

    def resized(self, num_pages: int) -> "PagePool":
        """A fresh pool with the same module/page size/metric identity but
        ``num_pages`` pages.  Refuses while sequences hold pages or a
        decode holds the slabs — resizing would orphan them.

        A prefix index retaining pages here is FLUSHED first (booked
        ``evicted{reason="pool_replaced"}``) and rebound to the successor:
        its entries name physical page ids of THIS pool's slabs, and an
        index surviving a resize un-flushed would hand those ids out
        against the replacement's slabs — freed-page aliasing (ISSUE 20
        regression)."""
        idx = self.prefix_index
        if idx is not None:
            # outside the pool lock: flush frees pages back through
            # free(), which takes it (index-lock -> pool-lock order)
            idx.flush(reason="pool_replaced")
        with self._cond:
            if self._borrowed or self.pages_in_use():
                raise RuntimeError(
                    f"cannot resize a busy page pool ({self.pages_in_use()} "
                    "page(s) held, borrowed="
                    f"{self._borrowed}) — wait for in-flight decodes")
        pool = PagePool(self.module, num_pages, self.page_size,
                        name=self._name, registry=self._registry,
                        clock=self._clock)
        pool.auto_sized = self.auto_sized
        if idx is not None:
            idx.rebind(pool)
            pool.prefix_index = idx
            self.prefix_index = None
        return pool

    def return_cache(self, cache) -> None:
        """Give the slabs back (pass ``None`` after a failed loop — the
        donated buffer state is unknown, so the next borrower rebuilds)."""
        with self._cond:
            self._borrowed = False
            self._cache = cache
            self._cond.notify()


class ModelRunner:
    """Compile-once execution cache + batch/serving/decode fronts.

    Accepts any of:

    - ``payload`` — an object exposing ``pure_apply`` / ``variables`` (and
      optionally ``module``): ``FlaxModelPayload``, ``OnnxModelPayload``;
    - ``module=`` + ``variables=`` — a flax module (resnet, transformer,
      bilstm); ``apply_kwargs`` forward to ``module.apply``;
    - ``apply_fn=`` + ``variables=`` — a raw pure ``(variables, batch)``
      callable.

    ``name`` labels every metric series and compile-report entry this
    runner books — keep it low-cardinality (a model family, not a uid).
    """

    #: sampled block_until_ready cadence for the decode dispatch/device
    #: split (the PR 6 Trainer pattern brought to the decode hot loop):
    #: every Nth step pays one forced sync so the device-time series costs
    #: 1/N of the async overlap; 0 disables the device phase entirely
    DEVICE_TIME_EVERY_DEFAULT = 32

    def __init__(self, payload=None, *, module=None, variables=None,
                 apply_fn: Optional[Callable] = None,
                 apply_kwargs: Optional[Dict[str, Any]] = None,
                 name: str = "model", batch_size: int = 64,
                 registry=None, device_time_every: Optional[int] = None):
        if payload is not None:
            self._pure = payload.pure_apply
            self.variables = payload.variables
            self.module = getattr(payload, "module", None)
        elif apply_fn is not None:
            self._pure = apply_fn
            self.variables = variables
            self.module = module
        elif module is not None:
            kw = dict(apply_kwargs or {})

            def _pure(vs, batch, _m=module, _kw=kw):
                return _m.apply(vs, batch, **_kw)

            self._pure = _pure
            self.variables = variables
            self.module = module
        else:
            raise ValueError("need a payload, a module, or an apply_fn")
        self.name = name
        self.batch_size = int(batch_size)
        from ..observability import get_registry
        self.registry = registry if registry is not None else get_registry()
        #: (kind, device_key, *shape) -> executable; every entry lowered once
        self._executables: Dict[Tuple, Callable] = {}
        #: name -> InstrumentedJit wrappers this runner created (compile
        #: introspection for tests and compile_stats)
        self._wrappers: list = []
        self._lock = make_lock("ModelRunner._lock")
        reg = self.registry
        c_batches = reg.counter(
            "mmlspark_runner_batches_total",
            "device dispatches per runner by front",
            labels=("runner", "front"))
        c_rows = reg.counter(
            "mmlspark_runner_rows_total",
            "real (unpadded) rows scored per runner by front",
            labels=("runner", "front"))
        self._c_batches = {f: c_batches.labels(runner=name, front=f)
                          for f in FRONTS}
        self._c_rows = {f: c_rows.labels(runner=name, front=f)
                        for f in FRONTS}
        c_input_bytes = reg.counter(
            "mmlspark_runner_input_bytes_total",
            "bytes of the padded input chunks handed to the batch executable",
            labels=("runner", "front"))
        self._c_input_bytes = {f: c_input_bytes.labels(runner=name, front=f)
                               for f in FRONTS}
        c_staged = reg.counter(
            "mmlspark_runner_staged_chunks_total",
            "chunks of a row source stacked into host staging buffers, by "
            "whether the buffer was the runner's kept pair or a fresh one",
            labels=("runner", "buffer"))
        self._c_staged = {b: c_staged.labels(runner=name, buffer=b)
                          for b in ("reused", "fresh")}
        from ..observability.tracing import LapClock
        h_batch = reg.histogram(
            "mmlspark_runner_batch_phase_seconds",
            "apply_batch's laps on the caller's thread: stage, dispatch, "
            "wait, fetch of a chunk and the final concat",
            labels=("runner", "front", "phase"))
        self._batch_laps = {
            f: LapClock("batch", "runner.apply_batch",
                        {p: h_batch.labels(runner=name, front=f, phase=p)
                         for p in BATCH_LAPS}, reg) for f in FRONTS}
        #: the idle pair of host staging buffers (``batch_size`` rows of
        #: the last row shape and dtype staged); None while a call has it
        #: checked out, and before the first row source
        self._staging: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._c_pad = reg.counter(
            "mmlspark_runner_pad_rows_total",
            "padding rows added by bucketing (wasted device work)",
            labels=("runner",)).labels(runner=name)
        self._c_decode_steps = reg.counter(
            "mmlspark_runner_decode_steps_total",
            "single-token decode-step dispatches",
            labels=("runner",)).labels(runner=name)
        self._c_decode_tokens = reg.counter(
            "mmlspark_runner_decode_tokens_total",
            "per-sequence real generated tokens (unfrozen steps only; "
            "eos-frozen tails and pad rows are not generated work)",
            labels=("runner",)).labels(runner=name)
        # the continuous engine's one step in flight (ISSUE 37): how often
        # a step was dispatched beside the one before it, and the rows that
        # costs (learnt too late that they had nothing left to compute)
        self._c_steps_overlapped = reg.counter(
            "mmlspark_runner_decode_steps_overlapped_total",
            "continuous-decode steps dispatched while the step before them "
            "was still unfetched (the engine thread's one step in flight)",
            labels=("runner",)).labels(runner=name)
        self._c_stale_rows = reg.counter(
            "mmlspark_runner_decode_stale_rows_total",
            "continuous-decode step rows computed for nothing: retired "
            "against a handle that had left, or stepped after their last "
            "token", labels=("runner",)).labels(runner=name)
        # decode-loop dispatch/device split (ISSUE 15): dispatch = host
        # time to enqueue each step program, device = sampled
        # block_until_ready wait every device_time_every steps — the
        # numbers that prove (or refute) "dispatch-bound"
        if device_time_every is None:
            device_time_every = self.DEVICE_TIME_EVERY_DEFAULT
        self.device_time_every = max(0, int(device_time_every))
        h_phase = reg.histogram(
            "mmlspark_runner_decode_phase_seconds",
            "decode-step breakdown: dispatch (host enqueue) vs device "
            "(sampled block_until_ready wait), and the other laps of the "
            "continuous engine's round", labels=("runner", "phase"))
        self._h_phase_dispatch = h_phase.labels(runner=name,
                                                phase="dispatch")
        self._h_phase_device = h_phase.labels(runner=name, phase="device")
        # the continuous engine's lap clock (ISSUE 39): every driver of a
        # ContinuousDecoder laps through it, each thread its own phase
        self._decode_laps = LapClock(
            "decode", "runner.decode.step",
            {p: h_phase.labels(runner=name, phase=p) for p in DECODE_LAPS},
            reg)
        # page-pool surface (paged decode): families registered at
        # construction so the telemetry-coverage sweep gates on them even
        # for runners that never decode; PagePool binds the children
        # (page_size in the labels: one runner keeps a pool per page size)
        reg.counter("mmlspark_runner_page_ops_total",
                    "KV page-pool pages moved by op (allocate/extend/free)",
                    labels=("runner", "page_size", "op"))
        reg.gauge("mmlspark_runner_page_pool_used_pages",
                  "KV pages currently held by live sequences",
                  labels=("runner", "page_size"))
        reg.gauge("mmlspark_runner_page_pool_high_water_pages",
                  "max KV pages ever simultaneously held",
                  labels=("runner", "page_size"))
        reg.counter("mmlspark_runner_page_seconds_total",
                    "KV page-seconds consumed (pages held x wall time, "
                    "integrated at pool-op edges)",
                    labels=("runner", "page_size"))
        # continuous-engine surface (ISSUE 13): families registered at
        # construction so the telemetry sweep gates on them even for
        # runners that never open a decode stream; ContinuousDecoder binds
        # the children
        reg.counter("mmlspark_runner_slots_joined_total",
                    "requests spliced into the in-flight decode batch",
                    labels=("runner",))
        reg.counter("mmlspark_runner_slots_left_total",
                    "slots released by outcome (ok/denied/expired/cancelled)",
                    labels=("runner", "outcome"))
        reg.gauge("mmlspark_runner_slot_occupancy_pct",
                  "reserved+live decode slots as % of the in-flight bucket",
                  labels=("runner",))
        reg.histogram("mmlspark_runner_ttft_seconds",
                      "submit-to-first-token latency of continuous decode",
                      labels=("runner",))
        # long-prompt joins and routed models (ISSUE 34): chunks a join's
        # prefill took, its prompt tokens by where their k/v came from, and
        # the distinct experts a decode step's tokens were routed to
        self._c_prefill_chunks = reg.counter(
            "mmlspark_runner_prefill_chunks_total",
            "prefill dispatches of continuous-decode joins (one a "
            "prompt_bucket chunk of the uncovered prompt)",
            labels=("runner",)).labels(runner=name)
        c_prefill_tokens = reg.counter(
            "mmlspark_runner_prefill_tokens_total",
            "prompt tokens of continuous-decode joins: computed by the "
            "join's prefill, or cached (covered by the prefix index)",
            labels=("runner", "source"))
        self._c_prefill_tokens = {
            src: c_prefill_tokens.labels(runner=name, source=src)
            for src in ("computed", "cached")}
        self._c_experts_touched = reg.counter(
            "mmlspark_runner_moe_experts_touched_total",
            "distinct experts with at least one token, summed over the "
            "layers and the decode steps of a routed model",
            labels=("runner",)).labels(runner=name)
        self._c_local_assignments = reg.counter(
            "mmlspark_runner_moe_local_assignments_total",
            "token-expert assignments that landed on an expert held here, "
            "summed over the layers and the decode steps of a routed model "
            "that holds a share of its experts",
            labels=("runner",)).labels(runner=name)
        # tail-tolerance surface (ISSUE 16): stall + supervised-restart
        # families registered at construction so the telemetry sweep gates
        # on them even for runners that never stall; the stall watchdog
        # and the scorer's restart supervisor bind/book the children
        self._c_stalls = reg.counter(
            "mmlspark_runner_stalls_total",
            "device dispatches that exceeded the stall watchdog timeout",
            labels=("runner",)).labels(runner=name)
        reg.counter(
            "mmlspark_engine_restarts_total",
            "supervised decode-engine rebuilds after an abort/stall",
            labels=("runner",))
        # goodput/cost-attribution surface (ISSUE 17): the useful-vs-
        # wasted token ledger plus the amortized device-seconds counter —
        # all host-side accounting, never a compile key
        from ..observability.attribution import attribution_instruments
        _att = attribution_instruments(reg)
        self._c_tok_outcome = _att["tokens"]
        self._c_device_s = _att["device"]
        # prefix-cache surface (ISSUE 20): hit/miss/eviction/CoW counters,
        # saved-prefill tokens, and the hit-rate / retained-pages gauges —
        # registered at construction so the telemetry-coverage sweep gates
        # on them even for runners that never enable the cache;
        # PrefixIndex binds the children
        from .prefix_cache import prefix_instruments
        prefix_instruments(reg)
        #: (device key, page size) -> shared PagePool for paged decode
        self._pools: Dict[Tuple, PagePool] = {}
        #: resolved geometry of the most recent decode (DecodeResult.extras)
        self.last_decode_extras: Optional[Dict[str, Any]] = None
        # flight-recorder roster (ISSUE 15): the postmortem dump walks the
        # registry's live runners for their last decode geometry — a
        # WeakSet, so enrolment never pins a discarded runner
        from ..observability.flightrecorder import _roster
        _roster(reg, "_model_runners").add(self)

    # ------------------------------------------------------------- lowering
    @staticmethod
    def _device_key() -> Tuple:
        """The local device set the executables are specialized to; a mesh
        change (tests swapping in mesh8, a late-attached accelerator)
        re-keys instead of serving a stale placement."""
        from ..parallel import get_active_mesh
        mesh = get_active_mesh()
        return tuple(int(d.id) for d in mesh.devices.flat)

    def _instrumented(self, fn: Callable, suffix: str = "", **jit_kwargs):
        from ..observability.compute import instrumented_jit
        wrapper = instrumented_jit(
            fn, name=f"runner.{self.name}{suffix}",
            registry=self.registry, **jit_kwargs)
        self._wrappers.append(wrapper)
        return wrapper

    def executable(self, bucket_n: int, feat_shape: Tuple[int, ...]):
        """The compiled apply for one (device set, bucketed batch shape) —
        built on first use, a dict hit forever after.  Multi-device meshes
        shard the batch dim over ``data`` with params replicated (inference
        DP); multi-host processes stage their host-local batch as a global
        array explicitly (jit refuses host-local numpy for non-replicated
        shardings; every process holds the SAME batch under the executor
        model — identical partition per call)."""
        key = ("apply", self._device_key(), int(bucket_n), tuple(feat_shape))
        fn = self._executables.get(key)
        if fn is not None:
            return fn
        with self._lock:
            fn = self._executables.get(key)
            if fn is not None:
                return fn
            import jax
            from ..parallel import batch_sharded, get_active_mesh, replicated
            mesh = get_active_mesh()
            n_dev = mesh.devices.size
            if n_dev > 1 and bucket_n % n_dev == 0:
                sharded = self._instrumented(
                    self._pure,
                    in_shardings=(replicated(mesh), batch_sharded(mesh)),
                    out_shardings=replicated(mesh))
                if jax.process_count() > 1:
                    bsh = batch_sharded(mesh)

                    def fn(variables, chunk, _inner=sharded, _s=bsh):
                        garr = jax.make_array_from_callback(
                            chunk.shape, _s, lambda idx: chunk[idx])
                        return _inner(variables, garr)
                else:
                    fn = sharded
            else:
                fn = self._instrumented(self._pure)
            self._executables[key] = fn
        return fn

    def compile_stats(self) -> Dict[str, Any]:
        """Introspection for tests and ops: executables cached by key plus
        the underlying compile count (one per signature by contract)."""
        return {
            "executables": sorted(
                "/".join(str(p) for p in k) for k in self._executables),
            "compiles": sum(getattr(w, "compiles", 0)
                            for w in self._wrappers),
        }

    # ------------------------------------------------------------ batch front
    @contextmanager
    def _staging_pair(self, rows: int, row_shape: Tuple[int, ...], dtype):
        """Check the two host staging buffers out for one call: the kept
        pair when it is idle and fits (``"reused"``), else a fresh one
        (``"fresh"``: the first call, another row shape or dtype, or a
        second thread while the pair is out; nobody waits).  The pair this
        call used is the one kept afterwards."""
        with self._lock:
            pair, self._staging = self._staging, None
        label = "reused"
        if pair is None or pair[0].shape[1:] != row_shape \
                or pair[0].dtype != dtype or pair[0].shape[0] < rows:
            pair = (np.empty((rows, *row_shape), dtype),
                    np.empty((rows, *row_shape), dtype))
            label = "fresh"
        try:
            yield pair, label
        finally:
            with self._lock:
                self._staging = pair

    def apply_batch(self, x, front: str = "transform",
                    batch_size: Optional[int] = None) -> np.ndarray:
        """Score a host batch of any row count: chunk to ``batch_size``,
        pad each chunk to its power-of-two bucket, run the cached
        executable, unpad, concatenate.  This is the ONE copy of the
        pad/bucket glue the per-model transformers used to hand-roll.

        ``x`` is a dense ``ndarray``, sliced as it is, or a
        :class:`RowSource`, which this loop stacks itself, chunk by chunk,
        into two host staging buffers the runner keeps between calls
        (2 x ``batch_size`` rows of the last row shape and dtype staged).

        The loop is a two-deep pipeline: a chunk is dispatched and NOT
        waited for; the next one is staged and dispatched while the device
        scores it; the output of chunk ``k - 2`` is fetched just before
        chunk ``k`` is staged.  So at most two chunks are in flight, and a
        staging buffer is refilled only after the OUTPUT of the program
        that read it is ready: that proves the input consumed, whether the
        backend was still uploading from the host buffer (TPU) or aliasing
        it without a copy (CPU).  A one-chunk call is stage, dispatch,
        fetch."""
        bs = int(batch_size or self.batch_size)
        n = x.shape[0]
        if n == 0:
            return np.empty((0,), dtype=np.float32)
        variables = self.variables
        outs = []
        pad_total = 0
        #: (device output, real rows) of the dispatched chunks, oldest first
        in_flight: deque = deque()
        laps = self._batch_laps[front]
        lap = laps.lap

        def retire():
            # the wait for the oldest chunk's output, ALL of it (a fetch
            # alone reads one replica of a sharded program's), and its fetch
            y, rows = in_flight.popleft()
            lap("wait")
            y.block_until_ready()
            lap("fetch")
            outs.append(np.asarray(y)[:rows])

        staging = nullcontext((None, None)) if isinstance(x, np.ndarray) \
            else self._staging_pair(bs, tuple(x.shape[1:]), x.dtype)
        with staging as (pair, label):
            try:
                for k, start in enumerate(range(0, n, bs)):
                    m = min(bs, n - start)
                    bucket = bucket_rows(m, bs)
                    pad_total += bucket - m
                    if len(in_flight) == 2:
                        retire()
                    lap("stage")
                    if pair is None:
                        chunk = _pad_rows(x[start:start + m], bucket)
                    else:
                        chunk = pair[k % 2][:bucket]
                        x.fill(chunk, start, start + m)
                        chunk[m:] = chunk[m - 1]
                        self._c_staged[label].inc()
                    lap("dispatch")
                    fn = self.executable(bucket, chunk.shape[1:])
                    y = fn(variables, chunk)
                    # queued behind the program now: the fetch in retire()
                    # finds the copy done or under way, not still to be
                    # asked for after a wait
                    y.copy_to_host_async()
                    in_flight.append((y, m))
                    self._c_batches[front].inc()
                    self._c_input_bytes[front].inc(chunk.nbytes)
                while in_flight:
                    retire()
                lap("concat")
                out = np.concatenate(outs, axis=0)
            finally:
                laps.stop()
                # after an error too, no buffer goes back while a program
                # may still be reading it
                for y, _ in in_flight:
                    y.block_until_ready()
        self._c_rows[front].inc(n)
        if pad_total:
            self._c_pad.inc(pad_total)
        return out

    # ---------------------------------------------------------- serving front
    def scorer(self, input_col: str = "request", reply_col: str = "reply",
               prepare: Optional[Callable] = None,
               encode: Optional[Callable] = None,
               mode: str = "score", continuous: bool = False,
               report_ttft: bool = False, supervisor=None,
               **decode_kwargs) -> "Transformer":
        """A ``Transformer`` front for ``PipelineServer`` / the streaming
        facade.  ``mode="score"`` stacks request rows (via ``prepare``,
        default ``np.asarray(..., float32)``) and scores them through
        :meth:`apply_batch`; ``mode="decode"`` treats each request as a
        token-id prompt and returns generated token lists from
        :meth:`decode` (``decode_kwargs`` forward — ``max_new_tokens=``,
        ``eos_id=``, and the cache layout: ``kv_layout="paged"`` with
        ``page_size=``/``pool=`` serves the drain from shared page-pool
        HBM by actual sequence length, instead of the dense per-sequence
        max-length reservation; the resolved geometry rides
        ``DecodeResult.extras`` / ``runner.last_decode_extras`` so
        ``mixed_load``'s decode class can report tokens/sec against it).
        The server's continuous-mode drain is the admission window:
        whatever is in flight when the scorer runs becomes ONE bucketed
        device batch.

        ``continuous=True`` (decode mode only, ISSUE 13) upgrades the drain
        from batch ticks to SLOT-level continuous batching: the scorer owns
        a :class:`ContinuousDecoder` (``decode_kwargs`` become
        :meth:`decode_stream` kwargs — ``slots=``, ``prompt_bucket=``,
        ``max_new_tokens=``, ``eos_id=``, ``page_size=``, ``pool=``) and
        exposes ``continuous_submit`` so ``PipelineServer``/the streaming
        facade admit each request into a free slot of the in-flight batch
        the moment it is drained — no flush tick, and a finished sequence
        replies while the batch keeps decoding.  Admission failure (no free
        slot, page pool exhausted) sheds with 503 + Retry-After.
        ``supervisor`` (continuous only, ISSUE 16) overrides the default
        :class:`~mmlspark_tpu.utils.resilience.RestartSupervisor` gating
        engine rebuilds (backoff/quarantine policy, injectable clock).
        ``report_ttft=True`` wraps decode replies as ``{"tokens",
        "ttft_ms"}`` — the in-band first-token latency ``mixed_load``'s
        ``ttft_p99_ms`` gate reads (for the ticked drain there is no
        client-visible token before the batch resolves, so its honest TTFT
        is the full latency)."""
        if mode not in ("score", "decode"):
            raise ValueError("scorer mode must be score|decode")
        return _RunnerScorer(self, input_col, reply_col, prepare, encode,
                             mode, decode_kwargs, continuous=continuous,
                             report_ttft=report_ttft, supervisor=supervisor)

    # ------------------------------------------------------------ decode front
    def page_pool(self, page_size: int = 64,
                  num_pages: Optional[int] = None) -> Optional["PagePool"]:
        """The runner's shared :class:`PagePool` for ``page_size`` —
        created on first use (sized by ``num_pages``; a paged decode
        without an explicit pool sizes it to its own worst case and grows
        it for larger batches) and reused by every later paged decode at
        this page size, so the occupancy/high-water gauges describe the
        shared cache HBM, not one call.  Passing ``num_pages`` when a pool
        already exists RESIZES it (the explicit-budget escape hatch;
        raises while sequences hold pages).  Returns ``None`` when no pool
        exists yet and ``num_pages`` was not given."""
        key = (self._device_key(), int(page_size))
        with self._lock:
            pool = self._pools.get(key)
            if num_pages is not None:
                if pool is None:
                    pool = self._pools[key] = PagePool(
                        self.module, num_pages, page_size, name=self.name,
                        registry=self.registry)
                elif pool.num_pages != int(num_pages):
                    pool = self._pools[key] = pool.resized(int(num_pages))
                pool.auto_sized = False
            return pool

    def _auto_pool(self, page_size: int, need_pages: int) -> PagePool:
        """The implicit pool for a paged decode that brought no budget:
        create at this call's worst case, or GROW an earlier auto-sized
        pool that a larger batch has outrun (an explicitly budgeted pool
        is never resized — its exhaustion is admission control).  Growth
        is best-effort: if another decode holds pages right now, the
        existing pool serves and may legitimately run out."""
        key = (self._device_key(), int(page_size))
        with self._lock:
            pool = self._pools.get(key)
            if pool is None:
                pool = self._pools[key] = PagePool(
                    self.module, need_pages, page_size, name=self.name,
                    registry=self.registry)
                pool.auto_sized = True
            elif pool.auto_sized and pool.num_pages < need_pages:
                try:
                    pool = self._pools[key] = pool.resized(need_pages)
                except RuntimeError:
                    pass                      # busy: keep the current pool
            return pool

    def prefix_cache(self, page_size: int = 64, *,
                     budget_pages: int = 64, pool: Optional[PagePool] = None):
        """Get-or-create the :class:`~.prefix_cache.PrefixIndex` attached
        to ``pool`` (default: the runner's shared pool for ``page_size``,
        created minimal if absent — a later decode grows it).  The index
        rides the pool (``pool.prefix_index``), so ``resized()`` /
        auto-grow flush-and-rebind it in one place.  ``budget_pages``
        applies only at creation; call this before the first cached decode
        to size the retention budget."""
        from .prefix_cache import PrefixIndex
        if pool is None:
            pool = self._auto_pool(int(page_size), 2)
        if pool.prefix_index is None:
            pool.prefix_index = PrefixIndex(
                pool, budget_pages=budget_pages, name=self.name,
                registry=self.registry)
        return pool.prefix_index

    @staticmethod
    def _alloc_with_reclaim(pool: PagePool, index, n: int,
                            op: str = "allocate", shared=None):
        """``pool.allocate`` with one prefix-eviction retry: under pool
        pressure the index's refcount-0 retentions are reclaimable memory,
        evicted LRU (``reason="pressure"``) BEFORE the allocation is
        denied.  Caller-level so the lock order stays index -> pool."""
        if index is not None and n > 0:
            short = pool.shortfall(n)
            if short:
                index.evict_pages(short, reason="pressure")
        return pool.allocate(n, op=op, shared=shared)

    def _cow_executable(self):
        """Device-side page copy for copy-on-write splits: clone one
        physical page's rows in EVERY slab of the cache pytree (k and v of
        every layer, and whatever else the module pages beside them) from
        ``src`` into ``dst``.
        src/dst are traced scalars and the slabs are donated, so the copy
        is in-place-update-shaped and mints no per-page compile keys (one
        executable per pool geometry)."""
        key = ("cow_copy", self._device_key())
        fn = self._executables.get(key)
        if fn is not None:
            return fn
        with self._lock:
            fn = self._executables.get(key)
            if fn is None:
                import jax

                def _cow(cache, src, dst):
                    return jax.tree_util.tree_map(
                        lambda slab: slab.at[dst].set(slab[src]), cache)

                fn = self._executables[key] = self._instrumented(
                    _cow, suffix=".cow_copy", donate_argnums=(0,))
        return fn

    def _cow_split_page(self, pool: PagePool, index, cache, donor: int):
        """Split a shared page before a divergent write lands on it: mint
        a private copy (device page clone), drop the caller's reference on
        the donor, book the split.  Returns ``(cache, new_page)`` — the
        caller updates its page-table row and pages list.  Raises
        :class:`PagePoolExhausted` (after a pressure-eviction retry) when
        no page is mintable — the caller sheds the row like any other
        mid-flight denial."""
        import jax.numpy as jnp
        new_page = self._alloc_with_reclaim(pool, index, 1, op="cow")[0]
        cache = self._cow_executable()(cache, jnp.int32(donor),
                                       jnp.int32(new_page))
        pool.free([donor])
        if index is not None:
            index.book_cow()
        return cache, new_page

    def _decode_executables(self, batch_b: int, prompt_b: int,
                            cache_len: Optional[int] = None, *,
                            page_size: Optional[int] = None,
                            table_w: Optional[int] = None,
                            fused: bool = False,
                            eos_id: Optional[int] = None):
        """(prefill, step) executables for one decode signature.

        Dense: prefill keys on (batch bucket, prompt bucket, cache length),
        the step on (batch bucket, cache length) only.  Paged: prefill keys
        on (batch bucket, prompt bucket, page size, table width) and the
        step on (batch bucket, page size, table width) — cache LENGTH is no
        longer a compile key, so decode signatures that differ only in
        reservation collapse onto one step executable.  Either way the
        step's input shapes are constant across the whole generation loop:
        EVERY token of EVERY request at the signature re-dispatches one
        compiled program.

        Donation contract (ISSUE 12): prefill donates the cache buffers it
        consumes, and the step donates the cache (and, on the fused path,
        the finished mask) so the per-token dispatch updates slots in place
        instead of allocating a fresh (B, S, H, D) per layer per token.
        The host loop must treat every donated argument as CONSUMED — it
        rebinds ``cache``/``finished`` from the step's outputs and never
        touches the stale references (the donation-safety regression test
        pins this).  ``fused=True`` builds the greedy/eos fast-path step
        that samples + freezes on device and returns the (B,) next token
        instead of (B, V) logits, and beside it what the module sowed (see
        ``_cached_apply``); ``eos_id`` is baked into that executable (part
        of its key — low-cardinality by construction)."""
        import jax.numpy as jnp
        module = self.module
        dkey = self._device_key()
        paged = page_size is not None
        if paged:
            kp = ("prefill_paged", dkey, batch_b, prompt_b, page_size,
                  table_w)
            ks = ("step_paged", dkey, batch_b, page_size, table_w)
        else:
            kp = ("prefill", dkey, batch_b, prompt_b, cache_len)
            ks = ("step", dkey, batch_b, cache_len)
        if fused:
            ks = ks + ("fused", eos_id)
        prefill = self._executables.get(kp)
        step = self._executables.get(ks)
        if prefill is not None and step is not None:
            return prefill, step
        sfx = "_paged" if paged else ""
        with self._lock:
            prefill = self._executables.get(kp)
            if prefill is None:
                def _prefill(variables, toks, positions, lengths, table,
                             cache, rows=None, _m=module):
                    # the head runs on the last REAL position of each
                    # sequence only: the (B, P, V) logits are never built,
                    # let alone fetched (ISSUE 34: at a vocabulary of 152k
                    # they were 311 MB a join for one row of use)
                    logits, cache, _ = _cached_apply(
                        _m, variables, toks, positions, table, cache,
                        logits_at=lengths - 1, rows=rows)
                    return logits[:, 0], cache

                prefill = self._executables[kp] = self._instrumented(
                    _prefill, suffix=f".prefill{sfx}", donate_argnums=(5,))
            step = self._executables.get(ks)
            if step is None:
                if fused:
                    def _step(variables, tok, positions, table, finished,
                              cache, _m=module, _eos=eos_id):
                        logits, cache, sown = _cached_apply(
                            _m, variables, tok[:, None], positions[:, None],
                            table, cache)
                        nxt, finished = _greedy_freeze(logits[:, 0],
                                                       finished, _eos)
                        return nxt, finished, cache, sown

                    step = self._instrumented(
                        _step, suffix=f".decode_step{sfx}",
                        donate_argnums=(4, 5))
                else:
                    def _step(variables, tok, positions, table, cache,
                              _m=module):
                        logits, cache, _ = _cached_apply(
                            _m, variables, tok, positions, table, cache)
                        return logits[:, 0], cache

                    step = self._instrumented(
                        _step, suffix=f".decode_step{sfx}",
                        donate_argnums=(4,))
                self._executables[ks] = step
        return prefill, step

    def _sample_executable(self, batch_b: int, eos_id: Optional[int]):
        """On-device greedy sampler for the fused fast path: argmax + eos
        freeze without the (B, V) prefill logits ever crossing to host.
        Donates the finished mask (aliased to the output mask); the logits
        have no same-shaped output to alias, so donating them would only
        warn."""
        key = ("sample", self._device_key(), batch_b, eos_id)
        fn = self._executables.get(key)
        if fn is not None:
            return fn
        with self._lock:
            fn = self._executables.get(key)
            if fn is None:
                def _sample(last, finished, _eos=eos_id):
                    return _greedy_freeze(last, finished, _eos)

                fn = self._executables[key] = self._instrumented(
                    _sample, suffix=".decode_sample", donate_argnums=(1,))
        return fn

    def decode(self, prompts: np.ndarray, lengths=None,
               max_new_tokens: int = 16, eos_id: Optional[int] = None,
               sample_fn: Optional[Callable] = None,
               collect_logits: bool = False,
               batch_bucket: Optional[int] = None,
               prompt_bucket: Optional[int] = None,
               cache_len: Optional[int] = None,
               kv_layout: str = "dense",
               page_size: int = 64,
               pool: Optional[PagePool] = None,
               prefix_cache: bool = False,
               watchdog=None) -> DecodeResult:
        """KV-cached batched autoregressive generation.

        ``prompts`` is ``(B, P)`` int32 (rows padded to the longest prompt);
        ``lengths`` gives each sequence's true prompt length so ragged
        batches decode exactly — each sequence writes and reads the cache at
        ITS own frontier.  Buckets: ``B`` pads to a power-of-two row bucket
        and ``P`` to a power-of-two prompt bucket.

        Cache memory (``kv_layout``): ``"dense"`` reserves one
        ``(cache_len,)`` slot row per sequence up front (``cache_len``
        defaults to the next power of two covering prompt + new tokens);
        ``"paged"`` allocates fixed-size pages from a shared
        :class:`PagePool` by ACTUAL length — ``ceil(true_len/page_size)``
        pages at prefill, one more at each page-boundary crossing, freed on
        eos — so concurrency scales with the tokens actually held, not
        ``B × max_len`` (pass ``pool=`` to share an explicitly sized
        budget; otherwise the runner's implicit pool for ``page_size`` is
        used, created at this call's worst case and grown when a larger
        batch outruns it).

        Sampling: ``sample_fn(logits) -> tokens`` defaults to greedy
        argmax; ``eos_id`` freezes finished sequences (and ends the loop
        early once ALL are finished).  When ``sample_fn`` is None and
        ``collect_logits`` is False, sampling + eos freezing run ON DEVICE
        and the step executables donate the cache/finished buffers: the
        common path fetches one (B,) token per step instead of the (B, V)
        logits, and the cache is updated in place instead of reallocated
        per token.

        Paged + eos caveat: once a frozen row's pages are freed its later
        logits are unspecified (its tokens are forced to ``eos_id``, and a
        ``sample_fn``'s output for frozen rows is discarded, so tokens are
        unaffected).  ``collect_logits=True`` keeps frozen rows' pages
        live instead, so the recorded distributions match the dense
        layout within the committed tolerance at every step.

        ``prefix_cache=True`` (paged + greedy only, ISSUE 20) consults the
        runner's :class:`~.prefix_cache.PrefixIndex` at admission: each
        row's cached prefix pages are PINNED instead of re-prefilled, only
        the suffix is allocated, and the prefill runs position-offset over
        the uncached suffix on the SAME executable signature (positions
        are traced data — zero new compile keys per hit length).  Rows
        that complete ok are retained into the index for the next
        request's hit.  Greedy tokens stay bit-identical to a cold
        decode — docs/runner.md "Prefix caching" states the argument."""
        if self.module is None or not (
                hasattr(self.module, "init_cache")
                or (kv_layout == "paged" or pool is not None)
                and hasattr(self.module, "init_paged_cache")):
            raise TypeError(
                "decode() needs a module with init_cache (a KV-cache-capable "
                "model, e.g. models.TransformerEncoder with causal=True, "
                "pool='none'); this runner wraps "
                f"{type(self.module).__name__ if self.module else 'a raw apply_fn'}")
        import jax
        import jax.numpy as jnp
        prompts = np.asarray(prompts, np.int32)
        if prompts.ndim != 2:
            raise ValueError("prompts must be (batch, prompt_len) int32")
        B, P = prompts.shape
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if kv_layout not in ("dense", "paged"):
            raise ValueError("kv_layout must be dense|paged")
        paged = kv_layout == "paged" or pool is not None
        lengths = (np.full(B, P, np.int32) if lengths is None
                   else np.asarray(lengths, np.int32))
        if lengths.shape != (B,) or lengths.min() < 1 or lengths.max() > P:
            raise ValueError("lengths must be (batch,) in [1, prompt_len]")
        B_b = batch_bucket or 1 << (B - 1).bit_length()
        P_b = prompt_bucket or 1 << (P - 1).bit_length()
        if B_b < B or P_b < P:
            raise ValueError("bucket smaller than the batch/prompt it serves")
        # greedy/eos fast path: sample + freeze on device (donated buffers)
        fused = sample_fn is None and not collect_logits
        if prefix_cache and not paged:
            raise ValueError("prefix_cache=True needs kv_layout='paged' — "
                             "the cache shares resident PagePool pages")
        if prefix_cache:
            _refuse_slot_state_prefix(self.module)
        if prefix_cache and (sample_fn is not None or collect_logits):
            raise ValueError(
                "prefix_cache=True supports the greedy fused path only: "
                "cached positions' logits are never recomputed, so a "
                "sample_fn / collect_logits caller would observe a "
                "different distribution surface than a cold decode")
        toks = np.zeros((B_b, P_b), np.int32)
        toks[:B, :P] = prompts
        lens = np.concatenate([lengths, np.ones(B_b - B, np.int32)])
        self._c_pad.inc((B_b - B) * P_b + B * (P_b - P))
        variables = self.variables

        table = None
        seq_pages: list = []
        index = None
        #: per-row cached prompt positions (prefix hit) — 0 without a hit;
        #: prefill positions offset past these, the step loop keeps TRUE
        #: lengths (cached k/v is read through the shared pages)
        shared_n = np.zeros(B_b, np.int32)
        if paged:
            if not hasattr(self.module, "init_paged_cache"):
                raise TypeError(
                    "kv_layout='paged' needs a module with init_paged_cache "
                    "(e.g. models.TransformerEncoder); "
                    f"{type(self.module).__name__} has none")
            if cache_len is not None:
                raise ValueError(
                    "cache_len is a dense-layout parameter (it sizes the "
                    "per-sequence reservation); the paged layout sizes "
                    "cache by pages — use page_size/pool instead")
            if pool is not None:
                page_size = pool.page_size
            page_size = int(page_size)
            if page_size < 1:
                raise ValueError("page_size must be >= 1")
            table_w = -(-(P_b + max_new_tokens) // page_size)
            max_len = getattr(self.module, "max_len", None)
            if max_len is not None and P_b + max_new_tokens > max_len:
                raise ValueError(
                    f"prompt_bucket + max_new_tokens = "
                    f"{P_b + max_new_tokens} exceeds the module's max_len "
                    f"{max_len} (positional table bound)")
            if pool is None:
                pool = self._auto_pool(page_size, B_b * table_w + 1)
            if prefix_cache:
                index = self.prefix_cache(page_size, pool=pool)
            prefill, step = self._decode_executables(
                B_b, P_b, page_size=page_size, table_w=table_w,
                fused=fused, eos_id=eos_id)
            table = np.zeros((B_b, table_w), np.int32)
            seq_pages = [[] for _ in range(B_b)]
            try:
                # allocate by TRUE length — pad rows (and unallocated table
                # entries) stay on the trash page and never hold pool pages.
                # With the prefix cache, cached prefix pages are pinned by
                # lookup() and only the suffix is freshly allocated; the
                # row's prompt tokens shift left to the uncached suffix
                for b in range(B):
                    if index is not None:
                        cpages, covered = index.lookup(
                            prompts[b, :int(lengths[b])])
                    else:
                        cpages, covered = [], 0
                    n_pages = -(-int(lengths[b]) // page_size)
                    try:
                        pgs = self._alloc_with_reclaim(
                            pool, index, n_pages - len(cpages))
                    except Exception:
                        if cpages:
                            pool.free(cpages)   # drop the lookup pins
                        raise
                    seq_pages[b] = list(cpages) + list(pgs)
                    table[b, :n_pages] = seq_pages[b]
                    if covered:
                        shared_n[b] = covered
                        suffix = int(lengths[b]) - covered
                        toks[b, :] = 0
                        toks[b, :suffix] = prompts[b, covered:int(lengths[b])]
                cache = pool.borrow_cache(B_b)
            except Exception:
                # a failed allocation or slab build must not leak the pages
                # already handed to earlier rows (borrow_cache resets its
                # own borrowed flag on failure)
                leftover = [p for pgs in seq_pages for p in pgs]
                if leftover:
                    pool.free(leftover)
                raise
            if index is not None:
                # copy-on-write guard over the prefill write range: a
                # suffix (or pad-tail) write landing on a refcount>1 page
                # would corrupt the other holders' admissible slots — mint
                # a private copy first (mid-page tail sharing is the one
                # admission shape that produces this; see prefix_cache.py)
                try:
                    for b in range(B):
                        lo = int(shared_n[b]) // page_size
                        hi = (int(lengths[b]) - 1) // page_size
                        for pi in range(lo, hi + 1):
                            pg = seq_pages[b][pi]
                            if pool.refcount(pg) > 1:
                                cache, newp = self._cow_split_page(
                                    pool, index, cache, pg)
                                seq_pages[b][pi] = newp
                                table[b, pi] = newp
                except Exception:
                    for pgs in seq_pages:
                        if pgs:
                            pool.free(pgs)
                    seq_pages = [[] for _ in range(B_b)]
                    pool.return_cache(None)
                    raise
            pages_prefill = sum(len(p) for p in seq_pages)
            peak_pages = pool.pages_in_use()
        else:
            S = cache_len or 1 << (P_b + max_new_tokens - 1).bit_length()
            if S < P_b + max_new_tokens:
                raise ValueError(
                    f"cache_len {S} is below prompt_bucket + max_new_tokens "
                    f"= {P_b + max_new_tokens}: the dense layout reserves "
                    "one full (cache_len,) slot row per sequence up front, "
                    "so the reservation must cover the longest possible "
                    "generation — raise cache_len, or switch to "
                    "kv_layout='paged' to size by actual length instead")
            prefill, step = self._decode_executables(
                B_b, P_b, cache_len=S, fused=fused, eos_id=eos_id)
            cache = self.module.init_cache(B_b, S)
            cache_nbytes = sum(int(l.nbytes)
                               for l in jax.tree_util.tree_leaves(cache))
        # prefill positions offset past each row's cached prefix (all-zero
        # offsets without a hit — identical to the cold layout); the gather
        # lengths are SUFFIX lengths so the last-real-token logits come
        # from the final uncached position.  Positions/lengths are traced
        # data, so hit lengths mint no compile keys by construction.
        positions = (shared_n[:, None]
                     + np.arange(P_b, dtype=np.int32)[None, :])
        plens = (lens - shared_n).astype(np.int32)
        sample = sample_fn or (lambda lg: np.argmax(lg, axis=-1))
        out_tokens = np.zeros((B_b, max_new_tokens), np.int32)
        out_logits = [] if collect_logits else None
        # pad rows are born finished: their garbage samples must never hold
        # the eos early-exit open (or inflate the step/token counters)
        finished = np.zeros(B_b, bool)
        finished[B:] = True
        steps = 0
        real_tokens = 0
        #: per-row unfrozen emissions — the useful-vs-wasted ledger needs
        #: a denied row's pre-denial tokens attributable (host-side only)
        row_tokens = np.zeros(B, np.int64)
        #: row -> tokens emitted when its pool extend was DENIED (ISSUE 13
        #: bugfix: a budgeted pool exhausting mid-decode freezes the row and
        #: yields a clean partial result instead of raising out of the loop)
        denied_at: Dict[int, int] = {}
        ok = False
        # every executable shares one signature; table is None (an empty
        # pytree) on the dense layout, and the device copy is re-uploaded
        # only when extend/free dirties it
        table_dev = jnp.asarray(table) if paged else None
        table_dirty = False
        # dispatch/device split (ISSUE 15, the PR 6 Trainer pattern on the
        # decode hot loop): dispatch = host time to enqueue each step,
        # device = sampled block_until_ready wait every Nth step; the loop
        # runs under an ambient profiler phase so host-stack samples
        # attribute to the decode loop by name
        from ..observability.tracing import (Span, _enter_phase,
                                             _exit_phase, current_trace_id,
                                             export_span)
        dte = self.device_time_every
        dispatch_s_total = device_s_total = 0.0
        t_loop0 = time.perf_counter()
        if watchdog is not None:
            # stall watchdog (ISSUE 16): one armed section spans prefill +
            # the whole token loop, with a per-iteration heartbeat after
            # each host fetch — the timeout bounds any SINGLE dispatch/
            # fetch (the hang shapes), never the loop's total wall time.
            # Build one via stall_watchdog() to book stalls + flight dumps.
            watchdog.arm("runner.decode")
        _phase = _enter_phase("runner.decode")
        try:
            last, cache = prefill(
                variables, jnp.asarray(toks), jnp.asarray(positions),
                jnp.asarray(plens), table_dev, cache)
            self._c_batches["decode"].inc()
            if fused:
                tok_d, fin_d = self._sample_executable(B_b, eos_id)(
                    last, jnp.asarray(finished))
            for t in range(max_new_tokens):
                if fused:
                    # the ONLY host fetches on the fast path: the (B,) token
                    # ids + (B,) finished flags; logits stay on device
                    tok = np.asarray(tok_d)
                    fin_now = np.asarray(fin_d)
                    if denied_at:
                        # the device-resident finished mask never learns of
                        # a host-side page denial — fold it back in, or the
                        # denied row thaws next iteration (re-inflating the
                        # decode-tokens counter and holding the eos
                        # early-exit open forever)
                        fin_now = fin_now.copy()
                        for b in denied_at:
                            fin_now[b] = True
                else:
                    lg = np.asarray(last)                  # (B_b, V) fetch
                    if collect_logits:
                        out_logits.append(lg)
                    tok = np.asarray(sample(lg), np.int32)
                    if eos_id is not None:
                        tok = np.where(finished, eos_id, tok)
                        fin_now = finished | (tok == eos_id)
                    else:
                        fin_now = finished
                if watchdog is not None:
                    watchdog.heartbeat()   # this step's host fetch returned
                # tokens emitted while a sequence was already frozen are eos
                # padding, not generated work (ISSUE 12 bugfix: the old
                # B * n_generated charge inflated fleet tokens/sec and the
                # autoscale signal on early-finishing batches)
                real_tokens += B - int(finished[:B].sum())
                row_tokens += ~finished[:B]
                out_tokens[:, t] = tok
                if paged and eos_id is not None and not collect_logits:
                    # free on eos: pages return to the pool mid-flight; the
                    # frozen row keeps stepping, but its zeroed table rows
                    # point every further write at the trash page (its
                    # post-freeze logits become unspecified — tokens are
                    # forced to eos either way).  collect_logits keeps
                    # frozen rows live instead, so the recorded
                    # distributions match the dense layout exactly.
                    for b in np.nonzero(fin_now[:B] & ~finished[:B])[0]:
                        if seq_pages[b]:
                            pool.free(seq_pages[b])
                            seq_pages[b] = []
                            table[b, :] = 0
                            table_dirty = True
                finished = fin_now
                if t == max_new_tokens - 1 or \
                        ((eos_id is not None or denied_at)
                         and bool(finished.all())):
                    break
                # token t sits at absolute position lengths + t; the step
                # writes it at that frontier and returns logits for t+1
                # (host path) or the sampled token t+1 (fused path)
                pos = (lens + t).astype(np.int32)
                if paged:
                    # extend at page boundaries: the write position must be
                    # backed by a real page BEFORE the step dispatches.
                    # Frozen rows stop extending once freed — except under
                    # collect_logits, where they stay live (logits parity)
                    for b in range(B):
                        if b in denied_at or \
                                (finished[b] and not collect_logits):
                            continue
                        pi = int(pos[b]) // page_size
                        needs_page = pi >= len(seq_pages[b])
                        needs_cow = (not needs_page and index is not None
                                     and pool.refcount(seq_pages[b][pi]) > 1)
                        if needs_page or needs_cow:
                            try:
                                if needs_cow:
                                    # the paged step detected a write
                                    # landing on a refcount>1 page: route
                                    # it to a freshly allocated private
                                    # copy — table row updated, donor
                                    # refcount decremented (ISSUE 20 CoW)
                                    cache, new_page = self._cow_split_page(
                                        pool, index, cache, seq_pages[b][pi])
                                else:
                                    new_page = self._alloc_with_reclaim(
                                        pool, index, 1, op="extend")[0]
                            except PagePoolExhausted:
                                # mid-decode exhaustion of a budgeted pool
                                # is admission control: freeze the row,
                                # release its pages for the survivors, and
                                # return its generation so far (the denial
                                # is already booked as op="denied"; serving
                                # maps the row to a 503 shed)
                                denied_at[b] = t + 1
                                if not finished.flags.writeable:
                                    # the fused path's finished vector is a
                                    # read-only view of the device fetch
                                    finished = finished.copy()
                                finished[b] = True
                                if seq_pages[b]:
                                    pool.free(seq_pages[b])
                                    seq_pages[b] = []
                                table[b, :] = 0
                                table_dirty = True
                                continue
                            if needs_cow:
                                seq_pages[b][pi] = new_page
                            else:
                                seq_pages[b].append(new_page)
                            table[b, pi] = new_page
                            table_dirty = True
                    peak_pages = max(peak_pages, pool.pages_in_use())
                    if table_dirty:
                        # re-upload only when extend/free actually changed
                        # the table — steady-state steps reuse the resident
                        # copy (the table arg is never donated)
                        table_dev = jnp.asarray(table)
                        table_dirty = False
                t_disp0 = time.perf_counter()
                if fused:
                    # donated dispatch: fin_d/cache are CONSUMED here — the
                    # loop rebinds all three outputs and must never touch
                    # the stale references again
                    tok_d, fin_d, cache, _ = step(variables, tok_d,
                                                  jnp.asarray(pos),
                                                  table_dev, fin_d, cache)
                else:
                    last, cache = step(variables, jnp.asarray(tok[:, None]),
                                       jnp.asarray(pos[:, None]), table_dev,
                                       cache)
                disp_s = time.perf_counter() - t_disp0
                dispatch_s_total += disp_s
                self._h_phase_dispatch.observe(disp_s)
                steps += 1
                self._c_decode_steps.inc()
                if dte and steps % dte == 0:
                    # sampled only: the forced sync ends async pipelining
                    # for this step, so the device series costs 1/N of the
                    # dispatch/execute overlap
                    t_dev0 = time.perf_counter()
                    jax.block_until_ready(tok_d if fused else last)
                    dev_s = time.perf_counter() - t_dev0
                    device_s_total += dev_s
                    self._h_phase_device.observe(dev_s)
            ok = True
        finally:
            _exit_phase(_phase)
            if watchdog is not None:
                watchdog.disarm()
            if paged:
                # retention (ISSUE 20): an ok row's pages hold valid k/v
                # for its prompt + every fed-back token (the final sampled
                # token is never written) — hand them to the prefix index
                # as the next request's hit instead of the free list.
                # Denied/eos-freed rows and failed loops free as before.
                for b in range(B_b):
                    pgs = seq_pages[b]
                    if not pgs:
                        continue
                    if (index is not None and ok and b < B
                            and b not in denied_at):
                        n_gen = int(t) + 1
                        ids = np.concatenate(
                            [prompts[b, :int(lengths[b])],
                             out_tokens[b, :max(n_gen - 1, 0)]])
                        index.release(ids, pgs)
                    else:
                        pool.free(pgs)
                    seq_pages[b] = []
                # after a mid-step failure the donated slab state is
                # unknown — drop it so the next borrower rebuilds zeros
                pool.return_cache(cache if ok else None)
        n_generated = t + 1
        # a denied row's post-denial slots hold whatever the trash-page
        # dispatches produced — overwrite with eos padding so the partial
        # result is clean up to (and silent past) its truncation point
        for b, cut in denied_at.items():
            out_tokens[b, cut:] = eos_id if eos_id is not None else 0
        self._c_decode_tokens.inc(real_tokens)
        self._c_rows["decode"].inc(B)
        # useful-vs-wasted ledger (ISSUE 17): every cell of the padded
        # batch emitted this call lands in exactly one outcome bucket, so
        # useful + wasted == B_b x iterations — a conservation law, not an
        # estimate.  Denied rows' pre-denial tokens were real device work
        # the caller only received truncated; pad cells cover bucket
        # padding AND frozen rows still riding the fused step.
        denied_tokens = int(sum(int(row_tokens[b]) for b in denied_at))
        useful_tokens = int(real_tokens) - denied_tokens
        pad_cells = B_b * n_generated - int(real_tokens)
        if useful_tokens:
            self._c_tok_outcome.inc(useful_tokens, outcome="useful")
        if denied_tokens:
            self._c_tok_outcome.inc(denied_tokens, outcome="denied_row")
        if pad_cells:
            self._c_tok_outcome.inc(pad_cells, outcome="pad_row")
        # attributed device-seconds: host-observed step wall time (enqueue
        # + the sampled residual device wait) — the cost denominator the
        # capacity model divides tokens into
        device_s_attr = dispatch_s_total + device_s_total
        self._c_device_s.inc(device_s_attr)
        extras: Dict[str, Any] = {
            "kv_layout": "paged" if paged else "dense",
            "real_tokens": real_tokens,
            "batch_bucket": B_b,
            "dispatch_s": round(dispatch_s_total, 6),
            "device_s": round(device_s_total, 6),
            "attribution": {"useful": useful_tokens,
                            "denied_row": denied_tokens,
                            "pad_row": pad_cells,
                            "device_s_attributed": round(device_s_attr, 6)},
        }
        # one span per decode call carrying the split (never per token —
        # the export ring is bounded); joins the ambient trace when the
        # call rides a served request
        span = Span("runner.decode", trace_id=current_trace_id(),
                    start_s=t_loop0,
                    attributes={"runner": self.name, "steps": steps,
                                "dispatch_s": round(dispatch_s_total, 6),
                                "device_s": round(device_s_total, 6),
                                "device_time_every": dte})
        span.finish(time.perf_counter())
        export_span(span, self.registry)
        if denied_at:
            extras["denied_rows"] = sorted(denied_at)
            extras["denied_at"] = {int(b): int(c)
                                   for b, c in sorted(denied_at.items())}
        if paged:
            extras.update(
                page_size=page_size, table_width=table_w,
                pool_pages=pool.capacity, pages_prefill=pages_prefill,
                pages_peak=peak_pages,
                page_occupancy_pct=round(
                    100.0 * peak_pages / max(pool.capacity, 1), 2),
                cache_bytes_per_seq=pool.page_nbytes() * peak_pages
                / max(B, 1))
            if index is not None:
                extras["prefix"] = {
                    "cached_tokens": int(shared_n[:B].sum()),
                    "hit_rows": int((shared_n[:B] > 0).sum()),
                    **index.stats()}
        else:
            extras.update(cache_len=S,
                          cache_bytes_per_seq=cache_nbytes / max(B, 1))
        self.last_decode_extras = extras
        logits = (np.stack(out_logits, axis=1)[:B] if collect_logits
                  else None)
        return DecodeResult(tokens=out_tokens[:B, :n_generated],
                            lengths=lengths, steps=steps, logits=logits,
                            extras=extras)

    # --------------------------------------------------------- stall watchdog
    def stall_watchdog(self, stall_timeout_s: float,
                       clock: Callable[[], float] = time.monotonic,
                       on_stall: Optional[Callable] = None):
        """A :class:`~mmlspark_tpu.utils.resilience.Watchdog` wired to this
        runner's stall telemetry (ISSUE 16): an armed section overrunning
        ``stall_timeout_s`` books ``mmlspark_runner_stalls_total`` and
        fires a flight-recorder postmortem dump on the stall edge
        (``trigger="stall"`` — the engine state BEFORE recovery tears it
        down), then chains the caller's ``on_stall(label, elapsed_s)``
        (the continuous engine hangs its poison-abort there).  Pass the
        result to :meth:`decode`'s ``watchdog=``, or let
        ``decode_stream(stall_timeout_s=...)`` build one internally."""
        from ..utils.resilience import Watchdog

        def _trip(label: str, elapsed: float) -> None:
            self._c_stalls.inc()
            try:
                from ..observability.flightrecorder import get_flight_recorder
                get_flight_recorder(self.registry).dump(trigger="stall")
            except Exception:  # noqa: BLE001 — the dump must never block
                pass           # stall recovery
            if on_stall is not None:
                on_stall(label, elapsed)

        return Watchdog(stall_timeout_s, clock=clock, on_stall=_trip,
                        name=self.name)

    # ------------------------------------------------------ continuous front
    def decode_stream(self, *, slots: int = 4, prompt_bucket: int = 16,
                      max_new_tokens: int = 16,
                      eos_id: Optional[int] = None, page_size: int = 64,
                      pool: Optional[PagePool] = None,
                      clock: Optional[Callable[[], float]] = None,
                      stall_timeout_s: Optional[float] = None,
                      prefix_cache: bool = False,
                      max_prompt_len: Optional[int] = None
                      ) -> "ContinuousDecoder":
        """A persistent in-flight decode loop over the paged pool (ISSUE 13
        tentpole): a fixed ``slots``-wide batch whose per-slot state (page-
        table row, length, finished flag) supports slot-level JOIN (a new
        arrival prefills into freshly allocated pages and splices into the
        running batch between steps) and LEAVE (eos/budget frees the slot's
        pages mid-flight; the slot is immediately admissible again).

        The stream reuses the ONE-SHOT executables at its geometry — the
        PR 12 step is keyed on (batch bucket, page size, table width), and
        each join prefills the arrival ALONE at the one-shot
        (1, prompt_bucket) prefill signature into its own pages — so
        admission introduces NO new compile keys (``warmup()`` covers all
        three signatures) and greedy tokens stay bit-identical to
        :meth:`decode`.  Greedy/eos fast path only
        (``sample_fn``/``collect_logits`` stay one-shot).

        With ``prefix_cache=True`` (ISSUE 20) admission consults the
        pool's :class:`~.prefix_cache.PrefixIndex`: a join allocates only
        the uncached suffix pages and prefills only the uncached positions
        (positions offset past the shared prefix — traced data, so joins
        STILL cannot mint a compile key), and a finished request's pages
        are retained in the index instead of freed, funding the next
        arrival's hit.  Greedy tokens stay bit-identical to cold-cache
        :meth:`decode` across hit/partial-hit/miss/CoW traffic.

        ``max_prompt_len`` (default: ``prompt_bucket``) is the longest
        prompt admitted; ``prompt_bucket`` is the prefill CHUNK.  A longer
        prompt joins chunk after chunk through the same (1, prompt_bucket)
        prefill executable with offset positions (ISSUE 34), and the page
        table's width follows ``max_prompt_len + max_new_tokens``.

        Drive it with :meth:`ContinuousDecoder.submit` + either
        :meth:`ContinuousDecoder.start` (background engine thread — what
        serving uses) or manual :meth:`ContinuousDecoder.step` calls
        (deterministic tests)."""
        return ContinuousDecoder(self, slots=slots,
                                 prompt_bucket=prompt_bucket,
                                 max_new_tokens=max_new_tokens,
                                 eos_id=eos_id, page_size=page_size,
                                 pool=pool, clock=clock,
                                 stall_timeout_s=stall_timeout_s,
                                 prefix_cache=prefix_cache,
                                 max_prompt_len=max_prompt_len)


class StreamHandle:
    """One request in flight on a :class:`ContinuousDecoder`.

    Lifecycle: ``queued`` (slot + prompt pages reserved at submit) →
    ``live`` (spliced into the batch; ``t_first_s``/``ttft_s`` set) → a
    terminal outcome: ``ok`` (eos or token budget), ``denied`` (page pool
    exhausted mid-flight — the generation so far is on ``tokens``),
    ``expired`` (deadline passed mid-flight), ``cancelled`` (decoder
    closed) or ``error`` (engine failure).  ``done`` fires at the terminal
    transition; ``on_done(handle)`` (if given) runs on the engine thread
    right after it."""

    __slots__ = ("prompt", "length", "max_new_tokens", "deadline_s",
                 "on_done", "slot", "tokens", "status", "done",
                 "t_submit_s", "t_first_s", "pages", "trace_id", "cost",
                 "prompt_hash", "covered")

    def __init__(self, prompt: np.ndarray, length: int, max_new_tokens: int,
                 deadline_s: Optional[float], on_done: Optional[Callable],
                 trace_id: Optional[str] = None,
                 prompt_hash: Optional[str] = None):
        self.prompt = prompt
        self.length = int(length)
        self.max_new_tokens = int(max_new_tokens)
        self.deadline_s = deadline_s
        self.on_done = on_done
        # the request's trace id (ISSUE 15 satellite): the TTFT histogram
        # observation carries it as an exemplar, so a p99 TTFT outlier on
        # /metrics resolves to the exact request via /trace/<id> even
        # though the observation books on the ENGINE thread, which has no
        # ambient span
        self.trace_id = trace_id
        self.slot = -1
        self.tokens: List[int] = []
        self.status = "queued"
        self.done = threading.Event()
        self.t_submit_s = 0.0
        self.t_first_s: Optional[float] = None
        self.pages: List[int] = []
        # per-request cost ledger (ISSUE 17) — attached at submit; engine
        # edges mutate it, the terminal outcome classifies its tokens
        self.cost = None
        # prefix-cache seam (ISSUE 20): the admission-time prompt hash the
        # serving layer passed through (observability only — the index
        # matches on token content), and how many leading prompt positions
        # the index covered (0 = cold; the join prefills only the suffix)
        self.prompt_hash = prompt_hash
        self.covered = 0

    @property
    def ttft_s(self) -> Optional[float]:
        """Submit-to-first-token latency (None until the join prefill)."""
        if self.t_first_s is None:
            return None
        return max(0.0, self.t_first_s - self.t_submit_s)

    def result(self, timeout: Optional[float] = None) -> DecodeResult:
        """Block until terminal and return a one-row :class:`DecodeResult`
        (partial for denied/expired/cancelled outcomes)."""
        if not self.done.wait(timeout):
            raise TimeoutError("decode stream request still in flight")
        toks = np.asarray(self.tokens, np.int32).reshape(1, -1)
        return DecodeResult(
            tokens=toks,
            lengths=np.asarray([self.length], np.int32),
            steps=max(0, len(self.tokens) - 1),
            extras={"status": self.status, "ttft_s": self.ttft_s})


@dataclass
class _StepInFlight:
    """What :meth:`ContinuousDecoder._dispatch` hands to ``_retire``."""
    #: the handles by slot AS THEY WERE at the dispatch; None for a row
    #: that was not stepped (no request, or its last token already taken)
    rows: List[Optional["StreamHandle"]]
    stepped: int          # rows with a handle
    dead: int             # rows stepped after their last token
    tok_d: Any            # the step's tokens and what the module sowed, on
    sown_d: Any           # the device; neither is donated to the next step
    t0: float             # perf_counter at the dispatch


class ContinuousDecoder:
    """Slot-level continuous batching on the paged KV pool (ISSUE 13).

    A fixed in-flight batch of ``slots`` rows decodes on ONE fused step
    executable; requests join free slots between steps and leave (freeing
    their pages) the moment they finish, so tokens/sec tracks the arrival
    process instead of the slowest member of a drained batch.  Per-slot
    state is the paged-decode substrate from PR 12: a page-table row, a
    true length, and a finished flag — empty slots are pad rows (finished,
    table row on the trash page).

    Join = a (1, prompt_bucket) prefill of the arrival alone into its
    freshly allocated pages, between steps — device work proportional to
    the arrival, never the batch width, and live rows' pages untouched
    (the prefill's table names only the joiner's pages).  Because every
    signature is exactly a one-shot :meth:`ModelRunner.decode` executable
    (and :meth:`warmup` pre-compiles all three), admission can never
    compile — the no-new-compile-keys rule the bench A/B counter-checks.

    Admission control at :meth:`submit`: no free slot raises
    :class:`SlotsExhausted`; the prompt's pages are allocated up front so
    pool exhaustion raises :class:`PagePoolExhausted` (booked as
    ``op="denied"``) — serving maps both to 503 + Retry-After.  A
    mid-flight extend denial resolves that slot as ``denied`` with its
    partial generation.

    Metrics: ``mmlspark_runner_slots_{joined,left}_total``,
    ``mmlspark_runner_slot_occupancy_pct``, the
    ``mmlspark_runner_ttft_seconds`` histogram, and
    ``mmlspark_runner_decode_{steps_overlapped,stale_rows}_total``, all
    labelled by runner.

    Threading: ``submit`` is thread-safe; the engine must have ONE
    driver — the :meth:`start` engine thread, or a single test/bench loop
    calling :meth:`step`.  Both run the same round (:meth:`_round`: splice
    arrivals, dispatch a step, retire the step before it); ``step()``
    retires what it dispatched before it returns, the thread leaves it in
    flight for the next round (docs/runner.md, "The engine's round").
    The decoder borrows the pool's device slabs at the first join and
    returns them at :meth:`close` (one-shot paged decodes on the same pool
    block until then, by the PR 12 borrow contract).
    """

    OUTCOMES = ("ok", "denied", "expired", "cancelled", "error")

    def __init__(self, runner: ModelRunner, *, slots: int = 4,
                 prompt_bucket: int = 16, max_new_tokens: int = 16,
                 eos_id: Optional[int] = None, page_size: int = 64,
                 pool: Optional[PagePool] = None,
                 clock: Optional[Callable[[], float]] = None,
                 stall_timeout_s: Optional[float] = None,
                 prefix_cache: bool = False,
                 max_prompt_len: Optional[int] = None):
        module = runner.module
        if module is None or not hasattr(module, "init_paged_cache"):
            raise TypeError(
                "decode_stream() needs a module with init_paged_cache "
                "(e.g. models.TransformerEncoder with causal=True)")
        if slots < 1 or prompt_bucket < 1 or max_new_tokens < 1:
            raise ValueError("slots, prompt_bucket and max_new_tokens "
                             "must all be >= 1")
        if prefix_cache:
            _refuse_slot_state_prefix(module)
        self.runner = runner
        self.slots = int(slots)
        self.prompt_bucket = int(prompt_bucket)
        #: the longest prompt admitted; one longer than the prefill chunk
        #: (``prompt_bucket``) joins in several chunks
        self.max_prompt_len = self.prompt_bucket if max_prompt_len is None \
            else int(max_prompt_len)
        if self.max_prompt_len < self.prompt_bucket:
            raise ValueError(
                f"max_prompt_len {self.max_prompt_len} is below the prefill "
                f"chunk (prompt_bucket {self.prompt_bucket})")
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.clock = clock or time.monotonic
        if pool is not None:
            page_size = pool.page_size
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.page_size = int(page_size)
        self.table_w = -(-(self.max_prompt_len + self.max_new_tokens)
                         // self.page_size)
        max_len = getattr(module, "max_len", None)
        if max_len is not None and \
                self.max_prompt_len + self.max_new_tokens > max_len:
            raise ValueError(
                f"longest prompt + max_new_tokens = "
                f"{self.max_prompt_len + self.max_new_tokens} exceeds the "
                f"module's max_len {max_len} (positional table bound)")
        self._explicit_pool = pool is not None
        self.pool = pool if pool is not None else runner._auto_pool(
            self.page_size, self.slots * self.table_w + 1)
        # cross-request prefix cache (ISSUE 20): the index rides the POOL
        # (resized()/auto-grow flush-and-rebind it there), the decoder
        # only holds the reference — _adopt_current_pool_locked re-reads
        # it whenever the idle stream re-binds to a replaced pool
        self._prefix_enabled = bool(prefix_cache)
        self.index = runner.prefix_cache(
            self.page_size, pool=self.pool) if prefix_cache else None
        # the one-shot executables AT THE STREAM GEOMETRY — shared cache
        # entries, so a warmed one-shot decode warms the stream and vice
        # versa, and joins can never mint a new compile key.  The step
        # runs at the full batch bucket; joins prefill each arrival ALONE
        # at the (1, prompt_bucket) signature — device work proportional
        # to the arrival, not the batch width (a full-width join prefill
        # costs slots× the compute per join), with the same one-shot
        # bit-parity by row independence.
        _, self._step = runner._decode_executables(
            self.slots, self.prompt_bucket, page_size=self.page_size,
            table_w=self.table_w, fused=True, eos_id=eos_id)
        self._prefill1, _ = runner._decode_executables(
            1, self.prompt_bucket, page_size=self.page_size,
            table_w=self.table_w, fused=True, eos_id=eos_id)
        self._sample1 = runner._sample_executable(1, eos_id)
        # per-slot state: empty slots behave as pad rows
        self._tok = np.zeros(self.slots, np.int32)
        self._fin = np.ones(self.slots, bool)
        self._lens = np.ones(self.slots, np.int32)
        self._emitted = np.zeros(self.slots, np.int32)
        self._table = np.zeros((self.slots, self.table_w), np.int32)
        self._table_dev = None
        self._table_dirty = True
        #: device-resident copies of _tok/_fin for the steady state — the
        #: previous step's outputs feed the next dispatch directly (as the
        #: one-shot fused loop does); a join invalidates them so the next
        #: dispatch re-uploads the host state it spliced into
        self._tok_dev = None
        self._fin_dev = None
        #: the step dispatched and not yet retired: held by the start()
        #: thread across the dispatch of the next one; None between two
        #: step() calls
        self._in_flight: Optional[_StepInFlight] = None
        #: perf_counter at the last retirement's fetch: the attribution
        #: clock charges an overlapped step from here, not from its dispatch
        self._t_retired = 0.0
        #: per slot, the device-resident (1,) row index a join's prefill
        #: names its slot's state by; None for a module all of whose
        #: layers are paged (its prefill takes no such argument)
        self._slot_rows = None
        if _keeps_slot_state(module):
            import jax.numpy as jnp
            self._slot_rows = [jnp.asarray([s], jnp.int32)
                               for s in range(self.slots)]
        self._handles: List[Optional[StreamHandle]] = [None] * self.slots
        self._free: List[int] = list(range(self.slots - 1, -1, -1))
        self._arrivals: "deque[StreamHandle]" = deque()
        self._cond = make_condition("ContinuousDecoder._cond")
        self._cache = None
        self._live = 0
        self._closed = False
        self._poisoned = False
        self._torn = False
        self._draining = False
        #: why the engine died ("stall"/"error"; None while alive or after
        #: a clean close) — the serving seam reads it to map stall-aborted
        #: handles to a retryable 503 instead of a 500 (ISSUE 16)
        self.abort_reason: Optional[str] = None
        self._thread: Optional[threading.Thread] = None
        # dispatch hang watchdog (ISSUE 16): armed around every engine
        # dispatch+fetch; a trip books the stall, dumps the flight
        # recorder (runner.stall_watchdog wires both), then poison-aborts
        # this engine from the monitor thread
        self.watchdog = None if stall_timeout_s is None else \
            runner.stall_watchdog(stall_timeout_s, clock=self.clock,
                                  on_stall=self._stall_abort)
        self.steps = 0       # fused step dispatches (join prefills excluded)
        self.joined = 0
        self.left = 0
        reg, name = runner.registry, runner.name
        self._name = name
        self._c_joined = reg.counter(
            "mmlspark_runner_slots_joined_total",
            "requests spliced into the in-flight decode batch",
            labels=("runner",)).labels(runner=name)
        fam_left = reg.counter(
            "mmlspark_runner_slots_left_total",
            "slots released by outcome (ok/denied/expired/cancelled)",
            labels=("runner", "outcome"))
        self._c_left = {o: fam_left.labels(runner=name, outcome=o)
                        for o in self.OUTCOMES}
        self._g_occ = reg.gauge(
            "mmlspark_runner_slot_occupancy_pct",
            "reserved+live decode slots as % of the in-flight bucket",
            labels=("runner",))
        self._h_ttft = reg.histogram(
            "mmlspark_runner_ttft_seconds",
            "submit-to-first-token latency of continuous decode",
            labels=("runner",)).labels(runner=name)
        # attribution plane (ISSUE 17): the decoder books token outcomes
        # and attributed device-seconds on the runner's shared families —
        # all host-side, so the ledger can never mint a compile key
        from ..observability.attribution import RequestCost, ENGINE_OUTCOME_MAP
        self._RequestCost = RequestCost
        self._outcome_map = ENGINE_OUTCOME_MAP
        self._c_tok_outcome = runner._c_tok_outcome
        self._c_device_s = runner._c_device_s
        self._book_occupancy()
        # flight-recorder roster (ISSUE 15): the postmortem dump reads the
        # live slot table + pool occupancy from here — WeakSet-held, so a
        # closed and discarded stream drops out on its own
        from ..observability.flightrecorder import _roster
        _roster(reg, "_decode_streams").add(self)

    # -------------------------------------------------------------- admission
    @property
    def closed(self) -> bool:
        """True once :meth:`close` ran or the engine aborted — a closed
        decoder refuses submits; callers holding one should rebuild."""
        return self._closed

    @property
    def draining(self) -> bool:
        """True once :meth:`drain` started: no new joins, existing slots
        running to completion."""
        return self._draining

    def occupancy(self) -> int:
        """Slots reserved or live (free slots are ``slots - occupancy``)."""
        with self._cond:
            return self.slots - len(self._free)

    def _book_occupancy(self) -> None:
        """Occupancy gauge — called with ``_cond`` held."""
        occ = self.slots - len(self._free)
        self._g_occ.set(100.0 * occ / self.slots, runner=self._name)

    def _adopt_current_pool_locked(self) -> None:
        """A FULLY idle stream re-binds to the runner's CURRENT implicit
        pool for its page size (``_cond`` held): ``page_pool(num_pages=)``
        resizes and ``_auto_pool`` growth REPLACE the runner's pool
        object, and a stream that kept the old reference would allocate
        from an orphaned budget (the operator's resize silently not
        applying) while both pools stomp one occupancy series.  Only when
        zero slots are reserved and the slabs are returned, so in-flight
        state never spans two pools; a stream built on an explicit
        ``pool=`` keeps it — that budget is the caller's contract."""
        if self._explicit_pool or self._cache is not None \
                or self._live or self._arrivals \
                or len(self._free) != self.slots:
            return
        current = self.runner._pools.get(
            (self.runner._device_key(), self.page_size))
        if current is not None and current is not self.pool:
            self.pool = current
            if self._prefix_enabled:
                # the index rode the old pool through resized()'s
                # flush-and-rebind (or needs creating on a fresh pool) —
                # either way the pool's attached index is authoritative
                self.index = self.runner.prefix_cache(
                    self.page_size, pool=current)

    def submit(self, prompt, *, max_new_tokens: Optional[int] = None,
               deadline_s: Optional[float] = None,
               on_done: Optional[Callable] = None,
               trace_id: Optional[str] = None,
               prompt_hash: Optional[str] = None) -> StreamHandle:
        """Admit one request: reserve a free slot and allocate its prompt
        pages NOW (the admission decision), splice into the batch at the
        next step boundary.  Raises :class:`SlotsExhausted` /
        :class:`PagePoolExhausted` when the engine is full — admission
        control, the serving layer's 503 signal.

        With the stream's prefix cache enabled, admission consults the
        index first: the longest page-aligned cached prefix is pinned
        (shared, refcounted) and only the SUFFIX pages are freshly
        allocated — the join then prefills only the uncached positions.
        ``prompt_hash`` is the serving seam's request identity (ISSUE 20)
        — recorded on the handle for ``/debug/requests``; the index
        itself matches on token content, so hash collisions cannot
        corrupt decode."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        length = int(prompt.size)
        if not 1 <= length <= self.max_prompt_len:
            raise ValueError(
                f"prompt length {length} outside [1, "
                f"{self.max_prompt_len}] (the stream's longest prompt)")
        budget = (self.max_new_tokens if max_new_tokens is None
                  else int(max_new_tokens))
        if not 1 <= budget <= self.max_new_tokens:
            raise ValueError(
                f"max_new_tokens {budget} outside [1, "
                f"{self.max_new_tokens}] (the stream's table bound)")
        n_pages = -(-length // self.page_size)
        with self._cond:
            if self._closed:
                raise RuntimeError("decoder is closed")
            if self._draining:
                # graceful drain (ISSUE 16): existing slots run to
                # eos/budget, new arrivals shed retryably — another
                # worker (or this one after restart) takes them
                raise EngineDraining(
                    "decoder is draining — no new joins")
            self._adopt_current_pool_locked()
            if not self._free:
                raise SlotsExhausted(
                    f"no free decode slot ({self.slots} in flight) — "
                    "retry after a sequence finishes, or run more slots")
            # pages allocated inside the slot reservation so the two
            # admission resources can never disagree (denied is booked by
            # the pool before the raise).  Prefix lookup first: cached
            # prefix pages are PINNED (shared), only the suffix is fresh —
            # lock order decoder._cond -> index._lock -> pool._cond.
            covered = 0
            cpages: List[int] = []
            if self.index is not None:
                cpages, covered = self.index.lookup(prompt)
            try:
                pages = list(cpages) + list(self.runner._alloc_with_reclaim(
                    self.pool, self.index, n_pages - len(cpages)))
            except Exception:
                if cpages:
                    self.pool.free(cpages)
                raise
            slot = self._free.pop()
            handle = StreamHandle(prompt, length, budget, deadline_s,
                                  on_done, trace_id=trace_id,
                                  prompt_hash=prompt_hash)
            handle.slot = slot
            handle.pages = pages
            handle.covered = covered
            handle.t_submit_s = self.clock()
            handle.cost = self._RequestCost(prefill_tokens=length)
            handle.cost.prefill_cached = covered
            handle.cost.page_edge(handle.t_submit_s, len(handle.pages))
            self._arrivals.append(handle)
            self._book_occupancy()
            self._cond.notify_all()
        return handle

    # ----------------------------------------------------------------- engine
    def _borrow(self) -> None:
        if self._cache is None:
            self._cache = self.pool.borrow_cache(self.slots)

    def _row_arg(self, s: int) -> Tuple:
        """The join prefill's trailing argument for slot ``s``: its row of
        the slot state, or nothing for a module that keeps none."""
        return (self._slot_rows[s],) if self._slot_rows else ()

    def _return_cache_if_idle(self) -> None:
        """Hand the borrowed slabs back while the engine is EMPTY (no live
        slot, no queued arrival): an idle engine holds no pages, so its
        slab contents are irrelevant — returning them lets one-shot paged
        decodes (and other streams on the same pool) interleave instead of
        blocking on the borrow until :meth:`close`.  The next join simply
        re-borrows."""
        if self._cache is None:
            return
        with self._cond:
            if self._arrivals:
                return
        cache, self._cache = self._cache, None
        self.pool.return_cache(cache)

    def warmup(self) -> None:
        """Compile the join-prefill/sampler/step executables with
        all-trash dispatches (zero page tables: no pool pages held, no
        slot state touched), so the first real join never pays a compile.
        The signatures are shared with one-shot :meth:`ModelRunner.decode`
        at this geometry, so a warmed one-shot also warms the stream."""
        import jax.numpy as jnp
        self._borrow()
        S, P_b = self.slots, self.prompt_bucket
        variables = self.runner.variables
        try:
            positions = jnp.broadcast_to(jnp.arange(P_b, dtype=jnp.int32),
                                         (1, P_b))
            table1 = jnp.zeros((1, self.table_w), jnp.int32)
            last, self._cache = self._prefill1(
                variables, jnp.zeros((1, P_b), jnp.int32), positions,
                jnp.ones(1, jnp.int32), table1, self._cache,
                *self._row_arg(0))
            self._sample1(last, jnp.ones(1, bool))
            _t, _f, self._cache, _ = self._step(
                variables, jnp.zeros(S, jnp.int32),
                jnp.zeros(S, jnp.int32),
                jnp.zeros((S, self.table_w), jnp.int32),
                jnp.ones(S, bool), self._cache)
            if self.index is not None:
                # CoW page-copy at this pool geometry: warmed by a trash
                # self-copy (page 0 -> page 0, no real page touched) so
                # the first real split under hit traffic never compiles
                self._cache = self.runner._cow_executable()(
                    self._cache, jnp.int32(0), jnp.int32(0))
        except Exception:
            with self._cond:  # same lock as close()/_abort readers (CCY002)
                self._poisoned = True  # donated slab state unknown (see step)
            raise
        if self._live == 0:
            self._return_cache_if_idle()

    def step(self) -> int:
        """One engine round, whole: splice queued arrivals (join prefill),
        advance every live slot one fused step, release finished slots
        (leave).  When it returns, every step it dispatched is RETIRED:
        its tokens are on their handles, its leaves have left.  ONE driver
        only — the :meth:`start` thread or a single test/bench loop.
        Returns the number of live slots remaining.

        The :meth:`start` thread drives the same :meth:`_round` without the
        retirement that closes this one: it keeps one step in flight, so
        the host's work between two steps runs beside the device."""
        with self._engine_work() as leavers:
            self._round(leavers)
            self._retire_in_flight(leavers)
        if self._live == 0:
            self._return_cache_if_idle()
        return self._live

    def _round(self, leavers: List[StreamHandle]) -> None:
        """Splice arrivals, dispatch the next step, THEN retire the step
        that was in flight before it: the device runs step N+1 while the
        host fetches and books step N.  Nothing the dispatch needs comes
        from that fetch (the sampled tokens feed the next step on the
        device, positions and page extends follow from counting); only an
        eos is learnt late, and costs its row one step it did not need
        (``mmlspark_runner_decode_stale_rows_total``).  The step dispatched
        here is left in flight."""
        with self._cond:
            joiners = list(self._arrivals)
            self._arrivals.clear()
        if joiners:
            self._join(joiners, leavers)
        prev, self._in_flight = self._in_flight, self._dispatch(leavers)
        if prev is not None:
            self._retire(prev, leavers)

    def _retire_in_flight(self, leavers: List[StreamHandle]) -> None:
        flight, self._in_flight = self._in_flight, None
        if flight is not None:
            self._retire(flight, leavers)

    @contextmanager
    def _engine_work(self):
        """The bracket of everything the engine's driver does: the
        ``runner.decode.step`` ambient phase (ISSUE 15: host-stack samples
        from ``/debug/profile`` attribute the driver's time to the decode
        loop by name — a span per round would flood the export ring at
        token cadence, the phase table costs two dict writes), the poison
        on a failure, and the leavers resolved at the end whatever
        happened.  Yields the list that collects the leavers."""
        from ..observability.tracing import _enter_phase, _exit_phase
        leavers: List[StreamHandle] = []
        _phase = _enter_phase("runner.decode.step")
        laps = self.runner._decode_laps
        try:
            yield leavers
        except Exception:
            # a failed dispatch leaves the donated slab state unknown —
            # poison the borrow so close()/abort return None and the next
            # borrower rebuilds zeros instead of consuming a dead buffer;
            # under the engine lock: close() on another thread reads the
            # flag deciding return-vs-drop of the borrowed slabs (CCY002)
            with self._cond:
                self._poisoned = True
            raise
        finally:
            try:
                if leavers:
                    laps.lap("notify")
                    self._finish(leavers)
            finally:
                laps.stop()
                _exit_phase(_phase)

    def _finish(self, leavers: List[StreamHandle]) -> None:
        for h in leavers:
            h.done.set()
            if h.on_done is not None:
                try:
                    h.on_done(h)
                except Exception:  # noqa: BLE001 — a reply callback must
                    pass           # never kill the shared engine

    def _join(self, joiners: List[StreamHandle],
              leavers: List[StreamHandle]) -> None:
        """Splice arrivals into their reserved slots.  Each joiner
        prefills ALONE at the (1, prompt_bucket) signature into its
        freshly allocated pool pages — per-row computation depends only
        on that row's pages and mask, so the tokens are bit-identical to
        one-shot prefill while the device work is proportional to the
        ARRIVAL, not the batch width (a full-width join prefill costs
        slots× the compute per join and dominated the trace's device
        passes); live rows' pages are untouched because the prefill's
        table argument only names the joiner's pages."""
        import jax.numpy as jnp
        runner = self.runner
        lap = runner._decode_laps.lap
        lap("join_prefill")
        self._borrow()
        P_b, W = self.prompt_bucket, self.table_w
        ps = self.page_size
        positions = np.broadcast_to(np.arange(P_b, dtype=np.int32),
                                    (1, P_b))
        pos_dev = jnp.asarray(positions)
        for h in joiners:
            lap("join_prefill")
            s = h.slot
            off = int(h.covered)
            if off and self.index is not None:
                # admission CoW guard (ISSUE 20): the suffix prefill
                # scatters positions [off, length) — a refcount>1 page in
                # that range (the partially-covered tail page of a
                # mid-page hit) must be split to a private copy BEFORE
                # the write lands on state other requests share
                try:
                    for pi in range(off // ps, (h.length - 1) // ps + 1):
                        if pi < len(h.pages) and \
                                self.pool.refcount(h.pages[pi]) > 1:
                            self._cache, newp = runner._cow_split_page(
                                self.pool, self.index, self._cache,
                                h.pages[pi])
                            h.pages[pi] = newp
                except PagePoolExhausted:
                    # admission-time denial: the arrival never joined —
                    # its pages fund the survivors, the client sees the
                    # same retryable verdict as a mid-flight denial
                    self._cancel_arrival(h, "denied", leavers)
                    continue
            jtable = np.zeros((1, W), np.int32)
            n = len(h.pages)
            jtable[0, :n] = h.pages
            self._table[s, :] = 0
            self._table[s, :n] = h.pages
            self._table_dirty = True
            self._handles[s] = h
            if self.watchdog is not None and self._in_flight is None:
                self.watchdog.arm("runner.decode.join")
            # the uncovered part of the prompt, chunk after chunk: positions
            # offset past the cached prefix and the chunks before are traced
            # DATA at the same (1, prompt_bucket) signature, so a hit join
            # and a long prompt reuse the cold join's executable — no new
            # compile key per hit length or prompt length.  Only the last
            # chunk's logits are sampled
            jtable_dev = jnp.asarray(jtable)
            row = self._row_arg(s)
            for at in range(off, h.length, P_b):
                n = min(P_b, h.length - at)
                toks = np.zeros((1, P_b), np.int32)
                toks[0, :n] = h.prompt[at:at + n]
                last, self._cache = self._prefill1(
                    runner.variables, jnp.asarray(toks),
                    jnp.asarray(positions + at) if at else pos_dev,
                    jnp.asarray([n], np.int32), jtable_dev, self._cache,
                    *row)
                runner._c_prefill_chunks.inc()
                if self._in_flight is not None:
                    # the start() thread's step in flight: this prefill is
                    # queued behind it, so the host retires it while the
                    # prefill runs.  The splice below needs the host's
                    # state whole, and the fetch of the joiner's first
                    # token drains the device anyway
                    self._retire_in_flight(leavers)
                    lap("join_prefill")
                    if self.watchdog is not None:
                        self.watchdog.arm("runner.decode.join")
                if self.watchdog is not None and at + P_b < h.length:
                    # the timeout bounds ONE dispatch, not a long join
                    lap("join_fetch")
                    last.block_until_ready()
                    lap("join_prefill")
                    self.watchdog.heartbeat()
            runner._c_prefill_tokens["computed"].inc(h.length - off)
            runner._c_prefill_tokens["cached"].inc(off)
            tok_d, fin_d = self._sample1(last, jnp.zeros(1, bool))
            lap("join_fetch")
            tok0 = int(np.asarray(tok_d)[0])
            fin0 = bool(np.asarray(fin_d)[0])
            lap("join_splice")
            if self.watchdog is not None:
                self.watchdog.disarm()
            runner._c_batches["decode"].inc()
            now = self.clock()
            h.status = "live"
            h.t_first_s = now
            # exemplar: the engine thread has no ambient span, so the
            # request's trace id rides the handle (ISSUE 15 satellite —
            # a TTFT outlier must resolve to its trace)
            self._h_ttft.observe(max(0.0, now - h.t_submit_s), h.trace_id)
            self._c_joined.inc()
            self.joined += 1
            self._live += 1
            self._lens[s] = h.length
            self._emitted[s] = 1
            self._tok[s] = tok0
            self._fin[s] = fin0
            self._tok_dev = None     # splice mutated host state
            self._fin_dev = None
            h.tokens.append(tok0)
            if h.cost is not None:
                h.cost.decode_tokens += 1
            runner._c_decode_tokens.inc()
            runner._c_rows["decode"].inc()
            if fin0 or h.max_new_tokens <= 1:
                self._release(s, "ok", leavers)

    def _dispatch(self, leavers: List[StreamHandle]
                  ) -> Optional[_StepInFlight]:
        """The first half of a step: deadline leaves (never spend a
        dispatch on a dead client), positions, page-boundary extends (a
        denial leaves the slot with its partial generation), uploads, and
        the SAME donated step executable one-shot decode dispatches.
        Returns what was dispatched, for :meth:`_retire`; None when no
        slot has a step left to take.

        Everything here follows from counting: a slot's position is its
        prompt length + the steps DISPATCHED for it (``_emitted``), the
        token it feeds is the step before's output, still on the device.
        A slot whose last token is already dispatched (``max_new_tokens``
        reached by count) waits for its retirement as a dead row: it gets
        no page and its write lands one position past its last, in its
        own tail page or on the trash page."""
        import jax.numpy as jnp
        runner = self.runner
        lap = runner._decode_laps.lap
        lap("prepare")
        now = self.clock()
        for s, h in enumerate(self._handles):
            if h is not None and h.deadline_s is not None \
                    and now > h.deadline_s:
                self._release(s, "expired", leavers)
        pos = np.zeros(self.slots, np.int32)
        rows: List[Optional[StreamHandle]] = [None] * self.slots
        stepped = dead = 0
        for s, h in enumerate(self._handles):
            if h is None:
                continue
            p = int(self._lens[s] + self._emitted[s] - 1)
            pos[s] = p
            if self._emitted[s] >= h.max_new_tokens:
                dead += 1
                continue
            pi = p // self.page_size
            needs_page = pi >= len(h.pages)
            # step-site CoW guard (ISSUE 20): this step writes position p;
            # if the page holding p is shared (refcount>1 — the request's
            # generation diverging from a retained/shared prefix mid-page)
            # the write must land on a private copy
            needs_cow = (not needs_page and self.index is not None
                         and self.pool.refcount(h.pages[pi]) > 1)
            if needs_page or needs_cow:
                try:
                    if needs_cow:
                        self._cache, new_page = self.runner._cow_split_page(
                            self.pool, self.index, self._cache, h.pages[pi])
                    else:
                        new_page = self.runner._alloc_with_reclaim(
                            self.pool, self.index, 1, op="extend")[0]
                except PagePoolExhausted:
                    # mid-flight denial: the slot leaves with what it has
                    # (op="denied" already booked by the pool), its pages
                    # fund the survivors
                    self._release(s, "denied", leavers)
                    continue
                if needs_cow:
                    h.pages[pi] = new_page
                else:
                    h.pages.append(new_page)
                    if h.cost is not None:
                        h.cost.page_edge(now, 1)
                self._table[s, pi] = new_page
                self._table_dirty = True
            rows[s] = h
            stepped += 1
            self._emitted[s] += 1
        if not stepped:
            return None
        # uploaded from COPIES: on the CPU jnp.asarray may alias a small
        # numpy buffer, and the host edits all three (a join's table row, a
        # leave's zeroes, the retired tokens) while the step dispatched here
        # may still be waiting to run: a pad row would then write position 0
        # through the row a join has just filled, into a page others share
        if self._table_dirty or self._table_dev is None:
            self._table_dev = jnp.asarray(self._table.copy())
            self._table_dirty = False
        tok_in = self._tok_dev if self._tok_dev is not None \
            else jnp.asarray(self._tok.copy())
        fin_in = self._fin_dev if self._fin_dev is not None \
            else jnp.asarray(self._fin.copy())
        if self.watchdog is not None:
            # armed from here until NO step is in flight: the dispatch and
            # the host fetch are both the hang shapes (a hung device
            # dispatch stalls the fetch; a dead runtime stalls the
            # enqueue).  Each retirement restarts the clock, so the
            # timeout still bounds one step
            self.watchdog.arm("runner.decode.step")
        t0 = lap("dispatch")
        tok_d, fin_d, self._cache, sown_d = self._step(
            runner.variables, tok_in, jnp.asarray(pos),
            self._table_dev, fin_in, self._cache)
        # dispatch/device split (ISSUE 15): the step call above is the
        # host enqueue, and the lap that ends here observes it; the
        # device's part is waited for in _retire
        lap("book")
        # fin_in was donated (consumed) by the dispatch, and fin_d will be
        # by the next: the finished mask lives on the device alone and no
        # host code reads it.  A release keeps both device copies (its row
        # has no handle, a zeroed table row and position 0: whatever it
        # computes lands on the trash page and is dropped at retirement);
        # a join re-uploads the host's state
        self._tok_dev, self._fin_dev = tok_d, fin_d
        if self._in_flight is not None:
            runner._c_steps_overlapped.inc()
        return _StepInFlight(rows, stepped, dead, tok_d, sown_d, t0)

    def _retire(self, flight: _StepInFlight,
                leavers: List[StreamHandle]) -> None:
        """The second half of a step: the one host fetch (the tokens, and
        what the module sowed: nothing, but for a routed model's counts),
        then tokens onto their handles, counters, attribution and leaves.
        Rows are retired against the handles the step was DISPATCHED for:
        a row whose handle has left since (eos, deadline, denial, cancel)
        is stale and its token is dropped."""
        import jax
        runner = self.runner
        lap = runner._decode_laps.lap
        t_wait0 = lap("fetch")
        # tok_d is an argument of the step after this one but not a donated
        # one; the finished mask IS donated there, so it is derived here as
        # the device derives it: a live row finishes on emitting eos
        tok, sown = jax.device_get((flight.tok_d, flight.sown_d))
        t_done = lap("book")
        if self.watchdog is not None:
            if self._in_flight is None:
                self.watchdog.disarm()
            else:
                self.watchdog.heartbeat()   # this step's fetch returned
        self.steps += 1
        dte = runner.device_time_every
        if dte and self.steps % dte == 0:
            # what was left of the step when the host came to fetch it:
            # the whole device wait under step(), the remainder of it
            # behind the start() thread's overlapped host work
            runner._h_phase_device.observe(t_done - t_wait0)
        runner._c_decode_steps.inc()
        for n in sown.get("experts_touched", ()):
            runner._c_experts_touched.inc(float(n))
        for n in sown.get("local_assignments", ()):
            runner._c_local_assignments.inc(float(n))
        # attribution (ISSUE 17): the wall time this step held the device
        # alone — from its dispatch, or from the retirement before it when
        # it was dispatched earlier than that and waited behind that step —
        # to its own fetch's return, amortized over the slots that had a
        # live request behind them at ITS dispatch; the rest of the batch
        # width was pad cells — dispatched-but-wasted by definition
        step_s = t_done - max(flight.t0, self._t_retired)
        self._t_retired = t_done
        share = step_s / flight.stepped
        if step_s > 0:
            self._c_device_s.inc(step_s)
        booked = 0
        for s, h in enumerate(flight.rows):
            if h is None:
                continue
            if h.cost is not None:
                h.cost.device_s += share
            if self._handles[s] is not h:
                continue            # left while this step was in flight
            booked += 1
            t = int(tok[s])
            self._tok[s] = t
            h.tokens.append(t)
            if h.cost is not None:
                h.cost.decode_tokens += 1
            runner._c_decode_tokens.inc()
            if t == self.eos_id or len(h.tokens) >= h.max_new_tokens:
                self._release(s, "ok", leavers)
        if booked < self.slots:
            self._c_tok_outcome.inc(self.slots - booked, outcome="pad_row")
        stale = flight.stepped - booked + flight.dead
        if stale:
            runner._c_stale_rows.inc(stale)

    def _release(self, s: int, outcome: str,
                 leavers: List[StreamHandle]) -> None:
        """Leave: free the slot's pages mid-flight, reset it to pad-row
        state (trash table row, finished), and hand the slot back to
        admission — the batch keeps stepping around it."""
        h = self._handles[s]
        self._handles[s] = None
        h.status = outcome
        if h.cost is not None:
            # terminal classification: every token this request generated
            # lands in exactly one outcome bucket — the conservation law
            h.cost.close_pages(self.clock())
            if h.cost.decode_tokens > 0:
                self._c_tok_outcome.inc(h.cost.decode_tokens,
                                        outcome=self._outcome_map[outcome])
        if h.pages:
            if outcome == "ok" and self.index is not None and h.tokens:
                # prefix retention (ISSUE 20): a cleanly finished request
                # donates its pages to the index keyed by every position
                # actually WRITTEN — prompt + generated[:-1] (the final
                # sampled token's k/v was never scattered) — turning this
                # prefill into the next arrival's hit.  The index takes
                # ownership of the references; budget-surplus pages free.
                ids = np.concatenate(
                    [h.prompt, np.asarray(h.tokens[:-1], np.int32)])
                self.index.release(ids, h.pages)
            else:
                self.pool.free(h.pages)
            h.pages = []
        self._table[s, :] = 0
        self._table_dirty = True
        self._fin[s] = True
        self._tok[s] = 0
        self._lens[s] = 1
        self._emitted[s] = 0
        # _tok_dev/_fin_dev stay: with a step in flight the host's tokens
        # are one step behind the device's, and the row needs no edit there
        # (see _dispatch)
        self._c_left[outcome].inc()
        self.left += 1
        self._live -= 1
        leavers.append(h)
        with self._cond:
            self._free.append(s)
            self._book_occupancy()
            self._cond.notify_all()

    # ------------------------------------------------------------- postmortem
    def debug_state(self) -> Dict[str, Any]:
        """JSON-able engine state for the flight recorder (ISSUE 15): the
        slot table, per-slot progress, and pool occupancy — the state that
        otherwise dies with a crashed/preempted worker.  Read under the
        admission lock so a dump mid-join sees a consistent table."""
        with self._cond:
            slots = []
            for s in range(self.slots):
                h = self._handles[s]
                slots.append({
                    "slot": s,
                    "live": h is not None,
                    "status": None if h is None else h.status,
                    "length": int(self._lens[s]),
                    "emitted": int(self._emitted[s]),
                    "finished": bool(self._fin[s]),
                    "pages": list(map(int, self._table[s]))})
            state = {
                "runner": self._name,
                "slots": self.slots,
                "occupancy": self.slots - len(self._free),
                "live": self._live,
                "queued_arrivals": len(self._arrivals),
                "steps": self.steps,
                "step_in_flight": self._in_flight is not None,
                "joined": self.joined,
                "left": self.left,
                "closed": self._closed,
                "draining": self._draining,
                "abort_reason": self.abort_reason,
                "slot_table": slots,
            }
        if self.watchdog is not None:
            state["watchdog"] = self.watchdog.as_dict()
        state["pool"] = {
            "page_size": self.pool.page_size,
            "capacity": self.pool.capacity,
            "pages_in_use": self.pool.pages_in_use(),
            "occupancy_pct": round(self.pool.occupancy_pct(), 2),
            "slot_state_bytes": self.pool.slot_state_nbytes()}
        if self.index is not None:
            state["prefix_cache"] = self.index.stats()
        return state

    # -------------------------------------------------------------- lifecycle
    def start(self) -> "ContinuousDecoder":
        """Run the engine on a background thread: steps while any slot is
        live, sleeps on the condition otherwise."""
        with self._cond:
            if self._torn:
                raise RuntimeError("decoder is closed — build a fresh "
                                   "stream (decode_stream()) instead")
            if self._thread is not None:
                return self
            self._closed = False
            self._thread = threading.Thread(
                target=self._run, daemon=True,
                name=f"mmlspark-decode-stream-{self._name}")
            self._thread.start()
        if self.watchdog is not None:
            # monitor thread mode: a test driving step() manually on a
            # FakeClock skips start() and polls watchdog.check() itself
            self.watchdog.start()
        return self

    def _run(self) -> None:
        """The pipelined driver: rounds with one step left in flight
        between them, drained when the engine closes."""
        try:
            while self._wait_for_work():
                with self._engine_work() as leavers:
                    self._round(leavers)
                if self._live == 0 and self._in_flight is None:
                    self._return_cache_if_idle()
            with self._engine_work() as leavers:
                # close() tears down once this thread has ended: no page is
                # freed and no slab returned under a step in flight
                self._retire_in_flight(leavers)
        except Exception:  # noqa: BLE001 — a poisoned step must not
            self._abort()  # strand clients on done.wait
            raise

    def _wait_for_work(self) -> bool:
        """Sleep on the condition while there is nothing to splice, step
        or retire; False once the engine is closed."""
        with self._cond:
            while not self._closed and not self._arrivals \
                    and self._live == 0 and self._in_flight is None:
                self._cond.wait(0.1)
            return not self._closed

    def _stall_abort(self, label: str, elapsed: float) -> None:
        """Watchdog trip (runs on the MONITOR thread — the engine thread
        is stuck inside the hung dispatch): mark the abort as a stall
        FIRST, so the on_done callbacks the teardown fires read it and
        shed 503 ``shed_engine_stall`` instead of erroring 500, then
        poison-abort — in-flight handles resolve, pages free, and the
        borrowed slabs drop (donated state is unknown while a dispatch is
        wedged inside them)."""
        self.abort_reason = "stall"
        self._abort()

    def _abort(self) -> None:
        """Engine failure: resolve every queued/live handle as ``error``
        and drop the borrowed slabs (donated state unknown — the next
        borrower rebuilds zeros)."""
        if self.abort_reason is None:
            self.abort_reason = "error"
        with self._cond:
            self._closed = True
            self._poisoned = True
            # the engine thread is exiting through this very call: clear
            # the handle so close() does not block joining ourselves
            self._thread = None
            self._cond.notify_all()
        self._teardown("error")

    def _teardown(self, outcome: str) -> None:
        """Release every queued/live handle with ``outcome`` and return
        (or drop, when poisoned) the borrowed slabs.  Claimed exactly once
        — ``_abort`` on the engine thread and ``close()`` on the caller
        can otherwise race the release loop into double-freed pages and a
        twice-listed free slot."""
        with self._cond:
            if self._torn:
                return
            self._torn = True
            arrivals = list(self._arrivals)
            self._arrivals.clear()
        # a step still in flight here has no driver left to retire it (the
        # engine failed or hangs inside it): dropped, its rows released
        # below like every other
        self._in_flight = None
        leavers: List[StreamHandle] = []
        for h in arrivals:
            self._cancel_arrival(h, outcome, leavers)
        for s, h in enumerate(self._handles):
            if h is not None:
                self._release(s, outcome, leavers)
        self._finish(leavers)
        cache, self._cache = self._cache, None
        if cache is not None:
            self.pool.return_cache(None if self._poisoned else cache)
        if self.watchdog is not None:
            # the engine is gone — nothing left to watch.  stop() is safe
            # from the monitor thread itself (stall-abort path): it sets
            # the stop event without self-joining.
            self.watchdog.disarm()
            self.watchdog.stop()

    def _cancel_arrival(self, h: StreamHandle, outcome: str,
                        leavers: List[StreamHandle]) -> None:
        h.status = outcome
        if h.cost is not None:
            # a cancelled arrival never joined: zero decode tokens, so no
            # outcome booking — only its reserved page-seconds close out
            h.cost.close_pages(self.clock())
        if h.pages:
            self.pool.free(h.pages)
            h.pages = []
        self._c_left[outcome].inc()
        self.left += 1
        leavers.append(h)
        with self._cond:
            self._free.append(h.slot)
            self._book_occupancy()

    def drain(self, timeout_s: Optional[float] = None,
              poll_s: float = 0.05) -> bool:
        """Graceful wind-down (ISSUE 16): stop admitting — ``submit``
        sheds :class:`EngineDraining` from here on — let queued arrivals
        and live slots run to eos/budget/deadline, then :meth:`close`.

        Returns True when every slot finished inside ``timeout_s`` (None
        = wait indefinitely), False when the timeout cut the wait short —
        ``close()`` then cancels the survivors (partial tokens stay on
        their handles).  Needs the :meth:`start` engine thread (or a
        concurrent external ``step()`` driver) to make progress; the wait
        keys on ALL slots returning to the free list, so a join in flight
        between the arrival snapshot and its splice can never be stranded
        by the close racing it."""
        with self._cond:
            self._draining = True
        deadline = None if timeout_s is None else self.clock() + timeout_s
        drained = False
        with self._cond:
            while not self._torn:
                if len(self._free) == self.slots and not self._arrivals:
                    drained = True
                    break
                if deadline is not None and self.clock() >= deadline:
                    break
                self._cond.wait(poll_s)
        self.close()
        return drained

    def close(self) -> None:
        """Stop the engine, cancel queued arrivals and live slots (partial
        tokens stay on their handles), free their pages, and return the
        borrowed device slabs to the pool.  A closed decoder is final —
        holders rebuild (``_RunnerScorer._ensure_decoder`` does)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=60)
        self._teardown("cancelled")


def _resolve_takes_cost(resolve: Callable) -> bool:
    """Whether a serving ``resolve`` callback accepts the ``cost=`` kwarg
    (ISSUE 17).  Introspected per request terminal — the server's resolve
    closure is fresh each call — so older callers (the streaming facade,
    out-of-tree fronts) keep working unchanged."""
    import inspect
    try:
        sig = inspect.signature(resolve)
    except (TypeError, ValueError):
        return False
    for p in sig.parameters.values():
        if p.kind is inspect.Parameter.VAR_KEYWORD or p.name == "cost":
            return True
    return False


class _RunnerScorer(Transformer):
    """Private serving front: built by :meth:`ModelRunner.scorer`, scored by
    ``PipelineServer`` / the streaming facade.  Not a registered stage —
    it is constructed programmatically around a live runner, never from
    params, so it stays out of codegen/fuzzing by the ``_`` convention."""

    def __init__(self, runner: ModelRunner, input_col: str, reply_col: str,
                 prepare: Optional[Callable], encode: Optional[Callable],
                 mode: str, decode_kwargs: Dict[str, Any],
                 continuous: bool = False, report_ttft: bool = False,
                 supervisor=None):
        super().__init__()
        self.runner = runner
        self.input_col, self.reply_col = input_col, reply_col
        self.prepare = prepare or (lambda v: np.asarray(v, np.float32))
        self.encode = encode or (lambda y: y)
        self.mode = mode
        self.decode_kwargs = dict(decode_kwargs)
        self.continuous = bool(continuous)
        self.report_ttft = bool(report_ttft)
        self._decoder: Optional[ContinuousDecoder] = None
        self._dec_lock = make_lock("_RunnerScorer._dec_lock")
        #: duck-typed health signal (ISSUE 16): PipelineServer's /health
        #: reads it — a quarantined runner flips it False so the fleet's
        #: probes evict the worker
        self.serving_healthy = True
        self.supervisor = None
        if self.continuous:
            if mode != "decode":
                raise ValueError("continuous=True requires mode='decode' "
                                 "(scoring rows already admit into the "
                                 "server's in-flight drain)")
            # instance attribute, not a class method: its PRESENCE is the
            # protocol — PipelineServer/streaming route entries here only
            # when the model exposes it, so a score-mode scorer (or any
            # other Transformer) never matches
            self.continuous_submit = self._continuous_submit
            # supervised engine recovery (ISSUE 16): rebuilds after an
            # abort ride capped exponential backoff; repeated stalls
            # quarantine the runner (serving_healthy -> False)
            from ..utils.resilience import RestartSupervisor
            self.supervisor = supervisor if supervisor is not None else \
                RestartSupervisor(
                    clock=self.decode_kwargs.get("clock") or time.monotonic)
            self._pending_restart = False
            self._c_restarts = runner.registry.counter(
                "mmlspark_engine_restarts_total",
                "supervised decode-engine rebuilds after an abort/stall",
                labels=("runner",)).labels(runner=runner.name)

    # ---------------------------------------------------- continuous protocol
    def _ensure_decoder(self) -> ContinuousDecoder:
        with self._dec_lock:
            dec = self._decoder
            if dec is not None and not dec.closed:
                return dec
            if dec is not None:
                # the engine died under us (poisoned dispatch, stall
                # abort): the first observer books the death; the backoff
                # below gates every rebuilder, so a request storm cannot
                # thrash rebuild-abort cycles (ISSUE 16)
                self._decoder = None
                self.supervisor.note_failure(dec.abort_reason or "error")
                self._pending_restart = True
            if self.supervisor.quarantined:
                # repeated stalls inside the window: stop restarting and
                # flip /health unhealthy — TopologyService probes evict
                # this worker; the fleet routes around it
                self.serving_healthy = False
                raise EngineUnavailable(
                    "decode engine quarantined after repeated stalls",
                    reason="engine_quarantined",
                    retry_after_s=self.supervisor.retry_after_s())
            wait = self.supervisor.retry_after_s()
            if wait > 0:
                raise EngineUnavailable(
                    f"decode engine restarting; backoff {wait:.2f}s left",
                    reason="engine_restarting",
                    retry_after_s=max(0.1, wait))
            self._decoder = self.runner.decode_stream(
                **self.decode_kwargs).start()
            if self._pending_restart:
                self._pending_restart = False
                self.supervisor.note_restart()
                self._c_restarts.inc()
            return self._decoder

    def continuous_close(self) -> None:
        """Stop the owned decode stream (PipelineServer.stop() calls this
        when present); a later request lazily reopens it."""
        with self._dec_lock:
            decoder, self._decoder = self._decoder, None
        if decoder is not None:
            decoder.close()
            if self.supervisor is not None:
                # a clean operator close is engine health, not failure —
                # the backoff exponent resets
                self.supervisor.note_success()

    def continuous_drain(self, timeout_s: Optional[float] = None) -> bool:
        """Graceful wind-down of the owned stream (ISSUE 16): no new
        joins, existing slots run to eos/budget, then close.  Returns
        True when every in-flight slot finished inside ``timeout_s``.  A
        later request lazily reopens a fresh engine (a drain is a clean
        close — no restart backoff)."""
        with self._dec_lock:
            decoder, self._decoder = self._decoder, None
        if decoder is None:
            return True
        drained = decoder.drain(timeout_s=timeout_s)
        if self.supervisor is not None:
            self.supervisor.note_success()
        return drained

    def _reply_body(self, tokens, ttft_s: Optional[float]):
        body = self.encode(np.asarray(tokens, np.int32))
        if isinstance(body, np.ndarray):
            # the default identity encode would otherwise reach the HTTP
            # writer as an ndarray and serialize as a numpy string repr
            body = body.tolist()
        if self.report_ttft:
            body = {"tokens": body,
                    "ttft_ms": None if ttft_s is None
                    else round(1000.0 * ttft_s, 3)}
        return body

    def _continuous_submit(self, payload, resolve, queue_age_s=0.0,
                           deadline_budget_s=None, trace_id=None,
                           prompt_hash=None) -> None:
        """The serving seam (ISSUE 13): admit ONE request into the
        in-flight batch.  ``resolve(reply=, status=, verdict=,
        retry_after_s=, ttft_s=)`` fires on the engine thread at the
        request's terminal outcome; admission failures raise out of here
        with ``.shed`` set so the caller sheds 503 + Retry-After.

        The caller's timing crosses the seam DOMAIN-FREE — ``queue_age_s``
        (time already spent queued at the caller) and
        ``deadline_budget_s`` (seconds of budget remaining) are relative,
        never absolute timestamps, so a server on an injectable clock and
        a decoder on ``time.monotonic`` can never be compared against each
        other.  Reported TTFT = queue age + the engine's
        submit-to-first-token.  ``trace_id`` (ISSUE 15) threads the
        request's trace through to the engine so the TTFT histogram's
        exemplar names it — the resolve path runs on the engine thread,
        where no ambient span exists to supply one.  ``prompt_hash``
        (ISSUE 20) is the admission seam's stable prompt identity,
        recorded on the stream handle for ``/debug/requests``."""
        decoder = self._ensure_decoder()
        prompt = np.asarray(payload, np.int32).reshape(-1)
        deadline_s = None if deadline_budget_s is None \
            else decoder.clock() + max(0.0, deadline_budget_s)
        pre_s = max(0.0, queue_age_s or 0.0)
        takes_cost = _resolve_takes_cost(resolve)

        def on_done(h: StreamHandle) -> None:
            # cost pass-through (ISSUE 17): the caller's queue wait lands
            # on the ledger at terminal time (race-free — on_done runs
            # once, on the engine thread) and rides resolve when the
            # caller's closure accepts it
            kw = {}
            if h.cost is not None:
                h.cost.queue_s = pre_s
                if takes_cost:
                    kw["cost"] = h.cost
            if h.status == "ok":
                ttft_s = None if h.ttft_s is None else pre_s + h.ttft_s
                resolve(reply=self._reply_body(h.tokens, ttft_s),
                        status=200, verdict="ok", ttft_s=ttft_s, **kw)
            elif h.status == "denied":
                resolve(reply={"error": "shed: page pool exhausted "
                                        "mid-decode"},
                        status=503, verdict="shed_page_pool",
                        retry_after_s=1.0, **kw)
            elif h.status == "expired":
                resolve(reply={"error": "deadline expired mid-decode"},
                        status=504, verdict="deadline_expired_decoding",
                        **kw)
            elif decoder.abort_reason == "stall":
                # the watchdog killed a hung dispatch under this request:
                # the prompt is fine and another worker (or this engine
                # after its supervised restart) can serve it — a
                # retryable 503, not a 500 (ISSUE 16)
                resolve(reply={"error": "shed: decode engine stalled"},
                        status=503, verdict="shed_engine_stall",
                        retry_after_s=1.0, **kw)
            else:  # cancelled / error — the engine went away under us
                resolve(reply={"error": f"decode {h.status}"},
                        status=500, verdict="error", **kw)

        decoder.submit(prompt, deadline_s=deadline_s, on_done=on_done,
                       trace_id=trace_id, prompt_hash=prompt_hash)

    # ------------------------------------------------------------- batch path
    def _decode_batch(self, col, n: int, out: np.ndarray, age) -> None:
        """Ticked/batch decode: one one-shot decode over the drained rows.
        Mid-decode page denials surface per row as :class:`ShedReply`
        (serving maps them to 503); ``report_ttft`` wraps replies with the
        honest ticked TTFT — the full latency (queue age at drain + decode
        wall, both RELATIVE durations so the server's clock domain never
        leaks in), since no token is client-visible before the batch
        resolves."""
        t0 = time.monotonic()
        prompts = [np.asarray(v, np.int32).reshape(-1) for v in col]
        lengths = np.asarray([len(q) for q in prompts], np.int32)
        P = int(lengths.max())
        stacked = np.zeros((n, P), np.int32)
        for i, q in enumerate(prompts):
            stacked[i, :len(q)] = q
        res = self.runner.decode(stacked, lengths=lengths,
                                 **self.decode_kwargs)
        denied = set((res.extras or {}).get("denied_rows", ()))
        wall_s = time.monotonic() - t0
        for i in range(n):
            if i in denied:
                out[i] = ShedReply("page pool exhausted mid-decode")
            elif age is not None:
                out[i] = self._reply_body(
                    res.tokens[i], max(0.0, float(age[i])) + wall_s)
            else:
                out[i] = self._reply_body(res.tokens[i], None)

    def _decode_batch_continuous(self, col, n: int, out: np.ndarray,
                                 age) -> None:
        """Batch front of a continuous scorer (streaming fallback, batch
        transform): rows ride the live stream — submit each into a slot,
        waiting for a free one when the batch is wider than the engine —
        so the executable cache, pool accounting and metrics stay one
        story."""
        decoder = self._ensure_decoder()
        handles: List[Optional[StreamHandle]] = [None] * n
        outstanding: List[StreamHandle] = []
        for i in range(n):
            prompt = np.asarray(col[i], np.int32).reshape(-1)
            while True:
                try:
                    handles[i] = decoder.submit(prompt)
                    outstanding.append(handles[i])
                    break
                except SlotsExhausted:
                    # the batch is wider than the engine (or concurrent
                    # serving traffic holds every slot): wait for capacity
                    # instead of shedding our own batch
                    if outstanding:
                        outstanding.pop(0).done.wait()
                    else:
                        time.sleep(0.005)
                except PagePoolExhausted as ex:
                    out[i] = ShedReply(str(ex))
                    break
        for i in range(n):
            h = handles[i]
            if h is None:
                continue
            h.done.wait()
            if h.status == "ok":
                pre_s = max(0.0, float(age[i])) if age is not None else 0.0
                out[i] = self._reply_body(
                    h.tokens, None if h.ttft_s is None
                    else pre_s + h.ttft_s)
            else:
                out[i] = ShedReply(f"decode {h.status}")

    def _transform(self, df: DataFrame) -> DataFrame:
        def per_part(p):
            col = p[self.input_col]
            n = len(col)
            out = np.empty(n, dtype=object)
            if n == 0:
                return {**p, self.reply_col: out}
            age = p.get("_enq_age_s") if hasattr(p, "get") else None
            if self.mode == "decode" and self.continuous:
                self._decode_batch_continuous(col, n, out, age)
            elif self.mode == "decode":
                self._decode_batch(col, n, out, age)
            else:
                x = np.stack([self.prepare(v) for v in col])
                y = self.runner.apply_batch(x, front="serving")
                for i in range(n):
                    out[i] = self.encode(y[i])
            return {**p, self.reply_col: out}

        return df.map_partitions(per_part)

    def transform_schema(self, schema):
        schema.require(self.input_col)
        return schema.add(self.reply_col, ColumnType.VECTOR)
