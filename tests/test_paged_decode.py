"""Paged KV cache + donated decode steps (ISSUE 12).

The acceptance contracts this file pins:

- paged-vs-dense parity: greedy tokens BIT-IDENTICAL to the dense path
  across ragged lengths, eos early-stop, page-boundary crossings, and pad
  rows; logits within the committed fp tolerance with ``collect_logits``
  (the non-fused host-sampling path);
- pool accounting: allocation by TRUE length (pad rows never hold pages),
  free-on-eos returns pages mid-flight (proven by a pool that can only
  serve the batch if it does), and a pool sized for N tokens serves a
  concurrency the dense max-length reservation provably cannot (>= 4x);
- executable-key collapse: the paged step is keyed on (batch bucket, page
  size, table width) — cache length is no longer a compile key, so decode
  signatures that differ only in reservation share one executable;
- donation safety: the step loop never reuses a donated (consumed) buffer
  reference — each dispatch consumes exactly the previous dispatch's
  output, stale references die, and the live cache-buffer count stays
  O(1) in the number of steps (the CPU stand-in for "no per-step
  full-cache allocation"; the on-chip bytes number is not measured).
"""
import gc
import weakref

import numpy as np
import pytest

#: committed fp tolerance for decode logits parity (f32; matches
#: tests/test_model_runner.py::DECODE_ATOL)
DECODE_ATOL = 1e-4


def _tiny_lm(vocab=48, layers=2, seed=0, max_len=128, embed_dim=32,
             num_heads=2):
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import TransformerEncoder
    mod = TransformerEncoder(vocab_size=vocab, num_classes=vocab,
                             embed_dim=embed_dim, num_heads=num_heads,
                             num_layers=layers, mlp_dim=64, max_len=max_len,
                             causal=True, pool="none")
    variables = mod.init(jax.random.PRNGKey(seed),
                         jnp.zeros((1, 4), jnp.int32))
    return mod, variables


def _runner(name, layers=2, registry=None, **width):
    from mmlspark_tpu.models import ModelRunner
    mod, variables = _tiny_lm(layers=layers, **width)
    return ModelRunner(module=mod, variables=variables, name=name,
                       registry=registry)


#: the pure-parity tests share one runner a width (warm dense executables
#: across tests); tests that assert counters or compile deltas build their
#: own
_SHARED = {}

#: (embed_dim, num_heads) of the paged-against-dense checks: the slab's
#: minor axis ``heads * head_dim`` under one 128-lane row, NOT in whole
#: lane rows with an odd head count (GPT-2 XL's case: 25 heads of 64 are
#: 12.5 rows), and exactly one row
WIDTHS = [(32, 2), (80, 5), (128, 2)]


def _shared_runner(embed_dim=32, num_heads=2):
    runner = _SHARED.get((embed_dim, num_heads))
    if runner is None:
        runner = _SHARED[embed_dim, num_heads] = _runner(
            f"paged.shared.{embed_dim}", embed_dim=embed_dim,
            num_heads=num_heads)
    return runner


# ---------------------------------------------------------------------------
# paged-vs-dense parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("embed_dim,num_heads", WIDTHS)
@pytest.mark.parametrize("page_size", [3, 8])
def test_paged_greedy_tokens_bit_identical_across_ragged_lengths(
        page_size, embed_dim, num_heads):
    """The acceptance gate: greedy generation through the paged cache emits
    the SAME token ids as the dense reservation — ragged prompts, a pad
    row (B=3 buckets to 4), and decode frontiers that cross page
    boundaries (max_new_tokens=9 crosses every page_size here)."""
    runner = _shared_runner(embed_dim, num_heads)
    rng = np.random.default_rng(1)
    lengths = np.asarray([7, 4, 2], np.int32)
    prompts = rng.integers(0, 48, (3, 7)).astype(np.int32)
    dense = runner.decode(prompts, lengths=lengths, max_new_tokens=9)
    paged = runner.decode(prompts, lengths=lengths, max_new_tokens=9,
                          kv_layout="paged", page_size=page_size)
    np.testing.assert_array_equal(dense.tokens, paged.tokens)
    assert paged.extras["kv_layout"] == "paged"
    assert paged.extras["page_size"] == page_size
    assert dense.extras["kv_layout"] == "dense"
    # the paged run held strictly less cache memory per sequence than the
    # dense max-length reservation it replaces
    assert paged.extras["cache_bytes_per_seq"] < \
        dense.extras["cache_bytes_per_seq"]


def test_paged_eos_early_stop_matches_dense():
    """eos freezing + early exit behave identically in both layouts, on
    the fused on-device sampling path (sample_fn=None)."""
    runner = _shared_runner()
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, 48, (3, 6)).astype(np.int32)
    lengths = np.asarray([6, 3, 1], np.int32)
    dense = runner.decode(prompts, lengths=lengths, max_new_tokens=8,
                          eos_id=0)
    paged = runner.decode(prompts, lengths=lengths, max_new_tokens=8,
                          eos_id=0, kv_layout="paged", page_size=4)
    np.testing.assert_array_equal(dense.tokens, paged.tokens)
    assert dense.steps == paged.steps
    assert dense.extras["real_tokens"] == paged.extras["real_tokens"]


@pytest.mark.parametrize("embed_dim,num_heads", WIDTHS)
def test_paged_logits_match_dense_within_committed_atol(embed_dim, num_heads):
    """collect_logits rides the host-sampling (non-fused) path: the full
    per-step distributions must agree within the committed tolerance, and
    the sampled tokens must still match exactly."""
    runner = _shared_runner(embed_dim, num_heads)
    rng = np.random.default_rng(2)
    lengths = np.asarray([7, 4, 2], np.int32)
    prompts = rng.integers(0, 48, (3, 7)).astype(np.int32)
    dense = runner.decode(prompts, lengths=lengths, max_new_tokens=6,
                          collect_logits=True)
    paged = runner.decode(prompts, lengths=lengths, max_new_tokens=6,
                          collect_logits=True, kv_layout="paged",
                          page_size=4)
    np.testing.assert_array_equal(dense.tokens, paged.tokens)
    np.testing.assert_allclose(dense.logits, paged.logits, atol=DECODE_ATOL)
    # and the fused on-device sampler agrees with host argmax sampling
    fused = runner.decode(prompts, lengths=lengths, max_new_tokens=6)
    np.testing.assert_array_equal(fused.tokens, dense.tokens)
    # eos + collect_logits: frozen rows stay LIVE under collect_logits (no
    # mid-flight free), so even post-freeze distributions match dense —
    # the audit path never records trash-page garbage
    def sf(lg):
        sf.t += 1
        out = np.argmax(lg, axis=-1)
        if sf.t >= 1:
            out[0] = 0                         # row 0 freezes at step 1
        return out
    kw = dict(lengths=lengths, max_new_tokens=6, eos_id=0,
              collect_logits=True)
    sf.t = -1
    de = runner.decode(prompts, sample_fn=sf, **kw)
    sf.t = -1
    pe = runner.decode(prompts, sample_fn=sf, kv_layout="paged",
                       page_size=4, **kw)
    np.testing.assert_array_equal(de.tokens, pe.tokens)
    np.testing.assert_allclose(de.logits, pe.logits, atol=DECODE_ATOL)


# ---------------------------------------------------------------------------
# pool accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("embed_dim,num_heads", WIDTHS)
def test_paged_slabs_are_pages_by_slots_by_merged_heads(embed_dim, num_heads):
    """The ONE paged layout: per layer a (k, v) pair of 3-D slabs
    ``(pages, page_size, heads * head_dim)``, heads merged into the minor
    axis (``models/transformer.py``'s header says why), and the pool's
    bytes a page are exactly layers x 2 x page_size x C x itemsize."""
    from mmlspark_tpu.models import PagePool
    mod, _ = _tiny_lm(layers=3, embed_dim=embed_dim, num_heads=num_heads)
    pages, ps = 5, 4
    cache = mod.init_paged_cache(pages, ps)
    assert len(cache) == 3 and all(len(kv) == 2 for kv in cache)
    for slab in (s for kv in cache for s in kv):
        assert slab.shape == (pages, ps, embed_dim)
    pool = PagePool(mod, num_pages=pages, page_size=ps, name="paged.shape")
    assert pool.page_nbytes() == 0                  # no slabs built yet
    pool.return_cache(pool.borrow_cache())
    assert pool.page_nbytes() == 3 * 2 * ps * embed_dim * 4


def test_pad_rows_never_allocate_pages():
    """B=3 buckets to 4: the pad row is born finished and must never hold
    pool pages — prefill allocation is exactly sum(ceil(true_len / ps))
    over REAL rows."""
    from mmlspark_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    runner = _runner("paged.pads", layers=1, registry=reg)
    lengths = np.asarray([7, 4, 1], np.int32)
    prompts = np.random.default_rng(4).integers(0, 48, (3, 7)).astype(np.int32)
    ps = 4
    res = runner.decode(prompts, lengths=lengths, max_new_tokens=3,
                        kv_layout="paged", page_size=ps)
    expect = sum(-(-int(l) // ps) for l in lengths)        # 2 + 1 + 1
    assert res.extras["pages_prefill"] == expect
    fam = reg.family("mmlspark_runner_page_ops_total")
    alloc = fam.labels(runner="paged.pads", page_size="4",
                       op="allocate").value
    free = fam.labels(runner="paged.pads", page_size="4", op="free").value
    extend = fam.labels(runner="paged.pads", page_size="4",
                        op="extend").value
    assert alloc == expect
    # every page handed out came back (completion frees everything)
    assert free == alloc + extend
    pool = runner.page_pool(ps)
    assert pool.pages_in_use() == 0 and pool.high_water > 0


def test_free_on_eos_returns_pages_midflight():
    """A pool sized so the batch can ONLY complete if eos frees pages
    mid-decode: row 0 finishes at step 0 and its 2 pages are what row 1's
    later page-boundary extends consume.  If free-on-eos regressed, the
    extend raises pool-exhausted."""
    from mmlspark_tpu.models import PagePool
    from mmlspark_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    runner = _runner("paged.eosfree", layers=1, registry=reg)
    pool = PagePool(runner.module, num_pages=6, page_size=2,
                    name="paged.eosfree", registry=reg)
    prompts = np.random.default_rng(5).integers(1, 48, (2, 4)).astype(np.int32)
    lengths = np.asarray([4, 3], np.int32)

    def sf(lg):
        sf.t += 1
        out = np.full(lg.shape[0], 7, np.int64)
        if sf.t == 0:
            out[0] = 0                       # row 0 emits eos immediately
        return out
    sf.t = -1

    # row 0 holds 2 pages, row 1 holds 2: 4 of 5 in use at prefill.  Row 1
    # extends at frontiers 4 and 6 (2 more pages) — only coverable because
    # row 0's eos at step 0 returned its 2 pages.
    res = runner.decode(prompts, lengths=lengths, max_new_tokens=5,
                        eos_id=0, sample_fn=sf, pool=pool)
    assert list(res.tokens[0]) == [7, 0, 0, 0, 0] or \
        list(res.tokens[0])[1:] == [0] * 4     # frozen after its eos
    assert (res.tokens[1] == 7).all()
    fam = reg.family("mmlspark_runner_page_ops_total")
    ops = {op: fam.labels(runner="paged.eosfree", page_size="2",
                          op=op).value
           for op in ("allocate", "extend", "free")}
    assert ops == {"allocate": 4, "extend": 2, "free": 6}
    assert pool.pages_in_use() == 0


def test_pool_sized_for_n_tokens_serves_4x_dense_concurrency():
    """The concurrency acceptance gate: under a FIXED cache HBM budget of
    N = 256 token slots, the dense max-length reservation (cache_len=64,
    the serving ceiling) admits 256/64 = 4 sequences; the paged pool runs
    a 16-sequence batch through the SAME budget — >= 4x — because pages
    track actual lengths (16 tokens/seq here), and the tokens still match
    the dense path bit-for-bit."""
    runner = _runner("paged.conc", layers=1)
    from mmlspark_tpu.models import PagePool
    ps, n_tokens = 8, 256
    pool = PagePool(runner.module, num_pages=n_tokens // ps + 1,
                    page_size=ps, name="paged.conc")
    assert pool.token_capacity() == n_tokens
    B = 16
    prompts = np.random.default_rng(6).integers(0, 48, (B, 8)).astype(np.int32)
    dense_reservation = 64                    # slots/seq the dense path holds
    dense_concurrency = n_tokens // dense_reservation
    res = runner.decode(prompts, max_new_tokens=8, pool=pool)
    assert res.tokens.shape == (B, 8)
    assert B >= 4 * dense_concurrency
    # worst case actually fit the budget: every page came from the pool
    assert res.extras["pages_peak"] <= pool.capacity
    # the dense path at the same per-sequence reservation yields the same
    # tokens — the budget win is free of accuracy cost
    dense = runner.decode(prompts, max_new_tokens=8,
                          cache_len=dense_reservation)
    np.testing.assert_array_equal(dense.tokens, res.tokens)
    # and the dense reservation provably blows the budget: B seqs at 64
    # slots each need 4x the pool
    assert B * dense_reservation == 4 * n_tokens


def test_pool_validation_and_accounting_standalone():
    from mmlspark_tpu.models import PagePool

    with pytest.raises(ValueError, match="trash page"):
        PagePool(None, num_pages=1, page_size=4)
    pool = PagePool(None, num_pages=5, page_size=4, name="acct")
    assert pool.capacity == 4 and pool.token_capacity() == 16
    pages = pool.allocate(3)
    assert 0 not in pages                     # trash page never handed out
    assert pool.pages_in_use() == 3 and pool.high_water == 3
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.allocate(2)
    pool.free(pages[:2])
    assert pool.pages_in_use() == 1 and pool.high_water == 3
    with pytest.raises(ValueError, match="trash"):
        pool.free([0])
    with pytest.raises(TypeError, match="without a module"):
        pool.borrow_cache()


def test_auto_pool_grows_for_larger_batches_but_budgets_do_not():
    """A pool the runner sized implicitly (no budget given) must not trap
    later, larger batches at the first call's worst case — it grows.  An
    explicitly budgeted pool stays fixed (its exhaustion IS the admission
    control), and `page_pool(num_pages=)` is the working resize hatch."""
    runner = _runner("paged.grow", layers=1)
    rng = np.random.default_rng(11)
    small = rng.integers(0, 48, (2, 4)).astype(np.int32)
    runner.decode(small, max_new_tokens=4, kv_layout="paged", page_size=8)
    n0 = runner.page_pool(8).num_pages
    big = rng.integers(0, 48, (8, 4)).astype(np.int32)
    res = runner.decode(big, max_new_tokens=4, kv_layout="paged",
                        page_size=8)                       # must not raise
    assert res.tokens.shape == (8, 4)
    assert runner.page_pool(8).num_pages > n0
    # explicit resize hatch replaces the idle pool...
    pool = runner.page_pool(8, num_pages=64)
    assert pool.num_pages == 64 and runner.page_pool(8) is pool
    # ...and an explicitly budgeted pool is NOT auto-grown: way too small
    # for the batch, so the decode must surface exhaustion, not resize
    runner.page_pool(8, num_pages=3)
    with pytest.raises(RuntimeError, match="exhausted"):
        runner.decode(big, max_new_tokens=4, kv_layout="paged", page_size=8)
    # a busy pool refuses to resize
    held = runner.page_pool(8)
    held.allocate(1)
    with pytest.raises(RuntimeError, match="busy"):
        held.resized(128)


def test_cache_len_is_rejected_for_paged_layout():
    runner = _runner("paged.args", layers=1)
    prompts = np.zeros((2, 4), np.int32) + 3
    with pytest.raises(ValueError, match="dense-layout parameter"):
        runner.decode(prompts, max_new_tokens=2, kv_layout="paged",
                      cache_len=64)
    # and the dense validation message now names the reservation + escape
    with pytest.raises(ValueError, match="paged"):
        runner.decode(prompts, max_new_tokens=8, cache_len=4)


# ---------------------------------------------------------------------------
# executable keys: cache length stops being a compile dimension
# ---------------------------------------------------------------------------

def test_paged_step_collapses_cache_len_executable_fanout():
    """Dense decode keys its step on cache_len, so reservations that
    differ only in length compile separate executables; the paged step is
    keyed on (batch bucket, page size, table width) and serves both from
    one program."""
    runner = _runner("paged.keys", layers=1)
    prompts = np.random.default_rng(7).integers(0, 48, (3, 8)).astype(np.int32)
    # paged: max_new 8 and 24 share table_w = ceil((8+max_new)/32) = 1
    runner.decode(prompts, max_new_tokens=8, kv_layout="paged",
                  page_size=32)
    n_paged = runner.compile_stats()["compiles"]
    runner.decode(prompts, max_new_tokens=24, kv_layout="paged",
                  page_size=32)
    assert runner.compile_stats()["compiles"] == n_paged, \
        "paged decode recompiled despite identical page geometry"
    # dense: the same two calls land on different cache_len keys (16 vs 32)
    runner.decode(prompts, max_new_tokens=8)
    n_dense = runner.compile_stats()["compiles"]
    runner.decode(prompts, max_new_tokens=24)
    assert runner.compile_stats()["compiles"] == n_dense + 2, \
        "expected a fresh dense prefill+step pair per cache_len"
    keys = runner.compile_stats()["executables"]
    assert any("step_paged" in k for k in keys)


# ---------------------------------------------------------------------------
# donation safety (the class of crash donation introduces)
# ---------------------------------------------------------------------------

def _spy_step(runner, key):
    """Wrap a step executable: assert each dispatch consumes EXACTLY the
    previous dispatch's output cache (no stale donated references), and
    record weakrefs so retention is observable after the loop."""
    import jax
    real = runner._executables[key]
    state = {"prev": None, "stale": [], "live_peak": 0,
             "leaf_shape": None}

    def spy(*args):
        cache = args[-1]
        leaves = jax.tree_util.tree_leaves(cache)
        state["leaf_shape"] = leaves[0].shape
        if state["prev"] is not None:
            assert all(a is b for a, b in zip(leaves, state["prev"])), \
                ("step dispatched with a cache that is NOT the previous "
                 "step's output — a stale reference to a donated buffer")
        live = sum(1 for a in jax.live_arrays()
                   if getattr(a, "shape", None) == leaves[0].shape)
        state["live_peak"] = max(state["live_peak"], live)
        out = real(*args)
        state["stale"].append([weakref.ref(l) for l in leaves])
        state["prev"] = jax.tree_util.tree_leaves(out[-1])
        return out

    runner._executables[key] = spy
    return real, state


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_step_loop_never_reuses_donated_buffers(layout):
    """ISSUE 12 regression gate: the decode loop rebinds cache/finished
    from each step's outputs and drops the consumed references — the
    identity chain is unbroken, stale buffers become garbage, and the
    number of live cache-shaped buffers stays O(1) across the loop (the
    CPU assertion that the donated step does not allocate a fresh full
    cache per token; on-chip bytes are not measured)."""
    runner = _runner(f"paged.donate.{layout}", layers=2)
    prompts = np.random.default_rng(8).integers(0, 48, (3, 6)).astype(np.int32)
    kw = {"kv_layout": "paged", "page_size": 4} if layout == "paged" else {}
    runner.decode(prompts, max_new_tokens=8, **kw)       # bind executables
    prefix = "step_paged" if layout == "paged" else "step"
    key = next(k for k in runner._executables if k[0] == prefix)
    real, state = _spy_step(runner, key)
    try:
        runner.decode(prompts, max_new_tokens=8, **kw)
    finally:
        runner._executables[key] = real
    assert len(state["stale"]) >= 6
    state["prev"] = None
    gc.collect()
    dead = [all(r() is None for r in refs) for refs in state["stale"][:-1]]
    assert all(dead), \
        "decode retained references to donated (consumed) cache buffers"
    n_leaves = 2 * runner.module.num_layers
    # at most the in-flight generation + its predecessor exist at once
    assert state["live_peak"] <= 2 * (n_leaves // runner.module.num_layers) \
        * runner.module.num_layers, \
        f"live cache buffers grew with steps: {state['live_peak']}"


def test_decode_tokens_counter_counts_unfrozen_steps_only():
    """ISSUE 12 bugfix: `mmlspark_runner_decode_tokens_total` charges
    per-sequence REAL tokens.  Row 0 finishes at step 0, so 4 steps of a
    2-row batch generate 1*2 + 3*1 = 5 tokens — the old B*n_generated
    charge said 8, inflating fleet tokens/sec on early-finishing
    batches."""
    from mmlspark_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    runner = _runner("paged.count", layers=1, registry=reg)
    prompts = np.random.default_rng(9).integers(1, 48, (2, 4)).astype(np.int32)

    def sf(lg):
        sf.t += 1
        out = np.full(lg.shape[0], 7, np.int64)
        if sf.t == 0:
            out[0] = 0
        return out
    sf.t = -1

    res = runner.decode(prompts, max_new_tokens=4, eos_id=0, sample_fn=sf)
    fam = reg.family("mmlspark_runner_decode_tokens_total")
    val = fam.labels(runner="paged.count").value
    assert val == 5.0, f"expected 5 real tokens booked, got {val}"
    assert res.extras["real_tokens"] == 5
    # pad rows never count either (fused path): 3 real rows bucket to 4
    reg2 = MetricsRegistry()
    runner2 = _runner("paged.count2", layers=1, registry=reg2)
    p3 = np.random.default_rng(10).integers(0, 48, (3, 4)).astype(np.int32)
    runner2.decode(p3, max_new_tokens=5)
    fam2 = reg2.family("mmlspark_runner_decode_tokens_total")
    assert fam2.labels(runner="paged.count2").value == 15.0   # 3 * 5, not 4*5
