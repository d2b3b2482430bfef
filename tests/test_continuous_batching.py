"""Continuous batching: slot-level join/leave on the paged pool (ISSUE 13).

The acceptance contracts this file pins:

- continuous-vs-one-shot greedy BIT-parity: tokens from the in-flight
  engine equal one-shot ``decode()`` for every request, across ragged
  arrivals (joins mid-flight into reused slots), eos leaves, and
  page-boundary joins/extends — and joins after warmup cause ZERO new
  step-executable compiles (the no-new-compile-keys rule);
- slot-reuse accounting: a freed slot's pages are reusable while the
  batch keeps running (a pool sized so later requests only fit if leaves
  free mid-flight), and occupancy returns to zero at quiescence;
- admission control: slot/page exhaustion raises shed-typed errors at
  submit (503 in serving), a budgeted pool exhausting MID-decode yields a
  clean partial result (one-shot) / a ``denied`` leave (stream) with
  ``page_ops_total{op="denied"}`` booked — never an exception out of the
  scorer thread;
- FakeClock TTFT/occupancy metric semantics, and the serving fronts end
  to end over real sockets: per-request replies from the in-flight batch,
  in-band ``ttft_ms``, and the ``mixed_load`` ttft gate passing on a
  continuous server at a load where the ticked drain fails it.
"""
import http.client
import json
import os
import threading
import time

import numpy as np
import pytest


def post_json(port, path, obj, timeout=30, return_headers=False,
              method_get=False):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    if method_get:
        conn.request("GET", path)
    else:
        conn.request("POST", path, json.dumps(obj),
                     {"Content-Type": "application/json"})
    resp = conn.getresponse()
    raw = resp.read().decode()
    conn.close()
    body = raw if method_get else json.loads(raw)
    if return_headers:
        return resp.status, body, dict(resp.getheaders())
    return resp.status, body


def _tiny_lm(vocab=48, layers=2, seed=0, max_len=128):
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import TransformerEncoder
    mod = TransformerEncoder(vocab_size=vocab, num_classes=vocab,
                             embed_dim=32, num_heads=2, num_layers=layers,
                             mlp_dim=64, max_len=max_len, causal=True,
                             pool="none")
    variables = mod.init(jax.random.PRNGKey(seed),
                         jnp.zeros((1, 4), jnp.int32))
    return mod, variables


def _runner(name, layers=2, registry=None):
    from mmlspark_tpu.models import ModelRunner
    mod, variables = _tiny_lm(layers=layers)
    return ModelRunner(module=mod, variables=variables, name=name,
                      registry=registry)


#: parity tests share one runner so executables stay warm across tests
_SHARED = {}


def _shared_runner():
    runner = _SHARED.get("runner")
    if runner is None:
        runner = _SHARED["runner"] = _runner("cont.shared", layers=1)
    return runner


def _drain(dec, pending=None):
    """Drive a (non-started) decoder to quiescence, submitting ``pending``
    [(prompt, budget)] with backpressure (wait for a leave on
    SlotsExhausted)."""
    from mmlspark_tpu.models import SlotsExhausted
    handles = []
    pending = list(pending or [])
    while pending or dec._arrivals or dec._live:
        while pending:
            try:
                p, b = pending[0]
                handles.append(dec.submit(p, max_new_tokens=b))
                pending.pop(0)
            except SlotsExhausted:
                break
        dec.step()
    return handles


# ---------------------------------------------------------------------------
# bit-parity + the no-new-compile-keys rule
# ---------------------------------------------------------------------------

def test_continuous_bit_identical_to_one_shot_across_ragged_arrivals():
    """The acceptance gate: requests joining the in-flight batch at
    arbitrary step boundaries — including REUSED slots whose previous
    owner's pages went back to the pool, and prompts/budgets that cross
    page boundaries (page_size=4) — generate tokens BIT-identical to
    one-shot ``decode()`` of each prompt alone.  And the whole trace,
    joins included, causes zero new step-executable compiles after
    warmup."""
    runner = _shared_runner()
    dec = runner.decode_stream(slots=4, prompt_bucket=8, max_new_tokens=9,
                               page_size=4)
    dec.warmup()
    n0 = runner.compile_stats()["compiles"]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 48, int(rng.integers(2, 9))).astype(np.int32)
               for _ in range(8)]
    budgets = [9, 4, 7, 2, 9, 5, 3, 8]
    handles = _drain(dec, list(zip(prompts, budgets)))
    assert runner.compile_stats()["compiles"] == n0, \
        "a join minted a new compile key"
    slots_used = {h.slot for h in handles}
    assert len(handles) == 8 and len(slots_used) <= 4  # slots were reused
    for p, b, h in zip(prompts, budgets, handles):
        assert h.status == "ok"
        ref = runner.decode(p[None], max_new_tokens=b, kv_layout="paged",
                            page_size=4)
        np.testing.assert_array_equal(np.asarray(h.tokens), ref.tokens[0])
        # result() round-trips the same tokens as a DecodeResult
        np.testing.assert_array_equal(h.result(timeout=1).tokens[0],
                                      ref.tokens[0])
    dec.close()


def test_eos_leave_matches_one_shot_and_frees_the_slot():
    """An eos mid-generation leaves the slot immediately (one-shot keeps
    dispatching frozen rows; the stream's truncation-at-freeze is the same
    token sequence), and the freed slot takes the next arrival while the
    other slot keeps decoding."""
    runner = _shared_runner()
    rng = np.random.default_rng(3)
    p = rng.integers(0, 48, 6).astype(np.int32)
    # pick the token the model actually emits as the eos id, so greedy
    # deterministically "finishes" mid-generation
    probe = runner.decode(p[None], max_new_tokens=8, kv_layout="paged",
                          page_size=4)
    eos = int(probe.tokens[0][2])               # freezes at the 3rd token
    ref = runner.decode(p[None], max_new_tokens=8, eos_id=eos,
                        kv_layout="paged", page_size=4)
    dec = runner.decode_stream(slots=2, prompt_bucket=8, max_new_tokens=8,
                               eos_id=eos, page_size=4)
    q = rng.integers(0, 48, 5).astype(np.int32)
    h1 = dec.submit(p, max_new_tokens=8)
    h2 = dec.submit(q, max_new_tokens=8)
    seen_free = False
    h3 = None
    while dec._arrivals or dec._live:
        dec.step()
        if h1.done.is_set() and h3 is None and dec._live:
            seen_free = True                   # h2 still decoding
            h3 = dec.submit(q, max_new_tokens=8)
    assert h1.status == "ok" and seen_free and h3 is not None
    np.testing.assert_array_equal(np.asarray(h1.tokens),
                                  ref.tokens[0][:len(h1.tokens)])
    # the stream stops at the freeze; one-shot pads frozen rows with eos
    assert h1.tokens[-1] == eos
    assert set(ref.tokens[0][len(h1.tokens):].tolist()) <= {eos}
    ref_q = runner.decode(q[None], max_new_tokens=8, eos_id=eos,
                          kv_layout="paged", page_size=4)
    for h in (h2, h3):
        assert h.done.wait(1) and h.status == "ok"
        np.testing.assert_array_equal(np.asarray(h.tokens),
                                      ref_q.tokens[0][:len(h.tokens)])
    dec.close()


# ---------------------------------------------------------------------------
# slot reuse / pool accounting
# ---------------------------------------------------------------------------

def test_freed_slot_pages_fund_later_requests_while_batch_runs():
    """A pool sized so the trace only completes if leaves free pages
    MID-flight: request A (short budget) leaves while B keeps decoding,
    and A's pages are what C's prefill + B's later extends consume."""
    from mmlspark_tpu.models import PagePool
    from mmlspark_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    runner = _runner("cont.reuse", layers=1, registry=reg)
    pool = PagePool(runner.module, num_pages=7, page_size=2,
                    name="cont.reuse", registry=reg)
    dec = runner.decode_stream(slots=2, prompt_bucket=4, max_new_tokens=6,
                               pool=pool)
    rng = np.random.default_rng(5)
    A = rng.integers(0, 48, 4).astype(np.int32)   # 2 pages at prefill
    B = rng.integers(0, 48, 4).astype(np.int32)   # 2 pages + extends
    C = rng.integers(0, 48, 4).astype(np.int32)   # needs A's freed pages
    hA = dec.submit(A, max_new_tokens=2)           # leaves after 1 step
    hB = dec.submit(B, max_new_tokens=6)           # 4 of 6 pages held
    hC = None
    while dec._arrivals or dec._live:
        dec.step()
        if hA.done.is_set() and hC is None:
            hC = dec.submit(C, max_new_tokens=2)   # only fits if A freed
    assert hA.status == hB.status == hC.status == "ok"
    for p, b, h in ((A, 2, hA), (B, 6, hB), (C, 2, hC)):
        ref = runner.decode(p[None], max_new_tokens=b, kv_layout="paged",
                            page_size=2, pool=pool)
        np.testing.assert_array_equal(np.asarray(h.tokens), ref.tokens[0])
    assert pool.pages_in_use() == 0 and pool.high_water <= pool.capacity
    fam = reg.family("mmlspark_runner_page_ops_total")
    ops = {op: fam.labels(runner="cont.reuse", page_size="2", op=op).value
           for op in ("allocate", "extend", "free", "denied")}
    assert ops["denied"] == 0
    assert ops["free"] == ops["allocate"] + ops["extend"]
    dec.close()


def test_admission_control_sheds_on_slots_and_pages():
    """submit() is the admission decision: no free slot raises
    SlotsExhausted, an unfundable prompt raises PagePoolExhausted with the
    denial booked as op="denied" — both carry the serving layer's shed
    duck-type."""
    from mmlspark_tpu.models import (PagePool, PagePoolExhausted,
                                     SlotsExhausted)
    from mmlspark_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    runner = _runner("cont.admit", layers=1, registry=reg)
    pool = PagePool(runner.module, num_pages=4, page_size=2,
                    name="cont.admit", registry=reg)
    dec = runner.decode_stream(slots=2, prompt_bucket=4, max_new_tokens=2,
                               pool=pool)
    p = np.asarray([1, 2, 3, 4], np.int32)
    dec.submit(p)
    dec.submit(np.asarray([1], np.int32))
    with pytest.raises(SlotsExhausted) as ei:
        dec.submit(p)
    assert getattr(ei.value, "shed", False) is True
    assert dec.occupancy() == 2
    dec.close()   # cancelled arrivals release their slots + pages
    assert pool.pages_in_use() == 0 and dec.occupancy() == 0
    # page admission: 2 slots free but the pool can't fund a 2-page prompt
    dec2 = runner.decode_stream(slots=2, prompt_bucket=4, max_new_tokens=2,
                                pool=pool)
    pool.allocate(2)                              # external hold
    with pytest.raises(PagePoolExhausted) as ei2:
        dec2.submit(p)                            # needs 2, 1 free
    assert getattr(ei2.value, "shed", False) is True
    fam = reg.family("mmlspark_runner_page_ops_total")
    assert fam.labels(runner="cont.admit", page_size="2",
                      op="denied").value == 2.0
    assert dec2.occupancy() == 0                  # failed submit holds nothing
    dec2.close()


def test_idle_stream_adopts_resized_pool():
    """Review regression: `page_pool(num_pages=)` (and auto-pool growth)
    REPLACE the runner's pool object — a stream keeping the old reference
    would allocate from an orphaned budget, the operator's resize silently
    not applying.  An idle stream re-binds at its next submit."""
    runner = _runner("cont.resize", layers=1)
    dec = runner.decode_stream(slots=2, prompt_bucket=4, max_new_tokens=2,
                               page_size=2)
    h = dec.submit(np.asarray([1, 2], np.int32))
    _drain(dec)
    assert h.status == "ok"
    old = dec.pool
    new = runner.page_pool(2, num_pages=64)       # operator resize hatch
    assert new is not old and new.num_pages == 64
    h2 = dec.submit(np.asarray([3, 4], np.int32))
    assert dec.pool is new and new.pages_in_use() > 0
    _drain(dec)
    assert h2.status == "ok" and new.pages_in_use() == 0
    dec.close()


# ---------------------------------------------------------------------------
# mid-decode pool exhaustion: clean partial results (ISSUE 13 bugfix)
# ---------------------------------------------------------------------------

def test_budgeted_pool_exhausting_mid_decode_yields_partial_result():
    """One-shot half of the satellite bugfix: an explicitly budgeted pool
    that cannot fund a page-boundary extend FREEZES the row — tokens up to
    the denial match the unconstrained run, the tail is eos padding, the
    denial is booked, and nothing raises out of the decode."""
    from mmlspark_tpu.models import PagePool
    from mmlspark_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    runner = _runner("cont.partial", layers=1, registry=reg)
    free = runner.decode(np.asarray([[3, 1, 4, 1]], np.int32),
                         max_new_tokens=6, kv_layout="paged", page_size=2)
    # 2 prefill pages + ZERO headroom: the first extend (frontier at
    # position 4) must be denied
    pool = PagePool(runner.module, num_pages=3, page_size=2,
                    name="cont.partial", registry=reg)
    res = runner.decode(np.asarray([[3, 1, 4, 1]], np.int32),
                        max_new_tokens=6, pool=pool)
    assert res.extras["denied_rows"] == [0]
    cut = res.extras["denied_at"][0]
    assert 1 <= cut < 6
    np.testing.assert_array_equal(res.tokens[0][:cut], free.tokens[0][:cut])
    assert set(res.tokens[0][cut:].tolist()) <= {0}      # clean eos/0 tail
    assert pool.pages_in_use() == 0                      # denial freed them
    fam = reg.family("mmlspark_runner_page_ops_total")
    assert fam.labels(runner="cont.partial", page_size="2",
                      op="denied").value > 0


def test_fused_path_denial_stays_frozen_and_tokens_stay_honest():
    """Review regression: on the FUSED path the device-resident finished
    mask never learns of a host-side page denial — without folding it back
    in, the denied row thaws on the next device fetch, its trash-page
    tokens re-inflate `real_tokens`/`mmlspark_runner_decode_tokens_total`
    (the exact inflation the PR 12 bugfix removed), and the eos early-exit
    can never fire.  Two rows, fused greedy, a pool that denies one row's
    first extend: the denied row must contribute exactly its pre-denial
    token to the counters while the survivor completes its full budget."""
    from mmlspark_tpu.models import PagePool
    from mmlspark_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    runner = _runner("cont.thaw", layers=1, registry=reg)
    prompts = np.random.default_rng(7).integers(0, 48, (2, 4)).astype(np.int32)
    free = runner.decode(prompts, max_new_tokens=6, kv_layout="paged",
                         page_size=2)
    # capacity 5: prefill holds 2+2, row 0 takes the free page at the
    # first extend, row 1 is DENIED there (cut=1); its freed pages fund
    # row 0's remaining extends
    pool = PagePool(runner.module, num_pages=6, page_size=2,
                    name="cont.thaw", registry=reg)
    fam = reg.family("mmlspark_runner_decode_tokens_total")
    before = fam.labels(runner="cont.thaw").value
    res = runner.decode(prompts, max_new_tokens=6, pool=pool)
    assert res.extras["denied_rows"] == [1]
    assert res.extras["denied_at"] == {1: 1}
    np.testing.assert_array_equal(res.tokens[0], free.tokens[0])
    np.testing.assert_array_equal(res.tokens[1][:1], free.tokens[1][:1])
    # 2 rows at t=0 + the survivor alone for t=1..5 — NOT 2*6
    assert res.extras["real_tokens"] == 7
    assert fam.labels(runner="cont.thaw").value - before == 7.0


def test_stream_mid_flight_denial_resolves_denied_and_slot_recovers():
    """Stream half: the denied slot leaves with its partial generation
    (status "denied"), its pages fund the survivors, and the slot is
    admissible again while the batch keeps running."""
    from mmlspark_tpu.models import PagePool
    from mmlspark_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    runner = _runner("cont.deny", layers=1, registry=reg)
    # capacity 5: prefill holds 2+2; the one free page funds slot 0's
    # first extend, slot 1's is DENIED — and slot 1's freed pages are
    # exactly what slot 0's remaining extends (5 pages total for a
    # 6-token budget) need to complete
    pool = PagePool(runner.module, num_pages=6, page_size=2,
                    name="cont.deny", registry=reg)
    dec = runner.decode_stream(slots=2, prompt_bucket=4, max_new_tokens=6,
                               pool=pool)
    p = np.asarray([3, 1, 4, 1], np.int32)       # 2 pages each at prefill
    hA = dec.submit(p, max_new_tokens=6)
    hB = dec.submit(p + 1, max_new_tokens=6)
    _drain(dec)
    statuses = sorted([hA.status, hB.status])
    assert statuses == ["denied", "ok"], statuses
    denied, okh = (hA, hB) if hA.status == "denied" else (hB, hA)
    assert 1 <= len(denied.tokens) < 6 and len(okh.tokens) == 6
    assert denied.result(timeout=1).extras["status"] == "denied"
    assert pool.pages_in_use() == 0
    fam = reg.family("mmlspark_runner_slots_left_total")
    assert fam.labels(runner="cont.deny", outcome="denied").value == 1.0
    assert fam.labels(runner="cont.deny", outcome="ok").value == 1.0
    dec.close()


# ---------------------------------------------------------------------------
# FakeClock TTFT + occupancy metric semantics
# ---------------------------------------------------------------------------

def test_ttft_and_occupancy_metrics_on_fake_clock():
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.utils.resilience import FakeClock

    clk = FakeClock()
    reg = MetricsRegistry()
    runner = _runner("cont.clock", layers=1, registry=reg)
    dec = runner.decode_stream(slots=4, prompt_bucket=4, max_new_tokens=3,
                               page_size=2, clock=clk)
    occ = reg.family("mmlspark_runner_slot_occupancy_pct")
    assert occ.labels(runner="cont.clock").value == 0.0
    p = np.asarray([5, 7], np.int32)
    h1 = dec.submit(p)
    h2 = dec.submit(p + 1)
    assert occ.labels(runner="cont.clock").value == 50.0   # 2 of 4 reserved
    clk.advance(0.125)                       # queue wait before the join
    dec.step()                               # join prefill = first token
    ttft = reg.family("mmlspark_runner_ttft_seconds")
    child = ttft.labels(runner="cont.clock")
    assert child.count == 2 and abs(child.sum - 0.250) < 1e-9
    assert h1.ttft_s == h2.ttft_s == 0.125
    joined = reg.family("mmlspark_runner_slots_joined_total")
    assert joined.labels(runner="cont.clock").value == 2.0
    while dec._live:
        dec.step()
    assert occ.labels(runner="cont.clock").value == 0.0
    left = reg.family("mmlspark_runner_slots_left_total")
    assert left.labels(runner="cont.clock", outcome="ok").value == 2.0
    # deadline leave on the same clock: expired before its first step
    h3 = dec.submit(p, deadline_s=clk() + 0.5)
    dec.step()                               # joins (first token emitted)
    clk.advance(1.0)
    dec.step()
    assert h3.status == "expired"
    assert left.labels(runner="cont.clock", outcome="expired").value == 1.0
    dec.close()


# ---------------------------------------------------------------------------
# serving fronts (real sockets)
# ---------------------------------------------------------------------------

def test_pipeline_server_continuous_decode_e2e():
    """PipelineServer + continuous decode scorer: replies come from the
    in-flight engine per request, bit-identical to one-shot decode, with
    in-band ttft_ms; concurrent requests share the batch."""
    from mmlspark_tpu.models import ModelRunner
    from mmlspark_tpu.serving import PipelineServer

    mod, variables = _tiny_lm(layers=1)
    runner = ModelRunner(module=mod, variables=variables, name="srv.cont")
    scorer = runner.scorer(mode="decode", continuous=True, report_ttft=True,
                           slots=4, prompt_bucket=8, max_new_tokens=4,
                           page_size=4,
                           encode=lambda t: [int(x) for x in t])
    srv = PipelineServer(scorer, port=0, mode="continuous").start()
    try:
        prompts = [[5, 7, 11], [9, 2], [1, 2, 3, 4, 5]]
        results = [None] * len(prompts)

        def fire(i):
            results[i] = post_json(srv.port, srv.api_path, prompts[i])

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, p in enumerate(prompts):
            status, reply = results[i]
            assert status == 200, reply
            ref = runner.decode(np.asarray(p, np.int32)[None],
                                max_new_tokens=4, kv_layout="paged",
                                page_size=4)
            assert reply["tokens"] == ref.tokens[0].tolist()
            assert reply["ttft_ms"] >= 0.0
    finally:
        srv.stop()
    # stop() closed the scorer's stream (engine thread + borrowed slabs)
    assert scorer._decoder is None


def test_default_encode_replies_are_json_lists_and_streaming_sheds_rows():
    """Review regressions: (a) a continuous scorer with the DEFAULT encode
    must reply a JSON list, not a numpy string repr — the deferred resolve
    path rides the server's reply_encoder exactly like the batch path;
    (b) the streaming sink maps the per-row ShedReply sentinel to a 503
    instead of encoding the sentinel object into a 200 body."""
    from mmlspark_tpu.models import ModelRunner, ShedReply
    from mmlspark_tpu.serving import PipelineServer
    from mmlspark_tpu.serving.streaming import HTTPStreamSource, _Pending

    mod, variables = _tiny_lm(layers=1)
    runner = ModelRunner(module=mod, variables=variables, name="srv.enc")
    scorer = runner.scorer(mode="decode", continuous=True, slots=2,
                           prompt_bucket=8, max_new_tokens=3, page_size=4)
    srv = PipelineServer(scorer, port=0, mode="continuous").start()
    try:
        status, reply = post_json(srv.port, srv.api_path, [5, 7, 11])
        assert status == 200
        assert isinstance(reply, list) and \
            all(isinstance(t, int) for t in reply), reply
    finally:
        srv.stop()
    src = HTTPStreamSource()
    entry = _Pending([1, 2])
    src._pending["r1"] = entry
    src.reply(["r1"], [ShedReply("page pool exhausted mid-decode")])
    assert entry.status == 503 and "shed" in entry.reply["error"]
    assert entry.done.is_set()


def test_pipeline_server_sheds_503_when_slots_exhausted():
    """Admission-control shedding end to end: with ONE slot and a slow
    generation in flight, a concurrent request sheds 503 + Retry-After
    instead of queueing behind the whole generation (or raising)."""
    from mmlspark_tpu.models import ModelRunner
    from mmlspark_tpu.serving import PipelineServer

    mod, variables = _tiny_lm(layers=1)
    runner = ModelRunner(module=mod, variables=variables, name="srv.shed")
    scorer = runner.scorer(mode="decode", continuous=True, slots=1,
                           prompt_bucket=8, max_new_tokens=96, page_size=8,
                           encode=lambda t: [int(x) for x in t])
    srv = PipelineServer(scorer, port=0, mode="continuous").start()
    try:
        done = threading.Event()
        first = {}

        def long_request():
            first["res"] = post_json(srv.port, srv.api_path,
                                     [5, 7, 11], timeout=60)
            done.set()

        t = threading.Thread(target=long_request)
        t.start()
        # wait until the long request owns the engine's only slot
        deadline = time.monotonic() + 10
        while scorer._decoder is None or scorer._decoder.occupancy() == 0:
            if time.monotonic() > deadline:
                raise AssertionError("first request never joined")
            time.sleep(0.01)
        status, reply, headers = post_json(srv.port, srv.api_path, [1, 2],
                                           return_headers=True)
        assert status == 503, reply
        assert "shed" in reply["error"]
        assert int(headers["Retry-After"]) >= 1
        assert done.wait(60) and first["res"][0] == 200
        # stats: exactly one shed, both requests counted
        st = json.loads(post_json(srv.port, "/stats", None,
                                  method_get=True)[1])
        assert st["shed"] == 1 and st["replied"] >= 1
    finally:
        srv.stop()


def test_streaming_facade_continuous_decode():
    """read_stream().transform_with(runner-scorer with continuous=True):
    rows admit into the in-flight engine from the trigger loop and reply
    per request."""
    from mmlspark_tpu.models import ModelRunner
    from mmlspark_tpu.serving import read_stream

    mod, variables = _tiny_lm(layers=1)
    runner = ModelRunner(module=mod, variables=variables, name="stream.cont")
    query = (read_stream().server(port=0)
             .transform_with(runner, mode="decode", continuous=True,
                             slots=2, prompt_bucket=8, max_new_tokens=3,
                             page_size=4,
                             encode=lambda t: [int(x) for x in t])
             .reply_to("reply"))
    try:
        status, reply = post_json(query.source.port, "/score", [3, 1, 4])
        assert status == 200
        ref = runner.decode(np.asarray([[3, 1, 4]], np.int32),
                            max_new_tokens=3, kv_layout="paged", page_size=4)
        assert reply == ref.tokens[0].tolist()
    finally:
        query.stop()


def test_mixed_load_ttft_gate_continuous_passes_where_ticked_fails():
    """The acceptance run: scoring + decode classes through mixed_load.
    Against the continuous-mode server both classes pass their gates —
    the decode class's ttft_p99_ms included.  Against the ticked drain
    (micro_batch flush tick) at the SAME load, the decode class FAILS the
    same ttft gate: no token is client-visible before the tick's batch
    resolves, so its honest TTFT is the full latency."""
    from mmlspark_tpu.core import DataFrame, Transformer
    from mmlspark_tpu.models import ModelRunner
    from mmlspark_tpu.serving import PipelineServer, mixed_load

    mod, variables = _tiny_lm(layers=1)
    lm = ModelRunner(module=mod, variables=variables, name="mix.cont")
    w = np.arange(6, dtype=np.float32).reshape(3, 2) / 10.0

    def mlp(v):
        return (np.asarray(v, np.float32) @ w + 1.0).tolist()

    dec_scorer = lm.scorer(mode="decode", continuous=True, report_ttft=True,
                           slots=4, prompt_bucket=8, max_new_tokens=3,
                           page_size=4,
                           encode=lambda t: [int(x) for x in t])
    ticked_scorer = lm.scorer(mode="decode", report_ttft=True,
                              max_new_tokens=3, kv_layout="paged",
                              page_size=4,
                              encode=lambda t: [int(x) for x in t])

    class Dispatch(Transformer):
        """One worker, two request classes: decode dicts ride the decode
        scorer (continuous protocol when the server admits continuously,
        the batch path under a ticked drain), vectors score inline."""

        def __init__(self, decode_scorer, continuous):
            super().__init__()
            self._dec = decode_scorer
            if continuous:
                self.continuous_submit = self._submit
                self.continuous_close = decode_scorer.continuous_close

        def _submit(self, payload, resolve, queue_age_s=0.0,
                    deadline_budget_s=None):
            if isinstance(payload, dict) and "decode" in payload:
                self._dec.continuous_submit(
                    payload["decode"], resolve, queue_age_s=queue_age_s,
                    deadline_budget_s=deadline_budget_s)
            else:
                resolve(reply=mlp(payload), status=200)

        def _transform(self, df):
            def per_part(p):
                col = p["request"]
                out = np.empty(len(col), dtype=object)
                dec_idx = [i for i, v in enumerate(col)
                           if isinstance(v, dict) and "decode" in v]
                if dec_idx:
                    sub_req = np.empty(len(dec_idx), dtype=object)
                    for j, i in enumerate(dec_idx):
                        sub_req[j] = col[i]["decode"]
                    sub = {"request": sub_req}
                    if "_enq_age_s" in p:
                        sub["_enq_age_s"] = np.asarray(
                            [p["_enq_age_s"][i] for i in dec_idx])
                    replies = self._dec._transform(
                        DataFrame([sub])).collect()["reply"]
                    for i, r in zip(dec_idx, replies):
                        out[i] = r
                for i, v in enumerate(col):
                    if i not in dec_idx:
                        out[i] = mlp(v)
                return {**p, "reply": out}
            return df.map_partitions(per_part)

        def transform_schema(self, schema):
            return schema

    score_body = json.dumps([1.0, 2.0, 3.0])
    decode_body = json.dumps({"decode": [3, 1, 4]})

    def run(server):
        try:
            return mixed_load("127.0.0.1", server.port, [
                {"name": "score", "path": server.api_path,
                 "body": score_body,
                 "headers": {"Content-Type": "application/json"},
                 "n_clients": 2, "per_client": 6,
                 "gates": {"p99_ms": 30000.0}},
                {"name": "decode", "path": server.api_path,
                 "body": decode_body,
                 "headers": {"Content-Type": "application/json"},
                 "n_clients": 2, "per_client": 6, "ttft_key": "ttft_ms",
                 "gates": {"p99_ms": 30000.0, "ttft_p99_ms": 200.0}},
            ], warm=2)
        finally:
            server.stop()

    cont = run(PipelineServer(Dispatch(dec_scorer, True), port=0,
                              mode="continuous").start())
    ticked = run(PipelineServer(Dispatch(ticked_scorer, False), port=0,
                                mode="micro_batch",
                                micro_batch_interval_ms=400).start())
    assert cont["score"]["gates"]["passed"], cont["score"]
    assert cont["decode"]["gates"]["passed"], cont["decode"]
    assert cont["decode"]["ttft_count"] == 12.0
    # the ticked drain fails the SAME ttft gate at the SAME load: every
    # request waited out the flush tick before any token reached it
    assert not ticked["decode"]["gates"]["passed"], ticked["decode"]
    failed = ticked["decode"]["gates"]["checks"]["ttft_p99_ms"]
    assert not failed["ok"] and failed["actual"] > 200.0


# ---------------------------------------------------------------------------
# profiling + postmortem plane over the decode hot loop (ISSUE 15)
# ---------------------------------------------------------------------------

def test_debug_profile_dump_and_compile_over_live_decode_stream(tmp_path):
    """The ISSUE 15 worked flow, end to end over real sockets: with a
    long generation holding the in-flight batch, (a) ``/debug/profile``
    attributes >= half its busy samples to the decode-step phase — the
    number that decomposes "dispatch-bound"; (b) ``/debug/compile`` shows
    the stream executables under the runner's wrapper names (a
    join-minted compile is visible fleet-wide, not just counter-checked);
    (c) "killing" the worker mid-stream (the preemption trigger a SIGTERM
    drill fires) leaves an atomic JSON-parseable dump with the live slot
    table, the ring tail, and the compile report; and (d) the request's
    ``serving.request`` span still lands in ``/debug/slow`` with its
    verdict, and the TTFT histogram's exemplar names the request's trace
    id even though the engine thread booked the observation (the PR 13
    engine-thread resolve seam)."""
    from mmlspark_tpu.models import ModelRunner
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.serving import PipelineServer
    from mmlspark_tpu.utils.resilience import (preemption_scope,
                                               request_preemption)

    reg = MetricsRegistry()
    # a LONG positional table: ~900 steps at a few ms each keeps the
    # stream alive through the profile window + the mid-stream drill
    # (prompt_bucket + max_new_tokens must fit max_len)
    mod, variables = _tiny_lm(layers=1, max_len=1024)
    runner = ModelRunner(module=mod, variables=variables, name="srv.prof",
                         registry=reg)
    scorer = runner.scorer(mode="decode", continuous=True, report_ttft=True,
                           slots=1, prompt_bucket=8, max_new_tokens=900,
                           page_size=8, encode=lambda t: [int(x) for x in t])
    srv = PipelineServer(scorer, port=0, mode="continuous",
                         registry=reg).start()
    first = {}
    done = threading.Event()
    try:
        def long_request():
            first["res"] = post_json(srv.port, srv.api_path, [5, 7, 11],
                                     timeout=120, return_headers=True)
            done.set()

        t = threading.Thread(target=long_request, daemon=True)
        t.start()
        # wait for real STEPS, not just occupancy: the slot is reserved at
        # submit, but a cold .xla_cache pays the prefill/step compiles
        # inside the first engine rounds — the drill below needs the
        # steady-state step loop (and its booked compile) underway
        deadline = time.monotonic() + 150
        while scorer._decoder is None or scorer._decoder.steps < 2:
            if time.monotonic() > deadline:
                raise AssertionError("the stream never started stepping")
            if done.is_set():
                raise AssertionError(f"request failed early: {first}")
            time.sleep(0.01)

        # throttle the step executable to a wall-clock floor: on a fast
        # host the bare tiny-LM step runs <1ms and the 900-step stream
        # would finish INSIDE the profile window below.  A busy-wait (not
        # sleep — the sampler would score the thread idle) keeps the
        # engine thread attributable to the ambient decode-step phase
        # while pinning the generation to a few seconds on any machine.
        real_step = scorer._decoder._step

        def throttled_step(*a, **k):
            t0 = time.monotonic()
            while time.monotonic() - t0 < 0.004:
                pass
            return real_step(*a, **k)

        scorer._decoder._step = throttled_step

        # (a) dispatch-heavy stream: >= half the busy samples attribute to
        # the decode step loop by name
        status, rep = post_json(
            srv.port, "/debug/profile?seconds=0.5&hz=150", None,
            method_get=True)
        assert status == 200
        rep = json.loads(rep)
        assert rep["samples"] > 0
        assert rep["by_span"].get("runner.decode.step", 0) >= \
            rep["samples"] / 2, rep["by_span"]

        # (b) the stream executables are visible on the compile plane
        status, comp = post_json(srv.port, "/debug/compile", None,
                                 method_get=True)
        fns = json.loads(comp)["functions"]
        for name in ("runner.srv.prof.prefill_paged",
                     "runner.srv.prof.decode_step_paged",
                     "runner.srv.prof.decode_sample"):
            assert name in fns and fns[name]["compiles"] >= 1, \
                f"{name} missing from /debug/compile"

        # (c) kill the worker mid-stream: the preemption trigger fires the
        # recorder and the dump is the debuggable artifact
        assert not done.is_set(), "generation finished before the drill"
        rec = reg._flight_recorder
        rec.dump_dir = str(tmp_path)
        with preemption_scope():
            assert request_preemption("chaos-kill") == 1
        names = os.listdir(tmp_path)
        assert len(names) == 1 and "preemption" in names[0]
        dump = json.load(open(tmp_path / names[0]))
        slot_rows = dump["decode_streams"][0]["slot_table"]
        assert any(row["live"] for row in slot_rows), \
            "dump lost the live slot table"
        assert dump["decode_streams"][0]["pool"]["pages_in_use"] > 0
        assert any(e.get("event") == "preemption_requested"
                   for e in dump["ring_events"]), "dump lost the ring tail"
        assert "runner.srv.prof.decode_step_paged" in \
            dump["compile"]["functions"], "dump lost the compile report"

        # (d) the preemption that dumped also DRAINS the server (ISSUE
        # 16): the in-flight generation still resolves 200 — zero-drop —
        # and only then does the listener stop.  The engine-thread
        # resolve still lands the serving.request span + TTFT exemplar
        # (the PR 13 attribution seam); with the HTTP plane gone by
        # contract, read them from the in-process collector that backs
        # ``/debug/slow``.
        from mmlspark_tpu.observability.collector import get_collector
        assert done.wait(120) and first["res"][0] == 200
        assert srv._drained.wait(60), "preemption hook never drained"
        trace_id = first["res"][2]["X-MMLSpark-Trace-Id"]
        rows = get_collector(reg).slowest(k=5, name="serving.request",
                                          server=srv._server_label)
        mine = [r for r in rows if r["traceId"] == trace_id]
        assert mine, f"serving.request span missing from slowest: {rows}"
        assert mine[0]["verdict"] == "ok"
        assert mine[0]["ttft_s"] >= 0.0
        ex = reg.family("mmlspark_runner_ttft_seconds").labels(
            runner="srv.prof").exemplars()
        assert ex is not None and any(tid == trace_id
                                      for _v, tid, _ts in ex.values()), \
            "TTFT exemplar lost the engine-thread request's trace id"
    finally:
        done.wait(120)
        srv.stop()
        reg._flight_recorder.close()


def test_engine_thread_crash_dumps_via_excepthook_without_deadlock(tmp_path):
    """A crashing scorer/engine thread is exactly when the black box must
    publish: poison the step executable mid-stream, let the engine thread
    die on the uncaught error, and assert the ``threading.excepthook``
    path wrote a parseable dump (with the slot table as of the crash)
    while clients resolve as errors and ``close()`` does not deadlock."""
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.observability.flightrecorder import FlightRecorder

    reg = MetricsRegistry()
    runner = _runner("cont.crash", layers=1, registry=reg)
    rec = FlightRecorder(registry=reg, dump_dir=str(tmp_path), install=True)
    try:
        dec = runner.decode_stream(slots=2, prompt_bucket=4,
                                   max_new_tokens=6, page_size=2)
        h = dec.submit(np.asarray([5, 7], np.int32), max_new_tokens=6)
        dec.step()                      # join + first token, healthy
        assert h.slot >= 0 and dec.occupancy() == 1

        def boom(*a, **k):
            raise RuntimeError("step executable poisoned")

        dec._step = boom
        dec.start()                     # engine thread picks up the stream
        assert h.done.wait(30), "client stranded by the crashed engine"
        assert h.status == "error"
        # ignore atomic_write's same-directory ``.tmp-<pid>`` staging
        # file: polling the bare listing can observe (and read) the
        # in-flight temp before the rename publishes the dump
        def _dumps():
            return [n for n in os.listdir(tmp_path) if ".tmp-" not in n]

        deadline = time.monotonic() + 30
        while not _dumps():
            if time.monotonic() > deadline:
                raise AssertionError("excepthook never dumped")
            time.sleep(0.01)
        names = _dumps()
        assert len(names) == 1 and "crash" in names[0]
        dump = json.load(open(tmp_path / names[0]))
        assert dump["trigger"] == "crash"
        streams = [s for s in dump["decode_streams"]
                   if s.get("runner") == "cont.crash"]
        assert streams and streams[0]["steps"] >= 1
        dec.close()                     # must return, not deadlock
        assert reg.family("mmlspark_flightrecorder_dumps_total").value(
            trigger="crash", result="ok") == 1
    finally:
        rec.close()


# ---------------------------------------------------------------------------
# one step in flight: the start() thread's pipelined rounds (ISSUE 37)
# ---------------------------------------------------------------------------

def _in_flight_engine(name, *, slots=3, budget=14, eos_id=None, clock=None,
                      stall_timeout_s=None, prefix=False, bucket=8,
                      longest=None):
    """A fresh runner and stream on pages of 4, so that answers cross page
    boundaries; with ``prefix`` an explicit pool that the index may fill."""
    from mmlspark_tpu.observability import MetricsRegistry
    runner = _runner(name, layers=1, registry=MetricsRegistry())
    pool = runner.page_pool(4, num_pages=96) if prefix else None
    dec = runner.decode_stream(
        slots=slots, prompt_bucket=bucket, max_prompt_len=longest,
        max_new_tokens=budget, eos_id=eos_id, page_size=4, pool=pool,
        prefix_cache=prefix, clock=clock, stall_timeout_s=stall_timeout_s)
    return runner, dec


def _mixed_requests(seed=37, n=10, shared=0):
    """Prompts of 2-8 tokens (the first ``shared`` of them one document's)
    and budgets of 3-14: joins and leaves mid-flight, page boundaries
    crossed by prompts and by answers."""
    rng = np.random.default_rng(seed)
    doc = rng.integers(0, 48, shared).astype(np.int32)
    out = []
    for _ in range(n):
        own = rng.integers(0, 48, int(rng.integers(2, 9 - shared)))
        out.append((np.concatenate([doc, own]).astype(np.int32),
                    int(rng.integers(3, 15))))
    return out


@pytest.mark.parametrize("prefix", [False, True],
                         ids=["cold_joins", "prefix_cache"])
def test_step_by_hand_and_the_start_thread_serve_the_same_tokens(prefix):
    """Satellite (a), (b) and (f) for ``TransformerEncoder``: the same
    seeded requests through both drivers, with joins and leaves mid-flight,
    answers over page boundaries and an eos some answers hit: the same
    tokens, every page back, the same retained ids, no compile key after
    warm-up; the overlap engages on the thread alone."""
    from tests.decode_drivers import serve_through_both_drivers

    runs = serve_through_both_drivers(
        lambda name, eos: _in_flight_engine(
            f"flight.{name}.{prefix}", eos_id=eos, prefix=prefix),
        _mixed_requests(shared=4 if prefix else 0))
    if prefix:
        assert runs["hand"]["retained"], "nothing was retained"


def _three_requests_and_their_eos():
    """A (budget 3), B (budget 9) and C (budget 12, cut to 5 tokens by the
    eos), none of A's or B's tokens the eos: with three slots every leave's
    step is known in advance."""
    runner, dec = _in_flight_engine("flight.pick")
    rng = np.random.default_rng(41)
    for _ in range(200):
        prompts = [rng.integers(0, 48, 5).astype(np.int32) for _ in range(3)]
        hs = [dec.submit(p, max_new_tokens=b)
              for p, b in zip(prompts, (3, 9, 12))]
        _drain(dec)
        a, b, c = (list(h.tokens) for h in hs)
        eos = c[4]
        if eos not in c[:4] and eos not in a and eos not in b:
            dec.close()
            return list(zip(prompts, (3, 9, 12))), eos, [a, b, c[:5]]
    raise AssertionError("no such three requests in 200 draws")


def test_overlap_and_stale_counters_count_what_the_step_in_flight_does():
    """Satellite (b).  Under ``start()`` with A, B, C joined in one round:
    eight steps, all but the first dispatched beside the one before; A is a
    dead row in step 3 (its last token dispatched in step 2, B and C still
    stepping), C's eos in step 4 is learnt after step 5 went out with it, B
    ends last and alone: two stale rows.  Under ``step()`` none of either.
    The ledger's laws hold under both."""
    from mmlspark_tpu.observability.attribution import OUTCOMES
    from tests.decode_drivers import counter, feed
    requests, eos, want = _three_requests_and_their_eos()
    for on_thread in (False, True):
        runner, dec = _in_flight_engine(f"flight.count.{on_thread}",
                                        eos_id=eos)
        dec.warmup()
        handles = feed(dec, requests, on_thread)
        dec.close()
        assert [list(h.tokens) for h in handles] == want
        assert dec.steps == 8 and dec.joined == 3
        assert counter(runner, "mmlspark_runner_decode_steps_total") == 8
        assert counter(runner, "mmlspark_runner_decode_tokens_total") \
            == 3 + 9 + 5
        assert counter(
            runner, "mmlspark_runner_decode_steps_overlapped_total") \
            == (7 if on_thread else 0)
        assert counter(runner, "mmlspark_runner_decode_stale_rows_total") \
            == (2 if on_thread else 0)
        # every step cell in exactly one bucket; device seconds charged to
        # requests are the counter's
        fam = runner.registry.family("mmlspark_decode_tokens_outcome_total")
        cells = {o: fam.labels(outcome=o).value for o in OUTCOMES}
        assert cells["useful"] == 17
        assert sum(cells.values()) == dec.steps * dec.slots + dec.joined
        dev = runner.registry.family(
            "mmlspark_decode_device_seconds_total").value()
        assert dev == pytest.approx(sum(h.cost.device_s for h in handles),
                                    rel=1e-6)
        assert dev > 0


def _rounds(dec, n=1):
    """``n`` of the engine thread's rounds, by hand: the step a round
    dispatches stays in flight."""
    for _ in range(n):
        with dec._engine_work() as leavers:
            dec._round(leavers)


def test_a_deadline_leave_with_a_step_in_flight_drops_that_steps_token():
    """Satellite (b), (c): the row of a request that expired while its step
    was in flight is retired against a handle that has left: one stale row,
    no token after the leave, resolved once, every page back."""
    from tests.decode_drivers import counter
    from mmlspark_tpu.utils.resilience import FakeClock
    clk = FakeClock()
    runner, dec = _in_flight_engine("flight.expire", clock=clk)
    done = []
    p = np.asarray([5, 7, 11], np.int32)
    h = dec.submit(p, deadline_s=clk() + 0.5, on_done=done.append)
    other = dec.submit(p + 1, on_done=done.append)
    _rounds(dec)                         # joins; step 1 in flight
    assert dec._in_flight is not None and len(h.tokens) == 1
    clk.advance(1.0)
    _rounds(dec)                         # expires h, dispatches 2, retires 1
    assert h.status == "expired" and done == [h]
    assert len(h.tokens) == 1 and len(other.tokens) == 2
    assert counter(runner, "mmlspark_runner_decode_stale_rows_total") == 1
    while not other.done.is_set():
        _rounds(dec)
    _rounds(dec)                         # the last step, stale, retires
    assert dec._in_flight is None and done == [h, other]
    assert len(other.tokens) == 14 and dec.pool.pages_in_use() == 0
    dec.close()
    assert done == [h, other] and dec.left == 2


def test_close_and_drain_retire_the_step_in_flight_before_the_teardown():
    """Satellite (c): ``close()`` lets the thread retire the step in flight
    (every dispatched step's token is on its handle), cancels what is live
    once, and leaves no page in use; ``drain()`` runs every slot to its
    end."""
    runner, dec = _in_flight_engine("flight.close", budget=100)
    done = []
    h = dec.submit(np.asarray([5, 7], np.int32), max_new_tokens=100,
                   on_done=done.append)
    real = dec._step

    def slow_step(*a):
        time.sleep(0.005)
        return real(*a)

    dec._step = slow_step
    dec.start()
    deadline = time.monotonic() + 120
    while dec.steps < 3:
        assert time.monotonic() < deadline
        time.sleep(0.001)
    dec.close()
    assert h.status == "cancelled" and done == [h]
    assert dec._in_flight is None
    assert len(h.tokens) == 1 + dec.steps < 100
    assert dec.pool.pages_in_use() == 0 and dec.left == 1
    dec.close()                          # torn once: nothing left to free
    assert dec.left == 1 and done == [h]

    runner, dec = _in_flight_engine("flight.drain")
    handles = [dec.submit(np.asarray([3, 1, 4], np.int32) + i,
                          max_new_tokens=6 + i) for i in range(3)]
    dec.start()
    assert dec.drain(timeout_s=120) is True
    assert [h.status for h in handles] == ["ok"] * 3
    assert [len(h.tokens) for h in handles] == [6, 7, 8]
    assert dec._in_flight is None and dec.pool.pages_in_use() == 0


def test_a_raising_dispatch_with_a_step_in_flight_resolves_every_handle_once():
    """Satellite (c): step 2's dispatch raises with step 1 in flight, in a
    round that had already released an expired request: that one resolves
    as expired, the others as errors, each once; the teardown runs once and
    no page is freed twice (``PagePool.free`` raises on a double free)."""
    from mmlspark_tpu.utils.resilience import FakeClock
    clk = FakeClock()
    runner, dec = _in_flight_engine("flight.raise", clock=clk)
    done = []
    p = np.asarray([5, 7, 11], np.int32)
    expiring = dec.submit(p, deadline_s=clk() + 0.5, on_done=done.append)
    live = dec.submit(p + 1, on_done=done.append)
    _rounds(dec)
    queued = dec.submit(p + 2, on_done=done.append)
    clk.advance(1.0)

    def boom(*a):
        raise RuntimeError("step executable poisoned")

    dec._step = boom                    # the join of ``queued`` still runs
    with pytest.raises(RuntimeError, match="poisoned"):
        _rounds(dec)
    # the round's own leaver is resolved although the round failed
    assert expiring.status == "expired" and done == [expiring]
    dec._abort()                         # what the engine thread does next
    assert dec.abort_reason == "error" and dec._in_flight is None
    assert live.status == queued.status == "error"
    assert sorted(map(id, done)) == sorted(map(id, (expiring, live, queued)))
    assert dec.pool.pages_in_use() == 0 and dec.left == 3
    dec.close()
    assert dec.left == 3 and len(done) == 3


def test_the_watchdog_bounds_one_step_while_a_step_is_in_flight():
    """Satellite (d), on a ``FakeClock``: armed from a dispatch until no
    step is in flight, its clock restarted by every retirement; a join of
    four chunks, each slower than half the timeout, does not trip it; a
    fetch that never returns trips ``runner.decode.step`` and aborts."""
    from mmlspark_tpu.utils.resilience import FakeClock
    clk = FakeClock()
    runner, dec = _in_flight_engine("flight.watch", clock=clk,
                                    stall_timeout_s=2.0, longest=32)
    wd = dec.watchdog
    tripped = []
    abort = wd.on_stall
    wd.on_stall = lambda label, s: (tripped.append(label), abort(label, s))
    rng = np.random.default_rng(43)
    first = dec.submit(rng.integers(0, 48, 5).astype(np.int32))
    try:
        _rounds(dec)
        assert wd.as_dict() == dict(wd.as_dict(), armed=True,
                                    label="runner.decode.step")
        for _ in range(4):               # 6 s of steps, 1.5 s each
            clk.advance(1.5)
            assert wd.check() is False
            _rounds(dec)
        # a long join beside the step in flight: every chunk takes 1.5 s
        real = dec._prefill1

        def slow_chunk(*a):
            clk.advance(1.5)
            assert wd.check() is False
            return real(*a)

        dec._prefill1 = slow_chunk
        long_one = dec.submit(rng.integers(0, 48, 30).astype(np.int32))
        _rounds(dec)
        assert long_one.status == "live" and not tripped
        assert runner.registry.family(
            "mmlspark_runner_prefill_chunks_total").labels(
                runner=runner.name).value == 1 + 4
        assert dec._in_flight is not None and wd.as_dict()["armed"]
        # the step in flight is never fetched: the monitor's next poll
        clk.advance(2.5)
        assert wd.check() is True
        assert tripped == ["runner.decode.step"]
        assert dec.abort_reason == "stall" and dec.closed
        assert first.status == long_one.status == "error"
        assert dec.pool.pages_in_use() == 0 and dec._in_flight is None
        assert not wd.as_dict()["armed"]
    finally:
        dec.close()
    # hand-driven step() leaves nothing armed behind it
    runner, dec = _in_flight_engine("flight.watch.hand", clock=clk,
                                    stall_timeout_s=2.0)
    dec.submit(np.asarray([5, 7], np.int32))
    dec.step()
    assert not dec.watchdog.as_dict()["armed"] and dec._in_flight is None
    dec.close()


@pytest.mark.parametrize("on_thread", [False, True],
                         ids=["step_by_hand", "start_thread"])
def test_neither_driver_reads_a_donated_buffer(on_thread, monkeypatch):
    """Satellite (e): the step donates its finished mask and the cache.
    Every dispatch is handed live buffers (the cache is the output of the
    dispatch before it), and what the engine fetches is never a buffer a
    later dispatch consumed, although on the thread the mask step N
    returned is already deleted when step N's tokens are fetched."""
    import jax
    from tests.decode_drivers import feed
    runner, dec = _in_flight_engine(f"flight.donate.{on_thread}")
    dec.warmup()
    seen = {"steps": 0, "mask_gone_at_fetch": 0, "outputs": []}
    real_step, real_get = dec._step, jax.device_get

    def spy_step(variables, tok, pos, table, fin, cache):
        leaves = jax.tree_util.tree_leaves(cache)
        assert not any(x.is_deleted() for x in [tok, fin] + leaves), \
            "a step was dispatched with a consumed buffer"
        out = real_step(variables, tok, pos, table, fin, cache)
        assert fin.is_deleted() and all(x.is_deleted() for x in leaves), \
            "the step no longer donates: this test has lost its teeth"
        seen["steps"] += 1
        seen["outputs"].append(out[:2])
        return out

    def spy_get(tree):
        fetched = jax.tree_util.tree_leaves(tree)
        assert not any(x.is_deleted() for x in fetched
                       if hasattr(x, "is_deleted")), \
            "the engine fetched a donated buffer"
        for tok_d, fin_d in seen["outputs"]:
            if any(x is tok_d for x in fetched) and fin_d.is_deleted():
                seen["mask_gone_at_fetch"] += 1
        return real_get(tree)

    dec._step = spy_step
    monkeypatch.setattr(jax, "device_get", spy_get)
    handles = feed(dec, _mixed_requests(seed=47, n=6), on_thread)
    dec.close()
    assert {h.status for h in handles} == {"ok"}
    assert seen["steps"] == dec.steps > 10
    if on_thread:
        assert seen["mask_gone_at_fetch"] > 0
    else:
        assert seen["mask_gone_at_fetch"] == 0


def test_many_submitting_threads_beside_the_step_in_flight():
    """More submitters than cores, the interpreter switching threads every
    10 us: every request served by the ``start()`` thread holds the tokens
    ``step()`` by hand gave it, each resolved once, every page back."""
    import sys
    from mmlspark_tpu.models import SlotsExhausted
    from tests.decode_drivers import feed
    requests = _mixed_requests(seed=53, n=24)
    _, dec = _in_flight_engine("flight.stress.hand", slots=4)
    want = [list(h.tokens) for h in feed(dec, requests, False)]
    dec.close()
    runner, dec = _in_flight_engine("flight.stress", slots=4)
    dec.warmup()
    got, resolved = {}, []
    deadline = time.monotonic() + 240

    def submitter(mine):
        for i in mine:
            prompt, budget = requests[i]
            while time.monotonic() < deadline:
                try:
                    got[i] = dec.submit(prompt, max_new_tokens=budget,
                                        on_done=resolved.append)
                    break
                except SlotsExhausted:
                    time.sleep(0.0002)

    workers = [threading.Thread(target=submitter, args=(range(k, 24, 12),))
               for k in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        dec.start()
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=240)
        assert not any(w.is_alive() for w in workers) and len(got) == 24
        assert all(h.done.wait(max(0.0, deadline - time.monotonic()))
                   for h in got.values())
    finally:
        sys.setswitchinterval(interval)
        dec.close()
    assert [list(got[i].tokens) for i in range(24)] == want
    assert {h.status for h in got.values()} == {"ok"}
    assert sorted(map(id, resolved)) == sorted(map(id, got.values()))
    assert dec.pool.pages_in_use() == 0 and dec._in_flight is None
