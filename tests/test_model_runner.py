"""Unified model runner (ISSUE 9): lower-once bucketed execution behind
batch transform, PipelineServer low-latency scoring, the streaming facade,
and KV-cached batched decode.

The acceptance contracts this file pins:

- runner-vs-legacy bit-parity: the runner's pad/bucket/dispatch produces
  the SAME numbers as the hand-rolled per-model glue it replaced (resnet
  and bilstm transform);
- KV-cached decode logits == full-recompute logits at EVERY step (within
  the committed fp tolerance, atol=1e-4 on f32);
- bucket-cache compile counts: one compile per (model, bucket) signature —
  no recompile storm across ragged batch sizes;
- one runner path serves batch transform AND PipelineServer low-latency
  scoring AND streaming replies, end to end over real sockets.
"""
import http.client
import json

import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame, load, save

#: committed fp tolerance for decode-vs-recompute logit parity (f32; the
#: single-token step reassociates reductions differently than the full pass)
DECODE_ATOL = 1e-4


def _mlp_runner(registry=None, batch_size=8, name="test.mlp"):
    from mmlspark_tpu.models import ModelRunner
    w = np.arange(6, dtype=np.float32).reshape(3, 2) / 10.0

    def apply_fn(variables, x):
        return x @ variables["w"] + 1.0

    return ModelRunner(apply_fn=apply_fn, variables={"w": w},
                       name=name, batch_size=batch_size, registry=registry)


def _tiny_lm(vocab=48, layers=2, seed=0):
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import TransformerEncoder
    mod = TransformerEncoder(vocab_size=vocab, num_classes=vocab,
                             embed_dim=32, num_heads=2, num_layers=layers,
                             mlp_dim=64, max_len=128, causal=True,
                             pool="none")
    variables = mod.init(jax.random.PRNGKey(seed),
                         jnp.zeros((1, 4), jnp.int32))
    return mod, variables


# ---------------------------------------------------------------------------
# runner-vs-legacy bit parity
# ---------------------------------------------------------------------------

def _legacy_apply(pure, variables, x, batch_size, executable=None):
    """The pre-runner JaxModel glue, verbatim: per-bucket jit + pad, one
    chunk at a time with a fetch after each.  ``executable(bucket,
    feat_shape)`` in place of the per-bucket jit scores through a runner's
    own programs."""
    import jax
    from mmlspark_tpu.models.runner import bucket_rows
    cache = {}
    outs = []
    for start in range(0, x.shape[0], batch_size):
        chunk = x[start:start + batch_size]
        m = chunk.shape[0]
        bucket = bucket_rows(m, batch_size)
        if m < bucket:
            chunk = np.concatenate(
                [chunk, np.repeat(chunk[-1:], bucket - m, axis=0)])
        fn = cache.get(bucket)
        if fn is None:
            fn = cache[bucket] = jax.jit(pure) if executable is None \
                else executable(bucket, chunk.shape[1:])
        outs.append(np.asarray(fn(variables, chunk))[:m])
    return np.concatenate(outs)


def test_runner_vs_legacy_bit_parity_resnet():
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import ModelRunner
    from mmlspark_tpu.models.resnet import cifar_resnet20

    module = cifar_resnet20(num_classes=5, width=8)
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(0), (9, 16, 16, 3),
                                      jnp.float32))
    variables = module.init(jax.random.PRNGKey(1), x[:1])

    def pure(vs, chunk):
        return module.apply(vs, chunk, features=True)

    runner = ModelRunner(module=module, variables=variables,
                         apply_kwargs={"features": True},
                         name="test.resnet", batch_size=4)
    got = runner.apply_batch(x)                       # chunks 4/4/1
    ref = _legacy_apply(pure, variables, x, 4)
    np.testing.assert_array_equal(got, ref)           # same programs: exact


def test_runner_vs_legacy_bit_parity_bilstm():
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import BiLSTMTagger, ModelRunner

    module = BiLSTMTagger(vocab_size=30, num_tags=4, embed_dim=8, hidden=8,
                          num_layers=1)
    toks = np.random.default_rng(0).integers(0, 30, (7, 6)).astype(np.int32)
    variables = module.init(jax.random.PRNGKey(0), jnp.asarray(toks[:1]))

    def pure(vs, chunk):
        return module.apply(vs, chunk)

    runner = ModelRunner(module=module, variables=variables,
                         name="test.bilstm", batch_size=4)
    got = runner.apply_batch(toks)                    # chunks 4/4(pad 1)
    ref = _legacy_apply(pure, variables, toks, 4)
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# the batch front as a two-deep staging pipeline (ISSUE 32)
# ---------------------------------------------------------------------------

_BS, _WIDTH = 16, 64


def _rows(n, dtype=np.float32, seed=0, width=_WIDTH):
    """An object column of ``n`` rows, as a table holds them."""
    rng = np.random.default_rng(seed)
    col = np.empty(n, dtype=object)
    for i in range(n):
        col[i] = (rng.integers(0, 256, width).astype(dtype)
                  if np.dtype(dtype) == np.uint8
                  else rng.normal(size=width).astype(dtype))
    return col


class _Pipe:
    """A runner whose program takes milliseconds on the CPU (dispatch is
    asynchronous there too), watched for the one rule the pipeline rests
    on: a staging buffer is refilled only after the OUTPUT of the program
    that read it is ready."""

    def __init__(self):
        import jax
        import jax.numpy as jnp
        from mmlspark_tpu.models import ModelRunner
        from mmlspark_tpu.observability import MetricsRegistry
        w = np.random.default_rng(1).normal(
            size=(_WIDTH, _WIDTH)).astype(np.float32) / 8

        def apply_fn(variables, x):
            h = x.astype(jnp.float32) / 16
            return jax.lax.fori_loop(
                0, 2000, lambda i, h: jnp.tanh(h @ variables["w"] + 0.1), h)

        self.reg = MetricsRegistry()
        self.runner = ModelRunner(apply_fn=apply_fn, variables={"w": w},
                                  name="test.pipe", batch_size=_BS,
                                  registry=self.reg)
        self.refilled_too_early = []
        #: address of a staged chunk -> the last output computed from it
        self._read_by = {}
        #: unwatched, for the reference (its chunks are temporaries whose
        #: addresses come back)
        self.executable = self.runner.executable

        def watched_executable(bucket, feat_shape):
            fn = self.executable(bucket, feat_shape)

            def call(variables, chunk):
                y = fn(variables, chunk)
                self._read_by[chunk.ctypes.data] = y
                return y
            return call
        self.runner.executable = watched_executable

    def source(self, col, dtype=np.float32, width=_WIDTH):
        from mmlspark_tpu.models import RowSource
        src, pipe = RowSource(col, (width,), dtype), self

        class Watched:
            shape, dtype = src.shape, src.dtype

            def fill(self, out, start, stop):
                y = pipe._read_by.get(out.ctypes.data)
                if y is not None and not y.is_ready():
                    pipe.refilled_too_early.append(start)
                src.fill(out, start, stop)
        return Watched()

    def counter(self, family, **labels):
        fam = self.reg.family(f"mmlspark_runner_{family}_total")
        return sum(c.value for key, c in fam._children.items()
                   if all(v in key for v in labels.values()))

    def staged(self):
        return (self.counter("staged_chunks", buffer="reused"),
                self.counter("staged_chunks", buffer="fresh"))

    def one_chunk_at_a_time(self, col, dtype, bs=_BS):
        """The plain reference: today's loop before the pipeline, through
        the runner's own programs."""
        x = np.stack([np.asarray(v) for v in col]).astype(dtype)
        return _legacy_apply(None, self.runner.variables, x, bs,
                             executable=self.executable)

    def check(self, n, dtype=np.float32, seed=0, dense=False, bs=None,
              staged=None):
        """``apply_batch`` over ``n`` rows is BIT-equal to the reference;
        ``staged`` = the expected rise of (reused, fresh)."""
        col = _rows(n, dtype, seed)
        want = self.one_chunk_at_a_time(col, dtype, bs or _BS)
        before = self.staged()
        x = np.stack(list(col)) if dense else self.source(col, dtype)
        got = self.runner.apply_batch(x, batch_size=bs)
        assert got.dtype == want.dtype and np.array_equal(got, want)
        assert not self.refilled_too_early
        if staged is not None:
            after = self.staged()
            assert (after[0] - before[0], after[1] - before[1]) == staged
        return got


def _case_source_chunks(k):
    def case(pipe):
        pipe.check(k * _BS, staged=(0, k))
    return case


def _case_ragged_last_chunk(pipe):
    pipe.check(2 * _BS + 5, staged=(0, 3))          # 16, 16, 5 in a bucket of 8
    assert pipe.counter("batches", front="transform") == 3
    assert pipe.counter("rows", front="transform") == 2 * _BS + 5
    assert pipe.counter("pad_rows") == 3
    assert pipe.counter("input_bytes", front="transform") \
        == (2 * _BS + 8) * _WIDTH * 4


def _case_dense_three_chunks(pipe):
    pipe.check(3 * _BS, dense=True, staged=(0, 0))   # nothing to stage
    pipe.check(2 * _BS + 3, dense=True, staged=(0, 0))
    assert pipe.counter("batches", front="transform") == 6


def _case_one_row(pipe):
    got = pipe.check(1, staged=(0, 1))
    assert got.shape == (1, _WIDTH)
    pipe.check(1, dense=True, staged=(0, 0))


def _case_second_call_reuses_the_pair(pipe):
    pipe.check(3 * _BS, seed=1, staged=(0, 3))
    pipe.check(5 * _BS + 2, seed=2, staged=(6, 0))
    pipe.check(1, seed=3, staged=(1, 0))            # a leading view of it


def _case_other_dtype_replaces_the_pair(pipe):
    pipe.check(3 * _BS, np.float32, staged=(0, 3))
    pipe.check(3 * _BS, np.uint8, staged=(0, 3))
    pipe.check(4 * _BS, np.uint8, seed=5, staged=(4, 0))
    pipe.check(2 * _BS, np.float32, staged=(0, 2))  # the float pair is gone


def _case_smaller_batch_uses_a_leading_view(pipe):
    pipe.check(3 * _BS, staged=(0, 3))
    pipe.check(3 * _BS, bs=_BS // 2, staged=(6, 0))
    pipe.check(3 * _BS, bs=2 * _BS, staged=(0, 2))   # too small: replaced


def _case_two_threads_at_once(pipe):
    import threading
    pipe.check(3 * _BS, staged=(0, 3))              # the pair exists
    barrier, failures = threading.Barrier(2), []

    def call(seed):
        try:
            barrier.wait(timeout=30)
            for _ in range(3):
                pipe.check(4 * _BS, seed=seed)
        except BaseException as e:       # read by the asserting thread
            failures.append(e)
    before = pipe.staged()
    threads = [threading.Thread(target=call, args=(s,)) for s in (7, 8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads) and not failures
    after = pipe.staged()
    assert sum(after) - sum(before) == 2 * 3 * 4    # nobody waited or skipped
    pipe.check(3 * _BS, staged=(3, 0))              # a pair was handed back


def _case_fill_raises_midway(pipe):
    pipe.check(3 * _BS, staged=(0, 3))
    src = pipe.source(_rows(4 * _BS))
    fill = src.fill

    def failing_fill(out, start, stop):
        if start == 2 * _BS:                        # two chunks in flight
            raise OSError("row store went away")
        fill(out, start, stop)
    src.fill = failing_fill
    with pytest.raises(OSError, match="went away"):
        pipe.runner.apply_batch(src)
    assert not pipe.refilled_too_early
    pipe.check(3 * _BS, seed=9, staged=(3, 0))      # handed back, and clean


def _case_row_of_another_shape_raises(pipe):
    col = _rows(2 * _BS)
    col[_BS + 3] = np.zeros(1, np.float32)          # would broadcast
    with pytest.raises(ValueError, match=f"row {_BS + 3} has shape"):
        pipe.runner.apply_batch(pipe.source(col))
    pipe.check(2 * _BS, staged=(2, 0))


def _case_another_row_shape_replaces_the_pair(pipe):
    pipe.check(2 * _BS, staged=(0, 2))
    wide = _rows(2 * _BS, width=2 * _WIDTH)
    with pytest.raises(TypeError):                   # (rows, 128) @ (64, 64)
        pipe.runner.apply_batch(pipe.source(wide, width=2 * _WIDTH))
    assert pipe.staged() == (0, 3)                   # staged fresh, then failed
    pipe.check(2 * _BS, staged=(0, 2))


_PIPELINE_CASES = {
    "source_1_chunk": _case_source_chunks(1),
    "source_2_chunks": _case_source_chunks(2),
    "source_3_chunks": _case_source_chunks(3),
    "source_7_chunks": _case_source_chunks(7),
    "ragged_last_chunk_padded": _case_ragged_last_chunk,
    "dense_3_chunks": _case_dense_three_chunks,
    "one_row": _case_one_row,
    "second_call_reuses_the_pair": _case_second_call_reuses_the_pair,
    "other_dtype_replaces_the_pair": _case_other_dtype_replaces_the_pair,
    "smaller_batch_uses_a_leading_view":
        _case_smaller_batch_uses_a_leading_view,
    "two_threads_at_once": _case_two_threads_at_once,
    "fill_raises_midway": _case_fill_raises_midway,
    "row_of_another_shape_raises": _case_row_of_another_shape_raises,
    "another_row_shape_replaces_the_pair":
        _case_another_row_shape_replaces_the_pair,
}


@pytest.mark.parametrize("case", list(_PIPELINE_CASES))
def test_apply_batch_pipeline_matches_one_chunk_at_a_time(case):
    """Every case BIT-equal to scoring the same rows one chunk at a time
    with a fetch after each, and never a staging buffer refilled before
    the output of the program that read it was ready."""
    _PIPELINE_CASES[case](_Pipe())


# ---------------------------------------------------------------------------
# KV-cached decode
# ---------------------------------------------------------------------------

def test_decode_logits_match_full_recompute_every_step():
    """The acceptance gate: at every decode step, the KV-cached single-token
    logits equal a full causal recompute over that sequence's true history —
    ragged prompts included (per-sequence cache frontiers)."""
    import jax.numpy as jnp
    from mmlspark_tpu.models import ModelRunner

    mod, variables = _tiny_lm()
    runner = ModelRunner(module=mod, variables=variables, name="test.lm")
    rng = np.random.default_rng(1)
    lengths = np.asarray([7, 4, 2], np.int32)
    prompts = rng.integers(0, 48, (3, 7)).astype(np.int32)
    T = 5
    res = runner.decode(prompts, lengths=lengths, max_new_tokens=T,
                        collect_logits=True)
    assert res.tokens.shape == (3, T) and res.logits.shape == (3, T, 48)
    for b in range(3):
        L = int(lengths[b])
        # the reference history carries the RUNNER's tokens so the
        # comparison stays conditioned on identical prefixes; by causal
        # masking ONE full pass over the final history yields every
        # prefix's full-recompute logits at once (position i is the
        # distribution after history[:i+1])
        hist = np.concatenate([prompts[b, :L], res.tokens[b]])
        full = np.asarray(mod.apply(
            variables, jnp.asarray(hist.astype(np.int32)[None])))[0]
        for t in range(T):
            np.testing.assert_allclose(res.logits[b, t], full[L + t - 1],
                                       atol=DECODE_ATOL)


def test_decode_eos_freezes_finished_sequences():
    from mmlspark_tpu.models import ModelRunner

    mod, variables = _tiny_lm(layers=1)
    runner = ModelRunner(module=mod, variables=variables, name="test.lm_eos")
    prompts = np.random.default_rng(2).integers(0, 48, (2, 4)).astype(np.int32)
    res = runner.decode(prompts, max_new_tokens=6, eos_id=0,
                        sample_fn=lambda lg: np.zeros(lg.shape[0], np.int64))
    # every sequence emits eos immediately, freezes, and the loop ends early
    assert res.tokens.shape[1] == 1
    assert (res.tokens == 0).all()
    assert res.steps == 0
    # non-power-of-two batch: the PAD rows are born finished, so they must
    # not hold the early exit open (review fix: 3 real rows pad to 4)
    p3 = np.random.default_rng(5).integers(0, 48, (3, 4)).astype(np.int32)
    res3 = runner.decode(p3, max_new_tokens=6, eos_id=0,
                         sample_fn=lambda lg: np.zeros(lg.shape[0], np.int64))
    assert res3.tokens.shape == (3, 1) and res3.steps == 0


def test_decode_rejects_cacheless_models():
    runner = _mlp_runner()
    with pytest.raises(TypeError, match="init_cache"):
        runner.decode(np.zeros((1, 4), np.int32))


# ---------------------------------------------------------------------------
# bucket cache: one compile per (model, bucket) signature
# ---------------------------------------------------------------------------

def test_one_compile_per_bucket_signature_across_ragged_batches():
    from mmlspark_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    runner = _mlp_runner(registry=reg)
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 5, 7, 8, 11, 13, 16, 17):   # ragged sweep
        runner.apply_batch(rng.normal(size=(n, 3)).astype(np.float32))
    stats = runner.compile_stats()
    # batch_size=8 -> buckets {1, 2, 4, 8} and nothing else, each ONCE
    assert stats["compiles"] == 4, stats
    before = stats["compiles"]
    for n in (1, 3, 9, 16):                        # repeat: pure cache hits
        runner.apply_batch(rng.normal(size=(n, 3)).astype(np.float32))
    assert runner.compile_stats()["compiles"] == before
    # the compile counter family agrees (it feeds /debug/compile)
    fam = reg.family("mmlspark_jit_compile_total")
    assert sum(c.value for c in fam._children.values()) == before


def test_decode_signature_compiles_once_across_requests():
    from mmlspark_tpu.models import ModelRunner

    mod, variables = _tiny_lm(layers=1)
    runner = ModelRunner(module=mod, variables=variables, name="test.lm_sig")
    rng = np.random.default_rng(3)
    p1 = rng.integers(0, 48, (3, 6)).astype(np.int32)
    runner.decode(p1, max_new_tokens=4)
    # prefill + fused step + on-device sampler (ISSUE 12 fast path)
    n0 = runner.compile_stats()["compiles"]
    assert n0 == 3, runner.compile_stats()
    # same signature (same buckets/cache) -> zero new compiles, any lengths
    p2 = rng.integers(0, 48, (4, 5)).astype(np.int32)
    runner.decode(p2, lengths=[5, 3, 2, 1], max_new_tokens=4)
    assert runner.compile_stats()["compiles"] == n0


# ---------------------------------------------------------------------------
# serving fronts (real sockets)
# ---------------------------------------------------------------------------

def _post(port, path, obj, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    body = json.dumps(obj)
    conn.request("POST", path, body, {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = json.loads(resp.read().decode())
    conn.close()
    return resp.status, data


def test_pipeline_server_low_latency_scoring_through_runner():
    """E2E: PipelineServer -> runner scorer -> bucketed executable, over a
    real socket.  The 1-row request rides the 1-row bucket (latency path),
    and the runner books its serving-front metrics on the shared registry
    the server exposes."""
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.serving import PipelineServer

    reg = MetricsRegistry()
    runner = _mlp_runner(registry=reg, name="srv.mlp")
    srv = PipelineServer(runner.scorer(), port=0, mode="continuous",
                         registry=reg).start()
    try:
        x = [1.0, 2.0, 3.0]
        status, reply = _post(srv.port, srv.api_path, x)
        assert status == 200
        w = np.arange(6, dtype=np.float32).reshape(3, 2) / 10.0
        np.testing.assert_allclose(reply, np.asarray(x, np.float32) @ w + 1.0,
                                   rtol=1e-6)
        # single-row request -> 1-row bucket, not batch_size
        buckets = {k[2] for k in runner._executables if k[0] == "apply"}
        assert buckets == {1}
        # serving front booked on the server's registry
        fam = reg.family("mmlspark_runner_rows_total")
        assert fam is not None
    finally:
        srv.stop()


def test_decode_scorer_through_pipeline_server():
    """Generative scoring as a serving workload: POST a token prompt, get
    generated token ids back through the KV-cached decode loop."""
    from mmlspark_tpu.models import ModelRunner
    from mmlspark_tpu.serving import PipelineServer

    mod, variables = _tiny_lm(layers=1)
    runner = ModelRunner(module=mod, variables=variables, name="srv.lm")
    scorer = runner.scorer(mode="decode", max_new_tokens=3,
                           encode=lambda toks: [int(t) for t in toks])
    srv = PipelineServer(scorer, port=0, mode="continuous").start()
    try:
        status, reply = _post(srv.port, srv.api_path, [5, 7, 11])
        assert status == 200
        assert isinstance(reply, list) and len(reply) == 3
        assert all(isinstance(t, int) and 0 <= t < 48 for t in reply)
    finally:
        srv.stop()


def test_streaming_facade_scores_through_runner():
    """read_stream().server(...).transform_with(<ModelRunner>) — the
    streaming facade wraps the runner in its scorer bound to the source's
    value column (same lower-once cache as every other front)."""
    from mmlspark_tpu.serving import read_stream

    runner = _mlp_runner(name="stream.mlp")
    query = (read_stream().server(port=0)
             .transform_with(runner)
             .reply_to("reply"))
    try:
        port = query.source.port
        status, reply = _post(port, "/score", [1.0, 0.0, 2.0])
        assert status == 200
        w = np.arange(6, dtype=np.float32).reshape(3, 2) / 10.0
        np.testing.assert_allclose(
            reply, np.asarray([1.0, 0.0, 2.0], np.float32) @ w + 1.0,
            rtol=1e-6)
    finally:
        query.stop()


def test_mixed_load_scoring_plus_decode_one_run():
    """ISSUE 9 satellite: one loadgen run drives scoring AND decode request
    classes through one server and one measurement window, reporting
    per-class and combined stats — the serving-fleet traffic generator."""
    from mmlspark_tpu.core import Transformer
    from mmlspark_tpu.models import ModelRunner
    from mmlspark_tpu.serving import PipelineServer, mixed_load

    mod, variables = _tiny_lm(layers=1)
    lm = ModelRunner(module=mod, variables=variables, name="mix.lm")
    mlp = _mlp_runner(name="mix.mlp")

    class Dispatch(Transformer):
        """Routes {"decode": [...]} rows to the LM, plain vectors to the
        MLP — the mixed-workload shape one fleet worker actually sees."""

        def _transform(self, df):
            def per_part(p):
                col = p["request"]
                out = np.empty(len(col), dtype=object)
                for i, v in enumerate(col):
                    if isinstance(v, dict) and "decode" in v:
                        res = lm.decode(
                            np.asarray(v["decode"], np.int32)[None],
                            max_new_tokens=2)
                        out[i] = [int(t) for t in res.tokens[0]]
                    else:
                        y = mlp.apply_batch(
                            np.asarray(v, np.float32)[None], front="serving")
                        out[i] = y[0].tolist()
                return {**p, "reply": out}
            return df.map_partitions(per_part)

        def transform_schema(self, schema):
            return schema

    srv = PipelineServer(Dispatch(), port=0, mode="continuous").start()
    try:
        res = mixed_load("127.0.0.1", srv.port, [
            {"name": "score", "path": srv.api_path,
             "body": json.dumps([1.0, 2.0, 3.0]),
             "headers": {"Content-Type": "application/json"},
             "n_clients": 2, "per_client": 5},
            {"name": "decode", "path": srv.api_path,
             "body": json.dumps({"decode": [3, 1, 4]}),
             "headers": {"Content-Type": "application/json"},
             "n_clients": 2, "per_client": 5},
        ], warm=1)
        for cls in ("score", "decode"):
            assert res[cls]["completed"] == 10.0, res
            assert res[cls]["errors"] == 0.0, res
            assert res[cls]["p99_ms"] > 0
        assert res["combined"]["completed"] == 20.0
        assert res["combined"]["rps"] > 0
        # duplicate class names would silently merge attribution (review fix)
        with pytest.raises(ValueError, match="duplicate workload names"):
            mixed_load("127.0.0.1", srv.port,
                       [{"name": "a", "path": "/x", "body": ""},
                        {"name": "a", "path": "/y", "body": ""}])
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# stage integration: save/load re-binds through the runner
# ---------------------------------------------------------------------------

def test_jax_model_save_load_rebinds_through_runner(tmp_path):
    """ISSUE 9 small fix: a loaded JaxModel holds no private jit state —
    _post_load drops the handle and the first transform re-binds a fresh
    ModelRunner over the deserialized payload."""
    import jax
    import jax.numpy as jnp
    import flax.linen as nn
    from mmlspark_tpu.dl import JaxModel

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(2)(x)

    mod = Tiny()
    variables = mod.init(jax.random.PRNGKey(0), jnp.zeros((1, 3)))
    m = JaxModel().set_model(module=mod, variables=variables)
    m.set_params(input_col="x", output_col="y", batch_size=4)
    df = DataFrame.from_dict({"x": np.ones((5, 3))})
    a = np.stack(list(m.transform(df).collect()["y"]))

    path = str(tmp_path / "jm_runner")
    save(m, path)
    m2 = load(path)
    assert m2._runner is None            # nothing stale deserialized
    b = np.stack(list(m2.transform(df).collect()["y"]))
    np.testing.assert_allclose(a, b, atol=1e-6)
    # and the handle is a real runner with the lower-once cache populated
    assert m2.runner().compile_stats()["compiles"] >= 1
    # set_model invalidates the binding (fresh payload, fresh runner)
    r_old = m2.runner()
    m2.set_model(module=mod, variables=variables)
    assert m2._runner is None and m2.runner() is not r_old
