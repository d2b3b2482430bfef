"""Chip smoke: the main paths once, on the TPU, through the public API.

    python chip_smoke.py

One process, one JAX import.  Requires ``jax.devices()[0].platform ==
"tpu"`` and exits non-zero before doing any work otherwise.  Phases:

  (a) GBDT trainer at the headline width (1M x 200, depth 5, 8 iterations,
      default backend settings) + held-out accuracy floor
  (b) integer histogram exactness on the device: what ``auto`` resolved to
      vs the packed scatter reference, bit for bit
  (c) ResNet-50 featurize through ImageFeaturizer -> JaxModel ->
      ModelRunner.apply_batch (bf16, 224 x 224) vs an f32 reference
  (d) decode: ModelRunner.decode() and decode_stream() (paged pool,
      prefix cache on), determinism and continuous == one-shot
  (e) HTTP server: LightGBMClassifier.fit -> PipelineServer, replies
      scored on the chip vs model.transform
  (f) sharded GBDT (only with more than one device): shards on every
      device, trees equal to the one-chip run's

Every phase has a check that raises; any failure is a non-zero exit.  Each
phase prints the path that actually ran plus its compile and run seconds —
facts about this smoke run, not rates.  The last stdout line is one JSON
object ``{"ok": true, "device": {...}}`` with the device as JAX reports it.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))

#: the f32 transformer bench.py's decode arm uses (bench.py phase_runner)
LM_CONFIG = dict(vocab_size=512, num_classes=512, embed_dim=256, num_heads=4,
                 num_layers=4, mlp_dim=512, max_len=4096, causal=True,
                 pool="none")


class CompileClock:
    """Process-wide compile seconds and persistent-cache hits, read from
    ``jax.monitoring`` (every compile in the process, instrumented or not).
    ``compile_s`` = MLIR lowering + backend compile (a persistent-cache hit
    books only its retrieval time there)."""

    _DURATIONS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event in self._DURATIONS:
            self.compile_s += duration

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.compile_s, self.hits, self.misses


def run_phase(clock: CompileClock, tag: str, fn, **kw):
    """Run one phase; print its PASS line with compile/run seconds.  A
    failing check raises out of here — nothing turns it into exit 0."""
    print(f"[{tag}] start", flush=True)
    c0, h0, m0 = clock.snapshot()
    t0 = time.perf_counter()
    detail = fn(**kw)
    wall = time.perf_counter() - t0
    c1, h1, m1 = clock.snapshot()
    compile_s = c1 - c0
    print(f"[{tag}] PASS compile_s={compile_s:.1f} "
          f"run_s={max(wall - compile_s, 0.0):.1f} "
          f"cache_hits={h1 - h0} cache_misses={m1 - m0} | {detail}",
          flush=True)


# ------------------------------------------------------------------ (a)

def make_gbdt_data(n: int, f: int, holdout: int):
    rng = np.random.default_rng(0)
    X = rng.standard_normal((n + holdout, f), dtype=np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1]
         + 0.3 * rng.standard_normal(n + holdout, dtype=np.float32)
         > 0).astype(np.float32)
    return X, y


def build_native_from_source() -> str:
    """Rebuild the native binning library from ``native/mmlspark_native.cpp``
    (never trust a stray .so) and report what the loader then got."""
    from mmlspark_tpu.utils import native_loader
    subprocess.run(["make", "-C", os.path.join(_REPO, "native"), "-B"],
                   check=True, capture_output=True, timeout=300)
    if native_loader.load_native() is None:
        raise AssertionError("native binning library built but not loadable")
    return "built from native/mmlspark_native.cpp and loaded"


def train_span_facts(trace_id: str) -> dict:
    """The ``lightgbm.train`` span's own account of the path it took."""
    from mmlspark_tpu.observability.collector import get_collector
    spans = [s for s in get_collector().trace(trace_id)
             if s.name == "lightgbm.train"]
    if not spans:
        raise AssertionError("no lightgbm.train span recorded")
    return dict(spans[-1].attributes)


def phase_gbdt(state: dict, n=1_000_000, f=200, iters=8, holdout=50_000,
               acc_floor=0.80):
    from mmlspark_tpu.lightgbm import GBDTParams, train
    from mmlspark_tpu.observability.compute import compile_report
    from mmlspark_tpu.observability.tracing import trace_span

    native = build_native_from_source()
    X, y = make_gbdt_data(n, f, holdout)
    params = GBDTParams(objective="binary", max_depth=5, num_iterations=iters)
    with trace_span("chip_smoke.gbdt") as sp:
        res = train(X[:n], y[:n], params)
    facts = train_span_facts(sp.trace_id)
    booster = res.booster
    if booster.num_trees != iters:
        raise AssertionError(f"{booster.num_trees} trees, wanted {iters}")
    pred = booster.predict(X[n:])
    if not np.isfinite(pred).all():
        raise AssertionError("non-finite predictions")
    acc = float(((pred > 0.5) == (y[n:] > 0.5)).mean())
    if acc < acc_floor:
        raise AssertionError(f"held-out accuracy {acc:.4f} < {acc_floor}")
    compiled = sorted(k for k, v in compile_report()["functions"].items()
                      if k.startswith("lightgbm.") and v["compiles"])
    state.update(X=X, y=y, n=n, params=params, booster=booster)
    return (f"{n}x{f} depth5 iters={iters} backend={facts['hist_backend']} "
            f"quantized={facts['quantized']} chunk={facts['chunk']} "
            f"compiled={compiled} native_binning: {native}; "
            f"holdout_acc={acc:.4f} (floor {acc_floor})")


# ------------------------------------------------------------------ (b)

def phase_hist_exact(shapes=((100_000, 32, 8, 255), (1_000_000, 200, 16, 256))):
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H

    backend = H.xla_backend()
    out = []
    for n, f, nodes, bins in shapes:
        rng = np.random.default_rng(n)
        binned = jnp.asarray(rng.integers(0, bins, (n, f)).astype(np.uint8))
        g = jnp.asarray(rng.standard_normal(n, dtype=np.float32))
        h = jnp.asarray(rng.uniform(0.1, 1.0, n).astype(np.float32))
        # ~3% masked rows (node -1), the rest spread over the frontier
        node = jnp.asarray(np.where(rng.random(n) < 0.03, -1,
                                    rng.integers(0, nodes, n)).astype(np.int32))

        @jax.jit
        def both(binned, g, h, node):
            qg, qh, _, _ = H.quantize_gradients(g, h, 16, seed=3)
            got = H.build_quantized(binned, qg, qh, node, nodes, bins,
                                    backend=backend)
            ref = H.build_histograms_quantized(binned, qg, qh, node, nodes,
                                               bins)
            return jnp.all(got == ref), got[..., 2].sum()

        same, rows = both(binned, g, h, node)
        kept = int((np.asarray(node) >= 0).sum()) * f
        if not bool(same):
            raise AssertionError(
                f"{backend} histogram != scatter reference at {n}x{f}")
        if int(rows) != kept:
            raise AssertionError(f"histogram counts {int(rows)} != {kept}")
        out.append(f"{n}x{f}/{nodes}nodes/{bins}bins")
    return f"auto->{backend}, bit-exact vs build_histograms_quantized at " \
        + ", ".join(out)


# ------------------------------------------------------------------ (c)

def phase_resnet(batch=64, hw=224, ref_images=4, rel_tol=0.05):
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.dl import ImageFeaturizer
    from mmlspark_tpu.models import resnet50
    from mmlspark_tpu.observability.compute import compile_report
    from mmlspark_tpu.ops import image as image_ops
    from mmlspark_tpu.parallel import get_active_mesh

    module = resnet50(num_classes=1000, dtype=jnp.bfloat16)
    # parameter shapes do not depend on H/W (global pool): init small
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 64, 64, 3), jnp.float32))
    rng = np.random.default_rng(1)
    images = rng.uniform(0, 255, (batch, hw, hw, 3)).astype(np.float32)
    col = np.empty(batch, dtype=object)
    for i in range(batch):
        col[i] = images[i]
    df = DataFrame.from_dict({"image": col})
    feat = ImageFeaturizer(input_col="image", output_col="features",
                           height=hw, width=hw, batch_size=batch)
    feat.set_model(module=module, variables=variables)
    out = np.stack(list(feat.transform(df).collect()["features"]))
    if out.shape != (batch, 2048):
        raise AssertionError(f"features shape {out.shape} != ({batch}, 2048)")
    out = out.astype(np.float32)
    if not np.isfinite(out).all():
        raise AssertionError("non-finite features")

    ref_module = resnet50(num_classes=1000, dtype=jnp.float32)

    @jax.jit
    def reference(variables, x):
        with jax.default_matmul_precision("highest"):
            return ref_module.apply(variables, image_ops.normalize(x),
                                    features=True)

    ref = np.asarray(reference(variables, images[:ref_images]))
    rel = np.linalg.norm(out[:ref_images] - ref, axis=1) \
        / np.linalg.norm(ref, axis=1)
    if not (rel.max() <= rel_tol):
        raise AssertionError(f"bf16 features off the f32 reference: relative "
                             f"L2 error {rel.max():.4f} > {rel_tol}")
    n_dev = get_active_mesh().devices.size
    placed = f"sharded over {n_dev} devices" \
        if n_dev > 1 and batch % n_dev == 0 else "on one device"
    sigs = [s["signature"].split(", ")[-1] for s in
            compile_report()["functions"]["runner.dl.jax_model"]["signatures"]]
    return (f"ResNet-50 bf16 {hw}x{hw} batch={batch} {placed} -> "
            f"{out.shape}, compiled buckets {sigs}; max relative L2 error "
            f"vs f32 'highest' on {ref_images} images {rel.max():.4f} "
            f"(tol {rel_tol})")


# ------------------------------------------------------------------ (d)

def phase_decode(batch=8, prompt=16, new_tokens=32, page_size=16):
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import ModelRunner, TransformerEncoder

    vocab = LM_CONFIG["vocab_size"]
    prompts = np.random.default_rng(3).integers(
        0, vocab, (batch, prompt)).astype(np.int32)
    paged_kw = dict(max_new_tokens=new_tokens, kv_layout="paged",
                    page_size=page_size)

    def make_runner(name):
        lm = TransformerEncoder(dtype=jnp.float32, **LM_CONFIG)
        variables = lm.init(jax.random.PRNGKey(2),
                            jnp.zeros((1, prompt), jnp.int32))
        return ModelRunner(module=lm, variables=variables, name=name,
                           batch_size=batch)

    def in_range(tokens, what):
        tokens = np.asarray(tokens)
        if tokens.shape != (batch, new_tokens):
            raise AssertionError(f"{what}: tokens shape {tokens.shape}")
        if tokens.min() < 0 or tokens.max() >= vocab:
            raise AssertionError(f"{what}: token id outside [0, {vocab})")
        return tokens

    def stream_once(runner):
        decoder = runner.decode_stream(slots=batch, prompt_bucket=prompt,
                                       max_new_tokens=new_tokens,
                                       page_size=page_size, prefix_cache=True)
        decoder.start()
        try:
            handles = [decoder.submit(p) for p in prompts]
            results = [h.result(timeout=600) for h in handles]
        finally:
            decoder.close()
        bad = [r.extras["status"] for r in results if r.extras["status"] != "ok"]
        if bad:
            raise AssertionError(f"stream requests ended {bad}")
        return (in_range(np.concatenate([r.tokens for r in results]),
                         "decode_stream"),
                decoder.index.stats())

    # ---- default matmul precision, the path a user runs: everything that
    # shares a batch geometry must agree exactly
    runner = make_runner("smoke.lm")
    dense = in_range(runner.decode(prompts, max_new_tokens=new_tokens).tokens,
                     "dense decode")
    paged = in_range(runner.decode(prompts, **paged_kw).tokens, "paged decode")
    again = in_range(runner.decode(prompts, **paged_kw).tokens, "paged repeat")
    if not np.array_equal(paged, again):
        raise AssertionError("same prompts decoded to different tokens")
    if not np.array_equal(dense, paged):
        raise AssertionError("paged decode != dense decode")
    cont, _ = stream_once(runner)
    # second pass: every prompt is now in the prefix index — hits must not
    # change a token
    cont2, index_stats = stream_once(runner)
    if not np.array_equal(cont2, cont):
        raise AssertionError("prefix-cache hits changed decoded tokens")
    if index_stats["hits"] < 1:
        raise AssertionError("repeated prompts never hit the prefix index")
    rows_equal = int((cont == paged).all(axis=1).sum())

    # ---- continuous (prefill at batch 1, step at `slots`) vs one-shot
    # (prefill at batch B) are different batch geometries.  On the TPU the
    # default f32 matmul is one bf16 pass, so their logits differ by ~2e-2
    # while this random-weight model's top-2 margins go down to ~1e-3
    # (measured, PERF.md PR 22): a near-tie argmax can flip and the greedy
    # tails then diverge.  The parity contract is therefore checked where
    # rounding cannot flip an argmax: full f32 matmul precision — set
    # process-wide, because the stream's engine thread compiles the join
    # prefill and the context-manager form is thread-local.
    default_precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        exact = make_runner("smoke.lm_highest")
        oneshot_hi = in_range(exact.decode(prompts, **paged_kw).tokens,
                              "one-shot (highest)")
        cont_hi, _ = stream_once(exact)
    finally:
        jax.config.update("jax_default_matmul_precision", default_precision)
    if not np.array_equal(cont_hi, oneshot_hi):
        raise AssertionError("continuous decode != one-shot decode at full "
                             "f32 matmul precision")
    return (f"transformer 4x256 f32 vocab {vocab}, {batch}x{new_tokens} "
            f"tokens: decode() dense == paged == repeat; decode_stream(slots="
            f"{batch}, prefix_cache=True) pass 1 == pass 2 (prefix index "
            f"hits/misses {index_stats['hits']}/{index_stats['misses']}); "
            f"continuous == one-shot at matmul precision 'highest' "
            f"({rows_equal}/{batch} rows also equal at the default "
            f"precision, where the two batch geometries round differently)")


# ------------------------------------------------------------------ (e)

def phase_server(rows=20_000, f=20, trees=100, req_rows=2048, requests=4):
    import http.client
    from mmlspark_tpu.core import DataFrame, Transformer
    from mmlspark_tpu.core.schema import vector_column
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    from mmlspark_tpu.observability import get_registry
    from mmlspark_tpu.serving import PipelineServer

    rng = np.random.default_rng(4)
    X = rng.standard_normal((rows, f)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] > 0).astype(np.float64)
    df = DataFrame.from_dict({"features": vector_column(list(X)), "label": y})
    model = LightGBMClassifier().set_params(num_iterations=trees).fit(df)

    def score(mat) -> np.ndarray:
        """P(class 1) per row through the fitted model's transform."""
        frame = DataFrame.from_dict({"features": vector_column(
            [np.asarray(r, np.float32) for r in mat])})
        prob = model.transform(frame).collect()["probability"]
        return np.asarray([p[1] for p in prob], np.float64)

    class Scorer(Transformer):
        """request = a matrix of rows, reply = their probabilities."""

        def _transform(self, frame):
            def per_part(p):
                out = np.empty(len(p["request"]), dtype=object)
                for i, mat in enumerate(p["request"]):
                    out[i] = score(mat).tolist()
                return {**p, "reply": out}
            return frame.map_partitions(per_part)

        def transform_schema(self, schema):
            return schema

    # the booster walks on the device only above rows*trees = 2**17
    if req_rows * trees <= 1 << 17:
        raise AssertionError("request too small to be scored on the chip")
    walk_compiles = get_registry().counter(
        "mmlspark_jit_compile_total", "XLA compilations by instrumented "
        "function", labels=("fn",)).labels(fn="models.gbdt_walk")
    walks0 = walk_compiles.value
    srv = PipelineServer(Scorer(), port=0, request_timeout_s=600.0).start()
    try:
        # the first request compiles the device walk: generous timeout
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=600)
        hdrs = {"Content-Type": "application/json"}
        worst = 0.0
        for r in range(requests + 1):          # request 0 is the warm-up
            batch = rng.standard_normal((req_rows, f)).astype(np.float32)
            conn.request("POST", srv.api_path, json.dumps(batch.tolist()), hdrs)
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                raise AssertionError(f"HTTP {resp.status}: {body[:200]!r}")
            reply = np.asarray(json.loads(body), np.float64)
            want = score(batch)
            if reply.shape != want.shape or not np.isfinite(reply).all():
                raise AssertionError(f"reply shape {reply.shape}")
            worst = max(worst, float(np.abs(reply - want).max()))
        conn.close()
    finally:
        srv.stop()
    if worst > 1e-6:
        raise AssertionError(f"server replies differ from model.transform "
                             f"by {worst:.2e}")
    on_chip = int(walk_compiles.value - walks0)
    if on_chip < 1:
        raise AssertionError("no device booster walk compiled: the requests "
                             "were scored on the host")
    acc = float(((score(X[:4096]) > 0.5) == (y[:4096] > 0.5)).mean())
    if acc < 0.9:
        raise AssertionError(f"classifier train accuracy {acc:.3f} < 0.9")
    return (f"LightGBMClassifier.fit {rows}x{f} {trees} trees (train acc "
            f"{acc:.3f}) -> PipelineServer: {requests}+1 requests x "
            f"{req_rows} rows scored by the device walk ({on_chip} "
            f"gbdt_walk compiles), max |reply - transform| {worst:.1e}")


# ------------------------------------------------------------------ (f)

def phase_sharded(state: dict):
    import jax
    from mmlspark_tpu.lightgbm import train
    from mmlspark_tpu.observability.tracing import trace_span
    from mmlspark_tpu.parallel import active_mesh, data_parallel_mesh

    X, y, n, params = state["X"], state["y"], state["n"], state["params"]
    n_dev = len(jax.devices())
    f = X.shape[1]
    seen = {}

    def watch_shards(_it, _ev):
        if seen:                              # one look is enough
            return
        # the binned matrix is the only (rows, F) uint8 array alive
        for a in jax.live_arrays():
            if a.dtype == np.uint8 and a.ndim == 2 and a.shape[1] == f \
                    and a.shape[0] >= n:
                seen["devices"] = sorted(
                    int(s.device.id) for s in a.addressable_shards)
                seen["shard_rows"] = sorted(
                    {int(s.data.shape[0]) for s in a.addressable_shards})

    def sharded(width):
        with active_mesh(data_parallel_mesh(width)), \
                trace_span(f"chip_smoke.sharded{width}") as sp:
            res = train(X[:n], y[:n], params, shard_rows=True,
                        callbacks=[watch_shards])
        return res.booster, train_span_facts(sp.trace_id)

    wide, facts = sharded(n_dev)
    if seen.get("devices") != sorted(int(d.id) for d in jax.devices()):
        raise AssertionError(
            f"binned matrix shards sit on devices {seen.get('devices')}, "
            f"not one on each of {n_dev}")
    shard_note = f"binned shards on devices {seen['devices']} " \
                 f"({seen['shard_rows']} rows each)"
    # the one-chip run of the SAME sharded program: bit-identical trees at
    # any mesh width is the elastic-resume contract (global-row-id noise)
    one, _ = sharded(1)
    for k in ("split_feature", "threshold_bin", "left_child", "right_child",
              "leaf_value"):
        if not np.array_equal(getattr(wide, k), getattr(one, k)):
            raise AssertionError(
                f"{n_dev}-chip trees differ from the one-chip run in {k}")
    # phase (a)'s unsharded booster draws its stochastic-rounding noise from
    # a shape-keyed stream, the sharded grower from a global-row-id-keyed
    # one (ops.histogram.quantize_gradients): same quantizer, different
    # noise, so those trees are compared by what they predict
    ref = state["booster"]
    hold = X[n:]
    same_splits = float((wide.split_feature == ref.split_feature).mean())
    p_wide, p_ref = wide.predict(hold), ref.predict(hold)
    gap = np.abs(p_wide - p_ref)
    acc = float(((p_wide > 0.5) == (y[n:] > 0.5)).mean())
    acc_ref = float(((p_ref > 0.5) == (y[n:] > 0.5)).mean())
    if gap.mean() > 0.02 or abs(acc - acc_ref) > 0.01:
        raise AssertionError(
            f"sharded vs unsharded boosters disagree beyond rounding noise: "
            f"mean |dP| {gap.mean():.4f}, holdout acc {acc:.4f} vs "
            f"{acc_ref:.4f}")
    return (f"{n_dev} devices, backend={facts['hist_backend']} "
            f"quantized={facts['quantized']}: {shard_note}; trees bit-equal "
            f"to the 1-device sharded run; vs unsharded phase (a) (other "
            f"rounding-noise stream): {100 * same_splits:.1f}% split "
            f"features equal, |dP| mean {gap.mean():.4f} max "
            f"{gap.max():.4f}, holdout_acc {acc:.4f} vs {acc_ref:.4f}")


# ----------------------------------------------------------------- main

def main() -> int:
    from importlib.metadata import version
    from mmlspark_tpu.utils.device import (enable_compilation_cache,
                                           require_tpu)
    try:
        dev = require_tpu()
    except RuntimeError as e:
        print(f"chip_smoke: {e}; nothing was run", file=sys.stderr)
        return 2
    cache_dir = enable_compilation_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"platform={dev['platform']} device_kind={dev['kind']} "
          f"count={dev['count']} jax={version('jax')} "
          f"jaxlib={version('jaxlib')} libtpu={version('libtpu')} "
          f"compile_cache={cache_dir} (entries at start: {entries})",
          flush=True)

    clock = CompileClock()
    t0 = time.perf_counter()
    state: dict = {}
    run_phase(clock, "a gbdt", phase_gbdt, state=state)
    run_phase(clock, "b hist_exact", phase_hist_exact)
    run_phase(clock, "c resnet50", phase_resnet)
    run_phase(clock, "d decode", phase_decode)
    run_phase(clock, "e server", phase_server)
    if dev["count"] > 1:
        run_phase(clock, "f sharded_gbdt", phase_sharded, state=state)
    else:
        print("[f sharded_gbdt] skipped: one device visible", flush=True)
    print(f"total compile_s={clock.compile_s:.1f} "
          f"wall_s={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
