"""North-star bench (BASELINE.json): LightGBM rows/sec/chip on 1M x 200.

Prints JSON lines {"metric", "value", "unit", "vs_baseline", ...extras};
the LAST line printed is the result (the driver parses last-JSON-wins).

vs_baseline = TPU rows/sec divided by this host's CPU-executor rows/sec for
the identical trainer (the reference target is >=8x CPU-executor throughput,
BASELINE.md).  ResNet-50 featurize images/sec/chip rides in the extras.

Process design:

- The PARENT process never imports JAX.  A chip belongs to one process at a
  time, so every device phase runs in its own child, one after another; the
  parent streams the child's merged output and kills only on silence.
- A device phase that finds no TPU fails (``require_tpu``), and the run
  exits non-zero when any device phase failed.  Nothing is re-run on the
  CPU under a device metric's name.
- A valid JSON result line is printed after EVERY phase, so an outer
  timeout can never erase completed measurements.
- The persistent XLA compilation cache is enabled in children
  (``mmlspark_tpu.utils.device.enable_compilation_cache``).
- The CPU-executor baseline and the HTTP serving phase are host cells: they
  run pinned to the CPU platform, the baseline FIRST and STRICTLY ALONE,
  median-of-3 with the host fingerprint (nproc/model/load) in extras.
- Device phases that miss their (compile-aware) deadline get ONE retry — a
  completed first-attempt compile lands in the persistent cache, making the
  retry measurement-only — and a killed phase leaves a note in
  extras.phase_notes instead of silence.
- Timed loops vary their inputs every step and end with a host fetch.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))

RESULT = {
    "metric": "lightgbm_train_rows_per_sec_per_chip_1Mx200",
    "value": None,
    "unit": "rows/sec",
    "vs_baseline": None,
    "extras": {},
}


def _emit() -> None:
    print(json.dumps(RESULT), flush=True)


def _metrics_snapshot_json(max_bytes: int = 4096) -> str:
    """Bounded, redact-free ``/metrics``-style snapshot of this process's
    registry (ISSUE 11): each phase child prints it as a ``PHASE_METRICS``
    marker so bench regressions can be diagnosed from counters instead of
    reruns.  Redact-free by construction: exemplars (trace ids) and help
    text are stripped; when the JSON overflows ``max_bytes`` the largest
    families are dropped and NAMED — truncation must be attributable,
    never silent."""
    import json as _json
    from mmlspark_tpu.observability import get_registry
    body = get_registry().to_dict()
    for fam in body.values():
        fam.pop("help", None)
        for s in fam.get("samples", ()):
            s.pop("exemplars", None)
    dropped = []
    while True:
        payload = dict(body)
        if dropped:
            payload["_dropped_families"] = dropped
        out = _json.dumps(payload, separators=(",", ":"), default=str)
        if len(out) <= max_bytes or not body:
            return out
        largest = max(body,
                      key=lambda k: len(_json.dumps(body[k], default=str)))
        body.pop(largest)
        dropped.append(largest)


def _emit_phase_metrics() -> None:
    """Print the post-phase registry snapshot marker (child side)."""
    try:
        print(f"PHASE_METRICS {_metrics_snapshot_json()}", flush=True)
    except Exception as e:  # noqa: BLE001 — telemetry must not kill a phase
        _log(f"[bench] phase metrics snapshot failed: {e}")


def _record_phase_metrics(phase: str, got: dict) -> bool:
    """Fold a child's ``PHASE_METRICS`` snapshot into the run artifact's
    extras; absent or garbled markers fold nothing (False)."""
    raw = got.get("PHASE_METRICS")
    if not isinstance(raw, str) or not raw:
        return False
    try:
        snap = json.loads(raw)
    except ValueError:
        return False
    if not isinstance(snap, dict):
        return False
    RESULT["extras"].setdefault("phase_metrics", {})[phase] = snap
    return True


def _log(msg) -> None:
    print(msg, file=sys.stderr, flush=True)


#: published per-chip peaks keyed by ``jax.devices()[0].device_kind``
#: (Google Cloud documentation, "TPU v5e").  A device that is not in the
#: table is an error, not a default.
_PEAKS = {"TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0}}


def _device_phase_setup() -> dict:
    """Every device phase starts here: fail without a TPU, place the
    compile cache, return this chip's peaks."""
    from mmlspark_tpu.utils.device import (enable_compilation_cache,
                                           require_tpu)
    kind = require_tpu()["kind"]
    enable_compilation_cache()
    if kind not in _PEAKS:
        raise RuntimeError(f"no published peaks on record for device_kind "
                           f"{kind!r}; add it to bench._PEAKS with its source")
    return _PEAKS[kind]


# --------------------------------------------------------------------------
# phase bodies (run inside child processes; print MARKER lines on stdout)
# --------------------------------------------------------------------------

def phase_gbdt(n=1_000_000, f=200, iters_a=8, iters_b=24, reps=3) -> None:
    """Marginal boosting rate: rows * (B - A) / (t_B - t_A), median of
    ``reps`` repetitions.  The marginal form subtracts the shared fixed
    costs (compile — cached across calls since the jitted per-iteration
    program's key excludes num_iterations — binning, host->device
    transfer), leaving the steady-state training rate both backends are
    judged by.  Every train() call flips a fresh window of labels, so
    init_score and the whole score trajectory differ between calls."""
    peaks = _device_phase_setup()
    import numpy as np
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y0 = (X[:, 0] + 0.5 * X[:, 1] + rng.normal(scale=0.3, size=n) > 0).astype(np.float32)
    nonce = [0]

    def fresh_y():
        nonce[0] += 1
        y = y0.copy()
        a = (37 * nonce[0]) % (n - 64)
        y[a:a + 64] = 1.0 - y[a:a + 64]
        return y

    bc = {}   # binning + device-put memo: X never changes across calls
    t0 = time.perf_counter()
    # warm at iters_a so BOTH timed runs hit the chunked program (default
    # CH engages from 2*CH iterations; 1-iteration warm would only
    # compile the unchunked path)
    train(X, fresh_y(), GBDTParams(num_iterations=iters_a, objective="binary",
                                   max_depth=5), bin_cache=bc)
    _log(f"[bench] gbdt warm(compile) {time.perf_counter() - t0:.0f}s")
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        train(X, fresh_y(), GBDTParams(num_iterations=iters_a,
                                       objective="binary", max_depth=5),
              bin_cache=bc)
        t_a = time.perf_counter() - t0
        t0 = time.perf_counter()
        train(X, fresh_y(), GBDTParams(num_iterations=iters_b,
                                       objective="binary", max_depth=5),
              bin_cache=bc)
        t_b = time.perf_counter() - t0
        rates.append(n * (iters_b - iters_a) / max(t_b - t_a, 1e-9))
        _log(f"[bench] gbdt rep rate {rates[-1]:.0f}")
    rates.sort()
    rate = rates[len(rates) // 2]
    print(f"GBDT_RPS {rate} {n}", flush=True)

    # achievable-utilization denominator (PR 6 follow-up): the instrumented
    # jit captured cost_analysis for the per-iteration program — fold its
    # bytes-accessed into an HBM-roofline utilization % so tile-size tuning
    # (and the fused-kernel item) have a denominator, not just a rate.
    try:
        from mmlspark_tpu.observability.compute import compile_report
        fns = compile_report()["functions"]
        if "lightgbm.multi_iter" in fns:
            cost = fns["lightgbm.multi_iter"].get("last_cost_analysis") or {}
            ch = int(os.environ.get("MMLSPARK_TPU_GBDT_CHUNK") or 4)
        else:
            cost = (fns.get("lightgbm.iter") or {}).get(
                "last_cost_analysis") or {}
            ch = 1
        bytes_prog = cost.get("bytes_accessed")
        if bytes_prog:
            bytes_per_iter = bytes_prog / max(1, ch)
            peak = peaks["hbm_gbps"] * 1e9
            util_pct = 100.0 * bytes_per_iter * (rate / n) / peak
            print(f"GBDT_UTIL {bytes_per_iter} {util_pct}", flush=True)
    except Exception as e:  # noqa: BLE001 — cost analysis is best-effort
        _log(f"[bench] gbdt util skipped: {e}")


def phase_hist_ab(n=1_000_000, f=200, nodes=16, reps=3) -> None:
    """Packed-int vs f32 3-channel histogram build A/B on the SAME shape —
    the attribution artifact for the quantized-gradient pipeline (packed
    int8 MXU operands vs the bf16 residual channels; see ops/histogram.py).

    Compares the matmul backends at the bench shape (1M x 200): f32 =
    ``residuals=False`` (the 3-channel f32 build, the strongest f32
    baseline) vs quantize+``build_histograms_matmul_quantized``.
    Quantization rides INSIDE the packed timing — the A/B charges the
    packed path its full per-iteration cost.  Inputs perturb per rep."""
    _device_phase_setup()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mmlspark_tpu.ops import histogram as hist_ops

    B = 256
    rng = np.random.default_rng(0)
    binned = jnp.asarray(rng.integers(0, B - 1, (n, f)).astype(np.uint8))
    g0 = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h0 = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
    node = jnp.asarray((np.arange(n) % nodes).astype(np.int32))

    @jax.jit
    def f32_build(g, h):
        return hist_ops.build_histograms_matmul(binned, g, h, node, nodes, B,
                                                residuals=False)

    @jax.jit
    def packed_build(g, h):
        qg, qh, _, _ = hist_ops.quantize_gradients(g, h, 16)
        return hist_ops.build_histograms_matmul_quantized(
            binned, qg, qh, node, nodes, B, quant_bins=16)

    def timed(fn, tag):
        jax.block_until_ready(fn(g0, h0))       # compile warm
        _log(f"[bench] hist_ab {tag} warm done")
        rates = []
        for r in range(1, reps + 1):
            g = g0 + 0.001 * r                  # first-sight args per rep
            t0 = time.perf_counter()
            jax.block_until_ready(fn(g, h0))
            rates.append(n / (time.perf_counter() - t0))
            _log(f"[bench] hist_ab {tag} rep rows/s {rates[-1]:.0f}")
        rates.sort()
        return rates[len(rates) // 2]

    r_f32 = timed(f32_build, "f32")
    r_packed = timed(packed_build, "packed")
    print(f"HIST_AB_RATES {r_f32} {r_packed} {r_packed / max(r_f32, 1e-9)}", flush=True)
    print(f"HIST_AB_MODE tpu_matmul {n} {f}", flush=True)


def phase_runner(n=2000, hw=32, batch=128, reps=3, vocab=512, dec_batch=8,
                 prompt=16, new_tokens=32) -> None:
    """Unified-runner A/B (ISSUE 9): batch featurize throughput through
    ``ModelRunner.apply_batch`` vs the legacy hand-rolled glue the runner
    replaced (per-bucket ``jax.jit`` + pad, inlined here verbatim since the
    library copy is gone) — same model, same buckets, same ragged row count,
    so the ratio isolates the runner's host-side overhead (acceptance:
    runner >= 0.9x legacy).  A decode arm then measures KV-cached batched
    generation (prefill + one compiled step re-dispatched per token) and
    reports tokens/sec — the ROADMAP's generative-serving number.  Inputs
    perturb per rep."""
    _device_phase_setup()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mmlspark_tpu.models import ModelRunner, TransformerEncoder, resnet18
    from mmlspark_tpu.models.runner import bucket_rows

    module = resnet18(num_classes=64, dtype=jnp.float32)
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, hw, hw, 3), jnp.float32))
    x0 = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (n, hw, hw, 3),
                                       jnp.float32))

    def pure(vs, chunk):
        return module.apply(vs, chunk, features=True)

    # --- legacy arm: the pre-runner JaxModel glue, one jit per bucket
    legacy_cache = {}

    def legacy_apply(x):
        outs = []
        for start in range(0, x.shape[0], batch):
            chunk = x[start:start + batch]
            m = chunk.shape[0]
            bucket = bucket_rows(m, batch)
            if m < bucket:
                chunk = np.concatenate(
                    [chunk, np.repeat(chunk[-1:], bucket - m, axis=0)])
            fn = legacy_cache.get(bucket)
            if fn is None:
                fn = legacy_cache[bucket] = jax.jit(pure)
            outs.append(np.asarray(fn(variables, chunk))[:m])
        return np.concatenate(outs)

    runner = ModelRunner(module=module, variables=variables,
                         apply_kwargs={"features": True},
                         name="bench.resnet", batch_size=batch)

    def timed(fn, tag):
        fn(x0)                                   # compile warm, all buckets
        _log(f"[bench] runner {tag} warm done")
        rates = []
        for r in range(1, reps + 1):
            x = x0 + np.float32(0.001 * r)       # first-sight args per rep
            t0 = time.perf_counter()
            fn(x)
            rates.append(n / (time.perf_counter() - t0))
            _log(f"[bench] runner {tag} rep rows/s {rates[-1]:.0f}")
        rates.sort()
        return rates[len(rates) // 2]

    r_legacy = timed(legacy_apply, "legacy")
    r_runner = timed(runner.apply_batch, "runner")
    print(f"RUNNER_AB {r_legacy} {r_runner} "
          f"{r_runner / max(r_legacy, 1e-9)}", flush=True)

    # --- decode arm: KV-cached batched generation tokens/sec
    lm = TransformerEncoder(vocab_size=vocab, num_classes=vocab,
                            embed_dim=256, num_heads=4, num_layers=4,
                            mlp_dim=512, max_len=4096, causal=True,
                            pool="none", dtype=jnp.float32)
    lm_vars = lm.init(jax.random.PRNGKey(2),
                      jnp.zeros((1, prompt), jnp.int32))
    dec = ModelRunner(module=lm, variables=lm_vars, name="bench.lm",
                      batch_size=dec_batch)
    rng = np.random.default_rng(0)
    prompts0 = rng.integers(0, vocab, (dec_batch, prompt)).astype(np.int32)
    dec.decode(prompts0, max_new_tokens=new_tokens)    # compile warm
    _log("[bench] runner decode warm done")
    rates = []
    for r in range(1, reps + 1):
        prompts = (prompts0 + r) % vocab               # first-sight args
        t0 = time.perf_counter()
        res = dec.decode(prompts, max_new_tokens=new_tokens)
        tps = res.tokens.size / (time.perf_counter() - t0)
        rates.append(tps)
        _log(f"[bench] runner decode rep tokens/s {tps:.1f}")
    rates.sort()
    print(f"RUNNER_DECODE {rates[len(rates) // 2]} {dec_batch} {new_tokens}",
          flush=True)

    # --- paged-vs-dense decode A/B at a high-concurrency ragged shape
    # (ISSUE 12): the paged cache reads W*page_size gathered slots instead
    # of the dense pow2 reservation AND updates pages donated in place, so
    # on-chip it must clear 1.2x dense tokens/sec.  Ragged lengths make the
    # occupancy number honest.
    conc = 32
    rngp = np.random.default_rng(7)
    rag = rngp.integers(0, vocab, (conc, prompt)).astype(np.int32)
    rag_lens = rngp.integers(max(2, prompt // 4), prompt + 1,
                             conc).astype(np.int32)
    rag_lens[0] = prompt                       # keep the prompt bucket full
    page_size = 16
    paged_kw = {"kv_layout": "paged", "page_size": page_size}
    state = {}

    def timed_paged_ab(kw, tag):
        dec.decode(rag, lengths=rag_lens, max_new_tokens=new_tokens, **kw)
        _log(f"[bench] runner decode {tag} warm done")
        rates = []
        for r in range(1, reps + 1):
            p = (rag + r) % vocab
            t0 = time.perf_counter()
            res = dec.decode(p, lengths=rag_lens, max_new_tokens=new_tokens,
                             **kw)
            rates.append(res.extras["real_tokens"]
                         / (time.perf_counter() - t0))
            _log(f"[bench] runner decode {tag} rep tokens/s {rates[-1]:.1f}")
        state[tag] = res.extras
        rates.sort()
        return rates[len(rates) // 2]

    d_tps = timed_paged_ab({}, "dense")
    p_tps = timed_paged_ab(paged_kw, "paged")
    occ = state["paged"]["page_occupancy_pct"]
    hbm = state["paged"]["cache_bytes_per_seq"]
    print(f"RUNNER_PAGED {d_tps} {p_tps} {p_tps / max(d_tps, 1e-9)} "
          f"{occ} {hbm}", flush=True)

    # --- continuous-vs-ticked decode A/B under ragged Poisson arrivals
    # (ISSUE 13): the SAME request trace — Poisson arrivals (in step units,
    # idle gaps fast-forwarded for free on both sides), ragged prompts and
    # ragged token budgets — served two ways.  Ticked: the pre-13 serving
    # drain — when the in-flight batch finishes, take whatever has arrived
    # (up to `slots`) and decode it as one batch bound by its SLOWEST
    # member's budget; arrivals mid-batch wait for the next tick.
    # Continuous: ContinuousDecoder — arrivals join free slots between
    # steps, finished sequences leave and free their slot mid-flight.
    # Both sides do identical useful work (each request's budget tokens),
    # so the wall ratio is the batching win; acceptance on-chip >= 1.5x.
    # The trace also counter-checks the no-new-compile-keys rule: joins after warmup must
    # cause ZERO new step-executable compiles.
    from collections import deque as _deque
    from mmlspark_tpu.models import SlotsExhausted
    slots = 8
    n_req = 48
    page = 16
    rngc = np.random.default_rng(17)
    # WIDELY ragged budgets are the ticked drain's waste driver (every
    # member runs to the group max); 1.25x-capacity Poisson arrivals keep
    # both engines saturated, so wall ratio == dispatched-work ratio and
    # the free idle fast-forward below almost never triggers
    min_b = max(2, new_tokens // 8)
    reqs = []
    rate = 1.25 * slots / ((min_b + new_tokens) / 2.0)
    arrive = 0.0
    for _ in range(n_req):
        arrive += rngc.exponential(1.0 / rate)
        plen = int(rngc.integers(max(2, prompt // 4), prompt + 1))
        reqs.append((rngc.integers(0, vocab, plen).astype(np.int32),
                     plen, int(rngc.integers(min_b, new_tokens + 1)),
                     int(arrive)))
    useful = sum(r[2] for r in reqs)

    disp = {"ticked": (0, 0), "cont": (0, 0)}   # (prefills, steps)

    def ticked_engine():
        t0 = time.perf_counter()
        clock_steps, i = 0, 0
        n_pre = n_steps = 0
        while i < len(reqs):
            if reqs[i][3] > clock_steps:
                clock_steps = reqs[i][3]          # idle: jump to arrival
            group = []
            while i < len(reqs) and reqs[i][3] <= clock_steps \
                    and len(group) < slots:
                group.append(reqs[i])
                i += 1
            gmax = max(r[2] for r in group)
            stacked = np.zeros((len(group), prompt), np.int32)
            lens = np.asarray([r[1] for r in group], np.int32)
            for j, r in enumerate(group):
                stacked[j, :r[1]] = r[0]
            res = dec.decode(stacked, lengths=lens, max_new_tokens=gmax,
                             kv_layout="paged", page_size=page,
                             batch_bucket=slots, prompt_bucket=prompt)
            n_pre += 1
            n_steps += res.steps
            clock_steps += gmax                   # batch held the engine
        disp["ticked"] = (n_pre, n_steps)
        return useful / (time.perf_counter() - t0)

    def continuous_engine():
        decoder = dec.decode_stream(slots=slots, prompt_bucket=prompt,
                                    max_new_tokens=new_tokens,
                                    page_size=page)
        b0 = dec._c_batches["decode"].value   # join-prefill dispatch base
        pend = _deque(reqs)
        handles = []
        t0 = time.perf_counter()
        virtual = 0
        while pend or decoder._live or decoder._arrivals:
            now_step = decoder.steps + virtual
            while pend and pend[0][3] <= now_step:
                try:
                    handles.append(decoder.submit(
                        pend[0][0], max_new_tokens=pend[0][2]))
                except SlotsExhausted:
                    break                          # backpressure: next leave
                pend.popleft()
            if decoder._live or decoder._arrivals:
                decoder.step()
            elif pend:
                virtual = pend[0][3] - decoder.steps  # idle fast-forward
        wall = time.perf_counter() - t0
        disp["cont"] = (int(dec._c_batches["decode"].value - b0),
                        decoder.steps)
        decoder.close()
        return useful / wall, handles

    # warmup: the stream executables + ONE ticked decode per distinct
    # table width any group's gmax in [min_b, new_tokens] can produce (a
    # width compiled mid-run would tax the ticked wall unfairly)
    dec.decode_stream(slots=slots, prompt_bucket=prompt,
                      max_new_tokens=new_tokens, page_size=page).warmup()
    widths = {}
    for m in range(min_b, new_tokens + 1):
        widths.setdefault(-(-(prompt + m) // page), m)
    for warm_nt in widths.values():
        wp = rngc.integers(0, vocab, (slots, prompt)).astype(np.int32)
        dec.decode(wp, max_new_tokens=warm_nt, kv_layout="paged",
                   page_size=page, batch_bucket=slots, prompt_bucket=prompt)
    _log("[bench] runner cont warm done")

    def step_compiles():
        return sum(getattr(w, "compiles", 0) for w in dec._wrappers
                   if "decode_step" in getattr(w, "name", ""))

    # attribution bracket (ISSUE 17): snapshot the useful-vs-wasted token
    # ledger and device-seconds counters around the measured A/B so the
    # round artifact carries goodput%% and device-cost-per-1k-tokens for
    # exactly the work the RUNNER_CONT numbers describe
    from mmlspark_tpu.observability.attribution import OUTCOMES
    att0 = {o: dec._c_tok_outcome.value(outcome=o) for o in OUTCOMES}
    dev_s0 = dec._c_device_s.value()
    gen0 = dec._c_decode_tokens.value

    # median of `reps` passes per engine (same protocol as the other
    # arms; the RATIO is the acceptance number)
    t_rates = []
    for _ in range(reps):
        t_rates.append(ticked_engine())
        _log(f"[bench] runner ticked tokens/s {t_rates[-1]:.1f}")
    t_rates.sort()
    t_tps = t_rates[len(t_rates) // 2]
    # the join-compile gate brackets the CONTINUOUS traces only: a ticked
    # compile (warmup gap) must never be misattributed to joins
    n_step0 = step_compiles()
    c_rates = []
    for _ in range(reps):
        c_tps, handles = continuous_engine()
        c_rates.append(c_tps)
        _log(f"[bench] runner continuous tokens/s {c_rates[-1]:.1f}")
    c_rates.sort()
    c_tps = c_rates[len(c_rates) // 2]
    # goodput + device cost over the bracket: useful share of every token
    # cell the ledger classified, and device-seconds per 1k real generated
    # tokens (the /fleet/capacity per-class number's bench ground truth)
    att = {o: dec._c_tok_outcome.value(outcome=o) - att0[o] for o in OUTCOMES}
    g_useful = att["useful"]
    g_wasted = sum(v for o, v in att.items() if o != "useful")
    goodput_pct = 100.0 * g_useful / max(g_useful + g_wasted, 1e-9)
    dev_s = dec._c_device_s.value() - dev_s0
    gen_tokens = dec._c_decode_tokens.value - gen0
    dev_per_1k = 1000.0 * dev_s / max(gen_tokens, 1e-9)
    _log(f"[bench] runner goodput ledger: useful {g_useful:.0f} wasted "
         f"{g_wasted:.0f} by-outcome "
         f"{ {o: round(v) for o, v in att.items() if v} } "
         f"device_s {dev_s:.3f} over {gen_tokens:.0f} tokens")
    print(f"RUNNER_GOODPUT {goodput_pct} {dev_per_1k}", flush=True)
    # device work per useful token is the machine-independent half of the
    # story: the ticked drain burns slowest-member padding steps (every
    # step at full batch width) and full-width prefills, while the
    # continuous engine steps only live work and prefills each arrival
    # alone.  Token-forward units: prefill = rows*prompt, step = batch
    # width.
    t_tf = disp["ticked"][0] * slots * prompt + disp["ticked"][1] * slots
    c_tf = disp["cont"][0] * prompt + disp["cont"][1] * slots
    _log(f"[bench] runner cont device work (token-forwards): "
         f"ticked {t_tf} vs continuous {c_tf} "
         f"({t_tf / max(c_tf, 1):.2f}x saved)")
    # read the counter BEFORE the parity references below: their one-shot
    # bb=1 signatures legitimately compile and must not be charged to joins
    new_steps = step_compiles() - n_step0
    parity = 1
    for (p, _plen, budget, _a), h in list(zip(reqs, handles))[:3]:
        ref = dec.decode(p[None], max_new_tokens=budget,
                         kv_layout="paged", page_size=page)
        if list(ref.tokens[0]) != h.tokens:
            parity = 0
    print(f"RUNNER_CONT {t_tps} {c_tps} {c_tps / max(t_tps, 1e-9)} "
          f"{parity} {new_steps}", flush=True)

    # --- prefix-cache cached-vs-cold TTFT A/B under template-sharing
    # arrivals (ISSUE 20): the SAME Poisson trace of template+suffix
    # prompts replayed twice through the ContinuousDecoder — cold
    # (prefix_cache=False, every join prefills the full prompt) and cached
    # (admission consults the PrefixIndex, joins prefill only the uncached
    # suffix).  Useful work is identical, so the TTFT-p99 ratio is the
    # skipped-prefill win; acceptance on-chip >= 1.3x.  The replay also
    # counter-checks the zero-new-compile-keys rule across EVERY hit
    # length the trace produces.
    page_p = 4
    slots_p = 8
    n_preq = 40
    tpl_len = max(page_p * 3, prompt - 4)     # 3 shared pages per template
    suf_len = max(2, prompt - tpl_len)
    pref_budget = max(4, new_tokens // 2)
    rngx = np.random.default_rng(23)
    templates = [rngx.integers(0, vocab, tpl_len).astype(np.int32)
                 for _ in range(3)]
    preqs = []
    arrive_p = 0.0
    rate_p = 1.25 * slots_p / max(pref_budget, 1)
    for i in range(n_preq):
        arrive_p += rngx.exponential(1.0 / rate_p)
        p = np.concatenate([templates[i % len(templates)],
                            rngx.integers(0, vocab, suf_len).astype(np.int32)])
        preqs.append((p.astype(np.int32), pref_budget, int(arrive_p)))

    def prefix_engine(enabled: bool):
        decoder = dec.decode_stream(slots=slots_p, prompt_bucket=prompt,
                                    max_new_tokens=pref_budget,
                                    page_size=page_p, prefix_cache=enabled)
        pend = _deque(preqs)
        handles = []
        virtual = 0
        while pend or decoder._live or decoder._arrivals:
            now_step = decoder.steps + virtual
            while pend and pend[0][2] <= now_step:
                try:
                    handles.append(decoder.submit(
                        pend[0][0], max_new_tokens=pend[0][1]))
                except SlotsExhausted:
                    break
                pend.popleft()
            if decoder._live or decoder._arrivals:
                decoder.step()
            elif pend:
                virtual = pend[0][2] - decoder.steps
        ttfts = sorted(1000.0 * h.ttft_s for h in handles
                       if h.ttft_s is not None)
        stats = decoder.index.stats() if enabled else None
        decoder.close()
        return ttfts, stats, handles

    # warm EVERY signature the replay can touch (join prefill, sampler,
    # fused step, CoW page copy) so the compile bracket below measures
    # the hit path, not first-build compiles
    dec.decode_stream(slots=slots_p, prompt_bucket=prompt,
                      max_new_tokens=pref_budget, page_size=page_p,
                      prefix_cache=True).warmup()
    _log("[bench] runner prefix warm done")

    def all_compiles():
        return sum(getattr(w, "compiles", 0) for w in dec._wrappers)

    n_c0 = all_compiles()
    cold_ttfts: list = []
    for _ in range(reps):
        t, _s, _h = prefix_engine(False)
        cold_ttfts.extend(t)
    cached_ttfts: list = []
    pstats, phandles = None, []
    for _ in range(reps):
        t, pstats, phandles = prefix_engine(True)
        cached_ttfts.extend(t)
    new_px = all_compiles() - n_c0      # read BEFORE the parity one-shots
    cold_ttfts.sort()
    cached_ttfts.sort()
    cold_p99 = cold_ttfts[int(len(cold_ttfts) * 0.99)] if cold_ttfts else 0.0
    cach_p99 = cached_ttfts[int(len(cached_ttfts) * 0.99)] \
        if cached_ttfts else 0.0
    hit_rate = (pstats or {}).get("hit_rate_pct", 0.0)
    # retained pages pin the shared auto pool full — release them so the
    # cold parity one-shots (prefix_cache=False, so no reclaim path) can
    # allocate from the same pool
    idx_p = dec.prefix_cache(page_p)
    idx_p.evict_pages(idx_p.retained_pages(), reason="pressure")
    parity_p = 1
    for (p, budget, _a), h in list(zip(preqs, phandles))[:3]:
        ref = dec.decode(p[None], max_new_tokens=budget,
                         kv_layout="paged", page_size=page_p)
        if list(ref.tokens[0]) != h.tokens:
            parity_p = 0
    _log(f"[bench] runner prefix ttft p99 cold {cold_p99:.2f}ms cached "
         f"{cach_p99:.2f}ms hit_rate {hit_rate:.1f}% compiles {new_px}")
    print(f"RUNNER_PREFIX {cold_p99} {cach_p99} "
          f"{cold_p99 / max(cach_p99, 1e-9)} {hit_rate} {parity_p} "
          f"{new_px}", flush=True)


def phase_ooc(n=200_000, f=50, iters=8, tiles=4, reps=3) -> None:
    """Out-of-core streamed-vs-in-memory A/B at a fits-in-memory shape —
    the OVERHEAD bound for the chunked pipeline (ISSUE 7 acceptance:
    streamed >= 0.9x in-memory when tiling buys nothing, with the
    prefetch-overlap %% reported so a miss is attributable to transfer
    stalls vs per-pass overhead).  Same trainer config both sides; the
    streamed run forces ``tiles`` tiles through ``tile_rows``.  Labels
    perturb per rep."""
    _device_phase_setup()
    import numpy as np
    from mmlspark_tpu.lightgbm import GBDTParams, train, train_streamed

    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y0 = (X[:, 0] + 0.5 * X[:, 1] + rng.normal(scale=0.3, size=n) > 0) \
        .astype(np.float32)
    nonce = [0]

    def fresh_y():
        nonce[0] += 1
        y = y0.copy()
        a = (37 * nonce[0]) % (n - 64)
        y[a:a + 64] = 1.0 - y[a:a + 64]
        return y

    pkw = dict(num_iterations=iters, objective="binary", max_depth=5)
    tile_rows = -(-n // max(1, tiles))
    t0 = time.perf_counter()
    train(X, fresh_y(), GBDTParams(**pkw))
    train_streamed(X, fresh_y(), GBDTParams(**pkw), tile_rows=tile_rows)
    _log(f"[bench] ooc warm(compile) {time.perf_counter() - t0:.0f}s")
    r_mem, r_str, overlaps = [], [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        train(X, fresh_y(), GBDTParams(**pkw))
        r_mem.append(n * iters / max(time.perf_counter() - t0, 1e-9))
        t0 = time.perf_counter()
        res = train_streamed(X, fresh_y(), GBDTParams(**pkw),
                             tile_rows=tile_rows)
        r_str.append(n * iters / max(time.perf_counter() - t0, 1e-9))
        overlaps.append(res.extras["prefetch_overlap_pct"])
        _log(f"[bench] ooc rep inmem {r_mem[-1]:.0f} streamed {r_str[-1]:.0f}"
             f" overlap {overlaps[-1]:.1f}%")
    r_mem.sort(), r_str.sort(), overlaps.sort()
    mid = len(r_mem) // 2
    print(f"OOC_AB {r_mem[mid]} {r_str[mid]} "
          f"{r_str[mid] / max(r_mem[mid], 1e-9)} {overlaps[mid]} {tiles}",
          flush=True)

    # checkpoint-overhead arm (ISSUE 10 acceptance: <= 5% at this shape):
    # the SAME streamed config with periodic atomic checkpoints on, so the
    # cost of durability is a tracked number instead of a vibe.  Snapshot
    # serialization rides a background writer; what this measures is the
    # residual drag (snapshot list copies + the terminal blocking save).
    import shutil
    import tempfile
    ck_every = max(1, iters // 4)
    r_ck = []
    for _ in range(reps):
        ckd = tempfile.mkdtemp(prefix="ooc_ckpt_")
        try:
            t0 = time.perf_counter()
            train_streamed(X, fresh_y(), GBDTParams(**pkw),
                           tile_rows=tile_rows, checkpoint_dir=ckd,
                           checkpoint_every=ck_every, resume="never")
            r_ck.append(n * iters / max(time.perf_counter() - t0, 1e-9))
        finally:
            shutil.rmtree(ckd, ignore_errors=True)
        _log(f"[bench] ooc ckpt rep {r_ck[-1]:.0f}")
    r_ck.sort()
    overhead_pct = 100.0 * (1.0 - r_ck[mid] / max(r_str[mid], 1e-9))
    print(f"OOC_CKPT {r_ck[mid]} {overhead_pct} {ck_every}", flush=True)


def phase_resnet(batch=256, steps=8, hw=224, reps=3) -> None:
    """ResNet-50 featurize throughput (reference CNTKModel's flagship
    inference path): batch 256 (MXU-filling) with the step loop INSIDE the
    jitted program (lax.scan over per-step input perturbations — one
    dispatch per timed rep, steps*batch images).  Each scan step perturbs
    the batch and every rep shifts the offset.  Prints images/sec and model
    FLOPs utilization (4.09 GFLOP/img fwd at 224^2 over the chip's
    published bf16 peak)."""
    peaks = _device_phase_setup()
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import resnet50
    from mmlspark_tpu.ops import image as image_ops

    module = resnet50(num_classes=1000, dtype=jnp.bfloat16)
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 64, 64, 3), jnp.float32))
    x = jax.random.uniform(jax.random.PRNGKey(1), (batch, hw, hw, 3),
                           jnp.float32, 0, 255)

    @jax.jit
    def featurize_many(variables, x, step_offsets):
        def body(acc, s):
            f = module.apply(variables, image_ops.normalize(x + s),
                             features=True)
            return acc + f.astype(jnp.float32).mean(), None
        acc, _ = jax.lax.scan(body, jnp.float32(0.0), step_offsets)
        return acc

    offs = jnp.arange(steps, dtype=jnp.float32)
    t0 = time.perf_counter()
    float(featurize_many(variables, x, offs - 7.0))  # warm, forced fetch
    _log(f"[bench] resnet warm(compile) {time.perf_counter() - t0:.0f}s")
    rates = []
    for r in range(1, reps + 1):
        t0 = time.perf_counter()
        float(featurize_many(variables, x, offs + 0.1 * r))
        rates.append(batch * steps / (time.perf_counter() - t0))
        _log(f"[bench] resnet rep img/s {rates[-1]:.0f}")
    rates.sort()
    ips = rates[len(rates) // 2]
    mfu_pct = 100.0 * ips * 4.09e9 / (peaks["bf16_tflops"] * 1e12)
    print(f"IMAGES_SEC {ips} {mfu_pct}", flush=True)


def phase_ranker(n=200_000, f=50, group=100, iters_a=2, iters_b=8,
                 reps=3) -> None:
    """LambdaRank marginal rows/sec, median of ``reps`` — the lambda pass is
    device-resident (make_lambdarank_grad_fn), so this measures the fused
    iteration rate.  Labels perturb per call."""
    _device_phase_setup()
    import numpy as np
    from mmlspark_tpu.lightgbm import GBDTParams, train
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, f)).astype(np.float32)
    rel0 = (X[:, 0] + 0.3 * rng.normal(size=n) > 0.5).astype(np.float32) \
        + (X[:, 1] > 1.0)
    gp = np.arange(0, n + 1, group)
    p = dict(objective="lambdarank", max_depth=5)
    nonce = [0]

    def fresh_rel():
        nonce[0] += 1
        rel = rel0.copy()
        a = (53 * nonce[0]) % (n - 32)
        rel[a:a + 32] = 2.0 - rel[a:a + 32]
        return rel

    bc = {}
    train(X, fresh_rel(), GBDTParams(num_iterations=iters_a, **p),
          group_ptr=gp, bin_cache=bc)
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        train(X, fresh_rel(), GBDTParams(num_iterations=iters_a, **p),
              group_ptr=gp, bin_cache=bc)
        t_a = time.perf_counter() - t0
        t0 = time.perf_counter()
        train(X, fresh_rel(), GBDTParams(num_iterations=iters_b, **p),
              group_ptr=gp, bin_cache=bc)
        t_b = time.perf_counter() - t0
        rates.append(n * (iters_b - iters_a) / max(t_b - t_a, 1e-9))
    rates.sort()
    print(f"RANKER_RPS {rates[len(rates) // 2]}", flush=True)


def phase_serving(n_requests=1000) -> None:
    """Serving p50 latency over real HTTP: a fitted GBDT pipeline behind the
    continuous-mode server, single-row requests scored via the host-side
    booster walk over ONE persistent HTTP/1.1 connection (the client pattern
    the reference's continuous-mode claim assumes).  Pure host — no device
    involvement (reference claim: ~1 ms, docs/mmlspark-serving.md:10-11)."""
    import http.client
    import json as _json
    import numpy as np
    from mmlspark_tpu.core import DataFrame, Transformer
    from mmlspark_tpu.core.schema import vector_column
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    from mmlspark_tpu.serving import PipelineServer

    rng = np.random.default_rng(0)
    X = rng.normal(size=(2000, 20))
    y = (X[:, 0] > 0).astype(float)
    df = DataFrame.from_dict({"features": vector_column(list(X)), "label": y})
    model = LightGBMClassifier().set_params(num_iterations=30,
                                            min_data_in_leaf=5).fit(df)

    class Scorer(Transformer):
        def _transform(self, frame):
            def per_part(p):
                feats = vector_column([np.asarray(v, np.float32)
                                       for v in p["request"]])
                out = model.transform(DataFrame.from_dict({"features": feats}))
                return {**p, "reply": out.collect()["prediction"]}
            return frame.map_partitions(per_part)

        def transform_schema(self, schema):
            return schema

    srv = PipelineServer(Scorer(), port=0, mode="continuous").start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        body = _json.dumps(list(np.asarray(X[0], float)))
        hdrs = {"Content-Type": "application/json"}
        for _ in range(50):  # warm
            conn.request("POST", srv.api_path, body, hdrs)
            conn.getresponse().read()
        lats = []
        for _ in range(n_requests):
            t0 = time.perf_counter()
            conn.request("POST", srv.api_path, body, hdrs)
            conn.getresponse().read()
            lats.append(time.perf_counter() - t0)
        lats.sort()
        print(f"SERVING_P50_MS {1000 * lats[len(lats) // 2]} "
              f"{1000 * lats[int(len(lats) * 0.95)]}", flush=True)

        # sustained concurrent load: 8 persistent connections back-to-back
        # (the reference's serving claims are about sustained throughput,
        # docs/mmlspark-serving.md:10-11); shared driver with the CI gate
        from mmlspark_tpu.serving import sustained_load
        res = sustained_load("127.0.0.1", srv.port, srv.api_path, body, hdrs)
        print(f"SERVING_LOAD {res['rps']} {res['p99_ms']}", flush=True)
    finally:
        srv.stop()

    # profiler overhead A/B on the ECHO microbench (ISSUE 15): a scorer
    # with no model cost, so the host-stack sampler's overhead has nowhere
    # to hide — the worst case for the <= 3% gate.  Measurement design
    # (validated against a null A/B on this class of host): per-batch
    # MEDIAN latency (throughput over a batch is swamped by contention
    # outliers), batches COUNTERBALANCED base/prof then prof/base (a null
    # pair showed ~5% monotone within-pair drift that a fixed order books
    # as phantom overhead), overhead from the pooled per-arm medians (a
    # per-pair ratio median stays drift-skewed at this pair count).
    class EchoScorer(Transformer):
        def _transform(self, frame):
            def per_part(p):
                return {**p, "reply": np.asarray(
                    [float(np.sum(v)) for v in p["request"]])}
            return frame.map_partitions(per_part)

        def transform_schema(self, schema):
            return schema

    from mmlspark_tpu.observability.profiling import SamplingProfiler
    esrv = PipelineServer(EchoScorer(), port=0, mode="continuous").start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", esrv.port, timeout=10)
        ebody = _json.dumps([1.0, 2.0, 3.0])

        def med_batch(n=60):
            lats = []
            for _ in range(n):
                t0 = time.perf_counter()
                conn.request("POST", esrv.api_path, ebody, hdrs)
                conn.getresponse().read()
                lats.append(time.perf_counter() - t0)
            lats.sort()
            return lats[n // 2]

        def prof_batch():
            sampler = SamplingProfiler()       # default hz — the gate's arm
            sampler.start()
            try:
                return med_batch()
            finally:
                sampler.stop()

        med_batch(100)                         # warm
        bases, profs = [], []
        for i in range(8):
            if i % 2 == 0:
                bases.append(med_batch())
                profs.append(prof_batch())
            else:
                profs.append(prof_batch())
                bases.append(med_batch())
        base_p50_ms = 1000.0 * sorted(bases)[len(bases) // 2]
        prof_p50_ms = 1000.0 * sorted(profs)[len(profs) // 2]
        overhead = 100.0 * (prof_p50_ms / base_p50_ms - 1.0)
        print(f"SERVING_PROFILER {base_p50_ms} {prof_p50_ms} {overhead}",
              flush=True)
    finally:
        esrv.stop()


def phase_cpu(n=200_000, f=200, reps=3) -> None:
    """CPU-executor baseline: identical trainer on the host CPU — run
    STRICTLY ALONE (VERDICT r4 weak #1: on a 1-core host any concurrent
    phase halves the denominator), median of ``reps`` marginal rates, with
    the host fingerprint printed next to the number so the artifact records
    what machine produced the denominator."""
    import json as _json
    import numpy as np
    from mmlspark_tpu.lightgbm import GBDTParams, train

    fp = {"nproc": os.cpu_count()}
    try:
        with open("/proc/cpuinfo") as fcpu:
            for line in fcpu:
                if line.startswith("model name"):
                    fp["cpu_model"] = line.split(":", 1)[1].strip()
                    break
        fp["loadavg_1m"] = round(os.getloadavg()[0], 2)
    except OSError:
        pass
    print(f"CPU_HOST {_json.dumps(fp)}", flush=True)

    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y0 = (X[:, 0] + 0.5 * X[:, 1] + rng.normal(scale=0.3, size=n) > 0).astype(np.float32)
    nonce = [0]

    def fresh_y():  # fresh labels per call, as in the TPU phases
        nonce[0] += 1
        y = y0.copy()
        a = (37 * nonce[0]) % (n - 64)
        y[a:a + 64] = 1.0 - y[a:a + 64]
        return y

    bc = {}   # identical binning memo as the TPU phase (symmetric marginal)
    train(X, fresh_y(), GBDTParams(num_iterations=1, objective="binary", max_depth=5),
          bin_cache=bc)
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        train(X, fresh_y(), GBDTParams(num_iterations=2, objective="binary", max_depth=5),
              bin_cache=bc)
        ta = time.perf_counter() - t0
        t0 = time.perf_counter()
        train(X, fresh_y(), GBDTParams(num_iterations=7, objective="binary", max_depth=5),
              bin_cache=bc)
        tb = time.perf_counter() - t0
        rates.append(n * 5 / max(tb - ta, 1e-9))
        _log(f"[bench] cpu rep rate {rates[-1]:.0f}")
    rates.sort()
    print(f"CPU_RPS {rates[len(rates) // 2]}", flush=True)


# --------------------------------------------------------------------------
# parent orchestration
# --------------------------------------------------------------------------

def _tpu_env() -> dict:
    return dict(os.environ)


def _cpu_env() -> dict:
    """Host cells (CPU baseline, HTTP serving) never touch the chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _spawn(phase: str, env: dict, extra_args=()) -> subprocess.Popen:
    # stderr merges into the captured stdout so the parent's streaming
    # reader can treat ANY child output (rep logs, jax warnings) as a sign
    # of life; every line is echoed to the parent's stderr for live logs
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase,
         *extra_args],
        cwd=_REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)   # binary pipe: parent reads raw fd


def _collect_multi(proc: subprocess.Popen, markers, idle: float,
                   hard: float = 1500.0) -> dict:
    """Stream the child's merged output; return {marker: floats-or-raw}.

    The parent kills only on SILENCE: the ``idle`` window (sized to cover
    the longest observed compile) resets on every output line, so a child
    that is computing, compiling noisily, or printing reps is never killed;
    a child that produces nothing for ``idle`` seconds is hung.  ``hard``
    is the absolute backstop."""
    import selectors
    got = {}

    def parse(line):
        for m in markers:
            if line.startswith(m):
                rest = line[len(m):].strip()
                try:
                    got[m] = [float(v) for v in rest.split()]
                except ValueError:   # non-numeric payload (e.g. JSON)
                    got[m] = rest

    # raw-fd reads with manual line splitting: readline() on a buffered
    # wrapper can block on a partial line (disabling the deadline checks)
    # and slurps lines select() then never reports again
    fd = proc.stdout.fileno()
    os.set_blocking(fd, False)
    sel = selectors.DefaultSelector()
    sel.register(proc.stdout, selectors.EVENT_READ)
    buf = b""
    t_start = last = time.perf_counter()
    while True:
        now = time.perf_counter()
        if now - last > idle or now - t_start > hard:
            proc.kill()
            _log(f"[bench] phase {markers[0]} killed: "
                 f"{'silent ' + str(round(now - last)) + 's' if now - last > idle else 'hard cap'}")
            break
        if not sel.select(timeout=5.0):
            if proc.poll() is not None:
                break
            continue
        try:
            chunk = os.read(fd, 65536)
        except BlockingIOError:
            continue
        if chunk == b"":                     # EOF: child exited
            break
        last = time.perf_counter()
        sys.stderr.write(chunk.decode("utf-8", "replace"))
        sys.stderr.flush()
        buf += chunk
        *lines, buf = buf.split(b"\n")
        for raw in lines:
            parse(raw.decode("utf-8", "replace"))
    try:
        rem = proc.communicate(timeout=10)[0]
        for line in (buf + (rem or b"")).decode("utf-8", "replace").splitlines():
            parse(line)
    except Exception:  # noqa: BLE001
        pass
    return got


def _collect(proc: subprocess.Popen, marker: str, idle: float,
             hard: float = 1500.0, phase: str = ""):
    # the PHASE_METRICS marker rides every phase child (ISSUE 11); folding
    # happens here so single-marker call sites get it for free
    got = _collect_multi(proc, (marker, "PHASE_METRICS"), idle, hard)
    if phase:
        _record_phase_metrics(phase, got)
    val = got.get(marker)
    if val is None:
        _log(f"[bench] phase {marker} ended rc={proc.returncode} without result")
    return val


def _note(phase: str, msg: str) -> None:
    RESULT["extras"].setdefault("phase_notes", {})[phase] = msg


def _record_hist_ab(got: dict) -> bool:
    """Fold a hist_ab child's markers into extras; False when absent."""
    vals = got.get("HIST_AB_RATES")
    if isinstance(vals, str):
        return False
    if not vals or len(vals) < 3:
        return False
    ex = RESULT["extras"]
    ex["hist_ab_f32_rows_per_sec"] = round(vals[0], 1)
    ex["hist_ab_packed_rows_per_sec"] = round(vals[1], 1)
    ex["hist_ab_packed_speedup"] = round(vals[2], 3)
    mode = got.get("HIST_AB_MODE")
    if isinstance(mode, str) and mode.split():
        parts = mode.split()
        ex["hist_ab_mode"] = parts[0]
        if len(parts) >= 3:
            ex["hist_ab_shape"] = f"{parts[1]}x{parts[2]}"
    return True


def _record_ooc(got: dict) -> bool:
    """Fold an ooc child's OOC_AB marker into extras; False when absent."""
    vals = got.get("OOC_AB")
    if isinstance(vals, str) or not vals or len(vals) < 4:
        return False
    ex = RESULT["extras"]
    ex["ooc_inmemory_rows_per_sec"] = round(vals[0], 1)
    ex["ooc_streamed_rows_per_sec"] = round(vals[1], 1)
    ex["ooc_streamed_vs_inmemory"] = round(vals[2], 3)
    ex["ooc_prefetch_overlap_pct"] = round(vals[3], 2)
    if len(vals) >= 5:
        ex["ooc_tiles"] = int(vals[4])
    ck = got.get("OOC_CKPT")
    if not isinstance(ck, str) and ck and len(ck) >= 2:
        # durability-cost arm: streamed-with-checkpoints vs streamed
        ex["ooc_ckpt_streamed_rows_per_sec"] = round(ck[0], 1)
        ex["ckpt_overhead_pct"] = round(ck[1], 2)
        if len(ck) >= 3:
            ex["ooc_ckpt_every"] = int(ck[2])
    else:
        # the A/B landed but the checkpoint arm was cut (killed/timed out):
        # the missing acceptance number must be attributable, not silent
        _note("ooc", "checkpoint arm produced no OOC_CKPT marker; "
                     "ckpt_overhead_pct missing this round")
    return True


def _record_runner(got: dict) -> bool:
    """Fold a runner child's markers into extras; False when absent."""
    ok = False
    ex = RESULT["extras"]
    vals = got.get("RUNNER_AB")
    if vals and not isinstance(vals, str) and len(vals) >= 3:
        ex["runner_ab_legacy_rows_per_sec"] = round(vals[0], 1)
        ex["runner_ab_runner_rows_per_sec"] = round(vals[1], 1)
        ex["runner_vs_legacy"] = round(vals[2], 3)
        if vals[2] < 0.9:
            _note("runner", f"runner/legacy {vals[2]:.3f} below the 0.9x "
                            "overhead gate")
        ok = True
    dec = got.get("RUNNER_DECODE")
    if dec and not isinstance(dec, str) and len(dec) >= 1:
        ex["runner_decode_tokens_per_sec"] = round(dec[0], 1)
        if len(dec) >= 3:
            ex["runner_decode_shape"] = f"b{int(dec[1])}xt{int(dec[2])}"
        ok = True
    pg = got.get("RUNNER_PAGED")
    if pg and not isinstance(pg, str) and len(pg) >= 3:
        # paged-vs-dense decode A/B (ISSUE 12): on-chip gate paged >= 1.2x
        # dense tokens/sec
        ex["decode_dense_tokens_per_sec"] = round(pg[0], 1)
        ex["decode_paged_tokens_per_sec"] = round(pg[1], 1)
        ex["decode_paged_vs_dense"] = round(pg[2], 3)
        if len(pg) >= 5:
            ex["decode_page_occupancy_pct"] = round(pg[3], 2)
            ex["decode_hbm_bytes_per_seq"] = round(pg[4], 1)
        if pg[2] < 1.2:
            _note("runner", f"paged/dense {pg[2]:.3f} below the 1.2x "
                            "on-chip gate")
        ok = True
    ct = got.get("RUNNER_CONT")
    if ct and not isinstance(ct, str) and len(ct) >= 3:
        # continuous-vs-ticked decode A/B (ISSUE 13): on-chip gate
        # continuous >= 1.5x ticked tokens/sec under ragged Poisson
        # arrivals; joins must cause zero step-executable compiles
        ex["decode_ticked_tokens_per_sec"] = round(ct[0], 1)
        ex["decode_cont_tokens_per_sec"] = round(ct[1], 1)
        ex["decode_cont_vs_ticked"] = round(ct[2], 3)
        if len(ct) >= 4:
            ex["decode_cont_parity"] = "ok" if ct[3] >= 1 else "MISMATCH"
            if ct[3] < 1:
                _note("runner", "continuous decode tokens DIVERGED from "
                                "one-shot decode() — parity gate failed")
        if len(ct) >= 5:
            ex["decode_cont_join_step_compiles"] = int(ct[4])
            if ct[4] > 0:
                _note("runner", f"{int(ct[4])} step-executable compile(s) "
                                "during the continuous trace — joins must "
                                "not mint compile keys")
        if ct[2] < 1.5:
            _note("runner", f"continuous/ticked {ct[2]:.3f} below the "
                            "1.5x on-chip gate")
        ok = True
    px = got.get("RUNNER_PREFIX")
    if px and not isinstance(px, str) and len(px) >= 4:
        # prefix-cache cached-vs-cold TTFT A/B (ISSUE 20): on-chip gate
        # cached TTFT p99 >= 1.3x better than cold under template-sharing
        # arrivals, and hits must mint zero new compile keys
        ex["decode_prefix_cold_ttft_p99_ms"] = round(px[0], 3)
        ex["decode_prefix_ttft_p99_ms"] = round(px[1], 3)
        ex["decode_prefix_vs_nocache"] = round(px[2], 3)
        ex["decode_prefix_hit_rate_pct"] = round(px[3], 2)
        if px[3] <= 0:
            _note("runner", "prefix-cache trace recorded a ZERO hit rate "
                            "— template-sharing arrivals must hit")
        if len(px) >= 5:
            ex["decode_prefix_parity"] = "ok" if px[4] >= 1 else "MISMATCH"
            if px[4] < 1:
                _note("runner", "prefix-cached decode tokens DIVERGED "
                                "from cold decode() — exactness gate "
                                "failed")
        if len(px) >= 6:
            ex["decode_prefix_hit_compiles"] = int(px[5])
            if px[5] > 0:
                _note("runner", f"{int(px[5])} executable compile(s) "
                                "during the prefix-cache replay — hits "
                                "must not mint compile keys")
        if px[2] < 1.3:
            _note("runner", f"prefix cached/cold TTFT {px[2]:.3f} below "
                            "the 1.3x on-chip gate")
        ok = True
    gp = got.get("RUNNER_GOODPUT")
    if gp and not isinstance(gp, str) and len(gp) >= 2:
        # goodput & cost attribution (ISSUE 17): useful-token share and
        # device-seconds per 1k generated tokens over the continuous A/B
        # bracket — the bench ground truth the /fleet/capacity per-class
        # cost number is judged against (agreement gate lives in tests)
        ex["decode_goodput_pct"] = round(gp[0], 2)
        ex["decode_device_s_per_1k_tokens"] = round(gp[1], 4)
        ok = True
    return ok


def _record_serving_profiler(got: dict) -> bool:
    """Fold the echo-serving profiler overhead A/B (ISSUE 15) into extras;
    False when the marker is absent.  Gate: the sampler ON at its default
    hz must stay within 3% of baseline — a miss leaves a phase note, so
    the artifact says WHY the number is missing its gate."""
    vals = got.get("SERVING_PROFILER")
    if isinstance(vals, str) or not vals or len(vals) < 3:
        return False
    ex = RESULT["extras"]
    ex["serving_echo_p50_ms"] = round(vals[0], 3)
    ex["serving_echo_profiled_p50_ms"] = round(vals[1], 3)
    ex["profiler_overhead_pct"] = round(vals[2], 2)
    if vals[2] > 3.0:
        _note("serving", f"profiler overhead {vals[2]:.2f}% exceeds the "
                         "3% echo-microbench gate")
    return True


def _record_gbdt_util(got: dict) -> bool:
    """Fold GBDT_UTIL (cost-analysis bytes/iter + HBM-roofline utilization
    %) into extras; False when the child had no cost analysis."""
    vals = got.get("GBDT_UTIL")
    if isinstance(vals, str) or not vals or len(vals) < 2:
        return False
    RESULT["extras"]["gbdt_hbm_bytes_per_iter"] = round(vals[0], 1)
    RESULT["extras"]["gbdt_achievable_util_pct"] = round(vals[1], 2)
    return True


def main() -> int:
    """Run every phase; return the process exit code (non-zero when any
    device phase failed or found no TPU)."""
    wall0 = time.perf_counter()
    failed = []

    # Phase 1 — CPU-executor baseline, FIRST and STRICTLY ALONE (VERDICT r4
    # weak #1: concurrency halves the denominator on a 1-core host).
    got = _collect_multi(_spawn("cpu", _cpu_env()),
                         ("CPU_RPS", "CPU_HOST", "PHASE_METRICS"),
                         idle=350, hard=700)
    _record_phase_metrics("cpu", got)
    cpu_rps = 0.0
    if got.get("CPU_RPS"):
        cpu_rps = got["CPU_RPS"][0]
        RESULT["extras"]["cpu_executor_rows_per_sec"] = round(cpu_rps, 1)
    else:
        _note("cpu", "CPU baseline child died or stalled; no vs_baseline")
    if isinstance(got.get("CPU_HOST"), str):
        try:
            RESULT["extras"]["cpu_host"] = json.loads(got["CPU_HOST"])
        except ValueError:
            pass
    _emit()

    # Phase 2 — headline metric: GBDT rows/sec on the chip (the GBDT_UTIL
    # marker rides along: cost-analysis bytes -> achievable-utilization %).
    got = _collect_multi(_spawn("gbdt", _tpu_env()),
                         ("GBDT_RPS", "GBDT_UTIL", "PHASE_METRICS"),
                         idle=600, hard=1200)
    _record_gbdt_util(got)
    _record_phase_metrics("gbdt", got)
    if got.get("GBDT_RPS"):
        tpu_rps = got["GBDT_RPS"][0]
        RESULT["value"] = round(tpu_rps, 1)
        if cpu_rps:
            RESULT["vs_baseline"] = round(tpu_rps / cpu_rps, 3)
    else:
        failed.append("gbdt")
        _note("gbdt", "failed; no TPU headline number")
    _emit()

    # Phase 2c — out-of-core streamed-vs-in-memory A/B on the chip
    # (overhead bound at a fits-in-HBM shape + prefetch overlap %).
    got = _collect_multi(_spawn("ooc", _tpu_env()),
                         ("OOC_AB", "OOC_CKPT", "PHASE_METRICS"),
                         idle=600, hard=1600)
    _record_phase_metrics("ooc", got)
    if not _record_ooc(got):
        failed.append("ooc")
        _note("ooc", "TPU streamed A/B stalled/failed")
    _emit()

    # Phase 2b — packed-int vs f32 histogram build A/B at the bench shape
    # (quantized-gradient acceptance: packed >= 1.5x the 3-channel f32
    # build; ISSUE 5).
    got = _collect_multi(_spawn("hist_ab", _tpu_env()),
                         ("HIST_AB_RATES", "HIST_AB_MODE", "PHASE_METRICS"),
                         idle=600, hard=1100)
    _record_phase_metrics("hist_ab", got)
    if not _record_hist_ab(got):
        failed.append("hist_ab")
        _note("hist_ab", "TPU A/B stalled/failed")
    _emit()

    # Phase 3 — LambdaRank iteration rate (device-resident lambdas).
    # Compile-aware deadline + one retry: the first attempt may spend its
    # window inside a fresh XLA compile.  A completed compile lands in the
    # persistent cache, so a second attempt is measurement-only.
    got = _collect(_spawn("ranker", _tpu_env()), "RANKER_RPS", idle=480,
                   hard=900, phase="ranker")
    if got is None:
        _note("ranker", "attempt 1 stalled (likely compile); retried")
        got = _collect(_spawn("ranker", _tpu_env()), "RANKER_RPS",
                       idle=700, hard=1000, phase="ranker")
    if got:
        RESULT["extras"]["lambdarank_train_rows_per_sec_200kx50"] = \
            round(got[0], 1)
    else:
        failed.append("ranker")
        _note("ranker", "both attempts failed; no lambdarank number")
    _emit()

    # Phase 4 — ResNet-50 featurize (same retry discipline).
    got = _collect(_spawn("resnet", _tpu_env()), "IMAGES_SEC", idle=420,
                   hard=800, phase="resnet")
    if got is None:
        _note("resnet", "attempt 1 stalled (likely compile); retried")
        got = _collect(_spawn("resnet", _tpu_env()), "IMAGES_SEC",
                       idle=600, hard=900, phase="resnet")
    if got:
        RESULT["extras"]["resnet50_featurize_images_per_sec_per_chip"] = \
            round(got[0], 1)
        if len(got) > 1:
            RESULT["extras"]["resnet50_featurize_mfu_pct"] = round(got[1], 1)
    else:
        failed.append("resnet")
        _note("resnet", "both attempts failed; no featurize number")
    _emit()

    # Phase 4d — unified-runner A/B + KV-cached decode tokens/sec on the
    # chip (ISSUE 9: runner >= 0.9x the legacy glue it replaced, plus the
    # generative-serving number).
    got = _collect_multi(_spawn("runner", _tpu_env()),
                         ("RUNNER_AB", "RUNNER_DECODE", "RUNNER_PAGED",
                          "RUNNER_CONT", "RUNNER_PREFIX",
                          "RUNNER_GOODPUT", "PHASE_METRICS"),
                         idle=600, hard=1100)
    _record_phase_metrics("runner", got)
    if not _record_runner(got):
        failed.append("runner")
        _note("runner", "TPU runner A/B stalled/failed")
    _emit()

    # Phase 5 — serving latency + sustained load: a host cell (CPU
    # platform), labelled as one.
    sproc = _spawn("serving", _cpu_env())
    got = _collect_multi(sproc, ("SERVING_P50_MS", "SERVING_LOAD",
                                 "SERVING_PROFILER", "PHASE_METRICS"),
                         idle=200, hard=400)
    _record_phase_metrics("serving", got)
    if got.get("SERVING_P50_MS"):
        RESULT["extras"]["serving_http_p50_ms"] = round(got["SERVING_P50_MS"][0], 2)
        RESULT["extras"]["serving_http_p95_ms"] = round(got["SERVING_P50_MS"][1], 2)
    if got.get("SERVING_LOAD"):
        RESULT["extras"]["serving_sustained_rps_8conn"] = round(got["SERVING_LOAD"][0], 1)
        RESULT["extras"]["serving_sustained_p99_ms"] = round(got["SERVING_LOAD"][1], 2)
    if not _record_serving_profiler(got):
        _note("serving", "echo profiler A/B produced no SERVING_PROFILER "
                         "marker; profiler_overhead_pct missing this round")
    if failed:
        RESULT["extras"]["failed_device_phases"] = failed
    _emit()
    _log(f"[bench] done in {time.perf_counter() - wall0:.0f}s; "
         f"failed device phases: {failed or 'none'}")
    return 1 if failed else 0


if __name__ == "__main__":
    if "--phase" in sys.argv:
        args = sys.argv[sys.argv.index("--phase") + 1:]
        phase, rest = args[0], args[1:]
        kw = {}
        for i in range(0, len(rest) - 1, 2):
            kw[rest[i].lstrip("-")] = int(rest[i + 1])
        {"gbdt": phase_gbdt, "ranker": phase_ranker,
         "resnet": phase_resnet, "cpu": phase_cpu, "hist_ab": phase_hist_ab,
         "ooc": phase_ooc, "serving": phase_serving,
         "runner": phase_runner}[phase](**kw)
        _emit_phase_metrics()
    else:
        sys.exit(main())
