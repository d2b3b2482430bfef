"""Static telemetry-coverage sweep (tier-1).

Reference: ``FuzzingTest.scala:18`` enforces stage coverage by reflection so
it cannot silently regress.  Same idea for telemetry: every public
``Estimator.fit`` / ``Transformer.transform`` must route through
``core/logging.log_verb`` (which also opens the tracing span), which holds
exactly when no stage overrides the public verb — stages implement
``_fit``/``_transform`` and inherit the instrumented wrappers.  A stage that
shadows the public verb drops out of the event ring, the span trace, AND
the ``mmlspark_span_seconds`` metrics at once, so this sweep is the only
thing standing between a refactor and a silent observability hole.
"""
import ast
import inspect
import pathlib

import mmlspark_tpu
from mmlspark_tpu.codegen import all_stage_classes
from mmlspark_tpu.core.pipeline import Estimator, Transformer

# stages allowed to bypass the instrumented verb wrappers (reference keeps
# the same kind of explicit exemption list); empty means full coverage
LOG_VERB_EXEMPT = set()


def test_base_verbs_are_instrumented():
    """The wrappers themselves must call log_verb — the sweep below is
    meaningless if the base class loses its instrumentation."""
    assert "log_verb" in inspect.getsource(Estimator.fit)
    assert "log_verb" in inspect.getsource(Transformer.transform)


def test_collector_public_surface_is_instrumented():
    """The span collector watches everything else, so the registry must
    watch the collector: its hot path (record) and flush path must book
    the drop/batch/span counters and the flush-latency histogram that
    ``instruments.instrument_collector`` declares, and every declared
    family must actually be registered at construction.  Source-level like
    the stage sweep, so a refactor cannot silently drop the accounting."""
    from mmlspark_tpu.observability import MetricsRegistry, collector

    record_src = inspect.getsource(collector.SpanCollector.record)
    flush_src = inspect.getsource(collector.SpanCollector.flush_now)
    # hot path books ring + export-queue drops; flush path books latency
    # and per-result batch/span outcomes (the _m children bound once by
    # instrument_collector)
    for needle in ('_m["ring_dropped"]', '_m["spans_dropped"]'):
        assert needle in record_src, f"record() lost {needle}"
    for needle in ('_m["flush_seconds"]', 'batches_', 'spans_',
                   '_m["sampled_out"]'):
        assert needle in flush_src, f"flush_now() lost {needle}"

    reg = MetricsRegistry()
    collector.SpanCollector(registry=reg, endpoint="")
    for family in ("mmlspark_span_ring_dropped_total",
                   "mmlspark_otlp_export_spans_total",
                   "mmlspark_otlp_export_batches_total",
                   "mmlspark_otlp_flush_seconds",
                   "mmlspark_otlp_export_queue_depth",
                   "mmlspark_otlp_sampled_out_total"):
        assert reg.family(family) is not None, \
            f"instrument_collector no longer registers {family}"


def test_lightgbm_phase_histogram_carries_backend_and_quant_labels():
    """A/B attribution contract: every lightgbm training phase observation
    — including the packed quantized-histogram path, which is just another
    backend/quantized label pair on the SAME family — must book
    ``mmlspark_lightgbm_phase_seconds`` with (phase, backend, quantized)
    labels.  Source-level like the stage sweep: a refactor that books the
    packed path into a different family (or drops the labels) would make
    packed-vs-f32 runs unattributable on /metrics."""
    from mmlspark_tpu.lightgbm import core as gbdt_core

    src = inspect.getsource(gbdt_core.train)
    assert '"mmlspark_lightgbm_phase_seconds"' in src
    assert 'labels=("phase", "backend", "quantized")' in src, \
        "phase histogram lost its backend/quantized labels"
    assert "backend=hist_backend" in src and "quantized=" in src, \
        "_observe_phase no longer books the resolved backend/quantization"
    # the quantized path must ride the same phase bookkeeping: the fused
    # iteration (histogram build included) books histogram_split_update
    # regardless of backend, so the only way to lose the packed phase is
    # to lose the labels above or the observation below
    assert src.count('_observe_phase("histogram_split_update"') >= 2


#: hot-module directories whose jit entry points must carry compute-plane
#: telemetry (ISSUE 6 contract; ISSUE 9 extended the sweep over the model
#: runner's home dirs — models/, dl/, featurize/ — so every runner jit site
#: is instrumented or pragma'd)
JIT_SWEEP_DIRS = ("lightgbm", "ops", "parallel", "serving", "models", "dl",
                  "featurize")

#: call targets that hand a function to the XLA compiler
_JIT_TARGETS = {"jax.jit", "jax.pmap", "jax.shard_map", "shard_map",
                "jax.experimental.shard_map.shard_map"}


def _dotted(fn) -> str:
    parts = []
    while isinstance(fn, ast.Attribute):
        parts.append(fn.attr)
        fn = fn.value
    if isinstance(fn, ast.Name):
        parts.append(fn.id)
    return ".".join(reversed(parts))


def test_every_jit_call_site_is_instrumented_or_justified():
    """Compute-plane coverage sweep: every ``jax.jit``/``jax.shard_map``
    call site in the hot modules either routes through
    ``observability.compute.instrumented_jit`` (lexically — the raw call
    is an argument of an ``instrumented_jit(...)`` call) or carries a
    ``# raw-jit: <why>`` pragma within two lines above it.  Otherwise a
    refactor could silently reopen the below-jit observability hole this
    PR closed: compiles, recompile storms, and cost analysis all vanish
    for that site."""
    root = pathlib.Path(mmlspark_tpu.__file__).parent
    offenders = []
    for sub in JIT_SWEEP_DIRS:
        for path in sorted((root / sub).rglob("*.py")):
            src = path.read_text()
            lines = src.splitlines()
            tree = ast.parse(src)
            parents = {}
            for node in ast.walk(tree):
                for child in ast.iter_child_nodes(node):
                    parents[child] = node
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call) or \
                        _dotted(node.func) not in _JIT_TARGETS:
                    continue
                cur, routed = parents.get(node), False
                while cur is not None:
                    if isinstance(cur, ast.Call) and \
                            _dotted(cur.func).endswith("instrumented_jit"):
                        routed = True
                        break
                    cur = parents.get(cur)
                if routed:
                    continue
                window = lines[max(0, node.lineno - 3):node.lineno]
                if any("# raw-jit:" in ln for ln in window):
                    continue
                offenders.append(
                    f"{path.relative_to(root)}:{node.lineno} "
                    f"{_dotted(node.func)}")
    assert not offenders, (
        "raw jit/shard_map call sites outside instrumented_jit (route them "
        "through observability.compute.instrumented_jit, or justify with a "
        f"'# raw-jit: <why>' pragma): {offenders}")


#: call targets that hand a kernel body to the Pallas/Mosaic compiler —
#: compile booking cannot wrap these lexically (they run INSIDE already
#: instrumented jit programs), so each site must say where its compile
#: accounting rides via a ``# pallas-site: <why>`` pragma
_PALLAS_TARGETS = {"pl.pallas_call", "pallas.pallas_call", "pallas_call",
                   "jax.experimental.pallas.pallas_call"}


def _unjustified_pallas_sites(root, subdirs):
    offenders = []
    for sub in subdirs:
        for path in sorted((root / sub).rglob("*.py")):
            src = path.read_text()
            lines = src.splitlines()
            tree = ast.parse(src)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call) or \
                        _dotted(node.func) not in _PALLAS_TARGETS:
                    continue
                window = lines[max(0, node.lineno - 3):node.lineno]
                if any("# pallas-site:" in ln for ln in window):
                    continue
                offenders.append(
                    f"{path.relative_to(root)}:{node.lineno}")
    return offenders


def test_every_pallas_site_is_instrumented_or_justified():
    """ISSUE 8 twin of the raw-jit sweep: every ``pl.pallas_call`` site in
    the hot modules carries a ``# pallas-site: <where compile booking
    rides>`` pragma within two lines above it.  A pallas kernel compiles
    inside its caller's jit program, so the compile counters see it only
    through that wrapper — an unpragma'd site is a kernel whose compile
    cost is silently unattributable.  The package ships no kernel now; the
    sweep is the gate for the next one."""
    root = pathlib.Path(mmlspark_tpu.__file__).parent
    offenders = _unjustified_pallas_sites(root, JIT_SWEEP_DIRS)
    assert not offenders, (
        "pallas_call sites without a '# pallas-site: <why>' pragma (state "
        "which instrumented_jit wrapper books their compiles): "
        f"{offenders}")
    # the sweep finds a site when there is one: the lint fixtures' kernels
    # carry no pragma
    fixtures = pathlib.Path(__file__).parent / "analysis_fixtures"
    found = _unjustified_pallas_sites(fixtures, ("ops",))
    assert "ops/pallas_ok.py:23" in found and len(found) == 4, found


def test_trainer_books_compute_phase_breakdown():
    """Source-level contract for the compute.train_step breakdown: the
    trainer must book trace/dispatch phases into the labelled phase
    histogram and gate the device-time sync behind the sampling knob."""
    from mmlspark_tpu.parallel import trainer as trainer_mod

    src = inspect.getsource(trainer_mod.Trainer.train_step)
    assert 'phase="trace"' in src and 'phase="dispatch"' in src \
        and 'phase="device"' in src
    assert "device_time_every" in src and "block_until_ready" in src, \
        "device-time sampling lost its opt-in gate"
    init_src = inspect.getsource(trainer_mod.Trainer.__init__)
    assert '"mmlspark_parallel_train_step_phase_seconds"' in init_src


def test_prefetch_seam_books_overlap_histograms():
    """Out-of-core coverage: the overlap metrics the tile-size tuning loop
    reads (docs/out_of_core.md) must stay wired.  Source-level like the
    stage sweep — TilePrefetcher's consumer loop must observe BOTH
    histograms (a refactor that books only one makes overlap % a lie) —
    plus a live check that construction registers the families, and that
    both streaming drivers actually ride the prefetcher rather than a
    bare loop the metrics never see."""
    from mmlspark_tpu.io import chunked
    from mmlspark_tpu.lightgbm import core as gbdt_core
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.parallel import trainer as trainer_mod

    init_src = inspect.getsource(chunked.TilePrefetcher.__init__)
    assert '"mmlspark_prefetch_wait_seconds"' in init_src
    assert '"mmlspark_tile_compute_seconds"' in init_src
    iter_src = inspect.getsource(chunked.TilePrefetcher.__iter__)
    assert "_h_wait.observe" in iter_src, "consumer loop lost the stall obs"
    assert "_h_tile.observe" in iter_src, "consumer loop lost the compute obs"

    reg = MetricsRegistry()
    chunked.TilePrefetcher(iter(()), lambda t: t, registry=reg)
    for family in ("mmlspark_prefetch_wait_seconds",
                   "mmlspark_tile_compute_seconds"):
        assert reg.family(family) is not None, \
            f"TilePrefetcher no longer registers {family}"

    assert "TilePrefetcher" in inspect.getsource(gbdt_core.train_streamed)
    assert "TilePrefetcher" in inspect.getsource(
        trainer_mod.Trainer.train_stream)


def test_checkpoint_surface_books_metrics():
    """ISSUE 10 coverage: the fault-tolerance layer's save/resume/retry
    sites must book their metric families — a checkpointing run whose
    last-success age silently stops updating is an unpageable outage.
    Source-level like the stage sweep (the writer must book save latency/
    bytes/outcomes, failed saves must book ``result="error"``, resume
    outcomes must ride ``book_resume``, the prefetch retry loop must tick
    its counter), plus a live check that construction registers every
    family, and that all three training drivers actually ride the
    instrumented managers."""
    import tempfile

    from mmlspark_tpu.io import checkpoint as ckpt_mod
    from mmlspark_tpu.io import chunked
    from mmlspark_tpu.lightgbm import core as gbdt_core
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.parallel import trainer as trainer_mod

    write_src = inspect.getsource(ckpt_mod.CheckpointManager._write_one)
    for needle in ('_m["save_seconds"]', '_m["bytes"]', '_m["saves"]'):
        assert needle in write_src, f"_write_one lost {needle}"
    writer_src = inspect.getsource(ckpt_mod.CheckpointManager._writer)
    assert 'result="error"' in writer_src, "failed saves no longer booked"
    assert "book_resume" in inspect.getsource(
        ckpt_mod.CheckpointManager.load_latest), \
        "resume outcomes no longer booked"

    retry_src = inspect.getsource(chunked.TilePrefetcher._load_with_retry)
    assert "_c_retry.inc" in retry_src, "retry loop lost its counter"
    init_src = inspect.getsource(chunked.TilePrefetcher.__init__)
    assert '"mmlspark_prefetch_retries_total"' in init_src

    reg = MetricsRegistry()
    with tempfile.TemporaryDirectory() as d:
        m = ckpt_mod.CheckpointManager(d, site="sweep", registry=reg)
        m.close()
    for family in ("mmlspark_checkpoint_save_seconds",
                   "mmlspark_checkpoint_bytes",
                   "mmlspark_checkpoint_saves_total",
                   "mmlspark_checkpoint_resumes_total",
                   "mmlspark_checkpoint_last_success_age_seconds"):
        assert reg.family(family) is not None, \
            f"CheckpointManager no longer registers {family}"

    # all three long-running training drivers ride the instrumented layer
    assert "CheckpointManager" in inspect.getsource(gbdt_core.train)
    assert "CheckpointManager" in inspect.getsource(gbdt_core.train_streamed)
    assert "TrainLoopCheckpointer" in inspect.getsource(
        trainer_mod.Trainer.train_stream)


def test_runner_books_front_and_decode_metrics():
    """ISSUE 9 coverage: the ModelRunner is the one copy of the pad/bucket/
    dispatch glue, so its metric seam is the only place batch-vs-serving-vs-
    decode attribution can come from.  Source-level: apply_batch must book
    rows/batches/padding, decode must book steps/tokens, and every
    executable must be built through the instrumented path (a raw jax.jit
    in the runner would silently drop compile accounting for every model it
    serves).  Live: construction registers all seven families."""
    import inspect as _inspect

    from mmlspark_tpu.models import runner as runner_mod
    from mmlspark_tpu.observability import MetricsRegistry

    apply_src = _inspect.getsource(runner_mod.ModelRunner.apply_batch)
    for needle in ("_c_batches[front]", "_c_rows[front]", "_c_pad",
                   "_c_input_bytes[front]"):
        assert needle in apply_src, f"apply_batch lost {needle}"
    decode_src = _inspect.getsource(runner_mod.ModelRunner.decode)
    for needle in ("_c_decode_steps", "_c_decode_tokens"):
        assert needle in decode_src, f"decode lost {needle}"
    for fn in (runner_mod.ModelRunner.executable,
               runner_mod.ModelRunner._decode_executables):
        assert "_instrumented" in _inspect.getsource(fn), \
            f"{fn.__name__} no longer lowers through instrumented_jit"

    reg = MetricsRegistry()
    runner_mod.ModelRunner(apply_fn=lambda v, x: x, variables={},
                           name="sweep", registry=reg)
    for family in ("mmlspark_runner_batches_total",
                   "mmlspark_runner_rows_total",
                   "mmlspark_runner_input_bytes_total",
                   "mmlspark_runner_staged_chunks_total",
                   "mmlspark_runner_pad_rows_total",
                   "mmlspark_runner_decode_steps_total",
                   "mmlspark_runner_decode_tokens_total"):
        assert reg.family(family) is not None, \
            f"ModelRunner no longer registers {family}"


def test_page_pool_surface_books_metrics():
    """ISSUE 12 coverage: the page pool is the decode memory substrate —
    fleet HBM occupancy and the continuous-batching admission decision
    both read its gauges, so the accounting must be un-droppable.
    Source-level (like the stage sweep): allocate/extend/free must book
    through ``_book`` (extend attributably, as its own op), the decode
    loop must actually ride the pool's three verbs, and every decode
    executable family must declare donated buffers — the static half of
    the donation-safety regression (the behavioural half lives in
    tests/test_paged_decode.py).  Live: runner construction registers the
    pool families even for runners that never decode."""
    from mmlspark_tpu.models import runner as runner_mod
    from mmlspark_tpu.observability import MetricsRegistry

    alloc_src = inspect.getsource(runner_mod.PagePool.allocate)
    assert "_book(op" in alloc_src, "allocate() lost its booking"
    extend_src = inspect.getsource(runner_mod.PagePool.extend)
    assert '"extend"' in extend_src, "extend() no longer books its own op"
    free_src = inspect.getsource(runner_mod.PagePool.free)
    assert '_book("free"' in free_src, "free() lost its booking"
    decode_src = inspect.getsource(runner_mod.ModelRunner.decode)
    # allocate/extend route through the reclaim seam since ISSUE 20 (same
    # pool verbs underneath — _alloc_with_reclaim ends in pool.allocate,
    # and the extend op keeps its own booking)
    for needle in ("_alloc_with_reclaim", 'op="extend"', "pool.free"):
        assert needle in decode_src, f"decode() lost {needle}"
    reclaim_src = inspect.getsource(runner_mod.ModelRunner._alloc_with_reclaim)
    assert "pool.allocate" in reclaim_src and "evict_pages" in reclaim_src
    # donation contract: the prefill and both step variants declare
    # donate_argnums (a refactor that drops one silently reverts to
    # per-token full-cache allocation on TPU)
    exe_src = inspect.getsource(runner_mod.ModelRunner._decode_executables)
    assert exe_src.count("donate_argnums") >= 3, \
        "decode executables lost donate_argnums declarations"
    sample_src = inspect.getsource(runner_mod.ModelRunner._sample_executable)
    assert "donate_argnums" in sample_src

    reg = MetricsRegistry()
    runner_mod.ModelRunner(apply_fn=lambda v, x: x, variables={},
                           name="sweep12", registry=reg)
    for family in ("mmlspark_runner_page_ops_total",
                   "mmlspark_runner_page_pool_used_pages",
                   "mmlspark_runner_page_pool_high_water_pages"):
        assert reg.family(family) is not None, \
            f"ModelRunner no longer registers {family}"


def test_continuous_engine_surface_books_metrics():
    """ISSUE 13 coverage: the continuous engine's join/leave/shed sites
    are what fleet dashboards read for slot occupancy, TTFT and admission
    pressure — the accounting must be un-droppable.  Source-level (like
    the page-pool sweep): the join must book the joined counter + TTFT
    histogram, the leave must book the per-outcome left counter + the
    occupancy gauge, pool exhaustion must book ``op="denied"`` before
    raising, and the serving seam must map shed-typed failures (the
    ``.shed`` duck-type) to the 503 path.  Live: runner construction
    registers all four families (the scorer shares the runner's
    registry), and ``page_ops_total`` accepts the denied op."""
    from mmlspark_tpu.models import runner as runner_mod
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.serving import server as server_mod

    join_src = inspect.getsource(runner_mod.ContinuousDecoder._join)
    assert "_c_joined" in join_src, "_join() lost the joined counter"
    assert "_h_ttft" in join_src, "_join() lost the TTFT observation"
    leave_src = inspect.getsource(runner_mod.ContinuousDecoder._release)
    assert "_c_left[outcome]" in leave_src, "_release() lost the counter"
    assert "_book_occupancy" in leave_src, "_release() lost the gauge"
    submit_src = inspect.getsource(runner_mod.ContinuousDecoder.submit)
    assert "_book_occupancy" in submit_src, "submit() lost the gauge"
    alloc_src = inspect.getsource(runner_mod.PagePool.allocate)
    assert '_book("denied"' in alloc_src, \
        "pool exhaustion no longer books op='denied'"
    assert "denied" in runner_mod.PagePool.OPS
    # the serving seam sheds on the duck-typed admission failures instead
    # of surfacing them as 500s (both the deferred and the batch path)
    seam_src = inspect.getsource(server_mod.PipelineServer._submit_continuous)
    assert 'getattr(ex, "shed", False)' in seam_src
    score_src = inspect.getsource(server_mod.PipelineServer._score_batch)
    assert 'getattr(r, "shed_reason", None)' in score_src
    assert 'getattr(ex, "shed", False)' in score_src

    reg = MetricsRegistry()
    runner_mod.ModelRunner(apply_fn=lambda v, x: x, variables={},
                           name="sweep13", registry=reg)
    for family in ("mmlspark_runner_slots_joined_total",
                   "mmlspark_runner_slots_left_total",
                   "mmlspark_runner_slot_occupancy_pct",
                   "mmlspark_runner_ttft_seconds"):
        assert reg.family(family) is not None, \
            f"ModelRunner no longer registers {family}"


def test_federation_surface_is_instrumented():
    """ISSUE 11 coverage: the fleet telemetry plane watches the workers,
    so the registry must watch the fleet plane.  Source-level (like the
    collector sweep): the scrape path must book per-worker outcomes, sweep
    latency, and the bucket-mismatch counter; the SLO evaluator must book
    burn/budget gauges and the ``slo_burn`` ring transition; the autoscale
    recompute must book the desired-replica gauge and the per-direction
    counter.  Live: constructing a TopologyService registers every fleet
    family — federator + SLO + autoscale instruments."""
    from mmlspark_tpu.observability import (MetricsRegistry, autoscale,
                                            federation, slo)
    from mmlspark_tpu.serving import TopologyService

    scrape_src = inspect.getsource(federation.MetricsFederator.scrape_once)
    for needle in ('_m["scrapes"]', '_m["scrape_seconds"]',
                   '_m["bucket_mismatch"]'):
        assert needle in scrape_src, f"scrape_once() lost {needle}"
    eval_src = inspect.getsource(slo.SLOEngine.evaluate)
    for needle in ('_m["burn_rate"]', '_m["budget_remaining"]',
                   '"slo_burn"', "log_event"):
        assert needle in eval_src, f"SLOEngine.evaluate() lost {needle}"
    rec_src = inspect.getsource(autoscale.AutoscaleAdvisor.recommend)
    for needle in ('_m["desired"]', '_m["recommendations"]'):
        assert needle in rec_src, f"AutoscaleAdvisor.recommend() lost {needle}"

    reg = MetricsRegistry()
    TopologyService(registry=reg, probe_interval_s=None)  # never started
    for family in ("mmlspark_federation_scrape_total",
                   "mmlspark_federation_scrape_seconds",
                   "mmlspark_federation_stale_workers",
                   "mmlspark_federation_bucket_mismatch_total",
                   "mmlspark_slo_burn_rate",
                   "mmlspark_slo_budget_remaining",
                   "mmlspark_autoscale_desired_replicas",
                   "mmlspark_autoscale_recommendations_total"):
        assert reg.family(family) is not None, \
            f"TopologyService no longer registers {family}"


def test_elastic_surface_books_metrics():
    """ISSUE 14 coverage: elastic resume's reshard and membership sites
    are what tells an operator a fleet changed shape under a training
    run — the accounting must be un-droppable.  Source-level (like the
    checkpoint sweep): all three drivers must book their topology delta
    through ``book_reshard``, ``book_reshard`` itself must tick the
    counter + ring event, the membership mutation sites must route
    through ``_book_membership`` (gauge + per-kind counter + ring event),
    and the growers' sharded quantization must key noise per global row
    (the width-independence elastic bit-identity rides on).  Live:
    CheckpointManager construction registers the reshard family and
    TopologyService construction registers both membership families."""
    import tempfile

    from mmlspark_tpu.io import checkpoint as ckpt_mod
    from mmlspark_tpu.lightgbm import core as gbdt_core
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.parallel import checkpoint as pckpt_mod
    from mmlspark_tpu.serving import TopologyService
    from mmlspark_tpu.serving import distributed as dist_mod

    assert "book_reshard" in inspect.getsource(gbdt_core.train)
    assert "book_reshard" in inspect.getsource(gbdt_core.train_streamed)
    assert "book_reshard" in inspect.getsource(
        pckpt_mod.TrainLoopCheckpointer.load_latest)
    book_src = inspect.getsource(ckpt_mod.book_reshard)
    assert '"reshard"' in book_src and "log_event" in book_src

    for handler_src in (inspect.getsource(TopologyService._make_handler),
                        inspect.getsource(TopologyService.probe_once)):
        assert "_book_membership" in handler_src, \
            "a membership mutation site lost its booking"
    bm_src = inspect.getsource(TopologyService._book_membership)
    for needle in ("_m_membership.set", "_m_membership_changes.inc",
                   "log_event"):
        assert needle in bm_src, f"_book_membership lost {needle}"
    # width-independent rounding: both sharded growers pass global row
    # ids into the quantizer (dropping one silently breaks the elastic
    # bit-identity contract in a way only a cross-width run would catch)
    for fn in (gbdt_core.make_tree_grower, gbdt_core.make_leafwise_grower):
        assert "row_ids=row_ids" in inspect.getsource(fn), \
            f"{fn.__name__} no longer keys rounding noise per global row"
    assert "row_ids=ids_t" in inspect.getsource(gbdt_core.train_streamed)

    reg = MetricsRegistry()
    with tempfile.TemporaryDirectory() as d:
        ckpt_mod.CheckpointManager(d, site="sweep14", registry=reg).close()
    assert reg.family("mmlspark_reshard_total") is not None, \
        "CheckpointManager no longer registers the reshard family"
    reg2 = MetricsRegistry()
    TopologyService(registry=reg2, probe_interval_s=None)  # never started
    for family in ("mmlspark_fleet_membership_epoch",
                   "mmlspark_fleet_membership_changes_total"):
        assert reg2.family(family) is not None, \
            f"TopologyService no longer registers {family}"
    assert dist_mod.MembershipWatcher is not None


def test_profiler_recorder_surface_books_metrics():
    """ISSUE 15 coverage: the profiling/postmortem plane observes the
    process at its worst moments, so its own accounting must be
    un-droppable.  Source-level: the sampler's start/stop/drop sites, the
    recorder's dump (every result), every dump TRIGGER (crash hooks,
    preemption hook, SLO burn edge, both HTTP endpoints, the fleet
    fan-out), and the preemption sites that fire the hooks.  Live:
    PipelineServer construction registers every profiler + recorder
    family (and the recorder itself), TopologyService construction
    registers the recorder families on the driver's registry."""
    from mmlspark_tpu.observability import flightrecorder, profiling, slo
    from mmlspark_tpu.observability.metrics import MetricsRegistry
    from mmlspark_tpu.serving import PipelineServer, TopologyService
    from mmlspark_tpu.serving import distributed as dist_mod
    from mmlspark_tpu.utils import resilience

    # sampler lifecycle books runs + per-span samples + bounded drops
    assert '_m["runs"]' in inspect.getsource(
        profiling.SamplingProfiler.start)
    stop_src = inspect.getsource(profiling.SamplingProfiler.stop)
    assert '_m["runs"]' in stop_src and '_m["samples"]' in stop_src
    assert '_m["dropped"]' in inspect.getsource(
        profiling.SamplingProfiler.sample_once)
    window_src = inspect.getsource(profiling.profile_window)
    assert 'result="busy"' in window_src and 'result="error"' in window_src

    # every dump outcome books; every trigger routes through dump()
    dump_src = inspect.getsource(flightrecorder.FlightRecorder.dump)
    for needle in ('_m["dumps"]', 'result="no_dir"', 'result="ok"',
                   'result="error"'):
        assert needle in dump_src, f"FlightRecorder.dump() lost {needle}"
    assert "set_function" in inspect.getsource(
        flightrecorder.FlightRecorder.__init__), \
        "recorder lost the last-dump-age callback gauge"
    for hook, trig in ((flightrecorder.FlightRecorder._sys_hook, "crash"),
                       (flightrecorder.FlightRecorder._threading_hook,
                        "crash"),
                       (flightrecorder.FlightRecorder._on_preemption,
                        "preemption")):
        assert f'trigger="{trig}"' in inspect.getsource(hook), \
            f"{hook.__name__} no longer dumps with trigger={trig}"
    assert 'trigger="slo_burn"' in inspect.getsource(slo.SLOEngine.evaluate)
    # both preemption paths fire the observer hooks the recorder rides
    assert "_fire_preemption_hooks" in inspect.getsource(
        resilience.request_preemption)
    assert "_fire_preemption_hooks" in inspect.getsource(
        resilience.preemption_scope)

    # both new endpoints serve through the booking call sites
    handler_src = inspect.getsource(PipelineServer._make_handler)
    assert "/debug/profile" in handler_src and \
        "profile_window" in handler_src
    assert "/debug/dump" in handler_src and \
        'trigger="http"' in handler_src
    fleet_src = inspect.getsource(TopologyService.fleet_dump)
    assert 'trigger="fleet"' in fleet_src and "dumps_c.inc" in fleet_src

    # live: server construction registers the families + the recorder
    reg = MetricsRegistry()
    srv = PipelineServer(lambda df: df, registry=reg)  # never started
    try:
        for family in ("mmlspark_profiler_runs_total",
                       "mmlspark_profiler_samples_total",
                       "mmlspark_profiler_stacks_dropped_total",
                       "mmlspark_flightrecorder_dumps_total",
                       "mmlspark_flightrecorder_last_dump_age_seconds"):
            assert reg.family(family) is not None, \
                f"PipelineServer no longer registers {family}"
        assert getattr(reg, "_flight_recorder", None) is not None, \
            "PipelineServer no longer creates the per-registry recorder"
    finally:
        reg._flight_recorder.close()   # uninstall the process crash hooks
    reg2 = MetricsRegistry()
    TopologyService(registry=reg2, probe_interval_s=None)  # never started
    try:
        for family in ("mmlspark_flightrecorder_dumps_total",
                       "mmlspark_flightrecorder_last_dump_age_seconds"):
            assert reg2.family(family) is not None, \
                f"TopologyService no longer registers {family}"
    finally:
        reg2._flight_recorder.close()
    assert dist_mod.TOPOLOGY_ENDPOINTS["GET"].count("/fleet/dump") == 1


def test_tail_tolerance_surface_books_metrics():
    """ISSUE 16 coverage: the tail-tolerance plane acts exactly when the
    fleet is at its worst — a hung dispatch, a draining worker, a full
    outage — so its accounting must be un-droppable.  Source-level: the
    stall watchdog books the stall counter and fires the stall-triggered
    postmortem dump; the continuous resolve path sheds
    ``shed_engine_stall``; the supervised rebuild books the restart
    counter; the server's drain observes its duration histogram and sheds
    ``draining`` with a connection teardown; the worker publishes the
    draining membership state; the routing client books shed cooldowns,
    hedge outcomes and budget grants/denials.  Live: PipelineServer
    construction registers the drain histogram (and ModelRunner the
    stall/restart families), RoutingClient construction registers the
    hedge + budget families — the series exist before the first incident,
    so dashboards and alerts can be built against a healthy fleet."""
    from mmlspark_tpu.observability.metrics import MetricsRegistry
    from mmlspark_tpu.serving import PipelineServer, RoutingClient
    from mmlspark_tpu.serving import distributed as dist_mod
    from mmlspark_tpu.serving import server as server_mod
    from mmlspark_tpu.utils import resilience

    # runner side (source-only: importing the models package costs a jax
    # import, which this sweep already pays elsewhere)
    from mmlspark_tpu.models import runner as runner_mod
    wd_src = inspect.getsource(runner_mod.ModelRunner.stall_watchdog)
    assert "_c_stalls" in wd_src, "stall trip lost its counter"
    assert 'trigger="stall"' in wd_src, \
        "stall trip lost the flight-recorder postmortem dump"
    assert "mmlspark_runner_stalls_total" in inspect.getsource(
        runner_mod.ModelRunner.__init__), \
        "stall family no longer registered at runner construction"
    submit_src = inspect.getsource(
        runner_mod._RunnerScorer._continuous_submit)
    assert 'verdict="shed_engine_stall"' in submit_src, \
        "a stall-killed request must shed 503, not error 500"
    ensure_src = inspect.getsource(runner_mod._RunnerScorer._ensure_decoder)
    assert "_c_restarts.inc" in ensure_src and \
        "note_failure" in ensure_src, \
        "supervised rebuild lost its restart booking"
    assert "serving_healthy = False" in ensure_src, \
        "quarantine no longer flips the health signal probes evict on"

    # server side: drain books its histogram; draining sheds tear the
    # connection down; /health reads both drain + engine health signals
    drain_src = inspect.getsource(server_mod.PipelineServer.drain)
    assert "_h_drain.observe" in drain_src
    handler_src = inspect.getsource(server_mod.PipelineServer._make_handler)
    assert "/admin/drain" in handler_src
    assert 'shed_reason == "draining"' in handler_src and \
        "close_connection" in handler_src
    assert "serving_healthy" in handler_src, \
        "/health no longer reads the engine-quarantine signal"
    assert 'state="draining"' in inspect.getsource(
        dist_mod.WorkerServer.drain), \
        "worker drain no longer publishes the draining membership state"

    # routing client: shed cooldown, hedge outcomes, budget counters
    attempt_src = inspect.getsource(dist_mod.RoutingClient._attempt)
    assert 'result="shed"' in attempt_src and \
        "_shed_retry_after" in attempt_src
    hedge_src = inspect.getsource(dist_mod.RoutingClient._hedged_exchange)
    for outcome in ("hedge_won", "primary_won", "both_failed",
                    "budget_denied", "no_candidate"):
        assert f'"{outcome}"' in hedge_src, \
            f"hedge accounting lost outcome={outcome}"
    request_src = inspect.getsource(dist_mod.RoutingClient.request)
    assert "deposit()" in request_src and "try_withdraw()" in request_src
    # the budget's own ledger backs the metrics
    assert "granted" in inspect.getsource(
        resilience.RetryBudget.try_withdraw)

    # live: construction registers every family up front
    reg = MetricsRegistry()
    srv = PipelineServer(lambda df: df, registry=reg)  # never started
    try:
        assert reg.family("mmlspark_serving_drain_seconds") is not None, \
            "PipelineServer no longer registers the drain histogram"
    finally:
        reg._flight_recorder.close()   # uninstall the process crash hooks
    reg2 = MetricsRegistry()
    RoutingClient("http://127.0.0.1:1", registry=reg2)  # never used
    for family in ("mmlspark_hedges_total",
                   "mmlspark_retry_budget_granted_total",
                   "mmlspark_retry_budget_denied_total"):
        assert reg2.family(family) is not None, \
            f"RoutingClient no longer registers {family}"


def test_every_metric_family_has_a_docs_row():
    """ISSUE 17 docs-coverage gate: every ``mmlspark_*`` family registered
    anywhere in source (a literal first argument to a registry
    ``counter``/``gauge``/``histogram`` call) must have a table row in
    docs/OBSERVABILITY.md — this drift was hand-patched in every PR since
    PR 2, so it is now machine-enforced like the stage sweep.  A row means
    the backticked family name appears on a markdown table line; prose
    mentions do not count (an operator greps the table)."""
    root = pathlib.Path(mmlspark_tpu.__file__).parent
    families = {}
    for path in sorted(root.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("counter", "gauge", "histogram") \
                    and node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str) \
                    and node.args[0].value.startswith("mmlspark_"):
                families.setdefault(node.args[0].value, []).append(
                    f"{path.relative_to(root)}:{node.lineno}")
    assert len(families) >= 80, \
        f"only {len(families)} families found — the sweep itself broke"
    doc = (root.parent / "docs" / "OBSERVABILITY.md").read_text()
    table = "\n".join(ln for ln in doc.splitlines()
                      if ln.lstrip().startswith("|"))
    undocumented = {f: sites for f, sites in sorted(families.items())
                    if f"`{f}`" not in table}
    assert not undocumented, (
        "metric families registered in source without a docs/"
        f"OBSERVABILITY.md table row: {undocumented}")


def test_attribution_surface_books_metrics():
    """ISSUE 17 coverage: the goodput/cost plane is the denominator every
    later decode optimisation is judged on, so its accounting must be
    un-droppable.  Source-level (like the continuous-engine sweep): the
    continuous step must amortize device time over live slots and book pad
    cells, terminal releases must classify tokens through the outcome map,
    the one-shot decode must book its ledger, the page pool must integrate
    page-seconds at its edges, the server must emit the wide-event record
    on both reply paths, and the hedge race must book the losing leg.
    Live: runner construction registers the ledger families; server
    construction registers the class-cost children."""
    from mmlspark_tpu.models import runner as runner_mod
    from mmlspark_tpu.observability import attribution
    from mmlspark_tpu.observability.metrics import MetricsRegistry
    from mmlspark_tpu.serving import PipelineServer
    from mmlspark_tpu.serving import distributed as dist_mod
    from mmlspark_tpu.serving import server as server_mod

    adv_src = inspect.getsource(runner_mod.ContinuousDecoder._retire)
    for needle in ("_c_device_s.inc", 'outcome="pad_row"',
                   "cost.device_s += share"):
        assert needle in adv_src, f"_retire() lost {needle}"
    rel_src = inspect.getsource(runner_mod.ContinuousDecoder._release)
    assert "_outcome_map[outcome]" in rel_src, \
        "_release() no longer classifies terminal tokens"
    dec_src = inspect.getsource(runner_mod.ModelRunner.decode)
    for needle in ('outcome="useful"', 'outcome="pad_row"',
                   'outcome="denied_row"'):
        assert needle in dec_src, f"one-shot decode() lost {needle}"
    pool_src = inspect.getsource(runner_mod.PagePool)
    assert "_integrate_locked" in pool_src, \
        "PagePool lost its page-seconds integral"
    for fn in (server_mod.PipelineServer._score_batch,
               server_mod.PipelineServer._submit_continuous):
        assert "_emit_record" in inspect.getsource(fn), \
            f"{fn.__name__} no longer emits the wide-event record"
    emit_src = inspect.getsource(server_mod.PipelineServer._emit_record)
    assert "_c_class_tokens" in emit_src and "_c_class_device" in emit_src
    hedge_src = inspect.getsource(dist_mod.RoutingClient._hedged_exchange)
    assert "_book_hedge_loser" in hedge_src, \
        "the losing hedge leg's tokens are no longer booked"
    assert 'outcome="hedge_loser"' in inspect.getsource(
        dist_mod.RoutingClient._book_hedge_loser)
    for outcome in attribution.ENGINE_OUTCOME_MAP.values():
        assert outcome in attribution.OUTCOMES

    reg = MetricsRegistry()
    runner_mod.ModelRunner(apply_fn=lambda v, x: x, variables={},
                           name="sweep17", registry=reg)
    for family in ("mmlspark_decode_tokens_outcome_total",
                   "mmlspark_decode_device_seconds_total",
                   "mmlspark_runner_page_seconds_total"):
        assert reg.family(family) is not None, \
            f"ModelRunner no longer registers {family}"
    reg2 = MetricsRegistry()
    srv = PipelineServer(lambda df: df, registry=reg2)  # never started
    try:
        for family in ("mmlspark_request_class_decode_tokens_total",
                       "mmlspark_request_class_device_seconds_total"):
            assert reg2.family(family) is not None, \
                f"PipelineServer no longer registers {family}"
        assert srv._records is not None
    finally:
        reg2._flight_recorder.close()


def test_topology_endpoint_sweep():
    """Every HTTP endpoint the TopologyService handler serves must appear
    in the declared ``TOPOLOGY_ENDPOINTS`` table (and vice versa): a new
    endpoint cannot land unlisted — the table is what the docs, the
    query-validation tests, and this sweep all key off.  Live half: every
    declared parameterless GET answers non-404 on a real socket."""
    import json
    import urllib.error
    import urllib.request

    from mmlspark_tpu.serving import TopologyService
    from mmlspark_tpu.serving.distributed import TOPOLOGY_ENDPOINTS

    svc = TopologyService(probe_interval_s=None)
    handler_src = inspect.getsource(svc._make_handler)
    # literal paths compared/prefixed in the handler, normalized: the
    # prefix-matched "/flag/" and "/fleet/trace/" reads are declared as
    # "/flag/<key>" / "/fleet/trace/<id>"
    import re
    literals = set(re.findall(r'"(/[a-z/]+)"', handler_src))
    normalized = {{"/flag/": "/flag/<key>",
                   "/fleet/trace/": "/fleet/trace/<id>"}.get(p, p)
                  for p in literals}
    declared = {p for paths in TOPOLOGY_ENDPOINTS.values() for p in paths}
    assert normalized == declared, (
        f"handler endpoints {sorted(normalized)} drifted from the declared "
        f"table {sorted(declared)} — update TOPOLOGY_ENDPOINTS (and docs/"
        "serving.md) with the change")

    svc.start()
    try:
        for path in TOPOLOGY_ENDPOINTS["GET"]:
            url = f"{svc.address}" \
                  f"{path.replace('<key>', 'sweep').replace('<id>', 'sweep')}"
            try:
                with urllib.request.urlopen(url, timeout=10) as r:
                    status = r.status
            except urllib.error.HTTPError as e:
                status = e.code
            # the trace lookup is the one declared GET whose healthy
            # empty-fleet answer is 404 ("no worker holds the id")
            want = 404 if path == "/fleet/trace/<id>" else 200
            assert status == want, f"{path} -> {status} (want {want})"
    finally:
        svc.stop()


def test_every_stage_routes_verbs_through_log_verb():
    classes = all_stage_classes()
    assert len(classes) >= 80, f"only {len(classes)} stages discovered"
    offenders = []
    for cls in classes:
        if cls.__qualname__ in LOG_VERB_EXEMPT:
            continue
        if issubclass(cls, Estimator) and \
                inspect.getattr_static(cls, "fit") is not \
                inspect.getattr_static(Estimator, "fit"):
            offenders.append(f"{cls.__qualname__}.fit")
        if issubclass(cls, Transformer) and \
                inspect.getattr_static(cls, "transform") is not \
                inspect.getattr_static(Transformer, "transform"):
            offenders.append(f"{cls.__qualname__}.transform")
    assert not offenders, (
        "stages overriding the instrumented public verb (implement _fit/"
        f"_transform instead, or add to LOG_VERB_EXEMPT with a reason): "
        f"{offenders}")


def test_trainwatch_surface_books_metrics():
    """ISSUE 19 coverage: the training plane is the only live view into a
    multi-hour job, so its accounting must be un-droppable.  Source-level:
    all three drivers expose ``monitor_port`` and route through
    ``start_training_monitor``; the tick path books steps/rows/step-time;
    the stall path books the stalls counter and dumps with
    ``trigger="train_stall"``; the monitor serves the four read endpoints.
    Live: constructing a run on a fresh registry registers every
    training-plane family."""
    from mmlspark_tpu.lightgbm import core as gbdt_core
    from mmlspark_tpu.observability import trainwatch
    from mmlspark_tpu.observability.metrics import MetricsRegistry
    from mmlspark_tpu.parallel import trainer as trainer_mod
    from mmlspark_tpu.utils.resilience import FakeClock

    # all three drivers carry the seam and wire it through one helper
    for fn in (gbdt_core.train, gbdt_core.train_streamed,
               trainer_mod.Trainer.train_stream):
        src = inspect.getsource(fn)
        assert "monitor_port" in src, f"{fn.__qualname__} lost monitor_port"
        assert "start_training_monitor" in src, \
            f"{fn.__qualname__} no longer wires the training monitor"
        assert "callbacks" in src, f"{fn.__qualname__} lost the callbacks seam"

    tick_src = inspect.getsource(trainwatch.TrainingRun.tick)
    for needle in ("_c_steps", "_c_rows", "_h_step", "arm("):
        assert needle in tick_src, f"TrainingRun.tick() lost {needle}"
    stall_src = inspect.getsource(trainwatch.TrainingRun._on_stall)
    assert "_c_stalls" in stall_src and 'trigger="train_stall"' in stall_src
    handler_src = inspect.getsource(trainwatch.MonitorServer._make_handler)
    for endpoint in ("/progress", "/metrics", "/debug/dump",
                     "/debug/profile", "/stats", "/health"):
        assert endpoint in handler_src, f"MonitorServer lost {endpoint}"
    # trainers federate but never take score traffic: the /routing handler
    # filters the role the monitor registers under
    from mmlspark_tpu.serving import distributed as dist_mod
    svc_src = inspect.getsource(dist_mod.TopologyService._make_handler)
    assert '"trainer"' in svc_src, \
        "GET /routing no longer filters trainer rows"
    assert '"role": "trainer"' in inspect.getsource(
        trainwatch.MonitorServer._registration)

    # live: one run registers the full family set
    reg = MetricsRegistry()
    run = trainwatch.TrainingRun("cov", total_steps=2, registry=reg,
                                 clock=FakeClock(), flight_dump=False)
    try:
        for family in ("mmlspark_training_steps_total",
                       "mmlspark_training_rows_total",
                       "mmlspark_training_stalls_total",
                       "mmlspark_training_step_seconds",
                       "mmlspark_training_progress_ratio",
                       "mmlspark_training_eta_seconds",
                       "mmlspark_training_rows_per_second"):
            assert reg.family(family) is not None, \
                f"TrainingRun no longer registers {family}"
    finally:
        run.close()


def test_prefix_cache_surface_books_metrics():
    """ISSUE 20 coverage: the prefix cache's hit rate is the number the
    whole tentpole is judged by, and its eviction/CoW counters are the
    safety valves' only witnesses — the accounting must be un-droppable.
    Source-level: lookup books the hit/miss split + hit tokens, both
    eviction paths book the reason-labelled counter, the pool's CoW split
    helper books through ``book_cow``, ``PagePool.resized()`` flushes the
    attached index as ``pool_replaced`` BEFORE building the successor,
    both admission fronts route scarcity through ``_alloc_with_reclaim``,
    and the cost ledger carries the ``prefill_cached`` lane the capacity
    report reads.  Live: ModelRunner construction registers all seven
    families even for runners that never enable the cache."""
    from mmlspark_tpu.models import prefix_cache as px_mod
    from mmlspark_tpu.models import runner as runner_mod
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.observability import attribution as attr_mod

    lookup_src = inspect.getsource(px_mod.PrefixIndex.lookup)
    for needle in ("_c_hits", "_c_misses", "_c_hit_tokens"):
        assert needle in lookup_src, f"lookup() lost {needle}"
    for fn in (px_mod.PrefixIndex._evict_node_locked,
               px_mod.PrefixIndex._evict_root_tail_locked):
        assert "_c_evict" in inspect.getsource(fn), \
            f"{fn.__name__} lost the eviction counter"
    assert "_c_cow" in inspect.getsource(px_mod.PrefixIndex.book_cow)
    assert "book_cow" in inspect.getsource(
        runner_mod.ModelRunner._cow_split_page), \
        "_cow_split_page no longer books the CoW split"
    resized_src = inspect.getsource(runner_mod.PagePool.resized)
    assert 'flush(reason="pool_replaced")' in resized_src, \
        "resized() no longer flushes the attached prefix index"
    assert "rebind" in resized_src
    # both admission fronts reclaim retention under pressure instead of
    # shedding while refcount-0 pages sit retained
    for fn in (runner_mod.ModelRunner.decode,
               runner_mod.ContinuousDecoder.submit,
               runner_mod.ContinuousDecoder._dispatch):
        assert "_alloc_with_reclaim" in inspect.getsource(fn), \
            f"{fn.__qualname__} lost the reclaim-then-allocate path"
    # the skipped-prefill lane rides the request record + capacity report
    assert "prefill_cached" in attr_mod.RequestCost.__slots__
    assert "prefill_cached" in inspect.getsource(attr_mod.RequestCost.as_dict)
    assert "PREFIX_TOKENS_FAMILY" in inspect.getsource(
        attr_mod.CapacityModel.report)

    reg = MetricsRegistry()
    runner_mod.ModelRunner(apply_fn=lambda v, x: x, variables={},
                           name="sweep20", registry=reg)
    for family in ("mmlspark_prefix_hits_total",
                   "mmlspark_prefix_misses_total",
                   "mmlspark_prefix_evictions_total",
                   "mmlspark_prefix_cow_splits_total",
                   "mmlspark_prefix_hit_tokens_total",
                   "mmlspark_prefix_hit_rate_pct",
                   "mmlspark_prefix_retained_pages"):
        assert reg.family(family) is not None, \
            f"ModelRunner no longer registers {family}"
