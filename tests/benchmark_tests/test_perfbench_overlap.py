"""``decode.overlapped_step_share``: the reader on recorded runs (the engine
thread's pipelined rounds, hand-driven ``step()``, a program without the
counter, a program without an engine) and its entry in the manifest."""
import types

import numpy as np
import pytest

from benchmark import measure
from benchmark.harness import Run
from benchmark.manifest import Manifest

from perfbench_tiny import REPO

METRIC = "decode.overlapped_step_share"
DECODE_CELLS = ["gpt2xl-generate-backlog", "keye-docqa-backlog",
                "kexaone-reasoning-backlog"]


def _recorded(before, after):
    """A ``Run`` as the readers see it: the two snapshots of a window."""
    run = types.SimpleNamespace(counters_before=before, counters_after=after)
    run.counter = types.MethodType(Run.counter, run)
    return run


def _read(run):
    return Manifest(REPO).module("layer_metrics", METRIC).read(run)


def _engine_window(on_thread):
    """A tiny engine's registry before and after four requests, four slots
    wide: through the ``start()`` thread or through ``step()`` by hand."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import ModelRunner, TransformerEncoder
    from mmlspark_tpu.observability import MetricsRegistry

    module = TransformerEncoder(
        vocab_size=48, num_classes=48, embed_dim=32, num_heads=2,
        num_layers=1, mlp_dim=64, max_len=64, causal=True, pool="none")
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    reg = MetricsRegistry()
    runner = ModelRunner(module=module, variables=variables,
                         name="overlap.reader", registry=reg)
    dec = runner.decode_stream(slots=4, prompt_bucket=8, max_new_tokens=12,
                               page_size=4)
    dec.warmup()
    before = measure.snapshot_registry(reg)
    rng = np.random.default_rng(5)
    # all four wait at the engine's first round, so the rounds that follow
    # are the same on any machine: one join, then eleven steps
    handles = [dec.submit(rng.integers(0, 48, 5).astype(np.int32),
                          max_new_tokens=n) for n in (12, 8, 5, 3)]
    if on_thread:
        dec.start()
        assert all(h.done.wait(60) for h in handles)
    else:
        while dec.step():
            pass
    dec.close()
    return before, measure.snapshot_registry(reg)


@pytest.mark.parametrize("on_thread", [True, False],
                         ids=["start_thread", "step_by_hand"])
def test_the_share_is_overlapped_steps_over_steps(on_thread):
    before, after = _engine_window(on_thread)
    run = _recorded(before, after)
    steps = run.counter("mmlspark_runner_decode_steps_total")
    overlapped = run.counter("mmlspark_runner_decode_steps_overlapped_total")
    assert steps == 11                  # the longest answer less its first
    if on_thread:
        # the step after the joins has nothing in flight before it
        assert overlapped == 10
    else:
        assert overlapped == 0
    assert _read(run) == pytest.approx(100.0 * overlapped / steps)


def test_the_window_is_what_is_read_not_the_whole_run():
    fam = "mmlspark_runner_decode_steps{}_total"
    key = (("runner", "causal_lm"),)
    before = {fam.format(""): {key: 100.0},
              fam.format("_overlapped"): {key: 90.0}}
    after = {fam.format(""): {key: 300.0},
             fam.format("_overlapped"): {key: 260.0}}
    assert _read(_recorded(before, after)) == pytest.approx(85.0)


@pytest.mark.parametrize("after", [
    pytest.param({}, id="no_engine_as_on_the_gbdt_cells"),
    pytest.param({"mmlspark_runner_decode_steps_total":
                  {(("runner", "causal_lm"),): 400.0}},
                 id="no_such_counter_as_on_the_parent"),
    pytest.param({"mmlspark_runner_decode_steps_total":
                  {(("runner", "r"),): 0.0},
                  "mmlspark_runner_decode_steps_overlapped_total":
                  {(("runner", "r"),): 0.0}},
                 id="no_step_in_the_window_as_on_resnet50_bulk")])
def test_nothing_to_read_gives_none(after):
    assert _read(_recorded({}, after)) is None


def assert_the_manifest_lists_the_metric(m):
    """The entry, found by its name, lists the three decode cells among its
    cells (a later decode cell may join them) and not the bulk cell."""
    entry = next(x for x in m.data["per_layer"] if x["name"] == METRIC)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "model_runner",
        "moves": "tokens_per_s"}
    assert set(DECODE_CELLS) <= set(entry["workloads"])
    assert "resnet50-bulk" not in entry["workloads"]
    for cell in DECODE_CELLS:
        assert METRIC in [x["name"] for x in m.metrics_for("per_layer", cell)]
    assert METRIC not in [x["name"]
                          for x in m.metrics_for("per_layer", "resnet50-bulk")]


def test_the_manifest_lists_the_metric_for_the_three_decode_cells():
    assert_the_manifest_lists_the_metric(Manifest(REPO))
