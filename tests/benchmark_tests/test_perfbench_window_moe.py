"""The window-and-share cell at a tiny size on the CPU: the ``causal_lm_window``
family (prompts of several prefill chunks, prefix cache off) under the
``backlog_stream`` traffic kind, the cell end to end, the planted faults and
the control that must come out as not correct, the arithmetic behind the
shares, and the readers."""
import json

import flax.linen as nn
import pytest

from benchmark import shapes_window_moe as shapes
from benchmark.families import causal_lm
from benchmark.harness import run_cell
from benchmark.manifest import Manifest

from perfbench_tiny import REPO, copy_benchmark, edit_json

CELL = "kexaone-reasoning-backlog"
CONFIG = "k-exaone-236b-a23b-ep8-bf16"
APPENDED = ["decode.step_ms", "decode.join_ms", "decode.host_ms_per_step",
            "decode.live_row_share", "kv.pages_in_use_share",
            "lm.experts_ms_per_step", "lm.unscoped_share"]
READERS = ["lm.window_attn_ms_per_step", "lm.full_attn_ms_per_step",
           "lm.dense_ms_per_step", "moe.local_assignment_share",
           "moe.held_experts_touched_share", "moe_share_experts_roofline",
           "window_full_attn_roofline", "window_moe_step_roofline",
           "window_moe_window_mfu"]
KINDS = dict(
    layer_types=["sliding_attention", "sliding_attention",
                 "sliding_attention", "full_attention", "sliding_attention"],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse"])
TINY_KWARGS = dict(KINDS, vocab_size=256, embed_dim=64, num_heads=4,
                   num_kv_heads=2, head_dim=16, sliding_window=8,
                   dense_dim=96, num_experts=16, experts_per_token=4,
                   expert_dim=32, shared_dim=32, first_expert=0,
                   experts_held=8, routed_scale=2.5, rope_theta=1e4,
                   rms_eps=1e-5, max_len=512)
TINY_SIZES = {"layers": 5, "width": 64, "heads": 4, "kv_heads": 2,
              "head_dim": 16, "experts_per_token": 4, "expert_width": 32,
              "vocab": 256, "moe_layers": 4, "window_layers": 4,
              "full_layers": 1, "window": 8, "experts_held": 8,
              "router_width": 16, "dense_width": 96, "shared_width": 32}
TINY_RULE = {"std": 0.08, "bias_std": 0.02, "scale_range": [0.5, 1.5]}
REFERENCE_KWARGS = dict(KINDS, num_heads=4, num_kv_heads=2, head_dim=16,
                        window=8, experts_per_token=4, first_expert=0,
                        routed_scale=2.5, rope_theta=1e4, eps=1e-5,
                        query_block=16, context_step=32)
#: the mean served gap at this size, float32 under the CPU's default
#: precision: the program reads 0 to rounding; each planted fault and the
#: float8 control are held to over three times the limit
TINY_GAP_LIMIT = 0.001


def tiny_root(dst, dtype="float32", **model):
    root = copy_benchmark(dst)
    edit_json(root, f"benchmark/configs/{CONFIG}.json",
              model={"kwargs": dict(TINY_KWARGS, dtype=dtype, **model)},
              sizes=TINY_SIZES, weights=TINY_RULE,
              engine={"slots": 3, "page_size": 4, "prompt_bucket": 16,
                      "max_prompt_len": 48, "max_new_tokens": 24},
              reference={"kwargs": REFERENCE_KWARGS,
                         "served_gap_mean_limit": TINY_GAP_LIMIT})
    edit_json(root, "benchmark/workloads/reasoning_backlog.json",
              prompt_tokens={"median": 20, "sigma": 0.9, "min": 8, "max": 48},
              answer_tokens={"median": 10, "sigma": 0.7, "min": 4, "max": 24},
              requests=24, ramp_seconds=2, trace_seconds=1,
              count_gap_steps=1000)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench_window"))


@pytest.fixture(autouse=True)
def _leave_the_process_as_found():
    import jax
    from mmlspark_tpu.parallel import get_active_mesh, set_active_mesh
    mesh = get_active_mesh()
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    set_active_mesh(mesh)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


# ------------------------------------------------------ the cell, end to end

def test_the_manifest_holds_and_the_cell_names_its_files():
    m = Manifest(REPO)
    assert m.problems() == []
    cell, cfg = m.cell(CELL), m.config(CONFIG)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "reasoning_backlog", 1)
    assert cfg["family"] == "causal_lm_window"
    mix = m.mix("reasoning_backlog")
    assert mix["kind"] == "backlog_stream"
    # ISSUE 36's traffic, letter for letter
    assert mix["prompt_tokens"] == {"median": 384, "sigma": 0.9, "min": 128,
                                    "max": 2048}
    assert mix["answer_tokens"] == {"median": 768, "sigma": 0.7, "min": 128,
                                    "max": 4096}
    assert (mix["requests"], mix["length_seed"], mix["ramp_seconds"],
            mix["count_gap_steps"], mix["trace_seconds"],
            mix["rate_metric"]) == (96, 36, 20, 2, 8, "tokens_per_s")
    mine = {x["name"] for x in m.metrics_for("per_layer", CELL)}
    assert set(READERS) | set(APPENDED) <= mine
    assert not {"decode_step_roofline", "lm_window_mfu",
                "sparse_moe_window_mfu", "moe.experts_touched_share"} & mine
    assert "tokens_per_s" in {x["name"]
                              for x in m.metrics_for("end_to_end", CELL)}


def test_the_configuration_is_the_catalogs_row_cut_as_it_says():
    cfg = Manifest(REPO).config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 153600}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 16, 19200)
    kw, pub = cfg["model"]["kwargs"], row["config"]
    assert (kw["embed_dim"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["dense_dim"], kw["expert_dim"],
            kw["num_experts"], kw["experts_per_token"], kw["sliding_window"],
            kw["routed_scale"], kw["rms_eps"], kw["rope_theta"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["num_experts"], pub["num_experts_per_tok"],
        pub["sliding_window"], pub["routed_scaling_factor"],
        pub["rms_norm_eps"], pub["rope_parameters"]["rope_theta"])
    assert kw["shared_dim"] == \
        pub["num_shared_experts"] * pub["moe_intermediate_size"]
    assert kw["layer_types"] == pub["layer_types"][:5]
    assert kw["mlp_layer_types"] == pub["mlp_layer_types"][:5]
    assert (kw["vocab_size"], kw["experts_held"], kw["first_expert"]) == \
        (cfg["vocab_size"], cfg["num_experts"], 0)
    assert {"qk_norm", "rope", "norm_placement", "router",
            "shared_expert"} <= set(cfg["assumed"])
    assert "num_nextn_predict_layers" in cfg["left_out"]
    ref = cfg["reference"]["kwargs"]
    assert (ref["layer_types"], ref["mlp_layer_types"], ref["window"],
            ref["first_expert"], ref["routed_scale"]) == (
        kw["layer_types"], kw["mlp_layer_types"], 128, 0, 2.5)
    module = causal_lm.make_module(cfg["model"])
    assert type(module).__name__ == "WindowMoEDecoder"


@pytest.mark.parametrize("traced", [False, True])
def test_the_tiny_cell_runs_and_ends_correct(root, traced):
    result = run_cell(root, CELL, seed=2**31 + 11, seconds=1.0, trace=traced,
                      platform="cpu")
    line = json.loads(json.dumps(result))
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] >= 3
    assert line["checks"]["served_gap_mean"]["value"] <= TINY_GAP_LIMIT
    for name in ("requests_not_ok", "answers_of_wrong_length",
                 "pool_pages_left_in_use"):
        assert line["checks"][name] == {"value": 0.0, "limit": 0.0}
    m = line["metrics"]
    if traced:
        assert m["compile.in_window"]["value"] == 0
        assert m["rebuilds.in_window"]["value"] == 0
        # 8 of 16 experts are held: about half of the assignments land
        assert 25 < m["moe.local_assignment_share"]["value"] < 75
        assert 0 < m["moe.held_experts_touched_share"]["value"] <= 100
        assert 0 < m["kv.pages_in_use_share"]["value"] <= 100
        assert 0 < m["decode.live_row_share"]["value"] <= 100
        # no device: no scope paths, no program times, no peaks
        assert not {"lm.window_attn_ms_per_step", "lm.unscoped_share",
                    "moe_share_experts_roofline", "window_moe_window_mfu",
                    "window_moe_step_roofline", "decode.step_ms"} & set(m)
    else:
        assert set(m) == {"tokens_per_s", "setup_s"}
        assert m["tokens_per_s"]["value"] > 0


# ------------------------------------- faults under the harness: not correct

def _not_correct(result):
    assert result["correct"] is False
    assert result["checks"]["served_gap_mean"]["value"] > 3 * TINY_GAP_LIMIT
    assert any("served_gap_mean" in f for f in result["failures"])


def _run_tiny(tmp_path, **model):
    return run_cell(tiny_root(tmp_path, **model), CELL, seed=5, seconds=0.5,
                    trace=False, platform="cpu")


def test_one_more_visible_key_is_not_correct(tmp_path):
    """The program's window layers see 9 keys (129 of the published 128)."""
    _not_correct(_run_tiny(tmp_path, sliding_window=9))


def test_the_routed_scale_dropped_is_not_correct(tmp_path):
    """``routed_scaling_factor`` 2.5 left out of the experts' weights."""
    _not_correct(_run_tiny(tmp_path, routed_scale=1.0))


def test_rope_on_the_full_layer_is_not_correct(tmp_path, monkeypatch):
    """Rotary positions on every layer, where the full layer has none."""
    import mmlspark_tpu.models.window_moe as program
    plain = program.WindowMoEDecoder._kinds
    monkeypatch.setattr(
        program.WindowMoEDecoder, "_kinds",
        lambda self: [(w, True, s) for w, _, s in plain(self)])
    _not_correct(_run_tiny(tmp_path))


def test_the_bias_inside_the_weights_is_not_correct(tmp_path, monkeypatch):
    """The correction bias in ``w_e`` too, where it only selects."""
    import jax
    import jax.numpy as jnp
    import mmlspark_tpu.models.window_moe as program

    class Biased(program.Router):
        @nn.compact
        def __call__(self, h):
            kernel = self.param("kernel", nn.initializers.normal(0.02),
                                (h.shape[-1], self.num_experts))
            bias = self.param("bias", nn.initializers.zeros,
                              (self.num_experts,))
            s = jax.nn.sigmoid(jnp.dot(h, kernel)) + bias
            w, ids = jax.lax.top_k(s, self.experts_per_token)
            return ids, self.scale * w / (w.sum(-1, keepdims=True) + 1e-20)

    monkeypatch.setattr(program, "Router", Biased)
    # a bias of the scores' own size, as a trained one is; at the family's
    # 0.02 the fault is real but 1% of a weight
    root = tiny_root(tmp_path)
    edit_json(root, f"benchmark/configs/{CONFIG}.json",
              weights=dict(TINY_RULE, bias_std=0.3))
    _not_correct(run_cell(root, CELL, seed=5, seconds=0.5, trace=False,
                          platform="cpu"))


def test_the_shared_expert_dropped_is_not_correct(tmp_path, monkeypatch):
    import mmlspark_tpu.models.window_moe as program
    plain = program.GatedMLP

    def gated(features, dtype, name):
        mlp = plain(features, dtype, name=name)
        return (lambda x: 0.0 * mlp(x)) if name == "shared" else mlp
    monkeypatch.setattr(program, "GatedMLP", gated)
    _not_correct(_run_tiny(tmp_path))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_in_the_programs_place_is_not_correct(seed):
    """The reference with its matmul operands in float8, put in the
    program's place at the same rows, is over the limit; the float32 program
    (one-shot paged ``decode``) is under it."""
    import functools
    import numpy as np
    from benchmark.families import window_moe_lm_reference as reference
    from mmlspark_tpu.models.runner import ModelRunner
    module = causal_lm.make_module(
        {"factory": "mmlspark_tpu.models.window_moe.WindowMoEDecoder",
         "kwargs": dict(TINY_KWARGS, dtype="float32")})
    variables = causal_lm.make_variables(module, seed, "float32", TINY_RULE)
    runner = ModelRunner(module=module, variables=variables,
                         name=f"window.control.{seed}")
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, 256, (3, 40)).astype(np.int32)
    out = runner.decode(prompts, max_new_tokens=24, kv_layout="paged",
                        page_size=4, prompt_bucket=40)
    forward = functools.partial(reference.window_moe_forward,
                                **REFERENCE_KWARGS)
    got = reference.check_served(
        forward, variables,
        [(p, [int(t) for t in toks]) for p, toks in zip(prompts, out.tokens)],
        pad_to=64, rows=24, control="fp8")
    assert got["positions"] == 72
    assert got["served_gap_mean"] <= TINY_GAP_LIMIT
    assert got["control_gap_mean"] > 3 * TINY_GAP_LIMIT
    assert np.isfinite(got["control_gap_max"])


# --------------------------------------------- the arithmetic of the shares

def test_the_parameters_by_hand():
    sizes = Manifest(REPO).config(CONFIG)["sizes"]
    p = shapes.params(sizes)
    assert p["attention"] == 2 * 6144 * 8192 + 2 * 6144 * 1024 == 113_246_208
    assert p["dense"] == 3 * 6144 * 18432 == 339_738_624
    assert p["expert"] == p["shared"] == 3 * 6144 * 2048 == 37_748_736
    assert p["router"] == 6144 * 128 + 128 and p["norms"] == 12_544
    assert p["dense_layer"] == 452_997_376
    assert p["moe_layer"] == 113_246_208 + 12_544 + 786_560 \
        + 17 * 37_748_736 == 755_773_824
    assert p["total"] == 452_997_376 + 4 * 755_773_824 \
        + 2 * 19_200 * 6144 + 6144 == 3_712_028_416
    # ISSUE 36: "3,712M parameters, 7.42 GB"
    assert round(p["total"] / 1e6) == 3712
    assert round(p["total"] * 2 / 1e9, 2) == 7.42
    # and "1,178M parameters" of weights every step reads
    assert round(p["step_weights"] / 1e6) == 1178
    assert shapes.kv_row_bytes(sizes) == 4096


def test_the_least_bytes_by_hand():
    sizes = Manifest(REPO).config(CONFIG)["sizes"]
    need = shapes.experts_need(14, 64, sizes)
    assert need["hbm_bytes"] == 14 * 75_497_472 + 64 * 2 * 6144 * 2
    assert need["flops"] == 2 * 64 * 37_748_736
    attn = shapes.attention_need(32, 32 * 1400, 32 * 128, sizes)
    assert attn["hbm_bytes"] == (4 * 32 * 128 + 32 * 1400) * 4096
    # one step of 32 sequences at 1,400 positions, 14 held experts a layer
    step = shapes.steps_need(1, 32, 32 * 1400, 56, 128, sizes)
    assert step["hbm_bytes"] == shapes.params(sizes)["step_weights"] * 2 \
        + 56 * 75_497_472 + 128 * 2 * 6144 * 2 + attn["hbm_bytes"]
    # ISSUE 36's floor "near 8.4 ms" at 819 GB/s
    assert step["hbm_bytes"] / 819e9 == pytest.approx(8.4e-3, rel=0.03)
    flops = shapes.window_flops(32, 32 * 1400, 128, 512, 512 * 513 / 2, 1,
                                sizes)
    assert flops > 2 * shapes.params(sizes)["every_matmul"] * 544


def test_the_backlog_is_the_same_work_for_every_seed():
    from benchmark.traffic.backlog_stream import make_requests
    mix = Manifest(REPO).mix("reasoning_backlog")
    a = make_requests(mix, 19_200, 1)
    b = make_requests(mix, 19_200, 2**31 + 5)
    pairs = lambda rs: sorted((len(p), n) for p, n in rs)  # noqa: E731
    assert len(a) == 96 and pairs(a) == pairs(b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    assert all(128 <= len(p) <= 2048 and 128 <= n <= 4096 for p, n in a)
    assert max(int(p.max()) for p, _ in a) < 19_200
    # ISSUE 36: "37% of joins more than one chunk"
    assert 0.2 < sum(len(p) > 512 for p, _ in a) / len(a) < 0.5


# ---------------------------------------------------------------- the readers

class Bare:
    manifest = Manifest(REPO)
    platform = "cpu"
    config = manifest.config(CONFIG)
    cell = manifest.cell(CELL)
    trace_summary = None
    peaks = None
    facts = {}
    window_start_s = window_end_s = 0.0

    def counter(self, family, **labels):
        return None

    def histogram(self, family, **labels):
        return None

    def device_busy_s(self):
        return None

    def note(self, text):
        pass


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_none(name):
    """Without a trace, and on a program that lacks the scopes and the
    counters (the parent commit): ``None``, never an exception."""
    from benchmark import measure
    reader = Manifest(REPO).module("layer_metrics", name)
    bare = Bare()
    bare.spans = measure.Spans()
    assert reader.read(bare) is None
    traced = Bare()
    traced.spans = measure.Spans()
    traced.device_busy_s = lambda: 0.5
    traced.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # the parent's program has the steps' counters and not the new one
    traced.counter = lambda family, **labels: \
        None if "local_assignments" in family else 0.0
    assert reader.read(traced) is None


def test_the_counter_readers_by_hand():
    m = Manifest(REPO)
    run = Bare()
    run.facts = {"step_tokens": 1000.0}
    counts = {"mmlspark_runner_moe_local_assignments_total": 4000.0,
              "mmlspark_runner_moe_experts_touched_total": 1792.0,
              "mmlspark_runner_decode_steps_total": 32.0}
    run.counter = lambda family, **labels: counts.get(family)
    # 4,000 of 1,000 x 8 x 4 assignments; 1,792 of 32 x 4 x 16 experts
    assert m.module("layer_metrics", "moe.local_assignment_share").read(
        run) == pytest.approx(12.5)
    assert m.module("layer_metrics", "moe.held_experts_touched_share").read(
        run) == pytest.approx(87.5)
