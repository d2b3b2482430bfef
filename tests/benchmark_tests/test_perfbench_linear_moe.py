"""The linear-attention cell at a tiny size on the CPU: the
``causal_lm_linear`` family (prompts of several prefill chunks, prefix cache
off) under the ``backlog_stream`` traffic kind, the cell end to end, the
planted faults and the control that must come out as not correct, the
arithmetic behind the shares, and the readers."""
import json

import jax
import jax.numpy as jnp
import pytest

from benchmark import shapes_linear_moe as shapes
from benchmark.families import causal_lm, causal_lm_linear
from benchmark.harness import run_cell
from benchmark.manifest import Manifest

from perfbench_tiny import REPO, copy_benchmark, edit_json

CELL = "solar-reasoning-backlog"
CONFIG = "solar-open2-250b-ep8-bf16"
#: the entries that list the three decode cells, and the window cell's
#: readers of the expert layer and the scopes, this cell appended to each
JOINED = ["tokens_per_s", "decode.step_ms", "decode.join_ms",
          "decode.host_ms_per_step", "decode.live_row_share",
          "kv.pages_in_use_share", "decode.overlapped_step_share",
          "decode.idle_prepare_ms_per_step", "decode.idle_join_ms_per_step",
          "decode.idle_retire_ms_per_step",
          "decode.idle_unphased_ms_per_step", "decode.host_work_ms_per_step",
          "lm.full_attn_ms_per_step", "lm.experts_ms_per_step",
          "lm.dense_ms_per_step", "lm.unscoped_share",
          "moe.local_assignment_share", "moe.held_experts_touched_share",
          "moe_share_experts_roofline"]
READERS = ["lm.linear_attn_ms_per_step", "lm.linear_attn_ms_per_join",
           "linear_state_roofline", "linear_chunk_roofline",
           "linear_moe_step_roofline", "linear_moe_window_mfu"]
LAYERS = ["full_attention", "linear_attention", "linear_attention",
          "linear_attention"]
TINY_KWARGS = dict(vocab_size=256, embed_dim=64, num_heads=4, num_kv_heads=2,
                   head_dim=16, layer_types=LAYERS, linear_heads=4,
                   linear_head_dim=16, conv_kernel=4, chunk_block=8,
                   num_experts=16, experts_per_token=4, expert_dim=32,
                   shared_dim=32, first_expert=0, experts_held=8,
                   routed_scale=1.0, rms_eps=1e-5, max_len=512)
TINY_SIZES = {"layers": 4, "width": 64, "heads": 4, "kv_heads": 2,
              "head_dim": 16, "experts_per_token": 4, "expert_width": 32,
              "vocab": 256, "bytes_per_value": 4, "moe_layers": 4,
              "full_layers": 1, "experts_held": 8, "router_width": 16,
              "dense_width": 96, "shared_width": 32, "linear_layers": 3,
              "linear_heads": 4, "linear_head_dim": 16, "conv_kernel": 4,
              "chunk_block": 8, "state_bytes_per_value": 4}
TINY_RULE = {"std": 0.08, "bias_std": 0.02, "scale_range": [0.5, 1.5],
             "by_name": {"A_log": ["log_uniform", 1, 16],
                         "dt_bias": ["inverse_softplus_log_uniform", 0.001,
                                     0.1],
                         "conv": ["uniform", -0.5, 0.5]}}
REFERENCE_KWARGS = dict(layer_types=LAYERS, num_heads=4, num_kv_heads=2,
                        head_dim=16, linear_heads=4, linear_head_dim=16,
                        experts_per_token=4, first_expert=0, routed_scale=1.0,
                        eps=1e-5, query_block=16, context_step=32)
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


@pytest.fixture(autouse=True)
def _leave_the_process_as_found():
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
    assert cfg["family"] == "causal_lm_linear"
    assert m.mix("reasoning_backlog")["kind"] == "backlog_stream"
    mine = {x["name"] for x in m.metrics_for("per_layer", CELL)}
    assert set(READERS) | set(JOINED[1:]) <= mine
    # GPT-2's shapes, the Keye cell's and the window cell's own readers
    assert not {"decode_step_roofline", "lm_window_mfu",
                "sparse_moe_window_mfu", "moe.experts_touched_share",
                "window_moe_step_roofline", "window_full_attn_roofline",
                "lm.window_attn_ms_per_step"} & mine
    assert "tokens_per_s" in {x["name"]
                              for x in m.metrics_for("end_to_end", CELL)}
    by_name = {x["name"]: x for x in m.data["per_layer"]}
    for name in READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert (by_name[name]["moves"], by_name[name]["layer"]) == \
            ("tokens_per_s", "kernels")
    # appended at the end, in the order of READERS
    assert [x["name"] for x in m.data["per_layer"][-6:]] == READERS
    assert m.data["workloads"][-1]["name"] == CELL
    assert m.data["configs"][-1]["name"] == CONFIG


#: the language model's settings in the source's ``config.json``, as the
#: configuration file copies them (keys that say nothing of its shape left
#: out)
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": list(range(0, 48, 4)),
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8}
SOURCE = "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"


def test_the_configuration_is_the_published_one_cut_as_it_says():
    cfg = Manifest(REPO).config(CONFIG)
    assert cfg["source"] == SOURCE
    differs = sorted(k for k, v in PUBLISHED.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 320, "vocab_size": 196608}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 40, 24576)
    kw, pub = cfg["model"]["kwargs"], PUBLISHED
    lin = pub["linear_attn_config"]
    assert (kw["embed_dim"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["expert_dim"], kw["num_experts"],
            kw["experts_per_token"], kw["routed_scale"], kw["rms_eps"],
            kw["linear_heads"], kw["linear_head_dim"], kw["conv_kernel"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["moe_intermediate_size"], pub["n_routed_experts"],
        pub["num_experts_per_tok"], pub["routed_scaling_factor"],
        pub["rms_norm_eps"], lin["num_heads"], lin["head_dim"],
        lin["short_conv_kernel_size"])
    assert kw["shared_dim"] == \
        pub["n_shared_experts"] * pub["moe_intermediate_size"]
    # one whole period from layer 0: G L L L
    assert kw["layer_types"] == [
        "full_attention" if i in pub["gqa_layers"] else "linear_attention"
        for i in range(4)]
    assert (kw["vocab_size"], kw["experts_held"], kw["first_expert"]) == \
        (cfg["vocab_size"], cfg["n_routed_experts"], 0)
    assert {"kda", "state_precision", "norm_placement", "gqa", "router",
            "shared_expert", "weights", "engine"} <= set(cfg["assumed"])
    ref = cfg["reference"]["kwargs"]
    assert (ref["layer_types"], ref["first_expert"], ref["routed_scale"],
            ref["linear_heads"]) == (kw["layer_types"], 0, 1.0, 64)
    module = causal_lm.make_module(cfg["model"])
    assert type(module).__name__ == "LinearMoEDecoder"


def test_the_parameters_are_the_modules_own_count():
    """``shapes_linear_moe.params`` against the module's parameter tree at
    the configuration's size (shapes only, nothing allocated)."""
    cfg = Manifest(REPO).config(CONFIG)
    module = causal_lm.make_module(cfg["model"])
    tree = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    count = sum(int(jnp.prod(jnp.asarray(a.shape)))
                for a in jax.tree_util.tree_leaves(tree))
    p = shapes.params(cfg["sizes"])
    assert p["total"] == count == 3_308_353_344
    assert p["linear"] == 137_732_288 and p["full"] == 109_051_904
    assert p["expert"] == 3 * 4096 * 1280 == 15_728_640
    assert 40 * p["expert"] + p["shared"] + p["router"] == 646_185_280
    # 6.62 GB of bf16 weights, and 31,457,280 B an expert
    assert round(p["total"] * 2 / 1e9, 2) == 6.62
    assert p["expert"] * 2 == 31_457_280


def test_the_state_and_the_step_by_hand():
    sizes = Manifest(REPO).config(CONFIG)["sizes"]
    # a row's recurrent state and conv history, one layer
    assert shapes.state_row_bytes(sizes) == 4_194_304 + 147_456
    # 65 rows x 3 layers: 0.85 GB
    assert round(65 * 3 * shapes.state_row_bytes(sizes) / 1e9, 2) == 0.85
    step = shapes.linear_step_need(64, sizes)
    assert step["hbm_bytes"] > 64 * 3 * 2 * 4_341_760
    # 1.61 GB of state read and written a step at 64 live rows
    assert round(64 * 3 * 2 * 4_194_304 / 1e9, 2) == 1.61
    whole = shapes.steps_need(1, 64, 64 * 1200, 32 * 4, 64 * 8 * 4 / 8,
                              sizes)
    assert whole["hbm_bytes"] > step["hbm_bytes"] + 128 * 31_457_280
    chunk = shapes.chunk_need(512, 1, sizes)
    assert chunk["flops"] == 3 * 512 * 64 * (7 * 64 * 128 + 6 * 128 * 128)
    flops = shapes.window_flops(64, 64 * 1200, 64, 512, 512 * 513 / 2, 1,
                                sizes)
    assert flops > 2 * shapes.params(sizes)["every_matmul"] * 576


@pytest.mark.parametrize("traced", [False, True])
def test_the_tiny_cell_runs_and_ends_correct(tmp_path, traced):
    root = tiny_root(tmp_path)
    result = run_cell(root, CELL, seed=2**31 + 11, seconds=1.0, trace=traced,
                      platform="cpu")
    line = json.loads(json.dumps(result))
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] >= 3
    assert line["checks"]["served_gap_mean"]["value"] <= TINY_GAP_LIMIT
    m = line["metrics"]
    if traced:
        assert m["compile.in_window"]["value"] == 0
        assert 25 < m["moe.local_assignment_share"]["value"] < 75
        assert 0 < m["moe.held_experts_touched_share"]["value"] <= 100
        # no device: no scope paths, no program times, no peaks
        assert not set(READERS) & set(m)
    else:
        assert set(m) == {"tokens_per_s", "setup_s"}


# ------------------------------------- faults under the harness: not correct

def _faults(program):
    """Each fault by the name of the function of ``linear_moe`` it stands
    in for."""
    plain_decay = program.kda_decay

    def decay_per_head(f, A_log, dt_bias):
        g = plain_decay(f, A_log, dt_bias)
        return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)

    return {
        # beta not doubled: eigenvalues of the transition kept positive
        "kda_beta": jax.nn.sigmoid,
        # one decay a head, where it is one a channel
        "kda_decay": decay_per_head,
        # the state not reset at a join: a slot's next request starts from
        # its last one's state and conv history
        "start_from": lambda old, fresh: old,
        # the conv history dropped: every step and chunk convolves zeros
        "conv_window": lambda history, x: jnp.concatenate(
            [jnp.zeros(history.shape, x.dtype), x], axis=1),
        # the full layer's output gate dropped
        "output_gate": lambda a, gate: a,
    }


@pytest.mark.parametrize("name", ["conv_window", "kda_beta", "kda_decay",
                                  "output_gate", "start_from"])
def test_a_planted_fault_is_not_correct(tmp_path, monkeypatch, name):
    import mmlspark_tpu.models.linear_moe as program
    monkeypatch.setattr(program, name, _faults(program)[name])
    result = run_cell(tiny_root(tmp_path), CELL, seed=5, seconds=0.5,
                      trace=False, platform="cpu")
    assert result["correct"] is False
    assert result["checks"]["served_gap_mean"]["value"] > 3 * TINY_GAP_LIMIT
    assert any("served_gap_mean" in f for f in result["failures"])


@pytest.mark.parametrize("seed", [1, 2])
def test_the_float8_control_in_the_programs_place_is_not_correct(seed):
    """The reference with its matmul operands in float8, put in the
    program's place at the same rows, is over the limit; the float32 program
    (one-shot paged ``decode``) is under it."""
    import functools
    import numpy as np
    from benchmark.families import linear_moe_lm_reference as reference
    from mmlspark_tpu.models.runner import ModelRunner
    module = causal_lm.make_module(
        {"factory": "mmlspark_tpu.models.linear_moe.LinearMoEDecoder",
         "kwargs": dict(TINY_KWARGS, dtype="float32")})
    variables = causal_lm_linear.make_variables(module, seed, "float32",
                                                TINY_RULE)
    runner = ModelRunner(module=module, variables=variables,
                         name=f"linear.control.{seed}")
    prompts = np.random.default_rng(seed).integers(0, 256, (3, 40)).astype(
        np.int32)
    out = runner.decode(prompts, max_new_tokens=24, kv_layout="paged",
                        page_size=4, prompt_bucket=40)
    forward = functools.partial(reference.linear_moe_forward,
                                **REFERENCE_KWARGS)
    got = reference.check_served(
        forward, variables,
        [(p, [int(t) for t in toks]) for p, toks in zip(prompts, out.tokens)],
        pad_to=64, rows=24, control="fp8")
    assert got["positions"] == 72
    assert got["served_gap_mean"] <= TINY_GAP_LIMIT
    assert got["control_gap_mean"] > 3 * TINY_GAP_LIMIT


def test_the_weights_are_drawn_by_name_from_the_seed():
    """``A_log``, ``dt_bias`` and the conv taps by their rules, the same
    arrays for the same seed."""
    import numpy as np
    module = causal_lm.make_module(
        {"factory": "mmlspark_tpu.models.linear_moe.LinearMoEDecoder",
         "kwargs": dict(TINY_KWARGS, dtype="float32")})
    a = causal_lm_linear.make_variables(module, 2**31 + 3, "float32",
                                        TINY_RULE)
    b = causal_lm_linear.make_variables(module, 2**31 + 3, "float32",
                                        TINY_RULE)
    attn = a["params"]["layer_1"]["attn"]
    A = np.exp(np.asarray(attn["A_log"]))
    dt = np.log1p(np.exp(np.asarray(attn["dt_bias"])))         # softplus
    taps = np.asarray(attn["q_conv"]["conv"])
    assert (1 <= A).all() and (A <= 16).all()
    assert np.allclose(dt.min(), 0.001, rtol=2) and dt.max() <= 0.1 * 1.001
    assert (np.abs(taps) <= 0.5).all() and taps.std() > 0.2
    assert all((np.asarray(x) == np.asarray(y)).all() for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


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
    """Without a trace, and on a program that lacks the scopes (the parent
    commit): ``None``, never an exception."""
    from benchmark import measure
    reader = Manifest(REPO).module("layer_metrics", name)
    bare = Bare()
    bare.spans = measure.Spans()
    assert reader.read(bare) is None
    traced = Bare()
    traced.spans = measure.Spans()
    traced.device_busy_s = lambda: 0.5
    traced.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    traced.counter = lambda family, **labels: 0.0
    assert reader.read(traced) is None


def test_the_window_readers_read_this_configurations_sizes():
    """The expert layer's readers the cell joins take an expert's bytes and
    the layers from the configuration: 31,457,280 B an expert here, not the
    window cell's 75,497,472."""
    m = Manifest(REPO)
    run = Bare()
    run.facts = {"step_tokens": 1000.0}
    counts = {"mmlspark_runner_moe_local_assignments_total": 4000.0,
              "mmlspark_runner_moe_experts_touched_total": 5120.0,
              "mmlspark_runner_decode_steps_total": 32.0}
    run.counter = lambda family, **labels: counts.get(family)
    # 4,000 of 1,000 x 8 x 4 assignments; 5,120 of 32 x 4 x 40 experts
    assert m.module("layer_metrics", "moe.local_assignment_share").read(
        run) == pytest.approx(12.5)
    assert m.module("layer_metrics", "moe.held_experts_touched_share").read(
        run) == pytest.approx(100.0)
    from benchmark import shapes_window_moe
    need = shapes_window_moe.experts_need(1, 0, run.config["sizes"])
    assert need["hbm_bytes"] == 31_457_280
