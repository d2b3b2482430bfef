"""The long-context cell at a tiny size on the CPU: the ``causal_lm_long``
family over an explicit pool with the prefix index on, the
``shared_prefix_backlog`` traffic kind, the cell end to end, the faults and
the control that must come out as not correct, the arithmetic behind the
shares, and the readers."""
import functools
import json

import numpy as np
import pytest

from benchmark import lm_phase_times, shapes_sparse_moe as shapes
from benchmark.families import causal_lm
from benchmark.families import sparse_moe_lm_reference as reference
from benchmark.harness import run_cell
from benchmark.manifest import Manifest

from perfbench_tiny import REPO, copy_benchmark, edit_json

CELL = "keye-docqa-backlog"
CONFIG = "keye-vl2-30b-a3b-text-bf16"
READERS = ["lm.indexer_ms_per_step", "lm.select_ms_per_step",
           "lm.sparse_attn_ms_per_step", "lm.experts_ms_per_step",
           "lm.unscoped_share", "moe.experts_touched_share",
           "prefix.cached_token_share", "moe_experts_roofline",
           "sparse_attn_roofline", "sparse_moe_step_roofline",
           "sparse_moe_window_mfu"]
SIZES = dict(num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
             experts_per_token=2, index_heads=2, index_dim=16, index_topk=8)
TINY_KWARGS = dict(SIZES, vocab_size=512, embed_dim=64, num_experts=8,
                   expert_dim=32, rope_theta=1e4, max_len=256)
TINY_RULE = {"std": 0.08, "bias_std": 0.02, "scale_range": [0.5, 1.5]}
REFERENCE_KWARGS = dict(SIZES, rope_theta=1e4, eps=1e-6, query_block=8,
                        context_step=16)
#: the mean served gap at this size, float32 under the CPU's default
#: precision: the program reads 0; each planted fault and the float8 control
#: are held to over three times the limit
TINY_GAP_LIMIT = 0.001
#: the language model's settings as the model's public config.json gives them
PUBLISHED_AT = ("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/"
                "main/config.json")
PUBLISHED = {'attention_bias': False,
 'decoder_sparse_step': 1,
 'head_dim': 128,
 'hidden_act': 'silu',
 'hidden_size': 2048,
 'intermediate_size': 6144,
 'max_position_embeddings': 262144,
 'max_window_layers': 48,
 'mlp_only_layers': [],
 'model_type': 'KeyeVL2',
 'moe_intermediate_size': 768,
 'norm_topk_prob': True,
 'num_attention_heads': 32,
 'num_experts': 128,
 'num_experts_per_tok': 8,
 'num_hidden_layers': 48,
 'num_key_value_heads': 4,
 'num_local_experts': 128,
 'rms_norm_eps': 1e-06,
 'rope_scaling': {'mrope_section': [16, 24, 24],
                  'rope_type': 'default',
                  'type': 'default'},
 'rope_theta': 10000000,
 'sa_config': {'indexer_head_dim': 64,
               'indexer_num_heads': 16,
               'indexer_num_kv_heads': 1,
               'kv_chunk_size': 512,
               'q_chunk_size': 512,
               'topk': 2048},
 'sliding_window': None,
 'tie_word_embeddings': False,
 'use_sliding_window': False,
 'vocab_size': 151936}


def tiny_root(dst, dtype="float32", **model):
    root = copy_benchmark(dst)
    edit_json(root, f"benchmark/configs/{CONFIG}.json",
              model={"kwargs": dict(TINY_KWARGS, dtype=dtype, **model)},
              sizes={"layers": 2, "width": 64, "heads": 4, "kv_heads": 2,
                     "head_dim": 16, "experts": 8, "experts_per_token": 2,
                     "expert_width": 32, "index_heads": 2, "index_dim": 16,
                     "index_topk": 8, "vocab": 512},
              weights=TINY_RULE,
              engine={"slots": 3, "page_size": 4, "prompt_bucket": 8,
                      "max_prompt_len": 48, "max_new_tokens": 8,
                      "pool_pages": 64, "prefix_budget_pages": 39},
              reference={"kwargs": REFERENCE_KWARGS,
                         "served_gap_mean_limit": TINY_GAP_LIMIT})
    edit_json(root, "benchmark/workloads/docqa_backlog.json",
              documents=2, document_tokens=32, setup_question_tokens=3,
              question_tokens={"median": 6, "sigma": 0.7, "min": 2, "max": 16},
              answer_tokens={"median": 5, "sigma": 0.6, "min": 2, "max": 8},
              requests=24, ramp_seconds=2, trace_seconds=1,
              count_gap_steps=1000)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench_moe"))


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
        (CONFIG, "docqa_backlog", 1)
    assert cfg["family"] == "causal_lm_long"
    assert m.mix("docqa_backlog")["kind"] == "shared_prefix_backlog"
    mine = {x["name"] for x in m.metrics_for("per_layer", CELL)}
    assert set(READERS) | {"decode.step_ms", "decode.join_ms",
                           "kv.pages_in_use_share"} <= mine
    assert not {"decode_step_roofline", "lm_window_mfu"} & mine
    assert "tokens_per_s" in {x["name"]
                              for x in m.metrics_for("end_to_end", CELL)}


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    cfg = Manifest(REPO).config(CONFIG)
    assert cfg["source"] == PUBLISHED_AT
    differs = [k for k, v in PUBLISHED.items() if cfg.get(k) != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == 48
    kw, sa = cfg["model"]["kwargs"], cfg["sa_config"]
    assert (kw["embed_dim"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["num_experts"], kw["experts_per_token"],
            kw["expert_dim"], kw["vocab_size"], kw["num_layers"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_experts"],
        cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
        cfg["vocab_size"], cfg["num_hidden_layers"])
    assert (kw["index_heads"], kw["index_dim"], kw["index_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert kw["rope_theta"] == cfg["rope_theta"]
    assert "vision_tower" in cfg["assumed"]
    module = causal_lm.make_module(cfg["model"])
    assert type(module).__name__ == "SparseMoEDecoder"
    e = cfg["engine"]
    assert e["pool_pages"] - 1 - e["prefix_budget_pages"] == e["slots"] * 13


@pytest.mark.parametrize("traced", [False, True])
def test_the_tiny_cell_runs_and_ends_correct(root, traced):
    result = run_cell(root, CELL, seed=2**31 + 7, seconds=1.0, trace=traced,
                      platform="cpu")
    line = json.loads(json.dumps(result))
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] >= 3
    assert line["checks"]["served_gap_mean"]["value"] <= TINY_GAP_LIMIT
    for name in ("requests_not_ok", "answers_of_wrong_length",
                 "pool_pages_left_in_use", "document_prefix_misses"):
        assert line["checks"][name] == {"value": 0.0, "limit": 0.0}
    m = line["metrics"]
    if traced:
        assert m["compile.in_window"]["value"] == 0
        assert m["rebuilds.in_window"]["value"] == 0
        assert 0 < m["moe.experts_touched_share"]["value"] <= 100
        # 32 of a prompt's 34 to 48 tokens are the resident document's
        assert 60 < m["prefix.cached_token_share"]["value"] < 100
        assert 0 < m["kv.pages_in_use_share"]["value"] <= 100
        # no device: no scope paths, no program times, no peaks
        assert not {"lm.experts_ms_per_step", "lm.unscoped_share",
                    "moe_experts_roofline", "sparse_moe_window_mfu",
                    "decode.step_ms"} & set(m)
    else:
        assert set(m) == {"tokens_per_s", "setup_s"}
        assert m["tokens_per_s"]["value"] > 0


# ------------------------------------- faults under the harness: not correct

def _not_correct(result):
    assert result["correct"] is False
    assert result["checks"]["served_gap_mean"]["value"] > 3 * TINY_GAP_LIMIT
    assert any("served_gap_mean" in f for f in result["failures"])


def test_attention_over_all_positions_is_not_correct(tmp_path):
    """The program attends to every position (its ``index_topk`` over any
    context) where the reference selects 8."""
    root = tiny_root(tmp_path, index_topk=10 ** 6)
    _not_correct(run_cell(root, CELL, seed=5, seconds=0.5, trace=False,
                          platform="cpu"))


def test_an_expert_left_out_is_not_correct(tmp_path, monkeypatch):
    """One of every token's chosen experts never runs."""
    import mmlspark_tpu.models.sparse_moe as program
    plain = program.routed_experts

    def one_short(x, gate, up, down, expert_ids, weights, **kw):
        return plain(x, gate, up, down, expert_ids,
                     weights.at[:, -1].set(0.0), **kw)
    monkeypatch.setattr(program, "routed_experts", one_short)
    _not_correct(run_cell(tiny_root(tmp_path), CELL, seed=5, seconds=0.5,
                          trace=False, platform="cpu"))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_in_the_programs_place_is_not_correct(seed):
    """The reference with its matmul operands in float8, put in the
    program's place at the same rows, is over the limit; the float32 program
    is under it."""
    import jax.numpy as jnp
    from mmlspark_tpu.models.runner import ModelRunner
    module = causal_lm.make_module(
        {"factory": "mmlspark_tpu.models.sparse_moe.SparseMoEDecoder",
         "kwargs": dict(TINY_KWARGS, dtype="float32")})
    variables = causal_lm.make_variables(module, seed, "float32", TINY_RULE)
    runner = ModelRunner(module=module, variables=variables,
                         name=f"moe.control.{seed}")
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, 512, (3, 40)).astype(np.int32)
    out = runner.decode(prompts, max_new_tokens=8, kv_layout="paged",
                        page_size=4, prompt_bucket=40)
    forward = functools.partial(reference.sparse_moe_forward,
                                **REFERENCE_KWARGS)
    got = reference.check_served(
        forward, variables,
        [(p, [int(t) for t in toks]) for p, toks in zip(prompts, out.tokens)],
        pad_to=48, rows=8, control="fp8")
    assert got["positions"] == 24
    assert got["served_gap_mean"] <= TINY_GAP_LIMIT
    assert got["control_gap_mean"] > 3 * TINY_GAP_LIMIT
    assert jnp.isfinite(got["control_gap_max"])


# --------------------------------------------- the arithmetic of the shares

def test_the_parameters_by_hand():
    sizes = Manifest(REPO).config(CONFIG)["sizes"]
    p = shapes.params(sizes)
    assert p["attention"] == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18_874_368
    assert p["indexer"] == 2_097_152 + 131_072 + 32_768 + 128 == 2_261_120
    assert p["router"] == 262_144 and p["norms"] == 4_352
    assert p["expert"] == 3 * 2048 * 768 == 4_718_592
    assert p["layer"] == 625_381_760
    assert p["total"] == 6 * 625_381_760 + 2 * 311_164_928 + 2048 \
        == 4_374_622_464
    assert p["routed_matmul"] == 6 * 8 * 4_718_592
    per = shapes.cache_bytes_per_token(sizes)
    assert 6 * (per["kv"] + per["index"]) == 13_056


def test_the_span_sums_and_the_least_bytes_by_hand():
    # contexts 6, 7, 8, 9, 10 under topk 8: selected 6 + 7 + 8 + 8 + 8
    assert shapes.span_sums([(6, 5)], 8) == (5.0, 40.0, 37.0)
    assert shapes.span_sums([(1, 3), (20, 2)], 8) == (5.0, 47.0, 22.0)
    sizes = Manifest(REPO).config(CONFIG)["sizes"]
    need = shapes.experts_need(50, 64, sizes)
    assert need["hbm_bytes"] == 50 * 9_437_184 + 64 * 2 * 2048 * 2
    assert need["flops"] == 2 * 64 * 4_718_592
    attn = shapes.sparse_attention_need(33_000, 2_048, sizes)
    assert attn["hbm_bytes"] == 6 * (33_000 * 128 + 2_048 * 2_048)
    # one step of 8 sequences at 33,000 positions, 50 experts a layer
    step = shapes.steps_need(1, [(33_000, 1)] * 8, 300, sizes)
    other = 6 * (625_381_760 - 128 * 4_718_592) + 311_164_928 + 2048
    assert step["hbm_bytes"] == other * 2 + 300 * 9_437_184 \
        + 8 * 6 * 8 * 2 * 2048 * 2 + 8 * attn["hbm_bytes"]
    # 5.03 ms at 819 GB/s: the "about 5 ms" ideal step of ISSUE 34
    assert step["hbm_bytes"] / 819e9 == pytest.approx(5.03e-3, rel=0.01)
    flops = shapes.window_flops([(33_000, 1)], [(32_768, 32_800)], 1, sizes)
    dense = 6 * (18_874_368 + 2_260_992 + 262_144)
    assert flops > 2 * (dense + 6 * 8 * 4_718_592) * 33


def test_the_backlog_is_the_same_work_for_every_seed():
    from benchmark.traffic.shared_prefix_backlog import make_documents, \
        make_requests
    mix = dict(Manifest(REPO).mix("docqa_backlog"), document_tokens=128)
    assert Manifest(REPO).mix("docqa_backlog")["document_tokens"] == 32_768
    docs_a = make_documents(mix, 151_936, 1)
    docs_b = make_documents(mix, 151_936, 2**31 + 5)
    assert docs_a.shape == (6, 128) and (docs_a != docs_b).any()
    a = make_requests(mix, docs_a, 151_936, 1)
    b = make_requests(mix, docs_b, 151_936, 2**31 + 5)
    pairs = lambda rs: sorted((len(p), n) for p, n in rs)  # noqa: E731
    assert len(a) == 64 and pairs(a) == pairs(b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    assert all(128 + 16 <= len(p) <= 128 + 448 and 32 <= n <= 384
               for p, n in a)
    assert all(any((p[:128] == d).all() for d in docs_a) for p, _ in a)


def test_step_phases_are_read_from_the_step_programs_alone():
    """Own times by scope, of operations whose path starts with the step
    program's name; a prefill under the same scope is left out."""
    ops = [("while", "jit(_step)/M/layer_0/lm.experts/while", 0.0, 100.0),
           ("dot", "jit(_step)/M/layer_0/lm.experts/while/body/dot", 10.0,
            40.0),
           ("sort", "jit(_step)/M/layer_0/lm.select/sort", 100.0, 150.0),
           ("copy", "jit(_step)/M/copy", 150.0, 160.0),
           ("dot", "jit(_prefill)/M/layer_0/lm.experts/dot", 200.0, 900.0)]
    got = lm_phase_times.reduce_chips([ops])
    assert got == pytest.approx({"lm.experts": 100e-9, "lm.select": 50e-9})
    assert lm_phase_times.reduce_chips([[ops[-1]]]) is None
    assert lm_phase_times.reduce_chips([[]]) is None


# ---------------------------------------------------------------- the readers

@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_none(name):
    """Without a trace, and on a program that lacks the scopes and the
    counters (the parent commit): ``None``, never an exception."""
    from benchmark import measure

    class Bare:
        manifest = Manifest(REPO)
        platform = "cpu"
        config = manifest.config(CONFIG)
        cell = manifest.cell(CELL)
        trace_summary = None
        peaks = None
        facts = {}
        spans = measure.Spans()
        window_start_s = window_end_s = 0.0

        def counter(self, family, **labels):
            return None

        def histogram(self, family, **labels):
            return None

        def device_busy_s(self):
            return None

        def note(self, text):
            pass

    reader = Manifest(REPO).module("layer_metrics", name)
    assert reader.read(Bare()) is None
    traced = Bare()
    traced.device_busy_s = lambda: 0.5
    traced.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    traced.counter = lambda family, **labels: 0.0
    assert reader.read(traced) is None


def test_the_references_selection_is_lax_top_k_with_its_ties():
    """The reference finds the selected set by counting, not by sorting:
    it is ``lax.top_k``'s set, equal scores to the lower position."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    scores = np.round(rng.standard_normal((16, 40)) * 2) / 2    # many ties
    scores[:, 30:] = -np.inf
    scores[3, :] = 0.0
    scores[4, 0] = -0.0
    for k in (1, 8, 29, 30, 35):
        got = np.asarray(reference.top_k_set(jnp.asarray(scores, jnp.float32),
                                             k))
        _, at = jax.lax.top_k(jnp.asarray(scores, jnp.float32), k)
        want = np.zeros_like(got)
        want[np.arange(16)[:, None], np.asarray(at)] = True
        finite = scores > -np.inf
        assert (got & finite == want & finite).all(), k
        assert (got.sum(1) >= np.minimum(k, 30)).all()
