"""The generative path's yardstick at a tiny size on the CPU: the
``causal_lm`` family and its plain reference, the ``backlog_stream`` traffic
kind, the cell end to end, the control and the faults that must come out as
not correct, the arithmetic behind the shares, and the readers."""
import functools
import json

import numpy as np
import pytest

from benchmark import shapes
from benchmark.families import causal_lm, causal_lm_reference as reference
from benchmark.harness import run_cell
from benchmark.manifest import Manifest

from perfbench_tiny import REPO, copy_benchmark, edit_json

CELL = "gpt2xl-generate-backlog"
READERS = ["decode.step_ms", "decode.join_ms", "decode.host_ms_per_step",
           "decode.live_row_share", "kv.pages_in_use_share",
           "decode_step_roofline", "lm_window_mfu"]
#: 2 layers, 64 wide, 4 heads of 16; weights four times GPT-2's initial
#: scale, so that so small a model's logits spread like a large one's
TINY_KWARGS = dict(vocab_size=512, num_classes=512, embed_dim=64, num_heads=4,
                   num_layers=2, mlp_dim=256, max_len=64, causal=True,
                   pool="none")
TINY_RULE = {"std": 0.08, "bias_std": 0.02, "scale_range": [0.5, 1.5]}
#: the mean served gap at this size over 64 positions: bf16 reads 0 to
#: 0.00024, the float8 control 0.0042-0.014 (CPU, seeds 1 to 6); float32 reads 0
TINY_GAP_LIMIT = 0.001
FORWARD = functools.partial(reference.gpt2_forward, num_heads=4, num_layers=2,
                            eps=1e-6)


def tiny_lm_root(dst, dtype="float32"):
    root = copy_benchmark(dst)
    edit_json(root, "benchmark/configs/gpt2-xl-bf16.json",
              model={"kwargs": dict(TINY_KWARGS, dtype=dtype)},
              sizes={"layers": 2, "width": 64, "heads": 4, "mlp": 256,
                     "vocab": 512, "positions": 64},
              weights=TINY_RULE,
              engine={"slots": 4, "page_size": 8, "prompt_bucket": 32,
                      "max_new_tokens": 16},
              reference={"kwargs": {"num_heads": 4, "num_layers": 2,
                                    "eps": 1e-6},
                         "served_gap_mean_limit": TINY_GAP_LIMIT})
    # a step takes half a millisecond here, less than reading the counters
    edit_json(root, "benchmark/workloads/generate_backlog.json",
              prompt_tokens={"median": 12, "sigma": 0.8, "min": 2, "max": 32},
              answer_tokens={"median": 8, "sigma": 0.7, "min": 2, "max": 16},
              requests=48, ramp_seconds=3, trace_seconds=1,
              count_gap_steps=1000)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_lm_root(tmp_path_factory.mktemp("bench_lm"))


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

@pytest.mark.parametrize("traced", [False, True])
def test_the_tiny_decode_cell_runs_and_prints_the_contracts_line(root, traced):
    result = run_cell(root, CELL, seed=2**31 + 7, seconds=1.0, trace=traced,
                      platform="cpu")
    line = json.loads(json.dumps(result))
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] >= 4
    assert list(line)[-1] == "checks"            # last, beside their limits
    assert line["checks"]["served_gap_mean"]["value"] <= TINY_GAP_LIMIT
    for name in ("requests_not_ok", "answers_of_wrong_length",
                 "pool_pages_left_in_use"):
        assert line["checks"][name] == {"value": 0.0, "limit": 0.0}
    m = line["metrics"]
    if traced:
        assert m["compile.in_window"]["value"] == 0
        assert m["rebuilds.in_window"]["value"] == 0
        assert 0 < m["decode.live_row_share"]["value"] <= 100
        assert 0 < m["kv.pages_in_use_share"]["value"] <= 100
        assert m["decode.host_ms_per_step"]["value"] > 0
        # no device: no program times, no peaks, so no device share
        assert not {"decode.step_ms", "decode.join_ms", "lm_window_mfu",
                    "decode_step_roofline"} & set(m)
        gaps = dict(line["breakdown"]["idle_gaps"])
        assert "wait_for_slot" in gaps or "submit" in gaps
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    else:
        assert set(m) == {"tokens_per_s", "setup_s"}
        assert m["tokens_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0


def test_the_family_builds_its_module_from_the_configuration_alone():
    """``causal_lm.py`` names no model: the dotted factory and its arguments
    are the configuration's."""
    source = open(causal_lm.__file__).read()
    assert "TransformerEncoder" not in source and "gpt2" not in source.lower()
    cfg = Manifest(REPO).config("gpt2-xl-bf16")
    module = causal_lm.make_module(cfg["model"])
    assert type(module).__name__ == "TransformerEncoder"
    assert (module.num_layers, module.embed_dim, module.num_heads,
            module.mlp_dim, module.vocab_size, module.max_len) == \
        (cfg["n_layer"], cfg["n_embd"], cfg["n_head"], cfg["n_inner"],
         cfg["vocab_size"], cfg["n_positions"])
    assert cfg["reference"]["kwargs"]["eps"] == cfg["layer_norm_epsilon"]
    assert Manifest(REPO).problems() == []


# ------------------------------- the paged path against the plain reference

def _decode_against_reference(dtype, seed, rounding=None):
    """Prefill and 15 steps through the paged cache for four ragged prompts;
    per position, relative L2 of the program's logits (or, with
    ``rounding``, of the reference's own pass at that lower precision)
    against the float32 reference's pass over prompt + answer."""
    from mmlspark_tpu.models.runner import ModelRunner
    module = causal_lm.make_module(
        {"factory": "mmlspark_tpu.models.TransformerEncoder",
         "kwargs": dict(TINY_KWARGS, dtype=dtype)})
    variables = causal_lm.make_variables(module, seed, dtype, TINY_RULE)
    runner = ModelRunner(module=module, variables=variables,
                         name=f"parity.{dtype}.{seed}")
    rng = np.random.default_rng(seed)
    lens = np.array([5, 19, 32, 11])
    prompts = np.zeros((4, 32), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = rng.integers(0, 512, n)
    out = runner.decode(prompts, lengths=lens, max_new_tokens=16,
                        collect_logits=True, kv_layout="paged", page_size=8,
                        prompt_bucket=32)
    worst, served = 0.0, []
    for i, n in enumerate(lens):
        seq = np.zeros(48, np.int32)
        seq[:n] = prompts[i, :n]
        seq[n:n + 15] = out.tokens[i, :-1]
        want = np.asarray(FORWARD(variables, seq))[n - 1:n + 15]
        got = out.logits[i] if rounding is None else np.asarray(
            FORWARD(variables, seq, rounding=rounding))[n - 1:n + 15]
        worst = max(worst, float(reference.relative_l2(got, want).max()))
        served.append((prompts[i, :n], [int(t) for t in out.tokens[i]]))
    return worst, variables, served


#: relative L2 per position at this size: float32 reads 6e-7 to 7e-7, bf16
#: 0.011-0.012, the float8 control 0.10-0.15 (CPU, seeds 1 to 6)
F32_TOLERANCE, BF16_TOLERANCE = 1e-5, 0.04


def test_paged_prefill_and_decode_match_the_reference_in_float32():
    worst, variables, served = _decode_against_reference("float32", 1)
    assert worst < F32_TOLERANCE
    got = reference.check_served(FORWARD, variables, served, pad_to=48)
    assert got["served_gap_max"] == 0.0 and got["positions"] == 64


def test_paged_prefill_and_decode_match_the_reference_in_bf16():
    worst, variables, served = _decode_against_reference("bfloat16", 1)
    assert F32_TOLERANCE < worst < BF16_TOLERANCE
    got = reference.check_served(FORWARD, variables, served, pad_to=48)
    assert got["served_gap_mean"] < TINY_GAP_LIMIT


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_at_a_lower_precision_fails_both_comparisons(seed):
    """The control of "How correct is decided": the reference with its matmul
    operands in float8, put in the program's place, is over the tolerance on
    logits and over the limit on the mean served gap, on three seeds."""
    worst, variables, served = _decode_against_reference("bfloat16", seed,
                                                         rounding="fp8")
    assert worst > BF16_TOLERANCE
    got = reference.check_served(FORWARD, variables, served, pad_to=48,
                                 control="fp8")
    assert got["control_gap_mean"] > 3 * TINY_GAP_LIMIT
    assert got["served_gap_mean"] < TINY_GAP_LIMIT


# ------------------------------------- faults under the harness: not correct

def _run_broken(tmp_path, monkeypatch, breakage):
    import mmlspark_tpu.models.runner as runner_mod
    breakage(monkeypatch, runner_mod)
    return run_cell(tiny_lm_root(tmp_path), CELL, seed=5, seconds=0.5,
                    trace=False, platform="cpu")


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tmp_path, monkeypatch):
    def alter(monkeypatch, runner_mod):
        plain = runner_mod._greedy_freeze

        def off_by_one(logits, finished, eos_id):
            tok, finished = plain(logits, finished, eos_id)
            return (tok + 1) % logits.shape[-1], finished
        monkeypatch.setattr(runner_mod, "_greedy_freeze", off_by_one)
    result = _run_broken(tmp_path, monkeypatch, alter)
    assert result["correct"] is False
    assert result["checks"]["served_gap_mean"]["value"] > 3 * TINY_GAP_LIMIT
    assert any("served_gap_mean" in f for f in result["failures"])


def test_an_answer_cut_short_is_not_correct(tmp_path, monkeypatch):
    def cut(monkeypatch, runner_mod):
        plain = runner_mod.ContinuousDecoder.submit

        def submit(self, prompt, *, max_new_tokens=None, **kw):
            return plain(self, prompt,
                         max_new_tokens=max(1, max_new_tokens - 1), **kw)
        monkeypatch.setattr(runner_mod.ContinuousDecoder, "submit", submit)
    result = _run_broken(tmp_path, monkeypatch, cut)
    assert result["correct"] is False
    assert result["checks"]["answers_of_wrong_length"]["value"] > 0


# --------------------------------------------- the arithmetic of the shares

def test_gpt2_xl_parameters_and_cache_bytes_by_hand():
    p = shapes.causal_lm_params(48, 1600, 6400, 50257, 1024)
    layer = 12 * 1600 ** 2 + 13 * 1600
    assert p["total"] == 48 * layer + 50257 * 1600 + 1024 * 1600 \
        + 2 * 1600 + 1600 * 50257 + 50257 == 1_638_072_657
    assert p["matmul"] == 48 * 12 * 1600 ** 2 + 1600 * 50257 == 1_554_971_200
    tied = shapes.causal_lm_params(48, 1600, 6400, 50257, 1024, False)
    assert p["total"] - tied["total"] == 80_461_457     # the untied head
    assert shapes.kv_bytes_per_token(48, 1600, 2) == 307_200
    need = shapes.causal_lm_need(Manifest(REPO).config("gpt2-xl-bf16")["sizes"])
    assert need["param_bytes"] == 2 * 1_638_072_657
    assert need["kv_token_bytes"] == 307_200


def test_the_least_bytes_and_operations_of_steps_and_prefills_by_hand():
    # one step of two sequences, 100 and 300 positions attended to
    least = shapes.decode_steps_least_bytes(1, 400, 3_276_145_314, 307_200)
    assert least == 3_276_145_314 + 400 * 307_200
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops = shapes.causal_lm_flops(1_554_971_200, 2, 400, 48, 1600)
    assert flops == 2 * 1_554_971_200 * 2 + 4 * 48 * 1600 * 400
    seconds, binds = shapes.least_s(flops, least, peaks)
    assert binds == "HBM bandwidth"
    assert seconds == pytest.approx(least / 819e9) == pytest.approx(4.15e-3,
                                                                    rel=0.01)
    # a prompt of 512 attends to 1 + 2 + ... + 512 positions, and binds on compute
    assert shapes.prefill_attended_positions(512) == 512 * 513 / 2
    flops = shapes.causal_lm_flops(1_554_971_200, 512,
                                   shapes.prefill_attended_positions(512),
                                   48, 1600)
    assert shapes.least_s(flops, 3_276_145_314, peaks)[1] == "bf16 compute"
    assert flops == pytest.approx(1.63e12, rel=0.01)


def test_the_backlog_is_the_same_work_for_every_seed():
    from benchmark.traffic.backlog_stream import make_requests
    mix = Manifest(REPO).mix("generate_backlog")
    a = make_requests(mix, 50257, 1)
    b = make_requests(mix, 50257, 2**31 + 5)
    pairs = lambda rs: sorted((len(p), n) for p, n in rs)  # noqa: E731
    assert pairs(a) == pairs(b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    assert all(16 <= len(p) <= 512 and 8 <= n <= 256 for p, n in a)
    assert all(0 <= p.min() and p.max() < 50257 for p, _ in a)


# ---------------------------------------------------------------- the readers

@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_none(name):
    """Without a trace, and with a trace in which no step ran."""
    from benchmark import measure

    class Bare:
        manifest = Manifest(REPO)
        platform = "cpu"
        config = manifest.config("gpt2-xl-bf16")
        cell = manifest.cell(CELL)
        trace_summary = None
        peaks = None
        facts = {}
        spans = measure.Spans()
        window_start_s = window_end_s = 0.0

        def counter(self, family, **labels):
            return 0.0

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
    assert reader.read(traced) is None
