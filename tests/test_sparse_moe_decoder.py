"""``models.SparseMoEDecoder`` (a routed expert layer and learned sparse
attention) at a tiny size on the CPU, alone and through the decode engine:
against the benchmark's plain reference, against dense grouped-query
attention, the expert layer against a per-token loop, long prompts joined in
chunks, prefix hits, copy-on-write over all three slabs, and the keys of the
executables that existing callers build (ISSUE 34)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import causal_lm
from benchmark.families import sparse_moe_lm_reference as reference
from mmlspark_tpu.models import TransformerEncoder
from mmlspark_tpu.models import sparse_moe
from mmlspark_tpu.models.runner import ModelRunner
from mmlspark_tpu.models.sparse_moe import SparseMoEDecoder

#: width 64, 2 layers, 8 experts top-2, 4 query / 2 KV heads of 16, an
#: indexer of 2 heads x 16, topk 8, pages of 4
SIZES = dict(num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
             experts_per_token=2, index_heads=2, index_dim=16, index_topk=8)
TINY = dict(SIZES, vocab_size=512, embed_dim=64, num_experts=8, expert_dim=32,
            rope_theta=1e4, max_len=256)
RULE = {"std": 0.08, "bias_std": 0.02, "scale_range": [0.5, 1.5]}
PAGE = 4
#: float32 on both sides, the program's products at "highest": the largest
#: difference over the largest logit read 5e-7 (CPU, seeds 1 to 3)
F32_TOLERANCE = 1e-5


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _forward(**changes):
    return functools.partial(
        reference.sparse_moe_forward, rope_theta=1e4, eps=1e-6, query_block=8,
        context_step=16,
        **dict(SIZES, **changes))


def _model(seed=3, **changes):
    module = SparseMoEDecoder(dtype=jnp.float32, **dict(TINY, **changes))
    return module, causal_lm.make_variables(module, seed, "float32", RULE)


def _relative(got, want):
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.abs(np.asarray(want)).max())


def _paged_logits(module, variables, toks, chunk, prefilled):
    """Logits of every position: ``prefilled`` positions through the paged
    cache in chunks of ``chunk``, the rest one decode step a token."""
    pages = -(-len(toks) // PAGE)
    cache = module.init_paged_cache(pages + 1, PAGE)
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    out = []
    for at in list(range(0, prefilled, chunk)) + list(
            range(prefilled, len(toks))):
        n = chunk if at < prefilled else 1
        logits, cache = module.apply(
            variables, toks[None, at:at + n],
            positions=jnp.arange(at, at + n, dtype=jnp.int32)[None],
            kv_cache=cache, page_table=table)
        out.append(np.asarray(logits)[0])
    return np.concatenate(out)


# -------------------------------------------- against the plain reference

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_chunked_prefill_then_decode_matches_the_reference(seed):
    """(a) Three prefill chunks of 8, then 16 decode steps through the paged
    cache, to a context of 5 x topk."""
    module, variables = _model(seed)
    toks = np.random.default_rng(seed).integers(0, 512, 40).astype(np.int32)
    want = _forward()(variables, toks)
    got = _paged_logits(module, variables, toks, chunk=8, prefilled=24)
    assert _relative(got, want) < F32_TOLERANCE
    # and the whole sequence in one call without a cache
    assert _relative(module.apply(variables, toks[None])[0], want) \
        < F32_TOLERANCE


@pytest.mark.parametrize("path", ["cached", "whole"])
def test_with_topk_over_the_context_the_block_is_dense_grouped_attention(path):
    """(b) ``index_topk`` >= context selects every position: plain
    grouped-query attention, which the reference computes with no selection
    at all; at topk 8 the same weights give other logits."""
    module, variables = _model(index_topk=64)
    toks = np.random.default_rng(5).integers(0, 512, 40).astype(np.int32)
    dense = _forward(index_topk=10 ** 6)(variables, toks)
    got = _paged_logits(module, variables, toks, 8, 24) if path == "cached" \
        else module.apply(variables, toks[None])[0]
    assert _relative(got, dense) < F32_TOLERANCE
    sparse = _forward()(variables, toks)
    assert _relative(sparse[:8], dense[:8]) < F32_TOLERANCE   # context <= 8
    assert _relative(sparse, dense) > 100 * F32_TOLERANCE


def test_the_selection_mask_is_lax_top_k_with_its_ties():
    """Equal scores go to the lower position, as ``lax.top_k`` takes them;
    ``-inf`` is never selected."""
    scores = jnp.asarray([[1., 0., 0., 2., 0., -jnp.inf, 0., 3.],
                          [0., 0., 0., 0., 0., 0., 0., 0.],
                          [5., -jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf,
                           -jnp.inf, -jnp.inf, -jnp.inf]])
    mask = np.asarray(sparse_moe._topk_mask(scores, 4))
    _, at = jax.lax.top_k(scores, 4)
    want = np.zeros_like(mask)
    want[np.arange(3)[:, None], np.asarray(at)] = True
    want &= np.asarray(scores) > -np.inf
    assert (mask == want).all()
    assert mask.sum(1).tolist() == [4, 4, 1]


# ------------------------------------------------------- the expert layer

def _expert_loop(x, gate, up, down, ids, w):
    out = np.zeros_like(x, dtype=np.float64)
    for t in range(x.shape[0]):
        for e, we in zip(ids[t], w[t]):
            h = x[t] @ gate[e]
            out[t] += we * ((h / (1 + np.exp(-h))) * (x[t] @ up[e])) @ down[e]
    return out


ROUTINGS = {
    "random": lambda rng, T: np.stack(
        [rng.permutation(8)[:2] for _ in range(T)]),
    # every token to experts 5 and 2: experts 0, 1, 3, 4, 6, 7 have no token
    "all_to_one_pair": lambda rng, T: np.tile([5, 2], (T, 1)),
    # one expert takes every token's first choice, more rows than a block
    "one_hot_expert": lambda rng, T: np.stack(
        [np.array([3, rng.choice([0, 1, 2, 4, 5, 6, 7])]) for _ in range(T)]),
}


@pytest.mark.parametrize("tokens,block", [(3, 64), (24, 8), (24, 64)])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_expert_layer_is_the_per_token_loop(routing, tokens, block):
    """(c) No token dropped whatever the imbalance, an expert without a token
    is skipped, groups longer than a block take several."""
    rng = np.random.default_rng(7)
    x = rng.standard_normal((tokens, 16)).astype(np.float32)
    gate, up = (rng.standard_normal((8, 16, 12)).astype(np.float32) * 0.3
                for _ in range(2))
    down = rng.standard_normal((8, 12, 16)).astype(np.float32) * 0.3
    ids = ROUTINGS[routing](rng, tokens).astype(np.int32)
    w = rng.uniform(0.1, 1.0, ids.shape).astype(np.float32)
    got, touched = sparse_moe.routed_experts(
        jnp.asarray(x), jnp.asarray(gate), jnp.asarray(up), jnp.asarray(down),
        jnp.asarray(ids), jnp.asarray(w), block_rows=block)
    want = _expert_loop(x, gate, up, down, ids, w)
    assert _relative(got, want) < F32_TOLERANCE
    assert int(touched) == len(np.unique(ids))


# ------------------------------------------------------ through the engine

def _engine(module, variables, name, *, chunk=8, longest=48, new=8,
            prefix=False, slots=3):
    runner = ModelRunner(module=module, variables=variables, name=name)
    pool = runner.page_pool(PAGE, num_pages=64) if prefix else None
    return runner, runner.decode_stream(
        slots=slots, prompt_bucket=chunk, max_prompt_len=longest,
        max_new_tokens=new, page_size=PAGE, pool=pool, prefix_cache=prefix)


def _serve(decoder, prompt, n):
    handle = decoder.submit(prompt, max_new_tokens=n)
    while not handle.done.is_set():
        decoder.step()
    assert handle.status == "ok"
    return handle


@pytest.fixture(scope="module")
def served():
    """One model, one prompt of 29 tokens (a 24-token document and a
    question), served four ways."""
    with jax.default_matmul_precision("highest"):
        module, variables = _model()
        rng = np.random.default_rng(11)
        doc = rng.integers(0, 512, 24).astype(np.int32)
        prompt = np.concatenate([doc, rng.integers(0, 512, 5)]).astype(
            np.int32)
        out = {"module": module, "variables": variables, "prompt": prompt}
        runner, dec = _engine(module, variables, "sm.chunked")
        out["chunked"] = _serve(dec, prompt, 8).tokens
        out["chunked_chunks"] = runner.registry.family(
            "mmlspark_runner_prefill_chunks_total").labels(
                runner="sm.chunked").value
        dec.close()
        _, dec = _engine(module, variables, "sm.one", chunk=32, longest=32)
        out["one_bucket"] = _serve(dec, prompt, 8).tokens
        dec.close()
        runner, dec = _engine(module, variables, "sm.prefix", prefix=True)
        _serve(dec, np.concatenate([doc, [7, 8, 9]]).astype(np.int32), 1)
        hit = _serve(dec, prompt, 8)
        out["hit"], out["hit_covered"] = hit.tokens, hit.covered
        out["prefix_runner"] = runner
        dec.close()
        return out


def test_a_long_prompt_joins_in_chunks_and_decodes_as_one_bucket_does(served):
    """(d) 29 tokens through the 8-token chunk are four prefill dispatches
    and the same greedy tokens as one 32-token bucket; both are the
    reference's best token at every position."""
    assert served["chunked_chunks"] == 4
    assert served["chunked"] == served["one_bucket"]
    seq = np.concatenate([served["prompt"], served["chunked"][:-1]]).astype(
        np.int32)
    best = np.asarray(_forward()(served["variables"], seq)).argmax(-1)
    assert served["chunked"] == best[len(served["prompt"]) - 1:].tolist()


def test_a_prefix_hit_on_a_multi_chunk_document_is_a_cold_join_bit_for_bit(
        served):
    """(d) The 24-token document was retained by an earlier request: the hit
    covers all of it, prefills the question alone, and yields the cold join's
    tokens exactly."""
    assert served["hit_covered"] == 24
    assert served["hit"] == served["chunked"]
    reg = served["prefix_runner"].registry
    tokens = reg.family("mmlspark_runner_prefill_tokens_total")
    assert tokens.labels(runner="sm.prefix", source="cached").value == 24
    assert tokens.labels(runner="sm.prefix", source="computed").value \
        == 27 + 5
    assert reg.family("mmlspark_runner_moe_experts_touched_total").labels(
        runner="sm.prefix").value > 0


def test_a_hit_that_ends_mid_page_splits_the_page_and_stays_exact(served):
    """A 22-token shared prefix ends in the middle of page 5: the write of
    the divergent suffix lands on a private copy (copy-on-write over all
    three slabs), and the tokens are the cold join's."""
    module, variables = served["module"], served["variables"]
    rng = np.random.default_rng(13)
    shared = rng.integers(0, 512, 22).astype(np.int32)
    second = np.concatenate([shared, rng.integers(0, 512, 9)]).astype(
        np.int32)
    runner, dec = _engine(module, variables, "sm.cow", prefix=True)
    _serve(dec, shared, 1)      # retained: five whole pages and a tail of 2
    hit = _serve(dec, second, 6)
    dec.close()
    assert hit.covered == 22
    assert runner.registry.family("mmlspark_prefix_cow_splits_total").labels(
        runner="sm.cow").value >= 1
    _, cold = _engine(module, variables, "sm.cow.cold")
    assert _serve(cold, second, 6).tokens == hit.tokens
    cold.close()


def test_copy_on_write_copies_every_slab_of_the_cache():
    """(e) k, v AND the indexer's keys of every layer."""
    module, variables = _model()
    runner = ModelRunner(module=module, variables=variables, name="sm.slabs")
    cache = module.init_paged_cache(6, PAGE)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.arange(s.size, dtype=s.dtype).reshape(s.shape), cache)
    assert [len(layer) for layer in cache] == [3, 3]
    before = jax.tree_util.tree_map(np.asarray, cache)
    after = runner._cow_executable()(cache, jnp.int32(2), jnp.int32(4))
    for old, new in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(after)):
        new = np.asarray(new)
        assert (new[4] == old[2]).all() and not (old[4] == old[2]).all()
        keep = [0, 1, 2, 3, 5]
        assert (new[keep] == old[keep]).all()


def test_a_prompt_over_the_longest_is_refused_and_the_table_follows_it():
    module, variables = _model()
    _, dec = _engine(module, variables, "sm.refuse", longest=40, new=8)
    assert dec.table_w == 12                      # (40 + 8) / 4
    with pytest.raises(ValueError, match="longest prompt"):
        dec.submit(np.zeros(41, np.int32))
    dec.close()
    with pytest.raises(ValueError, match="below the prefill chunk"):
        ModelRunner(module=module, variables=variables,
                    name="sm.short").decode_stream(
            prompt_bucket=8, max_prompt_len=4, page_size=PAGE)
    with pytest.raises(ValueError, match="paged layout only"):
        module.apply(variables, jnp.zeros((1, 4), jnp.int32),
                     positions=jnp.zeros((1, 4), jnp.int32),
                     kv_cache=module.init_paged_cache(4, PAGE))


# ----------------------------------------- what existing callers still build

def _gpt2_runner(name):
    module = TransformerEncoder(vocab_size=64, num_classes=64, embed_dim=32,
                                num_heads=2, num_layers=1, mlp_dim=64,
                                max_len=64, causal=True, pool="none")
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return ModelRunner(module=module, variables=variables, name=name)


@pytest.mark.parametrize("longest,table_w", [(None, 6), (40, 14)])
def test_existing_callers_build_the_same_executable_keys(longest, table_w):
    """(f) Without ``max_prompt_len`` a stream's executables are keyed as
    they were: (batch, prompt bucket, page size, table width of bucket + new
    tokens); with it only the table width moves."""
    runner = _gpt2_runner(f"sm.keys.{longest}")
    kw = {} if longest is None else {"max_prompt_len": longest}
    dec = runner.decode_stream(slots=2, prompt_bucket=8, max_new_tokens=16,
                               page_size=PAGE, **kw)
    dec.warmup()
    dec.close()
    assert dec.table_w == table_w
    on = runner._device_key()
    assert runner.compile_stats()["executables"] == sorted([
        f"prefill_paged/{on}/1/8/{PAGE}/{table_w}",
        f"prefill_paged/{on}/2/8/{PAGE}/{table_w}",
        f"sample/{on}/1/None",
        f"step_paged/{on}/1/{PAGE}/{table_w}/fused/None",
        f"step_paged/{on}/2/{PAGE}/{table_w}/fused/None"])


def test_the_prefill_head_runs_on_the_last_real_position_only():
    """``logits_at`` gives the rows the full table of logits holds there,
    for the GPT-2 block as for the new one."""
    rng = np.random.default_rng(17)
    toks = jnp.asarray(rng.integers(0, 64, (2, 8)), jnp.int32)
    at = jnp.asarray([2, 7], jnp.int32)
    runner = _gpt2_runner("sm.head")
    module, variables = runner.module, runner.variables
    whole = module.apply(variables, toks)
    one = module.apply(variables, toks, logits_at=at)
    assert one.shape == (2, 1, 64)
    np.testing.assert_allclose(one[:, 0], whole[jnp.arange(2), at],
                               rtol=1e-5, atol=1e-6)
    module, variables = _model()
    whole = module.apply(variables, toks)
    one = module.apply(variables, toks, logits_at=at)
    np.testing.assert_allclose(one[:, 0], whole[jnp.arange(2), at],
                               rtol=1e-5, atol=1e-6)


# ------------------------------------- the engine's two drivers (ISSUE 37)

def test_step_by_hand_and_the_start_thread_serve_the_same_tokens():
    """Questions over one 8-token document through the prefix cache, prompts
    of one and two prefill chunks, answers over page boundaries, an eos some
    answers hit: ``step()`` by hand and the ``start()`` thread (one step in
    flight) serve the same tokens, leave the same ids retained and every
    other page free, and mint no compile key after warm-up."""
    from tests.decode_drivers import serve_through_both_drivers
    module, variables = _model()
    rng = np.random.default_rng(37)
    doc = rng.integers(0, 512, 8).astype(np.int32)
    requests = [(np.concatenate(
        [doc, rng.integers(0, 512, int(rng.integers(2, 9)))]).astype(np.int32),
        int(rng.integers(3, 11))) for _ in range(8)]

    def make_engine(name, eos):
        runner = ModelRunner(module=module, variables=variables,
                             name=f"sm.flight.{name}")
        return runner, runner.decode_stream(
            slots=3, prompt_bucket=8, max_prompt_len=16, max_new_tokens=10,
            eos_id=eos, page_size=PAGE, prefix_cache=True,
            pool=runner.page_pool(PAGE, num_pages=96))

    runs = serve_through_both_drivers(make_engine, requests)
    assert runs["thread"]["retained"]
