"""``models.linear_moe.LinearMoEDecoder`` (Kimi Delta Attention layers with a
recurrent state a slot, a gated grouped-query layer over pages, routed layers
holding a share of their experts beside a shared expert) at a tiny size on
the CPU, alone and through the decode engine: the chunk form against the
recurrence, the program against the benchmark's plain reference through
chunked joins and steps, slots that change hands (the reset by position),
the shares that add up, the state's size and gauge, and prefix sharing
refused."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import causal_lm_linear
from benchmark.families import linear_moe_lm_reference as reference
from mmlspark_tpu.models import linear_moe
from mmlspark_tpu.models.linear_moe import LinearMoEDecoder
from mmlspark_tpu.models.runner import ModelRunner

#: one whole period (G L L L); width 64, 4 query / 2 KV heads of 16, 4 linear
#: heads of 16, blocks of 8 in the chunk form, 16 experts top-4 of which 8
#: (ids 4-11) are held, pages of 4, prefill chunks of 16
LAYERS = ("full_attention", "linear_attention", "linear_attention",
          "linear_attention")
TINY = dict(vocab_size=256, embed_dim=64, num_heads=4, num_kv_heads=2,
            head_dim=16, layer_types=LAYERS, linear_heads=4,
            linear_head_dim=16, conv_kernel=4, chunk_block=8, num_experts=16,
            experts_per_token=4, expert_dim=32, shared_dim=32, first_expert=4,
            experts_held=8, routed_scale=1.0, rms_eps=1e-5, max_len=512)
RULE = {"std": 0.08, "bias_std": 0.05, "scale_range": [0.5, 1.5],
        "by_name": {"A_log": ["log_uniform", 1, 16],
                    "dt_bias": ["inverse_softplus_log_uniform", 0.001, 0.1],
                    "conv": ["uniform", -0.5, 0.5]}}
PAGE, CHUNK = 4, 16
#: float32 on both sides, the products at "highest": the largest difference
#: over the largest logit read 2.4e-6 (CPU, seed 3); the served tokens are the
#: reference's best with a gap of 0
F32_TOLERANCE = 1e-5
#: the chunk form against the recurrence one position at a time, float32:
#: read 3.3e-7 over outputs of about 1, with decays down to exp(-20) a
#: position; the tolerance leaves rounding room of a float32 sum of 8-term
#: products over a block, and a wrong term moves the output by its own size
CHUNK_TOLERANCE = 1e-5


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _forward(**changes):
    kw = dict(layer_types=LAYERS, num_heads=4, num_kv_heads=2, head_dim=16,
              linear_heads=4, linear_head_dim=16, experts_per_token=4,
              first_expert=4, routed_scale=1.0, eps=1e-5, query_block=8,
              context_step=16)
    return functools.partial(reference.linear_moe_forward,
                             **dict(kw, **changes))


def _model(seed=3, **changes):
    module = LinearMoEDecoder(dtype=jnp.float32, **dict(TINY, **changes))
    return module, causal_lm_linear.make_variables(module, seed, "float32",
                                                   RULE)


def _relative(got, want):
    return float(np.abs(np.asarray(got, np.float32)
                        - np.asarray(want, np.float32)).max()
                 / np.abs(np.asarray(want)).max())


def _engine(module, variables, name, longest=48, new=24, slots=3, **kw):
    runner = ModelRunner(module=module, variables=variables, name=name)
    return runner, runner.decode_stream(
        slots=slots, prompt_bucket=CHUNK, max_prompt_len=longest,
        max_new_tokens=new, page_size=PAGE, **kw)


def _drain(dec):
    while dec.step():
        pass


def _gap_below_best(forward, variables, handle):
    """The widest gap by which a served token's logit lies below the
    reference's best at its row, over the handle's whole answer."""
    seq = np.concatenate([handle.prompt, handle.tokens[:-1]]).astype(np.int32)
    logits = np.asarray(forward(variables, seq))[len(handle.prompt) - 1:]
    got = logits[np.arange(len(handle.tokens)), handle.tokens]
    return float((logits.max(-1) - got).max() / np.abs(logits).max())


# ---------------------------------------- the chunk form and the recurrence

def _inputs(seed, B=2, L=37, H=3, d=8, strength=1.0):
    rng = np.random.default_rng(seed)

    def f(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q = linear_moe._l2norm(f(B, L, H, d)) / np.sqrt(d)
    k = linear_moe._l2norm(f(B, L, H, d))
    g = -jnp.abs(f(B, L, H, d)) * strength
    beta = linear_moe.kda_beta(f(B, L, H))
    return q, k, f(B, L, H, d), g, beta, f(B, H, d, d)


def _recurrence(q, k, v, g, beta, S):
    outs = []
    for t in range(q.shape[1]):
        o, S = linear_moe.kda_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                   beta[:, t], S)
        outs.append(o)
    return jnp.stack(outs, 1), S


@pytest.mark.parametrize("strength,L,block", [
    (1.0, 37, 8), (20.0, 37, 8), (1.0, 16, 16), (60.0, 9, 4)])
def test_the_chunk_form_is_the_recurrence(strength, L, block):
    """Decays of up to exp(-60) a position (a cumulative exponent that the
    factored form would have to raise to e^+1000), lengths on and off a
    block boundary."""
    args = _inputs(int(strength) + L, L=L, strength=strength)
    want_o, want_S = _recurrence(*args)
    got_o, got_S = linear_moe.kda_chunked(*args, block)
    assert np.isfinite(np.asarray(got_o)).all()
    assert _relative(got_o, want_o) < CHUNK_TOLERANCE
    assert _relative(got_S, want_S) < CHUNK_TOLERANCE


def test_the_step_is_the_equations_as_written():
    """``kda_step``'s two reads of the state against the update written
    out: S = Diag(e^g) S; S += beta k (v - S^T k)^T; o = S^T q."""
    q, k, v, g, beta, S = _inputs(4, L=1)
    q, k, v, g, beta = (a[:, 0] for a in (q, k, v, g, beta))
    Sd = jnp.exp(g)[..., None] * S
    u = beta[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, Sd))
    want_S = Sd + k[..., None] * u[..., None, :]
    want_o = jnp.einsum("bhk,bhkv->bhv", q, want_S)
    o, S2 = linear_moe.kda_step(q, k, v, g, beta, S)
    assert _relative(o, want_o) < CHUNK_TOLERANCE
    assert _relative(S2, want_S) < CHUNK_TOLERANCE


def test_rows_past_the_last_real_row_leave_the_state_as_that_row_left_it():
    """A prefill chunk of 16 rows of which 11 are real (``logits_at``), off
    the block boundary of 8, leaves the same state and conv history as a
    chunk of those 11 rows alone; the next chunk then agrees."""
    module, variables = _model()
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 256, (1, 16)),
                       jnp.int32)
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    rows = jnp.zeros(1, jnp.int32)

    @jax.jit
    def prefill(tokens, last):
        cache = (module.init_paged_cache(9, PAGE), module.init_slot_state(1))
        L = tokens.shape[1]
        return module.apply(
            variables, tokens, positions=jnp.arange(L)[None],
            kv_cache=cache, page_table=table, logits_at=jnp.asarray([last]),
            cache_rows=rows)

    padded = toks.at[0, 11:].set(7)
    logits_a, (_, state_a) = prefill(padded, 10)
    logits_b, (_, state_b) = prefill(toks[:, :11], 10)
    assert _relative(logits_a, logits_b) < CHUNK_TOLERANCE
    for a, b in zip(jax.tree_util.tree_leaves(state_a),
                    jax.tree_util.tree_leaves(state_b)):
        assert _relative(a[0], b[0]) < CHUNK_TOLERANCE


# -------------------------------------------- against the plain reference

@pytest.mark.parametrize("seed", [1, 3])
def test_the_whole_forward_pass_matches_the_reference(seed):
    module, variables = _model(seed)
    toks = np.random.default_rng(seed).integers(0, 256, 40).astype(np.int32)
    want = _forward()(variables, toks)
    got = jax.jit(module.apply)(variables, toks[None])[0]
    assert _relative(got, want) < F32_TOLERANCE
    some = _forward()(variables, toks, rows=(20, 9))
    assert _relative(some, want[20:29]) < F32_TOLERANCE


# ------------------------------------------------- through the decode engine

@pytest.fixture(scope="module")
def served():
    """Prompts of one to three prefill chunks, answers of 24 steps, three
    slots; then two shorter requests into slots that were left."""
    with jax.default_matmul_precision("highest"):
        module, variables = _model()
        rng = np.random.default_rng(11)
        runner, dec = _engine(module, variables, "lm.served")
        dec.warmup()
        first = [dec.submit(rng.integers(0, 256, n).astype(np.int32),
                            max_new_tokens=24) for n in (5, 33, 48)]
        _drain(dec)
        later = [dec.submit(rng.integers(0, 256, n).astype(np.int32),
                            max_new_tokens=12) for n in (16, 3)]
        _drain(dec)
        reg, pool = runner.registry, dec.pool
        out = dict(
            module=module, variables=variables, handles=first + later,
            slots=[h.slot for h in first + later],
            state_bytes=pool.slot_state_nbytes(),
            page_bytes=pool.page_nbytes(), pages_left=pool.pages_in_use(),
            chunks=reg.family("mmlspark_runner_prefill_chunks_total")
            .labels(runner="lm.served").value,
            local=reg.family("mmlspark_runner_moe_local_assignments_total")
            .labels(runner="lm.served").value,
            gauge=reg.family("mmlspark_runner_slot_state_bytes")
            .labels(runner="lm.served", page_size=str(PAGE)).value,
            steps=dec.steps, debug=dec.debug_state())
        dec.close()
        return out


def test_chunked_joins_and_steps_serve_the_references_best_tokens(served):
    """Prefill in chunks of 16 (1, 3 and 3 of them, the state carried across
    chunk boundaries) then 23 steps a request: every served token is the
    reference's best at its row, to rounding."""
    assert [h.status for h in served["handles"]] == ["ok"] * 5
    assert served["chunks"] == 1 + 3 + 3 + 1 + 1
    for h in served["handles"][:3]:
        assert _gap_below_best(_forward(), served["variables"], h) \
            < F32_TOLERANCE


def test_a_slot_taken_again_starts_from_a_zero_state(served):
    """The two later requests took slots that had served 33 and 48 prompt
    tokens and 24 steps: their state rows and conv histories still held
    those requests' when they joined, and the reset by position (a first
    position of 0) gives the fresh reference's tokens."""
    assert set(served["slots"][3:]) <= set(served["slots"][:3])
    assert served["pages_left"] == 0
    for h in served["handles"][3:]:
        assert _gap_below_best(_forward(), served["variables"], h) \
            < F32_TOLERANCE


def test_the_state_its_gauge_and_the_pages(served):
    # 3 linear layers x 4 rows: a float32 state of 4 x 16 x 16 and a
    # float32 conv history of 3 x 3 x 64
    state = 3 * 4 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    assert served["state_bytes"] == served["gauge"] == state
    assert served["debug"]["pool"]["slot_state_bytes"] == state
    # a page holds K and V of the ONE full layer
    assert served["page_bytes"] == PAGE * 2 * 32 * 4
    assert 0 < served["local"] < served["steps"] * 3 * 4 * 4


def test_prefix_sharing_over_recurrent_state_is_refused_by_name():
    module, variables = _model()
    runner = ModelRunner(module=module, variables=variables, name="lm.prefix")
    with pytest.raises(ValueError, match="recurrent state"):
        runner.decode_stream(slots=2, prompt_bucket=CHUNK, max_new_tokens=8,
                             page_size=PAGE, prefix_cache=True)
    with pytest.raises(ValueError, match="recurrent state"):
        runner.decode(np.zeros((1, 8), np.int32), max_new_tokens=4,
                      kv_layout="paged", page_size=PAGE, prefix_cache=True)


def test_step_by_hand_and_the_start_thread_serve_the_same_tokens():
    """The ``start()`` thread keeps a step in flight, so a row that has left
    advances its slot's state once more before the next request joins it:
    the reset by position makes that harmless, and both drivers serve the
    same tokens."""
    from tests.decode_drivers import serve_through_both_drivers
    module, variables = _model()
    rng = np.random.default_rng(42)
    requests = [(rng.integers(0, 256, int(rng.integers(3, 41))).astype(
        np.int32), int(rng.integers(3, 17))) for _ in range(7)]
    serve_through_both_drivers(
        lambda name, eos: _engine(module, variables, f"lm.flight.{name}",
                                  longest=48, new=16, eos_id=eos),
        requests)


# ------------------------------------------------- the share of the experts

def test_the_eight_shares_and_the_shared_expert_once_add_up():
    """Eight chips hold two experts each of a layer of 16: each share's
    block output less the block with its routed part zeroed (attention, the
    shared expert and the residual, what every chip computes alike) summed,
    plus that common part ONCE, is the block holding all 16."""
    def block(first, held):
        return linear_moe.LinearMoEBlock(
            False, 4, 2, 16, 4, 16, 4, 8, 16, 4, 32, 32, first, held, 1.0,
            1e-5, jnp.float32)

    x = jnp.asarray(np.random.default_rng(8).standard_normal((2, 12, 64)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
    whole = block(0, 16)
    params = whole.init(jax.random.PRNGKey(5), x, pos)
    params["params"]["router"]["bias"] = jnp.asarray(
        np.random.default_rng(9).standard_normal(16) * 0.05, jnp.float32)
    uncut = jax.jit(whole.apply)(params, x, pos)[0]
    experts = params["params"]["experts"]

    def share(rank, zero=False):
        p = jax.tree_util.tree_map(lambda a: a, params)
        p["params"]["experts"] = {
            n: (0.0 if zero else 1.0) * w[2 * rank:2 * rank + 2]
            for n, w in experts.items()}
        return jax.jit(block(2 * rank, 2).apply)(p, x, pos)[0]

    common = share(0, zero=True)
    parts = [share(r) - common for r in range(8)]
    total = sum(parts) + common
    assert _relative(total, uncut) < F32_TOLERANCE
    # the common part on every chip and summed would count eight times
    assert _relative(sum(p + common for p in parts), uncut) > 0.1
