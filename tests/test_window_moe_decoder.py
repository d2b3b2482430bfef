"""``models.window_moe.WindowMoEDecoder`` (window and full attention layers,
a dense MLP then routed layers holding a share of their experts beside a
shared expert) at a tiny size on the CPU, alone and through the decode
engine: against the benchmark's plain reference, window state that wraps and
slots that change hands, the shares that add up, a share nobody chose, the
expert layer with every expert held bit for bit as it was, the window state's
bound, and prefix sharing refused (ISSUE 36)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark.families import causal_lm
from benchmark.families import window_moe_lm_reference as reference
from mmlspark_tpu.models import sparse_moe, window_moe
from mmlspark_tpu.models.runner import ModelRunner, PagePool
from mmlspark_tpu.models.window_moe import WindowMoEDecoder

#: the published pattern's first five layers: the leading dense layer and one
#: LLLG period; width 64, 4 query / 2 KV heads of 16, window 8, 16 experts
#: top-4 of which 8 (ids 4-11) are held, pages of 4
KINDS = dict(
    layer_types=("sliding_attention", "sliding_attention",
                 "sliding_attention", "full_attention", "sliding_attention"),
    mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse"))
TINY = dict(KINDS, vocab_size=256, embed_dim=64, num_heads=4, num_kv_heads=2,
            head_dim=16, sliding_window=8, dense_dim=96, num_experts=16,
            experts_per_token=4, expert_dim=32, shared_dim=32,
            first_expert=4, experts_held=8, routed_scale=2.5, rope_theta=1e4,
            rms_eps=1e-5, max_len=512)
RULE = {"std": 0.08, "bias_std": 0.05, "scale_range": [0.5, 1.5]}
PAGE, WINDOW, CHUNK = 4, 8, 16
#: float32 on both sides, the program's products at "highest": the largest
#: difference over the largest logit read 1.3e-6 (CPU, seeds 1 to 3); the
#: same module in bfloat16 reads 0.37 (a flipped expert moves a logit by more
#: than rounding does) and fails it (tested below)
F32_TOLERANCE = 1e-5


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _forward(**changes):
    kw = dict(KINDS, num_heads=4, num_kv_heads=2, head_dim=16, window=WINDOW,
              experts_per_token=4, first_expert=4, routed_scale=2.5,
              rope_theta=1e4, eps=1e-5, query_block=8, context_step=16)
    return functools.partial(reference.window_moe_forward,
                             **dict(kw, **changes))


def _model(seed=3, dtype=jnp.float32, **changes):
    module = WindowMoEDecoder(dtype=dtype, **dict(TINY, **changes))
    return module, causal_lm.make_variables(
        module, seed, jnp.dtype(dtype).name, RULE)


def _relative(got, want):
    return float(np.abs(np.asarray(got, np.float32)
                        - np.asarray(want, np.float32)).max()
                 / np.abs(np.asarray(want)).max())


def _engine(module, variables, name, longest=64, new=40, slots=3, **kw):
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


# -------------------------------------------- against the plain reference

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_whole_forward_pass_matches_the_reference(seed):
    module, variables = _model(seed)
    toks = np.random.default_rng(seed).integers(0, 256, 48).astype(np.int32)
    want = _forward()(variables, toks)
    assert _relative(module.apply(variables, toks[None])[0], want) \
        < F32_TOLERANCE
    # rows alone, as the benchmark's comparison asks for them
    some = _forward()(variables, toks, rows=(20, 9))
    assert _relative(some, want[20:29]) < F32_TOLERANCE


def test_bfloat16_in_float32s_place_fails_the_tolerance():
    module, variables = _model(2)
    toks = np.random.default_rng(2).integers(0, 256, 48).astype(np.int32)
    want = _forward()(variables, toks)
    low, _ = _model(2, dtype=jnp.bfloat16)
    assert _relative(low.apply(variables, toks[None])[0], want) \
        > 100 * F32_TOLERANCE


@pytest.mark.parametrize("fault", [
    dict(window=WINDOW + 1), dict(routed_scale=1.0), dict(first_expert=0),
    dict(layer_types=("sliding_attention",) * 5)])
def test_the_reference_tells_the_equations_apart(fault):
    """One more visible key, the scale dropped, another share of the experts,
    a window (and RoPE) on the full layer: each is another function."""
    module, variables = _model()
    toks = np.random.default_rng(7).integers(0, 256, 48).astype(np.int32)
    got = module.apply(variables, toks[None])[0]
    assert _relative(got, _forward()(variables, toks)) < F32_TOLERANCE
    assert _relative(got, _forward(**fault)(variables, toks)) \
        > 100 * F32_TOLERANCE


# ------------------------------------------------- through the decode engine

@pytest.fixture(scope="module")
def served():
    """Prompts of one to four prefill chunks, answers of five windows, three
    slots; then two shorter requests into slots that were left."""
    with jax.default_matmul_precision("highest"):
        module, variables = _model()
        rng = np.random.default_rng(11)
        runner, dec = _engine(module, variables, "wm.served")
        dec.warmup()
        first = [dec.submit(rng.integers(0, 256, n).astype(np.int32),
                            max_new_tokens=40) for n in (5, 33, 64)]
        _drain(dec)
        later = [dec.submit(rng.integers(0, 256, n).astype(np.int32),
                            max_new_tokens=24) for n in (16, 3)]
        _drain(dec)
        reg, pool = runner.registry, dec.pool
        out = dict(
            module=module, variables=variables, handles=first + later,
            slots=[h.slot for h in first + later],
            window_bytes=pool.window_nbytes(),
            page_bytes=pool.page_nbytes(), pages_left=pool.pages_in_use(),
            chunks=reg.family("mmlspark_runner_prefill_chunks_total")
            .labels(runner="wm.served").value,
            local=reg.family("mmlspark_runner_moe_local_assignments_total")
            .labels(runner="wm.served").value,
            touched=reg.family("mmlspark_runner_moe_experts_touched_total")
            .labels(runner="wm.served").value,
            gauge=reg.family("mmlspark_runner_window_state_bytes")
            .labels(runner="wm.served", page_size=str(PAGE)).value,
            steps=dec.steps, debug=dec.debug_state())
        dec.close()
        return out


def test_chunked_joins_and_steps_serve_the_references_best_tokens(served):
    """Prefill in chunks of 16 (1, 3 and 4 of them) then 39 steps a request,
    the window state wrapping five times: every served token is the
    reference's best at its row, to rounding."""
    assert [h.status for h in served["handles"]] == ["ok"] * 5
    assert [len(h.tokens) for h in served["handles"]] == [40, 40, 40, 24, 24]
    assert served["chunks"] == 1 + 3 + 4 + 1 + 1
    for h in served["handles"]:
        assert _gap_below_best(_forward(), served["variables"], h) \
            < F32_TOLERANCE


def test_a_slot_taken_again_by_a_shorter_request_sees_no_stale_row(served):
    """The two later requests (16 and 3 tokens) took slots that had held 64
    and 33 tokens + 40: their rings and pages still hold those rows, and
    none is visible."""
    assert set(served["slots"][3:]) <= set(served["slots"][:3])
    assert served["pages_left"] == 0
    for h in served["handles"][3:]:
        assert _gap_below_best(_forward(), served["variables"], h) \
            < F32_TOLERANCE


def test_the_counters_and_the_gauge_of_the_window_state(served):
    ring = (3 + 1) * WINDOW * 2 * 16 * 4        # rows x window x C x float32
    assert served["window_bytes"] == served["gauge"] == ring * 2 * 4
    assert served["debug"]["pool"]["window_state_bytes"] == ring * 2 * 4
    # a page holds K and V of the ONE full layer
    assert served["page_bytes"] == PAGE * 2 * 16 * 4 * 2
    # 8 of 16 experts held: about half of a step's 3 x 4 x 4 assignments
    steps = served["steps"]
    assert 0 < served["local"] < steps * 3 * 4 * 4
    assert 0 < served["touched"] <= steps * 4 * 8


@pytest.mark.parametrize("new", [16, 400])
def test_window_state_does_not_grow_with_the_longest_sequence(new):
    module, variables = _model()
    _, dec = _engine(module, variables, f"wm.bound.{new}", longest=112,
                     new=new)
    dec.warmup()
    pool = dec.pool
    assert pool.window_nbytes() == 4 * WINDOW * 32 * 4 * 2 * 4
    assert pool.num_pages == 3 * -(-(112 + new) // PAGE) + 1
    assert pool.page_nbytes() == PAGE * 32 * 4 * 2
    dec.close()


def test_a_step_of_window_layers_reads_window_rows_a_slot():
    """With every layer a window layer, no array of a decode step has the
    table's width of positions in it: the step reads ``window`` rows a
    slot whatever the context length."""
    module, variables = _model(layer_types=("sliding_attention",) * 5)
    slots, width = 3, 37                       # 148 positions a slot
    cache = (module.init_paged_cache(8, PAGE), module.init_window_cache(slots))
    assert all(layer == () for layer in cache[0])
    assert {a.shape for a in jax.tree_util.tree_leaves(cache[1])} \
        == {(slots + 1, WINDOW, 32)}

    def step(tok, pos, table, cache):
        return module.apply(variables, tok[:, None], positions=pos[:, None],
                            kv_cache=cache, page_table=table)

    jaxpr = jax.make_jaxpr(step)(
        jnp.zeros(slots, jnp.int32), jnp.full(slots, 140, jnp.int32),
        jnp.ones((slots, width), jnp.int32), cache)
    dims = {d for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars
            for d in getattr(v.aval, "shape", ())}
    assert WINDOW in dims and not {width * PAGE, width} & dims


def test_ring_positions_and_visibility_by_hand():
    last = jnp.asarray([-1, 2, 8, 21])
    got = np.asarray(window_moe.ring_positions(last, 8))
    assert (got[0] < 0).all()                              # nothing written
    assert got[1].tolist() == [0, 1, 2, -5, -4, -3, -2, -1]
    assert got[2].tolist() == [8, 1, 2, 3, 4, 5, 6, 7]
    assert sorted(got[3].tolist()) == list(range(14, 22))
    assert all(p % 8 == i for row in got[1:] for i, p in enumerate(row))
    see = np.asarray(window_moe.window_visible(
        jnp.asarray([[21]]), jnp.asarray(got[3:4]), 8))
    assert see.all()
    see = np.asarray(window_moe.window_visible(
        jnp.asarray([[9]]), jnp.asarray([[9, 2, 1, -3, 10]]), 8))
    assert see[0, 0].tolist() == [True, True, False, False, False]


def test_prefix_sharing_over_window_state_is_refused_by_name():
    module, variables = _model()
    runner = ModelRunner(module=module, variables=variables, name="wm.prefix")
    with pytest.raises(ValueError, match="window"):
        runner.decode_stream(slots=2, prompt_bucket=CHUNK, max_new_tokens=8,
                             page_size=PAGE, prefix_cache=True)
    prompts = np.zeros((1, 8), np.int32)
    with pytest.raises(ValueError, match="window"):
        runner.decode(prompts, max_new_tokens=4, kv_layout="paged",
                      page_size=PAGE, prefix_cache=True)
    with pytest.raises(TypeError, match="init_cache"):
        runner.decode(prompts, max_new_tokens=4, kv_layout="dense")
    # and the one-shot paged decode serves the stream's tokens
    one = runner.decode(prompts, max_new_tokens=6, kv_layout="paged",
                        page_size=PAGE)
    _, dec = _engine(module, variables, "wm.prefix.stream")
    h = dec.submit(prompts[0], max_new_tokens=6)
    _drain(dec)
    dec.close()
    assert one.tokens[0].tolist() == h.tokens


def test_a_pool_without_a_module_and_a_paged_module_keep_no_window_state():
    assert PagePool(None, 4, PAGE, name="wm.bare").window_nbytes() == 0
    module = sparse_moe.SparseMoEDecoder(
        vocab_size=64, embed_dim=32, num_layers=1, num_heads=2,
        num_kv_heads=1, head_dim=16, num_experts=4, experts_per_token=2,
        expert_dim=16, index_heads=1, index_dim=16, index_topk=4, max_len=64)
    pool = PagePool(module, 4, PAGE, name="wm.paged")
    cache = pool.borrow_cache(3)
    assert len(cache) == 1 and len(cache[0]) == 3        # (k, v, index_k)
    assert pool.window_nbytes() == 0 and pool.page_nbytes() > 0
    pool.return_cache(cache)


# ------------------------------------------------- the share of the experts

def _routed_experts_as_it_was(x, gate, up, down, expert_ids, weights,
                              block_rows=64):
    """``sparse_moe.routed_experts`` word for word as PR 34 left it (every
    expert held)."""
    _dot, F32 = sparse_moe._dot, jnp.float32
    T, D = x.shape
    k, E = expert_ids.shape[1], gate.shape[0]
    A, Tb = T * k, min(T, block_rows)
    flat = expert_ids.reshape(A)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros(E, jnp.int32).at[flat].add(1)
    ends = jnp.cumsum(sizes)
    blocks = (sizes + Tb - 1) // Tb
    block_ends = jnp.cumsum(blocks)
    b = jnp.arange(A // Tb + E)
    owner = jnp.minimum(jnp.searchsorted(block_ends, b, side="right",
                                         method="compare_all"),
                        E - 1).astype(jnp.int32)
    row0 = ends[owner] - sizes[owner] \
        + (b - (block_ends[owner] - blocks[owner])) * Tb
    xs = jnp.concatenate([x[order // k], jnp.zeros((Tb, D), x.dtype)])

    def one_block(i, ys):
        e, r0 = owner[i], row0[i]
        rows = lax.dynamic_slice(xs, (r0, 0), (Tb, D))
        w_g, w_u, w_d = (lax.dynamic_index_in_dim(w, e, 0, keepdims=False)
                         for w in (gate, up, down))
        h = jax.nn.silu(_dot(rows, w_g)) * _dot(rows, w_u)
        y = _dot(h.astype(x.dtype), w_d)
        mine = (r0 + jnp.arange(Tb) < ends[e])[:, None]
        old = lax.dynamic_slice(ys, (r0, 0), (Tb, D))
        return lax.dynamic_update_slice(ys, jnp.where(mine, y, old), (r0, 0))

    ys = lax.fori_loop(0, block_ends[-1], one_block,
                       jnp.zeros((A + Tb, D), F32))
    per_choice = ys[jnp.argsort(order)].reshape(T, k, D)
    return jnp.einsum("tk,tkd->td", weights.astype(F32), per_choice), \
        (sizes > 0).sum().astype(jnp.int32)


def _layer(seed, tokens=37, experts=16, k=4, width=32, inner=24):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)  # noqa: E731
    x, gate, up, down = f(tokens, width), f(experts, width, inner), \
        f(experts, width, inner), f(experts, inner, width)
    ids = jnp.asarray(np.stack([rng.permutation(experts)[:k]
                                for _ in range(tokens)]), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (tokens, k)), jnp.float32)
    return x, gate, up, down, ids, w


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tokens,block", [(37, 64), (200, 16), (1, 64)])
def test_with_every_expert_held_the_layer_is_bit_for_bit_what_it_was(
        dtype, tokens, block):
    x, gate, up, down, ids, w = _layer(5, tokens=tokens)
    x, gate, up, down = (a.astype(dtype) for a in (x, gate, up, down))
    want, n = jax.jit(functools.partial(
        _routed_experts_as_it_was, block_rows=block))(x, gate, up, down, ids,
                                                      w)
    got, m = jax.jit(functools.partial(
        sparse_moe.routed_experts, block_rows=block))(x, gate, up, down, ids,
                                                      w)
    assert (np.asarray(got) == np.asarray(want)).all() and int(n) == int(m)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_eight_shares_and_the_shared_expert_once_add_up(seed):
    """Eight chips hold two experts each of a layer of 16: their routed
    parts, and the shared expert counted ONCE, are the uncut layer (a loop
    over every token's chosen experts in numpy)."""
    x, gate, up, down, ids, w = _layer(seed)
    def ffn(row, g, u, d):
        h = row @ g
        return ((h / (1 + np.exp(-h))) * (row @ u)) @ d

    xs = np.asarray(x, np.float64)
    g64, u64, d64 = (np.asarray(a, np.float64) for a in (gate, up, down))
    shared_g, shared_u, shared_d = (np.asarray(a[0], np.float64) for a in
                                    _layer(seed + 100, experts=1)[1:4])
    shared = np.stack([ffn(r, shared_g, shared_u, shared_d) for r in xs])
    uncut = shared.copy()
    for t in range(xs.shape[0]):
        for e, weight in zip(np.asarray(ids[t]), np.asarray(w[t])):
            uncut[t] += weight * ffn(xs[t], g64[e], u64[e], d64[e])
    parts, touched = [], 0
    for rank in range(8):
        held = slice(2 * rank, 2 * rank + 2)
        y, n = sparse_moe.routed_experts(x, gate[held], up[held], down[held],
                                         ids, w, first=2 * rank)
        parts.append(np.asarray(y, np.float64))
        touched += int(n)
    total = sum(parts) + shared
    assert np.abs(total - uncut).max() < 1e-5 * np.abs(uncut).max()
    assert touched == len(set(np.asarray(ids).reshape(-1).tolist()))
    # the shared expert on every chip and summed would count eight times
    assert np.abs(sum(p + shared for p in parts) - uncut).max() \
        > 0.1 * np.abs(uncut).max()
    # and the whole layer held under first=0 is the same sum
    whole, _ = sparse_moe.routed_experts(x, gate, up, down, ids, w, first=0)
    assert np.abs(np.asarray(whole) - sum(parts)).max() \
        < 1e-5 * np.abs(uncut).max()


def test_a_share_no_token_chose_reads_no_expert():
    """Assignments all to experts not held: zeros, nothing touched, and the
    loop over blocks takes no turn (NaN weights would show a read)."""
    x, gate, up, down, ids, w = _layer(9)
    ids = 8 + ids % 8                            # experts 8-15 only
    poison = jnp.full_like(gate[:4], jnp.nan)
    y, n = sparse_moe.routed_experts(
        x, poison, poison, jnp.full_like(down[:4], jnp.nan), ids, w, first=2)
    assert int(n) == 0 and (np.asarray(y) == 0).all()


def test_a_module_whose_share_nobody_chose_gives_the_shared_part_alone():
    """The held experts' correction bias far below every other's: no token
    chooses them, ``experts_touched`` and ``local_assignments`` are 0, and
    the logits do not depend on the held experts' matrices at all."""
    module, variables = _model()
    toks = jnp.asarray(np.random.default_rng(4).integers(0, 256, (2, 24)),
                       jnp.int32)
    p = jax.tree_util.tree_map(lambda a: a, variables)
    for i in range(1, 5):
        bias = p["params"][f"layer_{i}"]["router"]["bias"]
        p["params"][f"layer_{i}"]["router"]["bias"] = bias.at[4:12].set(-10.0)
    logits, sown = module.apply(p, toks, mutable=["intermediates"])
    counts = sown["intermediates"]
    assert int(counts["experts_touched"][0]) == 0
    assert int(counts["local_assignments"][0]) == 0
    q = jax.tree_util.tree_map(lambda a: a, p)
    for i in range(1, 5):
        q["params"][f"layer_{i}"]["experts"] = jax.tree_util.tree_map(
            lambda a: a * jnp.nan, q["params"][f"layer_{i}"]["experts"])
    again = module.apply(q, toks)
    assert np.isfinite(np.asarray(again)).all()
    assert (np.asarray(again) == np.asarray(logits)).all()
    # with the published bias the share is chosen and counted
    _, sown = module.apply(variables, toks, mutable=["intermediates"])
    local = int(sown["intermediates"]["local_assignments"][0])
    assert 0 < local < 2 * 24 * 4 * 4
    assert 0 < int(sown["intermediates"]["experts_touched"][0]) <= 4 * 8


# ------------------------------------- the engine's two drivers (ISSUE 37)

def test_step_by_hand_and_the_start_thread_serve_the_same_tokens():
    """Prompts of one to three prefill chunks, answers that wrap the window
    ring and cross page boundaries, an eos some answers hit, slots taken
    again: ``step()`` by hand and the ``start()`` thread (one step in flight,
    so a row that has left still writes its slot's ring once) serve the same
    tokens, free every page and mint no compile key after warm-up."""
    from tests.decode_drivers import serve_through_both_drivers
    module, variables = _model()
    rng = np.random.default_rng(37)
    requests = [(rng.integers(0, 256, int(rng.integers(3, 41))).astype(
        np.int32), int(rng.integers(3, 21))) for _ in range(8)]
    serve_through_both_drivers(
        lambda name, eos: _engine(module, variables, f"wm.flight.{name}",
                                  longest=48, new=20, eos_id=eos),
        requests)
