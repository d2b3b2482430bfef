"""The device phases of a boosting iteration: every name of
``lightgbm.core.DEVICE_PHASES`` is a ``jax.named_scope`` in the compiled
grower programs, every heavy operation lies under one, the scopes change no
arithmetic, and each has its per-layer metric in ``BENCHMARK.json``.

The programs are compiled on the CPU as the chip takes them: quantized
gradients, the ``matmul`` histogram builder, and (for ``lightgbm.multi_iter``)
the chunk of four iterations a dispatch."""
import contextlib
import json
import os
import re

import numpy as np
import pytest

from mmlspark_tpu.lightgbm import GBDTParams, core, train
from mmlspark_tpu.lightgbm.core import DEVICE_PHASES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEPTH = 3
#: the phases that run once a level, inside an ``L<d>`` scope
IN_LEVELS = ("gbdt.layout", "gbdt.hist", "gbdt.split", "gbdt.route")
HEAVY = ("dot", "sort", "gather", "scatter", "all-reduce")


def _data(rows, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, 8)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.normal(size=rows) > 0)
    return X, y.astype(np.float32)


def _params(iterations):
    return GBDTParams(num_iterations=iterations, objective="binary",
                      max_depth=DEPTH, max_bin=63, use_quantized_grad=True)


def _compiled_text(program):
    """Optimized HLO text of the newest compiled signature of ``program``
    among the trainer's cached programs."""
    found = [fn for fn in core._JIT_CACHE.values()
             if getattr(fn, "name", None) == program and fn._entries]
    assert len(found) == 1, (program, found)
    return list(found[0]._entries.values())[-1].compiled.as_text()


def _instructions(text):
    """``(opcode, op_name)`` of every instruction outside fused and applied
    computations.  One without an ``op_name`` of its own that calls a fused
    computation counts by that computation's root."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
            cur = m.group(1) if m else None
            if cur:
                comps[cur] = []
        elif cur and " = " in line:
            comps[cur].append(line)

    def op_name(line):
        m = re.search(r'op_name="([^"]*)"', line)
        return m.group(1) if m else ""

    called = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", text))
    out = []
    for comp, lines in comps.items():
        if comp in called:
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(", line)
            if not m:
                continue
            name = op_name(line)
            callee = re.search(r"calls=%?([\w.\-]+)", line)
            if not name and callee:
                roots = [ln for ln in comps.get(callee.group(1), [])
                         if ln.lstrip().startswith("ROOT")]
                name = op_name(roots[0]) if roots else ""
            out.append((m.group(1), name))
    return out


def _phase(name):
    parts = [p for p in name.split("/") if p in DEVICE_PHASES]
    return parts[-1] if parts else None


@pytest.fixture(scope="module")
def chip_path():
    """Makes the CPU trace the chip's path: the trainer and the histogram
    dispatchers are told they run on a TPU."""
    from mmlspark_tpu.ops import histogram as hist_ops
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(core, "platform", lambda: "tpu")
        mp.setattr(hist_ops, "platform", lambda: "tpu")
        yield


@pytest.fixture(scope="module")
def programs(chip_path):
    """HLO text of the grower programs: the per-iteration one at 4,000 rows,
    the chunked one (it needs 50,000), and over four virtual devices the
    sharded grower with the objective it dispatches beside it."""
    from mmlspark_tpu.parallel import active_mesh, data_parallel_mesh
    texts = {}
    train(*_data(4000), _params(3))
    texts["lightgbm.iter"] = _compiled_text("lightgbm.iter")
    res = train(*_data(50_000), _params(8))
    assert res.booster.num_trees == 8
    texts["lightgbm.multi_iter"] = _compiled_text("lightgbm.multi_iter")
    # the sharded path makes its jitted objective anew in every train()
    made = []
    with pytest.MonkeyPatch.context() as mp:
        jit = core.instrumented_jit
        mp.setattr(core, "instrumented_jit",
                   lambda *a, **kw: made.append(jit(*a, **kw)) or made[-1])
        with active_mesh(data_parallel_mesh(4)):
            train(*_data(4000), _params(2), shard_rows=True)
    texts["lightgbm.sharded_grower"] = _compiled_text("lightgbm.sharded_grower")
    (objective,) = [w for w in made if w.name == "lightgbm.objective"]
    texts["lightgbm.objective"] = list(
        objective._entries.values())[-1].compiled.as_text()
    return texts


@pytest.mark.parametrize("phase", [p for p in DEVICE_PHASES
                                   if p != "gbdt.allreduce"])
@pytest.mark.parametrize("program", ["lightgbm.iter", "lightgbm.multi_iter"])
def test_each_phase_is_a_scope_of_the_compiled_program(programs, program,
                                                       phase):
    # every instruction counts here, those inside fused computations too:
    # a fusion across a scope boundary carries one name outside
    names = re.findall(r'op_name="([^"]*)"', programs[program])
    mine = [n for n in names if _phase(n) == phase]
    assert mine, f"no instruction of {program} under {phase}"
    if phase in IN_LEVELS:
        levels = {p for n in mine for p in n.split("/")
                  if re.fullmatch(r"L\d+", p)}
        assert levels == {f"L{d}" for d in range(DEPTH)}, (phase, levels)
        # the level lies outside the phase: .../L2/gbdt.hist/...
        assert all(re.search(r"(^|/)L\d+/(.*/)?" + re.escape(phase) + "(/|$)", n)
                   for n in mine)


def test_the_sharded_grower_reduces_its_histograms_under_allreduce(programs):
    ops = _instructions(programs["lightgbm.sharded_grower"])
    reduces = [n for op, n in ops if op.startswith("all-reduce")]
    assert reduces
    hist = [n for n in reduces if _phase(n) == "gbdt.allreduce"]
    # one a level, each inside its level's scope
    assert {p for n in hist for p in n.split("/")
            if re.fullmatch(r"L\d+", p)} == {f"L{d}" for d in range(DEPTH)}
    # the scale maxima and the noise key of the quantization cross the mesh
    # too, under their own phase
    assert {_phase(n) for n in reduces} == {"gbdt.allreduce", "gbdt.quantize"}
    # the separately dispatched objective of the sharded path
    assert all(_phase(n) == "gbdt.grad"
               for op, n in _instructions(programs["lightgbm.objective"])
               if n and op != "parameter")


@pytest.mark.parametrize("program", ["lightgbm.iter", "lightgbm.multi_iter",
                                     "lightgbm.sharded_grower"])
def test_every_heavy_operation_lies_under_a_phase(programs, program):
    heavy = [(op, n) for op, n in _instructions(programs[program])
             if op.split("-start")[0].split("-done")[0] in HEAVY]
    assert {op for op, _ in heavy} >= {"dot", "sort"}
    bare = [(op, n) for op, n in heavy if _phase(n) is None]
    assert not bare, bare[:5]


@pytest.fixture(scope="module")
def cpu_multi_iter(programs):
    """HLO text of the chunked program as the CPU's own ``scatter`` path
    compiles it (the trainer's chunk override makes one there)."""
    from mmlspark_tpu.ops import histogram as hist_ops
    core._JIT_CACHE.clear()             # the chip path's multi_iter
    with pytest.MonkeyPatch.context() as mp:
        for module in (core, hist_ops):
            mp.setattr(module, "platform", lambda: "cpu")
        mp.setenv("MMLSPARK_TPU_GBDT_CHUNK", "4")
        train(*_data(50_000), _params(8))
        return _compiled_text("lightgbm.multi_iter")


def _route_ops(text):
    """``(opcode, type of the first operand)`` of every instruction under
    ``gbdt.route``, those inside fused computations too."""
    types = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", text))
    return [(op, types.get(arg)) for op, arg, name in re.findall(
                r"= \S+ ([\w\-]+)\(%([\w.\-]+)[^\n]*?op_name=\"([^\"]*)\"",
                text)
            if _phase(name) == "gbdt.route"]


@pytest.mark.parametrize("program", ["lightgbm.multi_iter",
                                     "lightgbm.sharded_grower"])
def test_the_matmul_path_routes_by_a_product_and_gathers_nothing(
        programs, cpu_multi_iter, program):
    """On the ``matmul`` backend every row's bin comes from one product with
    the level's split columns: the route holds a ``dot`` and no gather at
    all.  The ``scatter`` backend keeps its gather of a byte a row from the
    binned ``(rows, 8)`` matrix."""
    ops = _route_ops(programs[program])
    assert {op for op, _ in ops} & {"dot", "convolution"}
    assert not [t for op, t in ops if op == "gather"]
    binned = [t for op, t in _route_ops(cpu_multi_iter) if op == "gather"
              and t == "u8[50000,8]"]
    assert binned


def _primitives(jaxpr, outer=""):
    """``(primitive, scope path)`` of every equation, nested ones too."""
    import jax
    for eqn in jaxpr.eqns:
        path = outer + "/" + str(eqn.source_info.name_stack)
        yield eqn.primitive.name, path
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub, path)


@pytest.mark.parametrize("num_nodes,max_rows,sorts", [
    (1, None, 0), (1, 600, 1), (4, None, 1), (4, 600, 1), (40, None, 1)])
def test_the_row_layout_sorts_once_and_scatters_nothing(num_nodes, max_rows,
                                                        sorts):
    """The layout is one sort and block slices: no per-row slot, so no
    scatter under ``gbdt.layout``; one node without a bound is not even
    sorted.  The scan's accumulation into the node buffers stays the only
    scatter of the build."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram as H
    n = 1000
    binned = jnp.zeros((n, 4), jnp.uint8)
    q = jnp.zeros((n,), jnp.int32)
    for build, args in (
            (H.build_histograms_matmul_quantized, (binned, q, q, q)),
            (H.build_histograms_matmul,
             (binned, q.astype(jnp.float32), q.astype(jnp.float32), q))):
        prims = list(_primitives(jax.make_jaxpr(
            lambda *a: build(*a, num_nodes, 63, block_rows=256,
                             max_rows=max_rows))(*args).jaxpr))
        assert any("gbdt.layout" in path for _, path in prims)
        assert [path for name, path in prims if name == "sort"] == \
            ["/gbdt.layout"] * sorts
        scatters = [path for name, path in prims if "scatter" in name]
        assert len(scatters) == 1 and "gbdt.layout" not in scatters[0]


def test_the_scopes_change_no_arithmetic(chip_path, monkeypatch):
    """The trees of a fit, bit for bit, against the same fit traced with
    every scope taken out."""
    import jax
    X, y = _data(4000, seed=3)

    def fit():
        core._JIT_CACHE.clear()
        b = train(X, y, _params(3)).booster
        return {k: np.asarray(getattr(b, k)) for k in (
            "split_feature", "threshold", "threshold_bin", "split_gain",
            "internal_value", "internal_count", "leaf_value", "leaf_count")}

    scoped = fit()
    assert "gbdt.hist" in _compiled_text("lightgbm.iter")
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = fit()
    assert "gbdt.hist" not in _compiled_text("lightgbm.iter")
    core._JIT_CACHE.clear()
    assert (scoped["split_feature"] >= 0).sum() >= 3 * 3
    for key in scoped:
        np.testing.assert_array_equal(scoped[key], bare[key], err_msg=key)


def test_each_phase_has_its_metric_and_no_other_name_is_written():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        metrics = {m["name"]: m for m in json.load(f)["per_layer"]}
    for phase in DEVICE_PHASES:
        m = metrics[f"{phase}_ms_per_iter"]
        assert m["source"] == "device_trace" and m["moves"] == "rows_per_s"
        reader = os.path.join(REPO, "benchmark", "layer_metrics",
                              f"{phase}_ms_per_iter.py")
        with open(reader) as f:
            assert f'"{phase}"' in f.read()
    assert metrics["gbdt.allreduce_ms_per_iter"]["workloads"] == \
        ["gbdt-train-dp4"]
    assert len(set(DEVICE_PHASES)) == len(DEVICE_PHASES) == 8
    # the program spells a phase's name once, in the vocabulary
    for rel in ("mmlspark_tpu/lightgbm/core.py", "mmlspark_tpu/ops/histogram.py",
                "mmlspark_tpu/parallel/collectives.py"):
        with open(os.path.join(REPO, rel)) as f:
            code = f.read()
        for phase in DEVICE_PHASES:
            want = 1 if rel.endswith("core.py") else 0
            assert code.count(f'"{phase}"') == want, (rel, phase)
