"""The builder's tool, no part of a run: compile the programs the cells run, at
their real sizes, for a v5e that is described and not attached (rehearsal 3 of
the ``on-chip-measurement`` guide).  What the chip's compiler would refuse it
refuses here, at no chip time; ``memory_analysis`` says what each program
holds on a chip, which is how the cells were sized against the memory floor.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_for_v5e.py gbdt 1000000
    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_for_v5e.py gbdt-dp4 1000000
    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_for_v5e.py resnet 4 2048

``gbdt`` compiles ``lightgbm.multi_iter`` (what ``train()`` dispatches on one
chip) at that many rows x 200; ``gbdt-dp4`` the ``lightgbm.sharded_grower`` of
``train(shard_rows=True)`` on a 2x2 mesh at that many rows a chip, and lists
its collectives; ``resnet`` the featurizer's fused program at each batch size.

The program asks ``platform()`` which path to take and sees the CPU here, so
this script steers it from outside: it answers "tpu" in its place, and swaps
``instrumented_jit`` for a stand-in that hands back the function and the
arguments of the program wanted instead of running it.  Nothing runs, so
nothing here is a result or a time.
"""
from __future__ import annotations

import os
import re
import sys
import time
from collections import Counter

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FEATURES = 200


class _Captured(Exception):
    pass


def _capture_train(want: str, rows: int, mesh=None):
    """Run ``train()`` up to its first call of the program called ``want``;
    return that program's function, jit options and arguments."""
    import jax
    import numpy as np
    import mmlspark_tpu.lightgbm.core as core
    import mmlspark_tpu.observability.compute as compute
    import mmlspark_tpu.ops.histogram as hist
    from mmlspark_tpu.parallel import active_mesh
    core.platform = hist.platform = lambda: "tpu"
    got = {}

    def stand_in(fn=None, *, name=None, **kw):
        if fn is None:
            return lambda f: stand_in(f, name=name, **kw)
        kw.pop("registry", None)
        kw.pop("storm_signatures", None)

        def call(*args, **kwargs):
            if name == want:
                got.update(fn=fn, kw=kw, args=args)
                raise _Captured(name)
            return jax.jit(fn, **kw)(*args, **kwargs)
        return call

    core.instrumented_jit = stand_in
    if mesh is not None:               # no array can be put on a described chip
        compute.device_put = lambda x, sharding, site=None: \
            jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    rng = np.random.default_rng(0)
    block = rng.standard_normal((min(rows, 1_000_000), FEATURES),
                                dtype=np.float32)
    X = np.concatenate([np.roll(block, k, axis=1)
                        for k in range(-(-rows // len(block)))])[:rows]
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    params = core.GBDTParams(objective="binary", max_depth=5, max_bin=255,
                             learning_rate=0.1, num_iterations=8)
    try:
        if mesh is not None:
            with active_mesh(mesh):
                core.train(X, y, params, shard_rows=True)
        else:
            core.train(X, y, params)
    except _Captured:
        return got
    raise SystemExit(f"train() never called {want}")


def _report(tag: str, compiled, seconds: float) -> None:
    m = compiled.memory_analysis()
    held = m.temp_size_in_bytes + m.argument_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    print(f"{tag}: compiled for a described v5e in {seconds:.1f} s; on each "
          f"chip temp {m.temp_size_in_bytes / 1e9:.3f} GB, arguments "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB, outputs "
          f"{m.output_size_in_bytes / 1e9:.3f} GB, held {held / 1e9:.3f} GB",
          flush=True)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
        SingleDeviceSharding
    what, sizes = sys.argv[1], [int(a) for a in sys.argv[2:]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    if what == "gbdt":
        for rows in sizes:
            got = _capture_train("lightgbm.multi_iter", rows)
            shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one), got["args"])
            t0 = time.time()
            compiled = jax.jit(got["fn"], **got["kw"]).lower(*shapes).compile()
            _report(f"lightgbm.multi_iter {rows} x {FEATURES}", compiled,
                    time.time() - t0)
    elif what == "gbdt-dp4":
        mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
        for rows in sizes:
            got = _capture_train("lightgbm.sharded_grower", 4 * rows, mesh)
            specs = (P("data"),) * 4 + (P(), P())
            shapes = tuple(jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s))
                for a, s in zip(got["args"], specs))
            t0 = time.time()
            compiled = jax.jit(got["fn"], **got["kw"]).lower(*shapes).compile()
            _report(f"lightgbm.sharded_grower {rows} x {FEATURES} a chip on "
                    f"2x2", compiled, time.time() - t0)
            # opcode and result type of every collective, as the HLO spells it
            ops = re.findall(r"= (\S+) ((?:all-reduce|all-gather|reduce-scatter"
                             r"|all-to-all|collective-permute)[-\w]*)\(",
                             compiled.as_text())
            print(f"  collectives in the compiled program: "
                  f"{dict(Counter(f'{op} {ty}' for ty, op in ops))}")
    elif what == "resnet":
        from mmlspark_tpu.models import resnet50
        from mmlspark_tpu.ops import image as image_ops
        module = resnet50(num_classes=1000, dtype=jnp.bfloat16)
        variables = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            jax.eval_shape(lambda: module.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3), jnp.float32))))

        def fused(v, batch):
            return module.apply(v, image_ops.normalize(batch), features=True)
        for batch in sizes:
            x = jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.float32,
                                     sharding=one)
            t0 = time.time()
            compiled = jax.jit(fused).lower(variables, x).compile()
            _report(f"ResNet-50 bf16 features, batch {batch}", compiled,
                    time.time() - t0)
    else:
        raise SystemExit(__doc__)
    return 0


if __name__ == "__main__":
    sys.exit(main())
