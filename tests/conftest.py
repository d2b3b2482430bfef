"""Test harness bootstrap.

Reference test strategy (SURVEY.md §4): local[*] Spark with multiple tasks is
the "cluster in a box".  Here the analogue is a virtual 8-device CPU platform
(``--xla_force_host_platform_device_count=8``) so mesh/collective paths run
in-process without TPU hardware; chip_smoke.py separately targets the chip.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tier-1 is CPU-only, chip or no chip

# Runtime lock-order sanitizer (ISSUE 18): every make_lock/make_condition
# in the package becomes an order-validating wrapper for the whole tier-1
# run, so any lock inversion a test provokes trips HERE, not in a
# production hang.  setdefault is the kill switch: export
# MMLSPARK_TPU_LOCK_SANITIZER=0 to opt a run out (or =strict to fail on
# first inversion instead of recording).
os.environ.setdefault("MMLSPARK_TPU_LOCK_SANITIZER", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Persistent XLA compile cache: the tier-1 suite is compile-dominated, and
# every wrapper books compiles by SIGNATURE on the host side, so count/
# storm/report assertions are unaffected — only the redundant lower+compile
# wall time goes away on warm runs.
from mmlspark_tpu.utils.device import enable_compilation_cache

enable_compilation_cache()

import numpy as np
import pytest


@pytest.fixture(scope="session")
def mesh8():
    from mmlspark_tpu.parallel import data_parallel_mesh
    return data_parallel_mesh()


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


@pytest.fixture()
def as_platform(monkeypatch):
    """``as_platform("tpu")`` answers ``platform()`` in the GBDT trainer's and
    the histogram dispatchers' place, so that a test on the CPU takes the
    paths the chip takes: the ``matmul`` builders, quantized gradients unless
    the params say otherwise, four iterations a dispatch from 50,000 rows.
    The same two attributes ``benchmark/tools/compile_for_v5e.py``
    substitutes; the resolved backend is part of the trainer's jit-cache key,
    so both platforms' programs live side by side in one process."""
    from mmlspark_tpu.lightgbm import core
    from mmlspark_tpu.ops import histogram as hist_ops

    def answer(name):
        monkeypatch.setattr(core, "platform", lambda: name)
        monkeypatch.setattr(hist_ops, "platform", lambda: name)
    return answer


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_executables():
    """Drop every compiled executable between test modules.  Each one holds
    memory mappings; accumulated over the whole suite in one process they
    run into ``vm.max_map_count`` and the next XLA compile segfaults."""
    yield
    import gc

    from mmlspark_tpu.lightgbm import core as lgb_core
    from mmlspark_tpu.observability import get_registry

    lgb_core._JIT_CACHE.clear()
    for wrappers in getattr(get_registry(), "_jit_wrappers", {}).values():
        for w in list(wrappers):
            w.clear_cache()
    jax.clear_caches()
    gc.collect()
