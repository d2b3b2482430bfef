"""The one place that asks "which device am I on?" and places the XLA
compile cache.

Every platform decision in the package (histogram backend, quantized
default, scan chunk, Pallas interpret-vs-compile) and every launcher's
result stamp reads :func:`platform` / :func:`device_stamp`, so a machine
that registers under an unexpected platform name fails loudly instead of
silently taking the CPU or interpreter branch.
"""
from __future__ import annotations

import os
from typing import Dict

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: platforms the package has code paths for; anything else is an error
SUPPORTED_PLATFORMS = ("cpu", "tpu")


def platform() -> str:
    """``jax.default_backend()``, checked: ``"cpu"`` or ``"tpu"``.  A third
    platform raises — no dispatcher may treat an unknown device as either."""
    import jax
    plat = jax.default_backend()
    if plat not in SUPPORTED_PLATFORMS:
        raise RuntimeError(
            f"unsupported JAX platform {plat!r}: mmlspark_tpu has code paths "
            f"for {SUPPORTED_PLATFORMS} only")
    return plat


def device_stamp() -> Dict[str, object]:
    """``{"platform", "kind", "count"}`` exactly as JAX reports them — the
    stamp every chip result carries."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_tpu() -> Dict[str, object]:
    """The device stamp, or ``RuntimeError`` when the first device is not a
    TPU — device phases fail on a machine without the chip, they never fall
    back to the CPU."""
    stamp = device_stamp()
    if stamp["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: JAX reports platform {stamp['platform']!r} "
            f"({stamp['kind']} x{stamp['count']})")
    return stamp


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` in the environment JAX reads the
    variable itself, so no directory is set here; otherwise the cache lives
    at ``<checkout>/.xla_cache`` (git-ignored).  The path is fixed — it is
    part of the cache key, so a directory that moves never hits.  A cache
    that cannot be set raises: a silently cold cache turns every chip call
    into a full recompile.

    The key covers the programs' metadata (scope names and the source line
    of each operation), which JAX leaves out by default: an executable
    carries the ``jax.named_scope`` paths of the source that compiled it
    into every profiler trace, and the GBDT device phases are read from
    those (``lightgbm.core.DEVICE_PHASES``), so a hit on another source's
    entry would show that source's names, or none.  The callers' frames are
    kept out of that metadata in turn (a location holds the innermost frame
    alone): with them a program built again from another call site (the
    sharded trainer re-jits its objective in every ``train()``) would miss
    the entry its first build wrote."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_REPO, ".xla_cache")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
