"""The device helpers every launcher rides: no TPU -> fail (never a CPU
fallback), one compile-cache site that can be placed from outside, and
``chip_smoke.py`` refusing to run anywhere but on the chip."""
import os
import subprocess
import sys

import pytest

from mmlspark_tpu.utils import device

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_script, env_extra, cwd=_REPO, script=False):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=_REPO, **env_extra)
    argv = [sys.executable, code_or_script] if script \
        else [sys.executable, "-c", code_or_script]
    return subprocess.run(argv, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=120)


def test_platform_and_require_tpu_on_the_cpu():
    assert device.platform() == "cpu"
    stamp = device.device_stamp()
    assert stamp["platform"] == "cpu" and stamp["count"] == 8
    with pytest.raises(RuntimeError, match="no TPU"):
        device.require_tpu()


_PRINT_CACHE = (
    "import jax\n"
    "from mmlspark_tpu.utils.device import enable_compilation_cache\n"
    "got = enable_compilation_cache()\n"
    "print(got)\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(jax.config.jax_compilation_cache_include_metadata_in_key,\n"
    "      jax.config.jax_traceback_in_locations_limit, sep=',')\n")


def test_compile_cache_defaults_to_the_checkout():
    proc = _run(_PRINT_CACHE, {})
    assert proc.returncode == 0, proc.stderr[-800:]
    returned, configured, metadata_in_key = proc.stdout.split()
    assert returned == configured == os.path.join(_REPO, ".xla_cache")
    # a cached executable carries its source's scope names into every
    # trace, so the key covers them, but not the frames of who called
    assert metadata_in_key == "True,1"


def test_compile_cache_follows_the_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no directory of
    its own — JAX reads the variable — and creates nothing in the repo."""
    placed = str(tmp_path / "placed_cache")
    proc = _run(_PRINT_CACHE, {"JAX_COMPILATION_CACHE_DIR": placed})
    assert proc.returncode == 0, proc.stderr[-800:]
    returned, configured, _ = proc.stdout.split()
    assert returned == configured == placed


def test_chip_smoke_refuses_to_run_off_the_chip():
    """JAX_PLATFORMS=cpu: non-zero exit, a clear message, no work done and
    no result line."""
    proc = _run(os.path.join(_REPO, "chip_smoke.py"), {}, script=True)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "nothing was run" in proc.stderr
    assert proc.stdout.strip() == "", "no phase output, no JSON result"
