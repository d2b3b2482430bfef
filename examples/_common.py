"""Shared example setup: the CPU mesh when asked, else the TPU — or fail."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup(force_cpu: bool = False):
    """``force_cpu`` / ``MMLSPARK_TPU_EXAMPLES_CPU`` run on 8 virtual CPU
    devices; otherwise the example was asked to run on the accelerator, and
    a machine without one is an error, not a silent CPU run."""
    on_cpu = force_cpu or bool(os.environ.get("MMLSPARK_TPU_EXAMPLES_CPU"))
    if on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if on_cpu:
        jax.config.update("jax_num_cpu_devices", 8)
    print(f"devices: {jax.devices()}")
    if not on_cpu:
        from mmlspark_tpu.utils.device import require_tpu
        require_tpu()
