"""Roofline share of the ResNet forward pass, in percent: the least time a
chip with the published peaks could take for the window's images
(``benchmark/shapes.py``: two operations per multiply-accumulate of the
convolutions over the bf16 peak, or the input and weight bytes over the HBM
peak, whichever is larger; compute binds) over the device's busy time."""
from benchmark import shapes


def read(run):
    busy = run.device_busy_s()
    rows = run.counter("mmlspark_runner_rows_total", runner="dl.jax_model")
    if busy is None or busy <= 0 or not rows or run.peaks is None:
        return None
    cfg = run.config
    need = shapes.resnet_forward_need(int(rows), cfg["image_size"],
                                      tuple(cfg["stage_sizes"]))
    least_s, _ = shapes.resnet_forward_least_s(need, run.peaks)
    return 100.0 * least_s / busy
