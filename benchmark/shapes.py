"""What the algorithms need, from their shapes alone: operations and bytes of
one GBDT boosting iteration and of one ResNet forward pass, and the least time
a chip with given peaks could take for them.  A roofline share is this least
time over the device time a trace shows.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

#: channels a histogram accumulates per (feature, bin): gradient, hessian, count
HIST_CHANNELS = 3


def gbdt_iteration_need(rows: int, features: int, bins: int, depth: int,
                        channels: int = HIST_CHANNELS) -> Dict[str, float]:
    """One level-wise boosting iteration on ``rows`` x ``features`` binned
    uint8 data.  Each of the ``depth`` levels reads the binned matrix once
    and, built on a matrix unit, multiplies a one-hot ``rows x features x
    bins`` operand by the ``channels`` per-row weights in int8.  Sibling
    subtraction (half the rows below the root) is NOT credited: the
    definition is fixed so that the share compares across PRs.  Gradients,
    row-to-node ids and the split scan are lower-order and left out."""
    macs = float(depth) * rows * features * bins * channels
    return {"int8_ops": 2.0 * macs,
            "hbm_bytes": float(depth) * rows * features}


def gbdt_iteration_least_s(need: Dict[str, float],
                           peaks: Dict[str, float]) -> Tuple[float, str]:
    compute = need["int8_ops"] / peaks["int8_ops_per_s"]
    memory = need["hbm_bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "int8 compute") if compute >= memory \
        else (memory, "HBM bandwidth")


def _conv_out(size: int, kernel: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - kernel) // stride + 1


def resnet_forward_macs(image_size: int, stage_sizes: Sequence[int] = (3, 4, 6, 3),
                        width: int = 64, channels: int = 3,
                        num_classes: int = 0) -> float:
    """Multiply-accumulates of one bottleneck-ResNet forward pass on one
    ``image_size`` x ``image_size`` image (He et al. 2015, v1.5 stride
    placement): convolutions only, plus the classifier when ``num_classes``
    is given.  ResNet-50 at 224 with its 1000-way head: 4.09e9, the figure
    torchvision publishes as "GFLOPS"; the features-only pass the featurizer
    runs is 2.0e6 fewer."""
    hw = _conv_out(image_size, 7, 2, 3)
    macs = float(hw * hw * 7 * 7 * channels * width)          # stem
    hw = _conv_out(hw, 3, 2, 1)                               # max pool
    c_in = width
    for i, blocks in enumerate(stage_sizes):
        mid = width * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out_hw = -(-hw // stride)                         # SAME padding
            macs += hw * hw * c_in * mid                      # 1x1
            macs += out_hw * out_hw * 9 * mid * mid           # 3x3, strided
            macs += out_hw * out_hw * mid * mid * 4           # 1x1 expand
            if c_in != mid * 4 or stride != 1:
                macs += out_hw * out_hw * c_in * mid * 4      # projection
            c_in, hw = mid * 4, out_hw
    if num_classes:
        macs += c_in * num_classes
    return macs


def resnet_forward_need(images: int, image_size: int,
                        stage_sizes: Sequence[int] = (3, 4, 6, 3),
                        input_bytes_per_value: int = 4,
                        param_count: float = 23.5e6,
                        param_bytes_per_value: int = 4) -> Dict[str, float]:
    """Operations and the unavoidable HBM traffic (input read once, weights
    read once) of ``images`` features-only forward passes in one batch."""
    return {"flops": 2.0 * images * resnet_forward_macs(image_size, stage_sizes),
            "hbm_bytes": float(images) * image_size * image_size * 3
            * input_bytes_per_value + param_count * param_bytes_per_value}


def resnet_forward_least_s(need: Dict[str, float],
                           peaks: Dict[str, float]) -> Tuple[float, str]:
    compute = need["flops"] / peaks["bf16_flops_per_s"]
    memory = need["hbm_bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "bf16 compute") if compute >= memory \
        else (memory, "HBM bandwidth")
