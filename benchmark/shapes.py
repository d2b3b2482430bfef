"""What the algorithms need, from their shapes alone: operations and bytes of
one GBDT boosting iteration, of one ResNet forward pass and of a causal
language model's decode steps and prefills, and the least time a chip with
given peaks could take for them.  A roofline share is this least
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


# ------------------------------------------------- causal language models

def causal_lm_params(layers: int, width: int, mlp: int, vocab: int,
                     positions: int, untied_head: bool = True
                     ) -> Dict[str, float]:
    """Parameters of a GPT-2-shaped decoder (Radford et al. 2019): per layer
    the fused QKV (``3 w^2 + 3 w``), the attention's output projection
    (``w^2 + w``), two MLP matrices (``2 w mlp + mlp + w``) and two
    LayerNorms (``4 w``); a token and a learned position embedding; a final
    LayerNorm; and, where the head is not tied to the token embedding, a
    ``w x vocab`` head with its bias.  ``matmul`` counts the parameters that
    every token multiplies (the layers' matrices and the head's): two
    operations a token each.  GPT-2 XL with an untied head: 1,638,072,657 in
    all, 1,554,971,200 in matmuls."""
    per_layer = 4 * width * width + 2 * width * mlp \
        + (3 * width + width) + (mlp + width) + 4 * width
    head = width * vocab + vocab if untied_head else 0
    total = layers * per_layer + vocab * width + positions * width \
        + 2 * width + head
    matmul = layers * (4 * width * width + 2 * width * mlp) + width * vocab
    return {"total": float(total), "matmul": float(matmul)}


def causal_lm_need(sizes: Dict[str, float]) -> Dict[str, float]:
    """What the shares of a causal language model's cells are priced from,
    out of a configuration's ``sizes`` (``layers``, ``width``, ``mlp``,
    ``vocab``, ``positions``, ``bytes_per_value``, ``untied_head``)."""
    params = causal_lm_params(sizes["layers"], sizes["width"], sizes["mlp"],
                              sizes["vocab"], sizes["positions"],
                              sizes.get("untied_head", True))
    return {"param_bytes": params["total"] * sizes["bytes_per_value"],
            "matmul_params": params["matmul"],
            "kv_token_bytes": float(kv_bytes_per_token(
                sizes["layers"], sizes["width"], sizes["bytes_per_value"])),
            "layers": sizes["layers"], "width": sizes["width"]}


def kv_bytes_per_token(layers: int, width: int, bytes_per_value: int) -> int:
    """Bytes of keys and values one position holds over all layers (full
    multi-head attention: a key and a value of the model's width a layer)."""
    return layers * 2 * width * bytes_per_value


def decode_steps_least_bytes(steps: float, context_tokens: float,
                             param_bytes: float, kv_token_bytes: float
                             ) -> float:
    """The least HBM traffic of ``steps`` decode steps: every step reads the
    weights once, and every sequence alive in a step reads its keys and
    values at its TRUE length (``context_tokens`` is the sum, over the steps
    and the sequences alive in each, of the positions attended to).  What an
    ideal paged read would move: no table width, no page padding, no copy of
    the pool."""
    return steps * param_bytes + context_tokens * kv_token_bytes


def causal_lm_flops(matmul_params: float, tokens: float,
                    attended_positions: float, layers: int, width: int
                    ) -> float:
    """Operations of ``tokens`` positions through the model (generated and
    prefilled alike, at their true count): two per matmul parameter a
    position, and for attention two products (scores, and the weighted sum
    of values) of ``width`` multiply-accumulates per layer for every
    position attended to (``attended_positions``: the sum over the
    positions of their causal context)."""
    return 2.0 * matmul_params * tokens \
        + 4.0 * layers * width * attended_positions


def prefill_attended_positions(length: int) -> float:
    """Sum of the causal contexts of a prompt's positions: 1 + 2 + ... + n."""
    return length * (length + 1) / 2.0


def least_s(flops: float, hbm_bytes: float, peaks: Dict[str, float]
            ) -> Tuple[float, str]:
    compute = flops / peaks["bf16_flops_per_s"]
    memory = hbm_bytes / peaks["hbm_bytes_per_s"]
    return (compute, "bf16 compute") if compute >= memory \
        else (memory, "HBM bandwidth")
