"""Plain reference for the image-featurizer configurations: the bottleneck
ResNet forward pass (He et al. 2015, "Deep Residual Learning for Image
Recognition") in straightforward ``jax.numpy`` and float32, features only.

It reads the same variables the program's module holds and shares no code
with it.  Departures from the paper and from torchvision, all following what
the program's module states: the stride of a down-sampling block sits on its
3x3 convolution (the "v1.5" placement torchvision also uses); strided
convolutions inside blocks pad ``SAME`` (one more pixel after than before, as
TensorFlow does, where torchvision pads one each side); batch normalisation
runs on its stored statistics with epsilon 1e-5; the input is scaled to [0, 1]
and normalised with the ImageNet channel statistics first.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
BN_EPSILON = 1e-5


def _conv(x, kernel, stride: int, padding):
    import jax
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, params: Dict[str, Any], stats: Dict[str, Any]):
    import jax.numpy as jnp
    inv = params["scale"] / jnp.sqrt(stats["var"] + BN_EPSILON)
    return (x - stats["mean"]) * inv + params["bias"]


def forward(variables: Dict[str, Any], images,
            stage_sizes: Sequence[int] = (3, 4, 6, 3)):
    """``(N, H, W, 3)`` pixel values in [0, 255] -> ``(N, 2048)`` pooled
    features, float32 throughout at full matmul precision."""
    import jax
    import jax.numpy as jnp
    p, s = variables["params"], variables["batch_stats"]
    with jax.default_matmul_precision("highest"):
        x = images.astype(jnp.float32) / 255.0
        x = (x - jnp.asarray(IMAGENET_MEAN)) / jnp.asarray(IMAGENET_STD)
        x = _conv(x, p["conv_init"]["kernel"], 2, [(3, 3), (3, 3)])
        x = jax.nn.relu(_bn(x, p["bn_init"], s["bn_init"]))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
        block = 0
        for i, count in enumerate(stage_sizes):
            for j in range(count):
                name = f"BottleneckBlock_{block}"
                bp, bs = p[name], s[name]
                stride = 2 if i > 0 and j == 0 else 1
                y = _conv(x, bp["Conv_0"]["kernel"], 1, "SAME")
                y = jax.nn.relu(_bn(y, bp["BatchNorm_0"], bs["BatchNorm_0"]))
                y = _conv(y, bp["Conv_1"]["kernel"], stride, "SAME")
                y = jax.nn.relu(_bn(y, bp["BatchNorm_1"], bs["BatchNorm_1"]))
                y = _conv(y, bp["Conv_2"]["kernel"], 1, "SAME")
                y = _bn(y, bp["BatchNorm_2"], bs["BatchNorm_2"])
                if "conv_proj" in bp:
                    x = _conv(x, bp["conv_proj"]["kernel"], stride, "SAME")
                    x = _bn(x, bp["norm_proj"], bs["norm_proj"])
                x = jax.nn.relu(x + y)
                block += 1
        return jnp.mean(x, axis=(1, 2))


def relative_l2(got, want):
    """Per-row ``|got - want| / |want|``."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
