"""Plain reference of ResNet-50 v1.5 (He et al., arXiv:1512.03385 Table 1,
50-layer; stride 2 on the 3x3 convolution as torchvision places it): forward
pass in training mode (batch statistics) and mean cross-entropy, in
straightforward float32 ``jax.numpy``/``lax`` at ``highest`` precision.
Independent of bluefog_tpu/models/resnet.py; it only reads that model's
parameter tree by its names (stem, stem_bn, BottleneckBlock_i/{Conv_j,
BatchNorm_j, proj, proj_bn}, Dense_0).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
STAGES = (3, 4, 6, 3)


def _conv(x, kernel, stride, padding):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _bn(x, p, eps=1e-5):
    mu = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean((x - mu) ** 2, axis=(0, 1, 2))
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride):
    y = jax.nn.relu(_bn(_conv(x, p["Conv_0"]["kernel"], 1, "VALID"),
                        p["BatchNorm_0"]))
    y = jax.nn.relu(_bn(_conv(y, p["Conv_1"]["kernel"], stride,
                              [(1, 1), (1, 1)]), p["BatchNorm_1"]))
    y = _bn(_conv(y, p["Conv_2"]["kernel"], 1, "VALID"), p["BatchNorm_2"])
    if "proj" in p:
        x = _bn(_conv(x, p["proj"]["kernel"], stride, "VALID"), p["proj_bn"])
    return jax.nn.relu(x + y)


@functools.partial(jax.jit, static_argnames=("stages",))
def loss(params, images, labels, stages=STAGES):
    """params: the flax tree of models.ResNet50 (f32); images [B, H, W, 3];
    labels [B] int.  Mean softmax cross-entropy, f32."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = images.astype(jnp.float32)
    x = jax.nn.relu(_bn(_conv(x, p["stem"]["kernel"], 2, [(3, 3), (3, 3)]),
                        p["stem_bn"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    i = 0
    for stage, blocks in enumerate(stages):
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            x = _bottleneck(x, p[f"BottleneckBlock_{i}"], stride)
            i += 1
    x = jnp.mean(x, axis=(1, 2))
    lg = jnp.matmul(x, p["Dense_0"]["kernel"], precision=HIGHEST) \
        + p["Dense_0"]["bias"]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)
