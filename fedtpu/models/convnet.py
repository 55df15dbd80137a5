"""2-layer ConvNet for the CIFAR-10 FedAvg stress config (BASELINE.json #5).

No reference analogue — the reference is tabular-only (SURVEY.md §5,
"long-context" bullet). This model exists to stress the FedAvg aggregation
payload (~1M params vs the income MLP's ~11K) and the MXU conv path.

Architecture: [Conv3x3 -> MaxPool2x2 -> +bias -> ReLU] x len(conv_channels)
-> flatten -> Dense(hidden) -> ReLU -> Dense(classes). NHWC layout (TPU
native); convs via lax.conv_general_dilated so XLA tiles them onto the MXU.

Each block computes ``relu(maxpool(conv(h, w)) + b)``. Adding a per-channel
bias (with its rounding) and ReLU are monotone non-decreasing, so the max
commutes with them: the values equal ``maxpool(relu(conv(h, w) + b))`` bit
for bit in any floating-point format. Pooling first lets the backward pass
read the convolution's own output as the pool's operand, where the other
order keeps a second full-resolution (post-ReLU) copy of it for that.

The gradient is the same subgradient. A window whose maximum is <= -b gets
zero both ways; a strict maximum gets the whole gradient both ways. Tie
convention: where two distinct conv outputs round to one value once the
bias is added (common in bf16), the gradient goes to the larger conv output,
the true argmax; values that are equal before the bias go to the first in
window order, as ``reduce_window``'s backward does.
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def _conv_init(key, kh, kw, cin, cout, dtype):
    fan_in = kh * kw * cin
    bound = 1.0 / math.sqrt(fan_in)
    wk, bk = jax.random.split(key)
    return {
        "w": jax.random.uniform(wk, (kh, kw, cin, cout), dtype, -bound, bound),
        "b": jax.random.uniform(bk, (cout,), dtype, -bound, bound),
    }


def convnet_init(key: jax.Array, image_shape: Tuple[int, int, int],
                 conv_channels: Sequence[int], hidden: int, num_classes: int,
                 param_dtype=jnp.float32):
    h, w, cin = image_shape
    convs = []
    for cout in conv_channels:
        key, sub = jax.random.split(key)
        convs.append(_conv_init(sub, 3, 3, cin, cout, param_dtype))
        cin = cout
        h, w = h // 2, w // 2  # maxpool 2x2 per block
    flat = h * w * cin
    key, k1, k2, k3, k4 = jax.random.split(key, 5)
    b1 = 1.0 / math.sqrt(flat)
    b2 = 1.0 / math.sqrt(hidden)
    return {
        "convs": convs,
        "dense": {"w": jax.random.uniform(k1, (flat, hidden), param_dtype, -b1, b1),
                  "b": jax.random.uniform(k2, (hidden,), param_dtype, -b1, b1)},
        "head": {"w": jax.random.uniform(k3, (hidden, num_classes), param_dtype, -b2, b2),
                 "b": jax.random.uniform(k4, (num_classes,), param_dtype, -b2, b2)},
    }


def _maxpool2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1),
                             "VALID")


def convnet_apply(params, x: jax.Array, compute_dtype=None) -> jax.Array:
    """x: (N, H, W, C) or (N, H*W*C) flattened -> logits (N, classes)."""
    out_dtype = params["head"]["w"].dtype
    cast = (lambda a: a.astype(compute_dtype)) if compute_dtype else (lambda a: a)
    if x.ndim == 2:  # packed flat by the tabular-style pipeline
        first = params["convs"][0]["w"]
        cin = first.shape[2]
        side = int(math.isqrt(x.shape[1] // cin))
        x = x.reshape(x.shape[0], side, side, cin)
    h = cast(x)
    for conv in params["convs"]:
        h = lax.conv_general_dilated(
            h, cast(conv["w"]), window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = jax.nn.relu(_maxpool2(h) + cast(conv["b"]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ cast(params["dense"]["w"]) + cast(params["dense"]["b"]))
    h = h @ cast(params["head"]["w"]) + cast(params["head"]["b"])
    return h.astype(out_dtype)
