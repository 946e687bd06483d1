"""Plain layers shared by the reference forward passes: jax.numpy, float32, NHWC.

Independent of the program under test: nothing here imports fedtpu or flax.
Parameters are nested dicts whose keys follow the published block order
(``Conv_i`` / ``BatchNorm_i`` / ``Dense_i`` numbered in call order inside a
block, ``BasicBlock_k`` in depth order), which is also how the program names
its leaves, so the harness can hand the same seeded weights to both.

``quant`` is the hook of the lower-precision control (``lowprec.py``): a
function applied to both operands of every convolution and matrix product.
The reference proper passes none.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BN_MOMENTUM = 0.9  # running = 0.9 * running + 0.1 * batch (torch momentum 0.1)
BN_EPS = 1e-5


def ident(x):
    return x


def conv(x, w, stride, pad, quant=ident):
    return jax.lax.conv_general_dilated(
        quant(x), quant(w), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def dense(x, p, quant=ident):
    return quant(x) @ quant(p["kernel"]) + p["bias"]


def max_pool2(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def batch_norm(x, p, s):
    """Training-mode BatchNorm: batch statistics (biased variance), and the
    running statistics a client hands back for averaging."""
    mean = x.mean(axis=(0, 1, 2))
    var = jnp.maximum(0.0, (x * x).mean(axis=(0, 1, 2)) - mean * mean)
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    new = {
        "mean": BN_MOMENTUM * s["mean"] + (1 - BN_MOMENTUM) * mean,
        "var": BN_MOMENTUM * s["var"] + (1 - BN_MOMENTUM) * var,
    }
    return y, new
