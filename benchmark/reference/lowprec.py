"""The lower-precision controls: what `correct` has to come out false for.

The configurations state bfloat16 activations over float32 master weights,
so the nearest precision below is 8-bit floating point: both operands of
every convolution and matrix product are rounded to float8_e4m3fn (scaled
per tensor to its range, as an fp8 training recipe would), the product is
accumulated in float32. For the codec the configuration states 4 bits; the
step below is 2.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FP8_MAX = 448.0  # largest finite float8_e4m3fn


@jax.custom_vjp
def fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


def _fwd(x):
    return fp8(x), None


def _bwd(_, g):
    # Straight-through: the backward products see rounded operands too,
    # because they are built from the rounded forward operands.
    return (g,)


fp8.defvjp(_fwd, _bwd)

