"""Shared building blocks for the CIFAR zoo.

All models take NHWC inputs (TPU-friendly layout: the channel dimension lands
on the 128-wide lane axis) and return ``[batch, num_classes]`` logits. Batch
statistics live in a ``batch_stats`` collection so that, under FedAvg, they are
part of the aggregated state exactly as the reference averages BN running
stats alongside weights (``src/server.py:163-171``).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any

# Conv with PyTorch-style default initialisation is unnecessary; flax defaults
# (lecun_normal) are fine for parity-by-accuracy. Bias-free convs before BN
# mirror the reference blocks (e.g. src/models/mobilenet.py:15-20).
conv3x3 = partial(nn.Conv, kernel_size=(3, 3), use_bias=False, padding=1)
conv1x1 = partial(nn.Conv, kernel_size=(1, 1), use_bias=False, padding=0)


class BatchNorm(nn.BatchNorm):
    """``nn.BatchNorm`` with compute-dtype-safe normalization.

    flax's ``nn.BatchNorm`` upcasts the WHOLE activation to f32 for the
    statistics reduction and keeps every activation-sized elementwise op
    (``x - mean``, ``y * mul``, ``y + bias``) in f32, casting only the
    final output back — under ``RoundConfig.dtype="bfloat16"`` that made
    BN intermediates ~73% of the analytic per-round bytes on the BN-dense
    zoo (DenseNet/ResNet), erasing the residency lever this knob exists
    for. Here the statistics stay in f32 (stability; running stats remain
    f32 exactly as flax keeps them) but the feature-sized ``mean``/``mul``
    are cast to ``x.dtype`` BEFORE the activation-sized math, so the
    normalize runs in the compute dtype. For f32 inputs every cast is a
    no-op and the op sequence matches flax's fast-variance path exactly —
    bit-identical, pinned by tests/test_mixed_precision.py. The subclass
    keeps the class name so flax auto-naming (``BatchNorm_N``) and hence
    param/batch_stats trees and checkpoints are unchanged.

    Supports only the configuration :func:`batch_norm` constructs (no
    ``axis_name``/``mask``/custom ``axis``/``dtype`` — asserted below).
    """

    @nn.compact
    def __call__(self, x, use_running_average: bool | None = None):
        assert (
            self.axis == -1 and self.axis_name is None and self.dtype is None
            and self.use_bias and self.use_scale and self.use_fast_variance
        ), "compute-dtype-safe BatchNorm supports batch_norm() defaults only"
        use_running_average = nn.merge_param(
            "use_running_average", self.use_running_average, use_running_average
        )
        feature_shape = (x.shape[-1],)
        reduction_axes = tuple(range(x.ndim - 1))
        ra_mean = self.variable(
            "batch_stats", "mean", lambda s: jnp.zeros(s, jnp.float32),
            feature_shape,
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda s: jnp.ones(s, jnp.float32),
            feature_shape,
        )
        if use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            # f32 statistics, exactly flax's fast-variance formulation.
            xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
            mean = xf.mean(reduction_axes)
            mean2 = jax.lax.square(xf).mean(reduction_axes)
            var = jnp.maximum(0.0, mean2 - jax.lax.square(mean))
            if not self.is_initializing():
                ra_mean.value = (
                    self.momentum * ra_mean.value + (1 - self.momentum) * mean
                )
                ra_var.value = (
                    self.momentum * ra_var.value + (1 - self.momentum) * var
                )
        scale = self.param(
            "scale", self.scale_init, feature_shape, self.param_dtype
        )
        bias = self.param(
            "bias", self.bias_init, feature_shape, self.param_dtype
        )
        y = x - mean.astype(x.dtype)
        mul = jax.lax.rsqrt(var + self.epsilon) * scale
        y = y * mul.astype(x.dtype)
        return y + bias.astype(x.dtype)


def batch_norm(train: bool) -> nn.Module:
    """BatchNorm matching torch ``nn.BatchNorm2d`` defaults: torch momentum
    0.1 corresponds to flax momentum 0.9 (flax keeps
    ``momentum * old + (1 - momentum) * new``)."""
    return BatchNorm(use_running_average=not train, momentum=0.9, epsilon=1e-5)


def maybe_remat(block_cls, remat: bool):
    """Per-block rematerialisation wrapper (the HBM-for-FLOPs trade; see
    ``RoundConfig.remat``). ``static_argnums=(2,)`` marks the ``train`` flag
    static in ``__call__(self, x, train)``. Callers MUST pin the module
    ``name=`` explicitly: ``nn.remat`` renames modules to
    ``Checkpoint<Block>_N``, which would split the init RNG tree differently
    and break checkpoint compatibility with the non-remat form."""
    if not remat:
        return block_cls
    return nn.remat(block_cls, static_argnums=(2,))


def global_avg_pool(x: jnp.ndarray) -> jnp.ndarray:
    """Mean over the spatial dims of an NHWC tensor."""
    return jnp.mean(x, axis=(1, 2))


def max_pool(x, window: int, stride: int | None = None, padding: str = "VALID"):
    stride = stride or window
    if (
        os.environ.get("FEDTPU_TILED_POOL", "0") == "1"
        and stride == window
        and padding == "VALID"
        and x.ndim == 4
        and x.shape[1] % window == 0
        and x.shape[2] % window == 0
    ):
        return _tiled_max_pool(x, window)
    return nn.max_pool(x, (window, window), strides=(stride, stride), padding=padding)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tiled_max_pool(x, k: int):
    """Non-overlapping NHWC max-pool as transpose-free two-stage reshape+max.

    ``nn.max_pool``'s gradient lowers to ``select_and_scatter``, which the
    round-4 on-chip traces measured as the single largest op family in the
    fused round dispatch (~34% at the bf16 bench config,
    ``artifacts/MFU_PROFILE_r04_bf16.json``). OPT-IN via
    ``FEDTPU_TILED_POOL=1`` and kept as a twice-measured NEGATIVE result:
    despite that trace line, both reformulations LOST end-to-end on the
    real chip (``moveaxis``-flattened windows: 399 vs 598
    client-epochs/s/chip; this transpose-free two-stage version: 380 vs
    598) — the custom VJP is opaque to XLA's fusion and its argmax
    residuals add HBM traffic that ``select_and_scatter``, for all its op
    time, does not pay. Here the windowed view ``[N, H/k, k, W/k, k, C]``
    is a FREE reshape (row-major compatible); forward is
    ``max`` over the two window axes in turn, and the custom VJP routes the
    cotangent with one-hot ``argmax`` masks per stage. Two-stage first-max
    composes to FIRST max in row-major window order — the row holding the
    window max is the first row whose row-max equals it — matching both
    ``select_and_scatter`` and torch's ``MaxPool2d`` at ties (common right
    after ReLU), so forward AND backward are bit-identical to the
    ``nn.max_pool`` formulation.
    """
    n, h, w, c = x.shape
    return x.reshape(n, h // k, k, w // k, k, c).max(axis=(2, 4))


def _tiled_max_pool_fwd(x, k: int):
    n, h, w, c = x.shape
    xw = x.reshape(n, h // k, k, w // k, k, c)
    rowmax = xw.max(axis=4)                      # [n, h/k, k, w/k, c]
    colidx = jnp.argmax(xw, axis=4)
    rowidx = jnp.argmax(rowmax, axis=2)          # [n, h/k, w/k, c]
    return rowmax.max(axis=2), (rowidx, colidx, x.shape)


def _tiled_max_pool_bwd(k: int, res, g):
    rowidx, colidx, (n, h, w, c) = res
    win = jnp.arange(k, dtype=rowidx.dtype)
    zero = jnp.zeros((), g.dtype)
    # Stage 1: route g to the selected row of each window.
    rmask = win[None, None, :, None, None] == rowidx[:, :, None, :, :]
    g_row = jnp.where(rmask, g[:, :, None, :, :], zero)  # [n,h/k,k,w/k,c]
    # Stage 2: route each row's share to its selected column.
    cmask = win[None, None, None, None, :, None] == colidx[:, :, :, :, None, :]
    g_xw = jnp.where(cmask, g_row[:, :, :, :, None, :], zero)
    return (g_xw.reshape(n, h, w, c),)


_tiled_max_pool.defvjp(_tiled_max_pool_fwd, _tiled_max_pool_bwd)


def avg_pool(x, window: int, stride: int | None = None, padding: str = "VALID"):
    stride = stride or window
    return nn.avg_pool(x, (window, window), strides=(stride, stride), padding=padding)
