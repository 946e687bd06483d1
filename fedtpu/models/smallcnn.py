"""Small CNN — the BASELINE.md config-2/3 model ("small CNN on CIFAR-10").

Two conv+pool stages and a two-layer dense head; no BatchNorm, so it is also
the simplest all-weights FedAvg target.

A max-pool stage is ``relu(max_pool(conv(x)) + bias)``: pooled first, then
the convolution's bias, then ReLU. A per-channel constant and ReLU are both
monotone, so this is ``max_pool(relu(conv(x) + bias))`` bit for bit in any
dtype. What differs is what the backward reads and keeps:

- ReLU first leaves a ReLU'd full-size copy of the convolution's output
  (``select_and_scatter``'s operand) and a full-size sign mask; pooled
  first, ``select_and_scatter`` takes the convolution's output as it is and
  ReLU and its mask act on a quarter of the elements (PERF.md §6, PR 30).
- The bias inside the pool makes its gradient a sum over
  ``select_and_scatter``'s full-size result, one more pass over a tensor the
  size of the convolution's output, though that result is the pooled
  cotangent scattered among zeros; added after the pool, the bias gradient is
  the sum of the pooled cotangent itself, a quarter of the size (PERF.md §6,
  PR 32).

The gradients are those of the same function. Against the bias-inside order
they differ at rounding level in two places: the bias gradient adds the same
non-zero terms in another order, and where two raw outputs of a window differ
but ``conv + bias`` rounds them to one value (not rare in bfloat16), the
bias-inside order routes the window's cotangent to the first of the tie and
this one to the larger raw output. Pinned by
``tests/test_smallcnn_pool_order.py``.

``smallcnn_avgpool`` is a NON-PARITY perf-ablation variant: identical
parameters (pools are parameter-free), with both max-pools replaced by
average pools. Max-pool's gradient lowers to ``select_and_scatter``, the
largest single op family of the local step (24 % of ``sim192``'s device
time: PERF.md §5) and the one both custom-VJP rewrites failed to beat (see
``fedtpu.models.common._tiled_max_pool``); avg-pool's gradient is a dense
broadcast with no scatter, so benching this variant bounds what
``select_and_scatter`` actually costs END-TO-END rather than by
trace-share arithmetic. Its stages stay ``avg_pool(relu(conv(x) + bias))``:
a mean does not commute with ReLU, and with the bias only up to rounding, so
pooling first would be another model.
"""

from __future__ import annotations

import flax.linen as nn
import jax
from flax.linen.dtypes import promote_dtype

from fedtpu.models.common import avg_pool, max_pool
from fedtpu.models.registry import register


class Conv(nn.Module):
    """A max-pool stage: 3x3 same-padded convolution, 2x2 max-pool, the
    convolution's bias, ReLU (module docstring).

    Named ``Conv``, with ``kernel`` and ``bias`` declared as ``nn.Conv``
    declares them, so that flax names it ``Conv_N`` and the parameter tree,
    its initial values at a key and every checkpoint are ``nn.Conv``'s.
    """

    features: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            nn.linear.default_kernel_init,
            (3, 3, x.shape[-1], self.features),
        )
        bias = self.param("bias", nn.initializers.zeros_init(), (self.features,))
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=None)
        y = jax.lax.conv_general_dilated(
            x,
            kernel,
            window_strides=(1, 1),
            padding=((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return nn.relu(max_pool(y, 2) + bias)


class SmallCNNModule(nn.Module):
    num_classes: int = 10
    pool: str = "max"  # max | avg

    @nn.compact
    def __call__(self, x, train: bool = False):
        for features in (32, 64):
            if self.pool == "max":
                x = Conv(features)(x)
            else:
                x = avg_pool(nn.relu(nn.Conv(features, (3, 3), padding=1)(x)), 2)
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128)(x))
        return nn.Dense(self.num_classes)(x)


@register("smallcnn")
def SmallCNN(num_classes: int = 10) -> nn.Module:
    return SmallCNNModule(num_classes=num_classes)


@register("smallcnn_avgpool")
def SmallCNNAvgPool(num_classes: int = 10) -> nn.Module:
    return SmallCNNModule(num_classes=num_classes, pool="avg")
