"""Small CNN — the BASELINE.md config-2/3 model ("small CNN on CIFAR-10").

Two conv+pool stages and a two-layer dense head; no BatchNorm, so it is also
the simplest all-weights FedAvg target.

A max-pool stage is ``relu(max_pool(conv(x)))``: pooled first, ReLU'd after.
Both are monotone, so this is ``max_pool(relu(conv(x)))`` bit for bit in any
dtype, and the gradients are equal too (a window whose maximum is not
positive passes nothing back in either order; a positive maximum routes to
the same first maximum). What differs is what the backward keeps: ReLU first
leaves a ReLU'd full-size copy of the convolution's output
(``select_and_scatter``'s operand) and a full-size sign mask; pooled first,
``select_and_scatter`` takes the convolution's output as it is and ReLU and
its mask act on a quarter of the elements (on the chip: PERF.md §6, PR 30;
pinned by ``tests/test_smallcnn_pool_order.py``).

``smallcnn_avgpool`` is a NON-PARITY perf-ablation variant: identical
parameters (pools are parameter-free), with both max-pools replaced by
average pools. Max-pool's gradient lowers to ``select_and_scatter``, the
largest single op family in the round-4 on-chip traces
(``artifacts/MFU_PROFILE_r04_bf16.json``, ~34% of the fused dispatch) and
the one both custom-VJP rewrites failed to beat (see
``fedtpu.models.common._tiled_max_pool``); avg-pool's gradient is a dense
broadcast with no scatter, so benching this variant bounds what
``select_and_scatter`` actually costs END-TO-END rather than by
trace-share arithmetic. Its stages stay ``avg_pool(relu(conv(x)))``: a mean
does not commute with ReLU, so pooling first would be another model.
"""

from __future__ import annotations

import flax.linen as nn

from fedtpu.models.common import avg_pool, max_pool
from fedtpu.models.registry import register


def _relu_and_pool(y, pool: str):
    """ReLU and 2x2 pool of a convolution's output, in the order that leaves
    the backward least to keep (module docstring)."""
    if pool == "max":
        return nn.relu(max_pool(y, 2))
    return avg_pool(nn.relu(y), 2)


class SmallCNNModule(nn.Module):
    num_classes: int = 10
    pool: str = "max"  # max | avg

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = _relu_and_pool(nn.Conv(32, (3, 3), padding=1)(x), self.pool)
        x = _relu_and_pool(nn.Conv(64, (3, 3), padding=1)(x), self.pool)
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128)(x))
        return nn.Dense(self.num_classes)(x)


@register("smallcnn")
def SmallCNN(num_classes: int = 10) -> nn.Module:
    return SmallCNNModule(num_classes=num_classes)


@register("smallcnn_avgpool")
def SmallCNNAvgPool(num_classes: int = 10) -> nn.Module:
    return SmallCNNModule(num_classes=num_classes, pool="avg")
