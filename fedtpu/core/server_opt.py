"""Server-side optimization of the aggregated update (the FedOpt family).

The reference server applies the uniform mean of client states directly as
the new global model (``src/server.py:163-179``) — that is FedAvg, i.e.
``server_optimizer="none"``. This module adds the standard generalisation
(Reddi et al., "Adaptive Federated Optimization", 2021): treat the mean
client delta as a pseudo-gradient and feed it to a server optimizer —
SGD+momentum ("FedAvgM") or Adam ("FedAdam"). Runs inside the jitted round
step; its state (server momentum / Adam moments over the GLOBAL model, not
per-client) rides in ``FederatedState.server_opt_state`` and is replicated
across mesh shards.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import optax

from fedtpu.config import FedConfig

Pytree = Any


def make_server_optimizer(fed: FedConfig) -> Optional[optax.GradientTransformation]:
    """The optax transform for ``fed.server_optimizer``, or None for plain
    FedAvg (apply the mean delta directly — reference semantics)."""
    if fed.server_optimizer == "none":
        return None
    if fed.server_optimizer == "momentum":
        return optax.sgd(fed.server_lr, momentum=fed.server_momentum)
    if fed.server_optimizer == "adam":
        return optax.adam(
            fed.server_lr, b1=fed.server_momentum, b2=fed.server_beta2,
            eps=fed.server_eps,
        )
    if fed.server_optimizer == "yogi":
        return optax.yogi(
            fed.server_lr, b1=fed.server_momentum, b2=fed.server_beta2,
            eps=fed.server_eps,
        )
    raise ValueError(
        f"unknown server_optimizer {fed.server_optimizer!r}; "
        "have none | momentum | adam | yogi"
    )


def init(fed: FedConfig, params: Pytree) -> Pytree:
    """Initial ``server_opt_state`` — the empty pytree for plain FedAvg."""
    opt = make_server_optimizer(fed)
    return () if opt is None else opt.init(params)


@jax.named_scope("fed.server_step")
def apply(
    opt: Optional[optax.GradientTransformation],
    params: Pytree,
    mean_delta: Pytree,
    opt_state: Pytree,
) -> Tuple[Pytree, Pytree]:
    """New global params from the aggregated delta.

    ``opt=None``: ``params + mean_delta`` (FedAvg). Otherwise the delta's
    negation is the pseudo-gradient (optax descends, FedOpt ascends along the
    delta); with ``sgd(lr=1, momentum=0)`` this reduces exactly to FedAvg.
    """
    from fedtpu.utils import trees

    if opt is None:
        return trees.tree_add(params, mean_delta), opt_state
    grad = jax.tree.map(lambda d: -d, mean_delta)
    updates, new_state = opt.update(grad, opt_state, params)
    return optax.apply_updates(params, updates), new_state
