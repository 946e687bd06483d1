"""Local optimizer with reference-exact semantics.

The reference trainer uses torch ``SGD(lr, momentum=0.9, weight_decay=5e-4)``
with ``CosineAnnealingLR(T_max=200)`` (``src/main.py:99-101``). Two semantics
matter for parity and are easy to get wrong:

1. torch applies weight decay by adding ``wd * param`` to the gradient
   *before* the momentum buffer update (coupled, not AdamW-style decoupled).
2. The reference *persists* optimizer momentum across rounds inside each
   client process while *reloading* weights from the global checkpoint each
   round (``src/main.py:130-134`` reloads ``net``; ``optimizer`` is the module
   global from ``src/main.py:99``). fedtpu reproduces this by carrying the
   momentum buffers in per-client federated state (see
   :mod:`fedtpu.core.round`).

Implemented directly (not via optax.sgd) so the update order is explicit and
the state is a bare pytree of buffers — trivially vmappable over clients.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from fedtpu.config import OptimizerConfig

Pytree = Any


class SGDState(NamedTuple):
    momentum: Pytree  # same structure as params


def _momentum_dtype(cfg: Optional[OptimizerConfig]) -> jnp.dtype:
    name = "float32" if cfg is None else cfg.momentum_dtype
    if name not in ("float32", "bfloat16"):
        raise ValueError(
            f"unknown momentum_dtype {name!r}; have float32 | bfloat16"
        )
    return jnp.dtype(name)


def init(
    params: Pytree, cfg: Optional[OptimizerConfig] = None, buffers: bool = True
) -> SGDState:
    """Zero buffers in ``cfg.momentum_dtype`` (f32 when ``cfg`` is omitted —
    the reference-parity default). ``buffers=False`` keeps none: plain SGD
    at momentum 0 needs no copy of the model (:func:`apply` then takes the
    decayed gradient as the direction); the caller asks for it, because the
    buffers' tree is part of every checkpoint's layout."""
    dtype = _momentum_dtype(cfg)
    if not buffers:
        if cfg is None or cfg.momentum != 0:
            raise ValueError("no momentum buffers only at momentum 0")
        return SGDState(momentum=())
    return SGDState(
        momentum=jax.tree.map(lambda p: jnp.zeros(p.shape, dtype), params)
    )


def apply(
    params: Pytree,
    grads: Pytree,
    state: SGDState,
    lr,
    cfg: OptimizerConfig,
) -> Tuple[Pytree, SGDState]:
    """One torch-semantics SGD step. ``lr`` may be a traced scalar.

    With ``cfg.momentum_dtype='bfloat16'`` (non-parity, opt-in) the stored
    buffers are bf16 but the update math stays f32: the buffer is upcast,
    accumulated in f32, applied to the (f32) params, and only the STORED
    buffer is rounded — so the mode is exactly one bf16 round-trip per
    buffer per step, never a low-precision accumulation.
    """
    store_dtype = _momentum_dtype(cfg)
    decayed = jax.tree.map(lambda g, p: g + cfg.weight_decay * p, grads, params)
    if isinstance(state.momentum, tuple) and not state.momentum:
        # init(buffers=False): momentum 0, the direction is the gradient.
        return jax.tree.map(lambda p, d: p - lr * d, params, decayed), state
    new_buf = jax.tree.map(
        lambda b, g: cfg.momentum * b.astype(jnp.float32) + g,
        state.momentum, decayed,
    )
    if cfg.nesterov:
        direction = jax.tree.map(
            lambda g, b: g + cfg.momentum * b, decayed, new_buf
        )
    else:
        direction = new_buf
    new_params = jax.tree.map(lambda p, d: p - lr * d, params, direction)
    stored = jax.tree.map(lambda b: b.astype(store_dtype), new_buf)
    return new_params, SGDState(momentum=stored)
