"""Loss primitives shaped for the TPU backend.

``optax.softmax_cross_entropy_with_integer_labels`` selects each example's
label logit with ``take_along_axis`` — a one-element-per-row gather whose
XLA:TPU lowering is a SERIAL per-example slice loop, with a matching scatter
in the backward pass. At the bench config (64 clients x 128 batch, vmapped)
that is 8192 serial iterations per training step; the round-4 on-chip trace
(`artifacts/MFU_PROFILE_r04_presharded.json`) shows these loops, together
with the per-example crop gather, dominating the fused-round dispatch.

The one-hot contraction below computes the same value as a dense reduction
(VPU/MXU-friendly, fuses into the log-softmax) and its backward is a dense
broadcast instead of a scatter. Exactness: the selection itself is exact
(``1.0 * logp[label] + 0.0 * rest``; adding f32 zeros preserves bits), so
any deviation from the gather formulation comes only from softmax
accumulation order — measured <= 5e-10 on f32 gradients, 1e-6 on the
forward (pinned in ``tests/test_tpu_formulations.py``). As with every
zero-weight selection identity in this codebase (see
``fedtpu.data.augment``), it requires FINITE logits: ``0.0 * inf = nan``.

Parity: the loss itself matches the reference's ``nn.CrossEntropyLoss()``
(`/root/reference/src/main.py:77`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def softmax_ce_int_labels(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Per-example softmax cross-entropy with integer labels.

    ``logits: [..., C]`` (f32), ``labels: [...]`` int. Returns ``[...]`` f32.
    Same contract as ``optax.softmax_cross_entropy_with_integer_labels`` but
    gather-free (see module docstring): delegates to optax's DENSE-label CE,
    which contracts against the one-hot instead of gathering.
    """
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    return optax.softmax_cross_entropy(logits, onehot)


def next_token_ce_parts(logits: jnp.ndarray, targets: jnp.ndarray):
    """Next-token cross-entropy over a slice of the vocabulary, as ``(sum,
    count, hits)``: ``logits [..., T, V]`` over the ``V`` rows held here,
    ``targets [..., T]`` int ids in ``[0, V)`` or negative where a position
    has no target (a packed row's end). Float32 log-softmax over the slice;
    the mean loss is ``sum / count``, and a step taken in blocks adds the
    sums and the counts. ``hits`` counts the positions whose largest logit is
    the target."""
    valid = targets >= 0
    safe = jnp.where(valid, targets, 0)
    nll = softmax_ce_int_labels(logits.astype(jnp.float32), safe)
    hits = (jnp.argmax(logits, -1) == safe) & valid
    return (
        jnp.sum(jnp.where(valid, nll, 0.0)),
        jnp.sum(valid, dtype=jnp.float32),
        jnp.sum(hits, dtype=jnp.float32),
    )


def shift_targets(targets: jnp.ndarray, by: int) -> jnp.ndarray:
    """The targets of a head that predicts ``by`` tokens further: the row
    moved left, its end without targets."""
    if by == 0:
        return targets
    pad = jnp.full(targets.shape[:-1] + (by,), -1, targets.dtype)
    return jnp.concatenate([targets[..., by:], pad], axis=-1)
