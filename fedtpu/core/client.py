"""Per-client local training.

The reference runs one local epoch per round per client process: reload the
global checkpoint, iterate the round-robin-sharded loader, forward/backward/
SGD-step per batch, save weights (``src/main.py:128-165``). fedtpu's
equivalent is a pure function of (global model, persistent client state, the
round's batches): a ``lax.scan`` over local steps that XLA compiles into one
fused program, designed to sit under ``jax.vmap`` with the leading ``clients``
axis mapped — every simulated client trains simultaneously on its own slice of
the mesh.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from fedtpu.config import RoundConfig
from fedtpu.core import optim
from fedtpu.ops.losses import softmax_ce_int_labels
from fedtpu.utils import trees

Pytree = Any


class ClientOutput(NamedTuple):
    params: Pytree       # locally-updated weights
    batch_stats: Pytree  # locally-updated BN running stats
    opt_state: optim.SGDState
    loss: jnp.ndarray    # mean masked loss over the round
    accuracy: jnp.ndarray
    num_steps: jnp.ndarray


def make_local_update(
    apply_fn: Callable,
    cfg: RoundConfig,
    stream: bool = False,
    image_shape: Optional[Tuple[int, ...]] = None,
) -> Callable:
    """Build the single-client local-epoch function.

    ``apply_fn(variables, x, train, mutable)`` is the flax ``Module.apply``.
    The returned function is pure and vmappable:

        local_update(global_params, global_stats, opt_state, xs, ys,
                     step_mask, rng, round_idx) -> ClientOutput

    with ``xs: [steps, batch, ...]``, ``ys: [steps, batch]``,
    ``step_mask: [steps]`` (False steps are no-ops so ragged shards keep
    static shapes).

    With ``stream`` set the signature becomes

        local_update(global_params, global_stats, opt_state, images, labels,
                     takes, step_mask, rng, round_idx)

    and each scan step extracts ITS batch only, so the round never
    materialises the full ``[steps, batch, ...]`` tensor — the HBM lever
    that (with remat) fits 64-client resnet18 rounds on one chip (see
    BASELINE.md config 4 / tools/compile_pallas_tpu.py). Two forms:
    ``stream="gather"`` (alias ``True``): ``takes: [steps, batch]`` int32
    indices into the flat device-resident dataset. ``stream="presharded"``:
    ``images``/``labels`` are THIS client's presharded rows ``[2L, ...]``
    (:func:`fedtpu.data.device.preshard_arrays`) and ``takes: [steps]``
    per-step slice offsets — the extraction is a contiguous ``dynamic_slice``
    instead of a row-gather (the measured ~100x per-byte difference on TPU;
    see ``fedtpu/data/device.py``).
    """
    if stream is True:
        stream = "gather"
    mu = cfg.fed.fedprox_mu if cfg.fed.algorithm == "fedprox" else 0.0
    dtype = jnp.dtype(cfg.dtype)
    # Random crop + flip for CIFAR-style training, fused into the jitted step
    # (the reference augments on the host via torchvision, src/main.py:37-42).
    use_augment = cfg.data.augment and cfg.data.dataset in ("cifar10", "cifar100")

    def loss_fn(params, batch_stats, global_params, x, y, rng):
        # Cast to the compute dtype BEFORE augmentation: the crop/flip are
        # pure selections (exact in any dtype) and the model consumes
        # compute-dtype activations anyway, so augmenting in bf16 is
        # bit-identical to augment-then-cast while halving the augment
        # pipeline's HBM traffic — the largest elementwise fusions in the
        # round-4 on-chip trace (artifacts/MFU_PROFILE_r04_fastcrop.json).
        x = x.astype(dtype)
        if use_augment:
            from fedtpu.data.augment import augment_batch

            with jax.named_scope("fed.data"):
                aug_rng, rng = jax.random.split(rng)
                x = augment_batch(aug_rng, x, crop=cfg.data.augment_crop)
        # True mixed precision: master params stay f32 in FederatedState;
        # casting them (not just x) at use keeps the WHOLE forward in the
        # compute dtype — flax layers otherwise promote bf16 activations
        # back to f32 against f32 kernels, silently doubling activation HBM
        # and halving MXU rate. Gradients flow through the cast and come out
        # f32. BN running stats stay f32 (they are outputs in train mode).
        if dtype != jnp.float32:
            cast = jax.tree.map(lambda p: p.astype(dtype), params)
        else:
            cast = params
        variables = {"params": cast, "batch_stats": batch_stats}
        logits, updated = apply_fn(
            variables,
            x,
            train=True,
            mutable=["batch_stats"],
            rngs={"dropout": rng},
        )
        logits = logits.astype(jnp.float32)
        ce = softmax_ce_int_labels(logits, y).mean()
        loss = ce
        if mu > 0.0:
            # FedProx proximal term: mu/2 * ||w - w_global||^2 keeps local
            # iterates near the round's global model (BASELINE config 3).
            loss = loss + 0.5 * mu * trees.tree_sq_norm(
                trees.tree_sub(params, global_params)
            )
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        return loss, (updated.get("batch_stats", batch_stats), ce, acc)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    @jax.named_scope("fed.local_step")
    def _run_scan(
        global_params, global_stats, opt_state, step_elems, get_xy,
        steps, step_mask, rng, round_idx, anchor=None,
    ) -> ClientOutput:
        # FedProx proximal ANCHOR: defaults to the scan's initial params
        # (synchronous rounds start from the global model, so the two
        # coincide). The async engine passes the client's last-PULLED global
        # explicitly — its scan starts from the client's own diverged
        # trajectory, and anchoring there would make mu a per-tick no-op.
        anchor = global_params if anchor is None else anchor
        lr = cfg.opt.lr_at(round_idx)

        def one_step(carry, batch):
            params, stats, ostate = carry
            elem, live, step_rng = batch
            with jax.named_scope("fed.data"):
                x, y = get_xy(elem)
            with jax.named_scope("fed.local_step.fwd_bwd"):
                (loss, (new_stats, ce, acc)), grads = grad_fn(
                    params, stats, anchor, x, y, step_rng
                )
            if cfg.debug_per_batch:
                # Reference parity (src/utils.py:51-92): per-batch loss/acc
                # lines mid-epoch. A host callback per batch — debugging
                # only; under vmap one line prints per client per batch.
                jax.debug.print(
                    "  batch: loss {l:.4f} acc {a:.4f}", l=ce, a=acc
                )
            with jax.named_scope("fed.local_step.optimizer"):
                new_params, new_ostate = optim.apply(
                    params, grads, ostate, lr, cfg.opt
                )
                # Masked steps (padding of ragged shards / dead clients)
                # change nothing — the reference equivalent is the client
                # simply not having that batch.
                live_f = live.astype(jnp.float32)
                params = jax.tree.map(
                    lambda new, old: jnp.where(live, new, old),
                    new_params, params,
                )
                stats = jax.tree.map(
                    lambda new, old: jnp.where(live, new, old),
                    new_stats, stats,
                )
                ostate = jax.tree.map(
                    lambda new, old: jnp.where(live, new, old),
                    new_ostate, ostate,
                )
            return (params, stats, ostate), (ce * live_f, acc * live_f, live_f)

        step_rngs = jax.random.split(rng, steps)
        (params, stats, ostate), (ces, accs, lives) = jax.lax.scan(
            one_step,
            (global_params, global_stats, opt_state),
            (step_elems, step_mask, step_rngs),
        )
        n = jnp.maximum(jnp.sum(lives), 1.0)
        return ClientOutput(
            params=params,
            batch_stats=stats,
            opt_state=ostate,
            loss=jnp.sum(ces) / n,
            accuracy=jnp.sum(accs) / n,
            num_steps=jnp.sum(lives),
        )

    if stream:
        shape = tuple(image_shape or cfg.image_size)
        batch_size = cfg.data.batch_size

        def local_update(
            global_params: Pytree,
            global_stats: Pytree,
            opt_state: optim.SGDState,
            images: jnp.ndarray,
            labels: jnp.ndarray,
            takes: jnp.ndarray,
            step_mask: jnp.ndarray,
            rng: jax.Array,
            round_idx: jnp.ndarray,
            anchor: Pytree = None,
        ) -> ClientOutput:
            if stream == "presharded":
                # images/labels are THIS client's [2L, ...] presharded rows;
                # each scan step slices its [batch]-sized window at the
                # step's offset — one contiguous DMA, no gather.
                f_tail = tuple(images.shape[1:])

                def get_xy(o):
                    x = jax.lax.dynamic_slice(
                        images, (o,) + (0,) * len(f_tail),
                        (batch_size,) + f_tail,
                    )
                    if x.ndim == 2:
                        x = x.reshape((batch_size,) + shape)
                    y = jax.lax.dynamic_slice(labels, (o,), (batch_size,))
                    return x, y

            else:
                # Each scan step gathers only its own [batch]-sized slice
                # from the device-resident dataset — nothing
                # [steps, batch, ...]-sized ever exists. The dataset may
                # arrive FLATTENED ([N, H*W*C]): NHWC image tensors pad ~4x
                # under TPU tiled layouts, flat rows tile exactly; the
                # per-batch reshape after the gather is free.
                def get_xy(t):
                    x = images[t]
                    if x.ndim == 2:
                        x = x.reshape((t.shape[0],) + shape)
                    return x, labels[t]

            return _run_scan(
                global_params, global_stats, opt_state,
                takes, get_xy,
                takes.shape[0], step_mask, rng, round_idx, anchor,
            )

    else:

        def local_update(
            global_params: Pytree,
            global_stats: Pytree,
            opt_state: optim.SGDState,
            xs: jnp.ndarray,
            ys: jnp.ndarray,
            step_mask: jnp.ndarray,
            rng: jax.Array,
            round_idx: jnp.ndarray,
            anchor: Pytree = None,
        ) -> ClientOutput:
            return _run_scan(
                global_params, global_stats, opt_state,
                (xs, ys), lambda e: e,
                xs.shape[0], step_mask, rng, round_idx, anchor,
            )

    return local_update


def batch_eval_arrays(images, labels, batch_size: int):
    """Shape an eval set into ``[num_batches, batch, ...]`` for the jitted
    evaluator, dropping the ragged tail. Raises (rather than mis-reshaping)
    when the set is smaller than one batch."""
    import numpy as np

    nb = len(images) // batch_size
    if nb == 0:
        raise ValueError(
            f"eval set of {len(images)} examples is smaller than "
            f"eval_batch_size={batch_size}"
        )
    xs = np.asarray(images[: nb * batch_size]).reshape(
        (nb, batch_size) + images.shape[1:]
    )
    ys = np.asarray(labels[: nb * batch_size]).reshape((nb, batch_size))
    return jnp.asarray(xs), jnp.asarray(ys)


def make_eval_fn(apply_fn: Callable, cfg: RoundConfig) -> Callable:
    """Batched evaluation of a model snapshot (parity: ``src/main.py:167-191``,
    the eval the reference runs on every client after each SendModel)."""

    def eval_step(params, batch_stats, x, y):
        variables = {"params": params, "batch_stats": batch_stats}
        logits = apply_fn(variables, x, train=False, mutable=False)
        ce = softmax_ce_int_labels(logits.astype(jnp.float32), y)
        correct = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
        return ce.sum(), correct.sum()

    @jax.jit
    def evaluate(params, batch_stats, xs, ys):
        """xs: [num_batches, batch, ...] — returns (mean_loss, accuracy)."""
        losses, corrects = jax.lax.map(
            lambda b: eval_step(params, batch_stats, b[0], b[1]), (xs, ys)
        )
        n = ys.size
        return jnp.sum(losses) / n, jnp.sum(corrects) / n

    return evaluate
