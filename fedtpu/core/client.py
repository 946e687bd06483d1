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
from fedtpu.data.datasets import is_token_dataset
from fedtpu.ops.losses import next_token_ce_parts, softmax_ce_int_labels
from fedtpu.utils import trees

Pytree = Any


class ClientOutput(NamedTuple):
    params: Pytree       # locally-updated weights
    batch_stats: Pytree  # locally-updated BN running stats
    opt_state: optim.SGDState
    loss: jnp.ndarray    # mean masked loss over the round
    accuracy: jnp.ndarray
    num_steps: jnp.ndarray
    # What a token model counted over the round's live steps, ``()`` for
    # every other model: {"tokens", "moe_pairs_here"} summed,
    # "moe_load_max_over_mean" the largest (docs/OBSERVABILITY.md).
    counters: Any = ()


def _token_loss(heads, extra_weight):
    """A token model's training loss from its heads' ``(cross-entropy sum,
    count, hits)``: the next-token head's mean over its valid positions plus
    ``extra_weight`` times each further head's (a multi-token-prediction
    module's) over its own. Returns ``(loss, accuracy, tokens)``, the last
    two the next-token head's."""
    total = 0.0
    for depth, (ce_sum, count, _) in enumerate(heads):
        total = total + (extra_weight if depth else 1.0) * ce_sum / jnp.maximum(
            count, 1.0)
    _, count, hits = heads[0]
    return total, hits / jnp.maximum(count, 1.0), count


def _over_steps(counted):
    """A round's counters from the steps': sums, and the largest of a
    ``*_max_*`` reading."""
    if not counted:
        return ()
    return {k: (jnp.max(v) if "_max_" in k else jnp.sum(v))
            for k, v in counted.items()}


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
    # Token data ([batch, T] int32 ids, targets the next ids): the batch goes
    # to the model as the task gives it, and the loss is over sequences.
    tokens_task = is_token_dataset(cfg.data.dataset)
    mtp_weight = dict(cfg.model_args).get("mtp_loss_weight", 0.3)
    micro_rows = dict(cfg.model_args).get("micro_batch_rows", 0)

    def to_compute_dtype(params):
        if dtype == jnp.float32:
            return params
        return jax.tree.map(lambda p: p.astype(dtype), params)

    def token_loss_of_cast(cast, batch_stats, x, y, rng, share=1.0):
        """The token loss of parameters already in the compute dtype, times
        ``share`` (a micro-batch's share of its batch)."""
        heads, updated = apply_fn(
            {"params": cast, "batch_stats": batch_stats}, x, train=True,
            targets=y, mutable=["batch_stats", "counters"],
            rngs={"dropout": rng},
        )
        ce, acc, tokens = _token_loss(heads, mtp_weight)
        counters = dict(updated.get("counters", {}), tokens=tokens)
        return share * ce, (
            updated.get("batch_stats", batch_stats), ce, acc, counters)

    def proximal(params, global_params):
        return 0.5 * mu * trees.tree_sq_norm(trees.tree_sub(params, global_params))

    def token_loss_fn(params, batch_stats, global_params, x, y, rng):
        loss, aux = token_loss_of_cast(
            to_compute_dtype(params), batch_stats, x, y, rng)
        if mu > 0.0:
            loss = loss + proximal(params, global_params)
        return loss, aux

    def token_sgd_in_micro_batches(params, batch_stats, global_params, x, y,
                                   rng, lr):
        """One step of plain SGD (momentum 0) on the token loss of the batch,
        ``micro_rows`` rows through forward and backward at a time: the
        parameters are cast once, a micro-batch weighs by its share of the
        batch's next-token targets (the batch's own loss where every row has
        as many, as packed rows do), and each micro-batch's gradient goes
        straight into the float32 parameters, ``p - lr (wd p + prox) - lr g_1
        - lr g_2 ...``: what the step over the whole batch gives, to float32
        rounding, with the activations of ``micro_rows`` rows and no
        gradient accumulator beside the model (PERF.md §6, PR 34: an
        accumulator costs 2.5 copies of the parameters in the compiled round,
        this 1). ``lr`` is 0 for a masked step, which then changes nothing.
        Returns ``(params, (ce, accuracy, counters))``."""
        targets = jnp.maximum(jnp.sum(y >= 0, dtype=jnp.float32), 1.0)
        cut = lambda a: a.reshape((-1, micro_rows) + a.shape[1:])
        cast = to_compute_dtype(params)
        grad_of_cast = jax.value_and_grad(token_loss_of_cast, has_aux=True)
        if cfg.opt.weight_decay or mu > 0.0:
            params = jax.tree.map(
                lambda p, g: p - lr * (cfg.opt.weight_decay * p + mu * (p - g)),
                params, global_params)

        def one(carry, xy):
            params, ce, acc = carry
            share = jnp.sum(xy[1] >= 0, dtype=jnp.float32) / targets
            (_, (_, c, a, counted)), g = grad_of_cast(
                cast, batch_stats, *xy, rng, share)
            with jax.named_scope("fed.local_step.optimizer"):
                params = jax.tree.map(
                    lambda p, g: p - lr * g.astype(p.dtype), params, g)
            return (params, ce + share * c, acc + share * a), counted

        zero = jnp.zeros((), jnp.float32)
        (params, ce, acc), counted = jax.lax.scan(
            one, (params, zero, zero), (cut(x), cut(y)))
        return params, (ce, acc, _over_steps(counted))

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
        return loss, (updated.get("batch_stats", batch_stats), ce, acc, ())

    grad_fn = jax.value_and_grad(
        token_loss_fn if tokens_task else loss_fn, has_aux=True
    )
    buffers = bool(cfg.opt.momentum or cfg.opt.nesterov)
    # micro_rows equal to the batch is ONE micro-batch through the same path
    # under plain SGD, and the whole-batch step, as ever, with momentum.
    in_micro_batches = tokens_task and (
        0 < micro_rows < cfg.data.batch_size
        or (micro_rows == cfg.data.batch_size and not buffers)
    )
    if in_micro_batches and (cfg.data.batch_size % micro_rows or buffers):
        raise ValueError(
            f"micro_batch_rows={micro_rows} needs a batch_size it divides "
            f"({cfg.data.batch_size}) and plain SGD (momentum 0): each "
            "micro-batch's gradient goes straight into the parameters"
        )

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
            if in_micro_batches:
                live_f = live.astype(jnp.float32)
                with jax.named_scope("fed.local_step.fwd_bwd"):
                    params, (ce, acc, counted) = token_sgd_in_micro_batches(
                        params, stats, anchor, x, y, step_rng, lr * live_f
                    )
                counted = jax.tree.map(
                    lambda c: c * live.astype(c.dtype), counted)
                return (params, stats, ostate), (
                    ce * live_f, acc * live_f, live_f, counted)
            with jax.named_scope("fed.local_step.fwd_bwd"):
                (loss, (new_stats, ce, acc, counted)), grads = grad_fn(
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
            counted = jax.tree.map(lambda c: c * live.astype(c.dtype), counted)
            return (params, stats, ostate), (
                ce * live_f, acc * live_f, live_f, counted)

        step_rngs = jax.random.split(rng, steps)
        (params, stats, ostate), (ces, accs, lives, counted) = jax.lax.scan(
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
            counters=_over_steps(counted),
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
    ys = np.asarray(labels[: nb * batch_size]).reshape(
        (nb, batch_size) + labels.shape[1:]
    )
    return jnp.asarray(xs), jnp.asarray(ys)


def make_eval_fn(apply_fn: Callable, cfg: RoundConfig) -> Callable:
    """Batched evaluation of a model snapshot (parity: ``src/main.py:167-191``,
    the eval the reference runs on every client after each SendModel)."""

    tokens_task = is_token_dataset(cfg.data.dataset)

    def eval_step(params, batch_stats, x, y):
        variables = {"params": params, "batch_stats": batch_stats}
        logits = apply_fn(variables, x, train=False, mutable=False)
        if tokens_task:
            # (token loss summed, hits, positions with a target)
            ce_sum, count, hits = next_token_ce_parts(logits, y)
            return ce_sum, hits, count
        ce = softmax_ce_int_labels(logits.astype(jnp.float32), y)
        correct = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
        return ce.sum(), correct.sum(), jnp.float32(y.size)

    @jax.jit
    def evaluate(params, batch_stats, xs, ys):
        """xs: [num_batches, batch, ...] — returns (mean_loss, accuracy);
        for token data the next-token loss and accuracy over the positions
        that have a target."""
        losses, corrects, counts = jax.lax.map(
            lambda b: eval_step(params, batch_stats, b[0], b[1]), (xs, ys)
        )
        n = jnp.sum(counts)
        return jnp.sum(losses) / n, jnp.sum(corrects) / n

    return evaluate
