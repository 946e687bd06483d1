"""Standalone single-node training — the reference's vestigial-but-present
``main.py`` path (``src/main.py:104-125`` train, ``:193-228`` test with
best-accuracy checkpointing, ``:87-96`` ``--resume``), kept as a first-class
surface: train one model on the full dataset, evaluate per epoch, checkpoint
whenever test accuracy improves.

Jitted train step over shuffled epoch batches; the optimizer and cosine
schedule are the shared torch-semantics implementation
(:mod:`fedtpu.core.optim`), so solo and federated training use identical
update math.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from fedtpu import models as model_zoo
from fedtpu.config import RoundConfig
from fedtpu.core import optim
from fedtpu.ops.losses import softmax_ce_int_labels
from fedtpu.core.client import batch_eval_arrays, make_eval_fn
from fedtpu.data import dataset_info, load
from fedtpu.transport import wire
from fedtpu.utils.metrics import MetricsLogger
from fedtpu.utils.platform import enable_compile_cache


class SoloTrainer:
    """Single-model SGD trainer with best-acc checkpointing.

    >>> t = SoloTrainer(cfg, checkpoint_path="checkpoint/model.fckpt")
    >>> for epoch in range(200):
    ...     t.train_epoch()
    ...     t.test_epoch()   # saves when best
    """

    def __init__(
        self,
        cfg: RoundConfig,
        seed: int = 0,
        checkpoint_path: Optional[str] = None,
        resume: bool = False,
        mesh=None,
    ):
        """``mesh``: optional 1-D ``jax.sharding.Mesh`` (any axis name) for
        intra-node batch data parallelism: each step's batch shards across
        the mesh, gradients/BN-stats/metrics pmean over it, and the
        replicated update is identical on every device — the TPU-native
        form of the reference's vestigial ``torch.nn.DataParallel`` wrap
        (``src/main.py:79-81``; SURVEY §2d "intra-client DP"). The mesh
        size must divide the batch size.

        Numerics vs single-device: bit-identical for deterministic models
        (no BN, no dropout, augment off — test-pinned on mlp). BatchNorm
        models normalize each SHARD's sub-batch — the same semantics as
        torch DataParallel, whose replicas also normalize their sub-batches
        — so they match the reference's mechanism, not the single-device
        trajectory (running stats here are the pmean over shards).
        Dropout/augmentation RNG is fold_in-decorrelated per shard."""
        self.cfg = cfg
        if mesh is not None and cfg.data.batch_size % mesh.devices.size:
            # Validate before the model build / dataset load below.
            raise ValueError(
                f"batch_size={cfg.data.batch_size} not divisible by "
                f"mesh size {mesh.devices.size}"
            )
        enable_compile_cache()
        self.model = model_zoo.create(
            cfg.model, num_classes=cfg.num_classes, remat=cfg.remat
        )
        self.images, self.labels = load(
            cfg.data.dataset, "train", seed=cfg.data.seed, num=cfg.data.num_examples
        )
        self.test_images, self.test_labels = load(
            cfg.data.dataset, "test", seed=cfg.data.seed, num=cfg.data.num_examples
        )
        sample = jnp.zeros((1,) + tuple(self.images.shape[1:]), jnp.float32)
        variables = self.model.init(jax.random.PRNGKey(seed), sample, train=False)
        self.params = variables["params"]
        self.batch_stats = variables.get("batch_stats", {})
        self.opt_state = optim.init(self.params, cfg.opt)
        self.rng = jax.random.PRNGKey(seed + 1)
        self.epoch = 0
        self.best_acc = 0.0
        self.checkpoint_path = checkpoint_path
        if mesh is None:
            self._train_step = jax.jit(self._make_train_step())
        else:
            from jax.sharding import PartitionSpec as P

            axis = mesh.axis_names[0]
            body = self._make_train_step(axis_name=axis)
            self._train_step = jax.jit(
                shard_map(
                    body,
                    mesh=mesh,
                    in_specs=(
                        P(),        # params (replicated)
                        P(),        # batch_stats
                        P(),        # opt_state
                        P(axis),    # x — batch axis sharded
                        P(axis),    # y
                        P(),        # rng
                        P(),        # epoch_idx
                    ),
                    out_specs=(P(), P(), P(), P(), P()),
                    check_vma=False,
                )
            )
        self._evaluate = make_eval_fn(self.model.apply, cfg)
        if resume and checkpoint_path and os.path.exists(checkpoint_path):
            self.load_checkpoint(checkpoint_path)

    # ------------------------------------------------------------- training
    def _make_train_step(self, axis_name: Optional[str] = None):
        """``axis_name`` set = the per-shard body for batch data
        parallelism: grads/BN-stats/metrics pmean over the axis so the
        (replicated) update matches the full-batch computation exactly."""
        cfg = self.cfg
        use_augment = cfg.data.augment and cfg.data.dataset in (
            "cifar10",
            "cifar100",
        )

        def loss_fn(params, batch_stats, x, y, rng):
            if axis_name is not None:
                # Decorrelate ALL per-shard randomness (augmentation crops
                # and dropout masks); a replicated key would drop the same
                # positions on every shard's sub-batch.
                rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_name))
            if use_augment:
                from fedtpu.data.augment import augment_batch

                aug_rng, rng = jax.random.split(rng)
                x = augment_batch(aug_rng, x)
            variables = {"params": params, "batch_stats": batch_stats}
            logits, updated = self.model.apply(
                variables, x, train=True, mutable=["batch_stats"],
                rngs={"dropout": rng},
            )
            ce = softmax_ce_int_labels(logits.astype(jnp.float32), y).mean()
            acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
            return ce, (updated.get("batch_stats", batch_stats), acc)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def step(params, batch_stats, opt_state, x, y, rng, epoch_idx):
            (loss, (stats, acc)), grads = grad_fn(params, batch_stats, x, y, rng)
            if axis_name is not None:
                grads = jax.lax.pmean(grads, axis_name)
                stats = jax.lax.pmean(stats, axis_name)
                loss = jax.lax.pmean(loss, axis_name)
                acc = jax.lax.pmean(acc, axis_name)
            lr = cfg.opt.lr_at(epoch_idx)
            params, opt_state = optim.apply(params, grads, opt_state, lr, cfg.opt)
            return params, stats, opt_state, loss, acc

        return step

    def train_epoch(self) -> Tuple[float, float]:
        """One shuffled epoch (parity: ``train(epoch)``, ``src/main.py:104-125``).
        Returns (mean loss, mean accuracy)."""
        bs = self.cfg.data.batch_size
        n = len(self.images)
        self.rng, shuffle_rng = jax.random.split(self.rng)
        order = np.asarray(
            jax.random.permutation(shuffle_rng, n)
        )
        losses, accs = [], []
        for i in range(n // bs):
            take = order[i * bs : (i + 1) * bs]
            self.rng, step_rng = jax.random.split(self.rng)
            self.params, self.batch_stats, self.opt_state, loss, acc = (
                self._train_step(
                    self.params,
                    self.batch_stats,
                    self.opt_state,
                    jnp.asarray(self.images[take]),
                    jnp.asarray(self.labels[take]),
                    step_rng,
                    jnp.asarray(self.epoch, jnp.int32),
                )
            )
            losses.append(float(loss))
            accs.append(float(acc))
        self.epoch += 1
        return float(np.mean(losses)), float(np.mean(accs))

    # ------------------------------------------------------------------ eval
    def test_epoch(self) -> Tuple[float, float]:
        """Evaluate; checkpoint when test accuracy beats the best so far
        (parity: ``test(epoch)``, ``src/main.py:193-228``)."""
        xs, ys = batch_eval_arrays(
            self.test_images, self.test_labels, self.cfg.data.eval_batch_size
        )
        loss, acc = self._evaluate(self.params, self.batch_stats, xs, ys)
        loss, acc = float(loss), float(acc)
        if acc > self.best_acc:
            self.best_acc = acc
            if self.checkpoint_path:
                self.save_checkpoint(self.checkpoint_path)
        return loss, acc

    # ------------------------------------------------------------ checkpoint
    def _state_tree(self):
        return {
            "params": self.params,
            "batch_stats": self.batch_stats,
            "momentum": self.opt_state.momentum,
            "epoch": jnp.asarray(self.epoch, jnp.int32),
            "best_acc": jnp.asarray(self.best_acc, jnp.float32),
        }

    def save_checkpoint(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "wb") as fh:
            fh.write(wire.encode(self._state_tree(), compress=True))
        os.replace(tmp, path)

    def load_checkpoint(self, path: str) -> None:
        """Resume weights + optimizer + epoch + best accuracy (parity:
        ``--resume``, ``src/main.py:87-96``)."""
        like = jax.tree.map(np.asarray, self._state_tree())
        with open(path, "rb") as fh:
            tree = wire.decode(fh.read(), like)
        self.params = jax.tree.map(jnp.asarray, tree["params"])
        self.batch_stats = jax.tree.map(jnp.asarray, tree["batch_stats"])
        self.opt_state = optim.SGDState(
            momentum=jax.tree.map(jnp.asarray, tree["momentum"])
        )
        self.epoch = int(tree["epoch"])
        self.best_acc = float(tree["best_acc"])


def run_solo(
    cfg: RoundConfig,
    epochs: int,
    seed: int = 0,
    checkpoint_path: Optional[str] = None,
    resume: bool = False,
    logger: Optional[MetricsLogger] = None,
    mesh=None,
) -> SoloTrainer:
    trainer = SoloTrainer(
        cfg, seed=seed, checkpoint_path=checkpoint_path, resume=resume,
        mesh=mesh,
    )
    for _ in range(epochs):
        tr_loss, tr_acc = trainer.train_epoch()
        te_loss, te_acc = trainer.test_epoch()
        if logger is not None:
            logger.log(
                trainer.epoch,
                train_loss=tr_loss,
                train_acc=tr_acc,
                test_loss=te_loss,
                test_acc=te_acc,
                best_acc=trainer.best_acc,
            )
    return trainer
