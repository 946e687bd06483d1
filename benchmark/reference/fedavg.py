"""Plain federated averaging: the semantics the cells' ``correct`` is held to.

One round: every client starts from the global model, runs its local steps
of SGD (momentum, coupled weight decay, constant rate; the momentum buffers
stay with the client from round to round, as in the reference trainer), and
hands back the change of its weights and of its BatchNorm running
statistics. The server adds the example-weighted mean of the changes to the
global model. With a codec, each client's change goes through it first
(``rotq.py``), with the client's residual added before and kept after.

Straightforward jax.numpy in float32 with ``highest`` matrix precision: one
client at a time, one jitted local epoch, no vmap, no kernels, nothing from
the program under test. Clients are dealt round-robin onto the devices it is
given so that a four-chip cell's reference takes no longer than a one-chip
cell's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.layers import ident


def tree_map(f, *trees):
    return jax.tree.map(f, *trees)


def make_local_epoch(forward, loss, opt, quant=ident):
    """One client's local steps as a jitted function. ``loss(logits, targets)
    -> scalar`` is the task's (``tasks/<task>.py``)."""
    lr, mu, wd = opt["learning_rate"], opt["momentum"], opt["weight_decay"]

    def loss_fn(params, stats, x, y):
        logits, new_stats = forward(params, stats, x, quant)
        return loss(logits, y), new_stats

    grad = jax.value_and_grad(loss_fn, has_aux=True)

    def epoch(params, stats, mom, xs, ys):
        def one_step(carry, batch):
            params, stats, mom = carry
            (ce, stats), g = grad(params, stats, *batch)
            g = tree_map(lambda g, p: g + wd * p, g, params)
            mom = tree_map(lambda m, g: mu * m + g, mom, g)
            params = tree_map(lambda p, m: p - lr * m, params, mom)
            return (params, stats, mom), ce

        # A scan, not an unrolled loop, so that a round of six steps compiles
        # in the time of one (the check's first run pays the compile).
        (new, new_stats, mom), ces = jax.lax.scan(
            one_step, (params, stats, mom), (xs, ys))
        delta = tree_map(lambda a, b: a - b, new, params)
        sdelta = tree_map(lambda a, b: a - b, new_stats, stats)
        return delta, sdelta, mom, ces.mean()

    def with_precision(*args):
        with jax.default_matmul_precision("highest"):
            return epoch(*args)

    return jax.jit(with_precision)


class Reference:
    """The reference federation's state and one method, ``round()``.

    ``feed(client) -> (xs [steps, batch, ...], ys [steps, batch, ...])`` is
    the cell's feed, the same rows every round (``seeded.client_rows``).
    """

    def __init__(self, forward, loss, params, stats, opt, feed, weights,
                 devices, codec=None, quant=ident):
        self.devices = list(devices)
        self.epoch = make_local_epoch(forward, loss, opt, quant)
        self.params = tree_map(np.asarray, params)
        self.stats = tree_map(np.asarray, stats)
        self.weights = np.asarray(weights, np.float64)
        self.n = len(self.weights)
        self.feed = feed
        self.codec = codec
        self.mom = [None] * self.n
        self.residual = None  # [clients, padded] on device 0, codec cells
        self.round_idx = 0

    def round(self):
        """Run one round. Returns ``(loss, update, stats_update, extra)`` with
        the updates as host trees and ``extra`` the codec's readings."""
        glob = [jax.device_put((self.params, self.stats), d) for d in self.devices]
        share = (self.weights / self.weights.sum()).astype(np.float32)
        sums, rows, losses = [None] * len(self.devices), [], []
        for c in range(self.n):
            k = c % len(self.devices)
            p, s = glob[k]
            if self.mom[c] is None:
                self.mom[c] = tree_map(jnp.zeros_like, p)
            xs, ys = jax.device_put(self.feed(c), self.devices[k])
            d, sd, self.mom[c], loss = self.epoch(p, s, self.mom[c], xs, ys)
            losses.append(loss)
            # Weighted sums stay on the client's device; the host adds the
            # devices' sums at the end, so nothing waits on a transfer.
            sums[k] = _axpy(sums[k], (d, sd), share[c])
            if self.codec is not None:
                rows.append(jax.device_put(self.codec.pack(d), self.devices[0]))
        total = None
        for part in sums:
            if part is not None:
                part = tree_map(lambda a: np.asarray(a, np.float64), part)
                total = part if total is None else tree_map(np.add, total, part)
        upd, supd = tree_map(lambda a: a.astype(np.float32), total)
        extra = {}
        if self.codec is not None:
            upd, extra = self._through_codec(jnp.stack(rows), share)
        self.params = tree_map(np.add, self.params, upd)
        self.stats = tree_map(np.add, self.stats, supd)
        self.round_idx += 1
        return float(np.mean([float(l) for l in losses])), upd, supd, extra

    def _through_codec(self, rows, share):
        if self.residual is None:
            self.residual = jnp.zeros_like(rows)
        out, self.residual = self.codec.apply(rows + self.residual, self.round_idx)
        w = jax.device_put(jnp.asarray(share, jnp.float32), self.devices[0])
        mean = lambda m: np.asarray(jnp.einsum("c,cp->p", w, m, precision="highest"))
        extra = {
            "residual_norms": np.asarray(jnp.linalg.norm(self.residual, axis=1)),
            "mean_residual": self.codec.unpack(mean(self.residual), self.params),
        }
        return self.codec.unpack(mean(out), self.params), extra


@jax.jit
def _scaled(tree, w):
    return tree_map(lambda t: w * t, tree)


@jax.jit
def _plus_scaled(acc, tree, w):
    return tree_map(lambda a, t: a + w * t, acc, tree)


def _axpy(acc, tree, w):
    return _scaled(tree, w) if acc is None else _plus_scaled(acc, tree, w)
