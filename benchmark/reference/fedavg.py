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

What a device holds does not grow with the clients. Between turns: the global
model and the running weighted sum of the changes. In a turn, besides: the
client's momentum (on the host between its turns; none at all where the
momentum is 0), the weights the epoch steps (which end as the change) and a
step's gradient: five copies of the parameters at most, four without
momentum, and the activations of one step, or of one block of its rows where
the configuration states ``reference_block_rows`` (the blocks' running sum is
then up to one copy more).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.layers import ident


def tree_map(f, *trees):
    return jax.tree.map(f, *trees)


def make_local_epoch(forward, loss, opt, quant=ident, block_rows=None,
                     loss_parts=None):
    """One client's local steps as a jitted function, ``epoch(params, stats,
    mom, xs, ys) -> (change, stats' change, mom, mean loss)``; ``mom`` is
    donated, and is ``None`` in and out where the momentum is 0 (``0*m + g``
    is ``g``). ``loss(logits, targets) -> scalar`` is the task's
    (``tasks/<task>.py``).

    With ``block_rows`` a step's gradient is the sum of the gradients of
    blocks of that many rows, so that only one block's activations are alive:
    what the whole batch gives, up to the order of float32 sums. The blocks
    weigh alike, the loss being a mean over rows, unless the task says
    otherwise with ``loss_parts(logits, targets) -> (sum, count)``: the step's
    loss is then the summed sums over the summed counts."""
    lr, mu, wd = opt["learning_rate"], opt["momentum"], opt["weight_decay"]
    parts = loss_parts or (lambda logits, y: (loss(logits, y), 1.0))

    def loss_fn(params, stats, x, y):
        logits, new_stats = forward(params, stats, x, quant)
        return loss(logits, y), new_stats

    def parts_fn(params, stats, x, y):
        return parts(forward(params, stats, x, quant)[0], y)

    grad = jax.value_and_grad(loss_fn, has_aux=True)
    parts_grad = jax.value_and_grad(parts_fn, has_aux=True)

    def grad_in_blocks(params, stats, x, y):
        def one_block(acc, block):
            (total, count), g = parts_grad(params, stats, *block)
            return tree_map(jnp.add, acc, (g, total, count)), None

        blocks = tree_map(
            lambda a: a.reshape((-1, block_rows) + a.shape[1:]), (x, y))
        (g, total, count), _ = jax.lax.scan(
            one_block, (tree_map(jnp.zeros_like, params),) + 2 * (jnp.zeros(()),),
            blocks)
        return (total / count, stats), tree_map(lambda g: g / count, g)

    def epoch(params, stats, mom, xs, ys):
        def one_step(carry, batch):
            params, stats, mom = carry
            (ce, stats), g = (grad_in_blocks if block_rows else grad)(
                params, stats, *batch)
            g = tree_map(lambda g, p: g + wd * p, g, params)
            if mu:
                g = mom = tree_map(lambda m, g: mu * m + g, mom, g)
            params = tree_map(lambda p, m: p - lr * m, params, g)
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

    return jax.jit(with_precision, donate_argnums=2)


class Reference:
    """The reference federation's state and one method, ``round()``.

    ``feed(client) -> (xs [steps, batch, ...], ys [steps, batch, ...])`` is
    the cell's feed, the same rows every round (``seeded.client_rows``).
    """

    def __init__(self, forward, loss, params, stats, opt, feed, weights,
                 devices, codec=None, quant=ident, block_rows=None,
                 loss_parts=None):
        if block_rows and jax.tree.leaves(stats):
            raise ValueError("reference_block_rows with batch statistics: a "
                             "block's statistics are not the batch's")
        self.devices = list(devices)
        self.epoch = make_local_epoch(forward, loss, opt, quant, block_rows,
                                      loss_parts)
        self.momentum = opt["momentum"]
        self.params = tree_map(np.asarray, params)
        self.stats = tree_map(np.asarray, stats)
        self.weights = np.asarray(weights, np.float64)
        self.n = len(self.weights)
        self.feed = feed
        self.codec = codec
        self.mom = [None] * self.n  # host trees between the clients' turns
        self.residual = None  # [clients, padded] on device 0, codec cells
        self.round_idx = 0

    def round(self):
        """Run one round. Returns ``(loss, update, stats_update, extra)`` with
        the updates as host trees and ``extra`` the codec's readings."""
        glob = [jax.device_put((self.params, self.stats), d) for d in self.devices]
        share = (self.weights / self.weights.sum()).astype(np.float32)
        sums, rows, losses = [None] * len(self.devices), [], [None] * self.n
        turn = [None] * len(self.devices)  # a device's turn in flight

        def settle(k):
            """Wait for device ``k``'s turn in flight: its change added to the
            sum and let go, its loss, the client's momentum to the host (exact
            both ways). One turn behind, so a device holds one client's
            buffers and the others stay busy."""
            if turn[k] is not None:
                c, mom, loss = turn[k]
                turn[k] = None
                jax.block_until_ready(sums[k])
                losses[c], self.mom[c] = float(loss), jax.device_get(mom)

        for c in range(self.n):
            k = c % len(self.devices)
            settle(k)
            p, s = glob[k]
            xs, ys = jax.device_put(self.feed(c), self.devices[k])
            if not self.momentum:
                mom = None
            elif self.mom[c] is None:
                mom = tree_map(jnp.zeros_like, p)
            else:
                mom = jax.device_put(self.mom[c], self.devices[k])
            d, sd, mom, loss = self.epoch(p, s, mom, xs, ys)
            for leaf in jax.tree.leaves(mom):
                leaf.copy_to_host_async()  # on its way before ``settle`` asks
            turn[k] = (c, mom, loss)
            if self.codec is not None:
                rows.append(jax.device_put(self.codec.pack(d), self.devices[0]))
            # Weighted sums stay on the client's device, and the change goes
            # into them (donated): the host adds the devices' sums at the end.
            sums[k] = _axpy(sums[k], (d, sd), share[c])
            # Let go now, not when the next turn binds the names again: the
            # device would hold two clients' buffers meanwhile.
            del d, sd, mom
        for k in range(len(self.devices)):
            settle(k)
        # The devices' sums, added in float64 one leaf at a time.
        flat = [jax.tree.flatten(part) for part in sums if part is not None]
        total = []
        for leaves in zip(*(leaves for leaves, _ in flat)):
            leaf = np.asarray(leaves[0], np.float64)
            for more in leaves[1:]:
                leaf = np.add(leaf, np.asarray(more, np.float64))
            total.append(leaf.astype(np.float32))
        upd, supd = jax.tree.unflatten(flat[0][1], total)
        extra = {}
        if self.codec is not None:
            upd, extra = self._through_codec(jnp.stack(rows), share)
        self.params = tree_map(np.add, self.params, upd)
        self.stats = tree_map(np.add, self.stats, supd)
        self.round_idx += 1
        return float(np.mean(losses)), upd, supd, extra

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


@functools.partial(jax.jit, donate_argnums=0)
def _scaled(tree, w):
    return tree_map(lambda t: w * t, tree)


@functools.partial(jax.jit, donate_argnums=0)
def _plus_scaled(acc, tree, w):
    return tree_map(lambda a, t: a + w * t, acc, tree)


def _axpy(acc, tree, w):
    return _scaled(tree, w) if acc is None else _plus_scaled(acc, tree, w)
