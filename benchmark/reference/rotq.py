"""Plain rotated b-bit quantisation with error feedback (the ``rotq`` codec).

Konecny et al., arXiv:1610.05492, section 4 (structured random rotation,
uniform quantisation with stochastic rounding): a client's change, flattened
leaf after leaf in tree order and zero-padded to a power of two, is multiplied
by a random sign diagonal, Walsh-Hadamard transformed and scaled by
1/sqrt(h); each rotated coordinate is rounded at random to one of 2^bits
levels between the row's least and largest, with expectation equal to the
coordinate; the server undoes the rotation and drops what landed in the
padding. What the rounding lost is the client's residual for the next round.

The signs and the rounding draws come from this file's own seed: the
program's draws are its own business, and ``check.py`` compares only what
does not depend on them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def fwht(x):
    """Unnormalised Walsh-Hadamard transform of the last axis (a power of 2)."""
    shape, h, step = x.shape, x.shape[-1], 1
    while step < h:
        x = x.reshape(shape[:-1] + (h // (2 * step), 2, step))
        a, b = x[..., 0, :], x[..., 1, :]
        x = jnp.stack([a + b, a - b], axis=-2).reshape(shape)
        step *= 2
    return x


class RotQ:
    def __init__(self, template, bits, seed, block_rows=16):
        leaves = jax.tree.leaves(template)
        self.sizes = [int(np.prod(np.shape(l))) for l in leaves]
        self.total = sum(self.sizes)
        self.padded = 1 << max(7, (self.total - 1).bit_length())
        self.levels = float(2 ** bits - 1)
        self.seed = seed
        self.block_rows = block_rows
        self._apply = jax.jit(self._apply_block)

    def pack(self, tree):
        flat = jnp.concatenate([jnp.ravel(l) for l in jax.tree.leaves(tree)])
        return jnp.pad(flat.astype(jnp.float32), (0, self.padded - self.total))

    def unpack(self, row, template):
        leaves, treedef = jax.tree.flatten(template)
        out, off = [], 0
        for leaf, size in zip(leaves, self.sizes):
            out.append(np.asarray(row[off:off + size]).reshape(np.shape(leaf)))
            off += size
        return jax.tree.unflatten(treedef, out)

    def _apply_block(self, y, key):
        h = self.padded
        k_sign, k_unif = jax.random.split(key)
        signs = jnp.where(jax.random.bernoulli(k_sign, 0.5, (h,)), 1.0, -1.0)
        z = fwht(y * signs) * (1.0 / math.sqrt(h))
        lo = z.min(axis=1, keepdims=True)
        scale = (z.max(axis=1, keepdims=True) - lo) / self.levels
        safe = jnp.where(scale > 0, scale, 1.0)
        u = jax.random.uniform(k_unif, z.shape)
        q = jnp.clip(jnp.floor((z - lo) / safe + u), 0.0, self.levels)
        out = fwht(lo + q * safe) * (1.0 / math.sqrt(h)) * signs
        out = out.at[:, self.total:].set(0.0)
        return out, y - out

    def apply(self, y, round_idx):
        """``y: [clients, padded]`` (change plus residual) -> what the server
        reconstructs, and the new residuals. Rows go through in blocks, each
        with its own signs and rounding draws, so that the butterfly's
        temporaries stay a block's size."""
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), round_idx)
        outs, ress = [], []
        for i in range(0, y.shape[0], self.block_rows):
            out, res = self._apply(y[i:i + self.block_rows],
                                   jax.random.fold_in(key, i))
            outs.append(out)
            ress.append(res)
        return jnp.concatenate(outs), jnp.concatenate(ress)
