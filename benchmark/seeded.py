"""Everything a run draws from ``--seed``: the data, the clients' shards, the
weights. Made on the device in one jitted call each, in float32 values that
bfloat16 holds exactly for the images, so the program (which stores them in
its compute type) and the float32 reference see the same pixels.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int, stream: int):
    """A key for any whole-number seed (the driver's pass 2**31)."""
    k = jax.random.PRNGKey(seed % 1000003)
    return jax.random.fold_in(jax.random.fold_in(k, seed // 1000003), stream)


def make_data(seed, n, image_shape, num_classes):
    """Class prototypes plus unit noise: ``(images [n, H, W, C] f32, labels [n])``
    as host arrays (the program takes its dataset from the host)."""

    @jax.jit
    def gen(key):
        k_proto, k_lab, k_noise = jax.random.split(key, 3)
        proto = jax.random.normal(k_proto, (num_classes,) + tuple(image_shape))
        labels = jax.random.randint(k_lab, (n,), 0, num_classes)
        x = 0.25 * (proto[labels] + jax.random.normal(k_noise, (n,) + tuple(image_shape)))
        return x.astype(jnp.bfloat16).astype(jnp.float32), labels.astype(jnp.int32)

    images, labels = gen(key_of(seed, 1))
    return np.asarray(images), np.asarray(labels)


def make_shards(seed, n, clients):
    """An IID split: a seeded permutation cut into ``clients`` equal shards.
    ``(idx [clients, L] int32, mask [clients, L] bool)``, L = n // clients."""
    length = n // clients
    perm = np.random.default_rng(seed).permutation(n)[: clients * length]
    idx = perm.reshape(clients, length).astype(np.int32)
    return idx, np.ones_like(idx, bool)


def client_rows(idx_row, steps, batch):
    """Which examples a client trains on in a round, step by step: its shard
    in order from the head, cycled when the round is longer than the shard
    (an unshuffled loader restarted every round, as the reference trainer
    iterates it). ``[steps, batch]`` indices into the dataset."""
    pos = (np.arange(steps * batch) % len(idx_row)).reshape(steps, batch)
    return idx_row[pos]


def make_weights(seed, param_spec, stats_spec):
    """He-normal kernels (fan-in), a head a tenth of that so the first loss
    sits near ln(classes), zero biases, unit BatchNorm. Nested dicts."""
    unknown = {k for _, _, k in param_spec} - {"he", "head", "zeros", "ones"}
    if unknown:
        raise ValueError(f"unknown initialiser kinds in the parameter list: {unknown}")

    @jax.jit
    def gen(key):
        out = []
        for i, (_, shape, kind) in enumerate(param_spec):
            if kind in ("he", "head"):
                fan_in = math.prod(shape[:-1])
                std = math.sqrt(2.0 / fan_in) * (0.1 if kind == "head" else 1.0)
                out.append(std * jax.random.normal(jax.random.fold_in(key, i), shape))
            else:
                out.append(jnp.full(shape, 1.0 if kind == "ones" else 0.0))
        return out

    leaves = gen(key_of(seed, 2))
    params = _nest((path, leaf) for (path, _, _), leaf in zip(param_spec, leaves))
    stats = _nest(
        (path, np.full(shape, 1.0 if kind == "ones" else 0.0, np.float32))
        for path, shape, kind in stats_spec
    )
    return params, stats


def _nest(items):
    root = {}
    for path, leaf in items:
        node = root
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return root
