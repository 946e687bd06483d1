"""What a run draws from ``--seed`` whatever the task: the key streams, the
clients' shards, the weights (one jitted call on the device). The data is the
task's (``tasks/<task>.py::make_data``, stream 1 of ``key_of``).
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


def make_shards(seed, n, clients, how="iid"):
    """``clients`` equal shards, ``(idx [clients, L] int32, mask [clients, L]
    bool)``, L = n // clients, as the traffic file's ``"shards"`` says:
    ``iid`` (absent: this), a seeded permutation cut into shards;
    ``contiguous``, client ``c`` takes rows ``[c*L, (c+1)*L)`` in the task's
    own order, so that a task's ``make_data`` lays each client's rows out in
    turn and decides how the clients differ."""
    length = n // clients
    if how == "iid":
        rows = np.random.default_rng(seed).permutation(n)[: clients * length]
    elif how == "contiguous":
        rows = np.arange(clients * length)
    else:
        raise ValueError(f'"shards": {how!r}; the kinds are "iid" and "contiguous"')
    idx = rows.reshape(clients, length).astype(np.int32)
    return idx, np.ones_like(idx, bool)


def client_rows(idx_row, steps, batch):
    """Which examples a client trains on in a round, step by step: its shard
    in order from the head, cycled when the round is longer than the shard
    (an unshuffled loader restarted every round, as the reference trainer
    iterates it). ``[steps, batch]`` indices into the dataset."""
    pos = (np.arange(steps * batch) % len(idx_row)).reshape(steps, batch)
    return idx_row[pos]


def make_weights(seed, param_spec, stats_spec):
    """A reference's parameter list ``[(path, shape, kind)]`` as nested dicts.
    Kinds: ``he`` (normal, fan-in ``prod(shape[:-1])``), ``head`` (a tenth of
    that, so the first loss sits near ln(classes)), ``zeros``, ``ones``, or a
    number: the standard deviation of a normal (an embedding, a stacked
    ``[experts, in, out]`` leaf, whatever fan-in the shape does not show)."""
    unknown = {k for _, _, k in param_spec
               if not _is_std(k) and k not in ("he", "head", "zeros", "ones")}
    if unknown:
        raise ValueError(f"unknown initialiser kinds in the parameter list: {unknown}")

    @jax.jit
    def gen(key):
        out = []
        for i, (_, shape, kind) in enumerate(param_spec):
            if _is_std(kind) or kind in ("he", "head"):
                std = kind if _is_std(kind) else (
                    math.sqrt(2.0 / math.prod(shape[:-1]))
                    * (0.1 if kind == "head" else 1.0))
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


def _is_std(kind):
    return isinstance(kind, (int, float)) and not isinstance(kind, bool)


def _nest(items):
    root = {}
    for path, leaf in items:
        node = root
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return root
