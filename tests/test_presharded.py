"""Presharded device-data layout (fedtpu.data.device, DataConfig.device_layout).

Round-4 finding (artifacts/MFU_PROFILE_r04.json): the gather layout's
computed-index row-gather lowers on TPU to a serial ~2 us dynamic-slice loop
per example (~250k ops/dispatch at the 64-client CIFAR bench) and dominates
the fused round. The presharded layout reorganises the dataset once at upload
into [clients, 2*shard_len, features] so each round's batches are one
contiguous rotated slice. These tests pin its semantics:

* bit-parity with the gather layout and the host oracle when unshuffled
  (round_robin — the reference's own unshuffled-loader semantics,
  src/main.py:140);
* rotation shuffling draws only from each client's own shard, varies across
  rounds, and is deterministic;
* stream (per-step slicing) == non-stream (materialised window) bit-for-bit;
* fused scan == sequential stepping, mesh == single-program;
* multi-local-epoch windows (need > shard length) cycle like `pos % length`.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
from fedtpu.core import Federation
from fedtpu.data import partition
from fedtpu.data.device import (
    make_data_round_step,
    preshard_arrays,
    presharded_window,
)


def _cfg(layout="presharded", part="round_robin", clients=3, **kw):
    base = dict(
        model="mlp",
        num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05, weight_decay=0.0),
        data=DataConfig(
            dataset="synthetic",
            batch_size=4,
            partition=part,
            num_examples=96,
            augment=False,
            device_layout=layout,
        ),
        fed=FedConfig(num_clients=clients),
        steps_per_round=2,
    )
    base.update(kw)
    return RoundConfig(**base)


def _leaves(state):
    return jax.tree_util.tree_leaves(state.params)


def test_preshard_arrays_layout_and_cycling():
    rng = np.random.default_rng(0)
    images = rng.normal(size=(20, 2, 2, 1)).astype(np.float32)
    labels = rng.integers(0, 10, size=20)
    idx, mask = partition.dirichlet(labels, 3, alpha=0.5, seed=0)
    xs, ys = preshard_arrays(images, labels, idx, mask)
    n, L = idx.shape
    assert xs.shape == (n, 2 * L, 4) and ys.shape == (n, 2 * L)
    flat = images.reshape(20, -1)
    for c in range(n):
        own = idx[c][mask[c]]
        if not len(own):
            assert not xs[c].any()
            continue
        expect = own[np.arange(L) % len(own)]
        np.testing.assert_array_equal(ys[c][:L], labels[expect])
        np.testing.assert_array_equal(ys[c][L:], ys[c][:L])  # doubled
        np.testing.assert_array_equal(xs[c][:L], flat[expect])


def test_window_rotates_and_wraps():
    n, L, F = 2, 5, 3
    base = np.arange(n * L * F, dtype=np.float32).reshape(n, L, F)
    xs = jnp.asarray(np.concatenate([base, base], axis=1))
    ys_b = np.arange(n * L, dtype=np.int32).reshape(n, L)
    ys = jnp.asarray(np.concatenate([ys_b, ys_b], axis=1))
    # need (4) <= L: one contiguous slice at the offset.
    x, y = presharded_window(xs, ys, jnp.int32(3), steps=2, batch_size=2,
                             shape=(3,))
    np.testing.assert_array_equal(
        np.asarray(y).reshape(n, -1),
        [[3, 4, 0, 1], [8, 9, 5, 6]],
    )
    assert x.shape == (n, 2, 2, 3)
    # need (8) > L: the rotated epoch cycles, pos % L semantics.
    x, y = presharded_window(xs, ys, jnp.int32(3), steps=4, batch_size=2,
                             shape=(3,))
    np.testing.assert_array_equal(
        np.asarray(y)[0].reshape(-1),
        [3, 4, 0, 1, 2, 3, 4, 0],
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_round_robin_presharded_equals_gather_and_host(dtype):
    """Unshuffled semantics are bit-identical across all three paths, in
    either compute dtype (the benchmark's cells sit on both layouts in
    bfloat16)."""
    fp = Federation(_cfg("presharded", dtype=dtype), seed=0)
    fg = Federation(_cfg("gather", dtype=dtype), seed=0)
    fh = Federation(_cfg("presharded", dtype=dtype), seed=0)
    fp.step()
    fg.step()
    fh.step(fh.round_batch(0))
    for a, b, c in zip(_leaves(fp.state), _leaves(fg.state), _leaves(fh.state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-6)


def test_rotation_shuffle_stays_in_shard_and_varies():
    """Every example a client trains on in rotate mode belongs to its own
    shard, the window changes across rounds, and reruns are deterministic."""
    labels = np.random.default_rng(0).integers(0, 10, size=60)
    images = np.zeros((60, 2, 2, 1), np.float32)
    idx, mask = partition.dirichlet(labels, 3, alpha=0.5, seed=0)
    xs, ys = preshard_arrays(images, labels, idx, mask)
    key = jax.random.PRNGKey(7)
    wins = []
    for r in range(3):
        rng = jax.random.fold_in(key, r)
        off = jax.random.randint(rng, (), 0, idx.shape[1])
        _, y = presharded_window(jnp.asarray(xs), jnp.asarray(ys), off,
                                 steps=2, batch_size=2, shape=(4,))
        wins.append(np.asarray(y))
    for c in range(3):
        own = set(labels[idx[c][mask[c]]].tolist())
        for w in wins:
            assert set(w[c].reshape(-1).tolist()) <= own
    assert any(not np.array_equal(wins[0], w) for w in wins[1:])
    rng = jax.random.fold_in(key, 0)
    off = jax.random.randint(rng, (), 0, idx.shape[1])
    _, again = presharded_window(jnp.asarray(xs), jnp.asarray(ys), off,
                                 steps=2, batch_size=2, shape=(4,))
    np.testing.assert_array_equal(wins[0], np.asarray(again))


def test_stream_equals_materialised_window():
    cfg = _cfg(part="iid")
    fed = Federation(cfg, seed=0)
    xs, ys = preshard_arrays(fed.images, fed.labels, fed.client_idx,
                             fed.client_mask)
    args = (
        jnp.asarray(xs), jnp.asarray(ys),
        jnp.asarray(fed.client_idx), jnp.asarray(fed.client_mask),
        fed.weights, jnp.ones((3,), bool), jax.random.PRNGKey(0),
    )
    outs = []
    for stream in (False, True):
        step = jax.jit(make_data_round_step(
            fed.model, cfg, 2, shuffle=True, layout="presharded",
            stream=stream,
        ))
        st, _ = step(Federation(cfg, seed=0).state, *args)
        outs.append(st)
    for a, b in zip(_leaves(outs[0]), _leaves(outs[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_scan_equals_sequential_presharded(dtype):
    cfg = _cfg(part="iid", dtype=dtype)
    fa, fb = Federation(cfg, seed=0), Federation(cfg, seed=0)
    fa.run_on_device(3)
    for _ in range(3):
        fb.step()
    for a, b in zip(_leaves(fa.state), _leaves(fb.state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_mesh_equals_single_program_presharded(eight_devices):
    from jax.sharding import Mesh

    cfg = _cfg(part="dirichlet", clients=8,
               data=DataConfig(dataset="synthetic", batch_size=4,
                               partition="dirichlet", num_examples=256,
                               augment=False))
    mesh = Mesh(np.array(eight_devices).reshape(8,), ("clients",))
    fm = Federation(cfg, seed=0, mesh=mesh)
    fs = Federation(cfg, seed=0)
    fm.step()
    fs.step()
    for a, b in zip(_leaves(fm.state), _leaves(fs.state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_async_engine_presharded_matches_gather_unshuffled():
    """The async tick's presharded path: round_robin (unshuffled) keeps both
    layouts bit-identical through a buffered-aggregation tick."""
    from fedtpu.core.async_engine import AsyncFederation

    outs = []
    for layout in ("presharded", "gather"):
        af = AsyncFederation(_cfg(layout, clients=4,
                                  fed=FedConfig(num_clients=4)), seed=0,
                             buffer_k=2)
        af.tick()
        outs.append(af.state)
    for a, b in zip(jax.tree_util.tree_leaves(outs[0].params),
                    jax.tree_util.tree_leaves(outs[1].params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_empty_shard_client_is_masked():
    """A client with no data trains zero steps and contributes nothing —
    same invariant the gather layout pins."""
    labels = np.array([0, 1] * 12)
    images = np.random.default_rng(0).normal(size=(24, 2, 2, 1)).astype(
        np.float32
    )
    idx = np.zeros((3, 8), np.int64)
    mask = np.zeros((3, 8), bool)
    idx[0], mask[0] = np.arange(8), True
    idx[1], mask[1] = np.arange(8, 16), True
    # client 2: empty shard
    xs, ys = preshard_arrays(images, labels, idx, mask)
    assert not xs[2].any()
    cfg = _cfg(clients=3,
               data=DataConfig(dataset="synthetic", batch_size=4,
                               partition="iid", num_examples=24,
                               augment=False))
    fed = Federation(cfg, seed=0, data=(images, labels))
    fed.client_idx, fed.client_mask = idx, mask
    fed.weights = jnp.asarray(partition.shard_sizes(mask))
    m = fed.step()
    per_client = np.asarray(m.per_client_loss)
    assert np.isnan(per_client[2]) or per_client[2] == 0.0


def test_unknown_layout_raises():
    with pytest.raises(ValueError, match="device_layout"):
        Federation(_cfg("bogus"), seed=0)
    with pytest.raises(ValueError, match="device_layout"):
        make_data_round_step(None, _cfg(), 2, layout="bogus")
