#!/usr/bin/env python
"""Two-process multi-controller smoke: the REAL ``jax.distributed`` path.

Run one copy of this per "host" (here: local processes standing in for TPU
hosts; on a real slice each host runs the same program and the coordinator
address comes from the environment):

    python examples/multihost_cpu.py --process-id 0 --port 29500 &
    python examples/multihost_cpu.py --process-id 1 --port 29500

Each process brings up 4 virtual CPU devices, joins the 2-process cluster via
``fedtpu.parallel.multihost.initialize`` (the exact call a pod deployment
makes), builds one global 8-device ``clients`` mesh, and executes one full
sharded federated round — cross-process FedAvg psum included. This is the
CPU stand-in for the reference's multi-machine launch matrix
(``README.md:6-17``), with collectives instead of gRPC.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Platform pinning (and the device-count flag) must precede any jax
# backend initialisation.
from fedtpu.utils.platform import force_host_device_count  # noqa: E402

force_host_device_count(4)
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig  # noqa: E402
from fedtpu import models  # noqa: E402
from fedtpu.core import round as round_lib  # noqa: E402
from fedtpu.parallel import (  # noqa: E402
    client_mesh,
    make_sharded_round_step,
    multihost,
    shard_batch,
    shard_state,
)

NUM_PROCESSES = 2
NUM_CLIENTS = 8


def run_engine(args, n_dev):
    """Drive the high-level engine across both processes: Federation with a
    global mesh — per-client state and assignment sharded, dataset
    replicated, the on-device gather + psum FedAvg in one shard_map program
    per round. Every host executes the same code; only process 0 would do
    IO in a real deployment (multihost.is_coordinator)."""
    from fedtpu.core import Federation

    cfg = RoundConfig(
        model="mlp",
        num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05, weight_decay=0.0),
        data=DataConfig(
            dataset="synthetic", batch_size=4, partition="round_robin",
            num_examples=128,
        ),
        fed=FedConfig(num_clients=NUM_CLIENTS),
        steps_per_round=2,
    )
    fed = Federation(cfg, seed=0, mesh=client_mesh(axis_name=cfg.mesh_axis))
    losses = []
    for _ in range(3):
        m = fed.step()
        losses.append(round(float(m.loss), 6))
    assert int(m.num_active) == NUM_CLIENTS
    assert losses[-1] < losses[0], losses
    # The fused multi-round scan over the SAME multi-controller mesh: 2 more
    # rounds as one shard_map program, per-round psum over both processes.
    stacked = fed.run_on_device(2)
    fused = [round(float(stacked.loss[i]), 6) for i in range(2)]
    assert int(fed.state.round_idx) == 5
    assert fused[-1] <= losses[-1] + 1e-6, (losses, fused)
    print(
        f"multihost engine ok: process {args.process_id}/{NUM_PROCESSES}, "
        f"{n_dev} global devices, losses={losses}, fused={fused}",
        flush=True,
    )


def run_loss_sampling(args, n_dev):
    """Loss-proportional participation sampling across two controllers: the
    per-client loss vector is sharded by process, so each controller
    allgathers the full vector and the round-seeded draw must yield the
    SAME mask on every host — the property that makes the feature
    multihost-safe (engine._alive_for_round)."""
    from fedtpu.core import Federation

    cfg = RoundConfig(
        model="mlp",
        num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05, weight_decay=0.0),
        data=DataConfig(
            dataset="synthetic", batch_size=4, partition="iid",
            num_examples=128,
        ),
        fed=FedConfig(num_clients=NUM_CLIENTS, participation_fraction=0.5,
                      participation_sampling="loss"),
        steps_per_round=2,
    )
    fed = Federation(cfg, seed=0, mesh=client_mesh(axis_name=cfg.mesh_axis))
    masks = []
    for r in range(4):
        m = fed.step()
        # Round 0 samples uniformly (no loss observed yet); later rounds
        # weight by the allgathered loss vector.
        assert int(m.num_active) == NUM_CLIENTS // 2
        masks.append("".join(
            "1" if v else "0" for v in fed._alive_for_round(r + 1)))
    print(
        f"multihost loss-sampling ok: process {args.process_id}, "
        f"{n_dev} global devices, masks={masks}",
        flush=True,
    )


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--process-id", type=int, required=True)
    p.add_argument("--port", type=int, default=29500)
    p.add_argument("--engine", action="store_true",
                   help="drive Federation(mesh=...) instead of the raw "
                   "sharded round step")
    p.add_argument("--loss-sampling", action="store_true",
                   help="drive loss-proportional participation sampling "
                   "across both controllers (allgathered loss vector, "
                   "deterministic shared mask)")
    p.add_argument("--all", action="store_true",
                   help="run all three legs (raw round, engine, "
                   "loss-sampling) in one process pair")
    args = p.parse_args()

    multihost.initialize(
        f"localhost:{args.port}",
        num_processes=NUM_PROCESSES,
        process_id=args.process_id,
    )
    assert jax.process_count() == NUM_PROCESSES, jax.process_count()
    n_dev = len(jax.devices())
    assert n_dev == 4 * NUM_PROCESSES, n_dev
    if args.all:
        # Checked FIRST so --all always means all three legs, even combined
        # with a single-leg flag. One process pair covers everything (each
        # spawn costs ~20 s of jax import + gloo bring-up per process on
        # this 1-core host).
        run_raw(args, n_dev)
        run_engine(args, n_dev)
        return run_loss_sampling(args, n_dev)
    if args.engine:
        return run_engine(args, n_dev)
    if args.loss_sampling:
        return run_loss_sampling(args, n_dev)
    return run_raw(args, n_dev)


def run_raw(args, n_dev):
    cfg = RoundConfig(
        model="mlp",
        num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05, weight_decay=0.0),
        data=DataConfig(dataset="synthetic", batch_size=4),
        fed=FedConfig(num_clients=NUM_CLIENTS),
        steps_per_round=2,
    )
    mdl = models.create(cfg.model, num_classes=cfg.num_classes)
    # Same seed on every host -> identical host-global state/data, of which
    # each process materialises only its local devices' shards.
    state = round_lib.init_state(
        mdl, cfg, jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3), jnp.float32)
    )
    rng = np.random.default_rng(0)
    n, s, b = NUM_CLIENTS, cfg.steps_per_round, cfg.data.batch_size
    batch = round_lib.RoundBatch(
        x=jnp.asarray(rng.normal(size=(n, s, b, 16, 16, 3)).astype(np.float32)),
        y=jnp.asarray(rng.integers(0, 10, size=(n, s, b)).astype(np.int32)),
        step_mask=jnp.ones((n, s), bool),
        weights=jnp.ones((n,), jnp.float32),
        alive=jnp.ones((n,), bool),
    )

    mesh = client_mesh(axis_name=cfg.mesh_axis)  # spans BOTH processes
    local = multihost.local_client_slice(NUM_CLIENTS)
    assert (local.stop - local.start) == NUM_CLIENTS // NUM_PROCESSES

    step = make_sharded_round_step(mdl, cfg, mesh, donate=False)
    new_state, metrics = step(
        shard_state(state, mesh, cfg.mesh_axis),
        shard_batch(batch, mesh, cfg.mesh_axis),
    )
    jax.block_until_ready(new_state)
    assert int(metrics.num_active) == NUM_CLIENTS
    print(
        f"multihost ok: process {args.process_id}/{NUM_PROCESSES}, "
        f"{n_dev} global devices, {NUM_CLIENTS} clients, "
        f"loss={float(metrics.loss):.6f}",
        flush=True,
    )


if __name__ == "__main__":
    main()
