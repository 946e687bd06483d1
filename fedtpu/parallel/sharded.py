"""Mesh-parallel federated round via ``shard_map``.

This is the scale-out path replacing the reference's thread-per-client gRPC
fan-out (``src/server.py:124-153``): the ``clients`` axis of all per-client
state and data is sharded across the mesh, each device vmaps local SGD over
its own slice of clients, and FedAvg is a ``lax.psum`` over the mesh axis —
XLA lowers it to ICI all-reduces with zero host involvement.
"""

from __future__ import annotations

from typing import Callable, Tuple

import flax.linen as nn
import jax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedtpu.config import RoundConfig
from fedtpu.core.round import (
    FederatedState,
    RoundBatch,
    RoundMetrics,
    make_round_step,
)

Pytree = object


def state_specs(axis: str) -> FederatedState:
    """PartitionSpecs for FederatedState: global model replicated, per-client
    state sharded along the clients axis."""
    return FederatedState(
        params=P(),
        batch_stats=P(),
        opt_state=P(axis),
        client_rng=P(axis),
        round_idx=P(),
        comp_state=P(axis),
        server_opt_state=P(),  # server moments act on the global model
        last_client_loss=P(axis),
    )


def batch_specs(axis: str) -> RoundBatch:
    return RoundBatch(
        x=P(axis), y=P(axis), step_mask=P(axis), weights=P(axis), alive=P(axis)
    )


def make_sharded_round_step(
    model: nn.Module,
    cfg: RoundConfig,
    mesh: Mesh,
    compressor=None,  # Optional[fedtpu.ops.compression.Compressor]
    donate: bool = True,
) -> Callable[[FederatedState, RoundBatch], Tuple[FederatedState, RoundMetrics]]:
    """Jitted round step over a client mesh.

    ``cfg.fed.num_clients`` must be divisible by the mesh size; each device
    simulates ``num_clients / mesh_size`` clients.
    """
    axis = cfg.mesh_axis
    n_dev = mesh.devices.size
    if cfg.fed.num_clients % n_dev:
        raise ValueError(
            f"num_clients={cfg.fed.num_clients} not divisible by mesh size {n_dev}"
        )

    body = make_round_step(model, cfg, compressor=compressor, axis_name=axis)

    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=(state_specs(axis), batch_specs(axis)),
        out_specs=(
            state_specs(axis),
            RoundMetrics(P(), P(), P(), P(), P(axis), P(axis)),
        ),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def async_state_specs(axis: str):
    """PartitionSpecs for :class:`fedtpu.core.async_engine.AsyncState`.

    Same layout rule as the sync state: the global model (and the server
    optimizer moments + version counter) replicated, every per-client array
    sharded along the clients axis. Async's defining extra — per-client
    DIVERGED model copies (``client_*``) and pull snapshots (``base_*``) —
    shard by client exactly like presharded data rows, so per-device HBM is
    ``3 * params * clients_per_device`` (local + base + momentum) instead of
    ``3 * params * clients``: the mesh is what makes large async
    populations fit, not a reason async can't shard.
    """
    from fedtpu.core.async_engine import AsyncState

    return AsyncState(
        params=P(),
        batch_stats=P(),
        client_params=P(axis),
        client_stats=P(axis),
        base_params=P(axis),
        base_stats=P(axis),
        opt_state=P(axis),
        client_rng=P(axis),
        base_version=P(axis),
        version=P(),
        pending=P(axis),
        server_opt_state=P(),
        last_client_loss=P(axis),
    )


def _async_data_specs(axis: str, layout: str):
    """(images, labels, idx, mask) specs per device layout — mirrors
    ``Federation._ensure_device_data``: presharded per-client rows shard by
    client; the gather layout's flat dataset is replicated with only the
    assignment sharded."""
    if layout == "presharded":
        return (P(axis), P(axis), P(axis), P(axis))
    return (P(), P(), P(axis), P(axis))


def make_sharded_async_step(
    model: nn.Module,
    cfg: RoundConfig,
    mesh: Mesh,
    steps: int,
    staleness_power: float = 0.5,
    shuffle: bool = True,
    image_shape=None,
    layout: str = "presharded",
    num_ticks: int | None = None,
    staleness_damping: bool = True,
):
    """Jitted FedBuff tick (or ``num_ticks``-tick fused scan) over a client
    mesh — the async analogue of :func:`make_sharded_round_step`. Buffer
    aggregation and scalar metrics are ``psum`` collectives over ICI; the
    host schedules arrivals exactly as in the single-program form.
    """
    from fedtpu.core.async_engine import (
        AsyncMetrics,
        make_async_step,
        make_multi_async_step,
    )

    axis = cfg.mesh_axis
    n_dev = mesh.devices.size
    if cfg.fed.num_clients % n_dev:
        raise ValueError(
            f"num_clients={cfg.fed.num_clients} not divisible by mesh size {n_dev}"
        )
    if num_ticks is None:
        body = make_async_step(
            model, cfg, steps, staleness_power, shuffle=shuffle,
            image_shape=image_shape, layout=layout, axis_name=axis,
            staleness_damping=staleness_damping,
        )
        sched_spec = P(axis)  # arrive/alive: [clients]
    else:
        body = make_multi_async_step(
            model, cfg, steps, num_ticks, staleness_power, shuffle=shuffle,
            image_shape=image_shape, layout=layout, axis_name=axis,
            staleness_damping=staleness_damping,
        )
        sched_spec = P(None, axis)  # arrive/alive: [ticks, clients]

    metric_specs = AsyncMetrics(
        loss=P(), accuracy=P(), num_arrived=P(), staleness_mean=P(),
        update_norm=P(), per_client_loss=P(axis),
    )
    if num_ticks is not None:
        # Stacked over the scan axis: scalars gain a leading ticks dim.
        metric_specs = AsyncMetrics(
            loss=P(), accuracy=P(), num_arrived=P(), staleness_mean=P(),
            update_norm=P(), per_client_loss=P(None, axis),
        )
    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            async_state_specs(axis),
            *_async_data_specs(axis, layout),
            P(axis),      # weights
            sched_spec,   # arrive
            sched_spec,   # alive
            P(),          # data_key
        ),
        out_specs=(async_state_specs(axis), metric_specs),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,))


def _put(x, mesh: Mesh, spec) -> jax.Array:
    """Place a host-global array onto the mesh.

    Single-process: plain ``device_put`` (device-to-device for inputs already
    on device — no host roundtrip). Multi-controller: ``make_array_from_callback``
    so each process materialises only the shards its local devices own, even
    though the mesh spans every host (see :mod:`fedtpu.parallel.multihost`).
    """
    import numpy as np

    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    arr = np.asarray(x)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def shard_state(state: FederatedState, mesh: Mesh, axis: str) -> FederatedState:
    """Place a host-built FederatedState onto the mesh with the right
    shardings (global model replicated, client state split)."""
    specs = state_specs(axis)

    def put(x, spec):
        return _put(x, mesh, spec)

    return FederatedState(
        params=jax.tree.map(lambda x: put(x, specs.params), state.params),
        batch_stats=jax.tree.map(
            lambda x: put(x, specs.batch_stats), state.batch_stats
        ),
        opt_state=jax.tree.map(lambda x: put(x, P(axis)), state.opt_state),
        client_rng=put(state.client_rng, P(axis)),
        round_idx=put(state.round_idx, P()),
        comp_state=jax.tree.map(lambda x: put(x, P(axis)), state.comp_state),
        server_opt_state=jax.tree.map(
            lambda x: put(x, P()), state.server_opt_state
        ),
        last_client_loss=put(state.last_client_loss, P(axis)),
    )


def shard_batch(batch: RoundBatch, mesh: Mesh, axis: str) -> RoundBatch:
    def put(x, spec):
        return _put(x, mesh, spec)

    return RoundBatch(
        x=put(batch.x, P(axis)),
        y=put(batch.y, P(axis)),
        step_mask=put(batch.step_mask, P(axis)),
        weights=put(batch.weights, P(axis)),
        alive=put(batch.alive, P(axis)),
    )
