"""Device-resident data pipeline.

The dataset and the client-assignment matrix are uploaded to HBM ONCE; each
round's static-shape batch tensors are then produced on device *inside* the
jitted round program. This replaces a per-round host rebuild (~600 MB of
numpy fancy-indexing + H2D transfer at the 64-client CIFAR bench config).

Two HBM layouts (``DataConfig.device_layout``):

* ``"presharded"`` (default): the dataset is reorganised ONCE at upload into
  ``[clients, 2*shard_len, features]`` (:func:`preshard_arrays`), so each
  round's batches are ONE contiguous ``dynamic_slice`` at a per-round
  rotation offset. XLA:TPU lowers a computed-index row-gather into a serial
  ~2 us dynamic-slice loop per row, so the layout converts per-round data
  extraction from O(rows) serial ops to one DMA. Attribution honesty
  (round-4 trace history): the first trace blamed the batch gather for ~80%
  of the fused dispatch, but re-measuring after this layout shipped moved
  the bench only 246→250 client-epochs/s/chip — the dominant serial loop
  was actually the per-example augmentation crop + CE label gather (fixed
  in ``fedtpu/data/augment.py`` / ``fedtpu/ops/losses.py``; see
  ``artifacts/MFU_PROFILE_r04*.json`` and BASELINE.md). Presharded remains
  the default for the DMA-shaped extraction, the per-client sharding under
  ``shard_map``, and the bf16 residency it composes with.
* ``"gather"``: dataset stays ``[N, features]``; per-round index gather.
  Exact per-round permutation shuffling and no 2x data HBM, at the measured
  gather cost. This is the exact semantics of the rounds-1-3 artifacts.
  It is also what the massive-cohort simulation layer (:mod:`fedtpu.sim`)
  requires: the assignment matrix stays a *program input* of static shape,
  so swapping which population clients the cohort's device slots represent
  is a values-only ``idx``/``mask`` replacement per round
  (:meth:`fedtpu.core.engine.Federation.set_assignment`) — no recompile,
  no re-upload of the dataset. Presharding would bake the assignment into
  the uploaded per-client rows, costing an O(cohort·shard·features)
  re-preshard every cohort change.

The reference's analogue is its torch DataLoader re-iterated every epoch on
the host (``src/main.py:140-144``); there is deliberately no counterpart to
this module there — it exists because the TPU round loop must not block on
host data preparation.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from fedtpu.config import RoundConfig
from fedtpu.core.round import (
    FederatedState,
    RoundBatch,
    RoundMetrics,
    make_round_step,
)


def round_take_indices(
    idx: jnp.ndarray,
    mask: jnp.ndarray,
    need: int,
    rng: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """Per-client gather indices for one round, entirely on device.

    ``idx``/``mask``: the padded ``[clients, shard_len]`` assignment from
    :mod:`fedtpu.data.partition`. Returns ``take: [clients, need]`` where each
    client's row cycles through its own shard (in random order when ``rng`` is
    given, else in shard order — the reference iterates an *unshuffled* loader
    in federated mode, ``src/main.py:140``). Shards shorter than ``need`` wrap
    around, exactly like the host-side ``make_client_batches``. Clients with
    empty shards return index 0 rows; callers mask their steps out.
    """
    shard_len = idx.shape[1]
    lengths = jnp.maximum(mask.sum(axis=1), 1)  # [clients]
    if rng is None:
        ordered = idx
    else:
        # Random order with invalid slots sorted last: uniform keys, +inf on
        # padding, argsort. One independent permutation per client per round.
        keys = jax.random.uniform(rng, idx.shape)
        keys = jnp.where(mask, keys, jnp.inf)
        order = jnp.argsort(keys, axis=1)
        ordered = jnp.take_along_axis(idx, order, axis=1)
    pos = jnp.arange(need, dtype=jnp.int32)[None, :] % lengths[:, None]
    return jnp.take_along_axis(ordered, pos.astype(jnp.int32), axis=1)


def preshard_arrays(images, labels, idx, mask):
    """Reorganise the dataset into the per-client contiguous layout, ONCE.

    Returns ``(xs_c, ys_c)`` with ``xs_c: [clients, 2*L, features]`` float32
    and ``ys_c: [clients, 2*L]`` int32, where ``L = idx.shape[1]`` (the
    padded shard length). Each client's row is its own shard CYCLED to fill
    ``L`` (a shard of ``k`` examples repeats every ``k`` slots — the same
    wraparound rule as :func:`round_take_indices`'s ``pos % length``), then
    stored twice along the shard axis so any rotated window of length
    ``<= L`` is one contiguous slice. Images are flattened to rows
    (``[*, H*W*C]``): flat rows tile exactly under TPU tiled layouts where
    NHWC tensors pad ~4x. Clients with empty shards get zero rows; callers
    mask them out via ``mask.any(axis=1)`` exactly as in the gather layout.

    Cost: ``clients * 2L * features`` floats — 2x the dataset when shards
    are balanced (L ~= N/clients), but L is the padded MAX shard length, so
    a skewed non-iid partition (low-alpha dirichlet) pays
    ``clients * 2 * max_shard`` instead. The engine falls back to the gather
    layout automatically when this footprint is disproportionate
    (:meth:`fedtpu.core.engine.Federation._ensure_device_data` docs). Under
    ``shard_map`` the rows shard by CLIENT, so each device stores only its
    own clients' data (the gather layout replicates the full dataset to
    every device).
    """
    import numpy as np

    images = np.asarray(images, np.float32).reshape(len(images), -1)
    labels = np.asarray(labels, np.int32)
    idx = np.asarray(idx)
    mask = np.asarray(mask, bool)
    n, L = idx.shape
    xs = np.zeros((n, L, images.shape[1]), np.float32)
    ys = np.zeros((n, L), np.int32)
    for c in range(n):
        own = idx[c][mask[c]]
        if len(own):
            cyc = own[np.arange(L) % len(own)]
            xs[c] = images[cyc]
            ys[c] = labels[cyc]
    return (
        np.concatenate([xs, xs], axis=1),
        np.concatenate([ys, ys], axis=1),
    )


def _round_offset(labels, shuffle, rng):
    """Per-round rotation offset into the doubled presharded axis, shared
    across clients (and across mesh shards — no ``axis_index`` fold, so the
    sharded program is bit-identical to the single-program one). Unshuffled
    mode starts every round at the shard head, matching the reference's
    restart-per-epoch unshuffled loader (``src/main.py:140``) and the gather
    layout's ``shuffle=False`` prefix rule bit-for-bit."""
    L = labels.shape[1] // 2
    if rng is None or not shuffle:
        return jnp.zeros((), jnp.int32), L
    return jax.random.randint(rng, (), 0, L, dtype=jnp.int32), L


def make_data_round_step(
    model,
    cfg: RoundConfig,
    steps: int,
    compressor=None,
    shuffle: bool = True,
    axis_name: Optional[str] = None,
    stream: Optional[bool] = None,
    image_shape: Optional[Tuple[int, ...]] = None,
    layout: str = "presharded",
) -> Callable[..., Tuple[FederatedState, RoundMetrics]]:
    """Round step that extracts its own batches from the device-resident
    dataset: ``step(state, images, labels, idx, mask, weights, alive,
    data_key)``. The extraction + reshape fuse into the same XLA program as
    the local training scan and the FedAvg aggregation, so the host
    contributes nothing per round beyond the (tiny) ``alive`` mask.

    ``layout`` selects the HBM layout (see module docstring): with
    ``"presharded"``, ``images``/``labels`` are the ``[clients, 2L, ...]``
    outputs of :func:`preshard_arrays` and the per-round batch tensor is one
    contiguous rotated slice; ``idx`` is ignored (``mask`` still provides
    the has-data/weight masking). With ``"gather"`` they are the flat
    ``[N, ...]`` dataset and batches come from a per-round index gather.
    Shuffling semantics differ deliberately: gather reshuffles each client's
    shard into fresh batches every round (a true per-round permutation);
    presharded rotates the fixed shard order by a shared random offset each
    round ("shuffle once, rotate per round" — the standard trade for making
    the extraction a contiguous DMA). With ``shuffle=False`` the two layouts
    are bit-identical.

    With ``axis_name`` set this is the per-shard body for ``shard_map`` over
    a clients mesh (see :func:`make_sharded_data_round_step`): ``idx``,
    ``mask``, ``weights`` and ``alive`` are then the LOCAL client rows while
    ``images``/``labels`` are replicated, so each device gathers only its own
    clients' batches and aggregation psums over the mesh.

    ``stream`` (default: ``cfg.remat``, since both matter for the same
    big-model configs): gather each step's batch INSIDE the training scan
    instead of materialising all ``[clients, steps, batch, ...]`` up front —
    the full tensor never exists in HBM, only per-step batches. Numerically
    identical; the default stays off for small models where one big fused
    gather is faster.
    """
    if stream is None:
        stream = cfg.remat
    if layout not in ("presharded", "gather"):
        raise ValueError(
            f"unknown device_layout {layout!r}; have presharded | gather"
        )
    shape = tuple(image_shape or cfg.image_size)
    base = make_round_step(
        model, cfg, compressor, axis_name=axis_name,
        stream=(layout if stream else False), image_shape=shape,
    )
    batch_size = cfg.data.batch_size
    need = steps * batch_size

    def gather_step(
        state: FederatedState,
        images: jnp.ndarray,
        labels: jnp.ndarray,
        idx: jnp.ndarray,
        mask: jnp.ndarray,
        weights: jnp.ndarray,
        alive: jnp.ndarray,
        data_key: jax.Array,
        attack_seats=None,
    ) -> Tuple[FederatedState, RoundMetrics]:
        n = idx.shape[0]
        atk = () if attack_seats is None else attack_seats
        rng = None
        with jax.named_scope("fed.data"):
            if shuffle:
                rng = jax.random.fold_in(data_key, state.round_idx)
                if axis_name is not None:
                    # Decorrelate shuffles across mesh shards (the body sees
                    # only its local client rows; without this every device
                    # would draw the same per-row permutation pattern).
                    rng = jax.random.fold_in(
                        rng, jax.lax.axis_index(axis_name)
                    )
            take = round_take_indices(idx, mask, need, rng)
            has_data = mask.any(axis=1)
            step_mask = jnp.broadcast_to(has_data[:, None], (n, steps))
            if stream:
                takes = take.reshape((n, steps, batch_size))
        if stream:
            batch = RoundBatch(
                x=takes, y=takes, step_mask=step_mask, weights=weights,
                alive=alive, attack_seats=atk,
            )
            return base(state, batch, images, labels)
        with jax.named_scope("fed.data"):
            # Dataset may be stored flat ([N, H*W*C] — the TPU-friendly
            # layout, reshaped back via image_shape) or as images (shape
            # from the array).
            tail = shape if images.ndim == 2 else tuple(images.shape[1:])
            x = images[take].reshape((n, steps, batch_size) + tail)
            y = labels[take].reshape(
                (n, steps, batch_size) + tuple(labels.shape[1:])
            )
        batch = RoundBatch(
            x=x, y=y, step_mask=step_mask, weights=weights, alive=alive,
            attack_seats=atk,
        )
        return base(state, batch)

    def presharded_step(
        state: FederatedState,
        images: jnp.ndarray,
        labels: jnp.ndarray,
        idx: jnp.ndarray,
        mask: jnp.ndarray,
        weights: jnp.ndarray,
        alive: jnp.ndarray,
        data_key: jax.Array,
        attack_seats=None,
    ) -> Tuple[FederatedState, RoundMetrics]:
        n = mask.shape[0]
        atk = () if attack_seats is None else attack_seats
        with jax.named_scope("fed.data"):
            rng = (
                jax.random.fold_in(data_key, state.round_idx)
                if shuffle else None
            )
            off, shard_len = _round_offset(labels, shuffle, rng)
            has_data = mask.any(axis=1)
            step_mask = jnp.broadcast_to(has_data[:, None], (n, steps))
            x, y = presharded_window(
                images, labels, off, steps, batch_size, shape, stream=stream
            )
        batch = RoundBatch(
            x=x, y=y, step_mask=step_mask, weights=weights, alive=alive,
            attack_seats=atk,
        )
        if stream:
            return base(state, batch, images, labels)
        return base(state, batch)

    return presharded_step if layout == "presharded" else gather_step


def presharded_window(images, labels, off, steps, batch_size, shape,
                      stream=False):
    """Extract one round's batch tensors from the presharded layout.

    ``images: [n, 2L, F]`` / ``labels: [n, 2L]`` (:func:`preshard_arrays`),
    ``off``: scalar rotation offset in ``[0, L)``. Non-stream returns
    ``(x: [n, steps, batch, *shape], y: [n, steps, batch])`` — ONE
    contiguous ``dynamic_slice`` when the window fits in an epoch, or an
    epoch slice tiled to length when ``steps*batch > L`` (multi-local-epoch
    cycling, the ``pos % length`` rule). Stream mode returns per-step
    offsets ``[n, steps]`` instead; the slicing then happens inside the
    training scan (:mod:`fedtpu.core.client`), so nothing
    ``[n, steps, batch, ...]``-sized is ever materialised.
    """
    n, L2 = labels.shape
    L = L2 // 2
    need = steps * batch_size
    if stream:
        if batch_size > L:
            raise ValueError(
                f"presharded stream mode needs batch_size <= shard length "
                f"({batch_size} > {L}); use device_layout='gather'"
            )
        offs = (off + jnp.arange(steps, dtype=jnp.int32) * batch_size) % L
        offs = jnp.broadcast_to(offs[None, :], (n, steps))
        return offs, offs
    f_tail = tuple(images.shape[2:])
    if need <= L:
        x = jax.lax.dynamic_slice(
            images, (0, off) + (0,) * len(f_tail), (n, need) + f_tail
        )
        y = jax.lax.dynamic_slice(labels, (0, off), (n, need))
    else:
        reps = -(-need // L)
        xw = jax.lax.dynamic_slice(
            images, (0, off) + (0,) * len(f_tail), (n, L) + f_tail
        )
        yw = jax.lax.dynamic_slice(labels, (0, off), (n, L))
        x = jnp.tile(xw, (1, reps) + (1,) * len(f_tail))[:, :need]
        y = jnp.tile(yw, (1, reps))[:, :need]
    tail = shape if len(f_tail) == 1 else f_tail
    x = x.reshape((n, steps, batch_size) + tail)
    y = y.reshape((n, steps, batch_size))
    return x, y


def make_multi_round_step(
    model,
    cfg: RoundConfig,
    steps: int,
    num_rounds: int,
    compressor=None,
    shuffle: bool = True,
    axis_name: Optional[str] = None,
    stream: Optional[bool] = None,
    image_shape: Optional[Tuple[int, ...]] = None,
    layout: str = "presharded",
) -> Callable[..., Tuple[FederatedState, RoundMetrics]]:
    """``num_rounds`` federated rounds as ONE XLA program (``lax.scan``).

    The reference pays a full host round-trip per round — thread fan-out,
    blocking RPCs, checkpoint files (``src/server.py:120-153``). The jitted
    single-round step already collapses that to one dispatch per round;
    scanning the round body keeps the WHOLE multi-round run on device, with
    no dispatch or sync between rounds: per-round batches are still gathered fresh inside each scan
    iteration (``round_take_indices`` folds ``round_idx`` into the shuffle
    key, so round r's batches are identical to the sequential path's), and
    per-round metrics come back stacked ``[num_rounds, ...]``.

    Signature matches :func:`make_data_round_step` except ``alive`` is
    ``[num_rounds, clients]`` — one participation mask per round, so
    heartbeat deaths / client subsampling still vary per round inside the
    fused program. Returns ``(final_state, metrics_stacked)``.
    """
    body = make_data_round_step(
        model, cfg, steps, compressor, shuffle=shuffle, axis_name=axis_name,
        stream=stream, image_shape=image_shape, layout=layout,
    )

    def multi(
        state: FederatedState,
        images: jnp.ndarray,
        labels: jnp.ndarray,
        idx: jnp.ndarray,
        mask: jnp.ndarray,
        weights: jnp.ndarray,
        alive: jnp.ndarray,
        data_key: jax.Array,
        attack_seats=None,
    ) -> Tuple[FederatedState, RoundMetrics]:
        # attack_seats is per-BLOCK static (the fused block runs one cohort;
        # per-round fire decisions still vary inside the scan via round_idx).
        def scan_body(st, alive_r):
            return body(st, images, labels, idx, mask, weights, alive_r,
                        data_key, attack_seats)

        return jax.lax.scan(scan_body, state, alive, length=num_rounds)

    return multi


def _shard_wrap(body, cfg: RoundConfig, mesh, alive_ndim: int, donate: bool,
                layout: str = "presharded"):
    """Common shard_map+jit wrapper for the data-round bodies.

    Per-client state/assignment shard on the clients axis; FedAvg psums over
    ICI. The dataset's spec depends on the layout: presharded rows are
    per-client, so they SHARD on the clients axis (each device stores only
    its own clients' data); the gather layout's flat dataset replicates to
    every device (CIFAR-scale data fits HBM many times over, and replication
    keeps the gather local — no cross-chip data motion). ``alive_ndim`` is 1
    for a single-round body (``[clients]``) or 2 for the multi-round scan
    (``[rounds, clients]``, client axis sharded).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from fedtpu.parallel.sharded import state_specs

    axis = cfg.mesh_axis
    if cfg.fed.num_clients % mesh.devices.size:
        raise ValueError(
            f"num_clients={cfg.fed.num_clients} not divisible by mesh size "
            f"{mesh.devices.size}"
        )
    data_spec = P(axis) if layout == "presharded" else P()
    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            state_specs(axis),  # state
            data_spec,          # images ([clients, 2L, F] | flat replicated)
            data_spec,          # labels
            P(axis),            # idx
            P(axis),            # mask
            P(axis),            # weights
            P(axis) if alive_ndim == 1 else P(None, axis),  # alive
            P(),                # data_key
        ),
        out_specs=(
            state_specs(axis),
            # Scalar metrics replicate; per_client_loss and the screening
            # mask shard on their client axis — axis 0 for one round,
            # axis 1 when the scan stacks [R, n].
            RoundMetrics(
                P(), P(), P(), P(),
                P(axis) if alive_ndim == 1 else P(None, axis),
                P(axis) if alive_ndim == 1 else P(None, axis),
            ),
        ),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def make_sharded_multi_round_step(
    model,
    cfg: RoundConfig,
    steps: int,
    num_rounds: int,
    mesh,
    compressor=None,
    shuffle: bool = True,
    donate: bool = True,
    stream: Optional[bool] = None,
    image_shape: Optional[Tuple[int, ...]] = None,
    layout: str = "presharded",
):
    """Mesh-parallel form of :func:`make_multi_round_step`: the scan runs
    inside ``shard_map``, so a whole multi-round run is one program with one
    psum per round over ICI and zero host involvement between rounds.
    ``alive`` is ``[num_rounds, clients]``, sharded on its client axis."""
    body = make_multi_round_step(
        model, cfg, steps, num_rounds, compressor, shuffle=shuffle,
        axis_name=cfg.mesh_axis, stream=stream, image_shape=image_shape,
        layout=layout,
    )
    return _shard_wrap(body, cfg, mesh, alive_ndim=2, donate=donate,
                       layout=layout)


def make_sharded_data_round_step(
    model,
    cfg: RoundConfig,
    steps: int,
    mesh,
    compressor=None,
    shuffle: bool = True,
    donate: bool = True,
    stream: Optional[bool] = None,
    image_shape: Optional[Tuple[int, ...]] = None,
    layout: str = "presharded",
):
    """Mesh-parallel round step with the on-device batch extraction inside
    each shard.

    Call signature matches :func:`make_data_round_step`; inputs must be
    placed with :func:`shard_data_arrays` / :func:`fedtpu.parallel.shard_state`.
    Sharding layout: see :func:`_shard_wrap`.
    """
    body = make_data_round_step(
        model, cfg, steps, compressor, shuffle=shuffle, axis_name=cfg.mesh_axis,
        stream=stream, image_shape=image_shape, layout=layout,
    )
    return _shard_wrap(body, cfg, mesh, alive_ndim=1, donate=donate,
                       layout=layout)
