"""The federated round — one jitted XLA program.

Replaces the reference's entire orchestration layer (``src/server.py:113-179``:
thread-per-client fan-out, blocking unary RPCs, checkpoint files as messages,
host-side key-wise averaging) with:

    vmap(local_update) over the clients axis  →  compress deltas (optional)
    →  masked weighted mean  →  new global model

No host transfer, no serialization, no files. On a mesh, the same round step
runs under ``shard_map`` with the vmap axis sharded and the mean becoming a
``lax.psum`` over ICI (see :mod:`fedtpu.parallel.sharded`).
"""

from __future__ import annotations

import dataclasses
import inspect
import logging
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedtpu.config import (
    RoundConfig,
    screening_enabled,
    validate_screen_config,
)
from fedtpu.core import optim
from fedtpu.core.client import ClientOutput, make_local_update
from fedtpu.utils import trees

Pytree = Any

log = logging.getLogger("fedtpu.round")

# Aggregators already warned about ignoring example-count weights (warn
# ONCE per process per aggregator — the message is for operators reading a
# startup log, not a per-round nag).
_WEIGHTED_ROBUST_WARNED = set()


def warn_weighted_robust(aggregator: str) -> bool:
    """Robust aggregators deliberately ignore ``weighted=True`` example
    counts (a count-weighted robust statistic would hand adversaries their
    influence back through inflated self-reported counts) — but silently,
    which reads as a bug to an operator who set ``weighted=True``. Say it
    once, loudly; callers also stamp a ``weights_ignored`` flag on round
    records. Returns True when the combination applies."""
    if aggregator == "mean":
        return False
    if aggregator not in _WEIGHTED_ROBUST_WARNED:
        _WEIGHTED_ROBUST_WARNED.add(aggregator)
        log.warning(
            "aggregator=%r ignores example-count weights (weighted=True has "
            "no effect on the combine): robust statistics weight clients "
            "uniformly by design — self-reported counts are an adversary's "
            "influence knob. Set weighted=False to silence this.",
            aggregator,
        )
    return True


class FederatedState(NamedTuple):
    """Persistent cross-round state.

    - ``params`` / ``batch_stats``: the global model (the reference's
      ``optimizedModel.pth``, ``src/server.py:174-179``).
    - ``opt_state``: per-client momentum, stacked on a leading clients axis —
      persists across rounds exactly as each reference client process keeps
      its torch optimizer alive between StartTrain calls (``src/main.py:99``).
    - ``client_rng``: per-client PRNG keys, ``[clients, 2]`` uint32.
    - ``round_idx``: drives the cosine LR schedule.
    - ``comp_state``: per-client compressor residuals (error feedback,
      :mod:`fedtpu.ops.compression`); the empty pytree ``()`` when
      compression or error feedback is off.
    - ``server_opt_state``: server optimizer moments over the global model
      (:mod:`fedtpu.core.server_opt`, the FedOpt family); ``()`` for plain
      FedAvg.
    - ``last_client_loss``: ``[clients]`` f32, each client's most recent
      observed training loss (NaN until first observed; dead/unsampled
      clients keep their previous value). Updated inside the round step —
      so fused scans accumulate it per ROUND on device — and checkpointed
      with the rest of the state. Feeds loss-proportional participation
      sampling (:class:`fedtpu.config.FedConfig`).
    """

    params: Pytree
    batch_stats: Pytree
    opt_state: optim.SGDState
    client_rng: jnp.ndarray
    round_idx: jnp.ndarray
    comp_state: Pytree = ()
    server_opt_state: Pytree = ()
    last_client_loss: jnp.ndarray = ()


class RoundMetrics(NamedTuple):
    """``loss``/``accuracy`` average over ACTIVE clients; ``per_client_loss``
    is the raw ``[clients]`` vector (0 for dead/unsampled clients) — the
    observability hook for spotting a diverging or poisoned client, which
    pairs with the robust aggregators. The reference can only print
    per-batch console lines inside each client process
    (``src/utils.py:51-92``).

    Multi-controller caveat: unlike the replicated scalars,
    ``per_client_loss`` is SHARDED along the mesh's clients axis, so on a
    mesh spanning processes each host can ``np.asarray`` only its local
    slice; use ``jax.experimental.multihost_utils.process_allgather`` to
    fetch the global vector."""

    loss: jnp.ndarray
    accuracy: jnp.ndarray
    num_active: jnp.ndarray
    update_norm: jnp.ndarray
    per_client_loss: jnp.ndarray
    # ``[clients]`` bool: rows REJECTED by the fused screening stage this
    # round (always all-False when screening is off). Sharded like
    # per_client_loss on a mesh.
    screened: jnp.ndarray = ()
    # A token model's counters over the round's live clients and steps
    # (``()`` for every other model; docs/OBSERVABILITY.md): target positions
    # trained on; (token, expert) pairs the held experts computed, none
    # dropped; the busiest held expert's load over the held experts' mean,
    # the largest any expert layer of any step saw.
    tokens: jnp.ndarray = ()
    moe_pairs_here: jnp.ndarray = ()
    moe_load_max_over_mean: jnp.ndarray = ()


class RoundBatch(NamedTuple):
    """One round of input data for all clients, static shapes.

    ``x: [clients, steps, batch, ...]``, ``y: [clients, steps, batch]``,
    ``step_mask: [clients, steps]`` (ragged-shard padding),
    ``weights: [clients]`` (example counts for weighted FedAvg),
    ``alive: [clients]`` (participation mask — the jitted form of the
    reference's heartbeat-maintained ``clients[addr] = True/False`` registry,
    ``src/server.py:59-62,78-101``).
    """

    x: jnp.ndarray
    y: jnp.ndarray
    step_mask: jnp.ndarray
    weights: jnp.ndarray
    alive: jnp.ndarray
    # ``[clients]`` f32/bool attacker-seat mask for the seeded adversarial
    # harness (fedtpu.sim.adversary): 1 = this SEAT currently hosts a
    # malicious client. ``()`` (default) = no attack plumbing — the round
    # step only reads it when the config arms an attack
    # (``sim.malicious_fraction > 0``), so benign programs are unchanged.
    attack_seats: Any = ()


def init_state(
    model: nn.Module,
    cfg: RoundConfig,
    rng: jax.Array,
    sample_input: jnp.ndarray,
    compressor=None,
) -> FederatedState:
    """Initialise global model + per-client state. ``compressor`` (a
    :class:`fedtpu.ops.compression.Compressor`) seeds error-feedback
    residuals when given."""
    init_rng, client_rng = jax.random.split(rng)
    if jnp.issubdtype(sample_input.dtype, jnp.integer):
        # Token models: no leaf's shape depends on the sequence's length, so
        # a few positions do (a forward over the whole sequence at half a
        # billion parameters is a minute's compile); one jitted program; the
        # training pass, which also builds a prediction module's leaves.
        few = sample_input[:, :8]
        variables = jax.jit(
            lambda r, x: model.init(r, x, train=True, targets=x)
        )(init_rng, few)
    else:
        variables = model.init(init_rng, sample_input, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    if cfg.fed.dp_clip_norm > 0 and jax.tree_util.tree_leaves(batch_stats):
        raise ValueError(
            "DP requires a BatchNorm-free model: batch statistics are "
            "unbounded functions of client data and are released unclipped "
            "and unnoised, voiding the sensitivity bound. Pick a model "
            "without batch_stats (e.g. mlp)."
        )
    n = cfg.fed.num_clients
    # Per-client momentum buffers, stacked along a new leading axis.
    # Clients in sequence at momentum 0 keep no buffers: a model that takes
    # this schedule is one of which a chip holds a single local copy.
    single = optim.init(
        params, cfg.opt,
        buffers=not (cfg.fed.client_schedule == "sequential"
                     and cfg.opt.momentum == 0),
    )
    opt_state = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), single
    )
    from fedtpu.core import server_opt

    return FederatedState(
        params=params,
        batch_stats=batch_stats,
        opt_state=opt_state,
        client_rng=jax.random.split(client_rng, n),
        round_idx=jnp.zeros((), jnp.int32),
        comp_state=() if compressor is None else compressor.init(params, n),
        server_opt_state=server_opt.init(cfg.fed, params),
        last_client_loss=jnp.full((n,), jnp.nan, jnp.float32),
    )


@jax.named_scope("fed.aggregate")
def _robust_over_clients(
    stacked: Pytree,
    alive_w: jnp.ndarray,
    axis_name,
    aggregator: str,
    trim: float,
):
    """Coordinate-wise Byzantine-robust combine over the clients axis.

    ``median``: per-coordinate median of live clients' deltas.
    ``trimmed_mean``: mask coordinates outside the [trim, 1-trim] quantile
    band, then average the survivors (Yin et al. 2018, coordinate-wise).
    Dead/unsampled clients (``alive_w == 0``) are excluded via NaN-masking.
    Example-count weights are deliberately ignored: a robust aggregator that
    weighted by client-reported counts would hand adversaries their
    influence back.

    Under ``shard_map`` the statistic is global per coordinate, so the local
    client slices are first ``all_gather``-ed along the mesh axis — the
    collective rides ICI; the host never participates. This costs one full
    per-client delta tree per device; fine at CNN scale, and the price of a
    true global median (a mean can psum partial sums, a median cannot).
    """
    if aggregator == "trimmed_mean" and trim == 0.0:
        # trim 0 trims nothing: route through the EXACT uniform-mean ops so
        # the result is BIT-IDENTICAL to aggregator='mean' with
        # weighted=False (pinned in tests/test_robust_agg.py) — the
        # quantile-band formulation reduces the same values in a different
        # op order and drifts in the last ulp.
        return _mean_over_clients(
            stacked, (alive_w > 0).astype(jnp.float32), axis_name
        )[0]
    total = jnp.sum(alive_w)
    if axis_name is not None:
        with jax.named_scope("fed.aggregate.psum"):
            total = jax.lax.psum(total, axis_name)
    alive_any = total > 0

    def leaf(x):
        if axis_name is not None:
            with jax.named_scope("fed.aggregate.all_gather"):
                x = jax.lax.all_gather(x, axis_name, axis=0, tiled=True)
                w = jax.lax.all_gather(alive_w, axis_name, axis=0, tiled=True)
        else:
            w = alive_w
        mask = (w > 0).reshape((-1,) + (1,) * (x.ndim - 1))
        xf = x.astype(jnp.float32)
        masked = jnp.where(mask, xf, jnp.nan)
        if aggregator == "median":
            out = jnp.nanmedian(masked, axis=0)
        else:  # trimmed_mean
            # Band bounds snap to actual data points (method lower/higher):
            # an interpolated bound can exclude EVERY value at small client
            # counts (verified at n=2), silently zeroing the update.
            lo = jnp.nanquantile(
                masked, trim, axis=0, keepdims=True, method="lower"
            )
            hi = jnp.nanquantile(
                masked, 1.0 - trim, axis=0, keepdims=True, method="higher"
            )
            band = jnp.where(
                (masked >= lo) & (masked <= hi), masked, jnp.nan
            )
            out = jnp.nanmean(band, axis=0)
        # All-dead round (or a coordinate with no survivors): no update.
        out = jnp.nan_to_num(out, nan=0.0)
        return jnp.where(alive_any, out, 0.0).astype(x.dtype)

    return jax.tree.map(leaf, stacked)


_KRUM_BIG = 1e30  # large-finite "infinity": keeps argmin/sums NaN-free


@jax.named_scope("fed.aggregate")
def _krum_over_clients(
    stacked: Pytree,
    alive_w: jnp.ndarray,
    axis_name,
    trim: float,
):
    """Krum selection (Blanchard et al. 2017): pick the single client whose
    delta has the smallest summed squared distance to its ``n - f - 2``
    nearest neighbors, where ``f = floor(trim * n)`` is the assumed
    Byzantine count. TPU-idiomatic: the pairwise distances are ONE MXU
    matmul (``X @ X.T`` on the flattened ``[clients, params]`` matrix).

    Dead/unsampled clients are excluded from both candidacy and neighbor
    sets (large-finite distance). Degenerate when fewer than ``f + 3``
    clients are live — Krum's own precondition. Under ``shard_map`` the
    flattened deltas are ``all_gather``-ed (same cost/shape as the median
    path's gather).
    """
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    shapes = [l.shape for l in leaves]
    sizes = [math.prod(s[1:]) for s in shapes]
    X = jnp.concatenate(
        [l.reshape(l.shape[0], -1).astype(jnp.float32) for l in leaves], axis=1
    )
    w = alive_w
    if axis_name is not None:
        with jax.named_scope("fed.aggregate.all_gather"):
            X = jax.lax.all_gather(X, axis_name, axis=0, tiled=True)
            w = jax.lax.all_gather(w, axis_name, axis=0, tiled=True)
    n = X.shape[0]
    alive = w > 0
    sq = jnp.sum(X * X, axis=1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * (X @ X.T)
    pair_ok = alive[:, None] & alive[None, :]
    d2 = jnp.where(pair_ok, jnp.maximum(d2, 0.0), _KRUM_BIG)
    d2 = d2 + jnp.eye(n, dtype=d2.dtype) * _KRUM_BIG  # self never a neighbor
    # f and the neighbor count k derive from the LIVE count, not the stacked
    # row count: dead/unsampled rows carry only _KRUM_BIG distances, and a
    # static k > n_live - 1 would pull those into every live score —
    # flattening them all to ~k*1e30 in f32 and degrading argmin to "first
    # live index". k is dynamic, so select via a position mask over the
    # ascending sort instead of a static top_k.
    n_alive = jnp.sum(alive.astype(jnp.int32))
    f_dyn = jnp.floor(trim * n_alive).astype(jnp.int32)
    k_dyn = jnp.maximum(1, n_alive - f_dyn - 2)
    d2_sorted = jnp.sort(d2, axis=1)  # BIG (dead/self) entries sort last
    pos_mask = (jnp.arange(n)[None, :] < k_dyn).astype(d2.dtype)
    scores = jnp.sum(d2_sorted * pos_mask, axis=1)
    scores = jnp.where(alive, scores, jnp.inf)
    sel = jnp.argmin(scores)
    chosen = X[sel]
    alive_any = (jnp.sum(w) > 0).astype(jnp.float32)
    parts = []
    off = 0
    for shape, size in zip(shapes, sizes):
        parts.append(chosen[off : off + size].reshape(shape[1:]))
        off += size
    out_leaves = [
        (p * alive_any).astype(l.dtype) for p, l in zip(parts, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


@jax.named_scope("fed.aggregate")
def _dp_clip(stacked: Pytree, clip_norm: float) -> Pytree:
    """Scale each client's delta so its GLOBAL L2 norm (across all leaves)
    is at most ``clip_norm`` (DP-FedAvg per-client sensitivity bound). Each
    client lives wholly on one shard, so no collective is needed."""
    leaves = jax.tree_util.tree_leaves(stacked)
    sq = sum(
        jnp.sum(
            jnp.square(x.astype(jnp.float32)),
            axis=tuple(range(1, x.ndim)),
        )
        for x in leaves
    )
    norm = jnp.sqrt(jnp.maximum(sq, 1e-24))  # [clients]
    scale = jnp.minimum(1.0, clip_norm / norm)
    return jax.tree.map(
        lambda x: (
            x.astype(jnp.float32)
            * scale.reshape((-1,) + (1,) * (x.ndim - 1))
        ).astype(x.dtype),
        stacked,
    )


@jax.named_scope("fed.aggregate")
def _dp_noise(
    tree: Pytree, std: jnp.ndarray, round_idx: jnp.ndarray, seed: int
) -> Pytree:
    """Add seeded Gaussian noise to the aggregated delta. The key depends
    only on (static seed, round) so it is identical on every mesh shard —
    the aggregated delta is replicated and must stay so."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), round_idx)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(base, len(leaves))
    noised = [
        x + (jax.random.normal(k, x.shape, jnp.float32) * std).astype(x.dtype)
        for x, k in zip(leaves, keys)
    ]
    return jax.tree_util.tree_unflatten(treedef, noised)


@jax.named_scope("fed.aggregate")
def flat_weighted_mean(rows: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """Weighted mean over a ``[clients, P]`` flat-row buffer — the streaming
    server pipeline's post-barrier combine (one fused reduce over rows that
    are already device-resident, shipped row-by-row as replies arrived).

    Same per-coordinate math and the same order-stable stacked axis-0
    reduce as :func:`_mean_over_clients` / ``PrimaryServer._aggregate_impl``
    on the equivalent per-leaf tree, so the result is BIT-IDENTICAL to the
    barrier path's mean (the parity the stream tests pin). A running
    row-by-row accumulator would NOT be: a sequential f32 left fold differs
    from XLA's vectorised reduction in the last ulp on most coordinates
    (measured — see docs/PERF_ANALYSIS.md), which is why the stream path
    keeps the rows and reduces them in one op instead of folding eagerly.
    """
    total = jnp.maximum(jnp.sum(weights), 1e-9)
    w = weights.reshape((-1,) + (1,) * (rows.ndim - 1)).astype(rows.dtype)
    return jnp.sum(rows * w, axis=0) / total.astype(rows.dtype)


@jax.named_scope("fed.aggregate")
def _mean_over_clients(stacked: Pytree, weights: jnp.ndarray, axis_name):
    """Masked weighted mean over the clients axis.

    Without ``axis_name`` this is a plain mean over leading axis 0. Under
    ``shard_map`` the clients axis is sharded across devices, so the local
    weighted sums are combined with ``lax.psum`` over the mesh — the TPU-native
    replacement for the reference's host-side ``allreduce()``
    (``src/server.py:155-179``): the collective rides ICI, the host never sees
    a byte.
    """
    total = jnp.sum(weights)
    if axis_name is not None:
        with jax.named_scope("fed.aggregate.psum"):
            total = jax.lax.psum(total, axis_name)
    safe = jnp.where(total > 0, total, 1.0)

    def leaf_mean(x):
        w = weights.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        s = jnp.sum(x * w, axis=0)
        if axis_name is not None:
            with jax.named_scope("fed.aggregate.psum"):
                s = jax.lax.psum(s, axis_name)
        return s / safe.astype(x.dtype)

    mean = jax.tree.map(leaf_mean, stacked)
    # If every client is dead, callers expect "no update": make the mean zero
    # by scaling with [total > 0].
    alive_any = (total > 0).astype(jnp.float32)
    return jax.tree.map(lambda m: m * alive_any.astype(m.dtype), mean), safe


def _round_counters(counters, axis_name):
    """``RoundMetrics``' counter fields from the clients' stacked counters
    (``ClientOutput.counters``; ``()`` for a model that counts nothing)."""
    if not counters:
        return {}
    out = {}
    for name, per_client in counters.items():
        biggest = "_max_" in name
        value = jnp.max(per_client) if biggest else jnp.sum(per_client)
        if axis_name is not None:
            with jax.named_scope("fed.aggregate.psum"):
                value = (jax.lax.pmax if biggest else jax.lax.psum)(
                    value, axis_name)
        out[name] = value
    return out


def make_round_step(
    model: nn.Module,
    cfg: RoundConfig,
    compressor=None,  # Optional[fedtpu.ops.compression.Compressor]
    axis_name: Optional[str] = None,
    stream: bool = False,
    image_shape: Optional[Tuple[int, ...]] = None,
) -> Callable[..., Tuple[FederatedState, RoundMetrics]]:
    """Build the round step.

    With ``axis_name=None`` this is the single-program (vmap-only) form. With
    an axis name it is the *per-shard* body to be wrapped in ``shard_map``
    (see :mod:`fedtpu.parallel.sharded`): the vmap then runs over the local
    slice of clients and aggregation becomes ``psum`` collectives.

    ``compressor``, when given, is a stateful delta codec
    (:class:`fedtpu.ops.compression.Compressor`) — the ``-c Y`` parity path;
    its error-feedback residuals ride in ``state.comp_state``.

    With ``stream`` set the returned function is
    ``round_step(state, batch, images, labels)`` and each scan step extracts
    only its own batch, so nothing ``[clients, steps, batch, ...]``-sized is
    ever materialised — see :mod:`fedtpu.data.device`. Two stream forms:
    ``"gather"`` (alias ``True``): ``batch.x`` holds int32 gather indices
    ``[clients, steps, batch]`` into the flat dataset (``batch.y`` ignored);
    ``"presharded"``: ``images``/``labels`` are the per-client
    ``[clients, 2L, ...]`` presharded arrays and ``batch.x`` holds per-step
    slice offsets ``[clients, steps]``.
    """
    from fedtpu.core import server_opt as server_opt_lib

    if stream is True:
        stream = "gather"

    if cfg.fed.delta_layout not in ("per_leaf", "flat"):
        raise ValueError(
            f"unknown delta_layout {cfg.fed.delta_layout!r}; "
            "have per_leaf | flat"
        )
    flat_mode = cfg.fed.delta_layout == "flat"
    # Seeded codecs (rotq/randk) take the round index as their per-round
    # seed, and rotq needs the power-of-two row padding for the Hadamard
    # rotation — both are static properties of the compressor, resolved
    # once here so the traced body stays branch-free.
    flat_pow2 = compressor is not None and getattr(
        compressor, "pad_pow2", False
    )
    flat_takes_round = (
        compressor is not None
        and compressor.apply_flat is not None
        and "round_idx" in inspect.signature(compressor.apply_flat).parameters
    )
    if compressor is not None:
        comp_layout = getattr(compressor, "layout", "per_leaf")
        if flat_mode and compressor.apply_flat is None:
            raise ValueError(
                "delta_layout='flat' needs a flat-layout compressor "
                "(make_compressor reads FedConfig.delta_layout; or pass "
                "make_topk/make_int8(..., layout='flat'))"
            )
        if not flat_mode and comp_layout == "flat":
            raise ValueError(
                "flat-layout compressor given but "
                "FedConfig.delta_layout='per_leaf' — residual state shapes "
                "would not match; make both agree"
            )
    if cfg.fed.aggregator not in ("mean", "median", "trimmed_mean", "krum"):
        raise ValueError(
            f"unknown aggregator {cfg.fed.aggregator!r}; "
            "have mean | median | trimmed_mean | krum"
        )
    if cfg.fed.client_schedule not in ("vmap", "sequential"):
        raise ValueError(
            f"unknown client_schedule {cfg.fed.client_schedule!r}; "
            "have vmap | sequential"
        )
    sequential = cfg.fed.client_schedule == "sequential"
    if sequential:
        # The clients' rows never exist side by side: only a per-coordinate
        # weighted sum can be kept as a running sum.
        needs_rows = [
            (cfg.fed.aggregator != "mean",
             f"aggregator={cfg.fed.aggregator!r} is a statistic of all rows"),
            (screening_enabled(cfg.fed.screen),
             "update screening compares every row with the others"),
            (cfg.fed.dp_clip_norm > 0,
             "DP clipping is built on the stacked rows"),
            (compressor is not None or cfg.fed.compression != "none",
             f"compression={cfg.fed.compression!r}: the codecs and their "
             "error-feedback state are built on the stacked rows"),
            (flat_mode, "delta_layout='flat' packs every client's row"),
            (cfg.fed.sim.malicious_fraction > 0,
             "the adversarial harness rewrites stacked rows"),
            (axis_name is not None, "a mesh shards the stacked clients axis"),
        ]
        for refused, why in needs_rows:
            if refused:
                raise ValueError(
                    "FedConfig.client_schedule='sequential' keeps a running "
                    f"weighted sum, not the clients' rows, and {why}; use "
                    "client_schedule='vmap'"
                )
    if cfg.fed.weighted:
        warn_weighted_robust(cfg.fed.aggregator)
    # Fused update screening (ScreenConfig; one stats pass over the flat
    # [clients, P] buffer, rejected rows drop out through the agg mask).
    screen = (
        validate_screen_config(cfg.fed.screen)
        if screening_enabled(cfg.fed.screen) else None
    )
    # Seeded adversarial harness (fedtpu.sim.adversary): the attack PLAN is
    # static config; WHICH seats are malicious arrives per round through
    # batch.attack_seats (dynamic under cohort swapping). label_flip acts at
    # the data level (host-side label mutation in the engine) — no delta
    # transform here.
    attack_plan = None
    if cfg.fed.sim.malicious_fraction > 0:
        from fedtpu.sim.adversary import parse_attack

        attack_plan = parse_attack(cfg.fed.sim.attack)
        if attack_plan.kind == "label_flip":
            attack_plan = None
    if cfg.fed.aggregator != "mean":
        if compressor is not None:
            # Top-k deltas are zero outside each client's own top coordinates,
            # so a coordinate-wise median over them is ~0 everywhere — the
            # model would silently stop moving while residuals cycle.
            raise ValueError(
                f"aggregator={cfg.fed.aggregator!r} cannot compose with "
                "delta compression: sparse deltas zero out coordinate-wise "
                "robust statistics. Use compression='none'."
            )
        if not 0.0 <= cfg.fed.trim_fraction < 0.5:
            raise ValueError(
                f"trim_fraction must be in [0, 0.5), got "
                f"{cfg.fed.trim_fraction}"
            )
    if cfg.fed.dp_clip_norm > 0:
        if compressor is not None:
            raise ValueError(
                "DP clipping cannot compose with delta compression: error "
                "feedback re-injects unclipped residual, voiding the "
                "sensitivity bound. Use compression='none'."
            )
        if cfg.fed.weighted:
            raise ValueError(
                "DP requires uniform weighting (FedConfig(weighted=False)): "
                "example-count weights change per-client sensitivity."
            )
        if cfg.fed.aggregator != "mean":
            raise ValueError(
                "DP noise std clip*sigma/n assumes the mean aggregator; "
                f"aggregator={cfg.fed.aggregator!r} has per-client "
                "sensitivity up to ~clip, so the accounting would be "
                "silently invalid. Use aggregator='mean'."
            )
    server_opt = server_opt_lib.make_server_optimizer(cfg.fed)
    local_update = make_local_update(
        model.apply, cfg, stream=stream, image_shape=image_shape
    )
    if stream == "presharded":
        # images/labels are per-client rows — vmapped, unlike the shared
        # flat dataset of the gather form.
        in_axes = (None, None, 0, 0, 0, 0, 0, 0, None)
    elif stream:
        in_axes = (None, None, 0, None, None, 0, 0, 0, None)
    else:
        in_axes = (None, None, 0, 0, 0, 0, 0, None)
    vmapped = jax.vmap(local_update, in_axes=in_axes)

    def clients_in_sequence(state, agg_w, args):
        """``client_schedule='sequential'``: the clients' local updates one
        after another (``args`` as the vmapped call takes them), each
        client's weighted change added into one running sum as it ends.
        Returns ``(out, mean_delta, mean_stats_delta)``, ``out`` a
        :class:`ClientOutput` whose per-client readings are stacked and whose
        ``params`` / ``batch_stats`` are ``None`` (no rows are kept)."""
        total = jnp.sum(agg_w)
        share = agg_w / jnp.where(total > 0, total, 1.0)
        glob = (state.params, state.batch_stats)

        def one_client(acc, mapped):
            mapped, w = mapped
            rest = iter(mapped)
            out = local_update(*(
                next(rest) if ax == 0 else a for a, ax in zip(args, in_axes)
            ))
            with jax.named_scope("fed.aggregate.running_sum"):
                acc = jax.tree.map(
                    lambda s, c, g: s + w.astype(s.dtype) * (c - g),
                    acc, (out.params, out.batch_stats), glob,
                )
            return acc, out._replace(params=None, batch_stats=None)

        (mean_delta, mean_stats_delta), out = jax.lax.scan(
            one_client,
            jax.tree.map(jnp.zeros_like, glob),
            (tuple(a for a, ax in zip(args, in_axes) if ax == 0), share),
        )
        return out, mean_delta, mean_stats_delta

    def round_step(
        state: FederatedState,
        batch: RoundBatch,
        images: Optional[jnp.ndarray] = None,
        labels: Optional[jnp.ndarray] = None,
    ) -> Tuple[FederatedState, RoundMetrics]:
        n = batch.alive.shape[0]
        with jax.named_scope("fed.local_step"):
            rngs = jax.vmap(jax.random.fold_in)(
                state.client_rng, jnp.broadcast_to(state.round_idx, (n,))
            )
            # Dead clients also get their steps masked out: they do no local
            # work, mirroring a crashed reference client that never receives
            # StartTrain.
            step_mask = batch.step_mask & batch.alive[:, None]
        if stream:
            args = (
                state.params,
                state.batch_stats,
                state.opt_state,
                images,
                labels,
                batch.x,
                step_mask,
                rngs,
                state.round_idx,
            )
        else:
            args = (
                state.params,
                state.batch_stats,
                state.opt_state,
                batch.x,
                batch.y,
                step_mask,
                rngs,
                state.round_idx,
            )
        if sequential:
            if cfg.fed.weighted:
                agg_w = batch.weights * batch.alive.astype(batch.weights.dtype)
            else:
                agg_w = batch.alive.astype(jnp.float32)
            out, mean_delta, mean_stats_delta = clients_in_sequence(
                state, agg_w, args
            )
            comp_state, screened = state.comp_state, jnp.zeros((n,), bool)
        else:
            out: ClientOutput = vmapped(*args)
            mean_delta, mean_stats_delta, comp_state, screened = (
                aggregate_rows(state, batch, out, n)
            )
        return finish_round(
            state, batch, out, step_mask, mean_delta, mean_stats_delta,
            comp_state, screened,
        )

    def aggregate_rows(state, batch, out, n):
        """The stacked clients' rows to the aggregated change: pack, attack
        harness, codec, screening, DP, the combine."""
        with jax.named_scope("fed.pack" if flat_mode else "fed.aggregate"):
            if cfg.fed.weighted:
                agg_w = batch.weights * batch.alive.astype(batch.weights.dtype)
            else:
                # Uniform over *active* clients — the reference averages
                # uniformly (src/server.py:163-171) but (buggily) includes dead
                # clients' stale files; we deliberately fix that, see SURVEY
                # §"known bugs".
                agg_w = batch.alive.astype(jnp.float32)

            # Aggregate deltas rather than raw params: required for
            # compression and numerically identical to averaging params when
            # uncompressed.
            deltas = jax.tree.map(
                lambda c, g: c - g[None], out.params, state.params
            )
            if flat_mode:
                # Pack ONCE per round into the lane-aligned [clients, P]
                # buffer (fedtpu.ops.flat): compression, error feedback, DP
                # clipping and the aggregation below each become one op over
                # the whole model. A jnp array is itself a pytree, so every
                # downstream combine (mean/median/trimmed_mean/krum, _dp_clip)
                # applies unchanged; per-coordinate math is untouched, which is
                # what keeps compression='none' and 'int8' bit-identical across
                # layouts.
                from fedtpu.ops import flat as flat_ops

                flat_layout = flat_ops.make_layout(
                    state.params, pow2=flat_pow2
                )
                deltas = flat_ops.pack_stacked(flat_layout, deltas)
            # Model-level adversaries (fedtpu.sim.adversary): malicious
            # seats replace their honest delta with the attacked one BEFORE
            # the codec — the attacker follows the protocol, only its update
            # is hostile. Decisions (round window, per-round fire probability,
            # colluding draws) are pure functions of (plan seed, round_idx)
            # via jax.random — deterministic, so attack runs replay
            # bit-identically from seed.
            atk_fire = None
            if attack_plan is not None and not isinstance(
                batch.attack_seats, tuple
            ):
                from fedtpu.sim.adversary import attack_fire_mask

                atk_fire = attack_fire_mask(
                    attack_plan, batch.attack_seats, state.round_idx, n
                )
                coef = jnp.where(
                    atk_fire, jnp.float32(attack_plan.coef), jnp.float32(1.0)
                )

                def poison(x):
                    c = coef.reshape((-1,) + (1,) * (x.ndim - 1))
                    return (x.astype(jnp.float32) * c).astype(x.dtype)

                if attack_plan.coef != 1.0:
                    deltas = jax.tree.map(poison, deltas)
                if attack_plan.kind == "noise":
                    nkey = jax.random.fold_in(
                        jax.random.PRNGKey(attack_plan.seed ^ 0x4015E5),
                        state.round_idx,
                    )
                    leaves, treedef = jax.tree_util.tree_flatten(deltas)
                    keys = jax.random.split(nkey, max(len(leaves), 1))

                    def noisy(x, k):
                        # Colluding mode: ONE shared noise vector for the
                        # whole malicious set (a consistent fake cluster — the
                        # attack that defeats distance-based selection);
                        # otherwise independent per-seat draws.
                        shape = (
                            x.shape[1:] if attack_plan.collude else x.shape
                        )
                        nz = (
                            jax.random.normal(k, shape, jnp.float32)
                            * attack_plan.std
                        )
                        nz = jnp.broadcast_to(nz, x.shape)
                        m = atk_fire.reshape((-1,) + (1,) * (x.ndim - 1))
                        return jnp.where(
                            m,
                            (x.astype(jnp.float32) + nz).astype(x.dtype),
                            x,
                        )

                    deltas = jax.tree_util.tree_unflatten(
                        treedef,
                        [noisy(x, k) for x, k in zip(leaves, keys)],
                    )
        comp_state = state.comp_state
        if compressor is not None:
            if flat_mode:
                if flat_takes_round:
                    deltas, new_comp = compressor.apply_flat(
                        deltas, comp_state, flat_layout,
                        round_idx=state.round_idx,
                    )
                else:
                    deltas, new_comp = compressor.apply_flat(
                        deltas, comp_state, flat_layout
                    )
            else:
                deltas, new_comp = compressor.apply(deltas, comp_state)
            # Clients contributing nothing this round (agg_w == 0: dead,
            # non-sampled, or zero-weight) must not have their residuals
            # drained either — keep the old residual so the correction is
            # carried until they actually contribute.
            if jax.tree_util.tree_leaves(comp_state):
                with jax.named_scope("fed.codec.feedback"):
                    keep = agg_w > 0
                    comp_state = jax.tree.map(
                        lambda new, old: jnp.where(
                            keep.reshape((-1,) + (1,) * (new.ndim - 1)),
                            new, old,
                        ),
                        new_comp,
                        comp_state,
                    )
            else:
                comp_state = new_comp
        with jax.named_scope("fed.aggregate"):
            # BN stats deltas combine with the same rule as params
            # (reference averages the full state_dict, src/server.py:163-171);
            # computed here because krum must select ONE client jointly for
            # both trees.
            stats_delta = jax.tree.map(
                lambda c, g: c - g[None], out.batch_stats, state.batch_stats
            )
            if atk_fire is not None and attack_plan.coef != 1.0:
                # The attacker poisons its WHOLE submission coherently
                # (krum selects params + stats jointly, so a clean stats tree
                # would leak the honest update).
                stats_delta = jax.tree.map(poison, stats_delta)
            # Fused screening: one stats pass over the flat rows; rejected
            # rows leave the combine through the same zero-weight mask dead
            # clients use, so the weighted mean / robust aggregators are
            # untouched bit-cleanly for the survivors.
            screened = jnp.zeros((n,), bool)
            if screen is not None:
                from fedtpu.ops import flat as screen_flat_ops

                rows = (
                    deltas if flat_mode
                    else screen_flat_ops.pack_stacked(
                        screen_flat_ops.make_layout(state.params), deltas
                    )
                )
                keep, _ = screen_flat_ops.screen_rows(
                    rows, agg_w, screen.norm_max, screen.zmax,
                    screen.cos_min,
                )
                screened = (agg_w > 0) & ~keep
                agg_w = agg_w * keep.astype(agg_w.dtype)
            if cfg.fed.dp_clip_norm > 0:
                deltas = _dp_clip(deltas, cfg.fed.dp_clip_norm)
            if cfg.fed.aggregator == "krum":
                joint = _krum_over_clients(
                    {"p": deltas, "s": stats_delta}, agg_w, axis_name,
                    cfg.fed.trim_fraction,
                )
                mean_delta, mean_stats_delta = joint["p"], joint["s"]
            else:
                if cfg.fed.aggregator == "mean":
                    combine = lambda t: _mean_over_clients(
                        t, agg_w, axis_name
                    )[0]
                else:  # median | trimmed_mean — validated at build time
                    combine = lambda t: _robust_over_clients(
                        t, agg_w, axis_name, cfg.fed.aggregator,
                        cfg.fed.trim_fraction,
                    )
                mean_delta = combine(deltas)
                mean_stats_delta = combine(stats_delta)
        if flat_mode:
            # Unpack ONCE, on the aggregated [P] row (not per client) —
            # BEFORE DP noise so the per-leaf noise draw is identical to the
            # per-leaf layout's.
            mean_delta = flat_ops.unpack(flat_layout, mean_delta)
        if cfg.fed.dp_clip_norm > 0 and cfg.fed.dp_noise_multiplier > 0:
            with jax.named_scope("fed.aggregate"):
                n_participants = jnp.sum((agg_w > 0).astype(jnp.float32))
                if axis_name is not None:
                    with jax.named_scope("fed.aggregate.psum"):
                        n_participants = jax.lax.psum(
                            n_participants, axis_name
                        )
                std = (
                    cfg.fed.dp_clip_norm
                    * cfg.fed.dp_noise_multiplier
                    / jnp.maximum(n_participants, 1.0)
                )
                mean_delta = _dp_noise(
                    mean_delta, std, state.round_idx,
                    seed=cfg.data.seed ^ 0x5F5E5F,
                )
        return mean_delta, mean_stats_delta, comp_state, screened

    def finish_round(
        state, batch, out, step_mask, mean_delta, mean_stats_delta,
        comp_state, screened,
    ):
        new_params, new_server_opt = server_opt_lib.apply(
            server_opt, state.params, mean_delta, state.server_opt_state
        )
        with jax.named_scope("fed.server_step"):
            new_stats = trees.tree_add(state.batch_stats, mean_stats_delta)

        with jax.named_scope("fed.metrics"):
            alive_f = batch.alive.astype(jnp.float32)
            loss_sum = jnp.sum(out.loss * alive_f)
            acc_sum = jnp.sum(out.accuracy * alive_f)
            n_alive = jnp.sum(alive_f)
            if axis_name is not None:
                with jax.named_scope("fed.aggregate.psum"):
                    loss_sum = jax.lax.psum(loss_sum, axis_name)
                    acc_sum = jax.lax.psum(acc_sum, axis_name)
                    n_alive = jax.lax.psum(n_alive, axis_name)
            n_active = jnp.maximum(n_alive, 1.0)
            metrics = RoundMetrics(
                loss=loss_sum / n_active,
                accuracy=acc_sum / n_active,
                num_active=n_alive,
                update_norm=trees.tree_norm(mean_delta),
                per_client_loss=out.loss * alive_f,
                screened=screened,
                **_round_counters(out.counters, axis_name),
            )
            new_state = FederatedState(
                params=new_params,
                batch_stats=new_stats,
                opt_state=out.opt_state,
                client_rng=state.client_rng,
                round_idx=state.round_idx + 1,
                comp_state=comp_state,
                server_opt_state=new_server_opt,
                # Observe only clients that actually TRAINED this round:
                # an alive client with an empty shard runs zero steps and its
                # out.loss is a masked artifact (0.0) — recording it would
                # hand loss-proportional sampling a stale zero that starves
                # the client forever. Never-trained clients keep NaN and draw
                # at the optimistic prior instead (fedtpu.sim.sampling).
                last_client_loss=jnp.where(
                    step_mask.any(axis=1),
                    out.loss.astype(jnp.float32),
                    state.last_client_loss,
                ),
            )
        return new_state, metrics

    return round_step
