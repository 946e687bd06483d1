"""Flat-buffer delta layout: every parameter leaf in ONE contiguous row.

The per-leaf delta pipeline (:mod:`fedtpu.ops.compression`) dispatches each
codec stage once per pytree leaf; on the zoo's deep architectures (DenseNet,
DPN, RegNet — hundreds of leaves) that is hundreds of tiny ``top_k`` /
elementwise / reduce ops per round. Communication-efficiency practice
(Konečný et al., arXiv:1610.05492; FedJAX, arXiv:2108.02117) treats the
client update as one flat vector instead. This module is the packer for that
layout: all leaves flattened into one lane-aligned ``[clients, P]`` buffer
with a static offsets table, so compression, error feedback, DP clipping and
the FedAvg reduction each run as ONE op over the whole model.

Offsets-table format (static, derived from the params template at trace
time — never serialized with the data, both ends of a wire recompute it
from the shared model definition):

- leaves are enumerated in ``jax.tree_util.tree_flatten`` order;
- ``offsets[i]`` is leaf ``i``'s start in the flat row, ``sizes[i]`` its
  scalar count (``offsets[i+1] == offsets[i] + sizes[i]``);
- ``total = sum(sizes)``; the row is padded with zeros to
  ``padded = ceil(total / 128) * 128`` (TPU lane alignment, ``LANE``), so
  the buffer tiles exactly under Mosaic's ``(8, 128)`` f32 rule and the
  fused kernels in :mod:`fedtpu.ops.pallas_kernels` apply unchanged.

Padding rule: the pad region is ALWAYS zero on entry to every op here, and
every op here preserves that (thresholding keeps zeros at zero, quantization
maps 0 -> 0, residuals of zeros are zero), so padding never leaks into
codec statistics or aggregates and is simply dropped by :func:`unpack`.

Dtype invariant: the packed buffer is ALWAYS float32 — the concat
primitives (:func:`fedtpu.utils.trees.tree_concat_rows` /
``tree_concat_flat``) cast every leaf on entry, and :func:`unpack` /
:func:`unpack_stacked` restore original leaf dtypes from the layout table.
Under ``RoundConfig.dtype="bfloat16"`` deltas are taken against the f32
master params, so aggregation, FedOpt, screening statistics and checkpoint
wire bytes are bit-identical in layout to a pure-f32 run (pinned by
``tests/test_mixed_precision.py``).
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fedtpu.utils import trees

Pytree = Any

# TPU vector-lane width; rows padded to a multiple of this tile exactly.
LANE = 128


class FlatLayout(NamedTuple):
    """Static description of how a params pytree maps into one flat row.

    Hashable/static (shapes and offsets are plain ints), so it can be closed
    over by jitted round steps; only the packed buffer itself is traced.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    total: int  # real scalar count (sum of sizes)
    padded: int  # lane-aligned row length P >= total

    @property
    def num_leaves(self) -> int:
        return len(self.sizes)

    @property
    def pad(self) -> int:
        return self.padded - self.total


def next_pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1)."""
    return 1 << max(n - 1, 0).bit_length()


def _padded(total: int, lane: int, pow2: bool = False) -> int:
    lane_padded = max(lane, int(math.ceil(max(total, 1) / lane)) * lane)
    if not pow2:
        return lane_padded
    # Power-of-two padding (rotated-sketch codecs): the Hadamard rotation
    # needs the row length to be 2^m. Every pow2 >= LANE is lane-aligned,
    # so the Mosaic tiling rule still holds.
    return next_pow2(lane_padded)


def make_layout(
    template: Pytree, lane: int = LANE, pow2: bool = False
) -> FlatLayout:
    """Layout from a (single, unstacked) params-shaped pytree. Works on
    concrete arrays and on ``jax.eval_shape`` results alike — only shapes
    and dtypes are read. ``pow2=True`` pads the row to the next power of
    two instead of the next lane multiple (still lane-aligned), which is
    what the rotated-sketch codecs need for the Hadamard transform."""
    leaves, treedef = jax.tree_util.tree_flatten(template)
    shapes = tuple(tuple(int(d) for d in np.shape(l)) for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    offsets = tuple(int(o) for o in np.cumsum((0,) + sizes)[:-1])
    total = int(sum(sizes))
    return FlatLayout(
        treedef=treedef,
        shapes=shapes,
        dtypes=tuple(jnp.dtype(l.dtype) for l in leaves),
        offsets=offsets,
        sizes=sizes,
        total=total,
        padded=_padded(total, lane, pow2),
    )


def make_layout_stacked(
    stacked: Pytree, lane: int = LANE, pow2: bool = False
) -> FlatLayout:
    """Layout from a ``[clients, ...]``-stacked delta pytree (the leading
    axis is dropped from every leaf shape)."""
    single = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(tuple(l.shape[1:]), l.dtype), stacked
    )
    return make_layout(single, lane, pow2)


def segment_ids(layout: FlatLayout) -> np.ndarray:
    """``[padded]`` int32 map coordinate -> leaf index; padding coordinates
    get the extra segment ``num_leaves``. Host-side/static — used to compute
    per-leaf statistics (e.g. int8 scales) on the flat buffer with ONE
    segment reduction instead of one reduction per leaf."""
    ids = np.full((layout.padded,), layout.num_leaves, np.int32)
    for i, (off, size) in enumerate(zip(layout.offsets, layout.sizes)):
        ids[off : off + size] = i
    return ids


# ------------------------------------------------------------------ packing
@jax.named_scope("fed.pack")
def pack_stacked(layout: FlatLayout, stacked: Pytree) -> jnp.ndarray:
    """``[clients, ...]`` pytree -> ``[clients, padded]`` f32 buffer.

    One reshape per leaf plus one concatenate — pure data movement that XLA
    folds into the surrounding program; all codec/aggregation math then runs
    on the single result buffer.
    """
    leaves = jax.tree_util.tree_leaves(stacked)
    if len(leaves) != layout.num_leaves:
        raise ValueError(
            f"tree has {len(leaves)} leaves, layout expects {layout.num_leaves}"
        )
    flat = trees.tree_concat_rows(stacked)
    if layout.pad:
        flat = jnp.pad(flat, ((0, 0), (0, layout.pad)))
    return flat


@jax.named_scope("fed.unpack")
def unpack_stacked(layout: FlatLayout, flat: jnp.ndarray) -> Pytree:
    """Inverse of :func:`pack_stacked`: ``[clients, padded]`` -> stacked
    pytree (original leaf dtypes restored, padding dropped)."""
    n = flat.shape[0]
    leaves = [
        flat[:, off : off + size].reshape((n,) + shape).astype(dt)
        for off, size, shape, dt in zip(
            layout.offsets, layout.sizes, layout.shapes, layout.dtypes
        )
    ]
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


@jax.named_scope("fed.pack")
def pack(layout: FlatLayout, tree: Pytree) -> jnp.ndarray:
    """Single (unstacked) pytree -> ``[padded]`` f32 row."""
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != layout.num_leaves:
        raise ValueError(
            f"tree has {len(leaves)} leaves, layout expects {layout.num_leaves}"
        )
    flat = trees.tree_concat_flat(tree)
    if layout.pad:
        flat = jnp.pad(flat, (0, layout.pad))
    return flat


def pack_row_host(
    layout: FlatLayout, tree: Pytree, out: Optional[np.ndarray] = None
) -> np.ndarray:
    """Host-side (numpy) twin of :func:`pack`: a single pytree into a
    ``[padded]`` f32 row, written into ``out`` when given (the streaming
    server's preallocated row buffer) so no intermediate concatenation is
    materialised. ``out[total:]`` is left untouched (callers keep the pad
    region zero)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != layout.num_leaves:
        raise ValueError(
            f"tree has {len(leaves)} leaves, layout expects {layout.num_leaves}"
        )
    if out is None:
        out = np.zeros((layout.padded,), np.float32)
    for leaf, off, size in zip(leaves, layout.offsets, layout.sizes):
        out[off : off + size] = np.asarray(leaf, np.float32).ravel()
    return out


@jax.named_scope("fed.unpack")
def unpack(layout: FlatLayout, flat: jnp.ndarray) -> Pytree:
    """``[padded]`` row -> pytree (original dtypes, padding dropped)."""
    leaves = [
        flat[off : off + size].reshape(shape).astype(dt)
        for off, size, shape, dt in zip(
            layout.offsets, layout.sizes, layout.shapes, layout.dtypes
        )
    ]
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


# ------------------------------------------------------------- flat codecs
def topk_threshold(y: jnp.ndarray, fraction: float, total: int) -> Optional[jnp.ndarray]:
    """Per-client GLOBAL keep threshold: k-th largest |y| across the whole
    flat row, with ``k = ceil(fraction * total)`` counted against the REAL
    (unpadded) coordinate count. Returns None when k covers everything
    (keep-all). ONE ``top_k`` per round — the per-leaf path issues one per
    leaf, and its per-leaf k quantises the budget leaf-by-leaf; the global
    threshold spends the same overall budget on the globally largest
    coordinates (the documented semantic difference between layouts)."""
    k = max(1, int(math.ceil(fraction * total)))
    if k >= total:
        return None
    return jax.lax.top_k(jnp.abs(y), k)[0][:, -1]


def screen_rows(
    rows: jnp.ndarray,
    alive: jnp.ndarray,
    norm_max: float = 0.0,
    zmax: float = 0.0,
    cos_min: float = -1.0,
):
    """Fused Byzantine screening over a ``[clients, P]`` flat delta buffer.

    One program computes three per-row statistics and folds them into a
    keep/reject verdict (the thresholds are STATIC — callers close over a
    :class:`fedtpu.config.ScreenConfig`):

    - ``norm``: the row's L2 norm (per-row — under the streaming server
      pipeline this is the statistic that folds on arrival, host-side, with
      zero extra device syncs; the fused verdict below recomputes it in the
      same f32 math post-barrier).
    - ``cos``: cosine of the row against the live cohort's ROBUST
      REFERENCE DIRECTION — the mean of the norm-normalized live rows.
      Each client contributes exactly one unit vector, so a boosted
      update cannot drag the reference (the bounded-influence property a
      coordinate-wise median direction would give), and for a pure
      sign-flip minority the resultant stays exactly on the honest
      direction; unlike the median it is one elementwise pass, not a
      [clients, P] sort (measured 280 ms -> ~4 ms per round at densenet
      width on CPU — the difference between failing and passing the <=1%
      microbench gate). A sign-flipped/contrarian update scores ~-1 while
      honest heterogeneous updates stay positive.
    - ``z``: modified z-score of the row norm against the live cohort's
      median/MAD (``0.6745 * (norm - median) / MAD``, Iglewicz-Hoaglin).
      Median/MAD, not mean/std: a 30% boosted-attacker cohort inflates the
      mean and std enough to hide itself from a classical z-score, but
      cannot move the median while the honest majority holds. The check is
      ONE-SIDED (``z <= zmax`` keeps): only an inflated norm can dominate
      a combine — an unusually small update has bounded influence, and a
      two-sided cut would reject honest low-data clients.

    ``alive`` selects the rows that form the reference statistics (median
    direction, median/MAD of norms) — already-quarantined or failed rows
    must not pollute the reference population — but every row receives a
    verdict against those references, so a quarantined client keeps
    generating evidence (and can redeem itself).

    Invariances (property-pinned in ``tests/test_properties.py``): the
    per-row stats are permutation-equivariant (reordering rows reorders
    verdicts identically — median/MAD/median-direction are order-free
    reductions), and ``cos``/``z`` are invariant under a common positive
    scaling of all rows, so the relative checks need no per-model
    calibration (only ``norm_max`` is absolute by design).

    Returns ``(keep, stats)``: ``keep`` bool ``[clients]`` (True = row may
    enter the combine; a disarmed threshold never rejects), ``stats`` a
    dict of the three f32 ``[clients]`` vectors for records/telemetry.
    """
    rows = rows.astype(jnp.float32)
    live = (alive.astype(jnp.float32) > 0)
    norms = jnp.sqrt(jnp.maximum(jnp.sum(rows * rows, axis=1), 0.0))
    eps = jnp.float32(1e-12)
    # Robust reference direction: resultant of the live UNIT rows (see
    # docstring — bounded per-client influence at elementwise cost),
    # evaluated LEAVE-ONE-OUT per row: a row's own unit vector must not
    # vouch for it (at small cohorts self-inclusion inflates an outlier's
    # cosine by ~1/n_live). The LOO terms are pure dot-product algebra —
    # no second pass over the buffer.
    unit = rows / (norms + eps)[:, None]
    live_f = live.astype(jnp.float32)
    ref = jnp.sum(unit * live_f[:, None], axis=0)
    ref_sq = jnp.maximum(jnp.sum(ref * ref), 0.0)
    d = rows @ ref                      # [n]  <row_i, ref>
    u = d / (norms + eps)               # [n]  <unit_i, ref>
    loo_dot = d - live_f * norms        # <row_i, ref - unit_i> for live i
    loo_sq = jnp.maximum(ref_sq - live_f * (2.0 * u - 1.0), 0.0)
    cos = loo_dot / (norms * jnp.sqrt(loo_sq) + eps)
    # Modified z-score of the norms against the live median/MAD.
    norm_med = jnp.nan_to_num(
        jnp.nanmedian(jnp.where(live, norms, jnp.nan)), nan=0.0
    )
    mad = jnp.nan_to_num(
        jnp.nanmedian(jnp.where(live, jnp.abs(norms - norm_med), jnp.nan)),
        nan=0.0,
    )
    # MAD floor at 5% of the median scale: near convergence honest norms
    # become nearly identical and a raw MAD collapses toward 0, amplifying
    # harmless jitter into "outliers" (observed: honest evictions in the
    # 100-round Byzantine soak). A deviation within a few percent of the
    # cohort's scale is never evidence — an attacker must inflate its norm
    # by a meaningful multiple, which stays hundreds of sigmas out under
    # the floor. Scale-invariance is preserved (the floor tracks the
    # median).
    mad = jnp.maximum(mad, 0.05 * norm_med)
    z = 0.6745 * (norms - norm_med) / (mad + eps)
    keep = jnp.ones(norms.shape, bool)
    if norm_max > 0:
        keep = keep & (norms <= norm_max)
    if zmax > 0:
        keep = keep & (z <= zmax)
    if cos_min > -1.0:
        keep = keep & (cos >= cos_min)
    # Degenerate cohorts keep everything the thresholds didn't reject: with
    # <= 2 live rows the median IS the row set and MAD is 0 — the z/cos
    # checks would reject arbitrarily. Statistics need a population.
    n_live = jnp.sum(live.astype(jnp.int32))
    keep = jnp.where(n_live >= 3, keep, norms <= norm_max if norm_max > 0
                     else jnp.ones_like(keep))
    return keep, {"norm": norms, "cos": cos, "z": z}


# -------------------------------------------------- hierarchical partial sums
def partial_reduce_rows(
    rows: jnp.ndarray, weights: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold a ``[cohort, P]`` flat buffer into ONE pre-weighted sum row.

    The fan-in primitive of the hierarchical (multi-tier) topology: a leaf
    :class:`fedtpu.transport.aggregator.AggregatorServer` reduces its
    cohort's rows to ``(sum_i rows_i * w_i, sum_i w_i)`` and ships only
    that pair upstream.

    Exact-associativity contract (the property the 2-tier parity pins in
    ``tests/test_aggregator.py`` hold): the partial is the UNNORMALIZED
    weighted sum — division happens exactly once, at the root, in
    :func:`combine_partial_rows`. Addition is associative whenever the f32
    adds are exact, so any grouping of clients into tiers produces the
    bit-identical mean the one-tier :func:`flat_weighted_mean` computes
    (a mean-of-means scheme would round at every tier and cannot satisfy
    this). Padding rule: pad coordinates are zero on entry and a weighted
    sum of zeros is zero, so the partial row stays pad-clean.
    """
    w = weights.astype(rows.dtype).reshape((-1,) + (1,) * (rows.ndim - 1))
    return jnp.sum(rows * w, axis=0), jnp.sum(weights)


def combine_partial_rows(
    sum_rows: jnp.ndarray, weight_sums: jnp.ndarray
) -> jnp.ndarray:
    """Root-side combine of the ``[aggregators, P]`` partial-sum surface:
    ``sum(sum_rows) / max(sum(weight_sums), 1e-9)`` — the single division
    of the whole hierarchy (see :func:`partial_reduce_rows`). With one
    aggregator over the whole cohort this IS ``flat_weighted_mean``'s
    program (same sum order, same epsilon guard), which is what makes the
    single-tier degenerate case trivially bit-identical."""
    total = jnp.maximum(jnp.sum(weight_sums), 1e-9)
    return jnp.sum(sum_rows, axis=0) / total.astype(sum_rows.dtype)


def int8_scales(y: jnp.ndarray, layout: FlatLayout) -> jnp.ndarray:
    """Per-coordinate int8 scale vector reproducing the per-leaf codec
    EXACTLY: scale = max|leaf| / 127 per client per leaf, computed with one
    segment-max over the flat row and gathered back to ``[clients, padded]``.
    max is order-independent, so this is bit-identical to the per-leaf
    reductions — the property the layout-parity tests pin."""
    seg = jnp.asarray(segment_ids(layout))
    maxes = jax.vmap(
        lambda row: jax.ops.segment_max(
            row,
            seg,
            num_segments=layout.num_leaves + 1,
            indices_are_sorted=True,
        )
    )(jnp.abs(y))
    return maxes[:, seg] / 127.0
