"""On-device update compression — the TPU-native form of ``-c Y``.

The reference's compression is transport-level gzip on base64-pickled
checkpoints (``src/server.py:104-107``, ``src/client.py:39-43``): lossless,
host-side, and applied *after* a 33% base64 inflation. fedtpu compresses where
it actually pays on TPU: client *deltas* are sparsified/quantized on-device
*before* aggregation, so

- the FedAvg collective moves fewer effective bytes over ICI/DCN,
- the DCN edge transport (:mod:`fedtpu.transport`) can ship the compact form
  (top-k indices+values or int8 codes) instead of dense f32,
- error feedback keeps convergence: what a round drops is carried into the
  next round's delta (residual state per client, living alongside momentum in
  :class:`fedtpu.core.round.FederatedState`).

Codecs:
- ``topk``  — per-leaf, per-client magnitude top-k (fraction ``topk_fraction``).
- ``int8``  — per-leaf, per-client symmetric int8 quantization.
- ``rotq``  — flat-layout only: seeded structured random rotation
  (subsampled randomized Hadamard transform, Konečný et al. 1610.05492)
  followed by per-row uniform b-bit quantization with stochastic rounding;
  the server inverse-rotates the dequantized row. Requires the
  power-of-two row padding (``Compressor.pad_pow2``).
- ``randk`` — flat-layout only: seeded random-coordinate subsampling.
  With error feedback the kept coordinates ship unscaled (contractive; the
  residual carries exactly the dropped mass); without it they are rescaled
  by ``total/k`` so the estimator is unbiased. The per-round coordinate
  set is one shared seeded draw, so the codec is deterministic and both
  wire ends agree without shipping indices.

Both run through the fused Pallas kernels in
:mod:`fedtpu.ops.pallas_kernels`; both are simulated on-device (compress →
decompress) so aggregation sees exactly the numbers the wire format would
carry.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from fedtpu.config import FedConfig
from fedtpu.ops import flat as flat_ops
from fedtpu.ops import pallas_kernels as pk

Pytree = Any


class Compressor(NamedTuple):
    """A stateful delta codec.

    ``init(params, num_clients)`` builds the per-client residual state (the
    empty tuple ``()`` when error feedback is off). ``apply(deltas, state)`` maps
    stacked per-client deltas ``[clients, ...]`` to (compressed deltas, new
    state). ``apply`` is pure and jit/shard_map-safe; under ``shard_map`` the
    clients axis of both deltas and state is the sharded axis.

    ``layout`` names the delta layout the codec was built for. Per-leaf
    codecs (the default) map each pytree leaf independently. Flat codecs
    (``layout="flat"``, :mod:`fedtpu.ops.flat`) additionally expose
    ``apply_flat(flat_deltas, state, flat_layout)`` operating on the packed
    ``[clients, P]`` buffer directly — the round step packs once and calls
    it so the whole codec suite is a handful of fused ops instead of
    per-leaf dispatches; residual state is then one ``[clients, P]`` buffer.
    ``apply`` still works on pytrees for flat codecs (it packs/unpacks
    internally), so standalone callers need not care about the layout.

    ``pad_pow2`` marks codecs whose flat row must be padded to a power of
    two (the Hadamard rotation of ``rotq``): the round step and the
    residual initialiser build their layouts with
    ``make_layout(..., pow2=True)`` when it is set. Seeded codecs
    (``rotq``/``randk``) additionally accept a ``round_idx`` keyword on
    ``apply_flat`` — the per-round seed that keeps client and server (and
    replays) drawing identical rotations/coordinate sets.
    """

    init: Callable[[Pytree, int], Pytree]
    apply: Callable[[Pytree, Pytree], Tuple[Pytree, Pytree]]
    layout: str = "per_leaf"
    apply_flat: Optional[
        Callable[[jnp.ndarray, Pytree, flat_ops.FlatLayout], Tuple[jnp.ndarray, Pytree]]
    ] = None
    pad_pow2: bool = False


def _flatten_leaf(d: jnp.ndarray) -> jnp.ndarray:
    """[clients, ...] -> [clients, size] float32."""
    return d.reshape((d.shape[0], -1)).astype(jnp.float32)


def _make_init(error_feedback: bool) -> Callable[[Pytree, int], Pytree]:
    """Residual-state initialiser: per-client zeros shaped like the stacked
    params when error feedback is on; the empty pytree ``()`` otherwise (the
    same sentinel :class:`fedtpu.core.round.FederatedState` defaults to)."""

    def init(params: Pytree, num_clients: int) -> Pytree:
        if not error_feedback:
            return ()
        return jax.tree.map(
            lambda p: jnp.zeros((num_clients,) + p.shape, jnp.float32), params
        )

    return init


class _CodecPair(NamedTuple):
    """Sentinel wrapper for one leaf's (compressed, new_residual) result, so
    unzipping the mapped tree can't confuse codec outputs with tuple
    containers that happen to appear inside a caller's delta pytree."""

    compressed: jnp.ndarray
    residual: Optional[jnp.ndarray]


def _make_apply(
    leaf: Callable[[jnp.ndarray, Optional[jnp.ndarray]], Tuple[jnp.ndarray, jnp.ndarray]],
    error_feedback: bool,
) -> Callable[[Pytree, Pytree], Tuple[Pytree, Pytree]]:
    """Lift a per-leaf ``(delta, residual) -> (compressed, new_residual)``
    codec to pytrees, handling the no-error-feedback case (empty state)."""

    @jax.named_scope("fed.codec")
    def apply(deltas: Pytree, state: Pytree) -> Tuple[Pytree, Pytree]:
        if error_feedback:
            pairs = jax.tree.map(lambda d, e: _CodecPair(*leaf(d, e)), deltas, state)
        else:
            pairs = jax.tree.map(lambda d: _CodecPair(*leaf(d, None)), deltas)
        is_pair = lambda x: isinstance(x, _CodecPair)
        out = jax.tree.map(lambda p: p.compressed, pairs, is_leaf=is_pair)
        if not error_feedback:
            return out, state
        new_state = jax.tree.map(lambda p: p.residual, pairs, is_leaf=is_pair)
        return out, new_state

    return apply


def _make_flat_init(
    error_feedback: bool, pow2: bool = False
) -> Callable[[Pytree, int], Pytree]:
    """Flat-layout residual initialiser: ONE ``[clients, P]`` buffer instead
    of a per-leaf pytree (or ``()`` when error feedback is off)."""

    def init(params: Pytree, num_clients: int) -> Pytree:
        if not error_feedback:
            return ()
        lay = flat_ops.make_layout(params, pow2=pow2)
        return jnp.zeros((num_clients, lay.padded), jnp.float32)

    return init


def _lift_flat(
    apply_flat, pow2: bool = False
) -> Callable[[Pytree, Pytree], Tuple[Pytree, Pytree]]:
    """Pytree-level ``apply`` for a flat codec: pack once, run the flat
    codec, unpack. Standalone-caller convenience — the round step packs its
    own buffer and calls ``apply_flat`` directly."""

    @jax.named_scope("fed.codec")
    def apply(deltas: Pytree, state: Pytree) -> Tuple[Pytree, Pytree]:
        lay = flat_ops.make_layout_stacked(deltas, pow2=pow2)
        out, new_state = apply_flat(
            flat_ops.pack_stacked(lay, deltas), state, lay
        )
        return flat_ops.unpack_stacked(lay, out), new_state

    return apply


def _make_topk_flat(fraction: float, error_feedback: bool) -> Compressor:
    """Flat-layout top-k: ONE ``top_k`` + ONE threshold kernel over the
    whole ``[clients, P]`` buffer per round. The keep budget
    ``k = ceil(fraction * total)`` is GLOBAL across the model — the same
    overall budget as the per-leaf codec, spent on the globally largest
    coordinates instead of quantised leaf-by-leaf (the documented semantic
    difference between layouts; see docs/FLAT_DELTA.md)."""

    @jax.named_scope("fed.codec")
    def apply_flat(y, state, lay):
        if error_feedback:
            with jax.named_scope("fed.codec.feedback"):
                y = y + state
        with jax.named_scope("fed.codec.select"):
            kth = flat_ops.topk_threshold(y, fraction, lay.total)
            if kth is None:  # keep-all budget: nothing dropped, residual zero
                return y, (jnp.zeros_like(y) if error_feedback else state)
            if not error_feedback:
                return jnp.where(jnp.abs(y) >= kth[:, None], y, 0.0), state
            return pk.threshold_with_feedback(y, kth)

    return Compressor(
        init=_make_flat_init(error_feedback),
        apply=_lift_flat(apply_flat),
        layout="flat",
        apply_flat=apply_flat,
    )


def _make_int8_flat(error_feedback: bool) -> Compressor:
    """Flat-layout int8: one segment-max for every leaf's scale, one fused
    elementwise quantize-dequantize over the whole buffer. Scales reproduce
    the per-leaf codec exactly (max is order-independent), so this path is
    bit-identical to ``layout='per_leaf'`` — pinned by the parity tests."""

    @jax.named_scope("fed.codec")
    def apply_flat(y, state, lay):
        if error_feedback:
            with jax.named_scope("fed.codec.feedback"):
                y = y + state
        with jax.named_scope("fed.codec.quantize"):
            scale = flat_ops.int8_scales(y, lay)
            safe = jnp.where(scale > 0, scale, jnp.ones_like(scale))
            out = jnp.clip(jnp.round(y / safe), -127.0, 127.0) * safe
        if not error_feedback:
            return out, state
        with jax.named_scope("fed.codec.feedback"):
            return out, y - out

    return Compressor(
        init=_make_flat_init(error_feedback),
        apply=_lift_flat(apply_flat),
        layout="flat",
        apply_flat=apply_flat,
    )


# Base seeds for the per-round PRNG streams of the seeded codecs. The
# effective key is fold_in(PRNGKey(base), round_idx) — deterministic per
# round, shared by every client in the engine, and distinct between the
# rotation and subsampling codecs.
_ROTQ_SEED = 0x5EED0    # noqa: E262 — rotation/uniform stream
_RANDK_SEED = 0x5EED1   # coordinate-subsampling stream

ROTQ_BIT_WIDTHS = (1, 2, 4, 8)


def _make_rotq_flat(bits: int, error_feedback: bool) -> Compressor:
    """Flat-layout rotated-sketch quantizer (rotq): rotate the padded row
    through the seeded randomized Hadamard transform, uniform-quantize to
    ``bits`` bits per coordinate with stochastic rounding over the per-row
    [min, max] range, then inverse-rotate — so aggregation sees exactly the
    values the wire record reconstructs.

    Unbiasedness: stochastic rounding satisfies ``E[q] = z`` per rotated
    coordinate conditionally on the (z-measurable) range, and both
    rotations are linear, so ``E[out] = delta + residual`` — the property
    ``tests/test_properties.py`` pins over seeds. The rotation spreads each
    coordinate's energy across the row, so the per-row uniform grid costs
    ~O(||y||/sqrt(h)) per coordinate instead of O(max|y|) (Konečný et al.).

    Pad-clean rule: the rotated row legitimately mixes real coordinates
    into the pad region, so the codec re-zeros ``[total:]`` AFTER the
    inverse rotation. In exact math those coordinates are exactly zero
    (the pad of ``y`` is zero and the transform pair is the identity);
    only quantization noise lands there, and dropping it keeps the buffer
    invariant without biasing the real coordinates.
    """
    if bits not in ROTQ_BIT_WIDTHS:
        raise ValueError(
            f"rotq bits must be one of {ROTQ_BIT_WIDTHS}, got {bits}"
        )
    levels = float(2**bits - 1)

    @jax.named_scope("fed.codec")
    def apply_flat(y, state, lay, round_idx=0):
        if error_feedback:
            with jax.named_scope("fed.codec.feedback"):
                y = y + state
        h = lay.padded
        if h & (h - 1):
            raise ValueError(
                f"rotq needs a power-of-two row (got padded={h}); build the "
                "layout with make_layout(..., pow2=True)"
            )
        key = jax.random.fold_in(jax.random.PRNGKey(_ROTQ_SEED), round_idx)
        k_sign, k_unif = jax.random.split(key)
        signs = jax.random.rademacher(k_sign, (h,), jnp.float32)
        z = pk.hadamard_rotate(y, signs)
        with jax.named_scope("fed.codec.quantize"):
            lo = jnp.min(z, axis=1, keepdims=True)
            scale = (jnp.max(z, axis=1, keepdims=True) - lo) / levels
            safe = jnp.where(scale > 0, scale, jnp.ones_like(scale))
            u = jax.random.uniform(k_unif, z.shape, jnp.float32)
            q = jnp.clip(jnp.floor((z - lo) / safe + u), 0.0, levels)
            dequantized = lo + q * safe
        out = pk.hadamard_rotate(dequantized, signs, inverse=True)
        if lay.pad:
            out = jnp.concatenate(
                [out[:, : lay.total], jnp.zeros_like(out[:, lay.total :])],
                axis=1,
            )
        if not error_feedback:
            return out, state
        with jax.named_scope("fed.codec.feedback"):
            return out, y - out

    return Compressor(
        init=_make_flat_init(error_feedback, pow2=True),
        apply=_lift_flat(apply_flat, pow2=True),
        layout="flat",
        apply_flat=apply_flat,
        pad_pow2=True,
    )


def _make_randk_flat(fraction: float, error_feedback: bool) -> Compressor:
    """Flat-layout random-k subsampling (randk): one shared seeded draw of
    ``k = ceil(fraction * total)`` real coordinates per round; every client
    ships exactly those.

    The EF rescale rule (documented in docs/FLAT_DELTA.md, pinned by
    ``tests/test_properties.py``): with error feedback OFF the kept values
    are rescaled by ``total/k`` so the estimator is unbiased
    (``E[out] = y`` over the uniform coordinate draw). With error feedback
    ON the rescale is dropped — the residual then carries exactly the
    dropped mass (``out + residual == y``), which keeps the compression
    operator contractive; a rescaled-and-fed-back variant would inject the
    (total/k - 1)-amplified kept mass into the residual and diverge.
    """

    @jax.named_scope("fed.codec")
    def apply_flat(y, state, lay, round_idx=0):
        if error_feedback:
            with jax.named_scope("fed.codec.feedback"):
                y = y + state
        k = max(1, int(math.ceil(fraction * lay.total)))
        if k >= lay.total:  # keep-all budget
            return y, (jnp.zeros_like(y) if error_feedback else state)
        with jax.named_scope("fed.codec.select"):
            key = jax.random.fold_in(
                jax.random.PRNGKey(_RANDK_SEED), round_idx
            )
            idx = jax.random.choice(key, lay.total, (k,), replace=False)
            mask = jnp.zeros((lay.padded,), jnp.float32).at[idx].set(1.0)
            kept = y * mask[None, :]
        if error_feedback:
            with jax.named_scope("fed.codec.feedback"):
                return kept, y - kept
        return kept * jnp.float32(lay.total / k), state

    return Compressor(
        init=_make_flat_init(error_feedback),
        apply=_lift_flat(apply_flat),
        layout="flat",
        apply_flat=apply_flat,
    )


def make_rotq(
    bits: int = 4, error_feedback: bool = True, layout: str = "flat"
) -> Compressor:
    """Rotated-sketch quantizer — flat layout only (the rotation is over
    the whole concatenated update by construction)."""
    if layout != "flat":
        raise ValueError("rotq is a flat-layout codec; set delta_layout='flat'")
    return _make_rotq_flat(bits, error_feedback)


def make_randk(
    fraction: float, error_feedback: bool = True, layout: str = "flat"
) -> Compressor:
    """Random-k coordinate subsampling — flat layout only (the coordinate
    draw is over the whole concatenated update by construction)."""
    if layout != "flat":
        raise ValueError("randk is a flat-layout codec; set delta_layout='flat'")
    return _make_randk_flat(fraction, error_feedback)


def make_topk(
    fraction: float, error_feedback: bool = True, layout: str = "per_leaf"
) -> Compressor:
    """Magnitude top-k sparsification with optional error feedback.

    Per leaf, per client: keep the ``ceil(fraction * size)`` largest-|.|
    entries of (delta + residual), zero the rest, carry the dropped mass as
    the next round's residual. Ties at the threshold may keep a few extra
    entries (threshold comparison is ``>=``) — harmless for convergence and
    it keeps the kernel a pure elementwise mask.

    ``layout="flat"`` swaps in the packed single-buffer codec
    (:func:`_make_topk_flat`): one ``top_k`` with a model-global threshold
    instead of one per leaf.
    """
    if layout == "flat":
        return _make_topk_flat(fraction, error_feedback)
    if layout != "per_leaf":
        raise ValueError(f"unknown delta layout {layout!r}; have per_leaf | flat")

    def leaf(d: jnp.ndarray, e: Optional[jnp.ndarray]):
        shape = d.shape
        y = _flatten_leaf(d)
        if e is not None:
            with jax.named_scope("fed.codec.feedback"):
                y = y + e.reshape(y.shape)
        size = y.shape[1]
        k = max(1, int(math.ceil(fraction * size)))
        if k >= size:
            return y.reshape(shape).astype(d.dtype), jnp.zeros(shape, jnp.float32)
        with jax.named_scope("fed.codec.select"):
            # k-th largest magnitude per client row is the keep threshold.
            kth = jax.lax.top_k(jnp.abs(y), k)[0][:, -1]
            if e is None:
                # No residual output wanted: a plain masked select, which
                # XLA fuses; the two-output kernel would force a dead
                # full-size write.
                out = jnp.where(jnp.abs(y) >= kth[:, None], y, 0.0)
                return out.reshape(shape).astype(d.dtype), None
            out, new_e = pk.threshold_with_feedback(y, kth)
            return out.reshape(shape).astype(d.dtype), new_e.reshape(shape)

    return Compressor(init=_make_init(error_feedback), apply=_make_apply(leaf, error_feedback))


def make_int8(
    error_feedback: bool = True, layout: str = "per_leaf"
) -> Compressor:
    """Symmetric per-leaf int8 quantization with optional error feedback.

    scale = max|delta + residual| / 127 per client per leaf; wire format is
    int8 codes + one f32 scale (4096x smaller metadata than the values).
    On-device we simulate quantize→dequantize so FedAvg averages the exact
    wire numbers.

    ``layout="flat"`` swaps in the packed single-buffer codec
    (:func:`_make_int8_flat`): same per-leaf scales (bit-identical), one
    fused kernel instead of one per leaf.
    """
    if layout == "flat":
        return _make_int8_flat(error_feedback)
    if layout != "per_leaf":
        raise ValueError(f"unknown delta layout {layout!r}; have per_leaf | flat")

    def leaf(d: jnp.ndarray, e: Optional[jnp.ndarray]):
        shape = d.shape
        y = _flatten_leaf(d)
        if e is not None:
            with jax.named_scope("fed.codec.feedback"):
                y = y + e.reshape(y.shape)
        with jax.named_scope("fed.codec.quantize"):
            scale = jnp.max(jnp.abs(y), axis=1) / 127.0
            out = pk.quantdequant_int8(y, scale)
        with jax.named_scope("fed.codec.feedback"):
            new_e = None if e is None else (y - out).reshape(shape)
        return out.reshape(shape).astype(d.dtype), new_e

    return Compressor(init=_make_init(error_feedback), apply=_make_apply(leaf, error_feedback))


def make_compressor(fed: FedConfig) -> Optional[Compressor]:
    """Compressor from config (``FedConfig.compression`` +
    ``FedConfig.delta_layout``); None for 'none'."""
    if fed.compression == "none":
        return None
    if fed.compression == "topk":
        return make_topk(
            fed.topk_fraction, fed.error_feedback, layout=fed.delta_layout
        )
    if fed.compression == "int8":
        return make_int8(fed.error_feedback, layout=fed.delta_layout)
    if fed.compression == "rotq":
        return make_rotq(
            fed.rotq_bits, fed.error_feedback, layout=fed.delta_layout
        )
    if fed.compression == "randk":
        # randk shares the top-k keep-fraction knob: both answer "what
        # fraction of coordinates ship this round".
        return make_randk(
            fed.topk_fraction, fed.error_feedback, layout=fed.delta_layout
        )
    raise ValueError(f"unknown compression '{fed.compression}'")


def nnz_fraction(deltas: Pytree) -> jnp.ndarray:
    """Fraction of nonzero entries across a (compressed) delta pytree — an
    effective-wire-size diagnostic (used by tests and the transport edge;
    not currently part of RoundMetrics)."""
    leaves = jax.tree_util.tree_leaves(deltas)
    nnz = sum(jnp.sum(l != 0).astype(jnp.float32) for l in leaves)
    total = sum(l.size for l in leaves)
    return nnz / max(total, 1)
