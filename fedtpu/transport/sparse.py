"""Sparse/quantized *delta* payloads for the DCN edge.

The reference's ``-c Y`` gzips a base64 dense checkpoint — the wire still
carries every parameter (``src/server.py:104-107``). When fedtpu's delta
compression is on, the distributed edge ships what the codec actually kept:
top-k ``(indices, values)`` pairs or int8 codes + scale per leaf, framed and
CRC-checked like :mod:`fedtpu.transport.wire` (magic ``FSP1`` vs the dense
format's ``FTP1``, so a receiver can dispatch on the first 4 bytes).

Wire size: top-k at fraction f costs ~``8 * f * n`` bytes (int32 idx + f32
val) vs ``4n`` dense — a 50x reduction at f=0.01; int8 costs ``n`` bytes —
4x. Encoding uses the native codec (:mod:`fedtpu.native`) when built.

Payloads are self-describing msgpack (no template needed to decode — nnz
varies per round), with leaf order = ``jax.tree_util.tree_flatten`` order of
the delta pytree, which both ends derive from the same model definition.

Flat records (kinds ``topk_flat`` / ``int8_flat``, the wire form of the
engine's ``FedConfig.delta_layout='flat'`` pipeline, :mod:`fedtpu.ops.flat`):
instead of one msgpack map entry per leaf — hundreds of small records on
deep zoo models — the whole delta travels as ONE contiguous index/value (or
int8 code) block over the concatenated flat vector, plus a ``sizes`` offsets
table for validation. Top-k selection is then GLOBAL across the model (one
``kth_magnitude`` over the concatenation); int8 keeps per-leaf scales (a
``[num_leaves]`` f32 array), matching the engine's flat codec bit-for-bit.
The same ``FSP1`` frame carries all the kinds; :func:`decode` dispatches on
``kind``, so receivers need no code change to accept flat senders.

Hierarchical fan-in adds a fifth kind, ``partial_flat``
(:func:`encode_partial_flat`): ONE dense f32 row carrying a leaf
aggregator's pre-weighted SUM of its cohort's flat delta rows plus the
summed combine weight (``extra['weight_sum']``) — the payload of the
``SubmitPartial`` RPC (docs/FLAT_DELTA.md §FSP1 record kinds).

The sketched-update codecs add two more kinds (docs/FLAT_DELTA.md §Codec
matrix):

- ``rotq_flat`` (:func:`encode_rotq_flat`): the delta vector rotated
  through a SEEDED randomized Hadamard transform and uniform-quantized to
  b bits per coordinate with stochastic rounding — ``b*h/8`` bytes of
  packed codes plus four scalars (seed, bits, lo, scale) in the extra
  block. The receiver regenerates the rotation from the seed and
  inverse-rotates; nothing model-sized beyond the codes travels.
- ``randk_flat`` (:func:`encode_randk_flat`): a SEEDED uniform draw of k
  coordinates — only the k f32 values travel; the index set is
  regenerated from the seed on the receiver (the wire advantage over
  top-k, which must ship explicit indices).

Both are deterministic functions of (input, seed): encoding the same delta
with the same seed is byte-identical, and decode is a pure function of the
record — the bit-identical-replay property ``tests/test_properties.py``
pins. The per-record PRNG is ``numpy``'s Philox keyed by the record seed,
with a fixed draw order (signs/indices FIRST, encoder-only stochastic-
rounding uniforms after) so the decoder can stop after the shared prefix.
"""

from __future__ import annotations

import math
import struct
import zlib
from typing import Any, Optional, Tuple

import jax
import numpy as np
from flax import serialization

from fedtpu.native import (
    dequant_int8,
    kth_magnitude,
    pack_sparse,
    pack_sparse_with_residual,
    quant_int8,
    unpack_sparse,
)
from fedtpu.transport.wire import WireError, frame as _wire_frame, unframe as _wire_unframe

Pytree = Any

_MAGIC = b"FSP1"
# Tracks the shared frame version (fedtpu.transport.wire): v2 frames CRC
# the header bytes too; v1 frames from older senders still decode.
_VERSION = 2
_HEADER = struct.Struct("<4sBBI")


def is_sparse_payload(data: bytes) -> bool:
    return data[:4] == _MAGIC


def _frame(payload: bytes) -> bytes:
    return _wire_frame(_MAGIC, payload, 0, version=_VERSION)


def _unframe(data: bytes) -> bytes:
    return _wire_unframe(_MAGIC, data, "sparse", version=_VERSION)[1]


def encode_topk(
    deltas: Pytree,
    fraction: float,
    residuals: Optional[Pytree] = None,
    extra: Optional[dict] = None,
    collect_residual: bool = True,
) -> Tuple[bytes, Optional[Pytree]]:
    """Sparsify a delta pytree to wire bytes; returns (payload, residuals).

    ``residuals`` (same structure) are added to the deltas before selection
    and replaced by the dropped mass — client-side error feedback, the edge
    analogue of :mod:`fedtpu.ops.compression`. With
    ``collect_residual=False`` (error feedback off) no residual tree is
    materialised and None is returned in its place.
    """
    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    res_leaves = (
        jax.tree_util.tree_flatten(residuals)[0]
        if residuals is not None
        else [None] * len(leaves)
    )
    out_leaves, new_res = [], []
    for leaf, res in zip(leaves, res_leaves):
        x = np.asarray(leaf, np.float32).ravel()
        if res is not None:
            x = x + np.asarray(res, np.float32).ravel()
        k = max(1, int(math.ceil(fraction * x.size)))
        thresh = kth_magnitude(x, k)
        if thresh == 0.0:
            # Degenerate all-(near-)zero leaf: |x| >= 0 would "keep" every
            # element, making the sparse form 2x dense. Keep only true
            # nonzeros; the residual is exactly zero.
            idx = np.flatnonzero(x).astype(np.int32)
            vals = x[idx]
            residual = np.zeros_like(x) if collect_residual else None
        elif collect_residual:
            idx, vals, residual = pack_sparse_with_residual(x, thresh)
        else:
            idx, vals = pack_sparse(x, thresh)
            residual = None
        out_leaves.append(
            {"idx": idx, "vals": vals, "size": np.int64(x.size)}
        )
        if collect_residual:
            new_res.append(residual.reshape(np.shape(leaf)))
    body = {
        "kind": "topk",
        "leaves": {str(i): l for i, l in enumerate(out_leaves)},
        "extra": extra or {},
    }
    payload = _frame(serialization.msgpack_serialize(body))
    residual_tree = (
        jax.tree_util.tree_unflatten(treedef, new_res)
        if collect_residual
        else None
    )
    return payload, residual_tree


def encode_int8(
    deltas: Pytree,
    residuals: Optional[Pytree] = None,
    extra: Optional[dict] = None,
    collect_residual: bool = False,
) -> Tuple[bytes, Optional[Pytree]]:
    """Quantize a delta pytree to wire bytes; returns (payload, residuals).

    With ``collect_residual=True`` the per-round quantization error
    (``input - dequant(quant(input))``) is returned for error feedback,
    matching the simulated engine's int8 codec semantics
    (:func:`fedtpu.ops.compression.make_int8`).
    """
    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    res_leaves = (
        jax.tree_util.tree_flatten(residuals)[0]
        if residuals is not None
        else [None] * len(leaves)
    )
    out, new_res = [], []
    for leaf, res in zip(leaves, res_leaves):
        x = np.asarray(leaf, np.float32).ravel()
        if res is not None:
            x = x + np.asarray(res, np.float32).ravel()
        codes, scale = quant_int8(x)
        out.append(
            {"codes": codes, "scale": np.float32(scale), "size": np.int64(x.size)}
        )
        if collect_residual:
            back = dequant_int8(codes, scale, x.size)
            new_res.append((x - back).reshape(np.shape(leaf)))
    body = {
        "kind": "int8",
        "leaves": {str(i): l for i, l in enumerate(out)},
        "extra": extra or {},
    }
    payload = _frame(serialization.msgpack_serialize(body))
    residual_tree = (
        jax.tree_util.tree_unflatten(treedef, new_res)
        if collect_residual
        else None
    )
    return payload, residual_tree


def _flat_concat(
    leaves, res_leaves
) -> Tuple[np.ndarray, list]:
    """Concatenate leaves (+ residuals) into one f32 vector; returns
    (vector, per-leaf sizes)."""
    sizes = [int(np.size(l)) for l in leaves]
    x = (
        np.concatenate([np.asarray(l, np.float32).ravel() for l in leaves])
        if leaves
        else np.zeros((0,), np.float32)
    )
    if res_leaves is not None:
        x = x + np.concatenate(
            [np.asarray(r, np.float32).ravel() for r in res_leaves]
        )
    return x, sizes


def _split_flat(vec: np.ndarray, leaves, treedef) -> Pytree:
    """Inverse of the concat: slice ``vec`` back into leaf shapes."""
    out, off = [], 0
    for leaf in leaves:
        n = int(np.size(leaf))
        out.append(vec[off : off + n].reshape(np.shape(leaf)))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def encode_topk_flat(
    deltas: Pytree,
    fraction: float,
    residuals: Optional[Pytree] = None,
    extra: Optional[dict] = None,
    collect_residual: bool = True,
) -> Tuple[bytes, Optional[Pytree]]:
    """Flat top-k wire record: ONE ``(indices, values)`` block over the
    concatenated delta vector instead of one record per leaf.

    The keep budget ``k = ceil(fraction * total)`` is GLOBAL across the
    model (one :func:`fedtpu.native.kth_magnitude` over the concatenation) —
    the wire twin of the engine's ``delta_layout='flat'`` top-k codec.
    Error-feedback semantics match :func:`encode_topk`.
    """
    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    res_leaves = (
        jax.tree_util.tree_flatten(residuals)[0]
        if residuals is not None
        else None
    )
    x, sizes = _flat_concat(leaves, res_leaves)
    k = max(1, int(math.ceil(fraction * max(x.size, 1))))
    thresh = kth_magnitude(x, k)
    if thresh == 0.0:
        # Degenerate all-(near-)zero vector: keep only true nonzeros (the
        # same rule as the per-leaf encoder's zero-leaf guard).
        idx = np.flatnonzero(x).astype(np.int32)
        vals = x[idx]
        residual = np.zeros_like(x) if collect_residual else None
    elif collect_residual:
        idx, vals, residual = pack_sparse_with_residual(x, thresh)
    else:
        idx, vals = pack_sparse(x, thresh)
        residual = None
    body = {
        "kind": "topk_flat",
        "sizes": np.asarray(sizes, np.int64),
        "idx": idx,
        "vals": vals,
        "extra": extra or {},
    }
    payload = _frame(serialization.msgpack_serialize(body))
    residual_tree = (
        _split_flat(residual, leaves, treedef) if collect_residual else None
    )
    return payload, residual_tree


def encode_int8_flat(
    deltas: Pytree,
    residuals: Optional[Pytree] = None,
    extra: Optional[dict] = None,
    collect_residual: bool = False,
) -> Tuple[bytes, Optional[Pytree]]:
    """Flat int8 wire record: ONE contiguous code block + a ``[num_leaves]``
    scale array instead of one record per leaf.

    Scales stay PER LEAF (``max|leaf| / 127``) so the reconstruction is
    bit-identical to :func:`encode_int8` — the same invariant the engine's
    flat int8 codec pins against its per-leaf twin.
    """
    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    res_leaves = (
        jax.tree_util.tree_flatten(residuals)[0]
        if residuals is not None
        else None
    )
    x, sizes = _flat_concat(leaves, res_leaves)
    codes = np.empty(x.size, np.int8)
    scales = np.empty(len(sizes), np.float32)
    residual = np.empty(x.size, np.float32) if collect_residual else None
    off = 0
    for i, n in enumerate(sizes):
        seg = x[off : off + n]
        c, s = quant_int8(seg)
        codes[off : off + n] = c
        scales[i] = s
        if collect_residual:
            residual[off : off + n] = seg - dequant_int8(c, s, n)
        off += n
    body = {
        "kind": "int8_flat",
        "sizes": np.asarray(sizes, np.int64),
        "codes": codes,
        "scales": scales,
        "extra": extra or {},
    }
    payload = _frame(serialization.msgpack_serialize(body))
    residual_tree = (
        _split_flat(residual, leaves, treedef) if collect_residual else None
    )
    return payload, residual_tree


def encode_partial_flat(
    row: np.ndarray, sizes, extra: Optional[dict] = None
) -> bytes:
    """Hierarchical-aggregation wire record (kind ``partial_flat``): ONE
    dense f32 row — a cohort's PRE-WEIGHTED sum of flat delta rows
    (:func:`fedtpu.ops.flat.partial_reduce_rows`) — plus the per-leaf
    ``sizes`` table for validation. A sum of many clients' updates has no
    exploitable sparsity, so the record is dense by design; what the
    hierarchy saves is FAN-IN (the root decodes one record per aggregator,
    not one per client), not per-record bytes.

    ``extra`` MUST carry ``weight_sum`` (the cohort's summed combine
    weights — the root's combine weight for this row) and conventionally
    carries ``clients`` / ``t_leaf_s`` for records and the fan-in bench.
    ``row`` is the UNPADDED ``[total]`` prefix (pad coordinates of a
    pad-clean buffer are zero under a weighted sum, so they never travel).
    """
    sizes = [int(s) for s in sizes]
    row = np.ascontiguousarray(row, np.float32)
    if row.ndim != 1 or row.size != sum(sizes):
        raise ValueError(
            f"partial row has {row.shape} coordinates, sizes table sums to "
            f"{sum(sizes)}"
        )
    body = {
        "kind": "partial_flat",
        "sizes": np.asarray(sizes, np.int64),
        "row": row,
        "extra": extra or {},
    }
    return _frame(serialization.msgpack_serialize(body))


# --------------------------------------------------------------------------
# Seeded sketch codecs: rotq_flat (rotated b-bit quantization) and
# randk_flat (random-coordinate subsampling). Shared-seed regeneration means
# the model-sized side information (rotation signs, index set) never travels.
# --------------------------------------------------------------------------

# Bit widths the rotq wire codec packs (byte-aligned packing below covers
# exactly the divisors of 8). Mirrors fedtpu.ops.compression.ROTQ_BIT_WIDTHS.
ROTQ_BITS = (1, 2, 4, 8)


def _next_pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1) (fedtpu.ops.flat.next_pow2 twin —
    local copy so the wire layer stays importable without the engine ops)."""
    return 1 << max(n - 1, 0).bit_length()


def _fwht_np(x: np.ndarray) -> np.ndarray:
    """Unnormalized fast Walsh-Hadamard transform of a 1-D f32 vector.

    A stride-doubling butterfly in numpy for the wire hot path (the decode
    side runs on the serving thread, no jax dispatch). The engine's
    :func:`fedtpu.ops.pallas_kernels.hadamard_rotate` computes the same
    transform as matrix products and is tested against this one.
    ``x.size`` must be a power of two.
    """
    h = x.size
    y = np.array(x, np.float32, copy=True)
    step = 1
    while step < h:
        v = y.reshape(h // (2 * step), 2, step)
        a = v[:, 0, :].copy()
        b = v[:, 1, :].copy()
        v[:, 0, :] = a + b
        v[:, 1, :] = a - b
        step *= 2
    return y


def _philox(seed: int) -> np.random.Generator:
    """The per-record PRNG: counter-based, so the stream for a seed is a
    platform-independent pure function — the replay property both ends and
    the tests rely on."""
    return np.random.Generator(np.random.Philox(int(seed) & (2**64 - 1)))


def _rotq_signs(rng: np.random.Generator, h: int) -> np.ndarray:
    """Rademacher diagonal — the FIRST ``h`` draws of the record stream, so
    the decoder (which needs nothing else) can stop here while the encoder
    keeps drawing its stochastic-rounding uniforms from the same stream."""
    return rng.integers(0, 2, size=h).astype(np.float32) * 2.0 - 1.0


def _pack_codes(q: np.ndarray, bits: int) -> np.ndarray:
    """Pack uint8 codes < 2**bits into a dense byte array (little-endian
    within the byte for bits in {2, 4}; numpy's MSB-first convention for
    bits == 1 — each is its own unpack's exact inverse)."""
    if bits == 8:
        return np.ascontiguousarray(q, np.uint8)
    if bits == 1:
        return np.packbits(np.ascontiguousarray(q, np.uint8))
    per = 8 // bits
    pad = (-q.size) % per
    if pad:
        q = np.concatenate([q, np.zeros(pad, np.uint8)])
    q = np.ascontiguousarray(q, np.uint8).reshape(-1, per)
    out = np.zeros(q.shape[0], np.uint8)
    for j in range(per):
        out |= q[:, j] << np.uint8(bits * j)
    return out


def _unpack_codes(codes: np.ndarray, bits: int, h: int) -> np.ndarray:
    """Inverse of :func:`_pack_codes`; validates the byte count (untrusted
    wire data) and returns exactly ``h`` uint8 codes."""
    codes = np.ascontiguousarray(codes, np.uint8)
    if codes.size != (h * bits + 7) // 8:
        raise WireError("rotq_flat code block size mismatch")
    if bits == 8:
        q = codes
    elif bits == 1:
        q = np.unpackbits(codes)
    else:
        per = 8 // bits
        mask = np.uint8((1 << bits) - 1)
        q = np.empty(codes.size * per, np.uint8)
        for j in range(per):
            q[j::per] = (codes >> np.uint8(bits * j)) & mask
    return q[:h]


def _rotq_dequant(
    q: np.ndarray, lo: float, scale: float, signs: np.ndarray, h: int
) -> np.ndarray:
    """Shared reconstruction: dequantize codes and inverse-rotate. The
    encoder uses the SAME function for its error-feedback residual, so the
    client's residual is computed against exactly what the server will
    reconstruct — no encoder/decoder drift."""
    safe = np.float32(scale) if float(scale) > 0.0 else np.float32(1.0)
    zq = np.float32(lo) + q.astype(np.float32) * safe
    return _fwht_np(zq) * np.float32(1.0 / math.sqrt(h)) * signs


def encode_rotq_flat(
    deltas: Pytree,
    bits: int = 4,
    residuals: Optional[Pytree] = None,
    extra: Optional[dict] = None,
    collect_residual: bool = True,
    seed: int = 0,
) -> Tuple[bytes, Optional[Pytree]]:
    """Rotated-quantization wire record (kind ``rotq_flat``).

    The concatenated delta vector is zero-padded to the next power of two,
    rotated by the seeded SRHT ``R = (1/sqrt(h)) H D`` (signs regenerated
    from ``seed`` on both ends), and uniform-quantized to ``bits`` bits per
    coordinate with stochastic rounding — conditionally unbiased, and the
    rotation spreads outlier coordinates so the uniform grid wastes no
    range. Wire cost: ``bits * h / 8`` bytes of packed codes + four scalars
    (seed / bits / lo / scale) riding in the record's extra block — 8x
    smaller than dense f32 at bits=4, 16x at bits=2.

    Error feedback: with ``collect_residual=True`` the returned residual is
    ``input - reconstruct(record)`` via the same :func:`_rotq_dequant` the
    decoder runs, composing with the client's EF buffer exactly like the
    engine codec (:func:`fedtpu.ops.compression.make_rotq`).

    Same (input, seed) => byte-identical payload (Philox is counter-based).
    """
    if bits not in ROTQ_BITS:
        raise ValueError(f"rotq bits must be one of {ROTQ_BITS}, got {bits}")
    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    res_leaves = (
        jax.tree_util.tree_flatten(residuals)[0]
        if residuals is not None
        else None
    )
    x, sizes = _flat_concat(leaves, res_leaves)
    total = x.size
    h = _next_pow2(max(total, 1))
    rng = _philox(seed)
    signs = _rotq_signs(rng, h)
    xp = np.zeros(h, np.float32)
    xp[:total] = x
    z = _fwht_np(xp * signs) * np.float32(1.0 / math.sqrt(h))
    levels = np.float32(2**bits - 1)
    lo = np.float32(z.min())
    scale = np.float32((z.max() - lo) / levels)
    safe = scale if float(scale) > 0.0 else np.float32(1.0)
    # Stochastic rounding: floor(z/safe + u), u ~ U[0,1) — E[q] recovers z
    # exactly (conditionally unbiased given the rotation). Drawn AFTER the
    # signs from the same stream; the decoder never needs them.
    u = rng.random(h, dtype=np.float32)
    q = np.clip(np.floor((z - lo) / safe + u), 0.0, float(levels)).astype(
        np.uint8
    )
    body = {
        "kind": "rotq_flat",
        "sizes": np.asarray(sizes, np.int64),
        "codes": _pack_codes(q, bits),
        "extra": {
            **(extra or {}),
            "seed": np.uint64(seed),
            "bits": np.int64(bits),
            "lo": lo,
            "scale": scale,
        },
    }
    payload = _frame(serialization.msgpack_serialize(body))
    if not collect_residual:
        return payload, None
    back = _rotq_dequant(q, lo, scale, signs, h)
    residual = x - back[:total]
    return payload, _split_flat(residual, leaves, treedef)


def _rotq_reconstruct(body: dict, total: int) -> np.ndarray:
    """Decode a ``rotq_flat`` body to the dense ``[total]`` vector
    (regenerate signs from the seed, dequantize, inverse-rotate, drop the
    pow2 pad). All fields are untrusted wire data and validated."""
    ex = body.get("extra", {})
    try:
        bits = int(ex["bits"])
        seed = int(ex["seed"])
        lo = float(ex["lo"])
        scale = float(ex["scale"])
    except (KeyError, TypeError, ValueError):
        raise WireError("rotq_flat record missing codec scalars")
    if bits not in ROTQ_BITS:
        raise WireError(f"rotq_flat unsupported bit width {bits}")
    if not (math.isfinite(lo) and math.isfinite(scale)) or scale < 0.0:
        raise WireError("rotq_flat non-finite quantization scalars")
    h = _next_pow2(max(total, 1))
    q = _unpack_codes(np.asarray(body["codes"]), bits, h)
    signs = _rotq_signs(_philox(seed), h)
    return _rotq_dequant(q, np.float32(lo), np.float32(scale), signs, h)[
        :total
    ]


def _randk_indices(seed: int, total: int, k: int) -> np.ndarray:
    """The shared seeded index set: a uniform draw of k coordinates WITHOUT
    replacement, sorted for a cache-friendly scatter. Pure function of
    (seed, total, k) — the decoder regenerates it instead of receiving it."""
    if total <= 0 or k <= 0:
        return np.zeros(0, np.int64)
    rng = _philox(seed)
    return np.sort(rng.choice(total, size=k, replace=False).astype(np.int64))


def encode_randk_flat(
    deltas: Pytree,
    fraction: float,
    residuals: Optional[Pytree] = None,
    extra: Optional[dict] = None,
    collect_residual: bool = True,
    seed: int = 0,
) -> Tuple[bytes, Optional[Pytree]]:
    """Random-k wire record (kind ``randk_flat``): ship only the f32 values
    at a SEEDED uniform draw of ``k = ceil(fraction * total)`` coordinates.
    No index block travels (the receiver regenerates it from ``seed``), so
    the record costs ``4k`` bytes where flat top-k costs ``8k`` — the
    importance-sampling end of the codec frontier.

    Error-feedback rule (pinned, mirrors
    :func:`fedtpu.ops.compression.make_randk`): with
    ``collect_residual=True`` the kept values travel UNSCALED and the
    dropped mass goes to the residual — kept + residual == input exactly,
    the contraction EF needs. With ``collect_residual=False`` the values
    are pre-scaled by ``total / k`` on the encoder (unbiased estimator);
    the decoder just scatters either way.
    """
    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    res_leaves = (
        jax.tree_util.tree_flatten(residuals)[0]
        if residuals is not None
        else None
    )
    x, sizes = _flat_concat(leaves, res_leaves)
    total = x.size
    k = (
        min(max(1, int(math.ceil(fraction * total))), total)
        if total
        else 0
    )
    idx = _randk_indices(seed, total, k)
    vals = np.ascontiguousarray(x[idx], np.float32)
    if not collect_residual and 0 < k < total:
        vals = vals * np.float32(total / k)
    body = {
        "kind": "randk_flat",
        "sizes": np.asarray(sizes, np.int64),
        "vals": vals,
        "extra": {
            **(extra or {}),
            "seed": np.uint64(seed),
            "k": np.int64(k),
        },
    }
    payload = _frame(serialization.msgpack_serialize(body))
    if not collect_residual:
        return payload, None
    residual = x.copy()
    residual[idx] = 0.0
    return payload, _split_flat(residual, leaves, treedef)


def _randk_scatter(body: dict, total: int, out: np.ndarray) -> None:
    """Decode a ``randk_flat`` body into ``out[:total]`` (zeros elsewhere in
    the real-coordinate range). Untrusted fields validated."""
    ex = body.get("extra", {})
    try:
        k = int(ex["k"])
        seed = int(ex["seed"])
    except (KeyError, TypeError, ValueError):
        raise WireError("randk_flat record missing codec scalars")
    vals = np.asarray(body["vals"], np.float32)
    if k < 0 or k > total or vals.size != k:
        raise WireError("randk_flat k/value-block mismatch")
    idx = _randk_indices(seed, total, k)
    out[:total] = 0.0
    out[idx] = vals


def _decode_flat(body: dict, leaves, treedef) -> Pytree:
    """Reconstruct a dense delta pytree from a flat record body."""
    sizes = np.asarray(body["sizes"], np.int64)
    if len(sizes) != len(leaves):
        raise WireError(
            f"flat payload has {len(sizes)} leaves, template has {len(leaves)}"
        )
    for n, leaf in zip(sizes, leaves):
        if int(n) != np.size(leaf):
            raise WireError("flat leaf size mismatch with template")
    total = int(sizes.sum())
    if body["kind"] == "partial_flat":
        dense = np.asarray(body["row"], np.float32)
        if dense.size != total:
            raise WireError("partial_flat row size mismatch with template")
    elif body["kind"] == "rotq_flat":
        dense = _rotq_reconstruct(body, total)
    elif body["kind"] == "randk_flat":
        dense = np.zeros(total, np.float32)
        _randk_scatter(body, total, dense)
    elif body["kind"] == "topk_flat":
        idx = np.ascontiguousarray(body["idx"], np.int32)
        # Untrusted wire data: the native scatter writes unchecked.
        if idx.size and (idx.min() < 0 or idx.max() >= total):
            raise WireError("sparse index out of range")
        dense = unpack_sparse(idx, body["vals"], total)
    else:  # int8_flat
        codes = np.ascontiguousarray(body["codes"], np.int8)
        if codes.size != total:
            raise WireError("int8_flat code block size mismatch")
        scales = np.asarray(body["scales"], np.float32)
        if scales.size != len(sizes):
            raise WireError("int8_flat scale table size mismatch")
        dense = np.empty(total, np.float32)
        off = 0
        for n, s in zip(sizes, scales):
            n = int(n)
            dense[off : off + n] = dequant_int8(
                codes[off : off + n], float(s), n
            )
            off += n
    out = []
    off = 0
    for leaf in leaves:
        n = int(np.size(leaf))
        out.append(
            dense[off : off + n]
            .reshape(np.shape(leaf))
            .astype(np.asarray(leaf).dtype)
        )
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def decode_into_row(
    data: bytes, sizes, out: np.ndarray
) -> dict:
    """Decode a sparse payload DIRECTLY into a preallocated f32 row.

    The streaming server pipeline's decode: no per-leaf template trees, no
    ``tree_unflatten``, no per-leaf reshape/astype — the record's values
    land straight in ``out[: total]``, the row of the server's
    ``[clients, P]`` flat buffer (``fedtpu.ops.flat`` coordinate order,
    which both ends derive from the shared model definition). ``sizes`` is
    the per-leaf scalar-count table (``FlatLayout.sizes``). Every real
    coordinate of ``out`` is written (kept values, zeros for dropped top-k
    coordinates); ``out[total:]`` — the lane padding — is never touched, so
    a zero-initialised reusable buffer stays pad-clean across rounds.

    Returns the record's ``extra`` dict. Raises :class:`WireError` on any
    template mismatch or out-of-range index, exactly like :func:`decode`.
    """
    body = serialization.msgpack_restore(_unframe(data))
    sizes = [int(s) for s in sizes]
    total = sum(sizes)
    if out.shape[0] < total or out.dtype != np.float32:
        raise ValueError(
            f"row buffer too small or not f32: {out.shape} {out.dtype} "
            f"for {total} coordinates"
        )
    kind = body.get("kind")
    if kind in (
        "topk_flat",
        "int8_flat",
        "partial_flat",
        "rotq_flat",
        "randk_flat",
    ):
        wire_sizes = np.asarray(body["sizes"], np.int64)
        if len(wire_sizes) != len(sizes):
            raise WireError(
                f"flat payload has {len(wire_sizes)} leaves, layout has "
                f"{len(sizes)}"
            )
        for n, m in zip(wire_sizes, sizes):
            if int(n) != m:
                raise WireError("flat leaf size mismatch with layout")
        if kind == "partial_flat":
            # Hierarchical partial sum: a dense f32 row lands verbatim —
            # the straight-copy degenerate case of the streaming decode
            # (the root's per-aggregator cost is ONE memcpy + validation,
            # the O(aggregators) claim the fan-in bench measures).
            row = np.asarray(body["row"], np.float32)
            if row.size != total:
                raise WireError("partial_flat row size mismatch with layout")
            out[:total] = row
        elif kind == "rotq_flat":
            out[:total] = _rotq_reconstruct(body, total)
        elif kind == "randk_flat":
            _randk_scatter(body, total, out)
        elif kind == "topk_flat":
            idx = np.ascontiguousarray(body["idx"], np.int32)
            # Untrusted wire data: the scatter below writes unchecked.
            if idx.size and (idx.min() < 0 or idx.max() >= total):
                raise WireError("sparse index out of range")
            out[:total] = 0.0
            out[idx] = np.asarray(body["vals"], np.float32)
        else:  # int8_flat
            codes = np.ascontiguousarray(body["codes"], np.int8)
            if codes.size != total:
                raise WireError("int8_flat code block size mismatch")
            scales = np.asarray(body["scales"], np.float32)
            if scales.size != len(sizes):
                raise WireError("int8_flat scale table size mismatch")
            off = 0
            for n, s in zip(sizes, scales):
                out[off : off + n] = dequant_int8(
                    codes[off : off + n], float(s), n
                )
                off += n
        extra = dict(body.get("extra", {}))
        # Advisory decode-side codec tag for the per-codec wire accounting
        # (fedtpu_rpc_bytes_*_total{codec=...}); transport-internal, popped
        # by the server before extras reach user records.
        extra["_codec"] = kind
        return extra
    # Per-leaf record kinds (topk | int8): one entry per leaf, scattered
    # into the leaf's slice of the row.
    if len(body["leaves"]) != len(sizes):
        raise WireError(
            f"sparse payload has {len(body['leaves'])} leaves, layout has "
            f"{len(sizes)}"
        )
    off = 0
    for i, n in enumerate(sizes):
        e = body["leaves"][str(i)]
        if int(e["size"]) != n:
            raise WireError("sparse leaf size mismatch with layout")
        if kind == "topk":
            idx = np.ascontiguousarray(e["idx"], np.int32)
            if idx.size and (idx.min() < 0 or idx.max() >= n):
                raise WireError("sparse index out of range")
            out[off : off + n] = 0.0
            out[off + idx] = np.asarray(e["vals"], np.float32)
        elif kind == "int8":
            out[off : off + n] = dequant_int8(e["codes"], float(e["scale"]), n)
        else:
            raise WireError(f"unknown sparse kind {kind!r}")
        off += n
    extra = dict(body.get("extra", {}))
    extra["_codec"] = kind
    return extra


def decode(data: bytes, like: Pytree) -> Tuple[Pytree, dict]:
    """Reconstruct a dense delta pytree shaped like ``like``; returns
    (deltas, extra)."""
    body = serialization.msgpack_restore(_unframe(data))
    leaves, treedef = jax.tree_util.tree_flatten(like)
    if body.get("kind") in (
        "topk_flat",
        "int8_flat",
        "partial_flat",
        "rotq_flat",
        "randk_flat",
    ):
        extra = dict(body.get("extra", {}))
        extra["_codec"] = body["kind"]
        return _decode_flat(body, leaves, treedef), extra
    if len(body["leaves"]) != len(leaves):
        raise WireError(
            f"sparse payload has {len(body['leaves'])} leaves, template has "
            f"{len(leaves)}"
        )
    enc = [body["leaves"][str(i)] for i in range(len(leaves))]
    out = []
    for leaf, e in zip(leaves, enc):
        n = int(e["size"])
        if n != np.size(leaf):
            raise WireError("sparse leaf size mismatch with template")
        if body["kind"] == "topk":
            idx = np.ascontiguousarray(e["idx"], np.int32)
            # Wire data is untrusted: the native scatter writes out[idx[i]]
            # unchecked, so out-of-range indices would be a heap write.
            if idx.size and (idx.min() < 0 or idx.max() >= n):
                raise WireError("sparse index out of range")
            dense = unpack_sparse(idx, e["vals"], n)
        elif body["kind"] == "int8":
            dense = dequant_int8(e["codes"], float(e["scale"]), n)
        else:
            raise WireError(f"unknown sparse kind {body['kind']!r}")
        out.append(dense.reshape(np.shape(leaf)).astype(np.asarray(leaf).dtype))
    extra = dict(body.get("extra", {}))
    extra["_codec"] = body["kind"]
    return jax.tree_util.tree_unflatten(treedef, out), extra
