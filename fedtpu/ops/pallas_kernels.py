"""Pallas TPU kernels for the update-compression hot path.

What runs where. On a TPU backend ``threshold_with_feedback`` and
``quantdequant_int8`` lower through Mosaic — that is the default there, and
``chip_smoke.py`` compiles and runs both on the chip at the shapes the
codecs hand them (the flat ``[clients, P]`` row and the smallest per-leaf
``[clients, 10]``), bitwise against the plain-jnp bodies below. Off TPU the
default is those plain-jnp bodies (XLA fuses the same chain; the Pallas
interpreter costs ~1000x on CPU). ``hadamard_rotate`` is plain
``lax.dot_general`` on every backend: the Walsh-Hadamard matrix factors as
a Kronecker product of small Hadamard matrices, so the rotation is one f32
matrix product per factor (three at the 2^20-column row), which XLA hands
to the MXU. There is no ``pallas_call`` around it: a kernel that keeps a
row in VMEM across the products (one pass instead of three) is the
follow-up if the rotation is still the codec's largest scope (ROADMAP
Speed 1).

Whether the two kernels beat XLA's own fusion of the same chain has not
been measured on the current tree (the round-4 record,
``artifacts/PALLAS_TPU_RUN.json``, had them level); the codec cell of the
benchmark decides whether they stay (ROADMAP Design 3).

The compression pipeline (threshold mask, residual split, quantize — see
:mod:`fedtpu.ops.compression`) is a chain of elementwise ops over every
parameter of every client: at 64 clients x ~3.2M params (MobileNet, reference
``src/models/mobilenet.py``) that is ~800 MB of traffic per round if each op
round-trips HBM. The kernels pin the fusion explicitly — one read of the
combined delta+residual, one write of (compressed, new_residual).

Tiling obeys Mosaic's (8, 128) f32 tile rule: blocks are 8 client rows by a
lane-aligned column slice (~1 MB per operand per grid step — small enough
that the 4 double-buffered operands of the threshold kernel stay inside the
16 MB VMEM scoped limit). Per-row scalars (thresholds / scales) ride as a
``[rows, 1]`` column so their block shape satisfies the same rule.

The ``interpret`` argument: ``None`` (every production call site) decides by
backend as above; ``False`` forces Mosaic — compiling FOR a TPU from a CPU
host, ``tools/compile_pallas_tpu.py``; a true value runs the interpreted
``pallas_call`` and is for the CPU test suite only.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Max column-block size in elements: 32K f32 x 8 rows = 1 MB per operand per
# grid step — large enough that grid dispatch is negligible, small enough
# that the operands of a step (double-buffered) stay well inside VMEM.
_BLOCK_COLS = 32 * 1024
_BLOCK_ROWS = 8
assert _BLOCK_COLS % 128 == 0, "column blocks must stay lane-aligned"


def _mode(interpret: Optional[bool]) -> str:
    """'mosaic' (pallas, compiled) | 'interpret' (pallas, interpreted) |
    'xla' (plain-jnp equivalent, off-TPU default)."""
    if interpret is None:
        return "mosaic" if jax.default_backend() == "tpu" else "xla"
    return "interpret" if interpret else "mosaic"


def _blocks(rows: int, cols: int):
    """Mosaic-legal (row_block, col_block): rows tiled by 8 (or the full dim
    when smaller), columns tiled by the (lane-aligned) ``_BLOCK_COLS`` unless
    the block spans the whole dimension."""
    rb = rows if rows <= _BLOCK_ROWS else _BLOCK_ROWS
    cb = cols if cols <= _BLOCK_COLS else _BLOCK_COLS
    return rb, cb


def _threshold_kernel(y_ref, t_ref, out_ref, new_e_ref):
    """One tile of fused magnitude threshold + residual split.

    keep = |y| >= t (per-client threshold); out = y * keep; new_e = y - out.
    The caller precomputes y = delta + residual (it needs y anyway for the
    top-k threshold), so the kernel reads ONE full-size operand.
    """
    y = y_ref[...]
    keep = jnp.abs(y) >= t_ref[...]  # [rows, 1] broadcasts over [rows, cols]
    out = jnp.where(keep, y, jnp.zeros_like(y))
    out_ref[...] = out
    new_e_ref[...] = y - out


def threshold_with_feedback_jnp(y: jnp.ndarray, thresh: jnp.ndarray):
    """Plain-jnp body of :func:`threshold_with_feedback`: the off-TPU path
    and the reference the chip smoke compares the Mosaic kernel against."""
    out = jnp.where(jnp.abs(y) >= thresh[:, None], y, jnp.zeros_like(y))
    return out, y - out


@functools.partial(jax.jit, static_argnames=("interpret",))
def threshold_with_feedback(
    y: jnp.ndarray, thresh: jnp.ndarray, interpret: Optional[bool] = None
):
    """Fused ``out = y * (|y| >= thresh); new_e = y - out``.

    ``y: [rows, cols]`` (rows = clients, cols = leaf size; the caller's
    delta + residual), ``thresh: [rows]`` per-row magnitude threshold.
    Returns ``(out, new_e)``.
    """
    rows, cols = y.shape
    mode = _mode(interpret)
    if mode == "xla":
        return threshold_with_feedback_jnp(y, thresh)
    rb, cb = _blocks(rows, cols)
    grid = (pl.cdiv(rows, rb), pl.cdiv(cols, cb))
    return pl.pallas_call(
        _threshold_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, cb), lambda r, c: (r, c)),
            pl.BlockSpec((rb, 1), lambda r, c: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rb, cb), lambda r, c: (r, c)),
            pl.BlockSpec((rb, cb), lambda r, c: (r, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(y.shape, y.dtype),
            jax.ShapeDtypeStruct(y.shape, y.dtype),
        ],
        interpret=mode == "interpret",
    )(y, thresh.reshape(rows, 1))


# Widest Kronecker factor of the rotation: the v5e's MXU tile and the lane
# width of a vector register. An f32 register holds _SUBLANES rows of it.
_MAX_FACTOR = 128
_SUBLANES = 8


def _hadamard_factors(h: int) -> tuple:
    """Kronecker factor widths of ``H_h``, major first: a function of ``h``
    alone. Every factor but the major one is ``_MAX_FACTOR``; the major one
    takes the remainder (``2^20 -> (64, 128, 128)``, ``2^13 -> (64, 128)``,
    ``64 -> (64,)``)."""
    factors = []
    while h > _MAX_FACTOR:
        factors.append(_MAX_FACTOR)
        h //= _MAX_FACTOR
    factors.append(h)
    return tuple(reversed(factors))


@functools.lru_cache(maxsize=None)
def _sylvester(n: int) -> np.ndarray:
    """The Sylvester-ordered Walsh-Hadamard matrix ``H_n`` (entries +-1,
    symmetric, ``H_n @ H_n == n * I``) as a host constant."""
    hmat = np.ones((1, 1), np.float32)
    while hmat.shape[0] < n:
        hmat = np.block([[hmat, hmat], [hmat, -hmat]])
    return hmat


def _dot_f32(lhs, rhs, dimension_numbers):
    """An f32 product that stays f32 on a TPU: ``Precision.HIGHEST`` is six
    bf16 passes on the MXU. The DEFAULT there is ONE bf16 pass, which would
    truncate every coordinate to 8 bits of mantissa — a different codec,
    and one the benchmark's check would not catch.
    ``tests/test_compression.py`` walks the traced rotation so that it
    cannot ship."""
    return jax.lax.dot_general(
        lhs,
        rhs,
        dimension_numbers,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _fwht_products(x: jnp.ndarray) -> jnp.ndarray:
    """Unnormalized Walsh-Hadamard transform over the last axis, as one
    matrix product per Kronecker factor.

    ``H_(a*b*c) = H_a (x) H_b (x) H_c``: with the row viewed as
    ``[a, b, c]`` (``c`` minor) the transform is a right-multiplication of
    the minor axis by ``H_c`` and a left-multiplication of each other axis
    by its factor (``H_b @ X[r, a]``, ``H_a @ X[r]``): MXU-shaped products,
    none with a trailing dimension below ``_MAX_FACTOR`` once the row has
    one. ``h`` must be a power of two (the ``pow2=True`` flat layout
    guarantees it). H is symmetric and ``H @ H == h * I``, so the same body
    normalized by ``1/sqrt(h)`` is its own inverse — the property the rotq
    codec's decode side relies on.

    Rows are taken ``_SUBLANES`` at a time, ``[groups, tiles, g, lanes]``:
    that is how the TPU's (8, 128) tiling already lays a ``[rows, h]`` f32
    buffer out, so the first product reads the flat buffer in place and
    every later one keeps whole registers as its trailing dimensions.
    Viewed as ``[rows * a * b, c]`` instead, XLA copies the whole buffer
    into that layout before the first product and again between products
    (PERF.md, PR 26). The transposes are of the view only: XLA:TPU folds
    each into a product's output layout, and one copy is left at the end.
    """
    rows, h = x.shape
    factors = _hadamard_factors(h)
    lanes = factors[-1]
    g = math.gcd(rows, _SUBLANES)
    groups, tiles = rows // g, h // lanes
    x = x.reshape(groups, g, tiles, lanes).transpose(0, 2, 1, 3)
    x = _dot_f32(x, jnp.asarray(_sylvester(lanes)), (((3,), (0,)), ((), ())))
    minor = 1  # tiles spanned by the factors already transformed
    for f in reversed(factors[:-1]):
        major = tiles // (f * minor)
        # [f, groups, major, minor, g, lanes] -> [groups, major, f, ...]
        x = _dot_f32(
            jnp.asarray(_sylvester(f)),
            x.reshape(groups, major, f, minor, g, lanes),
            (((1,), (2,)), ((), ())),
        ).transpose(1, 2, 0, 3, 4, 5)
        minor *= f
    return x.reshape(groups, tiles, g, lanes).transpose(0, 2, 1, 3).reshape(rows, h)


@functools.partial(jax.jit, static_argnames=("inverse",))
@jax.named_scope("fed.codec.rotate")
def hadamard_rotate(
    y: jnp.ndarray, signs: jnp.ndarray, inverse: bool = False
) -> jnp.ndarray:
    """Seeded structured random rotation ``R = (1/sqrt(h)) * H * D``.

    ``y: [rows, h]`` with ``h`` a power of two; ``signs: [h]`` the
    Rademacher diagonal D. Forward: ``R y = fwht(y * signs) / sqrt(h)``;
    ``inverse=True`` computes ``R^-1 y = fwht(y) / sqrt(h) * signs``
    (exact, because ``fwht(fwht(x)) == h * x``). The rotq codec rotates on
    the client, quantizes, and inverse-rotates on the server — both ends
    regenerate ``signs`` from the shared record seed.

    One algorithm on every backend and for every width (see the module
    docstring): Kronecker-factored f32 matrix products, three passes over
    a 2^20-column row where a stride-doubling butterfly makes twenty. The
    summation order differs from the host butterflies'
    (``transport.sparse._fwht_np``, the benchmark's reference), so results
    agree with them to float rounding (1e-5 of the normalised values), not
    bit for bit.
    """
    h = y.shape[1]
    if h & (h - 1):
        raise ValueError(f"hadamard_rotate needs a power-of-two width, got {h}")
    y = y.astype(jnp.float32)
    signs = signs.astype(jnp.float32)
    if not inverse:
        y = y * signs[None, :]
    out = _fwht_products(y) * jnp.float32(1.0 / math.sqrt(h))
    if inverse:
        out = out * signs[None, :]
    return out


def _quantdequant_kernel(x_ref, s_ref, out_ref):
    """One tile of simulated int8 quantize-dequantize: round(x/s) * s."""
    s = s_ref[...]  # [rows, 1]
    # Guard the all-zero leaf: scale 0 would produce NaN via 0/0.
    safe = jnp.where(s > 0, s, jnp.ones_like(s))
    q = jnp.clip(jnp.round(x_ref[...] / safe), -127.0, 127.0)
    out_ref[...] = q * safe


def quantdequant_int8_jnp(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Plain-jnp body of :func:`quantdequant_int8` (off-TPU path; the chip
    smoke's reference for the Mosaic kernel)."""
    s = scale[:, None]
    safe = jnp.where(s > 0, s, jnp.ones_like(s))
    return jnp.clip(jnp.round(x / safe), -127.0, 127.0) * safe


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantdequant_int8(
    x: jnp.ndarray, scale: jnp.ndarray, interpret: Optional[bool] = None
) -> jnp.ndarray:
    """Simulated symmetric int8 codec: ``clip(round(x/scale), ±127) * scale``.

    ``x: [rows, cols]``, ``scale: [rows]`` (per-client max|x|/127). The wire
    format for the DCN edge transmits the int8 codes + one f32 scale per leaf
    (``fedtpu.transport.sparse.encode_int8``); on-device FedAvg uses this fused
    quantize-dequantize so aggregation sees exactly the wire numbers.
    """
    rows, cols = x.shape
    mode = _mode(interpret)
    if mode == "xla":
        return quantdequant_int8_jnp(x, scale)
    rb, cb = _blocks(rows, cols)
    grid = (pl.cdiv(rows, rb), pl.cdiv(cols, cb))
    return pl.pallas_call(
        _quantdequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, cb), lambda r, c: (r, c)),
            pl.BlockSpec((rb, 1), lambda r, c: (r, 0)),
        ],
        out_specs=pl.BlockSpec((rb, cb), lambda r, c: (r, c)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=mode == "interpret",
    )(x, scale.reshape(rows, 1))
