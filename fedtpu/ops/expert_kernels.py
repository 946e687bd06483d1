"""The held experts' grouped product as TPU kernels that read an expert's
weights where they lie: ``rows [n_blocks * block, in]`` laid out a block an
expert, the stacked weights ``[held, in, out]`` as they are, and the
block-to-expert map with the count of live blocks as scalar-prefetch operands.

What the plain body (``fedtpu.models.lm_layers.routed_experts``: a one-hot
product that writes a copy of an expert's matrix for every block, then a
batched product over all the blocks) computes, in the same arithmetic:
operands of the weights' dtype, float32 accumulation, the output in the dtype
asked for. Nothing is dropped or approximated: every live block is multiplied.

Forward (``expert_product``), grid ``(output tiles, blocks)``, the blocks
inner: block ``b``'s weight tile is read at ``expert[b]`` by its
``BlockSpec``'s index map, so no ``[n_blocks, in, out]`` copy exists, and
consecutive blocks of ONE expert (the layout sorts by expert) reuse the tile
they fetched. The live blocks come first; a dead block (``b >= live``)
multiplies nothing and writes zeros, and its index maps repeat the last live
block's, so no DMA is issued for it. The tile is the whole matrix where it
fits (:func:`_columns`: it does at every published width of whole lanes,
2-6.3 MB), else the widest lane multiple that does and divides the width; a
width no lane multiple divides (Nemotron-H's 1,856 = 14.5 x 128) goes whole,
since a block dimension equal to the array's is legal whatever its size, and
Mosaic pads its last half lane group in VMEM, where the padding enters no sum.

Backward, one ``jax.custom_vjp``: ``d rows = d out x w^T`` is the same kernel
on the transposed contraction (``expert_product_transposed``: the weight tile
enters the product by its last axis; a float32 cotangent is rounded to the
weights' dtype in VMEM, as the MXU's default pass rounds it in the plain
body); ``d w[e] = sum over e's blocks of rows_b^T x d out_b``
(``expert_weights_gradient``) walks the blocks with a float32 VMEM tile that
starts at an expert's first block and is written, in the weights' dtype, at
its last, through an OUTPUT index map that reads the expert. An expert no
block fell on is visited by none of those steps: after the blocks the grid
has one step an expert, and a missed expert's writes zeros there (the others'
repeat the index of the step before and do nothing, so nothing written is
touched again). Residuals are ``rows``, the weights and the map.

Which body runs: :func:`takes` says whether this module does, on a TPU backend
at widths of a lane group or more in whole sublane tiles (every published
size: whole lanes, and 1,856) and a block of whole sublane tiles; the plain
body everywhere else (``interpret`` as in :mod:`fedtpu.ops.pallas_kernels`). The
passes are jitted for the trace and the lowering alone (as
:mod:`fedtpu.ops.delta_rule_kernels`'): a model's layers, its three products
and the two traces differentiation makes share one lowered function a shape.
The backward rule names the scope itself.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedtpu.ops.pallas_kernels import _mode

SCOPE = "fed.local_step.fwd_bwd.moe.experts"
LANES = _LANES = 128
_SUBLANES = 16  # a bfloat16 tile's rows; float32's 8 divide it
# The most a weight tile may take (two of them are in flight): every published
# matrix of whole lanes (6.3 MB the largest) goes whole. A width no lane
# multiple divides cannot be cut and goes whole whatever it takes: 9.98 MB a
# bfloat16 tile of [2688, 1856], 19.96 MB the gradient's float32 one beside
# two output tiles of 9.98 MB, 64.1 MB scoped in all, under ``_VMEM_LIMIT``.
_TILE_BYTES = 8 * 1024 * 1024
# Two buffers each of a block's rows, a weight tile and the output, and the
# float32 tile of the weights' gradient, pass the 16 MiB a kernel gets by
# default at blocks of 1,024 rows; a v5e has 128 MiB.
_VMEM_LIMIT = 96 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b

# What a step of the weights' gradient does (bits of its ``kind``).
_LIVE, _FIRST, _LAST, _MISSED = 1, 2, 4, 8


def _fits(rows, w, block) -> bool:
    """Calls the kernels are built for: ``rows [n_blocks * block, in]`` on
    ``w [held, in, out]`` of one dtype, both widths a lane group or more in
    whole sublane tiles (whole lanes or not), a block of whole sublane
    tiles."""
    return (rows.ndim == 2 and w.ndim == 3 and rows.dtype == w.dtype
            and rows.shape[1] == w.shape[1]
            and all(width >= _LANES and width % _SUBLANES == 0
                    for width in w.shape[1:])
            and block % _SUBLANES == 0 and rows.shape[0] % block == 0)


def on_a_tpu(interpret: Optional[bool] = None) -> bool:
    """Whether the backend runs the kernels at all (or ``interpret`` says
    so), whatever the shapes."""
    return _mode(interpret) != "xla"


def takes(rows, w, block, interpret: Optional[bool] = None) -> bool:
    """Whether a grouped product goes through the kernels: on a TPU (or where
    ``interpret`` says so), at shapes they are built for."""
    return on_a_tpu(interpret) and _fits(rows, w, block)


def _columns(depth: int, width: int, itemsize: int) -> int:
    """The widest tile ``[depth, columns]`` of a ``[depth, width]`` matrix
    within ``_TILE_BYTES``: ``columns`` a lane multiple that divides
    ``width``, a lane group at the least; the whole ``width`` where no lane
    multiple divides it."""
    if width % _LANES:
        return width
    fit = [c for c in range(_LANES, width + 1, _LANES)
           if width % c == 0 and depth * c * itemsize <= _TILE_BYTES]
    return fit[-1] if fit else _LANES


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _params(interpret, name):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)


def _product_kernel(expert_ref, live_ref, x_ref, w_ref, o_ref, *, dims):
    b = pl.program_id(1)

    @pl.when(b < live_ref[0])
    def _():
        o_ref[...] = _dot(
            x_ref[...].astype(w_ref.dtype), w_ref[...], dims).astype(o_ref.dtype)

    @pl.when(b >= live_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(
    jax.jit, static_argnames=("block", "transposed", "out_dtype", "interpret"))
def _product(x, w, expert, live, block, transposed, out_dtype, interpret):
    """``x [n_blocks * block, .]`` times block ``b``'s ``w[expert[b]]``
    (``[in, out]``; ``transposed``: its transpose) -> ``[n_blocks * block,
    .]`` in ``out_dtype``, zeros in the blocks from ``live[0]`` on."""
    _, w_in, w_out = w.shape
    depth, width = (w_out, w_in) if transposed else (w_in, w_out)
    cols = _columns(depth, width, w.dtype.itemsize)

    def at(b, live):  # a dead block repeats the last live one: nothing moves
        return jnp.maximum(jnp.minimum(b, live[0] - 1), 0)

    if transposed:
        weights = pl.BlockSpec(
            (None, cols, depth), lambda j, b, e, n: (e[at(b, n)], j, 0))
    else:
        weights = pl.BlockSpec(
            (None, depth, cols), lambda j, b, e, n: (e[at(b, n)], 0, j))
    return pl.pallas_call(
        functools.partial(_product_kernel, dims=_NT if transposed else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(width // cols, x.shape[0] // block),
            in_specs=[
                pl.BlockSpec((block, depth), lambda j, b, e, n: (at(b, n), 0)),
                weights],
            out_specs=pl.BlockSpec((block, cols), lambda j, b, e, n: (b, j))),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], width), jnp.dtype(out_dtype)),
        **_params(interpret, "expert_product_transposed" if transposed
                  else "expert_product"),
    )(expert, live, x, w)


def _gradient_kernel(at_ref, where_ref, kind_ref, x_ref, dy_ref, dw_ref, acc_ref):
    kind = kind_ref[pl.program_id(1)]
    dtype = dw_ref.dtype

    @pl.when((kind & _LIVE) != 0)
    def _():
        part = _dot(x_ref[...].astype(dtype), dy_ref[...].astype(dtype), _TN)

        @pl.when((kind & _FIRST) != 0)
        def _():
            acc_ref[...] = part

        @pl.when((kind & _FIRST) == 0)
        def _():
            acc_ref[...] += part

        @pl.when((kind & _LAST) != 0)
        def _():
            dw_ref[...] = acc_ref[...].astype(dtype)

    @pl.when((kind & _MISSED) != 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)


def _steps(expert, live, held):
    """The weights' gradient's grid steps, a block each and then an expert
    each, as three int32 vectors: the block of rows a step reads, the expert
    whose tile it holds, and what it does there (``_LIVE | _FIRST | _LAST`` of
    an expert's run of blocks, ``_MISSED`` for an expert no live block fell
    on, 0 for nothing: such a step repeats the indices of the one before)."""
    b = jnp.arange(expert.shape[0], dtype=jnp.int32)
    is_live = b < live
    at = jnp.maximum(jnp.minimum(b, live - 1), 0)
    e = jnp.where(live > 0, expert[at], 0)
    first = is_live & ((b == 0) | (e != jnp.roll(e, 1)))
    last = is_live & ((b == live - 1) | (e != jnp.roll(e, -1)))
    experts = jnp.arange(held, dtype=jnp.int32)
    missed = ~jnp.any(is_live[:, None] & (expert[:, None] == experts), axis=0)
    # A missed expert's step goes to its tile; another's stays where the step
    # before was: at the last missed expert before it or, with none, at the
    # blocks' last tile.
    before = jax.lax.cummax(jnp.where(missed, experts, -1))
    tail = jnp.where(before >= 0, before, e[-1])
    kind = jnp.concatenate([
        is_live * _LIVE + first * _FIRST + last * _LAST, missed * _MISSED])
    return (jnp.concatenate([at, jnp.full((held,), at[-1])]),
            jnp.concatenate([e, tail]), kind.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("held", "block", "interpret"))
def _weights_gradient(x, dy, expert, live, held, block, interpret):
    """``d w [held, in, out]`` in ``x``'s dtype from ``x [n_blocks * block,
    in]`` and ``dy [n_blocks * block, out]``: expert ``e``'s is the sum over
    its live blocks of ``x_b^T dy_b``, zeros where it has none."""
    depth, width = x.shape[1], dy.shape[1]
    cols = _columns(depth, width, 4)  # the float32 tile sets the size
    at, where, kind = _steps(expert, live[0], held)
    return pl.pallas_call(
        _gradient_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(width // cols, at.shape[0]),
            in_specs=[
                pl.BlockSpec((block, depth), lambda j, s, at, e, k: (at[s], 0)),
                pl.BlockSpec((block, cols), lambda j, s, at, e, k: (at[s], j))],
            out_specs=pl.BlockSpec(
                (None, depth, cols), lambda j, s, at, e, k: (e[s], 0, j)),
            scratch_shapes=[pltpu.VMEM((depth, cols), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((held, depth, width), x.dtype),
        **_params(interpret, "expert_weights_gradient"),
    )(at, where, kind, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _grouped(x, w, expert, live, block, out_dtype, interpret):
    return _grouped_fwd(x, w, expert, live, block, out_dtype, interpret)[0]


def _grouped_fwd(x, w, expert, live, block, out_dtype, interpret):
    out = _product(x, w, expert, live, block, False, out_dtype, interpret)
    return out, (x, w, expert, live)


def _grouped_bwd(block, out_dtype, interpret, kept, dy):
    x, w, expert, live = kept
    with jax.named_scope(SCOPE):
        dx = _product(dy, w, expert, live, block, True, x.dtype.name, interpret)
        dw = _weights_gradient(x, dy, expert, live, w.shape[0], block, interpret)
    return dx, dw, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_product(rows, w, expert, live_blocks, block, out_dtype=None,
                    interpret: Optional[bool] = None):
    """``rows [n_blocks * block, in]`` times the weights of each block's
    expert, ``w[expert[b]]`` of ``w [held, in, out]`` -> ``[n_blocks * block,
    out]`` in ``out_dtype`` (``rows``' by default), at the shapes
    :func:`takes` admits. The first ``live_blocks`` blocks are multiplied, an
    expert's blocks one after the other (what the gradient of ``w`` sums a
    run of); the blocks behind them come out as zeros whatever their rows
    hold, and ``expert`` is not read there."""
    if not _fits(rows, w, block):
        raise ValueError(
            f"the kernels take rows and weights of one dtype at widths of a "
            f"lane group or more in whole {_SUBLANES}s and blocks of "
            f"{_SUBLANES}-row tiles, not "
            f"block={block} on {jnp.shape(rows)} {rows.dtype} and "
            f"{jnp.shape(w)} {w.dtype}")
    expert = jnp.clip(expert.astype(jnp.int32), 0, w.shape[0] - 1)
    live = jnp.reshape(live_blocks, (1,)).astype(jnp.int32)
    return _grouped(
        rows, w, expert, live, block, jnp.dtype(out_dtype or rows.dtype).name,
        _mode(interpret) == "interpret")
