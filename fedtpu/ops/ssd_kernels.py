"""Mamba-2's chunked selective scan as two fused TPU kernels, forward and
backward under one ``jax.custom_vjp``: a chunk's decay matrices and the
running state stay in VMEM.

What the plain chunks (``fedtpu.models.mamba2._plain_chunks``) compute, in
the same arithmetic: operands of ``x``'s dtype into every product, float32
accumulation; the step sizes, their running sums ``L``, every exponential, the
decay matrices before their cast, the ``D`` skip and the state between chunks
in float32; every exponent a difference ``L_t - L_s <= 0`` with ``s <= t`` (a
masked entry is ``exp`` of a large negative number, never ``inf - inf``); a
head reads its GROUP's ``B``, ``C`` and ``C B^T``, made once a group.

Forward, grid ``(groups, chunks)``, the chunks in order: a step holds one
chunk ``C`` of one group's ``R`` heads, ``x [C, R P]`` as the model has it (a
head's ``P`` columns beside the next head's), ``B, C [C, N]`` and the group's
float32 state ``[R P, N]`` in scratch across the chunk axis. It makes ``C
B^T`` and ``C S^T`` (every head's read of the starting state, one product)
once; a head, ``M = exp(L_t - L_s) (C_t . B_s)`` on and under the diagonal and
``M (dt x)``; adds ``exp(L_t) C_t S`` and ``D x_t``; and updates ``S <-
exp(L_end) S + (exp(L_end - L_s) dt_s x_s)^T B``, every head's in one product.
Where ``P`` is half a lane group two heads share the 128 lanes of a block of
``x``: what is a scalar a head (``dt``, an exponential) is spread over its
head's lanes by a mask, a head's product is made over the whole lane group
and taken at the head's lanes, and no lane is shifted. Written to HBM: ``y``
in ``x``'s dtype and each chunk's STARTING state in float32 (what the plain
body's scan hands out too): what the backward pass reads instead of making it
again.

Backward, the chunks in reverse with ``dS`` in scratch: a chunk's matrices
are made again in VMEM from the operands and the saved starting state.
Written: ``dx, dB, dC`` in the operands' dtype (a group's heads add their
parts of ``dB`` and ``dC`` inside the step), ``dD`` summed over the chunks in
a block that stays, and the per-head scalars' gradients in float32.

The per-head scalars of a chunk reach the kernels twice, made by ``jax.numpy``
outside them (:func:`_scalars`, 2 MB a layer a row each): time in the lanes
``[G, chunks, 2 R, C]`` (``L`` and, in lane 0, ``exp(L_end)``) and time down
the sublanes ``[G, chunks, C, 4 R]`` (``dt``, ``L``, ``exp(L)``, ``exp(L_end -
L)``): a column is read, never made from a row. The kernels hand back the
gradient of each entry of both; the chain through the exponentials, the
running sum and ``A`` is ``jax``'s own differentiation of :func:`_scalars`.

Which body runs: :func:`takes` says whether this module does, on a TPU backend
at ``R P`` and ``N`` of whole lanes, ``P`` a part of a lane group, and a chunk
of 128 (no other: a chunk's matrix is one lane group square) that divides the
length; the plain chunks everywhere else (``interpret`` as in
:mod:`fedtpu.ops.pallas_kernels`). Both passes run under
``jax.named_scope(SCOPE)``, the backward rule naming it itself; the output,
the starting states and the two layouts of the scalars are named ``KEPT`` for
a rematerialised block's policy, so its backward pass does not run the forward
kernel again.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedtpu.ops.attention_kernels import KEPT
from fedtpu.ops.delta_rule_kernels import _LANES, _MASKED, _NT, _TN, _dot
from fedtpu.ops.pallas_kernels import _mode

SCOPE = "fed.local_step.fwd_bwd.mamba.core"
_SUBLANES = 8
CHUNK = _LANES  # the one chunk the kernels are built for


def _fits(x, dt, A, B, C, D, chunk) -> bool:
    """Shapes the kernels are built for: ``x [T, H, P]``, ``dt [T, H]``, ``A,
    D [H]``, ``B, C [T, G, N]`` of ``x``'s dtype; ``G`` divides ``H``; a
    group's heads ``R P`` and the state ``N`` whole lanes, a head a part of a
    lane group (or all of one); a chunk of 128 that divides the length."""
    if not (x.ndim == 3 and B.ndim == 3 and B.shape == C.shape
            and x.dtype == B.dtype == C.dtype):
        return False
    (t, heads, p), (_, g, n) = x.shape, B.shape
    return (B.shape[0] == t and dt.shape == (t, heads)
            and A.shape == D.shape == (heads,)
            and heads % g == 0 and _LANES % p == 0
            and (heads // g * p) % _LANES == 0 and n % _LANES == 0
            and chunk == CHUNK and t % chunk == 0)


def takes(x, dt, A, B, C, D, chunk, interpret: Optional[bool] = None) -> bool:
    """Whether a sequence goes through the kernels: on a TPU (or where
    ``interpret`` says so), at shapes they are built for."""
    return _mode(interpret) != "xla" and _fits(x, dt, A, B, C, D, chunk)


def _over(x, axis):
    return jnp.sum(x, axis=axis, keepdims=True)


class _Step:
    """What both passes read of a grid step: the group's ``B``, ``C`` and ``C
    B^T``, the chunk's mask, the per-head scalars and how they lie over a
    lane group's heads."""

    def __init__(self, b_ref, c_ref, rows_ref, cols_ref, p):
        self.b, self.c = b_ref[...], c_ref[...]
        self.dtype, chunk = self.b.dtype, self.b.shape[0]
        self.rows, self.cols = rows_ref[...], cols_ref[...]
        self.heads = self.rows.shape[0] // 2  # R
        self.p, self.side = p, _LANES // p  # heads a lane group
        self.cb = _dot(self.c, self.b, _NT)
        at_t = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        at_s = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        self.under = at_t >= at_s
        lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1)
        # which of its lane group's heads a lane belongs to
        self.of = [(lane >= i * p) & (lane < (i + 1) * p) for i in range(self.side)]

    def spread(self, kind, group):
        """Column ``kind`` (0 ``dt``, 1 ``L``, 2 ``exp(L)``, 3 ``exp(L_end -
        L)``) of lane group ``group``'s heads, each over its head's lanes:
        ``[C, 128]``."""
        shape = self.of[0].shape
        at = kind * self.heads + group * self.side
        out = jnp.broadcast_to(self.cols[:, at:at + 1], shape)
        for i in range(1, self.side):
            out = jnp.where(self.of[i], jnp.broadcast_to(
                self.cols[:, at + i:at + i + 1], shape), out)
        return out

    def within(self, r):
        """Head ``r``'s ``exp(L_t - L_s) (C_t . B_s)`` on and under the
        diagonal in float32, and the decay alone."""
        run = self.cols[:, self.heads + r:self.heads + r + 1]  # [C, 1]
        decay = jnp.exp(jnp.where(
            self.under, run - self.rows[r:r + 1, :], _MASKED))
        return decay * self.cb, decay

    def keep(self):
        """``exp(L_end)`` of each head down its ``P`` rows of the state: ``[R
        P, 1]`` (Mosaic spreads a ``[1, 1]`` over sublanes or over lanes, not
        both at once)."""
        return jnp.concatenate([
            jnp.broadcast_to(self.rows[self.heads + r:self.heads + r + 1, :1],
                             (self.p, 1)) for r in range(self.heads)], axis=0)

    def by_head(self, x, group):
        """``x [C, 128]`` summed over each head's lanes: ``(head, [C, 1])``
        of lane group ``group``."""
        for i in range(self.side):
            part = x if self.side == 1 else jnp.where(self.of[i], x, 0.0)
            yield group * self.side + i, _over(part, 1)


def _zero_at_the_rows_start(ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ref[...] = jnp.zeros_like(ref)


def _fwd_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, skip_ref, y_ref,
                starts_ref, state, *, p):
    _zero_at_the_rows_start(state)
    f32 = jnp.float32
    m = _Step(b_ref, c_ref, rows_ref, cols_ref, p)
    start = state[...]
    starts_ref[...] = start
    read = _dot(m.c, start.astype(m.dtype), _NT)  # C S^T [C, R P]
    left = []
    for group in range(x_ref.shape[1] // _LANES):
        lanes = slice(group * _LANES, (group + 1) * _LANES)
        xf = x_ref[:, lanes].astype(f32)
        fed = xf * m.spread(0, group)  # dt_s x_s
        fed_in = fed.astype(m.dtype)
        y = m.spread(2, group) * read[:, lanes] + skip_ref[:, lanes] * xf
        for i in range(m.side):
            within, _ = m.within(group * m.side + i)
            mine = _dot(within.astype(m.dtype), fed_in)
            y = y + (mine if m.side == 1 else jnp.where(m.of[i], mine, 0.0))
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        left.append((fed * m.spread(3, group)).astype(m.dtype))
    state[...] = m.keep() * start + _dot(
        jnp.concatenate(left, axis=1), m.b, _TN)


def _bwd_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, skip_ref, starts_ref,
                dy_ref, dx_ref, db_ref, dc_ref, drows_ref, dcols_ref,
                dskip_ref, dstate, *, p):
    _zero_at_the_rows_start(dstate)
    _zero_at_the_rows_start(dskip_ref)
    f32 = jnp.float32
    m = _Step(b_ref, c_ref, rows_ref, cols_ref, p)
    dtype, heads, chunk = m.dtype, m.heads, m.b.shape[0]
    start = starts_ref[...]
    sb = start.astype(dtype)
    ds = dstate[...]  # of the state this chunk leaves
    dsb = ds.astype(dtype)
    read = _dot(m.c, sb, _NT)  # C S^T [C, R P]
    d_left_all = _dot(m.b, dsb, _NT)  # of exp(L_end - L_s) dt_s x_s [C, R P]
    d_cb = jnp.zeros_like(m.cb)
    d_read, left = [], []
    for group in range(x_ref.shape[1] // _LANES):
        lanes = slice(group * _LANES, (group + 1) * _LANES)
        xf = x_ref[:, lanes].astype(f32)
        dt, grown, fade = (m.spread(k, group) for k in (0, 2, 3))
        fed = xf * dt
        fed_in = fed.astype(dtype)
        dyf = dy_ref[:, lanes].astype(f32)
        d_left = d_left_all[:, lanes]
        d_fed = d_left * fade
        for r, total in m.by_head(dyf * read[:, lanes], group):
            dcols_ref[:, 2 * heads + r:2 * heads + r + 1] = total  # d exp(L)
        for r, total in m.by_head(d_left * fed, group):
            dcols_ref[:, 3 * heads + r:3 * heads + r + 1] = total
        for i in range(m.side):
            r = group * m.side + i
            within, decay = m.within(r)
            dy_mine = dy_ref[:, lanes] if m.side == 1 else jnp.where(
                m.of[i], dyf, 0.0).astype(dtype)
            d_within = _dot(dy_mine, fed_in, _NT)  # [C, C]
            d_fed = d_fed + _dot(within.astype(dtype), dy_mine, _TN)
            d_cb = d_cb + d_within * decay
            # decay_ts = exp(L_t - L_s): +row sums to L_t, -column sums to L_s
            e = d_within * within
            dcols_ref[:, heads + r:heads + r + 1] = _over(e, 1)
            drows_ref[r:r + 1, :] = -_over(e, 0)
        for r, total in m.by_head(d_fed * xf, group):
            dcols_ref[:, r:r + 1] = total  # d dt
        dx_ref[:, lanes] = (d_fed * dt + skip_ref[:, lanes] * dyf
                            ).astype(dx_ref.dtype)
        dskip_ref[:, lanes] += jnp.sum(
            (dyf * xf).reshape(chunk // _SUBLANES, _SUBLANES, _LANES), axis=0)
        d_read.append((grown * dyf).astype(dtype))
        left.append((fed * fade).astype(dtype))
    d_read, left = jnp.concatenate(d_read, axis=1), jnp.concatenate(left, axis=1)
    d_cb = d_cb.astype(dtype)
    dc_ref[...] = (_dot(d_read, sb) + _dot(d_cb, m.b)).astype(dc_ref.dtype)
    db_ref[...] = (_dot(left, dsb) + _dot(d_cb, m.c, _TN)).astype(db_ref.dtype)
    dstate[...] = m.keep() * ds + _dot(d_read, m.c, _TN)
    # exp(L_end) was read at lane 0 of its row
    first = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == 0
    for r in range(heads):
        rows = slice(r * p, (r + 1) * p)
        d_keep = _over(_over(ds[rows] * start[rows], 1), 0)  # [1, 1]
        drows_ref[heads + r:heads + r + 1, :] = jnp.where(first, d_keep, 0.0)


def _call(kernel, name, reverse, p, interpret, operands, out, scratch):
    """``kernel`` over the grid ``(groups, chunks)``, the chunks in order or
    from the end. ``operands`` and ``out``: ``(array or its ShapeDtypeStruct,
    kind)``, the kind naming the block a step takes; ``scratch``: the shape of
    a group's state."""
    groups, chunks, rows, chunk = dict(
        (kind, x.shape) for x, kind in operands)["rows"]
    wide, n = scratch  # a group's heads side by side, the state
    at = (lambda c: chunks - 1 - c) if reverse else (lambda c: c)
    a_chunk = lambda *block: pl.BlockSpec(
        (None, None) + block, lambda g, c: (g, at(c), 0, 0))
    specs = dict(
        heads=pl.BlockSpec((chunk, wide), lambda g, c: (at(c), g)),
        group=pl.BlockSpec((chunk, n), lambda g, c: (at(c), g)),
        rows=a_chunk(rows, chunk), cols=a_chunk(chunk, 2 * rows),
        states=a_chunk(wide, n),
        skip=pl.BlockSpec((None, 1, wide), lambda g, c: (g, 0, 0)),
        skips=pl.BlockSpec((None, _SUBLANES, wide), lambda g, c: (g, 0, 0)))
    return pl.pallas_call(
        functools.partial(kernel, p=p),
        grid=(groups, chunks),
        in_specs=[specs[kind] for _, kind in operands],
        out_specs=[specs[kind] for _, kind in out],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x, _ in out],
        scratch_shapes=[pltpu.VMEM(scratch, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=name)(*(x for x, _ in operands))


# Both passes are jitted for the trace and the lowering alone, as the delta
# rule's are: a model's layers of one shape then share one jaxpr and one
# lowered function.
@functools.partial(jax.jit, static_argnames=("p", "interpret"))
def _forward(x, b, c, rows, cols, skip, p, interpret):
    """``x [T, H P]``, ``b, c [T, G N]``, ``rows [G, chunks, 2 R, C]``, ``cols
    [G, chunks, C, 4 R]``, ``skip [G, 1, R P]`` (``D`` over its head's
    columns) -> ``y`` as ``x`` and, in float32, the chunks' starting states
    ``[G, chunks, R P, N]``."""
    groups, chunks = rows.shape[:2]
    states = (x.shape[1] // groups, b.shape[1] // groups)
    starts = jax.ShapeDtypeStruct((groups, chunks) + states, jnp.float32)
    return _call(
        _fwd_kernel, "selective_scan_fwd", False, p, interpret,
        [(x, "heads"), (b, "group"), (c, "group"), (rows, "rows"),
         (cols, "cols"), (skip, "skip")],
        [(x, "heads"), (starts, "states")], states)


@functools.partial(jax.jit, static_argnames=("p", "interpret"))
def _backward(x, b, c, rows, cols, skip, starts, dy, p, interpret):
    """Operands as :func:`_forward` takes and gives them, ``dy`` as ``x`` ->
    ``dx, db, dc`` as the operands, ``drows, dcols`` as ``rows, cols`` and
    ``dskip [G, 8, R P]``, whose sum over the sublanes is ``skip``'s."""
    skips = jax.ShapeDtypeStruct(
        (skip.shape[0], _SUBLANES, skip.shape[2]), jnp.float32)
    return _call(
        _bwd_kernel, "selective_scan_bwd", True, p, interpret,
        [(x, "heads"), (b, "group"), (c, "group"), (rows, "rows"),
         (cols, "cols"), (skip, "skip"), (starts, "states"), (dy, "heads")],
        [(x, "heads"), (b, "group"), (c, "group"), (rows, "rows"),
         (cols, "cols"), (skips, "skips")], starts.shape[2:])


def _flat(x):  # [T, heads, width] -> [T, heads x width]
    return x.reshape(x.shape[0], -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _core(x, B, C, rows, cols, skip, interpret):
    return _core_fwd(x, B, C, rows, cols, skip, interpret)[0]


def _core_fwd(x, B, C, rows, cols, skip, interpret):
    with jax.named_scope(SCOPE):
        y, starts = _forward(
            _flat(x), _flat(B), _flat(C), rows, cols, skip, x.shape[2], interpret)
        y = checkpoint_name(y.reshape(x.shape), KEPT)
        starts = checkpoint_name(starts, KEPT)
    return y, (x, B, C, rows, cols, skip, starts)


def _core_bwd(interpret, kept, dy):
    x, B, C, rows, cols, skip, starts = kept
    with jax.named_scope(SCOPE):
        dx, db, dc, drows, dcols, dskip = _backward(
            _flat(x), _flat(B), _flat(C), rows, cols, skip, starts, _flat(dy),
            x.shape[2], interpret)
        return (dx.reshape(x.shape), db.reshape(B.shape), dc.reshape(C.shape),
                drows, dcols, jnp.sum(dskip, axis=1, keepdims=True))


_core.defvjp(_core_fwd, _core_bwd)


def _scalars(dt, A, groups, chunk):
    """``dt [T, H]``, ``A [H]`` -> a chunk's per-head scalars as the kernels
    read them (module docstring): ``rows [G, chunks, 2 R, C]`` and ``cols [G,
    chunks, C, 4 R]``, float32. Heads first and time in the lanes ``[G, R,
    chunks, C]`` from ONE transpose on, so that every pass but the two that
    lay the results out runs on whole tiles (with the heads minor a vector
    register holds 8 of its 128 lanes)."""
    t, heads = dt.shape
    r = heads // groups
    dt = dt.T.reshape(groups, r, t // chunk, chunk)
    # L, the running sum as ONE float32 product with a triangle of ones (exact
    # factors, float32 sums): a ``cumsum`` is a window reduction on a TPU,
    # 0.7 ms for these 2 MB where the product takes 0.08
    upto = jnp.triu(jnp.ones((chunk, chunk), jnp.float32))
    run = jnp.einsum("grns,st->grnt", dt * A.reshape(groups, r, 1, 1), upto,
                     precision=jax.lax.Precision.HIGHEST)
    last = run[..., -1:]
    rows = jnp.concatenate(
        [run, jnp.broadcast_to(jnp.exp(last), run.shape)], axis=1)
    cols = jnp.concatenate(
        [dt, run, jnp.exp(run), jnp.exp(last - run)], axis=1)
    return rows.transpose(0, 2, 1, 3), cols.transpose(0, 2, 3, 1)


def selective_scan(x, dt, A, B, C, D, chunk, interpret: Optional[bool] = None):
    """The selective scan of one sequence, the function
    ``fedtpu.models.mamba2.selective_scan`` is, at the shapes
    :func:`takes` admits."""
    if not _fits(x, dt, A, B, C, D, chunk):
        raise ValueError(
            f"the kernels take a group's heads and the state in whole lanes, "
            f"heads of a part of a lane group and a chunk of {CHUNK} tokens "
            f"that divides the length, not chunk={chunk} on "
            f"{[jnp.shape(a) for a in (x, dt, A, B, C, D)]}")
    groups, p = B.shape[1], x.shape[2]
    with jax.named_scope(SCOPE):
        # 4 MB a layer a row: kept, a rematerialised layer lays them out once
        rows, cols = (checkpoint_name(a, KEPT)
                      for a in _scalars(dt, A, groups, chunk))
        skip = jnp.repeat(D, p).reshape(groups, 1, -1)
    return _core(x, B, C, rows, cols, skip, _mode(interpret) == "interpret")
