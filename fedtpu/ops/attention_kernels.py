"""The causal core of softmax attention as two fused TPU kernels: scores,
softmax and ``P v`` of one sequence, forward and backward, with a block's
float32 scores in VMEM only.

What the plain body (``fedtpu.models.lm_layers.causal_attention``)
computes, in the same arithmetic: operands of the inputs' dtype into every
product, float32 accumulation, the scale, mask, maximum, exponential and sums
in float32, ``P`` (and ``dS``) cast to the inputs' dtype before the products
that read them. The shapes say which form runs, one pair body for all of
them. A key and value head ``[T, KH, .]`` serves a group of ``G`` query heads
``[T, KH, G, .]`` (``[T, H, .]``: a key head each): the grid runs over the
``KH * G`` query heads and reads a head's key and value blocks at ``head //
G``. Queries and keys may come in two parts, ``nope`` (a head's own) and
``rope`` (the rotary part: the key's ``[T, rope]`` is every head's), which
enter the scores as two products; without the rotary operands there is the
one product. Values keep their own width. Head parts are whole lane groups
(128, 256), the rotary part a half, or, without rotary operands, queries, keys
and values all of HALF a lane group (64: ``lfm2_moe``).

At a width of 64 a grid step takes up to ``_STACK`` = 4 query heads of one key
head's group, their query blocks stacked along the step's rows (the grid is
``(KH * G / 4, block pairs)``; :func:`_heads_first` lays the queries out so):
the pair body is the same and sees a query block of 2,048 rows against ONE
fetched key and value block of 512, the mask reads a row's position modulo the
block, and ``dk`` / ``dv`` leave the kernel summed over the stacked heads by
the products' own contraction, in the operands' dtype where the step holds the
whole group. Why: at this width the MXU's passes bind, not the softmax (a
128-wide pass is spent on 64 contracted or 64 written columns whatever is
done: the score products contract over 64 and cannot be packed, and two heads
side by side in the lanes of a 64-wide OUTPUT need two passes all the same),
so what is left to save is a grid step's fixed cost and the key blocks' fetch.
On one row of LFM2's layer (``[4096, 8, 4, 64]`` bfloat16, a v5e; PERF.md §6,
PR 43), forward + backward kernel, ms: one head a step in 64-wide blocks as
they are 1.19 + 2.54, two heads 1.13 + 2.27, **four 1.105 + 2.137** (the plain
query blocks: 16.3); with the relayouts around them 4.23 / 3.90 / **3.71**.
``dS^T`` through a transposed-operand product or a bfloat16 transpose instead
of the float32 one: 3.81 either way against 3.82, not taken. Two heads side by
side in the lanes was reckoned and not built: it saves no pass, only
half-empty accumulator registers (a sixteenth of a pair's vector work).

Forward, grid ``(query heads, block pairs)``: the pairs ``(i, j <= i)`` of
query and key blocks on and under the diagonal are listed in two prefetched
index vectors, so a key block above the diagonal is neither fetched nor
computed; the mask is applied in the diagonal block alone. Running maximum,
sum and output live in VMEM across a query block's pairs; what is written is
the output ``[heads, T, v]`` and the row log-sum-exp ``[heads, 1, T]`` in
float32.

Backward, one kernel (``jax.custom_vjp``), pairs ordered by key block: a
pair's scores are made again transposed (``[keys, queries]``, so the row
statistics broadcast along sublanes), ``dP`` and ``dS`` formed, and the
products accumulate in float32: ``dv`` and ``dk`` in a key block's scratch,
``dq`` in a whole head's ``[T, .]`` scratch (3 MB at 4,096 tokens of 128 +
64, 8 MiB at 8,192 of 256: one head at a time; 8 MiB for four stacked heads
of 64 at 4,096, padded to the lanes), each written once. A grid step that has
its key head to itself writes ``dk`` and ``dv`` in the inputs' dtype; the
steps that share one write theirs in float32 (as every head its ``dk_rope``)
and their sum is taken outside. Nothing of ``heads x q x k`` size reaches HBM
in either direction.

Which body runs: :func:`takes` says whether this module does — on a TPU
backend, at the shapes :func:`_fits` lists; the plain body everywhere else
(``interpret`` as in :mod:`fedtpu.ops.pallas_kernels`: ``None`` decides by
backend, ``False`` forces Mosaic for a deviceless compile, a true value
interprets, for the CPU tests). Forward and backward both run under
``jax.named_scope(SCOPE)``, the heads-first relayouts around the kernels
too, the backward rule naming it itself, and the forward's output and
log-sum-exp are named ``KEPT`` for a rematerialised block's policy, so its
backward pass does not run the forward kernel again.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedtpu.ops.pallas_kernels import _mode

SCOPE = "fed.local_step.fwd_bwd.attention.core"
KEPT = "attention_core_out"

# Query and key block, chosen on the chip for heads of 128 + 64 and 128
# (PERF.md, PR 35): float32 scores of BLOCK x BLOCK a pair.
BLOCK = 512
_LANES = 128
# Query heads narrower than a lane group go through a grid step this many at a
# time, their blocks stacked along the step's rows (module docstring).
_STACK = 4
# Scores of masked pairs: finite, so that exp(masked - maximum) is 0 and never
# exp(-inf + inf).
_MASKED = -0.7 * float(np.finfo(np.float32).max)
# Whole-head dq scratch and outputs plus a pair's temporaries pass the 16 MiB
# a kernel gets by default; a v5e has 128 MiB.
_VMEM_LIMIT = 96 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _fits(q_nope, q_rope, k_nope, k_rope, v, window=None) -> bool:
    """Calls the kernels are built for: the whole causal prefix (no
    ``window``: the block pairs listed are the causal half, and a banded call
    would get another function's answer); queries ``[T, KH, G, d]`` (or ``[T,
    H, d]``: ``G = 1``) on keys and values ``[T, KH, .]``, a key head serving
    its ``G`` query heads; the rotary operands both there (``q_rope`` shaped
    as the queries, ``k_rope [T, .]`` every head's) or both ``None``; a length
    the blocks divide; head parts of whole lanes (the rotary part of half
    lanes) or, without rotary operands, queries, keys and values of half a
    lane group, 64, whose heads go through a grid step stacked
    (:func:`_stacked`, module docstring). Everything else is the plain body's:
    a window, narrower heads, other fractions of a lane group, a half-lane
    head beside rotary operands or beside values of another width."""
    rotary = q_rope is not None
    widths = (q_nope.shape[-1], v.shape[-1])
    return (window is None and q_nope.ndim in (3, 4) and k_nope.ndim == v.ndim == 3
            and k_nope.shape[1] == v.shape[1] == q_nope.shape[1]
            and rotary == (k_rope is not None)
            and q_nope.shape[0] % BLOCK == 0
            and (all(w % _LANES == 0 for w in widths)
                 or (not rotary and widths == (_LANES // 2, _LANES // 2)))
            and (not rotary or (q_rope.shape[:-1] == q_nope.shape[:-1]
                                and q_rope.shape[-1] % (_LANES // 2) == 0)))


def _stacked(q_nope) -> int:
    """How many query heads of ``q_nope [T, KH, G, d]`` (``[T, H, d]``) one
    grid step takes: one at a width of whole lanes, ``_STACK`` of a key head's
    group (as many as the group allows) at a narrower one."""
    group = q_nope.shape[2] if q_nope.ndim == 4 else 1
    return 1 if q_nope.shape[-1] % _LANES == 0 else math.gcd(group, _STACK)


def takes(q_nope, q_rope, k_nope, k_rope, v,
          interpret: Optional[bool] = None, window: Optional[int] = None) -> bool:
    """Whether a sequence goes through the kernels: on a TPU (or where
    ``interpret`` says so), at shapes they are built for, over the whole
    causal prefix (a ``window`` is the plain body's)."""
    return _mode(interpret) != "xla" and _fits(
        q_nope, q_rope, k_nope, k_rope, v, window)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _scores(a_nope, a_rope, b_nope, b_rope, scale):
    """``a b^T`` of the blocks in the refs, the rotary parts' product added
    where the operands have rotary parts."""
    s = _dot(a_nope[...], b_nope[...], _NT)
    if a_rope is not None:
        s = s + _dot(a_rope[...], b_rope[...], _NT)
    return s * scale


def _pairs(blocks: int, by_key: bool):
    """Block pairs ``(i, j <= i)`` as two int32 vectors ``(query blocks, key
    blocks)``: by query block with its keys ascending (the diagonal last), or
    by key block with its queries ascending (the diagonal first)."""
    if by_key:
        pairs = [(i, j) for j in range(blocks) for i in range(j, blocks)]
    else:
        pairs = [(i, j) for i in range(blocks) for j in range(i + 1)]
    qi, kj = zip(*pairs)
    return jnp.asarray(qi, jnp.int32), jnp.asarray(kj, jnp.int32)


# Blocks of the operands at grid point (query head h, pair n), the pair's
# query and key block read from the prefetched vectors.
def _by_q(width, stack=1):
    return pl.BlockSpec(
        (None, stack * BLOCK, width), lambda h, n, qi, kj: (h, qi[n], 0))


def _by_k(width, group=1):
    """The pair's key block of ``[KH, T, .]``, read at the key head that
    serves query head ``h`` (``group=1``: of a query head's own ``[H, T,
    .]``, and no division in its index map)."""
    if group == 1:
        return pl.BlockSpec((None, BLOCK, width), lambda h, n, qi, kj: (h, kj[n], 0))
    return pl.BlockSpec(
        (None, BLOCK, width), lambda h, n, qi, kj: (h // group, kj[n], 0))


def _shared_by_k(width):  # k_rope [T, .]: every head's
    return pl.BlockSpec((BLOCK, width), lambda h, n, qi, kj: (kj[n], 0))


def _row_by_q(stack=1):  # a statistic [H, 1, T]
    return pl.BlockSpec(
        (None, 1, stack * BLOCK), lambda h, n, qi, kj: (h, 0, qi[n]))


def _there(*xs):
    return [x for x in xs if x is not None]


def _across(x, width):
    """A row statistic ``[rows, lanes]`` (replicated along the lanes) as wide
    as a block of ``width``."""
    if width % _LANES:
        return x[:, :width]
    return jnp.tile(x, (1, width // _LANES))


def _at(shape, axis, keys):
    """Positions within a block of the queries along ``axis`` of a diagonal
    pair's scores: stacked heads' blocks each start at 0 again."""
    at = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return at if shape[axis] == keys else jax.lax.rem(at, keys)


def _named(refs, *there):
    """A kernel's refs in the order of its names: ``None`` under the name of
    an operand that is not there."""
    refs = iter(refs)
    return [next(refs) if t else None for t in there]


def _call(kernel, name, pairs, heads, scale, interpret, out_shape, **grid_spec):
    """``kernel`` over the grid ``(heads, pairs)``, the pairs prefetched."""
    qi, kj = pairs
    return functools.partial(
        pl.pallas_call(
            functools.partial(kernel, scale=scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(heads, qi.shape[0]), **grid_spec),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            name=name,
        ), qi, kj)


def _fwd_kernel(qi_ref, kj_ref, *refs, scale, rotary):
    (qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
     acc_ref) = _named(refs, 1, rotary, 1, rotary, 1, 1, 1, 1, 1, 1)
    n = pl.program_id(1)
    i, j = qi_ref[n], kj_ref[n]
    keys, vd = kn_ref.shape[0], acc_ref.shape[-1]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def pair(diagonal):
        s = _scores(qn_ref, qr_ref, kn_ref, kr_ref, scale)
        if diagonal:
            q_at = _at(s.shape, 0, keys)
            k_at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_at <= q_at, s, _MASKED)
        m_prev, l_prev = m_ref[...], l_ref[...]  # [rows, lanes], replicated
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _across(m_next, keys))
        alpha = jnp.exp(m_prev - m_next)
        m_ref[...] = m_next
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (
            _across(alpha, vd) * acc_ref[...]
            + _dot(p.astype(v_ref.dtype), v_ref[...]))

    @pl.when(j < i)
    def _():
        pair(False)

    @pl.when(j == i)
    def _():
        pair(True)
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / _across(l, vd)).astype(o_ref.dtype)
        lse_ref[...] = (m_ref[...] + jnp.log(l)).T[:1]


def _forward(q_nope, q_rope, k_nope, k_rope, v, scale, interpret):
    """Queries ``[H, T, .]`` on keys and values ``[KH, T, .]`` (``k_rope [T,
    .]``; the rotary operands may be ``None``) -> output ``[H, T, v]`` and
    log-sum-exp ``[H, 1, T]`` in float32. Stacked queries ``[H / S, S T, .]``
    (:func:`_heads_first`) give both in their own order of rows."""
    (h, rows, nope), (kh, t, vd) = q_nope.shape, v.shape
    stack, group = rows // t, h // kh
    rotary = q_rope is not None
    if_rotary = lambda make: make(q_rope.shape[-1]) if rotary else None
    return _call(
        functools.partial(_fwd_kernel, rotary=rotary), "latent_attention_core_fwd",
        _pairs(t // BLOCK, by_key=False), h, scale, interpret,
        in_specs=_there(
            _by_q(nope, stack), if_rotary(_by_q), _by_k(nope, group),
            if_rotary(_shared_by_k), _by_k(vd, group)),
        out_specs=[_by_q(vd, stack), _row_by_q(stack)],
        scratch_shapes=[
            pltpu.VMEM((stack * BLOCK, _LANES), jnp.float32),
            pltpu.VMEM((stack * BLOCK, _LANES), jnp.float32),
            pltpu.VMEM((stack * BLOCK, vd), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, rows, vd), v.dtype),
            jax.ShapeDtypeStruct((h, 1, rows), jnp.float32),
        ],
    )(*_there(q_nope, q_rope, k_nope, k_rope, v))


def _bwd_kernel(qi_ref, kj_ref, *refs, scale, rotary):
    (qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref, delta_ref,
     dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
     dqn_acc, dqr_acc, dkn_acc, dkr_acc, dv_acc) = _named(
        refs, 1, rotary, 1, rotary, 1, 1, 1, 1,
        1, rotary, 1, rotary, 1, 1, rotary, 1, rotary, 1)
    n = pl.program_id(1)
    i, j = qi_ref[n], kj_ref[n]
    block = qn_ref.shape[0]
    dtype = qn_ref.dtype

    @pl.when(n == 0)
    def _():
        for acc in _there(dqn_acc, dqr_acc):
            acc[...] = jnp.zeros_like(acc)

    @pl.when(i == j)
    def _():
        for acc in _there(dkn_acc, dkr_acc, dv_acc):
            acc[...] = jnp.zeros_like(acc)

    def pair(diagonal):
        # Everything [keys, queries]: the queries' statistics are rows.
        s = _scores(kn_ref, kr_ref, qn_ref, qr_ref, scale)
        if diagonal:
            k_at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            q_at = _at(s.shape, 1, kn_ref.shape[0])
            s = jnp.where(k_at <= q_at, s, _MASKED)
        p = jnp.exp(s - lse_ref[...])
        do = do_ref[...]
        dv_acc[...] += _dot(p.astype(dtype), do)
        dp = _dot(v_ref[...], do, _NT)
        ds = p * (dp - delta_ref[...]) * scale
        ds_kq = ds.astype(dtype)
        dkn_acc[...] += _dot(ds_kq, qn_ref[...])
        if rotary:
            dkr_acc[...] += _dot(ds_kq, qr_ref[...])
        ds_qk = ds.T.astype(dtype)
        rows = pl.ds(pl.multiple_of(i * block, block), block)
        dqn_acc[rows, :] += _dot(ds_qk, kn_ref[...])
        if rotary:
            dqr_acc[rows, :] += _dot(ds_qk, kr_ref[...])

    @pl.when(i == j)
    def _():
        pair(True)

    @pl.when(i > j)
    def _():
        pair(False)

    @pl.when(i == dqn_acc.shape[0] // block - 1)
    def _():
        dkn_ref[...] = dkn_acc[...].astype(dkn_ref.dtype)
        if rotary:
            dkr_ref[...] = dkr_acc[...]
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        dqn_ref[...] = dqn_acc[...].astype(dqn_ref.dtype)
        if rotary:
            dqr_ref[...] = dqr_acc[...].astype(dqr_ref.dtype)


def _backward(q_nope, q_rope, k_nope, k_rope, v, do, lse, delta, scale,
              interpret):
    """Operands as :func:`_forward` takes them, ``do [H, T, v]``, ``lse`` and
    ``delta [H, 1, T]`` -> ``dq_nope, dq_rope, dk_nope, dk_rope, dv``, each
    what ONE query head gives: ``dq`` in the operands' dtype; ``dk_rope [H,
    T, .]`` in float32 (both ``None`` without rotary operands); ``dk_nope``
    and ``dv [H, T, .]`` in the operands' dtype where a key head has one query
    head, else in float32, for the sum over its group."""
    (h, rows, nope), (kh, t, vd) = q_nope.shape, v.shape
    stack, group = rows // t, h // kh
    rotary = q_rope is not None
    if_rotary = lambda make: make(q_rope.shape[-1]) if rotary else None
    f32 = jnp.float32
    whole = lambda width: pl.BlockSpec(
        (None, rows, width), lambda h, n, qi, kj: (h, 0, 0))
    of_a_head = lambda like: jax.ShapeDtypeStruct(
        (h, t, like.shape[-1]), like.dtype if group == 1 else f32)
    return _named(_call(
        functools.partial(_bwd_kernel, rotary=rotary), "latent_attention_core_bwd",
        _pairs(t // BLOCK, by_key=True), h, scale, interpret,
        in_specs=_there(
            _by_q(nope, stack), if_rotary(_by_q), _by_k(nope, group),
            if_rotary(_shared_by_k), _by_k(vd, group), _by_q(vd, stack),
            _row_by_q(stack), _row_by_q(stack)),
        out_specs=_there(
            whole(nope), if_rotary(whole), _by_k(nope), if_rotary(_by_k),
            _by_k(vd)),
        scratch_shapes=_there(
            pltpu.VMEM((rows, nope), f32),
            if_rotary(lambda rope: pltpu.VMEM((rows, rope), f32)),
            pltpu.VMEM((BLOCK, nope), f32),
            if_rotary(lambda rope: pltpu.VMEM((BLOCK, rope), f32)),
            pltpu.VMEM((BLOCK, vd), f32)),
        out_shape=_there(
            jax.ShapeDtypeStruct(q_nope.shape, q_nope.dtype),
            if_rotary(lambda _: jax.ShapeDtypeStruct(q_rope.shape, q_rope.dtype)),
            of_a_head(k_nope),
            if_rotary(lambda rope: jax.ShapeDtypeStruct((h, t, rope), f32)),
            of_a_head(v)),
    )(*_there(q_nope, q_rope, k_nope, k_rope, v, do, lse, delta)),
        1, rotary, 1, rotary, 1)


def _heads_first(x, stack=1):
    """``[T, KH, G, .]`` or ``[T, H, .]`` -> ``[H, T, .]``, query head ``kh *
    G + g`` of key head ``kh``; with ``stack = S`` heads a grid step ``[H / S,
    S T, .]``: a step's heads' blocks ``i`` lie one under the other, head ``s``
    of them in rows ``(i S + s) BLOCK`` on."""
    if stack == 1:
        x = jnp.moveaxis(x, 0, -2)
        return x.reshape((-1,) + x.shape[-2:])
    x = x.reshape(x.shape[0] // BLOCK, BLOCK, -1, stack, x.shape[-1])
    x = x.transpose(2, 0, 3, 1, 4)
    return x.reshape(x.shape[0], -1, x.shape[-1])


def _heads_last(x, like, stack=1):
    """:func:`_heads_first`'s rows -> the layout of ``like [T, ..., .]``."""
    if stack == 1:
        return jnp.moveaxis(x.reshape(like.shape[1:-1] + x.shape[-2:]), -2, 0)
    x = x.reshape(x.shape[0], -1, stack, BLOCK, x.shape[-1])
    return x.transpose(1, 3, 0, 2, 4).reshape(like.shape[:-1] + x.shape[-1:])


def _if_there(f, x, *args):
    return None if x is None else f(x, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _core(q_nope, q_rope, k_nope, k_rope, v, scale, interpret):
    return _core_fwd(q_nope, q_rope, k_nope, k_rope, v, scale, interpret)[0]


def _core_fwd(q_nope, q_rope, k_nope, k_rope, v, scale, interpret):
    stack = _stacked(q_nope)
    with jax.named_scope(SCOPE):
        o, lse = _forward(
            _heads_first(q_nope, stack), _if_there(_heads_first, q_rope),
            _heads_first(k_nope), k_rope, _heads_first(v), scale, interpret)
        o = checkpoint_name(_heads_last(o, q_nope, stack), KEPT)
        lse = checkpoint_name(lse, KEPT)
    return o, (q_nope, q_rope, k_nope, k_rope, v, o, lse)


def _core_bwd(scale, interpret, kept, do):
    q_nope, q_rope, k_nope, k_rope, v, o, lse = kept
    stack = _stacked(q_nope)
    with jax.named_scope(SCOPE):
        delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)

        def as_lse(d):  # [T, heads...] -> [H, 1, T], or the stacked rows' order
            if stack == 1:
                return d.reshape(d.shape[0], -1).T[:, None, :]
            return _heads_first(d[..., None], stack).reshape(lse.shape)

        dq_nope, dq_rope, dk_nope, dk_rope, dv = _backward(
            _heads_first(q_nope, stack), _if_there(_heads_first, q_rope),
            _heads_first(k_nope), k_rope, _heads_first(v),
            _heads_first(do, stack), lse, as_lse(delta), scale, interpret)

        def of_key_heads(d, like):  # [H, T, .] -> like [T, KH, .]
            if d.shape[0] != like.shape[1]:  # a group's float32 parts
                d = jnp.sum(d.reshape((like.shape[1], -1) + d.shape[1:]), axis=1)
            return _heads_last(d.astype(like.dtype), like)

        return (_heads_last(dq_nope, q_nope, stack),
                _if_there(_heads_last, dq_rope, q_rope),
                of_key_heads(dk_nope, k_nope),
                _if_there(lambda d: jnp.sum(d, axis=0).astype(k_rope.dtype), dk_rope),
                of_key_heads(dv, v))


_core.defvjp(_core_fwd, _core_bwd)


def causal_attention(q_nope, q_rope, k_nope, k_rope, v, scale,
                     interpret: Optional[bool] = None):
    """Causal attention of one sequence, the function
    ``fedtpu.models.lm_layers.causal_attention`` is, at the shapes
    :func:`takes` admits."""
    if not _fits(q_nope, q_rope, k_nope, k_rope, v):
        shapes = [_if_there(jnp.shape, a) for a in (q_nope, q_rope, k_nope, k_rope, v)]
        raise ValueError(
            f"the kernels take a length that is a multiple of {BLOCK}, head "
            f"parts of whole lanes (or all of half a lane group) and the "
            f"rotary operands together or not at all, not {shapes}")
    return _core(q_nope, q_rope, k_nope, k_rope, v, float(scale),
                 _mode(interpret) == "interpret")
