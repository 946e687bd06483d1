"""The causal core of latent attention as two fused TPU kernels: scores,
softmax and ``P v`` of one sequence, forward and backward, with a block's
float32 scores in VMEM only.

What the plain body (``fedtpu.models.lm_layers.causal_attention``)
computes, in the same arithmetic: operands of the inputs' dtype into every
product, float32 accumulation, the scale, mask, maximum, exponential and sums
in float32, ``P`` (and ``dS``) cast to the inputs' dtype before the products
that read them. Queries and keys come in two parts, ``nope`` (a head's own)
and ``rope`` (the rotary part: the key's ``[T, rope]`` is every head's), and
enter the scores as two products; values keep their own width.

Forward, grid ``(heads, block pairs)``: the pairs ``(i, j <= i)`` of query
and key blocks on and under the diagonal are listed in two prefetched index
vectors, so a key block above the diagonal is neither fetched nor computed;
the mask is applied in the diagonal block alone. Running maximum, sum and
output live in VMEM across a query block's pairs; what is written is the
output ``[heads, T, v]`` and the row log-sum-exp ``[heads, 1, T]`` in float32.

Backward, one kernel (``jax.custom_vjp``), pairs ordered by key block: a
pair's scores are made again transposed (``[keys, queries]``, so the row
statistics broadcast along sublanes), ``dP`` and ``dS`` formed, and five
products accumulate in float32: ``dv`` and ``dk`` in a key block's scratch,
``dq`` in a whole head's ``[T, .]`` scratch (3 MB at 4,096 tokens), each
written once in the inputs' dtype. ``dk_rope`` leaves a head at a time in
float32 and is summed over heads outside. Nothing of ``heads x q x k`` size
reaches HBM in either direction.

Which body runs: :func:`takes` says whether this module does — on a TPU
backend, for a length the blocks divide; the plain body everywhere else
(``interpret`` as in :mod:`fedtpu.ops.pallas_kernels`: ``None`` decides by
backend, ``False`` forces Mosaic for a deviceless compile, a true value
interprets, for the CPU tests). Forward and backward both run under
``jax.named_scope(SCOPE)``, the backward rule naming it itself, and the
forward's output and log-sum-exp are named ``KEPT`` for a rematerialised
block's policy, so its backward pass does not run the forward kernel again.
"""

from __future__ import annotations

import functools
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
# Scores of masked pairs: finite, so that exp(masked - maximum) is 0 and never
# exp(-inf + inf).
_MASKED = -0.7 * float(np.finfo(np.float32).max)
# Whole-head dq scratch and outputs plus a pair's temporaries pass the 16 MiB
# a kernel gets by default; a v5e has 128 MiB.
_VMEM_LIMIT = 96 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _fits(q_nope, q_rope, v) -> bool:
    """Shapes the kernels are built for: queries ``[T, H, .]`` with a key and
    value head each and a separate rotary operand (a key head that serves a
    group of query heads, or no rotary operand, is the plain body's), a length
    the blocks divide, head parts of whole lanes (the rotary part of half
    lanes)."""
    return (q_rope is not None and q_nope.ndim == 3
            and v.shape[1] == q_nope.shape[1]
            and q_nope.shape[0] % BLOCK == 0
            and q_nope.shape[-1] % _LANES == 0 and v.shape[-1] % _LANES == 0
            and q_rope.shape[-1] % (_LANES // 2) == 0)


def takes(q_nope, q_rope, v, interpret: Optional[bool] = None) -> bool:
    """Whether a sequence ``[T, H, .]`` goes through the kernels: on a TPU
    (or where ``interpret`` says so), at shapes they are built for."""
    return _mode(interpret) != "xla" and _fits(q_nope, q_rope, v)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


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


# Blocks of the operands at grid point (head, pair n), the pair's query and
# key block read from the prefetched vectors.
def _by_q(width):
    return pl.BlockSpec((None, BLOCK, width), lambda h, n, qi, kj: (h, qi[n], 0))


def _by_k(width):
    return pl.BlockSpec((None, BLOCK, width), lambda h, n, qi, kj: (h, kj[n], 0))


def _shared_by_k(width):  # k_rope [T, .]: every head's
    return pl.BlockSpec((BLOCK, width), lambda h, n, qi, kj: (kj[n], 0))


def _row_by_q():  # a statistic [H, 1, T]
    return pl.BlockSpec((None, 1, BLOCK), lambda h, n, qi, kj: (h, 0, qi[n]))


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


def _fwd_kernel(qi_ref, kj_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                o_ref, lse_ref, m_ref, l_ref, acc_ref, *, scale):
    n = pl.program_id(1)
    i, j = qi_ref[n], kj_ref[n]
    block = qn_ref.shape[0]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def pair(diagonal):
        s = (_dot(qn_ref[...], kn_ref[...], _NT)
             + _dot(qr_ref[...], kr_ref[...], _NT)) * scale
        if diagonal:
            q_at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_at <= q_at, s, _MASKED)
        m_prev, l_prev = m_ref[...], l_ref[...]  # [block, lanes], replicated
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - jnp.tile(m_next, (1, block // _LANES)))
        alpha = jnp.exp(m_prev - m_next)
        m_ref[...] = m_next
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (
            jnp.tile(alpha, (1, acc_ref.shape[-1] // _LANES)) * acc_ref[...]
            + _dot(p.astype(v_ref.dtype), v_ref[...]))

    @pl.when(j < i)
    def _():
        pair(False)

    @pl.when(j == i)
    def _():
        pair(True)
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.tile(
            l, (1, acc_ref.shape[-1] // _LANES))).astype(o_ref.dtype)
        lse_ref[...] = (m_ref[...] + jnp.log(l)).T[:1]


def _forward(q_nope, q_rope, k_nope, k_rope, v, scale, interpret):
    """``[H, T, .]`` operands (``k_rope [T, .]``) -> output ``[H, T, v]`` and
    log-sum-exp ``[H, 1, T]``."""
    h, t, nope = q_nope.shape
    rope, vd = q_rope.shape[-1], v.shape[-1]
    return _call(
        _fwd_kernel, "latent_attention_core_fwd",
        _pairs(t // BLOCK, by_key=False), h, scale, interpret,
        in_specs=[_by_q(nope), _by_q(rope), _by_k(nope), _shared_by_k(rope),
                  _by_k(vd)],
        out_specs=[_by_q(vd), _row_by_q()],
        scratch_shapes=[
            pltpu.VMEM((BLOCK, _LANES), jnp.float32),
            pltpu.VMEM((BLOCK, _LANES), jnp.float32),
            pltpu.VMEM((BLOCK, vd), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, t, vd), v.dtype),
            jax.ShapeDtypeStruct((h, 1, t), jnp.float32),
        ],
    )(q_nope, q_rope, k_nope, k_rope, v)


def _bwd_kernel(qi_ref, kj_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                lse_ref, delta_ref, dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
                dqn_acc, dqr_acc, dkn_acc, dkr_acc, dv_acc, *, scale):
    n = pl.program_id(1)
    i, j = qi_ref[n], kj_ref[n]
    block = qn_ref.shape[0]
    dtype = qn_ref.dtype

    @pl.when(n == 0)
    def _():
        dqn_acc[...] = jnp.zeros_like(dqn_acc)
        dqr_acc[...] = jnp.zeros_like(dqr_acc)

    @pl.when(i == j)
    def _():
        dkn_acc[...] = jnp.zeros_like(dkn_acc)
        dkr_acc[...] = jnp.zeros_like(dkr_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def pair(diagonal):
        # Everything [keys, queries]: the queries' statistics are rows.
        s = (_dot(kn_ref[...], qn_ref[...], _NT)
             + _dot(kr_ref[...], qr_ref[...], _NT)) * scale
        if diagonal:
            k_at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            q_at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_at <= q_at, s, _MASKED)
        p = jnp.exp(s - lse_ref[...])
        do = do_ref[...]
        dv_acc[...] += _dot(p.astype(dtype), do)
        dp = _dot(v_ref[...], do, _NT)
        ds = p * (dp - delta_ref[...]) * scale
        ds_kq = ds.astype(dtype)
        dkn_acc[...] += _dot(ds_kq, qn_ref[...])
        dkr_acc[...] += _dot(ds_kq, qr_ref[...])
        ds_qk = ds.T.astype(dtype)
        rows = pl.ds(pl.multiple_of(i * block, block), block)
        dqn_acc[rows, :] += _dot(ds_qk, kn_ref[...])
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
        dkr_ref[...] = dkr_acc[...]
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        dqn_ref[...] = dqn_acc[...].astype(dqn_ref.dtype)
        dqr_ref[...] = dqr_acc[...].astype(dqr_ref.dtype)


def _backward(q_nope, q_rope, k_nope, k_rope, v, do, lse, delta, scale,
              interpret):
    """``[H, T, .]`` operands, ``lse`` and ``delta [H, 1, T]`` -> ``dq_nope,
    dq_rope, dk_nope, dv`` in the operands' dtype and each head's ``dk_rope
    [H, T, .]`` in float32."""
    h, t, nope = q_nope.shape
    rope, vd = q_rope.shape[-1], v.shape[-1]
    whole = lambda width: pl.BlockSpec(
        (None, t, width), lambda h, n, qi, kj: (h, 0, 0))
    f32 = jnp.float32
    return _call(
        _bwd_kernel, "latent_attention_core_bwd",
        _pairs(t // BLOCK, by_key=True), h, scale, interpret,
        in_specs=[_by_q(nope), _by_q(rope), _by_k(nope), _shared_by_k(rope),
                  _by_k(vd), _by_q(vd), _row_by_q(), _row_by_q()],
        out_specs=[whole(nope), whole(rope), _by_k(nope), _by_k(rope), _by_k(vd)],
        scratch_shapes=[
            pltpu.VMEM((t, nope), f32), pltpu.VMEM((t, rope), f32),
            pltpu.VMEM((BLOCK, nope), f32), pltpu.VMEM((BLOCK, rope), f32),
            pltpu.VMEM((BLOCK, vd), f32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q_nope.shape, q_nope.dtype),
            jax.ShapeDtypeStruct(q_rope.shape, q_rope.dtype),
            jax.ShapeDtypeStruct(k_nope.shape, k_nope.dtype),
            jax.ShapeDtypeStruct((h, t, rope), f32),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
    )(q_nope, q_rope, k_nope, k_rope, v, do, lse, delta)


def _heads_first(x):
    return x.transpose(1, 0, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _core(q_nope, q_rope, k_nope, k_rope, v, scale, interpret):
    return _core_fwd(q_nope, q_rope, k_nope, k_rope, v, scale, interpret)[0]


def _core_fwd(q_nope, q_rope, k_nope, k_rope, v, scale, interpret):
    with jax.named_scope(SCOPE):
        o, lse = _forward(
            _heads_first(q_nope), _heads_first(q_rope), _heads_first(k_nope),
            k_rope, _heads_first(v), scale, interpret)
        o = checkpoint_name(_heads_first(o), KEPT)
        lse = checkpoint_name(lse, KEPT)
    return o, (q_nope, q_rope, k_nope, k_rope, v, o, lse)


def _core_bwd(scale, interpret, kept, do):
    q_nope, q_rope, k_nope, k_rope, v, o, lse = kept
    with jax.named_scope(SCOPE):
        delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
        dq_nope, dq_rope, dk_nope, dk_rope, dv = _backward(
            _heads_first(q_nope), _heads_first(q_rope), _heads_first(k_nope),
            k_rope, _heads_first(v), _heads_first(do), lse,
            delta.T[:, None, :], scale, interpret)
        return (_heads_first(dq_nope), _heads_first(dq_rope),
                _heads_first(dk_nope),
                jnp.sum(dk_rope, axis=0).astype(k_rope.dtype), _heads_first(dv))


_core.defvjp(_core_fwd, _core_bwd)


def causal_attention(q_nope, q_rope, k_nope, k_rope, v, scale,
                     interpret: Optional[bool] = None):
    """Causal attention of one sequence ``[T, H, .]`` (``k_rope [T, .]``),
    the function ``fedtpu.models.lm_layers.causal_attention`` is, at
    the shapes :func:`takes` admits."""
    if not _fits(q_nope, q_rope, v):
        raise ValueError(
            f"the kernels take a length that is a multiple of {BLOCK} and head "
            f"parts of whole lanes, not {q_nope.shape}, {q_rope.shape}, {v.shape}")
    return _core(q_nope, q_rope, k_nope, k_rope, v, float(scale),
                 _mode(interpret) == "interpret")
