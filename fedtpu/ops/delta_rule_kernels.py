"""The chunked gated delta rule as two fused TPU kernels, forward and backward
under one ``jax.custom_vjp``: a chunk's matrices, the inverse of its unit
triangular system and the running state stay in VMEM.

What the plain chunks (``fedtpu.models.qwen3_next._plain_chunks``) compute,
in the same arithmetic: operands of ``v``'s dtype into the products that take
them there, float32 accumulation; the gates, their running sums and
exponentials, the inverse and the products that apply it in float32 at
``Precision.HIGHEST``; the state between chunks in float32; every exponent a
difference ``G_i - G_j <= 0``.

Forward, grid ``(key heads / 2, chunks)``, the chunks in order: a step holds
one chunk ``C`` of two key heads (one where their number is odd) with their
``R`` value heads each, one head's code after the other in one block, so that
the compiler interleaves their chains of small products (four a step are 3 %
faster and twice the body to trace and lower in every process's set-up). From ``q, k [C, dk]``, ``v [C,
dv]`` and the rows ``G`` (the chunk's running sum of ``g``) and ``beta`` it
makes ``k k^T`` and ``q k^T`` once a key head and, a value head, ``A =
beta_i exp(G_i - G_j) k_i.k_j`` under the diagonal, ``T = (I + A)^-1``, ``[U~
| W] = T [beta V | beta exp(G) K]``, ``U = U~ - W S``, the output ``exp(G) Q S
+ (exp(G_i - G_j) q_i.k_j) U`` and ``S <- exp(G_C) S + (exp(G_C - G) K)^T U``
with ``S [dk, dv]`` in scratch across the chunk axis. Written to HBM: ``o``
and, in float32, what the backward pass reads instead of making it again:
each chunk's starting state (64 KB a value head a chunk) and ``T`` (16 KB;
two value heads' side by side in the lanes, ``[C, R C]``).

``T`` (:func:`_inverses`): the 16 x 16 diagonal blocks by forward substitution
on the VPU, every block of a key head's value heads side by side in the lanes
(fifteen steps of a lane gather, a product and a sublane sum on ``[16,
128]``), then merged twice by ``T <- T - T A_off T`` on the MXU (``A_off``:
the blocks under the diagonal that the doubled block takes in), two value
heads a product. Exact to float32 rounding whatever the keys: no power of
``A`` is formed.

Backward, the chunks in reverse with ``dS`` in scratch: a chunk's matrices
are made again from the operands, the saved state and the saved ``T``; with
``T`` explicit the solve's gradient is two products, ``dRhs = T^T [dU~ |
dW]`` and ``dA = -dRhs [U~ | W]^T`` under the diagonal. The value heads of a
key head add their parts of ``dq`` and ``dk`` inside the step. Written: ``dq,
dk, dv`` in the operands' dtype and ``dG, dbeta`` in float32.

The kernels read v and ``do`` and write o and ``dv`` as the model has them,
``[T, heads x width]``, a step's block its heads' columns; q, k, ``dq`` and
``dk`` heads-major ``[Hk, T, dk]``, the order the model states for q and k.
The gates go in and their gradients come out as ``[Hk, chunks, 2 R, C]``
(time in the lanes; a column is made from a row inside the kernel): the one
relayout, 1 MB a layer a row.

Which body runs: :func:`takes` says whether this module does, on a TPU
backend at whole-lane heads and a chunk of 16, 32, 64 or 128; the plain
chunks everywhere else (``interpret`` as in :mod:`fedtpu.ops.pallas_kernels`).
Both passes run under ``jax.named_scope(SCOPE)``, the backward rule naming it
itself; the output, the states and the inverses are named ``KEPT`` for a
rematerialised block's policy, so its backward pass does not run the forward
kernel again.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedtpu.ops.attention_kernels import KEPT
from fedtpu.ops.pallas_kernels import _mode

SCOPE = "fed.local_step.fwd_bwd.linear_attention.core"
_LANES = 128
_BASE = 16  # the diagonal blocks inverted by substitution
# An exponent above the diagonal: exp(_MASKED) is 0.
_MASKED = -1e30

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _fits(q, k, v, g, beta, chunk) -> bool:
    """Shapes the kernels are built for: ``q, k [T, Hk, dk]``, ``v [T, Hk, R,
    dv]``, ``g, beta [T, Hk, R]``; heads of whole lanes; a chunk of 16, 32, 64
    or 128 tokens (the inverse doubles its blocks from 16, and a chunk's
    matrix lies within the lanes) that divides the length."""
    return (q.ndim == 3 and v.ndim == 4 and q.shape == k.shape
            and v.shape[:2] == q.shape[:2]
            and g.shape == beta.shape == v.shape[:3]
            and q.shape[-1] % _LANES == 0 and v.shape[-1] % _LANES == 0
            and chunk in (_BASE, 2 * _BASE, 4 * _BASE, _LANES)
            and q.shape[0] % chunk == 0)


def takes(q, k, v, g, beta, chunk, interpret: Optional[bool] = None) -> bool:
    """Whether a sequence goes through the kernels: on a TPU (or where
    ``interpret`` says so), at shapes they are built for."""
    return _mode(interpret) != "xla" and _fits(q, k, v, g, beta, chunk)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _hdot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(
        a, b, dims, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _floor(x, n):
    """``x - x % n`` of a non-negative int32 grid, ``n`` a power of two: one
    operation where ``//`` and ``%`` trace to a dozen each, in a kernel
    body that is traced and lowered wherever a model calls it."""
    return x & -n


def _inverses(mats):
    """``(I + a)^-1`` of each ``a [C, C]`` float32 of ``mats``, zero on and
    above the diagonal (module docstring). ``s = 128 / C`` of them go through
    the steps side by side in the lanes ``[., s C]``; in the products the
    right factors lie down a block diagonal ``[s C, s C]``, so one product of
    six passes merges ``s`` of them."""
    c, base = mats[0].shape[0], _BASE
    side = max(1, _LANES // c)
    at_i = jax.lax.broadcasted_iota(jnp.int32, (c, side * c), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, side * c), 1)
    at_j, block = lane & (c - 1), _floor(lane, c)  # block: its first lane
    # of the diagonal blocks side by side [16, s C]
    row = jax.lax.broadcasted_iota(jnp.int32, (base, side * c), 0)
    at = jax.lax.broadcasted_iota(jnp.int32, (base, side * c), 1)

    def diagonal(x):  # [C, s C] -> its s blocks down a diagonal [s C, s C]
        if side == 1:
            return x
        return jnp.concatenate(
            [jnp.where(block == b * c, x, 0.0) for b in range(side)], axis=0)

    inverses = []
    for lo in range(0, len(mats), side):
        group = mats[lo:lo + side]
        group = group + [jnp.zeros_like(group[0])] * (side - len(group))
        # Forward substitution in every 16 x 16 diagonal block at once. Down
        # the sublanes the index a step sums over (a column of a block of A,
        # a row of its inverse); in the lanes (matrix, block, row) of A's
        # blocks and (matrix, block, column) of the inverses'. Step i makes
        # row i of every block's inverse.
        turned = [a.T for a in group]
        coefficients = jnp.concatenate(
            [a[b:b + base, b:b + base] for a in turned
             for b in range(0, c, base)], axis=1)
        x = (row == (at & (base - 1))).astype(jnp.float32)
        for i in range(1, base):
            of_row = jnp.take_along_axis(
                coefficients, _floor(at, base) + i, axis=1)
            x = jnp.where(row == i, x - jnp.sum(
                of_row * x, axis=0, keepdims=True), x)
        t = jnp.where(_floor(at_i, base) == _floor(at_j, base),
                      jnp.concatenate([x] * (c // base), axis=0), 0.0)
        a = jnp.concatenate(group, axis=1)
        size = base
        while size < c:
            taken_in = ((_floor(at_i, size) == _floor(at_j, size) + size)
                        & (_floor(at_i, 2 * size) == _floor(at_j, 2 * size)))
            t = t - _hdot(_hdot(t, diagonal(jnp.where(taken_in, a, 0.0))),
                          diagonal(t))
            size *= 2
        inverses.append(t)
    return inverses


def _key_head(q, k):
    """What a key head's value heads share of a chunk: the index grids, ``k
    k^T`` and ``q k^T``, the operands in float32."""
    c = q.shape[0]
    at_i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    at_j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return SimpleNamespace(
        q=q, k=k, qf=q.astype(jnp.float32), kf=k.astype(jnp.float32),
        at_i=at_i, at_j=at_j, eye=at_i == at_j,
        kk=_dot(k, k, _NT), qk=_dot(q, k, _NT))


def _col(row, eye):  # [1, C] -> [C, 1]
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):  # [C, 1] -> [1, C]
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _gated(head, gates_ref, h, r, values):
    """Value head ``r`` of key head ``h``'s gates over a chunk, from its rows
    ``G`` and ``beta [1, C]`` of the block: both down a column, ``exp(G_i -
    G_j)`` on and under the diagonal and ``A``."""
    run_row = gates_ref[h, r:r + 1, :]
    beta_row = gates_ref[h, values + r:values + r + 1, :]
    run, beta = _col(run_row, head.eye), _col(beta_row, head.eye)
    decay = jnp.exp(jnp.where(head.at_i >= head.at_j, run - run_row, _MASKED))
    return SimpleNamespace(
        run_row=run_row, run=run, beta=beta, decay=decay,
        a=jnp.where(head.at_i > head.at_j, beta * decay * head.kk, 0.0))


def _chunk(head, gate, t, v, state):
    """One value head's chunk from its key head's part, its gates, ``t = (I +
    A)^-1``, ``v [C, dv]`` and the state ``[dk, dv]`` it starts from:
    everything both passes read."""
    dtype, c = v.dtype, v.shape[0]
    run, beta, decay = gate.run, gate.beta, gate.decay
    grown = jnp.exp(run)  # exp(G) [C, 1]
    vf = v.astype(jnp.float32)
    solved = _hdot(t, jnp.concatenate(
        [beta * vf, (beta * grown) * head.kf], axis=1))  # [U~ | W]
    dv = v.shape[1]
    w = solved[:, dv:].astype(dtype)
    sb = state.astype(dtype)
    u = (solved[:, :dv].astype(dtype).astype(jnp.float32)
         - _dot(w, sb)).astype(dtype)
    # G_C down a column (Mosaic spreads a [1, 1] over sublanes or over lanes,
    # not both at once)
    last = lambda rows: jnp.broadcast_to(gate.run_row[:, c - 1:], (rows, 1))
    left = jnp.exp(last(c) - run)  # exp(G_C - G) [C, 1]
    return SimpleNamespace(
        vf=vf, beta=beta, decay=decay, t=t, grown=grown, solved=solved, w=w,
        sb=sb, u=u, left=left, keep=jnp.exp(last(state.shape[0])),  # [dk, 1]
        attend=(decay * head.qk).astype(dtype),
        q_run=(grown * head.qf).astype(dtype),
        k_left=(left * head.kf).astype(dtype))


def _heads_of(kernel):
    """``kernel`` of key head ``h`` of a step's blocks -> the kernel of a grid
    step of ``heads`` key heads, one after the other in one block of code
    (the compiler interleaves their chains of products). The last ref is the
    scratch, zero where a row starts."""
    def step(*refs, heads, values):
        @pl.when(pl.program_id(1) == 0)
        def _():
            refs[-1][...] = jnp.zeros_like(refs[-1])

        for h in range(heads):
            kernel(*refs, h=h, values=values)
    return step


def _fwd_kernel(q_ref, k_ref, v_ref, gates_ref, o_ref, starts_ref,
                inverses_ref, state, *, h, values):
    c, dv = v_ref.shape[0], starts_ref.shape[-1]
    head = _key_head(q_ref[h], k_ref[h])
    gates = [_gated(head, gates_ref, h, r, values) for r in range(values)]
    inverses = jnp.concatenate(_inverses([gate.a for gate in gates]), axis=1)
    inverses_ref[h] = inverses
    for r in range(values):
        n = h * values + r
        cols = slice(n * dv, (n + 1) * dv)
        start = state[n]
        starts_ref[h, r] = start
        m = _chunk(head, gates[r], inverses[:, r * c:(r + 1) * c],
                   v_ref[:, cols], start)
        o_ref[:, cols] = (_dot(m.q_run, m.sb) + _dot(m.attend, m.u)
                          ).astype(o_ref.dtype)
        state[n] = m.keep * start + _dot(m.k_left, m.u, _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, gates_ref, starts_ref, inverses_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dgates_ref, dstate, *, h,
                values):
    f32 = jnp.float32
    dtype = v_ref.dtype
    c, dv = v_ref.shape[0], starts_ref.shape[-1]
    head = _key_head(q_ref[h], k_ref[h])
    over = lambda x, axis: jnp.sum(x, axis=axis, keepdims=True)
    dq, dk = jnp.zeros_like(head.qf), jnp.zeros_like(head.kf)
    dkk, dqk = jnp.zeros_like(head.kk), jnp.zeros_like(head.qk)
    for r in range(values):
        n = h * values + r
        cols = slice(n * dv, (n + 1) * dv)
        start = starts_ref[h, r]
        m = _chunk(head, _gated(head, gates_ref, h, r, values),
                   inverses_ref[h, :, r * c:(r + 1) * c], v_ref[:, cols], start)
        do = do_ref[:, cols]
        ds = dstate[n]  # of the state this chunk leaves
        dsb = ds.astype(dtype)
        # o = q_run S + attend U;  S' = keep S + k_left^T U;  U = U~ - W S
        d_q_run = _dot(do, m.sb, _NT)
        d_attend = _dot(do, m.u, _NT)
        du = (_dot(m.attend, do, _TN) + _dot(m.k_left, dsb)).astype(dtype)
        d_k_left = _dot(m.u, dsb, _NT)
        d_keep = over(over(ds * start, 1), 0)  # [1, 1]
        dw = (-_dot(du, m.sb, _NT)).astype(dtype)
        dstate[n] = m.keep * ds + _dot(m.q_run, do, _TN) - _dot(m.w, du, _TN)
        # [U~ | W] = T rhs:  dRhs = T^T [dU~ | dW],  dA = -dRhs [U~ | W]^T
        d_rhs = _hdot(m.t, jnp.concatenate(
            [du.astype(f32), dw.astype(f32)], axis=1), _TN)
        da = jnp.where(head.at_i > head.at_j, -_hdot(d_rhs, m.solved, _NT), 0.0)
        d_rv, d_rk = d_rhs[:, :dv], d_rhs[:, dv:]
        along_k = over(d_rk * head.kf, 1)
        dbeta = (over(da * m.decay * head.kk, 1) + over(d_rv * m.vf, 1)
                 + m.grown * along_k)
        dv_ref[:, cols] = (m.beta * d_rv).astype(dv_ref.dtype)
        dk = dk + (m.beta * m.grown) * d_rk + m.left * d_k_left
        dq = dq + m.grown * d_q_run
        d_grown = m.beta * along_k + over(d_q_run * head.qf, 1)
        d_left = over(d_k_left * head.kf, 1) * m.left  # times its own value
        # decay_ij = exp(G_i - G_j): +row sums to G_i, -column sums to G_j
        e = (da * m.beta * head.kk + d_attend * head.qk) * m.decay
        d_run = (_row(over(e, 1) + d_grown * m.grown - d_left, head.eye)
                 - over(e, 0))
        at_last = jax.lax.broadcasted_iota(jnp.int32, d_run.shape, 1) == c - 1
        d_run = d_run + jnp.where(
            at_last, over(d_left, 0) + d_keep * m.keep[:1], 0.0)
        dgates_ref[h, r:r + 1, :] = d_run
        dgates_ref[h, values + r:values + r + 1, :] = _row(dbeta, head.eye)
        dkk = dkk + da * (m.beta * m.decay)
        dqk = dqk + d_attend * m.decay
    dkk, dqk = dkk.astype(dtype), dqk.astype(dtype)
    dk = dk + _dot(dkk, head.k) + _dot(dkk, head.k, _TN) + _dot(dqk, head.q, _TN)
    dq = dq + _dot(dqk, head.k)
    dq_ref[h] = dq.astype(dq_ref.dtype)
    dk_ref[h] = dk.astype(dk_ref.dtype)


def _inverse_lanes(chunk, values):
    """Lanes of a key head's inverses side by side: :func:`_inverses` fills
    whole groups of ``128 / C``."""
    side = max(1, _LANES // chunk)
    return -(-values // side) * side * chunk


def _call(kernel, name, reverse, chunk, starts, interpret, operands, out):
    """``kernel`` over the grid ``(key heads / step, chunks)``, the chunks in
    order or from the end. ``operands`` and ``out``: ``(array or its
    ShapeDtypeStruct, kind)``, the kind naming the block a step takes."""
    heads, chunks, values, dk, dv = starts.shape
    step = 2 if heads % 2 == 0 else 1  # key heads a step (module docstring)
    at = (lambda n: chunks - 1 - n) if reverse else (lambda n: n)
    specs = dict(
        keys=pl.BlockSpec((step, chunk, dk), lambda h, n: (h, at(n), 0)),
        values=pl.BlockSpec((chunk, step * values * dv), lambda h, n: (at(n), h)),
        gates=pl.BlockSpec((step, None, 2 * values, chunk),
                           lambda h, n: (h, at(n), 0, 0)),
        states=pl.BlockSpec((step, None, values, dk, dv),
                            lambda h, n: (h, at(n), 0, 0, 0)),
        inverses=pl.BlockSpec((step, None, chunk, _inverse_lanes(chunk, values)),
                              lambda h, n: (h, at(n), 0, 0)))
    return pl.pallas_call(
        functools.partial(_heads_of(kernel), heads=step, values=values),
        grid=(heads // step, chunks),
        in_specs=[specs[kind] for _, kind in operands],
        out_specs=[specs[kind] for _, kind in out],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x, _ in out],
        scratch_shapes=[pltpu.VMEM((step * values, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=name)(*(x for x, _ in operands))


# Both passes are jitted for the trace and the lowering alone: a model's
# layers of one shape (and the two traces differentiation makes of a forward
# pass) then share one jaxpr and one lowered function, where every bare
# ``pallas_call`` would trace and lower its kernel again.
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _forward(q, k, v, gates, chunk, interpret):
    """``q, k [Hk, T, dk]``, ``v [T, Hk R dv]``, ``gates [Hk, chunks, 2 R,
    C]`` -> ``o`` as ``v`` and, in float32, the chunks' starting states
    ``[Hk, chunks, R, dk, dv]`` and inverses ``[Hk, chunks, C, R C]``."""
    heads, chunks, rows, _ = gates.shape
    values = rows // 2
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    starts = f32(heads, chunks, values, q.shape[2], v.shape[1] // (heads * values))
    return _call(
        _fwd_kernel, "gated_delta_rule_fwd", False, chunk, starts, interpret,
        [(q, "keys"), (k, "keys"), (v, "values"), (gates, "gates")],
        [(v, "values"), (starts, "states"),
         (f32(heads, chunks, chunk, _inverse_lanes(chunk, values)), "inverses")])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _backward(q, k, v, gates, starts, inverses, do, chunk, interpret):
    """Operands as :func:`_forward` takes and gives them, ``do`` as ``v`` ->
    ``dq, dk, dv`` as the operands and ``dgates`` as ``gates``."""
    return _call(
        _bwd_kernel, "gated_delta_rule_bwd", True, chunk, starts, interpret,
        [(q, "keys"), (k, "keys"), (v, "values"), (gates, "gates"),
         (starts, "states"), (inverses, "inverses"), (do, "values")],
        [(q, "keys"), (k, "keys"), (v, "values"), (gates, "gates")])


def _gates(run, beta, chunk):
    """``run, beta [T, Hk, R]`` -> ``[Hk, chunks, 2 R, C]``: a value head's
    ``G`` and, ``R`` rows on, its ``beta``, time in the lanes."""
    both = jnp.concatenate([run, beta], axis=-1)
    return both.reshape((-1, chunk) + both.shape[1:]).transpose(2, 0, 3, 1)


def _flat(x):  # [T, heads, ..., width] -> [T, heads x ... x width]
    return x.reshape(x.shape[0], -1)


def _heads_first(x):  # [T, Hk, dk] <-> [Hk, T, dk]
    return jnp.swapaxes(x, 0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _core(q, k, v, run, beta, chunk, interpret):
    return _core_fwd(q, k, v, run, beta, chunk, interpret)[0]


def _core_fwd(q, k, v, run, beta, chunk, interpret):
    with jax.named_scope(SCOPE):
        o, starts, inverses = _forward(
            _heads_first(q), _heads_first(k), _flat(v),
            _gates(run, beta, chunk), chunk, interpret)
        o = checkpoint_name(o.reshape(v.shape), KEPT)
        starts = checkpoint_name(starts, KEPT)
        inverses = checkpoint_name(inverses, KEPT)
    return o, (q, k, v, run, beta, starts, inverses)


def _core_bwd(chunk, interpret, kept, do):
    q, k, v, run, beta, starts, inverses = kept
    with jax.named_scope(SCOPE):
        dq, dk, dv, dgates = _backward(
            _heads_first(q), _heads_first(k), _flat(v),
            _gates(run, beta, chunk), starts, inverses, _flat(do), chunk,
            interpret)
        # [Hk, chunks, 2 R, C] -> [T, Hk, 2 R]
        dgates = dgates.transpose(1, 3, 0, 2).reshape(
            run.shape[:2] + (2 * run.shape[2],))
        values = run.shape[2]
        return (_heads_first(dq), _heads_first(dk), dv.reshape(v.shape),
                dgates[..., :values], dgates[..., values:])


_core.defvjp(_core_fwd, _core_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk, interpret: Optional[bool] = None):
    """The gated delta rule of one sequence, the function
    ``fedtpu.models.qwen3_next.gated_delta_rule`` is, at the shapes
    :func:`takes` admits."""
    if not _fits(q, k, v, g, beta, chunk):
        raise ValueError(
            f"the kernels take heads of whole lanes and a chunk of 16, 32, 64 "
            f"or 128 tokens that divides the length, not "
            f"chunk={chunk} on {[jnp.shape(a) for a in (q, k, v, g, beta)]}")
    with jax.named_scope(SCOPE):
        run = jnp.cumsum(
            g.reshape((-1, chunk) + g.shape[1:]), axis=1).reshape(g.shape)
    return _core(q, k, v, run, beta, chunk, _mode(interpret) == "interpret")
