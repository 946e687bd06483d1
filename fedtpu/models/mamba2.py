"""Mamba-2's state-space mixer, as the two models that have one share it
(``nemotron_h``: eight groups of 8 heads in chunks of 128, held whole;
``granite_hybrid``: ONE group of 64 heads in chunks of 256, a share of the
heads held). Beside :mod:`fedtpu.models.lm_layers`, whose plain layers and
convolution it is built from, and not inside it: two of the six language
models have the mixer, and what all six import stays free of its kernels. It
takes plain fields, never a model's ``Sizes``.

The mixer, with ``H`` heads of ``P``, ``d_in = H P``, ``G`` groups of ``B``
and ``C`` on a state of ``N``: ``[z | xBC | dt] = W_in u``, widths ``d_in |
d_in + 2 G N | H``; ``xBC = silu(conv(xBC) + b_conv)``, a causal depthwise
convolution (:func:`fedtpu.models.lm_layers.causal_conv`, with a bias a
channel where the model has one); ``xBC = [x | B | C]``, ``x [T, H, P]``, ``B,
C [T, G, N]``, head ``h`` reads group ``h // (H / G)``; ``dt = softplus(dt +
dt_bias) [T, H]``, ``A = -exp(A_log) [H]``, float32, with no limit on ``dt``.
A head's state ``S [P, N]``, float32, ``S_0 = 0``: ``S_t = exp(dt_t A)
S_{t-1} + dt_t x_t B_t^T``; ``y_t = S_t C_t + D x_t``. ``y = RMSNorm_groups(y
* silu(z)) * w``: the gate BEFORE the norm, the norm over each group's
channels; ``out = W_out y``. The state runs across the document boundaries of
a packed row.

**A share of the heads** (``heads_held = (lo, hi)``; all by default; one
group only). ``W_in`` holds the held heads' columns of ``z``, ``x`` and ``dt``
and ALL of ``B`` and ``C`` (what every chip computes alike); the taps and the
bias those channels; ``dt_bias``, ``A_log``, ``D`` and the norm's weight the
held heads'; ``W_out`` the matching rows, so the output is the partial sum
that tensor parallelism over heads would all-reduce. The gated norm's mean
square runs over all ``d_in`` channels of the one group, the absent heads'
too: a share of heads carries a STATISTIC here and not only a partial sum.
:func:`normed` takes the mean square as an argument next to the gated values;
the module hands it the mean over the channels it holds (one chip runs its
layer without the exchange: what the absent heads would add to the sum of
squares and to ``W_out``'s sum is left out), or the ``mean_square`` it is
called with: what the all-reduce of the shares' sums would deliver. Its own
mean is sown into ``intermediates`` (``gated_mean_square``), which is how a
caller that holds several shares adds their sums up.

The recurrence's training form (:func:`selective_scan`) takes a chunk of
``chunk`` tokens at a time. With ``L_t`` the chunk's running sum of ``dt A``:
inside the chunk ``y_t += sum_{s <= t} exp(L_t - L_s) (C_t . B_s) dt_s x_s``,
one ``[chunk, chunk]`` decay matrix a head whose every exponent is a
difference ``L_t - L_s`` with ``s <= t``, never positive, times the ``C B^T``
of the head's GROUP (computed once a group, never copied a head); the chunk
adds ``sum_s exp(L_end - L_s) dt_s x_s B_s^T`` to the state it met, decayed
by ``exp(L_end)``: a rematerialised ``lax.scan`` over the chunks carries the
float32 state and hands out each chunk's START state (what its backward pass
keeps: chunks x H x P x N x 4 B), and ``y_t += exp(L_t) S_start C_t``. A
length the chunk does not divide is padded with steps of ``dt = 0``, which
leave the state as it is. Operands of ``x``'s dtype go into the products,
sums, gates and the state are float32. A chunk's length is no part of the
function: any chunk gives the recurrence's value.

ONE function with two bodies since PR 50, chosen from the backend and the
operands' shapes (:func:`fedtpu.ops.ssd_kernels.takes`) and counted in
``fedtpu_ssd_cores_traced_total{body}`` as the other cores are: on a TPU, at a
group's heads and a state of whole lanes, heads of a part of a lane group and
a chunk of 128 that divides the length (Nemotron-H's published sizes on 8,192
tokens), :mod:`fedtpu.ops.ssd_kernels`' two kernels under one
``jax.custom_vjp`` (``body="kernel"``: a chunk's decay matrices and a group's
float32 state stay in VMEM; the output and each chunk's float32 starting
state are named for the rematerialised layer's policy, so its backward pass
runs no forward kernel again); everywhere else (the CPU, the tiny twins'
widths, a length the chunk does not divide, the eight tokens a model is
initialised on, and ANOTHER CHUNK: Granite's published 256) the plain
``jax.numpy`` chunks above, pad and all (``body="plain"``). Where a TPU run
takes the plain body at heads and a state the kernels would take but for the
chunk or the length, one warning a process names both. SiLU runs on ``x``,
``B`` and ``C`` apart, so that each is written once, as the core reads it.

Device time is named under ``fed.local_step.fwd_bwd.mamba``: ``.proj``
(``W_in``), ``.conv`` (taps, bias and SiLU), ``.core`` (the step sizes and
the chunked scan), ``.out`` (gate, norm and ``W_out``).
"""

from __future__ import annotations

import logging
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedtpu.models.lm_layers import (
    SCOPE, Linear, causal_conv, held_range, normed)
from fedtpu.obs.registry import get_global_registry
from fedtpu.ops import ssd_kernels

SSD_CORES_TRACED = "fedtpu_ssd_cores_traced_total"

_PLAIN_SCANS_WARNED = set()


def _warn_of_plain_scan(x, dt, A, B, C, D, chunk):
    """One warning a process, chunk and length, at trace time, where a TPU
    run takes the plain chunks at shapes the kernels would take but for the
    chunk or the length (a published chunk of 256; a length their chunk does
    not divide): the run is right and slower than its neighbours, and says
    so. Silent on the CPU, at heads or a state of part lanes (the tiny twins)
    and at a row shorter than the kernels' chunk (the eight tokens a model is
    initialised on)."""
    t = x.shape[0]
    none = lambda a: jax.ShapeDtypeStruct((0,) + a.shape[1:], a.dtype)
    if (t >= ssd_kernels.CHUNK and (chunk, t) not in _PLAIN_SCANS_WARNED
            and ssd_kernels.takes(none(x), none(dt), A, none(B), none(C), D,
                                  ssd_kernels.CHUNK)):
        _PLAIN_SCANS_WARNED.add((chunk, t))
        logging.getLogger(__name__).warning(
            "selective scan of %d tokens in chunks of %d: the plain chunks on "
            "this TPU (fedtpu.ops.ssd_kernels takes a chunk of %d that "
            "divides the length)", t, chunk, ssd_kernels.CHUNK)


def selective_scan(x, dt, A, B, C, D, chunk):
    """Mamba-2's selective state-space recurrence of one sequence, a chunk at
    a time (module docstring). ``x [T, H, P]``, ``dt [T, H]`` float32 and
    positive, ``A [H]`` float32 and negative, ``B, C [T, G, N]`` (head ``h``
    reads group ``h // (H / G)``), ``D [H]`` float32. Returns ``y [T, H, P]``
    in ``x``'s dtype. Operands of ``x``'s dtype go into the products; sums,
    decays and the state between chunks are float32. One function of the same
    operands by the body its shapes and the backend call for: the fused
    kernels (:mod:`fedtpu.ops.ssd_kernels`) or the plain chunks below. Counted
    in the process's registry by the body taken, once a core traced."""
    kernel = ssd_kernels.takes(x, dt, A, B, C, D, chunk)
    get_global_registry().counter(
        SSD_CORES_TRACED, "selective state-space cores traced, by the body "
        "taken", labels={"body": "kernel" if kernel else "plain"}).inc()
    if not kernel:
        _warn_of_plain_scan(x, dt, A, B, C, D, chunk)
    body = ssd_kernels.selective_scan if kernel else _plain_chunks
    return body(x, dt, A, B, C, D, chunk)


def _plain_chunks(x, dt, A, B, C, D, chunk):
    """:func:`selective_scan` in plain ``jax.numpy``: every chunk's matrices at
    once, a rematerialised scan over the chunks for the state; a length the
    chunk does not divide is padded with steps of ``dt = 0``."""
    (t, heads, p), dtype, g = x.shape, x.dtype, B.shape[1]
    r, rest = divmod(heads, g)
    if rest:
        raise ValueError(f"{heads} heads are no multiple of {g} groups")
    # A group's R heads side by side: [., G, R, .]
    x, dt = x.reshape(t, g, r, p), dt.reshape(t, g, r)
    A, D = A.reshape(g, r), D.reshape(g, r)
    pad = -t % chunk
    if pad:  # steps of dt = 0: the state stays, the rows are cut off again
        x, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       for a in (x, dt, B, C))
    n = (t + pad) // chunk
    cut = lambda a: a.reshape((n, chunk) + a.shape[1:])
    x, dt, B, C = cut(x), cut(dt), cut(B), cut(C)
    f32 = dict(preferred_element_type=jnp.float32)

    # Per head, time last: [n, G, R, C]
    run = jnp.cumsum(jnp.moveaxis(dt * A, 1, -1), axis=-1)  # L
    at = jnp.arange(chunk)
    # exp(L_t - L_s) where s <= t, else 0: [n, G, R, C, C]
    decay = jnp.exp(jnp.where(
        at[:, None] >= at[None, :],
        run[..., :, None] - run[..., None, :], -jnp.inf))
    cb = jnp.einsum("ntgk,nsgk->ngts", C, B, **f32)  # a GROUP's, once
    fed = x.astype(jnp.float32) * dt[..., None]  # dt_s x_s [n, C, G, R, P]
    y = jnp.einsum("ngrts,nsgrp->ntgrp", (decay * cb[:, :, None]).astype(dtype),
                   fed.astype(dtype), **f32)
    # What a chunk adds to the state it met, and what it keeps of that one.
    last = run[..., -1:]  # L_end [n, G, R, 1]
    left = jnp.moveaxis(jnp.exp(last - run), -1, 1)[..., None]  # [n, C, G, R, 1]
    added = jnp.einsum("nsgrp,nsgk->ngrpk", (fed * left).astype(dtype), B, **f32)
    keep = jnp.exp(last)[..., None]  # [n, G, R, 1, 1]

    @jax.checkpoint
    def one_chunk(state, xs):
        keep, added = xs
        return keep * state + added, state  # the state the chunk STARTS from

    _, start = jax.lax.scan(
        one_chunk, jnp.zeros(added.shape[1:], jnp.float32), (keep, added))
    read = jnp.einsum("ntgk,ngrpk->ntgrp", C, start.astype(dtype), **f32)
    y = y + jnp.moveaxis(jnp.exp(run), -1, 1)[..., None] * read
    y = y + D[:, :, None] * x.astype(jnp.float32)
    return y.astype(dtype).reshape(n * chunk, heads, p)[:t]


def _step_bias_init(step_min: float, step_max: float, step_floor: float):
    """``dt_bias`` so that ``softplus(dt_bias)`` is log-uniform on
    ``[step_min, step_max]`` and no less than ``step_floor`` (the family's
    initialiser)."""
    lo, hi = math.log(step_min), math.log(step_max)

    def init(key, shape, dtype=jnp.float32):
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, lo, hi)), step_floor)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)  # softplus^-1

    return init


class Mamba2(nn.Module):
    """The mixer of the module docstring on ``x [B, T, d]``, or this chip's
    share of its heads. ``mean_square`` (what broadcasts against ``[B, T, G,
    1]``): the gated norm's statistic where the caller has it (the shares'
    sums of squares added up, over all ``d_in`` channels); ``None``: the mean
    over the channels held here."""

    heads: int  # of the WHOLE layer
    head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk: int
    eps: float
    conv_bias: bool = True
    heads_held: Optional[Tuple[int, int]] = None  # [lo, hi); None: all
    # What the step sizes start from (the program's own initialiser).
    step_min: float = 0.001
    step_max: float = 0.1
    step_floor: float = 0.0001

    @nn.compact
    def __call__(self, x, mean_square=None):
        b, t, d = x.shape
        p, g, n = self.head_dim, self.groups, self.state
        lo, hi = held_range(
            self.heads_held, self.heads, "heads_held", "state-space heads")
        heads = hi - lo
        if heads != self.heads and g != 1:
            raise ValueError(
                f"heads_held={self.heads_held} of {self.heads} heads on {g} "
                "groups: a share of the heads is built for ONE group, whose "
                "B and C every chip computes alike (several groups would be "
                "shared by the group)")
        d_in, wide = heads * p, heads * p + 2 * g * n
        taps = self.param(
            "conv", nn.initializers.normal(1.0 / math.sqrt(self.conv_kernel)),
            (self.conv_kernel, wide))
        conv_bias = self.param(
            "conv_bias", nn.initializers.zeros_init(), (wide,)
        ) if self.conv_bias else None
        dt_bias = self.param("dt_bias", _step_bias_init(
            self.step_min, self.step_max, self.step_floor), (heads,))
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)), (heads,))
        skip = self.param("D", nn.initializers.ones_init(), (heads,))
        norm = self.param("norm", nn.initializers.ones_init(), (d_in,))
        with jax.named_scope(SCOPE + "mamba.proj"):
            zxbcdt = Linear(d_in + wide + heads, name="in_proj")(x)
        z = zxbcdt[..., :d_in]
        f32 = lambda a: a.astype(jnp.float32)

        def one_sequence(args):
            xbc, dt = args
            with jax.named_scope(SCOPE + "mamba.conv"):
                xbc = causal_conv(xbc, taps, conv_bias)
                # SiLU a part: the pass that makes x, B or C writes it as
                # the core reads it, and no slice of the whole is copied
                x_in, b_in, c_in = (
                    jax.nn.silu(xbc[:, lo:hi]) for lo, hi in (
                        (0, d_in), (d_in, d_in + g * n), (d_in + g * n, wide)))
            with jax.named_scope(SCOPE + "mamba.core"):
                dt = jax.nn.softplus(f32(dt) + f32(dt_bias))
                return selective_scan(
                    x_in.reshape(t, heads, p), dt, -jnp.exp(f32(a_log)),
                    b_in.reshape(t, g, n), c_in.reshape(t, g, n), f32(skip),
                    self.chunk)

        y = jax.lax.map(
            one_sequence, (zxbcdt[..., d_in:d_in + wide], zxbcdt[..., d_in + wide:]))
        with jax.named_scope(SCOPE + "mamba.out"):
            gated = (y.reshape(b, t, d_in).astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
            gated, weight = (gated.reshape(b, t, g, d_in // g),
                             norm.reshape(g, d_in // g))
            gf = gated.astype(jnp.float32)
            held = jnp.mean(gf * gf, axis=-1, keepdims=True)
            self.sow("intermediates", "gated_mean_square", held)
            y = normed(gf, held if mean_square is None else mean_square,
                       weight, self.eps).astype(x.dtype)
            return Linear(d, name="out_proj")(y.reshape(b, t, d_in))
