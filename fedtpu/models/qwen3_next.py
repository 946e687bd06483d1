"""Qwen3-Next — a language model whose layers are gated DeltaNet linear
attention three times in four and gated softmax attention the fourth, each
followed by an expert layer that holds a share of 512 softmax-routed experts
(``huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct``, ``config.json``:
``model_type: qwen3_next``, 80B-A3B; the family's published modelling code is
``modeling_qwen3_next.py``).

The layers, as the plain reference (``benchmark/reference/qwen3_next.py``)
writes them too. ``Norm(x) = x * rsqrt(mean x^2 + eps) * (1 + w)``, ``w``
initialised 0, is every norm but the gated one:

- Block ``i``: ``h += Mixer_i(Norm(h))``; ``h += MoE(Norm(h))``. ``Mixer_i``
  is the gated softmax layer where ``(i + 1) % full_attention_interval == 0``,
  else gated DeltaNet.
- Gated DeltaNet (``Hk`` key heads and ``Hv`` value heads of widths ``dk``,
  ``dv``; key head ``j`` serves value heads ``j * Hv / Hk`` on): ``[q, k, v,
  z] = W_qkvz x``, ``[b, a] = W_ba x``; a causal depthwise convolution of
  width ``linear_conv_kernel_dim`` without bias over the channels of ``[q, k,
  v]`` (``y_t = sum_i w_i x_{t - width + 1 + i}``:
  :func:`fedtpu.models.lm_layers.causal_conv`, float32 sums over an operand
  that stays bfloat16 in memory, one pass over ``[T, 2 Hk dk + Hv dv]``
  forward and one over its cotangent backward under one differentiation rule;
  the backward pass keeps the operand and the taps. Four multiply-adds a
  channel a token: its cost is its bytes, so nothing the size of the operand
  is written in float32), then SiLU; ``q, k`` divided
  by ``sqrt(sum of squares + 1e-6)`` over their width, ``q`` scaled by
  ``dk^-0.5``; ``beta_t = sigmoid(b_t)``, ``g_t = -exp(A_log) softplus(a_t +
  dt_bias)`` in float32, one a value head. A value head's state ``S`` (``dk x
  dv``, keys by values, ``S_0 = 0``): ``S'_t = exp(g_t) S_{t-1}``; ``u_t =
  beta_t (v_t - S'_t^T k_t)``; ``S_t = S'_t + k_t u_t^T``; ``o_t = S_t^T
  q_t``. Output: ``o_t * rsqrt(mean o_t^2 + eps) * w`` (a plain weight, ones)
  ``* SiLU(z_t)``, then ``W_o``. The state runs across the document
  boundaries of a packed row. Columns of ``W_qkvz`` are ``[q | k | v | z]``
  and of ``W_ba`` ``[b | a]``, each part head by head (the published
  projection groups its columns by key head: with seeded weights any fixed
  layout is the same model).
- The recurrence's training form (:func:`gated_delta_rule`) takes a chunk of
  ``gdn_chunk`` tokens at a time. With ``G_i`` the chunk's running sum of
  ``g`` and ``A_ij = beta_i exp(G_i - G_j) k_i.k_j`` for ``j < i``, the
  chunk's ``u`` solve the unit lower-triangular system ``(I + A) U = beta (V -
  exp(G) K S_0)`` (float32), so ``U = U~ - W S_0`` with ``[U~ | W] = (I +
  A)^-1 beta [V | exp(G) K]``; then ``O = exp(G) Q S_0 + (exp(G_i - G_j)
  q_i.k_j)_{j <= i} U`` and ``S_C = exp(G_C) S_0 + (exp(G_C - G) K)^T U``.
  Every exponent is a difference ``G_i - G_j`` with ``j <= i``, never
  positive. A length the chunk does not divide is padded with tokens of
  ``beta = 0``, ``g = 0``, which leave the state as it is. One function, two
  bodies, chosen by the backend and the operands' shapes alone
  (:func:`fedtpu.ops.delta_rule_kernels.takes`) and counted in
  ``fedtpu_delta_rule_cores_traced_total{body}``: on a TPU, at heads of whole
  lanes (the published 128) and a chunk of 16, 32, 64 or 128, two fused
  kernels, forward and backward, that keep a chunk's matrices, the inverse
  and the state in VMEM and leave in memory a chunk-start state and an
  inverse a chunk (:mod:`fedtpu.ops.delta_rule_kernels`); everywhere else
  (the CPU, the tiny twin's heads of 64, the tests' yardstick) the plain
  chunks: every chunk's matrices at once, one triangular solve, a
  rematerialised ``lax.scan`` over the chunks that carries the state. q and
  k are handed over with their order in memory stated (:func:`_lying`: no
  value changes), heads-major, and so is the float32 copy their
  normalisation reads: left to itself the TPU compiler turns ``[T, heads x
  dk]`` into ``[T, heads, dk]`` (heads in the sublanes) for the sum over
  ``dk`` by a copy without the program's scope.
- Gated softmax layer: ``[q, gate] = W_q x`` (a head's ``head_dim`` of ``q``,
  then its ``head_dim`` of ``gate``), ``k = W_k x``, ``v = W_v x``; ``q, k <-
  Norm(q), Norm(k)`` over ``head_dim``; rotary turns (rotate-half pairing:
  dimension ``i`` with ``i + rot / 2``) on the first ``rot = head_dim *
  partial_rotary_factor`` dimensions of each head; causal softmax of ``q.k /
  sqrt(head_dim)`` in float32, ``num_attention_heads / num_key_value_heads``
  query heads a key-value head, by :func:`fedtpu.models.lm_layers.attention_core`
  (``joyai_llm_flash``'s: the fused kernels on a TPU, the plain query blocks
  elsewhere; a key-value head is read by its group, not copied); ``o *
  sigmoid(gate)``; ``W_o``.
- Expert layer: ``p = softmax(W_r x)`` in float32 over ALL ``num_experts``;
  chosen = the ``num_experts_per_tok`` largest; ``g = p[chosen] / sum
  p[chosen]``; ``y = sigmoid(w_s . x) SwiGLU_shared(x) + sum over chosen e
  that are HELD of g_e SwiGLU_e(x)``. ``experts_held = (lo, hi)`` says which
  experts live here (all by default); what the absent ones would add is left
  out and the partial sum goes on. The layer is
  :class:`fedtpu.models.lm_layers.ExpertLayer` with this rule handed in
  (:func:`experts`).
- Embedding, final ``Norm``, head, next-token cross-entropy over the
  vocabulary's rows held here. No prediction module: the config has no key
  for one.

The stack around the blocks is :class:`fedtpu.models.lm_layers.DecoderStack`.
Every size is a keyword of the constructor (``RoundConfig.model_args``); the
defaults are the published ones.

Device time is named under ``fed.local_step.fwd_bwd.``: ``embed``,
``linear_attention`` (``.proj``, ``.conv``, ``.core``: gates, normalisation
and the chunked rule, whichever body runs it; ``.out``: gated norm and
``W_o``), ``attention``
(``.core``) for the softmax layer, ``moe`` (``.router``, ``.dispatch``,
``.experts``, ``.combine``), ``lm_loss``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from fedtpu.models.lm_layers import (  # noqa: F401 (KEEP: the tests reach it through this module)
    KEEP, SCOPE, DecoderStack, Linear, _rms, causal_conv, feed_forward,
    grouped_query_attention, held_range, register_language_model,
    rematerialised, rope_half, top_k_gates)
from fedtpu.obs.registry import get_global_registry
from fedtpu.ops import delta_rule_kernels

L2_EPS = 1e-6  # under the square root of q's and k's normalisation
DELTA_CORES_TRACED = "fedtpu_delta_rule_cores_traced_total"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The config's keys by their published names, and what the cut and the
    program add (``experts_held`` on)."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
    rms_norm_eps: float = 1e-6
    experts_held: Optional[Tuple[int, int]] = None  # [lo, hi); None: all
    # Read by the local step (fedtpu.core.client): how many rows of a batch
    # go through forward and backward at a time (0: the whole batch).
    micro_batch_rows: int = 0
    gdn_chunk: int = 64
    attn_q_block: int = 512
    moe_chunk_pairs: int = 8192  # these two as the cell runs them: a held
    moe_block_rows: int = 128  # expert of 16 sees 160 pairs a row of 8,192

    def is_softmax_layer(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0


class Norm(nn.Module):
    """``x * rsqrt(mean x^2 + eps) * (1 + scale)``, ``scale`` from 0."""

    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros_init(), (x.shape[-1],))
        return _rms(x, 1.0 + scale, self.eps)


def _lying(x, *major_to_minor):
    """``x``, with the order of its axes in memory said to the compiler
    (``jax.experimental.layout``; the values are ``x``'s). Left to itself the
    TPU compiler writes such an operand in the order of the products that
    made it and turns it for its reader by a copy that carries no scope of
    the program (a capture reads those as ``_unscoped_``); told the order the
    reader wants, the producing fusion writes it so."""
    return with_layout_constraint(x, Layout(major_to_minor=major_to_minor))


def gated_delta_rule(q, k, v, g, beta, chunk):
    """The gated delta rule of one sequence, a chunk at a time (module
    docstring). ``q, k [T, Hk, dk]`` normalised, ``v [T, Hk, R, dv]`` (``R``
    value heads a key head), ``g, beta [T, Hk, R]`` float32. Returns ``o [T,
    Hk, R, dv]`` in ``v``'s dtype. Operands of ``v``'s dtype go into the
    products, sums are float32, and so are the gates, the triangular solve and
    the state between chunks. One function of the same operands by the body
    its shapes and the backend call for: the fused kernels
    (:mod:`fedtpu.ops.delta_rule_kernels`) or the plain chunks below. Counted
    in the process's registry by the body taken, once a core traced."""
    t = q.shape[0]
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in (q, k, v, g, beta))
    kernel = delta_rule_kernels.takes(q, k, v, g, beta, chunk)
    get_global_registry().counter(
        DELTA_CORES_TRACED, "gated delta rule cores traced, by the body taken",
        labels={"body": "kernel" if kernel else "plain"}).inc()
    body = delta_rule_kernels.gated_delta_rule if kernel else _plain_chunks
    return body(q, k, v, g, beta, chunk)[:t]


def _plain_chunks(q, k, v, g, beta, chunk):
    """:func:`gated_delta_rule` at a length the chunk divides, in plain
    ``jax.numpy``: every chunk's matrices at once, one triangular solve, a
    rematerialised scan over the chunks."""
    dtype = v.dtype
    n = q.shape[0] // chunk
    cut = lambda a: a.reshape((n, chunk) + a.shape[1:])
    q, k, v, g, beta = cut(q), cut(k), cut(v), cut(g), cut(beta)
    f32 = dict(preferred_element_type=jnp.float32)

    # Per value head, time last but one: [n, Hk, R, C, .]
    g_h, beta_h = jnp.moveaxis(g, 1, -1), jnp.moveaxis(beta, 1, -1)
    v_h = jnp.moveaxis(v, 1, 3).astype(jnp.float32)
    q_h = jnp.moveaxis(q, 1, 2)[:, :, None].astype(jnp.float32)
    k_h = jnp.moveaxis(k, 1, 2)[:, :, None].astype(jnp.float32)
    run = jnp.cumsum(g_h, axis=-1)  # G [n, Hk, R, C]
    at = jnp.arange(chunk)
    # exp(G_i - G_j) where j <= i, else 0: [n, Hk, R, C, C]
    decay = jnp.exp(jnp.where(
        at[:, None] >= at[None, :],
        run[..., :, None] - run[..., None, :], -jnp.inf))
    kk = jnp.einsum("nchd,nshd->nhcs", k, k, **f32)[:, :, None]
    qk = jnp.einsum("nchd,nshd->nhcs", q, k, **f32)[:, :, None]
    a_mat = jnp.where(at[:, None] > at[None, :],
                      beta_h[..., :, None] * decay * kk, 0.0)
    # (I + A) [U~ | W] = beta [V | exp(G) K], every chunk's at once
    rhs = jnp.concatenate([
        beta_h[..., None] * v_h, (beta_h * jnp.exp(run))[..., None] * k_h,
    ], axis=-1)
    solved = jax.lax.linalg.triangular_solve(
        a_mat, rhs, left_side=True, lower=True, unit_diagonal=True)
    dv = v.shape[-1]
    u_free, w = solved[..., :dv].astype(dtype), solved[..., dv:].astype(dtype)
    attend = (decay * qk).astype(dtype)
    q_run = (jnp.exp(run)[..., None] * q_h).astype(dtype)
    last = run[..., -1:]  # G_C [n, Hk, R, 1]
    k_left = (jnp.exp(last - run)[..., None] * k_h).astype(dtype)
    keep = jnp.exp(last)[..., None]  # [n, Hk, R, 1, 1]

    @jax.checkpoint
    def one_chunk(state, xs):
        u_free, w, attend, q_run, k_left, keep = xs
        s = state.astype(dtype)
        u = (u_free.astype(jnp.float32)
             - jnp.einsum("hrcd,hrdv->hrcv", w, s, **f32)).astype(dtype)
        o = (jnp.einsum("hrcd,hrdv->hrcv", q_run, s, **f32)
             + jnp.einsum("hrcs,hrsv->hrcv", attend, u, **f32))
        state = keep * state + jnp.einsum("hrcd,hrcv->hrdv", k_left, u, **f32)
        return state, o.astype(dtype)

    zero = jnp.zeros(v.shape[2:4] + (k.shape[-1], dv), jnp.float32)
    _, o = jax.lax.scan(one_chunk, zero, (u_free, w, attend, q_run, k_left, keep))
    # [n, Hk, R, C, dv] -> [T, Hk, R, dv]
    return jnp.moveaxis(o, 3, 1).reshape((n * chunk,) + o.shape[1:3] + (dv,))


class GatedDeltaNet(nn.Module):
    sizes: Sizes

    @nn.compact
    def __call__(self, x):
        c = self.sizes
        b, t, d = x.shape
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
        r = hv // hk
        if hk * r != hv:
            raise ValueError(
                f"linear_num_value_heads={hv} is no multiple of "
                f"linear_num_key_heads={hk}")
        conv_kernel = self.param(
            "conv", nn.initializers.normal(1.0 / math.sqrt(c.linear_conv_kernel_dim)),
            (c.linear_conv_kernel_dim, 2 * hk * dk + hv * dv))
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)), (hv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones_init(), (hv,))
        norm = self.param("norm", nn.initializers.ones_init(), (dv,))
        with jax.named_scope(SCOPE + "linear_attention.proj"):
            qkvz = Linear(2 * hk * dk + 2 * hv * dv, name="in_proj_qkvz")(x)
            ba = Linear(2 * hv, name="in_proj_ba")(x).astype(jnp.float32)
        z = qkvz[..., 2 * hk * dk + hv * dv:].reshape(b, t, hk, r, dv)

        def one_sequence(args):
            qkv, ba = args
            with jax.named_scope(SCOPE + "linear_attention.conv"):
                qkv = jax.nn.silu(causal_conv(qkv, conv_kernel))
            with jax.named_scope(SCOPE + "linear_attention.core"):
                def unit(a):
                    a = _lying(a.astype(jnp.float32), 1, 0, 2)
                    return a * jax.lax.rsqrt(
                        jnp.sum(jnp.square(a), -1, keepdims=True) + L2_EPS)
                # q and k heads-major: time stays in the sublanes, where
                # the projection's [T, heads x dk] has it and the rule's
                # products want it.
                q = _lying((unit(qkv[:, :hk * dk].reshape(t, hk, dk)) * dk ** -0.5
                            ).astype(x.dtype), 1, 0, 2)
                k = _lying(unit(qkv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
                                ).astype(x.dtype), 1, 0, 2)
                v = qkv[:, 2 * hk * dk:].reshape(t, hk, r, dv)
                beta = jax.nn.sigmoid(ba[:, :hv]).reshape(t, hk, r)
                g = (-jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                    ba[:, hv:] + dt_bias.astype(jnp.float32))).reshape(t, hk, r)
                return gated_delta_rule(q, k, v, g, beta, c.gdn_chunk)

        o = jax.lax.map(
            one_sequence, (qkvz[..., :2 * hk * dk + hv * dv], ba))
        with jax.named_scope(SCOPE + "linear_attention.out"):
            o = _rms(o, norm, c.rms_norm_eps).astype(jnp.float32) * jax.nn.silu(
                z.astype(jnp.float32))
            return Linear(d, name="out_proj")(
                o.astype(x.dtype).reshape(b, t, hv * dv))


class GatedAttention(nn.Module):
    sizes: Sizes

    @nn.compact
    def __call__(self, x):
        c = self.sizes
        b, t, d = x.shape
        h, kh, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        group, rot = h // kh, int(hd * c.partial_rotary_factor)
        if kh * group != h:
            raise ValueError(
                f"num_attention_heads={h} is no multiple of "
                f"num_key_value_heads={kh}")
        qg = Linear(h * 2 * hd, name="q_proj")(x).reshape(b, t, kh, group, 2 * hd)
        q = Norm(c.rms_norm_eps, name="q_norm")(qg[..., :hd])
        gate = qg[..., hd:]
        k = Norm(c.rms_norm_eps, name="k_norm")(
            Linear(kh * hd, name="k_proj")(x).reshape(b, t, kh, hd))
        v = Linear(kh * hd, name="v_proj")(x).reshape(b, t, kh, hd)

        rotary = lambda a: rope_half(a, c.rope_theta, rot)
        return Linear(d, name="o_proj")(grouped_query_attention(
            q, k, v, rotary, c.attn_q_block, gate=gate))


def experts(sizes: Sizes, layer: int) -> dict:
    """Expert layer ``layer``'s fields of :class:`lm_layers.ExpertLayer`,
    every layer's alike: the shared expert behind its sigmoid gate, the module
    docstring's gate rule."""
    c = sizes
    return dict(
        routed=c.num_experts, k=c.num_experts_per_tok,
        held=held_range(c.experts_held, c.num_experts),
        width=c.moe_intermediate_size, chunk_pairs=c.moe_chunk_pairs,
        block_rows=c.moe_block_rows,
        shared_width=c.shared_expert_intermediate_size, shared_gated=True,
        gate_rule=lambda logits, k: top_k_gates(
            jax.nn.softmax(logits, axis=-1), k))


class Block(nn.Module):
    """``remat``: the mixer and the expert layer are each rematerialised by
    themselves, so a block's backward pass holds one of them at a time (the
    two together pass a chip's memory beside an 8,192-token row)."""

    sizes: Sizes
    layer: int
    remat: bool = False

    @nn.compact
    def __call__(self, h):
        c = self.sizes
        x = Norm(c.rms_norm_eps, name="mixer_norm")(h)
        if c.is_softmax_layer(self.layer):
            with jax.named_scope(SCOPE + "attention"):
                h = h + rematerialised(GatedAttention, self.remat)(
                    c, name="self_attn")(x)
        else:
            with jax.named_scope(SCOPE + "linear_attention"):
                h = h + rematerialised(GatedDeltaNet, self.remat)(
                    c, name="linear_attn")(x)
        # This model's norm in front of the expert layer has always run under
        # the layer's scope: ``moe.device_share`` counts it.
        with jax.named_scope(SCOPE + "moe"):
            x = Norm(c.rms_norm_eps, name="ffn_norm")(h)
        y, pairs, load = feed_forward(x, self.remat, experts(c, self.layer))
        return h + y, pairs, load


@register_language_model("qwen3_next", Sizes)
def Qwen3Next(sizes: Sizes, remat: bool) -> nn.Module:
    """The final norm is the model's ``Norm``: ``1 + w``."""
    c = sizes
    return DecoderStack(
        vocab_size=c.vocab_size, hidden_size=c.hidden_size, eps=c.rms_norm_eps,
        blocks=tuple(functools.partial(Block, c, i, remat)
                     for i in range(c.num_hidden_layers)),
        final_norm_offset=1.0)
