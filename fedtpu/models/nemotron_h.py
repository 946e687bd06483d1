"""Nemotron-H — a language model whose layers are ONE half each: a Mamba-2
state-space mixer, an expert layer of two-matrix ``relu2`` experts, or
grouped-query softmax attention, in the order a pattern string gives
(``huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``,
``config.json``: ``model_type: nemotron_h``, 52 layers of hidden 2,688,
``hybrid_override_pattern`` ``MEMEM*EMEMEM*...``: 23 ``M``, 23 ``E``, 6 ``*``).

The layers, as the plain reference (``benchmark/reference/nemotron_h.py``)
writes them too. ``RMSNorm(x) = w * x * rsqrt(mean x^2 +
layer_norm_epsilon)``, the weight entering as ``w``; no bias anywhere but the
convolution's:

- Layer ``l`` of kind ``hybrid_override_pattern[l]``: ``h +=
  Mixer_l(RMSNorm(h))``, ONE norm and ONE half a layer, the mixer Mamba-2
  (``M``), the expert layer (``E``) or attention (``*``); a dense ``-`` layer
  is no part of this pattern and is refused. After the last layer one RMSNorm
  (``norm_eps``), then an untied head.
- ``M``, with ``H = mamba_num_heads``, ``P = mamba_head_dim``, ``d_in = H P``
  (4,096: NOT ``expand`` x hidden, 5,376, which would be 84 heads of 64),
  ``G = n_groups``, ``N = ssm_state_size``: ``[z | xBC | dt] = W_in u``,
  widths ``d_in | d_in + 2 G N | H``; ``xBC = silu(conv(xBC) + b_conv)``, a
  causal depthwise convolution of ``conv_kernel`` taps
  (:func:`fedtpu.models.lm_layers.causal_conv`, the hybrid's and LFM2's, with
  a bias a channel); ``xBC = [x | B | C]``, ``x [T, H, P]``, ``B, C [T, G,
  N]``, head ``h`` reads group ``h // (H / G)``; ``dt = softplus(dt +
  dt_bias) [T, H]``, ``A = -exp(A_log) [H]``, float32, with no limit on
  ``dt`` (the config has no ``time_step_limit``). A head's state ``S [P,
  N]``, float32, ``S_0 = 0``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  B_t^T``; ``y_t = S_t C_t + D x_t``. ``y = RMSNorm_groups(y * silu(z)) *
  w``: the gate BEFORE the norm, the norm over each group's ``d_in / G``
  channels (512); ``out = W_out y``. The state runs across the document
  boundaries of a packed row.
- The recurrence's training form (:func:`selective_scan`) takes a chunk of
  ``chunk_size`` tokens at a time. With ``L_t`` the chunk's running sum of
  ``dt A``: inside the chunk ``y_t += sum_{s <= t} exp(L_t - L_s) (C_t . B_s)
  dt_s x_s``, one ``[chunk, chunk]`` decay matrix a head whose every exponent
  is a difference ``L_t - L_s`` with ``s <= t``, never positive, times the
  ``C B^T`` of the head's GROUP (computed once a group, never copied a head);
  the chunk adds ``sum_s exp(L_end - L_s) dt_s x_s B_s^T`` to the state it
  met, decayed by ``exp(L_end)``: a rematerialised ``lax.scan`` over the
  chunks carries the float32 state and hands out each chunk's START state
  (what its backward pass keeps: chunks x H x P x N x 4 B, 134 MB a layer a
  row at the published sizes), and ``y_t += exp(L_t) S_start C_t``. A length
  the chunk does not divide is padded with steps of ``dt = 0``, which leave
  the state as it is. Operands of ``x``'s dtype go into the products, sums,
  gates and the state are float32. ONE function with two bodies since PR 50,
  chosen from the backend and the operands' shapes
  (:func:`fedtpu.ops.ssd_kernels.takes`) and counted in
  ``fedtpu_ssd_cores_traced_total{body}`` as the other cores are: on a TPU,
  at a group's heads and a state of whole lanes, heads of a part of a lane
  group and a chunk of 128 that divides the length (the published sizes on
  8,192 tokens), :mod:`fedtpu.ops.ssd_kernels`' two kernels under one
  ``jax.custom_vjp`` (``body="kernel"``: a chunk's decay matrices and a
  group's float32 state stay in VMEM; the output and each chunk's float32
  starting state, 67 + 134 MB a layer a row, are named for the
  rematerialised layer's policy, so its backward pass runs no forward kernel
  again); everywhere else (the CPU, the tiny twins' widths, a length the
  chunk does not divide, the eight tokens a model is initialised on) the
  plain ``jax.numpy`` chunks above, pad and all (``body="plain"``). SiLU
  runs on ``x``, ``B`` and ``C`` apart, so that each is written once, as the
  core reads it.
- ``E``: ``s = sigmoid(W_r u)`` in float32 over ALL ``n_routed_experts``;
  chosen = the ``num_experts_per_tok`` largest of ``s + b`` (``n_group`` 1,
  ``topk_group`` 1: no group limit); ``g = routed_scaling_factor * s[chosen]
  / sum s[chosen]`` (``norm_topk_prob``); ``y = MLP_shared(u) + sum over
  chosen e that are HELD of g_e MLP_e(u)``, ``MLP(u) = W_down relu(W_up
  u)^2`` (``mlp_hidden_act: relu2``): TWO matrices an expert, the shared one
  of ``moe_shared_expert_intermediate_size``, a routed one of
  ``moe_intermediate_size``. ``b`` is a constant: ``bias_std`` times a
  standard normal from a key fixed by the PUBLISHED layer's index (JoyAI's
  rule: it shifts choices, takes no gradient, no round changes it).
  ``experts_held = (lo, hi)`` says which experts live here (all by default);
  what the absent ones would add is left out and the partial sum goes on. The
  layer is :class:`fedtpu.models.lm_layers.ExpertLayer` in its two-matrix form
  with this rule handed in (:func:`experts`). The published width, 1,856 =
  14.5 x 128 lanes, goes through the kernels on a TPU as every other model's
  does (:func:`fedtpu.ops.expert_kernels.takes` asks for a lane group or more
  in whole sublane tiles): a tile of the whole width, no padded copy.
- ``*``: ``num_attention_heads`` query heads on ``num_key_value_heads``
  key-value heads of ``head_dim``, rotary turns over ``head_dim *
  partial_rotary_factor`` dimensions at ``rope_theta`` in the rotate-half
  pairing, float32 softmax of ``q.k / sqrt(head_dim)`` over the keys ``j <=
  t``, ``W_o``: :func:`fedtpu.models.lm_layers.grouped_query_attention`, the
  body of every grouped-query layer here (its core the fused kernels on a TPU
  where the shapes fit, the plain query blocks elsewhere).
- ``layers_held`` names the published layers built here, in order (all by
  default): the pattern is read at those indices, so a cut states the
  published pattern and the layers it holds.

The stack around the layers is :class:`fedtpu.models.lm_layers.DecoderStack`.
Every size is a keyword of the constructor (``RoundConfig.model_args``); the
defaults are the published ones.

Device time is named under ``fed.local_step.fwd_bwd.``: ``embed``, ``mamba``
(``.proj``: ``W_in``; ``.conv``: taps, bias and SiLU; ``.core``: the step
sizes and the chunked scan; ``.out``: gate, grouped norm and ``W_out``),
``attention`` (``.core``), ``moe`` (``.router``, ``.dispatch``, ``.experts``,
``.combine``), ``lm_loss``. A layer's norm runs under its layer's scope.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedtpu.models.lm_layers import (
    SCOPE, DecoderStack, Linear, RMSNorm, _rms, causal_conv, feed_forward,
    grouped_query_attention, held_range, no_pairs, register_language_model,
    relu2, rematerialised, rope_half, top_k_gates)
from fedtpu.obs.registry import get_global_registry
from fedtpu.ops import ssd_kernels

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
SSD_CORES_TRACED = "fedtpu_ssd_cores_traced_total"
BIAS_KEY = 20261003
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The config's keys by their published names, and what the cut and the
    program add (``layers_held`` on)."""

    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52  # the layers BUILT: len(layers_held)
    hybrid_override_pattern: str = PATTERN  # the PUBLISHED layers' kinds
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    use_conv_bias: bool = True
    chunk_size: int = 128
    # What the step sizes start from (the program's own initialiser).
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    norm_eps: float = 1e-5
    layers_held: Optional[Tuple[int, ...]] = None  # published indices; None: all
    experts_held: Optional[Tuple[int, int]] = None  # [lo, hi); None: all
    bias_std: float = 0.01
    # Read by the local step (fedtpu.core.client): how many rows of a batch
    # go through forward and backward at a time (0: the whole batch).
    micro_batch_rows: int = 0
    attn_q_block: int = 512
    # These two as the cell runs them: of a row of 8,192 tokens a held expert
    # of 8 expects 384 pairs, 3,072 in all.
    moe_chunk_pairs: int = 8192
    moe_block_rows: int = 128

    @property
    def layers(self) -> Tuple[int, ...]:
        """The published index of each layer built here."""
        held = self.layers_held or tuple(range(self.num_hidden_layers))
        if len(held) != self.num_hidden_layers:
            raise ValueError(
                f"layers_held={held} names {len(held)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        return tuple(int(i) for i in held)

    def kind(self, layer: int) -> str:
        """``layer``: a published index. The scope its half runs under."""
        pattern = self.hybrid_override_pattern
        if not 0 <= layer < len(pattern) or pattern[layer] not in KINDS:
            raise ValueError(
                f"hybrid_override_pattern={pattern!r} names no kind, "
                f"{', '.join(KINDS)}, for layer {layer}")
        return KINDS[pattern[layer]]


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


def _step_bias_init(sizes: Sizes):
    """``dt_bias`` so that ``softplus(dt_bias)`` is log-uniform on
    ``[time_step_min, time_step_max]`` and no less than ``time_step_floor``
    (the family's initialiser)."""
    lo, hi = math.log(sizes.time_step_min), math.log(sizes.time_step_max)

    def init(key, shape, dtype=jnp.float32):
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, lo, hi)), sizes.time_step_floor)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)  # softplus^-1

    return init


class Mamba2(nn.Module):
    sizes: Sizes

    @nn.compact
    def __call__(self, x):
        c = self.sizes
        b, t, d = x.shape
        heads, p, g, n = (c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                          c.ssm_state_size)
        d_in, wide = heads * p, heads * p + 2 * g * n
        taps = self.param(
            "conv", nn.initializers.normal(1.0 / math.sqrt(c.conv_kernel)),
            (c.conv_kernel, wide))
        conv_bias = self.param(
            "conv_bias", nn.initializers.zeros_init(), (wide,)
        ) if c.use_conv_bias else None
        dt_bias = self.param("dt_bias", _step_bias_init(c), (heads,))
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
                    c.chunk_size)

        y = jax.lax.map(
            one_sequence, (zxbcdt[..., d_in:d_in + wide], zxbcdt[..., d_in + wide:]))
        with jax.named_scope(SCOPE + "mamba.out"):
            gated = (y.reshape(b, t, d_in).astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
            y = _rms(gated.reshape(b, t, g, d_in // g), norm.reshape(g, d_in // g),
                     c.layer_norm_epsilon)
            return Linear(d, name="out_proj")(y.reshape(b, t, d_in))


class Attention(nn.Module):
    sizes: Sizes

    @nn.compact
    def __call__(self, x):
        c = self.sizes
        b, t, d = x.shape
        h, kh, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        group, rest = divmod(h, kh)
        if rest:
            raise ValueError(
                f"num_attention_heads={h} is no multiple of "
                f"num_key_value_heads={kh}")
        q = Linear(h * hd, name="q_proj")(x).reshape(b, t, kh, group, hd)
        k = Linear(kh * hd, name="k_proj")(x).reshape(b, t, kh, hd)
        v = Linear(kh * hd, name="v_proj")(x).reshape(b, t, kh, hd)
        rotary = lambda a: rope_half(
            a, c.rope_theta, int(hd * c.partial_rotary_factor))
        return Linear(d, name="o_proj")(grouped_query_attention(
            q, k, v, rotary, c.attn_q_block, turn_in_core=False))


def selection_bias(layer: int, sizes: Sizes) -> jnp.ndarray:
    """The selection bias of PUBLISHED layer ``layer`` (module docstring)."""
    key = jax.random.fold_in(jax.random.PRNGKey(BIAS_KEY), layer)
    return sizes.bias_std * jax.random.normal(
        key, (sizes.n_routed_experts,), jnp.float32)


def experts(sizes: Sizes, layer: int) -> dict:
    """Expert layer ``layer``'s fields of :class:`lm_layers.ExpertLayer`:
    two-matrix ``relu2`` experts, the shared one ungated, the module
    docstring's gate rule."""
    c = sizes
    return dict(
        routed=c.n_routed_experts, k=c.num_experts_per_tok,
        held=held_range(c.experts_held, c.n_routed_experts),
        width=c.moe_intermediate_size, chunk_pairs=c.moe_chunk_pairs,
        block_rows=c.moe_block_rows,
        shared_width=c.moe_shared_expert_intermediate_size, activation=relu2,
        gate_rule=lambda logits, k: top_k_gates(
            jax.nn.sigmoid(logits), k, bias=selection_bias(layer, c),
            scale=c.routed_scaling_factor))


class Block(nn.Module):
    """ONE half behind ONE norm. ``remat``: the half is rematerialised."""

    sizes: Sizes
    layer: int  # the published index
    remat: bool = False

    @nn.compact
    def __call__(self, h):
        c = self.sizes
        kind = c.kind(self.layer)
        with jax.named_scope(SCOPE + kind):
            x = RMSNorm(c.layer_norm_epsilon, name="norm")(h)
            if kind != "moe":
                mixer, name = ((Mamba2, "mamba") if kind == "mamba"
                               else (Attention, "self_attn"))
                return (h + rematerialised(mixer, self.remat)(c, name=name)(x),
                        ) + no_pairs()
        y, pairs, load = feed_forward(x, self.remat, experts(c, self.layer))
        return h + y, pairs, load


@register_language_model("nemotron_h", Sizes)
def NemotronH(sizes: Sizes, remat: bool) -> nn.Module:
    """A layer a published index held; the final norm's epsilon is its own
    key (``norm_eps``)."""
    c = sizes
    return DecoderStack(
        vocab_size=c.vocab_size, hidden_size=c.hidden_size, eps=c.norm_eps,
        blocks=tuple(functools.partial(Block, c, layer, remat)
                     for layer in c.layers))
