"""LFM2-MoE — a language model whose layers are double-gated short
convolutions three times in four and grouped-query softmax attention the
fourth, each followed by a feed-forward that is dense in the leading layers
and, after them, a share of sigmoid-routed experts with no shared expert
(``huggingface.co/LiquidAI/LFM2-24B-A2B``, ``config.json``: ``model_type:
lfm2_moe``, 24B-A2B; the family's published modelling code is
``modeling_lfm2_moe.py``).

The layers, as the plain reference (``benchmark/reference/lfm2_moe.py``)
writes them too. ``RMSNorm(x) = w * x * rsqrt(mean x^2 + norm_eps)``, the
weight entering as ``w``:

- Block ``i``: ``h += Op_i(RMSNorm(h))``; ``h += FF_i(RMSNorm(h))``. After
  the last block one RMSNorm, then the head.
- ``Op_i`` where ``layer_types[i] == "conv"``: ``[B | C | X] = W_in u`` (three
  parts of the hidden width, no bias); ``z_t = sum_j k_j (B x X)_{t - L + 1 +
  j}`` a channel, ``k [L, hidden]`` with ``L = conv_L_cache`` taps, zeros
  before the row's start (:func:`fedtpu.models.lm_layers.causal_conv`,
  ``qwen3_next``'s too); ``Op = W_out (C x z)``. No activation between the
  gates, no state beyond ``L - 1`` tokens. The gates and the taps work in
  float32 from the compute dtype's ``B``, ``C``, ``X`` and leave the compute
  dtype's ``C x z`` (:func:`gated_short_conv`). The convolution's operand is
  the float32 ``B x X``, written once by the gate in front and kept for the
  backward pass beside the taps; the convolution's own rule writes nothing
  else of that size in float32 (its cotangent passes the reversed taps in
  one fusion, no padded copy a tap), and the taps' gradient is summed over
  time and rows in float32 before it is rounded.
- ``Op_i`` where ``layer_types[i] == "full_attention"``: ``q = W_q u``
  (``num_attention_heads`` heads of ``hidden / heads``), ``k = W_k u``, ``v =
  W_v u`` (``num_key_value_heads`` heads), RMSNorm over each q and k head's
  width (own weights), rotary turns in the rotate-half pairing over the whole
  head, causal softmax of ``q.k / sqrt(head)`` in float32, key-value head
  ``j`` serving query heads ``j G .. j G + G - 1``, by
  :func:`fedtpu.models.lm_layers.attention_core` (a key-value head is read by
  its group, not copied; on a TPU, at a length their blocks divide, the fused
  kernels of :mod:`fedtpu.ops.attention_kernels` take the published heads of
  64, a key head's four query heads stacked in one grid step, and the plain
  query blocks run everywhere else), then ``W_o``. No bias, no gate.
- ``FF_i`` where ``i < num_dense_layers``: SwiGLU of ``intermediate_size``.
- ``FF_i`` otherwise: ``s = sigmoid(W_r u)`` in float32 over ALL
  ``num_experts``; chosen = the ``num_experts_per_tok`` largest of ``s + b``;
  ``g = routed_scaling_factor * s[chosen] / (sum s[chosen] + 1e-6)``; ``y =
  sum over chosen e that are HELD of g_e SwiGLU_e(u)``: no shared expert.
  ``experts_held = (lo, hi)`` says which experts live here (all by default);
  what the absent ones would add is left out and the partial sum goes on, so a
  token whose experts are all absent keeps its residual. The layer is
  :class:`fedtpu.models.lm_layers.ExpertLayer` with this rule handed in
  (:func:`experts`).
- ``b`` (``use_expert_bias``) is a constant here: a normal draw of standard
  deviation ``bias_std`` from a key fixed by the layer's index. It shifts
  choices, takes no gradient and no round changes it.
- The head is the embedding's transpose (tied), next-token cross-entropy over
  the vocabulary's rows held here.

The stack around the blocks is :class:`fedtpu.models.lm_layers.DecoderStack`.
Every size is a keyword of the constructor (``RoundConfig.model_args``); the
defaults are the published ones.

Device time is named under ``fed.local_step.fwd_bwd.``: ``embed``,
``short_conv`` (``.proj``: ``W_in``; ``.core``: the two gates and the taps;
``.out``: ``W_out``), ``attention`` (``.core``), ``dense_ffn``, ``moe``
(``.router``, ``.dispatch``, ``.experts``, ``.combine``), ``lm_loss``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedtpu.models.lm_layers import (  # noqa: F401 (KEEP: the tests reach it through this module)
    KEEP, SCOPE, DecoderStack, Linear, RMSNorm, causal_conv, feed_forward,
    grouped_query_attention, held_range, register_language_model,
    rematerialised, rope_half, top_k_gates)

GATE_EPS = 1e-6  # beside the sum of a token's chosen scores
BIAS_KEY = 20261001


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The config's keys by their published names, and what the cut and the
    program add (``experts_held`` on)."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    # A kind a layer, "conv" or "full_attention"; None: the published
    # pattern, attention at layers 2, 6, ... and conv everywhere else.
    layer_types: Optional[Tuple[str, ...]] = None
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    num_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    experts_held: Optional[Tuple[int, int]] = None  # [lo, hi); None: all
    bias_std: float = 0.01
    # Read by the local step (fedtpu.core.client): how many rows of a batch
    # go through forward and backward at a time (0: the whole batch).
    micro_batch_rows: int = 0
    attn_q_block: int = 512
    # These two as the cell runs them: of a micro-batch of 32,768 tokens a
    # held expert of 8 expects 2,048 pairs, two blocks; a chunk holds twice
    # the 16,384 pairs expected in all, so a second chunk all but never runs:
    # chosen for steady rounds, not speed (1.5 x is 5 % faster and swings).
    moe_chunk_pairs: int = 32768
    moe_block_rows: int = 1024

    @property
    def kinds(self) -> Tuple[str, ...]:
        kinds = self.layer_types or tuple(
            "full_attention" if i % 4 == 2 else "conv"
            for i in range(self.num_hidden_layers))
        if len(kinds) != self.num_hidden_layers or set(kinds) - {
                "conv", "full_attention"}:
            raise ValueError(
                f"layer_types={kinds} does not name a kind, conv or "
                f"full_attention, for each of {self.num_hidden_layers} layers")
        return kinds


def selection_bias(layer: int, sizes: Sizes) -> jnp.ndarray:
    """The selection bias of expert layer ``layer`` (module docstring)."""
    key = jax.random.fold_in(jax.random.PRNGKey(BIAS_KEY), layer)
    return sizes.bias_std * jax.random.normal(
        key, (sizes.num_experts,), jnp.float32)


def gated_short_conv(b, c, x, taps):
    """``c * conv(b * x)`` of one sequence, ``b, c, x [T, channels]``, ``taps
    [L, channels]``: the gates and the taps' sums in float32, the result in
    the operands' dtype."""
    f32 = lambda a: a.astype(jnp.float32)
    return (f32(c) * causal_conv(f32(b) * f32(x), taps)).astype(x.dtype)


class ShortConv(nn.Module):
    sizes: Sizes

    @nn.compact
    def __call__(self, x):
        c = self.sizes
        d = x.shape[-1]
        taps = self.param(
            "conv", nn.initializers.normal(1.0 / math.sqrt(c.conv_L_cache)),
            (c.conv_L_cache, d))
        with jax.named_scope(SCOPE + "short_conv.proj"):
            bcx = Linear(3 * d, name="in_proj")(x)
        with jax.named_scope(SCOPE + "short_conv.core"):
            y = jax.vmap(gated_short_conv, in_axes=(0, 0, 0, None))(
                bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:], taps)
        with jax.named_scope(SCOPE + "short_conv.out"):
            return Linear(d, name="out_proj")(y)


class Attention(nn.Module):
    sizes: Sizes

    @nn.compact
    def __call__(self, x):
        c = self.sizes
        b, t, d = x.shape
        h, kh = c.num_attention_heads, c.num_key_value_heads
        hd, group = d // h, h // kh
        if h * hd != d or kh * group != h:
            raise ValueError(
                f"num_attention_heads={h} has to divide hidden_size={d} and be "
                f"a multiple of num_key_value_heads={kh}")
        q = RMSNorm(c.norm_eps, name="q_layernorm")(
            Linear(h * hd, name="q_proj")(x).reshape(b, t, kh, group, hd))
        k = RMSNorm(c.norm_eps, name="k_layernorm")(
            Linear(kh * hd, name="k_proj")(x).reshape(b, t, kh, hd))
        v = Linear(kh * hd, name="v_proj")(x).reshape(b, t, kh, hd)

        rotary = lambda a: rope_half(a, c.rope_theta, hd)
        return Linear(d, name="out_proj")(
            grouped_query_attention(q, k, v, rotary, c.attn_q_block))


def experts(sizes: Sizes, layer: int) -> dict:
    """Expert layer ``layer``'s fields of :class:`lm_layers.ExpertLayer`: no
    shared expert, the module docstring's gate rule."""
    c = sizes
    return dict(
        routed=c.num_experts, k=c.num_experts_per_tok,
        held=held_range(c.experts_held, c.num_experts),
        width=c.moe_intermediate_size, chunk_pairs=c.moe_chunk_pairs,
        block_rows=c.moe_block_rows,
        gate_rule=lambda logits, k: top_k_gates(
            jax.nn.sigmoid(logits), k, bias=selection_bias(layer, c),
            scale=c.routed_scaling_factor, eps=GATE_EPS))


class Block(nn.Module):
    """``remat``: the operator and the feed-forward are each rematerialised
    by themselves, so a block's backward pass holds one of them at a time."""

    sizes: Sizes
    layer: int
    remat: bool = False

    @nn.compact
    def __call__(self, h):
        c = self.sizes
        x = RMSNorm(c.norm_eps, name="operator_norm")(h)
        if c.kinds[self.layer] == "full_attention":
            with jax.named_scope(SCOPE + "attention"):
                h = h + rematerialised(Attention, self.remat)(
                    c, name="self_attn")(x)
        else:
            with jax.named_scope(SCOPE + "short_conv"):
                h = h + rematerialised(ShortConv, self.remat)(c, name="conv")(x)
        dense = self.layer < c.num_dense_layers
        y, pairs, load = feed_forward(
            RMSNorm(c.norm_eps, name="ffn_norm")(h), self.remat,
            experts(c, self.layer),
            dense=("feed_forward", c.intermediate_size) if dense else None)
        return h + y, pairs, load


@register_language_model("lfm2_moe", Sizes)
def Lfm2Moe(sizes: Sizes, remat: bool) -> nn.Module:
    """The head is tied; the embedding starts at the published 0.02."""
    c = sizes
    return DecoderStack(
        vocab_size=c.vocab_size, hidden_size=c.hidden_size, eps=c.norm_eps,
        blocks=tuple(functools.partial(Block, c, i, remat)
                     for i in range(c.num_hidden_layers)),
        tied_head=True, embedding_init=nn.initializers.normal(0.02))
