"""Granite-hybrid — a DENSE language model of two-half layers whose mixer is a
Mamba-2 state-space mixer nine layers in ten and grouped-query softmax
attention WITHOUT positions the tenth, a SwiGLU in every layer, four scale
multipliers and a tied head
(``huggingface.co/ibm-granite/granite-4.0-h-micro``, ``config.json``:
``model_type: granitemoehybrid``, 40 layers of hidden 2,048, ``layer_types``
``mamba`` but for ``attention`` at layers 5, 15, 25 and 35; ``num_local_experts``
0: the family's dense member).

The layers, as the plain reference (``benchmark/reference/granite_hybrid.py``)
writes them too. ``RMSNorm(x) = w * x * rsqrt(mean x^2 + rms_norm_eps)``, the
weight entering as ``w``; no bias but the convolution's (``attention_bias``
false, ``mamba_proj_bias`` false, ``mamba_conv_bias`` true):

- ``h_0 = embedding_multiplier * Emb(t)`` (12). Layer ``l`` of kind
  ``layer_types[l]``: ``h += residual_multiplier * Mixer_l(RMSNorm(h))``, then
  ``h += residual_multiplier * MLP(RMSNorm(h))`` (0.22, twice). After the
  last layer one RMSNorm, then ``logits = (h Emb^T) / logits_scaling`` (8;
  ``tie_word_embeddings`` true): the embedding's gradient has a factor 12 from
  below and 1/8 from above.
- ``mamba``: :class:`fedtpu.models.mamba2.Mamba2`, the mixer this model shares
  with ``nemotron_h`` (its equations and the chunked training form of the
  recurrence are that module's docstring), at ``H = mamba_n_heads`` 64 heads
  of ``P = mamba_d_head`` 64, ``d_in = H P = mamba_expand x hidden = 4,096``,
  ``G = mamba_n_groups`` ONE group of ``B`` and ``C`` that every head reads,
  a state of ``N = mamba_d_state`` 128, a biased convolution of
  ``mamba_d_conv`` 4, chunks of ``mamba_chunk_size`` 256, no limit on ``dt``
  (the config has no ``time_step_limit``), the gate BEFORE the norm and the
  norm over ALL ``d_in`` channels. The published chunk of 256 is not the one
  chunk :mod:`fedtpu.ops.ssd_kernels` is built for, so the recurrence takes
  the plain chunks on a TPU too, and the run says so once.
- **A share of the state-space heads.** ``mamba_heads_held = (lo, hi)`` says
  which heads live here (all by default): the mixer holds their columns of
  ``z``, ``x`` and ``dt``, ALL of ``B`` and ``C``, and ``W_out``'s matching
  rows, and its gated norm runs on the mean square of the channels HELD (one
  chip runs its layer without the exchange; the adds-up test hands the mixer
  what the all-reduce of the shares' sums of squares would deliver). The
  first share in this repo that carries a statistic and not only a partial
  sum.
- ``attention``: ``num_attention_heads`` 32 query heads on
  ``num_key_value_heads`` 8 key-value heads of ``hidden / heads = 64``, NO
  rotary turn and no other position term (``position_embedding_type: nope``;
  another value is refused), float32 softmax of ``attention_multiplier * q.k``
  (0.015625 = 1/64, where ``1 / sqrt(64)`` is 1/8) over the keys ``j <= t``,
  ``W_o``: :func:`fedtpu.models.lm_layers.grouped_query_attention` with no
  rotary rule and the scale handed in (its core the fused kernels on a TPU,
  the plain query blocks elsewhere). ``kv_heads_held = (lo, hi)`` says which
  key-value heads live here (all by default), their query heads with them
  (Laguna's way): ``W_q``, ``W_k``, ``W_v`` hold their columns, ``W_o`` the
  matching rows, and the output is the partial sum that tensor parallelism
  over heads would all-reduce.
- ``MLP(u) = W_down (silu(W_gate u) * W_up u)`` at
  ``shared_intermediate_size`` 8,192, whole on every chip (the family stores
  gate and up as one ``input_linear`` of 16,384: the same numbers).
  ``num_local_experts`` 0 and ``num_experts_per_tok`` 0: no router is built,
  every block routes nothing (:func:`fedtpu.models.lm_layers.no_pairs`), and
  sizes that state experts are REFUSED: the family's sparse members put a
  router and experts beside this SwiGLU, which is not written here.
- ``layers_held`` names the published layers built here, in order (all by
  default): ``layer_types`` is read at those indices, so a cut states the
  published forty and the layers it holds.

The stack around the blocks is :class:`fedtpu.models.lm_layers.DecoderStack`
with its two factors. Every size is a keyword of the constructor
(``RoundConfig.model_args``); the defaults are the published ones.

Device time is named under ``fed.local_step.fwd_bwd.``: ``embed``, ``mamba``
(``.proj``, ``.conv``, ``.core``, ``.out``), ``attention`` (``.core``),
``dense_ffn``, ``lm_loss``: the scopes Nemotron-H's, LFM2's and Laguna's
layers name. A half's norm runs under its half's scope.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax

from fedtpu.models.lm_layers import (
    SCOPE, DecoderStack, Linear, RMSNorm, feed_forward,
    grouped_query_attention, held_range, register_language_model,
    rematerialised)
from fedtpu.models.mamba2 import Mamba2

KINDS = ("mamba", "attention")
PUBLISHED_LAYER_TYPES = (KINDS[0],) * 5 + (KINDS[1],) + (
    (KINDS[0],) * 9 + (KINDS[1],)) * 3 + (KINDS[0],) * 4


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The config's keys by their published names, and what the cut and the
    program add (``layers_held`` on)."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40  # the layers BUILT: len(layers_held)
    # A kind a published layer, as published: attention at layers 5, 15, 25
    # and 35. A configuration's file hands its own list (``model_args``); no
    # rule makes up the kind of a layer the list does not name.
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = True
    mamba_chunk_size: int = 256
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    position_embedding_type: str = "nope"
    shared_intermediate_size: int = 8192
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    embedding_multiplier: float = 12.0
    logits_scaling: float = 8.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    rms_norm_eps: float = 1e-5
    layers_held: Optional[Tuple[int, ...]] = None  # published indices; None: all
    mamba_heads_held: Optional[Tuple[int, int]] = None  # [lo, hi); None: all
    kv_heads_held: Optional[Tuple[int, int]] = None  # [lo, hi); None: all
    # Read by the local step (fedtpu.core.client): how many rows of a batch
    # go through forward and backward at a time (0: the whole batch).
    micro_batch_rows: int = 0
    attn_q_block: int = 512

    def __post_init__(self):
        if self.num_local_experts or self.num_experts_per_tok:
            raise ValueError(
                f"num_local_experts={self.num_local_experts}, "
                f"num_experts_per_tok={self.num_experts_per_tok}: "
                "granite_hybrid builds the family's DENSE member, a SwiGLU "
                "in every layer and no router; the sparse members' experts "
                "beside it are not written here, and are not guessed at")
        if self.position_embedding_type != "nope":
            raise ValueError(
                f"position_embedding_type={self.position_embedding_type!r}: "
                "granite_hybrid's attention has no position term ('nope'); "
                "no rotary rule is written for it")
        if self.mamba_n_heads * self.mamba_d_head != (
                self.mamba_expand * self.hidden_size):
            raise ValueError(
                f"mamba_n_heads x mamba_d_head = "
                f"{self.mamba_n_heads * self.mamba_d_head} is not "
                f"mamba_expand x hidden_size = "
                f"{self.mamba_expand * self.hidden_size}")

    @property
    def kv_held(self) -> Tuple[int, int]:
        return held_range(self.kv_heads_held, self.num_key_value_heads,
                          "kv_heads_held", "key-value heads")

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
        """``layer``: a published index. The scope its mixer runs under."""
        if not 0 <= layer < len(self.layer_types) or (
                self.layer_types[layer] not in KINDS):
            raise ValueError(
                f"layer_types={self.layer_types} names no kind, {KINDS[0]} or "
                f"{KINDS[1]}, for layer {layer}")
        return self.layer_types[layer]


def mamba(sizes: Sizes) -> dict:
    """A ``mamba`` layer's fields of :class:`fedtpu.models.mamba2.Mamba2`."""
    c = sizes
    return dict(
        heads=c.mamba_n_heads, head_dim=c.mamba_d_head, groups=c.mamba_n_groups,
        state=c.mamba_d_state, conv_kernel=c.mamba_d_conv,
        chunk=c.mamba_chunk_size, eps=c.rms_norm_eps,
        conv_bias=c.mamba_conv_bias, heads_held=c.mamba_heads_held)


class Attention(nn.Module):
    """This chip's share of the attention layer's heads."""

    sizes: Sizes

    @nn.compact
    def __call__(self, x):
        c = self.sizes
        b, t, d = x.shape
        hd, rest = divmod(c.hidden_size, c.num_attention_heads)
        group, odd = divmod(c.num_attention_heads, c.num_key_value_heads)
        if rest or odd:
            raise ValueError(
                f"num_attention_heads={c.num_attention_heads} divides neither "
                f"hidden_size={c.hidden_size} into heads nor into "
                f"num_key_value_heads={c.num_key_value_heads} groups")
        lo, hi = c.kv_held
        kh = hi - lo
        q = Linear(kh * group * hd, name="q_proj")(x).reshape(b, t, kh, group, hd)
        k = Linear(kh * hd, name="k_proj")(x).reshape(b, t, kh, hd)
        v = Linear(kh * hd, name="v_proj")(x).reshape(b, t, kh, hd)
        return Linear(d, name="o_proj")(grouped_query_attention(
            q, k, v, None, c.attn_q_block, scale=c.attention_multiplier))


class Block(nn.Module):
    """Two halves, each behind its norm and scaled by ``residual_multiplier``.
    ``remat``: the mixer and the SwiGLU are each rematerialised by themselves,
    so a block's backward pass holds one of them at a time."""

    sizes: Sizes
    layer: int  # the published index
    remat: bool = False

    @nn.compact
    def __call__(self, h):
        c = self.sizes
        kind = c.kind(self.layer)
        with jax.named_scope(SCOPE + kind):
            x = RMSNorm(c.rms_norm_eps, name="mixer_norm")(h)
            if kind == "mamba":
                y = rematerialised(Mamba2, self.remat)(**mamba(c), name="mamba")(x)
            else:
                y = rematerialised(Attention, self.remat)(c, name="self_attn")(x)
            h = h + c.residual_multiplier * y
        with jax.named_scope(SCOPE + "dense_ffn"):
            x = RMSNorm(c.rms_norm_eps, name="ffn_norm")(h)
        y, pairs, load = feed_forward(
            x, self.remat, None, dense=("shared_mlp", c.shared_intermediate_size))
        return h + c.residual_multiplier * y, pairs, load


@register_language_model("granite_hybrid", Sizes)
def GraniteHybrid(sizes: Sizes, remat: bool) -> nn.Module:
    """A block a layer held, under its PUBLISHED index; the stream scaled on
    entry, the logits on exit, the head the embedding's transpose. The
    embedding starts at a deviation of 0.03: with the head tied and the
    stream 12 times the embedding, the logit of the token a position has just
    read grows with the deviation's SQUARE (15.9 at 0.08, a first loss of 15;
    3.2 at 0.03, a first loss near ``ln(vocabulary)``: PERF.md §6, PR 51)."""
    c = sizes
    return DecoderStack(
        vocab_size=c.vocab_size, hidden_size=c.hidden_size, eps=c.rms_norm_eps,
        blocks=tuple(functools.partial(Block, c, layer, remat)
                     for layer in c.layers),
        tied_head=True, embedding_init=nn.initializers.normal(0.03),
        embedding_multiplier=c.embedding_multiplier,
        logits_scaling=c.logits_scaling)
