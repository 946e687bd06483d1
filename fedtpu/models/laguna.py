"""Laguna — a language model that mixes two kinds of grouped-query softmax
attention in one stack, sliding-window layers three times in four and
full-attention layers the fourth, with DIFFERENT numbers of query heads by
kind, a sigmoid gate a head, and two rotary rules; a dense feed-forward in the
first layer and, after it, a share of softmax-routed experts beside an ungated
shared expert (``huggingface.co/poolside/Laguna-S-2.1``, ``config.json``:
``model_type: laguna``, 48 layers of hidden 3,072).

The layers, as the plain reference (``benchmark/reference/laguna.py``) writes
them too. ``RMSNorm(x) = w * x * rsqrt(mean x^2 + rms_norm_eps)``, the weight
entering as ``w``; no bias anywhere:

- Block ``l``: ``h += Mixer_l(RMSNorm(h))``; ``h += FF_l(RMSNorm(h))``. After
  the last block one RMSNorm, then an untied head.
- ``Mixer_l``, ``H_l = num_attention_heads_per_layer[l]`` query heads (48 in a
  ``full_attention`` layer, 72 in a ``sliding_attention`` one) on
  ``num_key_value_heads`` key-value heads of ``head_dim``: ``q = W_q u [T,
  H_l, hd]``, ``k = W_k u``, ``v = W_v u [T, KH, hd]``, ``g = sigmoid(W_g u)
  [T, H_l]`` (``gating: per-head``); query head ``h`` reads key-value head
  ``h // (H_l / KH)``. Rotary turns in the rotate-half pairing: a full layer
  on the first ``head_dim * partial_rotary_factor`` dimensions with YaRN's
  blended frequencies (:func:`fedtpu.models.lm_layers.yarn_inv_freq`) and
  cos and sin times ``attention_factor``; a sliding layer on the whole head,
  plain, with its own ``rope_theta``. Float32 softmax of ``q.k / sqrt(hd)``
  over the keys ``j <= t`` (full) or ``t - sliding_window < j <= t`` (a query
  sees itself and the ``sliding_window - 1`` before it), by
  :func:`fedtpu.models.lm_layers.grouped_query_attention`, the body of every
  grouped-query layer here: a full layer's core takes the fused kernels on a
  TPU where its shapes fit, a sliding layer's the plain query blocks cut to
  the band, everywhere. ``o = concat_h(g_h * head_h) W_o``. No norm of q or k
  a head: the config has no key for one.
- **A share of the heads.** ``kv_heads_held = (lo, hi)`` says which key-value
  heads live here (all by default); the query heads of those groups follow.
  The layer projects only those (``W_q``, ``W_k``, ``W_v``, ``W_g`` hold their
  columns, ``W_o`` the matching rows), so its output is the partial sum that
  tensor parallelism over heads would all-reduce; on one chip it goes on as
  it is, and nothing stands in for the absent chips.
- ``FF_l`` where ``l`` is in ``mlp_only_layers``: SwiGLU of
  ``intermediate_size``.
- ``FF_l`` otherwise: ``p = softmax(W_r u)`` in float32 over ALL
  ``num_experts`` (no soft cap: ``moe_router_logit_softcapping`` 0); chosen =
  the ``num_experts_per_tok`` largest; ``g = moe_routed_scaling_factor *
  p[chosen] / sum p[chosen]`` on the experts' OUTPUTS; ``y =
  SwiGLU_shared(u) + sum over chosen e that are HELD of g_e SwiGLU_e(u)``,
  the shared expert ungated. ``experts_held = (lo, hi)`` says which experts
  live here (all by default); what the absent ones would add is left out and
  the partial sum goes on. The layer is
  :class:`fedtpu.models.lm_layers.ExpertLayer` with this rule handed in
  (:func:`experts`).
- ``layers_held`` names the published layers built here, in order (all by
  default): ``layer_types``, ``num_attention_heads_per_layer`` and
  ``mlp_only_layers`` are read at those indices, so a cut states the
  published lists and the layers it holds.

The stack around the blocks is :class:`fedtpu.models.lm_layers.DecoderStack`.
Every size is a keyword of the constructor (``RoundConfig.model_args``); the
defaults are the published ones.

Device time is named under ``fed.local_step.fwd_bwd.``: ``embed``,
``window_attention`` (``.core``) for a sliding layer, ``attention``
(``.core``) for a full one (projections, gate, rotary turns and ``W_o`` in
the layer's scope, scores, softmax and ``P v`` alone in its core's), ``dense_ffn``,
``moe`` (``.router``, ``.dispatch``, ``.experts``, ``.combine``), ``lm_loss``.
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
    rematerialised, rope_half, top_k_gates, yarn_inv_freq)

KINDS = ("full_attention", "sliding_attention")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The config's keys by their published names (``rope_parameters``' two
    groups flattened under ``full_`` and ``sliding_``), and what the cut and
    the program add (``layers_held`` on)."""

    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48  # the layers BUILT: len(layers_held)
    # A kind a published layer; None: the published pattern, a full layer
    # every fourth from layer 0 and sliding layers between.
    layer_types: Optional[Tuple[str, ...]] = None
    # Query heads a published layer; None: 48 full, 72 sliding.
    num_attention_heads_per_layer: Optional[Tuple[int, ...]] = None
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    full_rope_theta: float = 500000.0
    full_partial_rotary_factor: float = 0.5
    full_rope_factor: float = 128.0  # YaRN's
    full_original_max_position_embeddings: int = 8192
    full_beta_fast: float = 32.0
    full_beta_slow: float = 1.0
    full_attention_factor: float = 1.4852030263919618
    sliding_rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    mlp_only_layers: Tuple[int, ...] = (0,)
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_routed_scaling_factor: float = 2.5
    layers_held: Optional[Tuple[int, ...]] = None  # published indices; None: all
    experts_held: Optional[Tuple[int, int]] = None  # [lo, hi); None: all
    kv_heads_held: Optional[Tuple[int, int]] = None  # [lo, hi); None: all
    # Read by the local step (fedtpu.core.client): how many rows of a batch
    # go through forward and backward at a time (0: the whole batch).
    micro_batch_rows: int = 0
    attn_q_block: int = 512
    # These two as the cell runs them: of a row of 8,192 tokens a held expert
    # of 8 expects 320 pairs, 2,560 in all.
    moe_chunk_pairs: int = 8192
    moe_block_rows: int = 128

    @property
    def kv_held(self) -> Tuple[int, int]:
        lo, hi = self.kv_heads_held or (0, self.num_key_value_heads)
        if not 0 <= lo < hi <= self.num_key_value_heads:
            raise ValueError(
                f"kv_heads_held={self.kv_heads_held} is no range of the "
                f"{self.num_key_value_heads} key-value heads")
        return int(lo), int(hi)

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
        """``layer``: a published index."""
        if self.layer_types is None:
            return KINDS[0] if layer % 4 == 0 else KINDS[1]
        if not 0 <= layer < len(self.layer_types) or (
                self.layer_types[layer] not in KINDS):
            raise ValueError(
                f"layer_types={self.layer_types} names no kind, {KINDS[0]} or "
                f"{KINDS[1]}, for layer {layer}")
        return self.layer_types[layer]

    def mixer_scope(self, layer: int) -> str:
        """The scope a published layer's mixer runs under (its core under
        ``<scope>.core``)."""
        return "window_attention" if self.kind(layer) == KINDS[1] else "attention"

    def query_heads(self, layer: int) -> int:
        """Query heads of the WHOLE published layer ``layer``."""
        per_layer = self.num_attention_heads_per_layer
        if per_layer is None:
            return 48 if self.kind(layer) == KINDS[0] else 72
        if not 0 <= layer < len(per_layer):
            raise ValueError(
                f"num_attention_heads_per_layer={per_layer} has no entry for "
                f"layer {layer}")
        return per_layer[layer]


class Attention(nn.Module):
    """This chip's share of one attention layer's heads, either kind."""

    sizes: Sizes
    layer: int  # the published index

    @nn.compact
    def __call__(self, x):
        c = self.sizes
        b, t, d = x.shape
        hd, sliding = c.head_dim, c.kind(self.layer) == KINDS[1]
        group, rest = divmod(c.query_heads(self.layer), c.num_key_value_heads)
        if rest:
            raise ValueError(
                f"layer {self.layer}'s {c.query_heads(self.layer)} query heads "
                f"are no multiple of num_key_value_heads={c.num_key_value_heads}")
        lo, hi = c.kv_held
        kh = hi - lo
        q = Linear(kh * group * hd, name="q_proj")(x).reshape(b, t, kh, group, hd)
        k = Linear(kh * hd, name="k_proj")(x).reshape(b, t, kh, hd)
        v = Linear(kh * hd, name="v_proj")(x).reshape(b, t, kh, hd)
        gate = Linear(kh * group, name="g_proj")(x).reshape(b, t, kh, group, 1)
        if sliding:
            rotary = lambda a: rope_half(a, c.sliding_rope_theta, hd)
        else:
            rot = int(hd * c.full_partial_rotary_factor)
            table = yarn_inv_freq(
                c.full_rope_theta, rot, c.full_rope_factor,
                c.full_original_max_position_embeddings, c.full_beta_fast,
                c.full_beta_slow)
            rotary = lambda a: rope_half(
                a, c.full_rope_theta, rot, table, c.full_attention_factor)
        return Linear(d, name="o_proj")(grouped_query_attention(
            q, k, v, rotary, c.attn_q_block, gate=gate,
            window=c.sliding_window if sliding else None,
            scope=c.mixer_scope(self.layer), turn_in_core=False))


def experts(sizes: Sizes, layer: int) -> dict:
    """Expert layer ``layer``'s fields of :class:`lm_layers.ExpertLayer`,
    every layer's alike: the shared expert ungated, the module docstring's
    gate rule."""
    c = sizes
    return dict(
        routed=c.num_experts, k=c.num_experts_per_tok,
        held=held_range(c.experts_held, c.num_experts),
        width=c.moe_intermediate_size, chunk_pairs=c.moe_chunk_pairs,
        block_rows=c.moe_block_rows,
        shared_width=c.shared_expert_intermediate_size,
        gate_rule=lambda logits, k: top_k_gates(
            jax.nn.softmax(logits, axis=-1), k,
            scale=c.moe_routed_scaling_factor))


class Block(nn.Module):
    """``remat``: the mixer and the feed-forward are each rematerialised by
    themselves, so a block's backward pass holds one of them at a time."""

    sizes: Sizes
    layer: int  # the published index
    remat: bool = False

    @nn.compact
    def __call__(self, h):
        c = self.sizes
        x = RMSNorm(c.rms_norm_eps, name="mixer_norm")(h)
        with jax.named_scope(SCOPE + c.mixer_scope(self.layer)):
            h = h + rematerialised(Attention, self.remat)(
                c, self.layer, name="self_attn")(x)
        dense = self.layer in c.mlp_only_layers
        y, pairs, load = feed_forward(
            RMSNorm(c.rms_norm_eps, name="ffn_norm")(h), self.remat,
            experts(c, self.layer),
            dense=("feed_forward", c.intermediate_size) if dense else None)
        return h + y, pairs, load


@register_language_model("laguna", Sizes)
def Laguna(sizes: Sizes, remat: bool) -> nn.Module:
    """A block a layer held, under its PUBLISHED index."""
    c = sizes
    return DecoderStack(
        vocab_size=c.vocab_size, hidden_size=c.hidden_size, eps=c.rms_norm_eps,
        blocks=tuple(functools.partial(Block, c, layer, remat)
                     for layer in c.layers))
