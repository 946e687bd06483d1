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
- ``M``: :class:`fedtpu.models.mamba2.Mamba2`, the mixer this model shares
  with ``granite_hybrid`` (its equations, the chunked training form of the
  recurrence and its two bodies are that module's docstring), at ``H =
  mamba_num_heads`` heads of ``P = mamba_head_dim``, ``d_in = H P`` (4,096:
  NOT ``expand`` x hidden, 5,376, which would be 84 heads of 64), ``G =
  n_groups`` groups on a state of ``N = ssm_state_size``, a convolution of
  ``conv_kernel`` taps with a bias, chunks of ``chunk_size``, every head
  held, the gated norm over each group's ``d_in / G`` channels (512), no
  limit on ``dt`` (the config has no ``time_step_limit``). On a TPU at the
  published sizes on 8,192 tokens the recurrence runs through
  :mod:`fedtpu.ops.ssd_kernels`, the plain chunks everywhere else.
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
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedtpu.models.lm_layers import (
    SCOPE, DecoderStack, Linear, RMSNorm, feed_forward,
    grouped_query_attention, held_range, no_pairs, register_language_model,
    relu2, rematerialised, rope_half, top_k_gates)
from fedtpu.models.mamba2 import Mamba2

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
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


def mamba(sizes: Sizes) -> dict:
    """A Mamba-2 layer's fields of :class:`fedtpu.models.mamba2.Mamba2`,
    every head held."""
    c = sizes
    return dict(
        heads=c.mamba_num_heads, head_dim=c.mamba_head_dim, groups=c.n_groups,
        state=c.ssm_state_size, conv_kernel=c.conv_kernel, chunk=c.chunk_size,
        eps=c.layer_norm_epsilon, conv_bias=c.use_conv_bias,
        step_min=c.time_step_min, step_max=c.time_step_max,
        step_floor=c.time_step_floor)


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
            if kind == "mamba":
                return (h + rematerialised(Mamba2, self.remat)(
                    **mamba(c), name="mamba")(x),) + no_pairs()
            if kind == "attention":
                return (h + rematerialised(Attention, self.remat)(
                    c, name="self_attn")(x),) + no_pairs()
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
