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
  token whose experts are all absent keeps its residual. The routed path is
  :func:`fedtpu.models.lm_layers.routed_experts` (its grouped
  products: :mod:`fedtpu.ops.expert_kernels` on a TPU at the published
  widths, a batched product over blocks elsewhere).
- ``b`` (``use_expert_bias``) is a constant here: a normal draw of standard
  deviation ``bias_std`` from a key fixed by the layer's index. It shifts
  choices, takes no gradient and no round changes it.
- The head is the embedding's transpose (tied), next-token cross-entropy over
  the vocabulary's rows held here.

In training the module takes the targets and returns ``((cross-entropy sum,
count, hits),)``, the final norm, head and loss worked out a row at a time;
in evaluation the next-token logits. Every size is a keyword of the
constructor (``RoundConfig.model_args``); the defaults are the published ones.
``num_classes`` is the vocabulary's rows held here.

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

from fedtpu.models.lm_layers import (
    KEEP, SCOPE, Linear, RMSNorm, SwiGLU, _expert_init, _rms, _row_loss_parts,
    causal_conv, grouped_query_attention, held_range, rope_half,
    routed_experts, sizes_from_keywords)
from fedtpu.models.registry import register

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
    def held(self) -> Tuple[int, int]:
        return held_range(self.experts_held, self.num_experts)

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


class ExpertLayer(nn.Module):
    """This chip's share of the routed experts, and nothing else. Returns
    ``(y, pairs, load)``: the pairs computed here and the busiest held
    expert's load over the held experts' mean load."""

    sizes: Sizes
    layer: int

    @nn.compact
    def __call__(self, x):
        c = self.sizes
        lo, hi = c.held
        held, k = hi - lo, c.num_experts_per_tok
        d, width = x.shape[-1], c.moe_intermediate_size
        xf = x.reshape(-1, d)
        router = self.param(
            "router", nn.initializers.variance_scaling(2.0, "fan_in", "normal"),
            (d, c.num_experts))
        w_gate = self.param("experts_gate", _expert_init, (held, d, width))
        w_up = self.param("experts_up", _expert_init, (held, d, width))
        w_down = self.param("experts_down", _expert_init, (held, width, d))

        with jax.named_scope(SCOPE + "moe.router"):
            s = jax.nn.sigmoid(jnp.dot(
                xf, router.astype(xf.dtype),
                preferred_element_type=jnp.float32))
            _, chosen = jax.lax.top_k(s + selection_bias(self.layer, c), k)
            picked = (chosen[:, :, None] == jnp.arange(c.num_experts)).any(1)
            s_picked = jnp.where(picked, s, 0.0)
            gates = c.routed_scaling_factor * s_picked / (
                jnp.sum(s_picked, axis=-1, keepdims=True) + GATE_EPS)
            # Held experts are a range: a token's gates for them are a slice.
            gates_here, picked_here = gates[:, lo:hi], picked[:, lo:hi]

        y, pairs, load = routed_experts(
            xf, None, gates_here, picked_here, w_gate, w_up, w_down, k,
            c.moe_chunk_pairs, c.moe_block_rows)
        return y.reshape(x.shape), pairs, load


class Block(nn.Module):
    """``remat``: the operator and the feed-forward are each rematerialised
    by themselves, so a block's backward pass holds one of them at a time."""

    sizes: Sizes
    layer: int
    remat: bool = False

    @nn.compact
    def __call__(self, h):
        c = self.sizes
        part = lambda cls: nn.remat(
            cls, policy=jax.checkpoint_policies.save_only_these_names(KEEP)
        ) if self.remat else cls
        x = RMSNorm(c.norm_eps, name="operator_norm")(h)
        if c.kinds[self.layer] == "full_attention":
            with jax.named_scope(SCOPE + "attention"):
                h = h + part(Attention)(c, name="self_attn")(x)
        else:
            with jax.named_scope(SCOPE + "short_conv"):
                h = h + part(ShortConv)(c, name="conv")(x)
        x = RMSNorm(c.norm_eps, name="ffn_norm")(h)
        if self.layer < c.num_dense_layers:
            with jax.named_scope(SCOPE + "dense_ffn"):
                y = part(SwiGLU)(c.intermediate_size, name="feed_forward")(x)
            pairs, load = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)
        else:
            with jax.named_scope(SCOPE + "moe"):
                y, pairs, load = part(ExpertLayer)(c, self.layer, name="moe")(x)
        return h + y, pairs, load


class Lfm2MoeModule(nn.Module):
    sizes: Sizes
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = False, targets=None):
        """``tokens [B, T]`` int ids. In evaluation the next-token logits
        ``[B, T, vocab]`` in float32. In training, with ``targets [B, T]``
        (the next ids, negative where there is none), ``((cross-entropy sum,
        count, hits),)``: one head."""
        c = self.sizes
        embed = nn.Embed(c.vocab_size, c.hidden_size, name="embed",
                         embedding_init=nn.initializers.normal(0.02))
        norm_scale = self.param(
            "final_norm", nn.initializers.ones_init(), (c.hidden_size,))
        with jax.named_scope(SCOPE + "embed"):
            h = embed(tokens)
        head = embed.embedding.T  # tied
        pairs, loads = [], []
        for i in range(c.num_hidden_layers):
            h, p, l = Block(c, i, self.remat, name=f"layer_{i}")(h)
            pairs.append(p)
            loads.append(l)
        if not train:
            with jax.named_scope(SCOPE + "lm_loss"):
                return jnp.dot(
                    _rms(h, norm_scale, c.norm_eps), head.astype(h.dtype),
                    preferred_element_type=jnp.float32)
        rows = jax.lax.map(
            lambda a: _row_loss_parts(a[0], a[1], norm_scale, head, c.norm_eps),
            (h, targets))
        self.sow("counters", "moe_pairs_here", sum(pairs),
                 reduce_fn=lambda _, x: x, init_fn=lambda: 0)
        self.sow("counters", "moe_load_max_over_mean",
                 functools.reduce(jnp.maximum, loads),
                 reduce_fn=lambda _, x: x, init_fn=lambda: 0)
        return (tuple(jnp.sum(p) for p in rows),)


@register("lfm2_moe")
def Lfm2Moe(num_classes: int = 65536, remat: bool = False,
            **sizes) -> nn.Module:
    """``num_classes``: the vocabulary's rows held here; ``sizes``: any field
    of :class:`Sizes` (lists from a JSON file become tuples)."""
    return Lfm2MoeModule(sizes_from_keywords(
        Sizes, "lfm2_moe", num_classes, sizes), remat=remat)
