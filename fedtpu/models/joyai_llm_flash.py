"""JoyAI-LLM-Flash — a language model with latent attention, an expert layer
that holds a share of the routed experts, and a multi-token-prediction
module (``huggingface.co/jdopensource/JoyAI-LLM-Flash``, ``config.json``:
``model_type: joyai_llm_flash``, 48B-A2.7B).

The layers, as the plain reference (``benchmark/reference/joyai_llm_flash.py``)
writes them too:

- Block: ``h += Attn(RMSNorm(h))``, ``h += FFN(RMSNorm(h))``.
- Latent attention: ``c_q = RMSNorm(W_qa x)``; per head ``[q_nope, q_rope] =
  W_qb c_q``; ``[c_kv, k_rope] = W_kva x`` with ``k_rope`` one for all
  heads; ``[k_nope, v] = W_kvb RMSNorm(c_kv)``; interleaved RoPE on
  ``q_rope`` and ``k_rope``; causal softmax of ``q.k / sqrt(nope + rope)`` in
  float32; ``W_o`` on the heads' ``P v``. One sequence at a time, by one of
  two bodies of the same function (:func:`attention_core` chooses by the
  backend and the shapes, and counts which): on a TPU, at a length the
  kernels' blocks divide, the fused kernels of
  :mod:`fedtpu.ops.attention_kernels` (a block's scores stay in VMEM,
  forward and backward); everywhere else plain query blocks against the
  keys up to the block's end, so no ``[B, heads, T, T]`` tensor exists.
- Expert layer: ``s = sigmoid(W_r x)`` in float32 over ALL routed experts;
  chosen = top ``k`` of ``s + b``; ``g = scale * s[chosen] / sum(s[chosen])``;
  ``y = SwiGLU_shared(x) + sum over chosen e that are HELD of g_e
  SwiGLU_e(x)``. ``experts_held = (lo, hi)`` says which experts live here
  (expert parallelism's share; all of them by default). What the absent
  experts would add is left out and the partial sum goes on. The layer is
  :class:`fedtpu.models.lm_layers.ExpertLayer`, every language model's, with
  this rule handed in (:func:`experts`); how the held experts' pairs are
  laid out and multiplied is :func:`fedtpu.models.lm_layers.routed_experts`'.
- ``b`` (the config's ``e_score_correction_bias``) is a constant here: a
  normal draw of standard deviation ``bias_std`` from a key fixed by the
  layer's index. It shifts choices, takes no gradient and no round changes
  it (the balancing rule that would train it is no key of the config).
- Prediction module (depth 1): ``h' = W_eh [RMSNorm(Emb(t_{i+1})) ;
  RMSNorm(h_i)]`` with ``h_i`` the last block's output before the final
  norm, one expert-layer block, the model's final norm and head: logits for
  ``t_{i+2}``. Embedding, final norm and head are the model's own.

Embedding, blocks, final norm, head and loss are
:class:`fedtpu.models.lm_layers.DecoderStack`'s; in training this model
returns a ``(sum, count, hits)`` for each head (the local step weighs the
prediction module's by ``mtp_loss_weight``, :mod:`fedtpu.core.client`).
Every size is a keyword of the constructor (``RoundConfig.model_args``); the
defaults are the published ones.

Device time is named under ``fed.local_step.fwd_bwd.``: ``embed``,
``attention`` (``attention.core``: scores, softmax, ``P v``), ``moe``
(``.router``, ``.dispatch``, ``.experts``, ``.combine``), ``dense_ffn``,
``mtp``, ``lm_loss`` (final norm, the head's product, log-softmax).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedtpu.models.lm_layers import (  # noqa: F401 (names the tests and tools reach through this module)
    CORES_TRACED, KEEP, SCOPE, DecoderStack, Linear, RMSNorm, Trunk,
    attention_core, causal_attention, feed_forward, head_sums, held_range,
    register_language_model, rematerialised, top_k_gates)
from fedtpu.ops.losses import shift_targets


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The config's keys by their published names, and what the cut and the
    program add (``experts_held`` on)."""

    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    rms_norm_eps: float = 1e-6
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    experts_held: Optional[Tuple[int, int]] = None  # [lo, hi); None: all
    bias_std: float = 0.01
    # Read by the local step (fedtpu.core.client), not here: the prediction
    # modules' weight in the loss, and how many rows of a batch go through
    # forward and backward at a time (0: the whole batch), their gradients
    # summed in float32: activations of that many rows, not of the batch.
    mtp_loss_weight: float = 0.3
    micro_batch_rows: int = 0
    attn_q_block: int = 512
    moe_chunk_pairs: int = 16384
    moe_block_rows: int = 256


def correction_bias(layer: int, sizes: Sizes) -> jnp.ndarray:
    """The selection bias of expert layer ``layer`` (module docstring)."""
    key = jax.random.fold_in(jax.random.PRNGKey(20260428), layer)
    return sizes.bias_std * jax.random.normal(
        key, (sizes.n_routed_experts,), jnp.float32
    )


def rope(x, theta: float):
    """Interleaved rotary embedding over the last axis of ``x [T, ..., d]``:
    pairs ``(x[2i], x[2i+1])`` turn by ``t * theta^(-2i/d)``."""
    t, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class LatentAttention(nn.Module):
    sizes: Sizes

    @nn.compact
    def __call__(self, x):
        c = self.sizes
        b, t, _ = x.shape
        h, nope, rp, vd = (c.num_attention_heads, c.qk_nope_head_dim,
                           c.qk_rope_head_dim, c.v_head_dim)
        c_q = RMSNorm(c.rms_norm_eps, name="q_norm")(
            Linear(c.q_lora_rank, name="q_a")(x))
        q = Linear(h * (nope + rp), name="q_b")(c_q).reshape(b, t, h, nope + rp)
        kv_a = Linear(c.kv_lora_rank + rp, name="kv_a")(x)
        c_kv, k_rope = kv_a[..., :c.kv_lora_rank], kv_a[..., c.kv_lora_rank:]
        kv = Linear(h * (nope + vd), name="kv_b")(
            RMSNorm(c.rms_norm_eps, name="kv_norm")(c_kv)
        ).reshape(b, t, h, nope + vd)

        def one_sequence(args):
            q, kv, k_rope = args
            with jax.named_scope(SCOPE + "attention.core"):
                return attention_core(
                    q[..., :nope], rope(q[..., nope:], c.rope_theta),
                    kv[..., :nope], rope(k_rope, c.rope_theta), kv[..., nope:],
                    1.0 / math.sqrt(nope + rp), c.attn_q_block,
                )

        # The core's output is kept through a block's rematerialisation
        # (``KEEP``): the layer's backward pass then makes the scores once
        # more, not twice.
        o = jax.lax.map(one_sequence, (q, kv, k_rope))
        return Linear(x.shape[-1], name="o")(o.reshape(b, t, h * vd))


def experts(sizes: Sizes, layer: int) -> dict:
    """Expert layer ``layer``'s fields of :class:`lm_layers.ExpertLayer`: the
    shared experts as one SwiGLU, the module docstring's gate rule."""
    c = sizes
    return dict(
        routed=c.n_routed_experts, k=c.num_experts_per_tok,
        held=held_range(c.experts_held, c.n_routed_experts),
        width=c.moe_intermediate_size, chunk_pairs=c.moe_chunk_pairs,
        block_rows=c.moe_block_rows,
        shared_width=c.moe_intermediate_size * c.n_shared_experts,
        gate_rule=lambda logits, k: top_k_gates(
            jax.nn.sigmoid(logits), k, bias=correction_bias(layer, c),
            scale=c.routed_scaling_factor))


class Block(nn.Module):
    sizes: Sizes
    layer: int
    dense: bool

    @nn.compact
    def __call__(self, h):
        c = self.sizes
        with jax.named_scope(SCOPE + "attention"):
            h = h + LatentAttention(c, name="attn")(
                RMSNorm(c.rms_norm_eps, name="attn_norm")(h))
        # the whole block is rematerialised (``JoyAILLMFlash``), not its halves
        y, pairs, load = feed_forward(
            RMSNorm(c.rms_norm_eps, name="ffn_norm")(h), False,
            experts(c, self.layer),
            dense=("ffn", c.intermediate_size) if self.dense else None)
        return h + y, pairs, load


class JoyAILLMFlashModule(DecoderStack):
    """The stack and, on its last block's output, the prediction modules
    (module docstring): ``prediction_blocks``, a block's constructor a module,
    called with the block's ``name``."""

    prediction_blocks: Tuple[Callable[..., nn.Module], ...] = ()

    @nn.compact
    def __call__(self, tokens, train: bool = False, targets=None):
        """``tokens [B, T]`` int ids. In evaluation the next-token logits
        ``[B, T, vocab]`` in float32. In training, with ``targets [B, T]``
        (the next ids, negative where there is none), a tuple with one
        ``(cross-entropy sum, count, hits)`` a head: the next-token head, then
        the prediction modules', module ``k`` against the targets moved
        ``k + 1`` further."""
        trunk = Trunk(self, tokens)
        if not train:
            return trunk.logits()
        h, heads = trunk.h, [head_sums(trunk.head_rows(trunk.h, targets))]
        for depth, block in enumerate(self.prediction_blocks):
            with jax.named_scope(SCOPE + "mtp"):
                with jax.named_scope(SCOPE + "embed"):
                    nxt = trunk.embed(jnp.roll(tokens, -(depth + 1), axis=1))
                both = jnp.concatenate([
                    RMSNorm(self.eps, name=f"mtp_{depth}_enorm")(nxt),
                    RMSNorm(self.eps, name=f"mtp_{depth}_hnorm")(h),
                ], axis=-1)
                h = Linear(self.hidden_size, name=f"mtp_{depth}_eh_proj")(both)
                h = trunk.run(block(name=f"mtp_{depth}_block"), h)
                heads.append(head_sums(trunk.head_rows(
                    h, shift_targets(targets, depth + 1))))
        trunk.sow()
        return tuple(heads)


@register_language_model("joyai_llm_flash", Sizes)
def JoyAILLMFlash(sizes: Sizes, remat: bool) -> nn.Module:
    """``remat``: every block is rematerialised, whole."""
    c, block = sizes, rematerialised(Block, remat)
    return JoyAILLMFlashModule(
        vocab_size=c.vocab_size, hidden_size=c.hidden_size, eps=c.rms_norm_eps,
        blocks=tuple(functools.partial(block, c, i, i < c.first_k_dense_replace)
                     for i in range(c.num_hidden_layers)),
        prediction_blocks=tuple(
            functools.partial(block, c, c.num_hidden_layers + depth, False)
            for depth in range(c.num_nextn_predict_layers)))
