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
  experts would add is left out and the partial sum goes on. The (token,
  expert) pairs that fall on held experts are sorted by expert and
  multiplied group by group, a chunk of ``moe_chunk_pairs`` sorted pairs at
  a time: within a chunk each expert's pairs start at a boundary of
  ``moe_block_rows`` rows, so every block has one expert. On a TPU at the
  published widths the grouped product is
  :mod:`fedtpu.ops.expert_kernels`' (a block's weights read in place, the
  blocks no pair fell in skipped); on the CPU and at the tiny test models'
  widths it is a batched product over all the blocks, each with a copy of
  its expert's matrices (:func:`fedtpu.models.lm_layers.routed_experts`
  says which, ``fedtpu_expert_products_traced_total{body}`` counts it;
  ``jax.lax.ragged_dot`` in neither: the chip's compiler turns that into
  kernels named ``ragged-dot-none``, which carry no scope of the program,
  and a capture would read the experts' time as ``_unscoped_``). A chunk
  past the last pair is skipped, so the work follows the load and no pair
  is ever dropped.
- ``b`` (the config's ``e_score_correction_bias``) is a constant here: a
  normal draw of standard deviation ``bias_std`` from a key fixed by the
  layer's index. It shifts choices, takes no gradient and no round changes
  it (the balancing rule that would train it is no key of the config).
- Prediction module (depth 1): ``h' = W_eh [RMSNorm(Emb(t_{i+1})) ;
  RMSNorm(h_i)]`` with ``h_i`` the last block's output before the final
  norm, one expert-layer block, the model's final norm and head: logits for
  ``t_{i+2}``. Embedding, final norm and head are the model's own.

In training the module takes the targets and returns each head's
cross-entropy ``(sum, count, hits)``, the final norm, head and loss worked
out a row at a time (the local step weighs the prediction module's by
``mtp_loss_weight``, :mod:`fedtpu.core.client`); in evaluation the next-token
logits. Every size is a keyword of the
constructor (``RoundConfig.model_args``); the defaults are the published
ones. ``num_classes`` is the vocabulary's rows held here.

Device time is named under ``fed.local_step.fwd_bwd.``: ``embed``,
``attention`` (``attention.core``: scores, softmax, ``P v``), ``moe``
(``.router``, ``.dispatch``, ``.experts``, ``.combine``), ``dense_ffn``,
``mtp``, ``lm_loss`` (final norm, the head's product, log-softmax).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedtpu.models.lm_layers import (  # noqa: F401 (names the tests and tools reach through this module)
    CORES_TRACED, KEEP, SCOPE, Linear, RMSNorm, SwiGLU, _expert_init, _rms,
    _row_loss_parts, attention_core, causal_attention, held_range,
    routed_experts, sizes_from_keywords)
from fedtpu.models.registry import register
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

    @property
    def held(self) -> Tuple[int, int]:
        return held_range(self.experts_held, self.n_routed_experts)


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


class ExpertLayer(nn.Module):
    """Shared expert plus this chip's share of the routed experts. Returns
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
        shared = SwiGLU(width * c.n_shared_experts, name="shared")(xf)
        router = self.param(
            "router", nn.initializers.variance_scaling(2.0, "fan_in", "normal"),
            (d, c.n_routed_experts))
        w_gate = self.param("experts_gate", _expert_init, (held, d, width))
        w_up = self.param("experts_up", _expert_init, (held, d, width))
        w_down = self.param("experts_down", _expert_init, (held, width, d))

        with jax.named_scope(SCOPE + "moe.router"):
            # Float32 out of the accumulator: exact products of the compute
            # dtype's operands, summed in float32.
            s = jax.nn.sigmoid(jnp.dot(
                xf, router.astype(xf.dtype),
                preferred_element_type=jnp.float32))
            _, chosen = jax.lax.top_k(s + correction_bias(self.layer, c), k)
            picked = (chosen[:, :, None] == jnp.arange(c.n_routed_experts)).any(1)
            s_picked = jnp.where(picked, s, 0.0)
            gates = c.routed_scaling_factor * s_picked / jnp.sum(
                s_picked, axis=-1, keepdims=True)
            # Held experts are a range: a token's gates for them are a slice.
            gates_here = gates[:, lo:hi]  # [n, held], 0 where not chosen
            picked_here = picked[:, lo:hi]

        y, pairs, load = routed_experts(
            xf, shared, gates_here, picked_here, w_gate, w_up, w_down, k,
            c.moe_chunk_pairs, c.moe_block_rows)
        return y.reshape(x.shape), pairs, load


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
        x = RMSNorm(c.rms_norm_eps, name="ffn_norm")(h)
        if self.dense:
            with jax.named_scope(SCOPE + "dense_ffn"):
                y = SwiGLU(c.intermediate_size, name="ffn")(x)
            pairs, load = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)
        else:
            with jax.named_scope(SCOPE + "moe"):
                y, pairs, load = ExpertLayer(c, self.layer, name="moe")(x)
        return h + y, pairs, load


class JoyAILLMFlashModule(nn.Module):
    sizes: Sizes
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = False, targets=None):
        """``tokens [B, T]`` int ids. In evaluation the next-token logits
        ``[B, T, vocab]`` in float32. In training, with ``targets [B, T]``
        (the next ids, negative where there is none), a tuple with one
        ``(cross-entropy sum, count, hits)`` a head: the next-token head, then
        the prediction modules', module ``k`` against the targets moved
        ``k + 1`` further."""
        c = self.sizes
        block = nn.remat(
            Block, policy=jax.checkpoint_policies.save_only_these_names(KEEP)
        ) if self.remat else Block
        embed = nn.Embed(c.vocab_size, c.hidden_size, name="embed",
                         embedding_init=nn.initializers.normal(1.0))
        norm_scale = self.param(
            "final_norm", nn.initializers.ones_init(), (c.hidden_size,))
        head = self.param(
            "head", nn.initializers.variance_scaling(0.02, "fan_in", "normal"),
            (c.hidden_size, c.vocab_size))

        def parts_of(h, depth):
            rows = jax.lax.map(
                lambda a: _row_loss_parts(
                    a[0], a[1], norm_scale, head, c.rms_norm_eps),
                (h, shift_targets(targets, depth)))
            return tuple(jnp.sum(p) for p in rows)

        with jax.named_scope(SCOPE + "embed"):
            h = embed(tokens)
        pairs, loads = [], []
        for i in range(c.num_hidden_layers):
            h, p, l = block(c, i, i < c.first_k_dense_replace,
                            name=f"layer_{i}")(h)
            pairs.append(p)
            loads.append(l)
        if not train:
            with jax.named_scope(SCOPE + "lm_loss"):
                return jnp.dot(
                    _rms(h, norm_scale, c.rms_norm_eps), head.astype(h.dtype),
                    preferred_element_type=jnp.float32)
        heads = [parts_of(h, 0)]
        for depth in range(c.num_nextn_predict_layers):
            with jax.named_scope(SCOPE + "mtp"):
                with jax.named_scope(SCOPE + "embed"):
                    nxt = embed(jnp.roll(tokens, -(depth + 1), axis=1))
                both = jnp.concatenate([
                    RMSNorm(c.rms_norm_eps, name=f"mtp_{depth}_enorm")(nxt),
                    RMSNorm(c.rms_norm_eps, name=f"mtp_{depth}_hnorm")(h),
                ], axis=-1)
                h = Linear(c.hidden_size, name=f"mtp_{depth}_eh_proj")(both)
                h, p, l = block(c, c.num_hidden_layers + depth, False,
                                name=f"mtp_{depth}_block")(h)
                pairs.append(p)
                loads.append(l)
                heads.append(parts_of(h, depth + 1))
        self.sow("counters", "moe_pairs_here", sum(pairs),
                 reduce_fn=lambda _, x: x, init_fn=lambda: 0)
        self.sow("counters", "moe_load_max_over_mean",
                 functools.reduce(jnp.maximum, loads),
                 reduce_fn=lambda _, x: x, init_fn=lambda: 0)
        return tuple(heads)


@register("joyai_llm_flash")
def JoyAILLMFlash(num_classes: int = 129280, remat: bool = False,
                  **sizes) -> nn.Module:
    """``num_classes``: the vocabulary's rows held here; ``sizes``: any field
    of :class:`Sizes` (lists from a JSON file become tuples)."""
    return JoyAILLMFlashModule(sizes_from_keywords(
        Sizes, "joyai_llm_flash", num_classes, sizes), remat=remat)
