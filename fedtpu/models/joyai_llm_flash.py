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
  ``moe_block_rows`` rows, so every block has one expert and the grouped
  product is a batched product over blocks (not ``jax.lax.ragged_dot``: the
  chip's compiler turns that into kernels named ``ragged-dot-none``, which
  carry no scope of the program, and a capture would read the experts' time
  as ``_unscoped_``). A chunk past the last pair is skipped, so the work
  follows the load and no pair is ever dropped.
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
from jax.ad_checkpoint import checkpoint_name

from fedtpu.models.registry import register
from fedtpu.obs.registry import get_global_registry
from fedtpu.ops import attention_kernels
from fedtpu.ops.losses import next_token_ce_parts, shift_targets

SCOPE = "fed.local_step.fwd_bwd."
# What a rematerialised block keeps of its attention core: the output
# [T, heads, v] and, where the kernels run, the rows' log-sum-exp.
KEEP = attention_kernels.KEPT
CORES_TRACED = "fedtpu_attention_cores_traced_total"


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
        lo, hi = self.experts_held or (0, self.n_routed_experts)
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held={self.experts_held} is no range of the "
                f"{self.n_routed_experts} routed experts"
            )
        return int(lo), int(hi)


def correction_bias(layer: int, sizes: Sizes) -> jnp.ndarray:
    """The selection bias of expert layer ``layer`` (module docstring)."""
    key = jax.random.fold_in(jax.random.PRNGKey(20260428), layer)
    return sizes.bias_std * jax.random.normal(
        key, (sizes.n_routed_experts,), jnp.float32
    )


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(), (x.shape[-1],))
        return _rms(x, scale, self.eps)


class Linear(nn.Module):
    """``x @ kernel``, no bias."""

    features: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
            (x.shape[-1], self.features),
        )
        return jnp.dot(x, kernel.astype(x.dtype))


class SwiGLU(nn.Module):
    width: int

    @nn.compact
    def __call__(self, x):
        h = jax.nn.silu(Linear(self.width, name="gate")(x)) * Linear(
            self.width, name="up")(x)
        return Linear(x.shape[-1], name="down")(h)


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


@functools.partial(jax.checkpoint, static_argnums=(5, 6, 7))
def _attend_block(q_nope, q_rope, k_nope, k_rope, v, lo, hi, scale):
    """Queries ``[lo, hi)`` of a sequence ``[T, H, .]`` against the keys up to
    ``hi`` (``k_rope [T, .]`` is every head's): float32 scores and softmax.
    The whole sequence comes in and is cut here, so that what the backward
    pass keeps of a block is the sequence itself and no copy of a prefix."""
    q_nope, q_rope = q_nope[lo:hi], q_rope[lo:hi]
    k_nope, k_rope, v = k_nope[:hi], k_rope[:hi], v[:hi]
    s = jnp.einsum("qhd,khd->hqk", q_nope, k_nope,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("qhd,kd->hqk", q_rope, k_rope,
                       preferred_element_type=jnp.float32)
    seen = jnp.arange(hi)[None, :] <= (lo + jnp.arange(hi - lo))[:, None]
    s = jnp.where(seen[None], s * scale, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v)


def causal_attention(q_nope, q_rope, k_nope, k_rope, v, scale, q_block):
    """Causal attention of one sequence ``[T, H, .]`` in query blocks."""
    t = q_nope.shape[0]
    qb = min(q_block, t)
    if t % qb:
        raise ValueError(f"attn_q_block={q_block} does not divide T={t}")
    return jnp.concatenate([
        _attend_block(q_nope, q_rope, k_nope, k_rope, v, lo, lo + qb, scale)
        for lo in range(0, t, qb)
    ], axis=0)


def attention_core(q_nope, q_rope, k_nope, k_rope, v, scale, q_block):
    """One sequence's causal attention by the body its shapes and the backend
    call for: the fused kernels (:mod:`fedtpu.ops.attention_kernels`) or the
    plain query blocks above, one function of the same operands. Counted in
    the process's registry by the body taken, once a core traced."""
    kernel = attention_kernels.takes(q_nope, q_rope, v)
    get_global_registry().counter(
        CORES_TRACED, "attention cores traced, by the body taken",
        labels={"body": "kernel" if kernel else "plain"}).inc()
    if kernel:
        return attention_kernels.causal_attention(
            q_nope, q_rope, k_nope, k_rope, v, scale)
    return checkpoint_name(causal_attention(
        q_nope, q_rope, k_nope, k_rope, v, scale, q_block), KEEP)


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


def _expert_init(key, shape, dtype=jnp.float32):
    """Stacked ``[experts, in, out]`` leaves: normal over the fan-in."""
    return jax.random.normal(key, shape, dtype) / math.sqrt(shape[1])


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
        n = xf.shape[0]
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

        with jax.named_scope(SCOPE + "moe.dispatch"):
            # Pair p = token * held + expert. Sorted by expert (then token),
            # the pairs on held experts first, the rest behind them.
            key = jnp.where(picked_here, jnp.arange(held)[None, :], held)
            order = jnp.argsort(key.reshape(-1), stable=True).astype(jnp.int32)
            counts = jnp.sum(picked_here, axis=0, dtype=jnp.int32)  # [held]
            ends = jnp.cumsum(counts)
            starts, pairs = ends - counts, ends[-1]

        chunk = min(c.moe_chunk_pairs, n * held)
        n_chunks = -(-n * held // chunk)
        order = jnp.pad(order, (0, n_chunks * chunk - n * held))
        flat_gates = gates_here.reshape(-1)

        block = min(c.moe_block_rows, chunk)
        if chunk % block:
            raise ValueError(
                f"moe_block_rows={c.moe_block_rows} does not divide the chunk "
                f"of {chunk} pairs")
        n_blocks = chunk // block + held  # every expert may end in a part block

        @jax.checkpoint
        def one_chunk(base):
            """Sorted pairs ``[base, base + chunk)`` through their experts:
            ``(gated outputs [rows, d] float32, their tokens [rows])``. Each
            expert's pairs are laid out from a block boundary on, so a block of
            ``block`` rows has ONE expert and the grouped product is a batched
            one over blocks; rows past an expert's last pair are zeros."""
            with jax.named_scope(SCOPE + "moe.dispatch"):
                sizes = jnp.clip(
                    jnp.minimum(ends, base + chunk) - jnp.maximum(starts, base),
                    0, None)  # each expert's pairs in this chunk
                blocks = (sizes + block - 1) // block
                last = jnp.cumsum(blocks)
                expert = jnp.searchsorted(last, jnp.arange(n_blocks), side="right")
                used = expert < held
                expert = jnp.minimum(expert, held - 1)
                within = ((jnp.arange(n_blocks) - (last - blocks)[expert]) * block
                          )[:, None] + jnp.arange(block)[None, :]
                live = (used[:, None] & (within < sizes[expert][:, None])).reshape(-1)
                at = (jnp.cumsum(sizes) - sizes)[expert][:, None] + within
                src = jax.lax.dynamic_slice(order, (base,), (chunk,))[
                    jnp.where(live, at.reshape(-1), 0)]
                token = src // held
                rows = jnp.where(live[:, None], xf[token], 0).reshape(
                    n_blocks, block, d)
                pick = jax.nn.one_hot(expert, held, dtype=rows.dtype)
                of_block = lambda w: jnp.einsum("be,eio->bio", pick, w.astype(rows.dtype))
            with jax.named_scope(SCOPE + "moe.experts"):
                hidden = jax.nn.silu(
                    jnp.einsum("bri,bio->bro", rows, of_block(w_gate))
                ) * jnp.einsum("bri,bio->bro", rows, of_block(w_up))
                out = jnp.einsum("bri,bio->bro", hidden, of_block(w_down),
                                 preferred_element_type=jnp.float32)
            with jax.named_scope(SCOPE + "moe.combine"):
                gate = jnp.where(live, flat_gates[src], 0.0)
                return out.reshape(-1, d) * gate[:, None], token

        def add_chunk(routed, base):
            out, token = one_chunk(base)
            with jax.named_scope(SCOPE + "moe.combine"):
                return routed.at[token].add(out)

        # Chunk by chunk while pairs are left: the first nearly always holds
        # them all, the others are there so that nothing is ever dropped.
        routed = jnp.zeros((n, d), jnp.float32)
        for j in range(n_chunks):
            routed = jax.lax.cond(
                j * chunk < pairs, add_chunk, lambda routed, _: routed,
                routed, jnp.int32(j * chunk))
        with jax.named_scope(SCOPE + "moe.combine"):
            y = (shared.astype(jnp.float32) + routed).astype(x.dtype)
        load = jnp.max(counts) * held / jnp.maximum(pairs, 1)
        return y.reshape(x.shape), pairs, load.astype(jnp.float32)


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


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _row_loss_parts(h, targets, scale, kernel, eps):
    """One row's final norm, head and cross-entropy, ``(sum, count, hits)``;
    the row's float32 logits ``[T, vocab]`` are made again in the backward
    pass, so that no step holds a whole batch of them."""
    with jax.named_scope(SCOPE + "lm_loss"):
        logits = jnp.dot(_rms(h, scale, eps), kernel.astype(h.dtype),
                         preferred_element_type=jnp.float32)
        return next_token_ce_parts(logits, targets)


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
    unknown = set(sizes) - {f.name for f in dataclasses.fields(Sizes)}
    if unknown:
        raise ValueError(
            f"joyai_llm_flash has no size {sorted(unknown)}; the sizes are "
            f"{[f.name for f in dataclasses.fields(Sizes)]}"
        )
    sizes = {k: tuple(v) if isinstance(v, (list, tuple)) else v
             for k, v in sizes.items()}
    return JoyAILLMFlashModule(
        Sizes(vocab_size=num_classes, **sizes), remat=remat)
