"""Plain reference of Laguna's forward pass as the configuration cuts it
(``configs/laguna_s_2_1.json``): jax.numpy, float32, nothing of the program
under test or of flax. ``spec(cfg)`` lists the parameters,
``make_forward(cfg)`` gives ``(params, stats, tokens [B, T], quant) ->
(logits [B, T, vocab], stats)``; the task's loss is ``tasks/next_token.py``'s.

The equations (huggingface.co/poolside/Laguna-S-2.1, ``config.json``).
``RMSNorm(x) = w * x * rsqrt(mean x^2 + rms_norm_eps)``; no bias anywhere:

- The layers held here are ``layers_held`` of the published 48, in order;
  layer ``l``'s kind is ``layer_types[l]``, its feed-forward
  ``mlp_layer_types[l]``. Block: ``h += Mixer(RMSNorm(h))``; ``h +=
  FF(RMSNorm(h))``.
- Mixer, ``H = num_attention_heads_per_layer[l]`` query heads on
  ``num_key_value_heads`` key-value heads of ``head_dim`` (both counts are
  what is HELD here: a share's weights are the columns of its heads): ``q =
  W_q u [T, H, hd]``, ``k = W_k u``, ``v = W_v u [T, KH, hd]``, ``g =
  sigmoid(W_g u) [T, H]`` (``gating: per-head``); query head ``h`` on
  key-value head ``h // (H / KH)``.
- Rotary, dimension ``i`` paired with ``i + rot / 2`` (rotate-half), from
  ``rope_parameters[kind]``. ``full_attention``: ``rot = head_dim *
  partial_rotary_factor``, ``rope_type: yarn``: with ``f_i =
  rope_theta^(-2i/rot)``, ``dim(r) = rot ln(original_max_position_embeddings
  / (2 pi r)) / (2 ln rope_theta)`` the dimension that turns ``r`` times in
  the original context, ``low = max(floor(dim(beta_fast)), 0)``, ``high =
  min(ceil(dim(beta_slow)), rot - 1)`` and ``ramp_i = clip((i - low) / (high
  - low), 0, 1)``, the pair ``i`` turns by ``t (f_i / factor ramp_i + f_i (1
  - ramp_i))``, and cos and sin are multiplied by ``attention_factor``.
  ``sliding_attention``: ``rope_type: default`` over the whole head.
- Scores ``q k^T / sqrt(head_dim)`` under a dense ``[T, T]`` mask, one query
  head's at a time: ``j <= t`` (full) or ``t - sliding_window < j <= t``
  (sliding: a query sees itself and the ``sliding_window - 1`` before it);
  softmax; ``o = concat_h(g_h softmax_h v) W_o``.
- ``dense``: ``W_2 (silu(W_1 u) x W_3 u)`` of ``intermediate_size``.
- ``sparse``: ``p = softmax(W_r u)`` over all ``router_width`` experts
  (``moe_router_logit_softcapping`` 0: none); chosen = the
  ``num_experts_per_tok`` largest; ``g = moe_routed_scaling_factor *
  p[chosen] / sum p[chosen]`` (``norm_topk_prob``), on the experts' outputs
  (``moe_apply_router_weight_on_input`` false); ``y = SwiGLU_shared(u) + sum
  over chosen e HELD of g_e SwiGLU_e(u)``: a loop over the held experts
  (``num_experts`` of them from ``experts_held_from``; one rematerialised
  ``lax.scan`` body), every token through each, masked. The shared expert of
  ``shared_expert_intermediate_size`` has no gate.
- Embedding, final RMSNorm, an untied head (``tie_word_embeddings`` false).

Departures from the published model, each also in the configuration's
``assumed``: (1) the chip's share: the absent experts' part and the absent
heads' part of a layer's sum are left out and the partial sums go on;
vocabulary rows 0..vocab_size-1 only. (2) The gate is a sigmoid of its own
bias-free projection, taken before ``W_o``. (3) SiLU in every SwiGLU; softmax
scores and no selection bias in the router. (4) Attention runs across the
document boundaries of a packed row.

``quant`` is the lower-precision control's hook (``lowprec.py``), applied to
both operands of every matrix product, attention's two products included.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.layers import ident


def _dims(cfg):
    held = cfg["layers_held"]
    if len(held) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"layers_held={held} names {len(held)} layers, "
            f"num_hidden_layers is {cfg['num_hidden_layers']}")
    return dict(
        d=cfg["hidden_size"], hd=cfg["head_dim"],
        kinds=[cfg["layer_types"][i] for i in held],
        heads=[cfg["num_attention_heads_per_layer"][i] for i in held],
        dense=[cfg["mlp_layer_types"][i] == "dense" for i in held],
        kv_heads=cfg["num_key_value_heads"], ffn=cfg["intermediate_size"],
        moe=cfg["moe_intermediate_size"],
        shared=cfg["shared_expert_intermediate_size"],
        held=cfg["num_experts"], experts=cfg["router_width"],
        vocab=cfg["vocab_size"], window=cfg["sliding_window"],
    )


def _swiglu_spec(prefix, d, width):
    fan = lambda n: 1.0 / math.sqrt(n)
    return [(prefix + ("gate", "kernel"), (d, width), fan(d)),
            (prefix + ("up", "kernel"), (d, width), fan(d)),
            (prefix + ("down", "kernel"), (width, d), fan(width))]


def _block_spec(name, m, heads, dense):
    fan = lambda n: 1.0 / math.sqrt(n)
    d, prefix, at = m["d"], (name,), (name, "self_attn")
    wide, kv = heads * m["hd"], m["kv_heads"] * m["hd"]
    out = [
        (prefix + ("mixer_norm", "scale"), (d,), "ones"),
        (at + ("q_proj", "kernel"), (d, wide), fan(d)),
        (at + ("k_proj", "kernel"), (d, kv), fan(d)),
        (at + ("v_proj", "kernel"), (d, kv), fan(d)),
        (at + ("g_proj", "kernel"), (d, heads), fan(d)),
        (at + ("o_proj", "kernel"), (wide, d), fan(wide)),
        (prefix + ("ffn_norm", "scale"), (d,), "ones"),
    ]
    if dense:
        return out + _swiglu_spec(prefix + ("feed_forward",), d, m["ffn"])
    w, e, moe = m["moe"], m["held"], prefix + ("moe",)
    return out + _swiglu_spec(moe + ("shared",), d, m["shared"]) + [
        (moe + ("router",), (d, m["experts"]), math.sqrt(2.0 / d)),
        (moe + ("experts_gate",), (e, d, w), fan(d)),
        (moe + ("experts_up",), (e, d, w), fan(d)),
        (moe + ("experts_down",), (e, w, d), fan(w)),
    ]


def spec(cfg):
    m = _dims(cfg)
    out = [(("embed", "embedding"), (m["vocab"], m["d"]), 1.0)]
    for i, (heads, dense) in enumerate(zip(m["heads"], m["dense"])):
        out += _block_spec(f"layer_{i}", m, heads, dense)
    out += [(("final_norm",), (m["d"],), "ones"),
            (("head",), (m["d"], m["vocab"]), "head")]
    return out, []


def rms_norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def inverse_frequencies(rope, head_dim):
    """``(inv_freq [rot / 2] float32, factor on cos and sin)`` of one of
    ``rope_parameters``' groups (module docstring)."""
    rot = int(head_dim * rope["partial_rotary_factor"])
    theta = float(rope["rope_theta"])
    plain = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}: default or yarn")
    turning = lambda turns: rot * math.log(
        rope["original_max_position_embeddings"] / (turns * 2 * math.pi)
    ) / (2 * math.log(theta))
    low = max(math.floor(turning(rope["beta_fast"])), 0)
    high = min(math.ceil(turning(rope["beta_slow"])), rot - 1)
    ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    blended = plain / rope["factor"] * ramp + plain * (1.0 - ramp)
    return blended.astype(np.float32), float(rope["attention_factor"])


def rotate_half(x, inv_freq, factor):
    """RoPE on the first ``2 len(inv_freq)`` dimensions of ``x [T, d]``: the
    pair ``(x[i], x[i + rot/2])`` of position ``t`` turns by ``t
    inv_freq[i]``, cos and sin times ``factor``; the rest pass."""
    t, half = x.shape[0], len(inv_freq)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos, sin = factor * jnp.cos(angle), factor * jnp.sin(angle)
    a, b = x[:, :half], x[:, half:2 * half]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[:, 2 * half:]], axis=1)


def make_forward(cfg):
    m = _dims(cfg)
    eps, hd, window = cfg["rms_norm_eps"], m["hd"], m["window"]
    first_held, top = cfg["experts_held_from"], cfg["num_experts_per_tok"]
    scaling = cfg["moe_routed_scaling_factor"]
    rotary = {kind: inverse_frequencies(rope, hd)
              for kind, rope in cfg["rope_parameters"].items()}

    def attention(p, x, kind, quant):
        """``x [T, d]``, one sequence; ``kind``: the layer's."""
        mm = lambda a, b: quant(a) @ quant(b)
        # the heads are those whose columns the weights hold: a share's
        t = x.shape[0]
        h, kv = p["g_proj"]["kernel"].shape[1], p["k_proj"]["kernel"].shape[1] // hd
        q = mm(x, p["q_proj"]["kernel"]).reshape(t, h, hd)
        k = mm(x, p["k_proj"]["kernel"]).reshape(t, kv, hd)
        v = mm(x, p["v_proj"]["kernel"]).reshape(t, kv, hd)
        gate = jax.nn.sigmoid(mm(x, p["g_proj"]["kernel"]))  # [T, H]
        at = jnp.arange(t)
        seen = at[None, :] <= at[:, None]
        if kind == "sliding_attention":
            seen = seen & (at[None, :] > at[:, None] - window)
        turn = lambda a: rotate_half(a, *rotary[kind])

        @jax.checkpoint  # one head's [T, T] scores alive at a time
        def one_head(args):
            q_h, k_h, v_h = args
            scores = mm(turn(q_h), turn(k_h).T) / math.sqrt(hd)
            weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return mm(weights, v_h)

        of_query_head = lambda a: jnp.repeat(a.transpose(1, 0, 2), h // kv, axis=0)
        heads = jax.lax.map(
            one_head, (q.transpose(1, 0, 2), of_query_head(k), of_query_head(v)))
        o = heads.transpose(1, 0, 2) * gate[:, :, None]
        return mm(o.reshape(t, h * hd), p["o_proj"]["kernel"])

    def swiglu(x, gate, up, down, quant):
        mm = lambda a, b: quant(a) @ quant(b)
        return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)

    def expert_layer(p, x, quant):
        prob = jax.nn.softmax(quant(x) @ quant(p["router"]), axis=-1)
        g, chosen = jax.lax.top_k(prob, top)
        if cfg["norm_topk_prob"]:
            g = g / jnp.sum(g, axis=1, keepdims=True)
        g = scaling * g
        s = p["shared"]
        y = swiglu(x, s["gate"]["kernel"], s["up"]["kernel"], s["down"]["kernel"],
                   quant)

        @jax.checkpoint  # the backward pass keeps no expert's activations
        def one_expert(y, held):
            e, w_gate, w_up, w_down = held
            # this expert's weight for each token: g where it was chosen, else 0
            g_e = jnp.sum(jnp.where(chosen == first_held + e, g, 0.0), axis=1)
            return y + g_e[:, None] * swiglu(x, w_gate, w_up, w_down, quant), None

        held = m["held"]
        return jax.lax.scan(one_expert, y, (
            jnp.arange(held), p["experts_gate"][:held], p["experts_up"][:held],
            p["experts_down"][:held]))[0]

    def one_block(p, h, kind, quant):
        x = rms_norm(h, p["mixer_norm"]["scale"], eps)
        h = h + attention(p["self_attn"], x, kind, quant)
        x = rms_norm(h, p["ffn_norm"]["scale"], eps)
        if "feed_forward" in p:
            f = p["feed_forward"]
            return h + swiglu(x, f["gate"]["kernel"], f["up"]["kernel"],
                              f["down"]["kernel"], quant)
        return h + expert_layer(p["moe"], x, quant)

    # A layer's activations are recomputed in the backward pass.
    block = jax.checkpoint(one_block, static_argnums=(2, 3))

    def one_sequence(params, tokens, quant):
        h = params["embed"]["embedding"][tokens]
        for i, kind in enumerate(m["kinds"]):
            h = block(params[f"layer_{i}"], h, kind, quant)
        return quant(rms_norm(h, params["final_norm"], eps)) @ quant(params["head"])

    def forward(params, stats, tokens, quant=ident):
        return jnp.stack([one_sequence(params, row, quant) for row in tokens]), stats

    # The layers by themselves, for the tests that hold the program to them.
    forward.attention, forward.expert_layer = attention, expert_layer
    forward.block = one_block
    return forward
