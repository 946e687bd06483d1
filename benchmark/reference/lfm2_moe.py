"""Plain reference of LFM2-MoE's forward pass as the configuration cuts it
(``configs/lfm2_24b_a2b.json``): jax.numpy, float32, nothing of the program
under test or of flax. ``spec(cfg)`` lists the parameters,
``make_forward(cfg)`` gives ``(params, stats, tokens [B, T], quant) ->
(logits [B, T, vocab], stats)``; the task's loss is ``tasks/next_token.py``'s.

The equations (huggingface.co/LiquidAI/LFM2-24B-A2B, ``config.json``; the
family's published modelling code, ``modeling_lfm2_moe.py``). ``RMSNorm(x) = w
* x * rsqrt(mean x^2 + norm_eps)``:

- The layers held here are ``layers_held`` of the published ``layer_types``,
  in order; the first ``num_dense_layers`` of them have the dense
  feed-forward. Block: ``h += Op(RMSNorm(h))``; ``h += FF(RMSNorm(h))``.
- ``conv``: ``[B | C | X] = W_in u``; ``z_t = sum_j k_j (B x X)_{t - L + 1 +
  j}`` a channel (``L = conv_L_cache`` shifted sums, zeros before the row's
  start); ``W_out (C x z)``. No bias, no activation.
- ``full_attention``: ``q = W_q u`` (``num_attention_heads`` heads of ``hidden
  / heads``), ``k = W_k u``, ``v = W_v u`` (``num_key_value_heads`` heads);
  RMSNorm of each q and k head (own weights); rotary turns over the whole head
  (theta from ``rope_parameters``, dimension ``i`` paired with ``i + head /
  2``); causal softmax of ``q.k / sqrt(head)``, query head ``h`` on key-value
  head ``h // (heads / kv_heads)``; the full ``[T, T]`` scores of one head at
  a time; ``W_o``.
- Dense feed-forward: ``W_2 (silu(W_1 u) x W_3 u)`` of ``intermediate_size``.
- Expert layer: ``s = sigmoid(W_r u)`` over all ``router_width`` experts;
  chosen = the ``num_experts_per_tok`` largest of ``s + b``
  (``use_expert_bias``); ``g = routed_scaling_factor * s[chosen] / (sum
  s[chosen] + 1e-6)`` (``norm_topk_prob``); ``y = sum over chosen e HELD of
  g_e SwiGLU_e(u)``: a loop over the held experts (``num_experts`` of them
  from ``experts_held_from``; one rematerialised ``lax.scan`` body, so that
  they compile once and the backward pass keeps none of their activations),
  every token through each, masked. No shared expert.
- ``b`` is a constant: ``bias_std`` times a standard normal from a key fixed
  by the held layer's index; no gradient, unchanged by a round.
- Embedding, final RMSNorm, the embedding's transpose as the head.

Departures from the published model, each also in the configuration's
``assumed``: (1) the chip's share: the absent experts' part of the sum is left
out and the partial sum goes on; vocabulary rows 0..vocab_size-1 only. (2)
The head is tied to the embedding: the family's code defaults to it and the
config has no key. (3) The convolution and the attention run across the
document boundaries of a packed row. (4) The selection bias is a seeded
constant, not a trained buffer.

``quant`` is the lower-precision control's hook (``lowprec.py``), applied to
both operands of every matrix product and of the convolution's taps,
attention's two products included.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.layers import ident

GATE_EPS = 1e-6
BIAS_KEY = 20261001


def _dims(cfg):
    kinds = [cfg["layer_types"][i] for i in cfg["layers_held"]]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"layers_held={cfg['layers_held']} names {len(kinds)} layers, "
            f"num_hidden_layers is {cfg['num_hidden_layers']}")
    return dict(
        d=cfg["hidden_size"], kinds=kinds, dense=cfg["num_dense_layers"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        hd=cfg["hidden_size"] // cfg["num_attention_heads"],
        taps=cfg["conv_L_cache"], ffn=cfg["intermediate_size"],
        moe=cfg["moe_intermediate_size"], held=cfg["num_experts"],
        experts=cfg["router_width"], vocab=cfg["vocab_size"],
    )


def _swiglu_spec(prefix, d, width):
    fan = lambda n: 1.0 / math.sqrt(n)
    return [(prefix + ("gate", "kernel"), (d, width), fan(d)),
            (prefix + ("up", "kernel"), (d, width), fan(d)),
            (prefix + ("down", "kernel"), (width, d), fan(width))]


def _block_spec(name, m, kind, dense):
    fan = lambda n: 1.0 / math.sqrt(n)
    d, prefix = m["d"], (name,)
    out = [(prefix + ("operator_norm", "scale"), (d,), "ones")]
    if kind == "full_attention":
        at, kv = prefix + ("self_attn",), m["kv_heads"] * m["hd"]
        out += [
            (at + ("q_proj", "kernel"), (d, d), fan(d)),
            (at + ("k_proj", "kernel"), (d, kv), fan(d)),
            (at + ("v_proj", "kernel"), (d, kv), fan(d)),
            (at + ("q_layernorm", "scale"), (m["hd"],), "ones"),
            (at + ("k_layernorm", "scale"), (m["hd"],), "ones"),
            (at + ("out_proj", "kernel"), (d, d), fan(d)),
        ]
    else:
        at = prefix + ("conv",)
        out += [
            (at + ("in_proj", "kernel"), (d, 3 * d), fan(d)),
            (at + ("conv",), (m["taps"], d), fan(m["taps"])),
            (at + ("out_proj", "kernel"), (d, d), fan(d)),
        ]
    out += [(prefix + ("ffn_norm", "scale"), (d,), "ones")]
    if dense:
        return out + _swiglu_spec(prefix + ("feed_forward",), d, m["ffn"])
    w, e, moe = m["moe"], m["held"], prefix + ("moe",)
    return out + [
        (moe + ("router",), (d, m["experts"]), math.sqrt(2.0 / d)),
        (moe + ("experts_gate",), (e, d, w), fan(d)),
        (moe + ("experts_up",), (e, d, w), fan(d)),
        (moe + ("experts_down",), (e, w, d), fan(w)),
    ]


def spec(cfg):
    m = _dims(cfg)
    out = [(("embed", "embedding"), (m["vocab"], m["d"]), 0.02)]
    for i, kind in enumerate(m["kinds"]):
        out += _block_spec(f"layer_{i}", m, kind, i < m["dense"])
    return out + [(("final_norm",), (m["d"],), "ones")], []


def rms_norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rotate_half(x, theta):
    """RoPE over all of ``x [T, d]``: the pair ``(x[i], x[i + d/2])`` of
    position ``t`` turns by ``t / theta^(2i/d)``."""
    t, d = x.shape
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] / (
        theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None, :]
    a, b = x[:, :d // 2], x[:, d // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], axis=1)


def selection_bias(layer, cfg):
    key = jax.random.fold_in(jax.random.PRNGKey(BIAS_KEY), layer)
    return cfg["bias_std"] * jax.random.normal(key, (cfg["router_width"],), jnp.float32)


def make_forward(cfg):
    m = _dims(cfg)
    eps, theta = cfg["norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    first_held, top = cfg["experts_held_from"], cfg["num_experts_per_tok"]
    scaling = cfg["routed_scaling_factor"]

    def short_conv(p, x, quant):
        """``x [T, d]``, one sequence."""
        mm = lambda a, b: quant(a) @ quant(b)
        t, d = x.shape
        bcx = mm(x, p["in_proj"]["kernel"])
        gated = bcx[:, :d] * bcx[:, 2 * d:]  # B x X
        taps = m["taps"]
        padded, w = quant(jnp.pad(gated, ((taps - 1, 0), (0, 0)))), quant(p["conv"])
        z = sum(padded[j:j + t] * w[j] for j in range(taps))
        return mm(bcx[:, d:2 * d] * z, p["out_proj"]["kernel"])

    def attention(p, x, quant):
        """``x [T, d]``, one sequence."""
        mm = lambda a, b: quant(a) @ quant(b)
        t = x.shape[0]
        h, kv, hd = m["heads"], m["kv_heads"], m["hd"]
        q = rms_norm(mm(x, p["q_proj"]["kernel"]).reshape(t, h, hd),
                     p["q_layernorm"]["scale"], eps)
        k = rms_norm(mm(x, p["k_proj"]["kernel"]).reshape(t, kv, hd),
                     p["k_layernorm"]["scale"], eps)
        v = mm(x, p["v_proj"]["kernel"]).reshape(t, kv, hd)
        causal = jnp.tril(jnp.ones((t, t), bool))

        @jax.checkpoint  # one head's [T, T] scores alive at a time
        def one_head(args):
            q_h, k_h, v_h = args
            scores = mm(rotate_half(q_h, theta),
                        rotate_half(k_h, theta).T) / math.sqrt(hd)
            weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return mm(weights, v_h)

        of_query_head = lambda a: jnp.repeat(a.transpose(1, 0, 2), h // kv, axis=0)
        heads = jax.lax.map(
            one_head, (q.transpose(1, 0, 2), of_query_head(k), of_query_head(v)))
        return mm(heads.transpose(1, 0, 2).reshape(t, h * hd),
                  p["out_proj"]["kernel"])

    def swiglu(x, gate, up, down, quant):
        mm = lambda a, b: quant(a) @ quant(b)
        return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)

    def expert_layer(p, x, layer, quant):
        s = jax.nn.sigmoid(quant(x) @ quant(p["router"]))
        bias = selection_bias(layer, cfg) if cfg["use_expert_bias"] else 0.0
        _, chosen = jax.lax.top_k(s + bias, top)
        g = jnp.take_along_axis(s, chosen, axis=1)
        if cfg["norm_topk_prob"]:
            g = g / (jnp.sum(g, axis=1, keepdims=True) + GATE_EPS)
        g = scaling * g

        @jax.checkpoint  # the backward pass keeps no expert's activations
        def one_expert(y, held):
            e, w_gate, w_up, w_down = held
            # this expert's weight for each token: g where it was chosen, else 0
            g_e = jnp.sum(jnp.where(chosen == first_held + e, g, 0.0), axis=1)
            return y + g_e[:, None] * swiglu(x, w_gate, w_up, w_down, quant), None

        held = m["held"]
        return jax.lax.scan(one_expert, jnp.zeros_like(x), (
            jnp.arange(held), p["experts_gate"][:held], p["experts_up"][:held],
            p["experts_down"][:held]))[0]

    def one_block(p, h, layer, quant):
        x = rms_norm(h, p["operator_norm"]["scale"], eps)
        if "self_attn" in p:
            h = h + attention(p["self_attn"], x, quant)
        else:
            h = h + short_conv(p["conv"], x, quant)
        x = rms_norm(h, p["ffn_norm"]["scale"], eps)
        if "feed_forward" in p:
            f = p["feed_forward"]
            return h + swiglu(x, f["gate"]["kernel"], f["up"]["kernel"],
                              f["down"]["kernel"], quant)
        return h + expert_layer(p["moe"], x, layer, quant)

    # A layer's activations are recomputed in the backward pass.
    block = jax.checkpoint(one_block, static_argnums=(2, 3))

    def one_sequence(params, tokens, quant):
        table = params["embed"]["embedding"]
        h = table[tokens]
        for i in range(len(m["kinds"])):
            h = block(params[f"layer_{i}"], h, i, quant)
        return quant(rms_norm(h, params["final_norm"], eps)) @ quant(table.T)

    def forward(params, stats, tokens, quant=ident):
        return jnp.stack([one_sequence(params, row, quant) for row in tokens]), stats

    # The layers by themselves, for the tests that hold the program to them.
    forward.short_conv, forward.attention = short_conv, attention
    forward.expert_layer, forward.block = expert_layer, one_block
    return forward
