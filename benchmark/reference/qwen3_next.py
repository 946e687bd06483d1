"""Plain reference of Qwen3-Next's forward pass as the configuration cuts it
(``configs/qwen3_next_80b_a3b.json``): jax.numpy, float32, nothing of the
program under test or of flax. ``spec(cfg)`` lists the parameters,
``make_forward(cfg)`` gives ``(params, stats, tokens [B, T], quant) ->
(logits [B, T, vocab], stats)``; the task's loss is ``tasks/next_token.py``'s.

The equations (huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct,
``config.json``; the family's published modelling code,
``modeling_qwen3_next.py``). ``Norm(x) = x * rsqrt(mean x^2 + eps) * (1 + w)``
is every norm but the gated one:

- Block ``i``: ``h += Mixer_i(Norm(h))``; ``h += MoE(Norm(h))``; ``Mixer_i``
  is the gated softmax layer where ``(i + 1) % full_attention_interval == 0``,
  else gated DeltaNet.
- Gated DeltaNet: ``[q, k, v, z] = W_qkvz x``, ``[b, a] = W_ba x``; a causal
  depthwise convolution (width ``linear_conv_kernel_dim``, no bias) over the
  channels of ``[q, k, v]``, then SiLU; ``q, k`` divided by ``sqrt(sum of
  squares + 1e-6)``, ``q`` scaled by ``dk^-0.5``; ``beta_t = sigmoid(b_t)``,
  ``g_t = -exp(A_log) softplus(a_t + dt_bias)``, one a value head; value head
  ``h`` reads key head ``h // (Hv / Hk)``. The state ``S`` (``dk x dv``, from
  0) TOKEN BY TOKEN, exactly as written and with no chunk algebra, so that it
  owes nothing to the program's form: ``S' = exp(g_t) S``; ``u = beta_t (v_t -
  S'^T k_t)``; ``S = S' + k_t u^T``; ``o_t = S^T q_t`` (:func:`delta_rule`: a
  ``lax.scan`` over blocks of tokens around a rematerialised ``lax.scan`` over
  a block's tokens, so the backward pass holds a state a block and, for one
  block at a time, a state a token). Output ``o_t * rsqrt(mean o_t^2 + eps) *
  w * SiLU(z_t)``, then ``W_o``.
- Gated softmax layer: ``[q, gate] = W_q x`` a head, ``k = W_k x``, ``v = W_v
  x``; ``Norm`` of ``q`` and ``k`` over ``head_dim``; rotary turns (theta from
  the config, dimension ``i`` paired with ``i + rot / 2``) on the first ``rot
  = head_dim * partial_rotary_factor`` dimensions; causal softmax of ``q.k /
  sqrt(head_dim)``, query head ``h`` on key-value head ``h // (heads /
  kv_heads)``; the full ``[T, T]`` scores of one head at a time; ``o *
  sigmoid(gate)``; ``W_o``.
- Expert layer: ``p = softmax(W_r x)`` over all ``router_width`` experts;
  chosen = the ``num_experts_per_tok`` largest; ``g = p[chosen] / sum
  p[chosen]``; ``y = sigmoid(w_s . x) SwiGLU_shared(x) + sum over chosen e
  HELD of g_e SwiGLU_e(x)``: a loop over the held experts (``num_experts`` of
  them from ``experts_held_from``; one rematerialised ``lax.scan`` body, so
  that sixteen experts compile once and the backward pass keeps none of
  their activations), every token through each, masked.
- Embedding, final ``Norm``, head.

Departures from the published model, each also in the configuration's
``assumed``: (1) the chip's share: the absent experts' part of the sum is left
out and the partial sum goes on; vocabulary rows 0..vocab_size-1 only. (2) No
multi-token-prediction module: the config has no key for one. (3) The
recurrent state and the attention run across the document boundaries of a
packed row. (4) Columns of ``W_qkvz`` are ``[q | k | v | z]`` and of ``W_ba``
``[b | a]``, head by head, where the published projection groups them by key
head: the same model under seeded weights. (5) The 1e-6 under the square root
of q's and k's normalisation is the published kernels' default.

``quant`` is the lower-precision control's hook (``lowprec.py``), applied to
both operands of every matrix product and of the convolution, the
recurrence's three products a token and attention's two included.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.layers import ident

L2_EPS = 1e-6
TOKENS_A_BLOCK = 128  # the recurrence's inner scan, rematerialised


def _dims(cfg):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        every=cfg["full_attention_interval"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        rot=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        hk=hk, hv=hv, dk=dk, dv=dv, conv=cfg["linear_conv_kernel_dim"],
        moe=cfg["moe_intermediate_size"],
        shared=cfg["shared_expert_intermediate_size"],
        held=cfg["num_experts"], experts=cfg["router_width"],
        vocab=cfg["vocab_size"],
    )


def is_softmax_layer(layer, cfg):
    return (layer + 1) % cfg["full_attention_interval"] == 0


def _swiglu_spec(prefix, d, width):
    fan = lambda n: 1.0 / math.sqrt(n)
    return [(prefix + ("gate", "kernel"), (d, width), fan(d)),
            (prefix + ("up", "kernel"), (d, width), fan(d)),
            (prefix + ("down", "kernel"), (width, d), fan(width))]


def _block_spec(name, m, softmax):
    fan = lambda n: 1.0 / math.sqrt(n)
    d, prefix = m["d"], (name,)
    out = [(prefix + ("mixer_norm", "scale"), (d,), "zeros")]
    if softmax:
        at, wide = prefix + ("self_attn",), m["heads"] * m["hd"]
        out += [
            (at + ("q_proj", "kernel"), (d, 2 * wide), fan(d)),
            (at + ("k_proj", "kernel"), (d, m["kv_heads"] * m["hd"]), fan(d)),
            (at + ("v_proj", "kernel"), (d, m["kv_heads"] * m["hd"]), fan(d)),
            (at + ("q_norm", "scale"), (m["hd"],), "zeros"),
            (at + ("k_norm", "scale"), (m["hd"],), "zeros"),
            (at + ("o_proj", "kernel"), (wide, d), fan(wide)),
        ]
    else:
        at = prefix + ("linear_attn",)
        keys, values = m["hk"] * m["dk"], m["hv"] * m["dv"]
        out += [
            (at + ("in_proj_qkvz", "kernel"), (d, 2 * keys + 2 * values), fan(d)),
            (at + ("in_proj_ba", "kernel"), (d, 2 * m["hv"]), fan(d)),
            (at + ("conv",), (m["conv"], 2 * keys + values), fan(m["conv"])),
            (at + ("A_log",), (m["hv"],), 1.0),
            (at + ("dt_bias",), (m["hv"],), "ones"),
            (at + ("norm",), (m["dv"],), "ones"),
            (at + ("out_proj", "kernel"), (values, d), fan(values)),
        ]
    w, e = m["moe"], m["held"]
    moe = prefix + ("moe",)
    return out + [(prefix + ("ffn_norm", "scale"), (d,), "zeros")] + _swiglu_spec(
        moe + ("shared",), d, m["shared"]) + [
        (moe + ("shared_gate", "kernel"), (d, 1), fan(d)),
        (moe + ("router",), (d, m["experts"]), math.sqrt(2.0 / d)),
        (moe + ("experts_gate",), (e, d, w), fan(d)),
        (moe + ("experts_up",), (e, d, w), fan(d)),
        (moe + ("experts_down",), (e, w, d), fan(w)),
    ]


def spec(cfg):
    m = _dims(cfg)
    out = [(("embed", "embedding"), (m["vocab"], m["d"]), 1.0)]
    for i in range(m["layers"]):
        out += _block_spec(f"layer_{i}", m, is_softmax_layer(i, cfg))
    out += [(("final_norm",), (m["d"],), "zeros"),
            (("head",), (m["d"], m["vocab"]), "head")]
    return out, []


def norm(x, w, eps):
    """``x * rsqrt(mean x^2 + eps) * (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def rotate_half(x, theta, rot):
    """RoPE on the first ``rot`` dimensions of ``x [T, d]``: the pair ``(x[i],
    x[i + rot/2])`` of position ``t`` turns by ``t / theta^(2i/rot)``."""
    t, half = x.shape[0], rot // 2
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] / (
        theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))[None, :]
    a, b = x[:, :half], x[:, half:rot]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle),
                            x[:, rot:]], axis=1)


def delta_rule(q, k, v, g, beta, quant=ident):
    """The gated delta rule token by token. ``q, k [T, H, dk]``, ``v [T, H,
    dv]``, ``g, beta [T, H]`` (a key head already repeated for its value
    heads); returns ``o [T, H, dv]``."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    inner = math.gcd(t, TOKENS_A_BLOCK)

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        decayed = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum("hkv,hk->hv", quant(decayed), quant(k_t))
        u = beta_t[:, None] * (v_t - read)
        state = decayed + jnp.einsum("hk,hv->hkv", quant(k_t), quant(u))
        return state, jnp.einsum("hkv,hk->hv", quant(state), quant(q_t))

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    blocks = jax.tree.map(
        lambda a: a.reshape((t // inner, inner) + a.shape[1:]), (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((h, dk, dv), jnp.float32), blocks)
    return o.reshape(t, h, dv)


def make_forward(cfg):
    m = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    first_held, top = cfg["experts_held_from"], cfg["num_experts_per_tok"]

    def delta_net(p, x, quant):
        """``x [T, d]``, one sequence."""
        mm = lambda a, b: quant(a) @ quant(b)
        t = x.shape[0]
        hk, hv, dk, dv = m["hk"], m["hv"], m["dk"], m["dv"]
        keys, values, per_key = hk * dk, hv * dv, hv // hk
        qkvz = mm(x, p["in_proj_qkvz"]["kernel"])
        ba = mm(x, p["in_proj_ba"]["kernel"])
        qkv, z = qkvz[:, :2 * keys + values], qkvz[:, 2 * keys + values:]
        # y_t = sum_i w_i x_{t - width + 1 + i}, zeros before the start
        width = m["conv"]
        padded, w = quant(jnp.pad(qkv, ((width - 1, 0), (0, 0)))), quant(p["conv"])
        qkv = jax.nn.silu(sum(padded[i:i + t] * w[i] for i in range(width)))
        unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
        q = unit(qkv[:, :keys].reshape(t, hk, dk)) * dk ** -0.5
        k = unit(qkv[:, keys:2 * keys].reshape(t, hk, dk))
        v = qkv[:, 2 * keys:].reshape(t, hv, dv)
        beta = jax.nn.sigmoid(ba[:, :hv])
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
        of_value_head = lambda a: jnp.repeat(a, per_key, axis=1)
        o = delta_rule(of_value_head(q), of_value_head(k), v, g, beta, quant)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * p["norm"]
        o = o * jax.nn.silu(z.reshape(t, hv, dv))
        return mm(o.reshape(t, values), p["out_proj"]["kernel"])

    def attention(p, x, quant):
        """``x [T, d]``, one sequence."""
        mm = lambda a, b: quant(a) @ quant(b)
        t = x.shape[0]
        h, kv, hd = m["heads"], m["kv_heads"], m["hd"]
        q_gate = mm(x, p["q_proj"]["kernel"]).reshape(t, h, 2 * hd)
        q = norm(q_gate[:, :, :hd], p["q_norm"]["scale"], eps)
        gate = q_gate[:, :, hd:]
        k = norm(mm(x, p["k_proj"]["kernel"]).reshape(t, kv, hd),
                 p["k_norm"]["scale"], eps)
        v = mm(x, p["v_proj"]["kernel"]).reshape(t, kv, hd)
        causal = jnp.tril(jnp.ones((t, t), bool))

        @jax.checkpoint  # one head's [T, T] scores alive at a time
        def one_head(args):
            q_h, k_h, v_h = args
            scores = mm(rotate_half(q_h, theta, m["rot"]),
                        rotate_half(k_h, theta, m["rot"]).T) / math.sqrt(hd)
            weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return mm(weights, v_h)

        of_query_head = lambda a: jnp.repeat(a.transpose(1, 0, 2), h // kv, axis=0)
        heads = jax.lax.map(
            one_head, (q.transpose(1, 0, 2), of_query_head(k), of_query_head(v)))
        o = heads.transpose(1, 0, 2) * jax.nn.sigmoid(gate)
        return mm(o.reshape(t, h * hd), p["o_proj"]["kernel"])

    def swiglu(x, gate, up, down, quant):
        mm = lambda a, b: quant(a) @ quant(b)
        return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)

    def expert_layer(p, x, quant):
        prob = jax.nn.softmax(quant(x) @ quant(p["router"]), axis=-1)
        p_chosen, chosen = jax.lax.top_k(prob, top)
        g = p_chosen / jnp.sum(p_chosen, axis=1, keepdims=True)
        s = p["shared"]
        y = jax.nn.sigmoid(quant(x) @ quant(p["shared_gate"]["kernel"])) * swiglu(
            x, s["gate"]["kernel"], s["up"]["kernel"], s["down"]["kernel"], quant)

        @jax.checkpoint  # the backward pass keeps no expert's activations
        def one_expert(y, held):
            e, w_gate, w_up, w_down = held
            # this expert's weight for each token: g where it was chosen, else 0
            g_e = jnp.sum(jnp.where(chosen == first_held + e, g, 0.0), axis=1)
            return y + g_e[:, None] * swiglu(x, w_gate, w_up, w_down, quant), None

        # One body for the held experts and not a copy each: the same sum in
        # the same order, compiled once.
        held = m["held"]
        return jax.lax.scan(one_expert, y, (
            jnp.arange(held), p["experts_gate"][:held], p["experts_up"][:held],
            p["experts_down"][:held]))[0]

    def one_block(p, h, quant):
        x = norm(h, p["mixer_norm"]["scale"], eps)
        if "self_attn" in p:
            h = h + attention(p["self_attn"], x, quant)
        else:
            h = h + delta_net(p["linear_attn"], x, quant)
        return h + expert_layer(
            p["moe"], norm(h, p["ffn_norm"]["scale"], eps), quant)

    # A layer's activations are recomputed in the backward pass.
    block = jax.checkpoint(one_block, static_argnums=(2,))

    def one_sequence(params, tokens, quant):
        h = params["embed"]["embedding"][tokens]
        for i in range(m["layers"]):
            h = block(params[f"layer_{i}"], h, quant)
        return quant(norm(h, params["final_norm"], eps)) @ quant(params["head"])

    def forward(params, stats, tokens, quant=ident):
        return jnp.stack([one_sequence(params, row, quant) for row in tokens]), stats

    # The layers by themselves, for the tests that hold the program to them.
    forward.delta_net, forward.attention = delta_net, attention
    forward.expert_layer, forward.block = expert_layer, one_block
    return forward
