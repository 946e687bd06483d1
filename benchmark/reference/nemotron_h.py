"""Plain reference of Nemotron-H's forward pass as the configuration cuts it
(``configs/nemotron_3_nano_30b_a3b.json``): jax.numpy, float32, nothing of the
program under test or of flax. ``spec(cfg)`` lists the parameters,
``make_forward(cfg)`` gives ``(params, stats, tokens [B, T], quant) ->
(logits [B, T, vocab], stats)``; the task's loss is ``tasks/next_token.py``'s.

The equations (huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``config.json``, ``model_type: nemotron_h``). ``RMSNorm(x) = w * x *
rsqrt(mean x^2 + layer_norm_epsilon)``; no bias but the convolution's:

- The layers held here are ``layers_held`` of the published 52, in order;
  layer ``l``'s kind is ``hybrid_override_pattern[l]``: ``M`` Mamba-2, ``E``
  the expert layer, ``*`` attention. A layer is ONE half behind ONE norm:
  ``h += Mixer_l(RMSNorm(h))``. After the last one RMSNorm (``norm_eps``) and
  an untied head.
- ``M``, ``H = mamba_num_heads`` heads of ``P = mamba_head_dim``, ``d_in = H
  P``, ``G = n_groups`` groups of state size ``N = ssm_state_size``: ``[z |
  xBC | dt] = W_in u`` of widths ``d_in | d_in + 2 G N | H``; ``xBC =
  silu(conv(xBC) + b_conv)``, ``conv(a)_t = sum_i w_i a_{t - conv_kernel + 1 +
  i}`` a channel, zeros before the start; ``xBC = [x | B | C]``, head ``h``
  reads group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``. The recurrence ONE TOKEN AT A TIME, as the rule is written,
  a head's state ``S [P, N]`` from zero: ``S_t = exp(dt_t A) S_{t-1} + (dt_t
  x_t) B_t^T``; ``y_t = S_t C_t + D x_t`` (a ``lax.scan`` over blocks of
  ``TOKENS_A_BLOCK`` tokens around a rematerialised ``lax.scan`` over tokens:
  the backward pass keeps a state a block, not a token; no chunk algebra, so
  the program's chunked form is held against the rule itself). ``y =
  RMSNorm(y * silu(z))`` over each group's ``d_in / G`` channels, times ``w``;
  ``out = W_out y``.
- ``E``: ``s = sigmoid(W_r u)`` over all ``router_width`` experts; chosen =
  the ``num_experts_per_tok`` largest of ``s + b`` (``n_group`` 1,
  ``topk_group`` 1: no group limit); ``g = routed_scaling_factor * s[chosen] /
  sum s[chosen]`` (``norm_topk_prob``); ``y = MLP_shared(u) + sum over chosen
  e HELD of g_e MLP_e(u)``, ``MLP(u) = W_down relu(W_up u)^2``
  (``mlp_hidden_act: relu2``): a loop over the held experts
  (``n_routed_experts`` of them from ``experts_held_from``; one
  rematerialised ``lax.scan`` body), every token through each, masked. ``b``:
  ``bias_std`` times a standard normal from a key fixed by the published
  layer's index, a constant.
- ``*``: ``q = W_q u [T, num_attention_heads, head_dim]``, ``k = W_k u``, ``v
  = W_v u [T, num_key_value_heads, head_dim]``, query head ``h`` on key-value
  head ``h // (heads / kv heads)``; rotary turns (rotate-half pairing) on the
  first ``head_dim * partial_rotary_factor`` dimensions at ``rope_theta``;
  scores ``q k^T / sqrt(head_dim)`` under a dense ``[T, T]`` mask ``j <= t``,
  one query head's at a time; softmax; ``W_o``.

Departures from the published model, each also in the configuration's
``assumed``: (1) the chip's share: the absent experts' part of a layer's sum
is left out and the partial sum goes on; vocabulary rows 0..vocab_size-1 only.
(2) ``d_in`` is heads x head size, not ``expand`` x hidden. (3) Sigmoid scores
with a constant selection bias. (4) State and attention run across the
document boundaries of a packed row.

``quant`` is the lower-precision control's hook (``lowprec.py``), applied to
both operands of every matrix product: the projections, the convolution, the
state's update and its read, attention's two products, the experts'.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.layers import ident

TOKENS_A_BLOCK = 128  # the recurrence's inner scan, rematerialised
BIAS_KEY = 20261003  # the program's (fedtpu/models/nemotron_h.py)
KINDS = {"M": "mamba", "E": "moe", "*": "self_attn"}


def _dims(cfg):
    held = cfg["layers_held"]
    if len(held) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"layers_held={held} names {len(held)} layers, "
            f"num_hidden_layers is {cfg['num_hidden_layers']}")
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return dict(
        d=cfg["hidden_size"], layers=list(held),
        kinds=[KINDS[cfg["hybrid_override_pattern"][i]] for i in held],
        heads=heads, p=p, g=g, n=n, d_in=heads * p, wide=heads * p + 2 * g * n,
        conv=cfg["conv_kernel"], q_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        moe=cfg["moe_intermediate_size"],
        shared=cfg["moe_shared_expert_intermediate_size"],
        held=cfg["n_routed_experts"], experts=cfg["router_width"],
        vocab=cfg["vocab_size"],
    )


def _layer_spec(name, m, kind):
    fan = lambda n: 1.0 / math.sqrt(n)
    d, at = m["d"], (name, kind)
    out = [((name, "norm", "scale"), (d,), "ones")]
    if kind == "mamba":
        return out + [
            (at + ("in_proj", "kernel"), (d, m["d_in"] + m["wide"] + m["heads"]), fan(d)),
            (at + ("conv",), (m["conv"], m["wide"]), fan(m["conv"])),
            (at + ("conv_bias",), (m["wide"],), fan(m["conv"])),
            # the configuration's assumed.init: what the harness's kinds allow
            (at + ("dt_bias",), (m["heads"],), 3.0),
            (at + ("A_log",), (m["heads"],), 1.0),
            (at + ("D",), (m["heads"],), "ones"),
            (at + ("norm",), (m["d_in"],), "ones"),
            (at + ("out_proj", "kernel"), (m["d_in"], d), fan(m["d_in"])),
        ]
    if kind == "self_attn":
        wide, kv = m["q_heads"] * m["hd"], m["kv_heads"] * m["hd"]
        return out + [
            (at + ("q_proj", "kernel"), (d, wide), fan(d)),
            (at + ("k_proj", "kernel"), (d, kv), fan(d)),
            (at + ("v_proj", "kernel"), (d, kv), fan(d)),
            (at + ("o_proj", "kernel"), (wide, d), fan(wide)),
        ]
    w, e = m["moe"], m["held"]
    return out + [
        (at + ("shared", "up", "kernel"), (d, m["shared"]), fan(d)),
        (at + ("shared", "down", "kernel"), (m["shared"], d), fan(m["shared"])),
        (at + ("router",), (d, m["experts"]), math.sqrt(2.0 / d)),
        (at + ("experts_up",), (e, d, w), fan(d)),
        (at + ("experts_down",), (e, w, d), fan(w)),
    ]


def spec(cfg):
    m = _dims(cfg)
    out = [(("embed", "embedding"), (m["vocab"], m["d"]), 1.0)]
    for i, kind in enumerate(m["kinds"]):
        out += _layer_spec(f"layer_{i}", m, kind)
    out += [(("final_norm",), (m["d"],), "ones"),
            (("head",), (m["d"], m["vocab"]), "head")]
    return out, []


def rms_norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


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


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def selection_bias(layer, cfg):
    """``layer``: the PUBLISHED index."""
    key = jax.random.fold_in(jax.random.PRNGKey(BIAS_KEY), layer)
    return cfg["bias_std"] * jax.random.normal(key, (cfg["router_width"],), jnp.float32)


def state_space(x, dt, a, b, c, skip, quant=ident):
    """The selective state-space recurrence token by token. ``x [T, H, P]``,
    ``dt [T, H]``, ``a [H]``, ``b, c [T, H, N]`` (a group's already repeated
    for its heads), ``skip [H]``; returns ``y [T, H, P]``."""
    t, h, p = x.shape
    inner = math.gcd(t, TOKENS_A_BLOCK)

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t * a)[:, None, None] * state + jnp.einsum(
            "hp,hn->hpn", quant(dt_t[:, None] * x_t), quant(b_t))
        y_t = jnp.einsum("hpn,hn->hp", quant(state), quant(c_t))
        return state, y_t + skip[:, None] * x_t

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    blocks = jax.tree.map(
        lambda v: v.reshape((t // inner, inner) + v.shape[1:]), (x, dt, b, c))
    _, y = jax.lax.scan(block, jnp.zeros((h, p, b.shape[-1]), jnp.float32), blocks)
    return y.reshape(t, h, p)


def make_forward(cfg):
    m = _dims(cfg)
    eps, hd = cfg["layer_norm_epsilon"], m["hd"]
    first_held, top = cfg["experts_held_from"], cfg["num_experts_per_tok"]
    rot = int(hd * cfg["partial_rotary_factor"])
    theta = float(cfg["rope_theta"])

    def mamba(p, u, quant):
        """``u [T, d]``, one sequence."""
        mm = lambda a, b: quant(a) @ quant(b)
        t = u.shape[0]
        heads, hp, g, n, d_in, wide = (m[k] for k in ("heads", "p", "g", "n", "d_in", "wide"))
        zxbcdt = mm(u, p["in_proj"]["kernel"])
        z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + wide],
                      zxbcdt[:, d_in + wide:])
        width = m["conv"]
        padded, w = quant(jnp.pad(xbc, ((width - 1, 0), (0, 0)))), quant(p["conv"])
        xbc = jax.nn.silu(
            sum(padded[i:i + t] * w[i] for i in range(width)) + p["conv_bias"])
        x = xbc[:, :d_in].reshape(t, heads, hp)
        of_head = lambda a: jnp.repeat(a.reshape(t, g, n), heads // g, axis=1)
        b, c = of_head(xbc[:, d_in:d_in + g * n]), of_head(xbc[:, d_in + g * n:])
        dt = jax.nn.softplus(dt + p["dt_bias"])
        y = state_space(x, dt, -jnp.exp(p["A_log"]), b, c, p["D"], quant)
        y = (y.reshape(t, d_in) * jax.nn.silu(z)).reshape(t, g, d_in // g)
        y = rms_norm(y, p["norm"].reshape(g, d_in // g), eps)
        return mm(y.reshape(t, d_in), p["out_proj"]["kernel"])

    def attention(p, u, quant):
        """``u [T, d]``, one sequence."""
        mm = lambda a, b: quant(a) @ quant(b)
        t = u.shape[0]
        h, kv = m["q_heads"], m["kv_heads"]
        q = mm(u, p["q_proj"]["kernel"]).reshape(t, h, hd)
        k = mm(u, p["k_proj"]["kernel"]).reshape(t, kv, hd)
        v = mm(u, p["v_proj"]["kernel"]).reshape(t, kv, hd)
        causal = jnp.tril(jnp.ones((t, t), bool))

        @jax.checkpoint  # one head's [T, T] scores alive at a time
        def one_head(args):
            q_h, k_h, v_h = args
            scores = mm(rotate_half(q_h, theta, rot),
                        rotate_half(k_h, theta, rot).T) / math.sqrt(hd)
            weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return mm(weights, v_h)

        of_query_head = lambda a: jnp.repeat(a.transpose(1, 0, 2), h // kv, axis=0)
        heads = jax.lax.map(
            one_head, (q.transpose(1, 0, 2), of_query_head(k), of_query_head(v)))
        return mm(heads.transpose(1, 0, 2).reshape(t, h * hd), p["o_proj"]["kernel"])

    def mlp(u, up, down, quant):
        mm = lambda a, b: quant(a) @ quant(b)
        return mm(relu2(mm(u, up)), down)

    def expert_layer(p, u, layer, quant):
        """``layer``: the published index (the selection bias's key)."""
        s = jax.nn.sigmoid(quant(u) @ quant(p["router"]))
        _, chosen = jax.lax.top_k(s + selection_bias(layer, cfg), top)
        g = jnp.take_along_axis(s, chosen, axis=1)
        if cfg["norm_topk_prob"]:
            g = g / jnp.sum(g, axis=1, keepdims=True)
        g = cfg["routed_scaling_factor"] * g
        y = mlp(u, p["shared"]["up"]["kernel"], p["shared"]["down"]["kernel"], quant)

        @jax.checkpoint  # the backward pass keeps no expert's activations
        def one_expert(y, held):
            e, w_up, w_down = held
            # this expert's weight for each token: g where it was chosen, else 0
            g_e = jnp.sum(jnp.where(chosen == first_held + e, g, 0.0), axis=1)
            return y + g_e[:, None] * mlp(u, w_up, w_down, quant), None

        held = m["held"]
        return jax.lax.scan(one_expert, y, (
            jnp.arange(held), p["experts_up"][:held], p["experts_down"][:held]))[0]

    def one_layer(p, h, kind, layer, quant):
        u = rms_norm(h, p["norm"]["scale"], eps)
        if kind == "mamba":
            return h + mamba(p["mamba"], u, quant)
        if kind == "self_attn":
            return h + attention(p["self_attn"], u, quant)
        return h + expert_layer(p["moe"], u, layer, quant)

    # A layer's activations are recomputed in the backward pass.
    layer_fn = jax.checkpoint(one_layer, static_argnums=(2, 3, 4))

    def one_sequence(params, tokens, quant):
        h = params["embed"]["embedding"][tokens]
        for i, (kind, layer) in enumerate(zip(m["kinds"], m["layers"])):
            h = layer_fn(params[f"layer_{i}"], h, kind, layer, quant)
        return quant(rms_norm(h, params["final_norm"], cfg["norm_eps"])
                     ) @ quant(params["head"])

    def forward(params, stats, tokens, quant=ident):
        return jnp.stack([one_sequence(params, row, quant) for row in tokens]), stats

    # The layers by themselves, for the tests that hold the program to them.
    forward.mamba, forward.attention = mamba, attention
    forward.expert_layer, forward.layer = expert_layer, one_layer
    return forward
