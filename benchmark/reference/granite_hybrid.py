"""Plain reference of Granite-hybrid's forward pass as the configuration cuts
it (``configs/granite_4_0_h_micro.json``): jax.numpy, float32, nothing of the
program under test, of flax, or of another reference's mixer. ``spec(cfg)``
lists the parameters, ``make_forward(cfg)`` gives ``(params, stats, tokens [B,
T], quant) -> (logits [B, T, vocab], stats)``; the task's loss is
``tasks/next_token.py``'s.

The equations (huggingface.co/ibm-granite/granite-4.0-h-micro,
``config.json``, ``model_type: granitemoehybrid``; the multipliers where the
family's modelling code has them). ``RMSNorm(x) = w * x * rsqrt(mean x^2 +
rms_norm_eps)``; no bias but the convolution's:

- ``h_0 = embedding_multiplier * Emb(t)``. The layers held here are
  ``layers_held`` of the published forty, in order; layer ``l``'s kind is
  ``layer_types[l]``, ``mamba`` or ``attention``. A layer is two halves: ``h
  += residual_multiplier * Mixer_l(RMSNorm(h))``, then ``h +=
  residual_multiplier * MLP(RMSNorm(h))``. After the last one RMSNorm, then
  ``logits = (h Emb^T) / logits_scaling``: the head is the embedding's
  transpose (``tie_word_embeddings``).
- ``mamba``, ``H = mamba_n_heads`` heads HELD of ``P = mamba_d_head``, ``d_in
  = H P``, ONE group of state size ``N = mamba_d_state`` (``mamba_n_groups``
  1; another count is refused): ``[z | xBC | dt] = W_in u`` of widths ``d_in
  | d_in + 2 N | H``; ``xBC = silu(conv(xBC) + b_conv)``, ``conv(a)_t = sum_i
  w_i a_{t - mamba_d_conv + 1 + i}`` a channel, zeros before the start;
  ``xBC = [x | B | C]``, every head reads the one ``B`` and ``C``; ``dt =
  softplus(dt + dt_bias)`` with no clamp, ``A = -exp(A_log)``. The recurrence
  ONE TOKEN AT A TIME, as the rule is written, a head's state ``S [P, N]``
  from zero: ``S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T``; ``y_t = S_t
  C_t + D x_t`` (a ``lax.scan`` over blocks of ``TOKENS_A_BLOCK`` tokens
  around a rematerialised ``lax.scan`` over tokens: the backward pass keeps a
  state a block, not a token; no chunk algebra, so the program's chunks of
  256 are held against the rule itself). ``y = RMSNorm(y * silu(z))`` over
  the ``d_in`` channels held, times ``w``: the gate before the norm; ``out =
  W_out y``.
- ``attention``: ``q = W_q u [T, num_attention_heads, hd]``, ``k = W_k u``,
  ``v = W_v u [T, num_key_value_heads, hd]``, both counts the heads HELD,
  ``hd = head_dim`` (the published hidden over the published heads), query
  head ``h`` on key-value head ``h // (heads / kv heads)``; NO position term
  of any kind; scores ``attention_multiplier * q k^T`` under a dense ``[T,
  T]`` mask ``j <= t``, one query head's at a time; softmax; ``W_o``.
- ``MLP(u) = W_down (silu(W_gate u) * W_up u)`` at
  ``shared_intermediate_size``. A configuration that states experts
  (``num_local_experts`` or ``num_experts_per_tok`` above 0) is refused: the
  family's sparse form is not written here.

Departures from the published model, each also in the configuration's
``assumed``: (1) the chip's share: of each mixer the heads held (``W_out``'s
and ``W_o``'s partial sums go on as they are, and the gated norm's mean
square is over the channels HELD: one chip runs its layer without the
exchange that tensor parallelism makes of both); vocabulary rows
0..vocab_size-1 only. (2) State and attention run across the document
boundaries of a packed row.

``quant`` is the lower-precision control's hook (``lowprec.py``), applied to
both operands of every matrix product: the projections, the convolution, the
state's update and its read, attention's two products, the SwiGLU's three, the
head.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.layers import ident

TOKENS_A_BLOCK = 128  # the recurrence's inner scan, rematerialised
KINDS = {"mamba": "mamba", "attention": "self_attn"}  # a kind's parameter name


def _dims(cfg):
    if cfg["num_local_experts"] or cfg["num_experts_per_tok"]:
        raise ValueError(
            f"num_local_experts={cfg['num_local_experts']}, "
            f"num_experts_per_tok={cfg['num_experts_per_tok']}: this is the "
            "reference of the family's DENSE member; the sparse members' "
            "router and experts are not written here")
    if cfg["mamba_n_groups"] != 1:
        raise ValueError(
            f"mamba_n_groups={cfg['mamba_n_groups']}: the reference is "
            "written for the ONE group every head reads")
    held = cfg["layers_held"]
    if len(held) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"layers_held={held} names {len(held)} layers, "
            f"num_hidden_layers is {cfg['num_hidden_layers']}")
    heads, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    return dict(
        d=cfg["hidden_size"], kinds=[cfg["layer_types"][i] for i in held],
        heads=heads, p=p, n=n, d_in=heads * p, wide=heads * p + 2 * n,
        conv=cfg["mamba_d_conv"], q_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        ffn=cfg["shared_intermediate_size"], vocab=cfg["vocab_size"],
    )


def _layer_spec(name, m, kind):
    fan = lambda n: 1.0 / math.sqrt(n)
    d, at = m["d"], (name, KINDS[kind])
    out = [((name, "mixer_norm", "scale"), (d,), "ones")]
    if kind == "mamba":
        out += [
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
    else:
        wide, kv = m["q_heads"] * m["hd"], m["kv_heads"] * m["hd"]
        out += [
            (at + ("q_proj", "kernel"), (d, wide), fan(d)),
            (at + ("k_proj", "kernel"), (d, kv), fan(d)),
            (at + ("v_proj", "kernel"), (d, kv), fan(d)),
            (at + ("o_proj", "kernel"), (wide, d), fan(wide)),
        ]
    mlp, w = (name, "shared_mlp"), m["ffn"]
    return out + [
        ((name, "ffn_norm", "scale"), (d,), "ones"),
        (mlp + ("gate", "kernel"), (d, w), fan(d)),
        (mlp + ("up", "kernel"), (d, w), fan(d)),
        (mlp + ("down", "kernel"), (w, d), fan(w)),
    ]


def spec(cfg):
    m = _dims(cfg)
    out = [(("embed", "embedding"), (m["vocab"], m["d"]), cfg["embedding_std"])]
    for i, kind in enumerate(m["kinds"]):
        out += _layer_spec(f"layer_{i}", m, kind)
    return out + [(("final_norm",), (m["d"],), "ones")], []


def rms_norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def state_space(x, dt, a, b, c, skip, quant=ident):
    """The selective state-space recurrence token by token. ``x [T, H, P]``,
    ``dt [T, H]``, ``a [H]``, ``b, c [T, N]`` (the one group's, every head's
    alike), ``skip [H]``; returns ``y [T, H, P]``."""
    t, h, p = x.shape
    inner = math.gcd(t, TOKENS_A_BLOCK)

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t * a)[:, None, None] * state + jnp.einsum(
            "hp,n->hpn", quant(dt_t[:, None] * x_t), quant(b_t))
        y_t = jnp.einsum("hpn,n->hp", quant(state), quant(c_t))
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
    eps, hd = cfg["rms_norm_eps"], m["hd"]
    residual, scale = cfg["residual_multiplier"], cfg["attention_multiplier"]

    def mamba(p, u, quant):
        """``u [T, d]``, one sequence."""
        mm = lambda a, b: quant(a) @ quant(b)
        t = u.shape[0]
        heads, hp, n, d_in, wide = (m[k] for k in ("heads", "p", "n", "d_in", "wide"))
        zxbcdt = mm(u, p["in_proj"]["kernel"])
        z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + wide],
                      zxbcdt[:, d_in + wide:])
        width = m["conv"]
        padded, w = quant(jnp.pad(xbc, ((width - 1, 0), (0, 0)))), quant(p["conv"])
        xbc = jax.nn.silu(
            sum(padded[i:i + t] * w[i] for i in range(width)) + p["conv_bias"])
        x = xbc[:, :d_in].reshape(t, heads, hp)
        b, c = xbc[:, d_in:d_in + n], xbc[:, d_in + n:]
        dt = jax.nn.softplus(dt + p["dt_bias"])
        y = state_space(x, dt, -jnp.exp(p["A_log"]), b, c, p["D"], quant)
        # the gate, then ONE norm over every channel held
        y = rms_norm(y.reshape(t, d_in) * jax.nn.silu(z), p["norm"], eps)
        return mm(y, p["out_proj"]["kernel"])

    def attention(p, u, quant):
        """``u [T, d]``, one sequence; no position enters."""
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
            scores = scale * mm(q_h, k_h.T)
            weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return mm(weights, v_h)

        of_query_head = lambda a: jnp.repeat(a.transpose(1, 0, 2), h // kv, axis=0)
        heads = jax.lax.map(
            one_head, (q.transpose(1, 0, 2), of_query_head(k), of_query_head(v)))
        return mm(heads.transpose(1, 0, 2).reshape(t, h * hd), p["o_proj"]["kernel"])

    def mlp(p, u, quant):
        mm = lambda a, b: quant(a) @ quant(b)
        return mm(jax.nn.silu(mm(u, p["gate"]["kernel"])) * mm(u, p["up"]["kernel"]),
                  p["down"]["kernel"])

    def one_layer(p, h, kind, quant):
        u = rms_norm(h, p["mixer_norm"]["scale"], eps)
        mixer = mamba if kind == "mamba" else attention
        h = h + residual * mixer(p[KINDS[kind]], u, quant)
        u = rms_norm(h, p["ffn_norm"]["scale"], eps)
        return h + residual * mlp(p["shared_mlp"], u, quant)

    # A layer's activations are recomputed in the backward pass.
    layer_fn = jax.checkpoint(one_layer, static_argnums=(2, 3))

    def one_sequence(params, tokens, quant):
        table = params["embed"]["embedding"]
        h = cfg["embedding_multiplier"] * table[tokens]
        for i, kind in enumerate(m["kinds"]):
            h = layer_fn(params[f"layer_{i}"], h, kind, quant)
        logits = quant(rms_norm(h, params["final_norm"], eps)) @ quant(table.T)
        return logits / cfg["logits_scaling"]

    def forward(params, stats, tokens, quant=ident):
        return jnp.stack([one_sequence(params, row, quant) for row in tokens]), stats

    # The layers by themselves, for the tests that hold the program to them.
    forward.mamba, forward.attention, forward.mlp = mamba, attention, mlp
    forward.layer = one_layer
    return forward
