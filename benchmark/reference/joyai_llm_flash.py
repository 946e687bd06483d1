"""Plain reference of JoyAI-LLM-Flash's forward pass as the configuration
cuts it (``configs/joyai_llm_flash.json``): jax.numpy, float32, nothing of
the program under test or of flax. ``spec(cfg)`` lists the parameters,
``make_forward(cfg)`` gives ``(params, stats, tokens [B, T], quant) ->
((logits, mtp_logits), stats)``, both ``[B, T, vocab]``; the task's loss
(``tasks/next_token.py``) weighs them.

The equations (huggingface.co/jdopensource/JoyAI-LLM-Flash, ``config.json``;
the family's published modelling code is DeepSeek-V3's):

- Block: ``h += Attn(RMSNorm(h))``; ``h += FFN(RMSNorm(h))``; eps 1e-6.
- Latent attention: ``c_q = RMSNorm(W_qa x)``; per head ``[q_nope, q_rope] =
  W_qb c_q``; ``[c_kv, k_rope] = W_kva x`` (``k_rope`` one for all heads);
  ``[k_nope, v] = W_kvb RMSNorm(c_kv)``; RoPE (interleaved pairs, theta from
  the config) on ``q_rope`` and ``k_rope``; ``k = [k_nope, k_rope]``; causal
  softmax of ``q.k / sqrt(192)``; ``W_o`` on the heads' ``P v``. The full
  ``[T, T]`` scores of one head of one sequence at a time.
- Expert layer: ``s = sigmoid(W_r x)`` over all ``router_width`` experts;
  chosen = the ``num_experts_per_tok`` largest of ``s + b``; ``g =
  routed_scaling_factor * s[chosen] / sum(s[chosen])``; ``y = SwiGLU_shared(x)
  + sum over chosen e HELD of g_e SwiGLU_e(x)``: a loop over the held experts
  (``n_routed_experts`` of them from ``experts_held_from``), every token
  through each, masked.
- Prediction module: ``h' = W_eh [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)]``,
  ``h_i`` the last block's output before the final norm; an expert-layer
  block; the model's final norm and head.

Departures from the published model, each also in the configuration's
``assumed``: (1) the chip's share: the absent experts' part of the sum is left
out and the partial sum goes on; vocabulary rows 0..vocab_size-1 only. (2)
``b`` (``e_score_correction_bias``) is a constant: ``bias_std`` times a
standard normal drawn from ``fold_in(PRNGKey(20260428), layer)``, the layer
counted from 0 with the prediction module's block after the last; no gradient.
(3) Attention runs across the document boundaries of a packed row. (4) The
prediction module shares the final norm with the model; position ``i`` of it
pairs ``h_i`` with ``t_{i+1}`` taken from the row itself (the last position
wraps and has no target).

``quant`` is the lower-precision control's hook (``lowprec.py``), applied to
both operands of every matrix product, attention's two included.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.layers import ident

BIAS_KEY = 20260428


def _dims(cfg):
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        vd=cfg["v_head_dim"], q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        ffn=cfg["intermediate_size"], moe=cfg["moe_intermediate_size"],
        held=cfg["n_routed_experts"], experts=cfg["router_width"],
        vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
        dense=cfg["first_k_dense_replace"], mtp=cfg["num_nextn_predict_layers"],
    )


def _attention_spec(prefix, m):
    fan = lambda n: 1.0 / math.sqrt(n)
    h = m["heads"]
    return [
        (prefix + ("attn_norm", "scale"), (m["d"],), "ones"),
        (prefix + ("attn", "q_a", "kernel"), (m["d"], m["q_rank"]), fan(m["d"])),
        (prefix + ("attn", "q_norm", "scale"), (m["q_rank"],), "ones"),
        (prefix + ("attn", "q_b", "kernel"),
         (m["q_rank"], h * (m["nope"] + m["rope"])), fan(m["q_rank"])),
        (prefix + ("attn", "kv_a", "kernel"),
         (m["d"], m["kv_rank"] + m["rope"]), fan(m["d"])),
        (prefix + ("attn", "kv_norm", "scale"), (m["kv_rank"],), "ones"),
        (prefix + ("attn", "kv_b", "kernel"),
         (m["kv_rank"], h * (m["nope"] + m["vd"])), fan(m["kv_rank"])),
        (prefix + ("attn", "o", "kernel"), (h * m["vd"], m["d"]), fan(h * m["vd"])),
        (prefix + ("ffn_norm", "scale"), (m["d"],), "ones"),
    ]


def _swiglu_spec(prefix, d, width):
    fan = lambda n: 1.0 / math.sqrt(n)
    return [(prefix + ("gate", "kernel"), (d, width), fan(d)),
            (prefix + ("up", "kernel"), (d, width), fan(d)),
            (prefix + ("down", "kernel"), (width, d), fan(width))]


def _block_spec(name, m, dense, shared_experts):
    prefix = (name,)
    out = _attention_spec(prefix, m)
    if dense:
        return out + _swiglu_spec(prefix + ("ffn",), m["d"], m["ffn"])
    d, w, e = m["d"], m["moe"], m["held"]
    return out + _swiglu_spec(prefix + ("moe", "shared"), d, w * shared_experts) + [
        (prefix + ("moe", "router"), (d, m["experts"]), math.sqrt(2.0 / d)),
        (prefix + ("moe", "experts_gate"), (e, d, w), 1.0 / math.sqrt(d)),
        (prefix + ("moe", "experts_up"), (e, d, w), 1.0 / math.sqrt(d)),
        (prefix + ("moe", "experts_down"), (e, w, d), 1.0 / math.sqrt(w)),
    ]


def spec(cfg):
    m = _dims(cfg)
    shared = cfg["n_shared_experts"]
    out = [(("embed", "embedding"), (m["vocab"], m["d"]), 1.0)]
    for i in range(m["layers"]):
        out += _block_spec(f"layer_{i}", m, i < m["dense"], shared)
    out += [(("final_norm",), (m["d"],), "ones"),
            (("head",), (m["d"], m["vocab"]), "head")]
    for k in range(m["mtp"]):
        out += [((f"mtp_{k}_enorm", "scale"), (m["d"],), "ones"),
                ((f"mtp_{k}_hnorm", "scale"), (m["d"],), "ones"),
                ((f"mtp_{k}_eh_proj", "kernel"), (2 * m["d"], m["d"]),
                 1.0 / math.sqrt(2 * m["d"]))]
        out += _block_spec(f"mtp_{k}_block", m, False, shared)
    return out, []


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotate(x, theta):
    """Interleaved RoPE on ``x [T, d]``: pair ``(x[2i], x[2i+1])`` of position
    ``t`` turns by ``t / theta^(2i/d)``."""
    t, d = x.shape
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] / (
        theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None, :]
    even, odd = x[:, 0::2], x[:, 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        even * jnp.sin(angle) + odd * jnp.cos(angle)], axis=-1)
    return turned.reshape(t, d)


def selection_bias(layer, cfg):
    key = jax.random.fold_in(jax.random.PRNGKey(BIAS_KEY), layer)
    return cfg["bias_std"] * jax.random.normal(key, (cfg["router_width"],), jnp.float32)


def make_forward(cfg):
    m = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    first_held = cfg["experts_held_from"]
    top, scaling = cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]

    def attention(p, x, quant):
        """``x [T, d]``, one sequence."""
        mm = lambda a, b: quant(a) @ quant(b)
        t = x.shape[0]
        c_q = rms_norm(mm(x, p["q_a"]["kernel"]), p["q_norm"]["scale"], eps)
        q = mm(c_q, p["q_b"]["kernel"]).reshape(t, m["heads"], m["nope"] + m["rope"])
        kv_a = mm(x, p["kv_a"]["kernel"])
        c_kv, k_rope = kv_a[:, :m["kv_rank"]], rotate(kv_a[:, m["kv_rank"]:], theta)
        kv = mm(rms_norm(c_kv, p["kv_norm"]["scale"], eps),
                p["kv_b"]["kernel"]).reshape(t, m["heads"], m["nope"] + m["vd"])
        causal = jnp.tril(jnp.ones((t, t), bool))

        @jax.checkpoint  # one head's [T, T] scores alive at a time
        def one_head(args):
            q_h, kv_h = args
            q_full = jnp.concatenate(
                [q_h[:, :m["nope"]], rotate(q_h[:, m["nope"]:], theta)], axis=1)
            k_full = jnp.concatenate([kv_h[:, :m["nope"]], k_rope], axis=1)
            scores = mm(q_full, k_full.T) / math.sqrt(m["nope"] + m["rope"])
            weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return mm(weights, kv_h[:, m["nope"]:])

        heads = jax.lax.map(one_head, (q.transpose(1, 0, 2), kv.transpose(1, 0, 2)))
        return mm(heads.transpose(1, 0, 2).reshape(t, -1), p["o"]["kernel"])

    def swiglu(x, gate, up, down, quant):
        mm = lambda a, b: quant(a) @ quant(b)
        return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)

    def expert_layer(p, x, layer, quant):
        s = jax.nn.sigmoid(quant(x) @ quant(p["router"]))
        _, chosen = jax.lax.top_k(s + selection_bias(layer, cfg), top)
        s_chosen = jnp.take_along_axis(s, chosen, axis=1)
        g = scaling * s_chosen / jnp.sum(s_chosen, axis=1, keepdims=True)
        y = swiglu(x, p["shared"]["gate"]["kernel"], p["shared"]["up"]["kernel"],
                   p["shared"]["down"]["kernel"], quant)
        for e in range(m["held"]):
            # this expert's weight for each token: g where it was chosen, else 0
            g_e = jnp.sum(jnp.where(chosen == first_held + e, g, 0.0), axis=1)
            y = y + g_e[:, None] * swiglu(
                x, p["experts_gate"][e], p["experts_up"][e], p["experts_down"][e],
                quant)
        return y

    def one_block(p, h, layer, quant):
        h = h + attention(p["attn"], rms_norm(h, p["attn_norm"]["scale"], eps), quant)
        x = rms_norm(h, p["ffn_norm"]["scale"], eps)
        if "ffn" in p:
            f = p["ffn"]
            return h + swiglu(x, f["gate"]["kernel"], f["up"]["kernel"],
                              f["down"]["kernel"], quant)
        return h + expert_layer(p["moe"], x, layer, quant)

    # A layer's activations are recomputed in the backward pass.
    block = jax.checkpoint(one_block, static_argnums=(2, 3))

    def one_sequence(params, tokens, quant):
        embed = params["embed"]["embedding"]
        head = lambda h: quant(rms_norm(h, params["final_norm"], eps)) @ quant(
            params["head"])
        h = embed[tokens]
        for i in range(m["layers"]):
            h = block(params[f"layer_{i}"], h, i, quant)
        out = [head(h)]
        for k in range(m["mtp"]):
            nxt = embed[jnp.roll(tokens, -(k + 1))]
            both = jnp.concatenate([
                rms_norm(nxt, params[f"mtp_{k}_enorm"]["scale"], eps),
                rms_norm(h, params[f"mtp_{k}_hnorm"]["scale"], eps)], axis=1)
            h = quant(both) @ quant(params[f"mtp_{k}_eh_proj"]["kernel"])
            h = block(params[f"mtp_{k}_block"], h, m["layers"] + k, quant)
            out.append(head(h))
        return tuple(out)

    def forward(params, stats, tokens, quant=ident):
        rows = [one_sequence(params, row, quant) for row in tokens]
        return tuple(jnp.stack(parts) for parts in zip(*rows)), stats

    # The layers by themselves, for the tests that hold the program to them.
    forward.attention, forward.expert_layer, forward.block = (
        attention, expert_layer, one_block)
    return forward
