"""Operations the forward and backward passes of the configuration's cut of
Laguna-S-2.1 need for one sample (one packed row of ``seq_len`` tokens), from
its shapes: the matrix products of every layer that is held here (an
attention layer's five projections at the heads HELD of its kind, its score
and value products over the pairs its mask admits: the causal half in a full
layer, the band ``sum_t min(t + 1, sliding_window)`` in a sliding one; the
dense feed-forward; a sparse layer's router, its shared expert and the held
routed experts at their EXPECTED load: a token picks ``num_experts_per_tok``
of ``router_width`` experts, ``num_experts`` of which live here, ``k * held /
width`` of them a token on average; the real load follows the routing), and
the head over the vocabulary slice. Training counts a forward and two
backward products (2 FLOP x MACs x 3). No norm, softmax, rotary, gate or
activation function, no optimizer, no recompute.
"""


def held(cfg, key):
    return [cfg[key][i] for i in cfg["layers_held"]]


def attention_proj_macs_per_token(cfg, heads):
    """q, k, v, the gate a head, and the output projection, at ``heads``
    query heads held."""
    d, hd, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    return d * heads * hd + 2 * d * kv * hd + d * heads + heads * hd * d


def attention_core_macs_per_token(cfg, kind, heads):
    """Scores and ``P v`` of one token against the keys it sees on average in
    a row of ``T``: ``(T + 1) / 2`` of the prefix, or the band's share."""
    t = cfg["seq_len"]
    if kind == "full_attention":
        keys = (t + 1) / 2
    else:
        w = min(cfg["sliding_window"], t)
        keys = (w * (w + 1) / 2 + (t - w) * w) / t
    return heads * 2 * cfg["head_dim"] * keys


def dense_macs_per_token(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def sparse_macs_per_token(cfg):
    """``(router, shared expert, held experts at their expected load)``."""
    d = cfg["hidden_size"]
    here = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_width"]
    return (d * cfg["router_width"], 3 * d * cfg["shared_expert_intermediate_size"],
            3 * d * cfg["moe_intermediate_size"] * here)


def parts_macs_per_token(cfg):
    """Forward multiply-adds a token by part of the model."""
    out = dict.fromkeys(("attention_proj", "full_core", "window_core", "dense",
                         "router", "shared", "experts", "head"), 0.0)
    for kind, heads, ffn in zip(held(cfg, "layer_types"),
                                held(cfg, "num_attention_heads_per_layer"),
                                held(cfg, "mlp_layer_types")):
        out["attention_proj"] += attention_proj_macs_per_token(cfg, heads)
        core = "full_core" if kind == "full_attention" else "window_core"
        out[core] += attention_core_macs_per_token(cfg, kind, heads)
        if ffn == "dense":
            out["dense"] += dense_macs_per_token(cfg)
        else:
            for part, macs in zip(("router", "shared", "experts"),
                                  sparse_macs_per_token(cfg)):
                out[part] += macs
    out["head"] = cfg["hidden_size"] * cfg["vocab_size"]
    return out


def forward_macs_per_token(cfg):
    return sum(parts_macs_per_token(cfg).values())


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs_per_token(cfg) * cfg["seq_len"]
