"""Operations the forward and backward passes of the configuration's cut of
Nemotron-3-Nano-30B-A3B need for one sample (one packed row of ``seq_len``
tokens), from its shapes: the matrix products of every layer that is held
here, ONE half a layer by ``hybrid_override_pattern`` (a Mamba-2 layer's two
projections and its recurrence, three ``P x N`` multiply-adds a token a head:
the state decayed, the update ``dt x B^T`` added, the state read with ``C``,
``flops/ssd_core.py``'s count; an attention layer's four projections and its
score and value products over the causal half; an expert layer's router, its
shared expert and the held routed experts at their EXPECTED load, TWO matrices
an expert: a token picks ``num_experts_per_tok`` of ``router_width`` experts,
``n_routed_experts`` of which live here, ``k * held / width`` of them a token
on average; the real load follows the routing), and the head over the
vocabulary slice. Training counts a forward and two backward products (2 FLOP
x MACs x 3). No norm, softmax, rotary, gate, convolution (4 taps a channel) or
activation function, no optimizer, no recompute.
"""

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def kinds_held(cfg):
    return [KINDS[cfg["hybrid_override_pattern"][i]] for i in cfg["layers_held"]]


def mamba_proj_macs_per_token(cfg):
    """``W_in`` into ``[z | xBC | dt]`` and ``W_out``."""
    d, heads = cfg["hidden_size"], cfg["mamba_num_heads"]
    d_in = heads * cfg["mamba_head_dim"]
    wide = d_in + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return d * (d_in + wide + heads) + d_in * d


def mamba_core_macs_per_token(cfg):
    """Three ``P x N`` multiply-adds a head: decay, update, read."""
    return 3 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * cfg["ssm_state_size"]


def attention_proj_macs_per_token(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def attention_core_macs_per_token(cfg):
    """Scores and ``P v`` of one token against the ``(T + 1) / 2`` keys it
    sees on average in a row of ``T``."""
    return cfg["num_attention_heads"] * 2 * cfg["head_dim"] * (cfg["seq_len"] + 1) / 2


def sparse_macs_per_token(cfg):
    """``(router, shared expert, held experts at their expected load)``, two
    matrices an expert."""
    d = cfg["hidden_size"]
    here = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["router_width"]
    return (d * cfg["router_width"],
            2 * d * cfg["moe_shared_expert_intermediate_size"],
            2 * d * cfg["moe_intermediate_size"] * here)


def parts_macs_per_token(cfg):
    """Forward multiply-adds a token by part of the model."""
    out = dict.fromkeys(("mamba_proj", "mamba_core", "attention_proj",
                         "attention_core", "router", "shared", "experts", "head"), 0.0)
    for kind in kinds_held(cfg):
        if kind == "mamba":
            out["mamba_proj"] += mamba_proj_macs_per_token(cfg)
            out["mamba_core"] += mamba_core_macs_per_token(cfg)
        elif kind == "attention":
            out["attention_proj"] += attention_proj_macs_per_token(cfg)
            out["attention_core"] += attention_core_macs_per_token(cfg)
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
