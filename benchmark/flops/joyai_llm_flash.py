"""Operations the forward and backward passes of the configuration's cut of
JoyAI-LLM-Flash need for one sample (one packed row of ``seq_len`` tokens),
from its shapes: the matrix products of every layer that is held here, the
attention's score and value products over the causal half, the held routed
experts at their EXPECTED load (a token picks ``num_experts_per_tok`` of
``router_width`` experts, ``n_routed_experts`` of which live here: ``k * held
/ width`` of them a token on average; the real load follows the routing),
the prediction module, and the head over the vocabulary slice, once a head.
Training counts a forward and two backward products (2 FLOP x MACs x 3). No
norm, softmax, rotary or activation function, no optimizer, no recompute.
"""


def attention_proj_macs_per_token(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * (nope + rope)
            + d * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * h * (nope + vd) + h * vd * d)


def attention_core_macs_per_token(cfg):
    """Scores and ``P v`` of one token against the ``(T + 1) / 2`` keys it
    sees on average in a causal row of ``T``."""
    h = cfg["num_attention_heads"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return h * width * (cfg["seq_len"] + 1) / 2


def expert_layer_macs_per_token(cfg):
    d, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
    routed_here = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                   / cfg["router_width"])
    return (d * cfg["router_width"]
            + 3 * d * w * (cfg["n_shared_experts"] + routed_here))


def forward_macs_per_token(cfg):
    d = cfg["hidden_size"]
    attention = attention_proj_macs_per_token(cfg) + attention_core_macs_per_token(cfg)
    dense = cfg["first_k_dense_replace"]
    expert_layers = cfg["num_hidden_layers"] - dense
    mtp = cfg["num_nextn_predict_layers"]
    return (
        dense * (attention + 3 * d * cfg["intermediate_size"])
        + (expert_layers + mtp) * (attention + expert_layer_macs_per_token(cfg))
        + mtp * 2 * d * d
        + (1 + mtp) * d * cfg["vocab_size"]
    )


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs_per_token(cfg) * cfg["seq_len"]
