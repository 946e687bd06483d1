"""Operations the forward and backward passes of the configuration's cut of
Qwen3-Next need for one sample (one packed row of ``seq_len`` tokens), from
its shapes: the matrix products of every layer that is held here, the gated
delta rule's three ``dk x dv`` products a token a value head (decay-and-read,
update, output: what the rule needs at any chunk length), the softmax layer's
score and value products over the causal half, the held routed experts at
their EXPECTED load (a token picks ``num_experts_per_tok`` of ``router_width``
experts, ``num_experts`` of which live here: ``k * held / width`` of them a
token on average; the real load follows the routing), and the head over the
vocabulary slice. Training counts a forward and two backward products (2 FLOP
x MACs x 3). No norm, softmax, rotary, gate, convolution tap or activation
function, no triangular solve, no optimizer, no recompute.
"""


def softmax_layers(cfg):
    return cfg["num_hidden_layers"] // cfg["full_attention_interval"]


def gdn_layers(cfg):
    return cfg["num_hidden_layers"] - softmax_layers(cfg)


def gdn_proj_macs_per_token(cfg):
    d = cfg["hidden_size"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return (d * (2 * keys + 2 * values) + d * 2 * cfg["linear_num_value_heads"]
            + values * d)


def gdn_core_macs_per_token(cfg):
    return (3 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"])


def attention_proj_macs_per_token(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * 2 * h * hd + 2 * d * kv * hd + h * hd * d


def attention_core_macs_per_token(cfg):
    """Scores and ``P v`` of one token against the ``(T + 1) / 2`` keys it
    sees on average in a causal row of ``T``."""
    return cfg["num_attention_heads"] * 2 * cfg["head_dim"] * (cfg["seq_len"] + 1) / 2


def expert_layer_macs_per_token(cfg):
    d = cfg["hidden_size"]
    routed_here = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_width"]
    return (d * cfg["router_width"] + d
            + 3 * d * cfg["shared_expert_intermediate_size"]
            + 3 * d * cfg["moe_intermediate_size"] * routed_here)


def forward_macs_per_token(cfg):
    experts = expert_layer_macs_per_token(cfg)
    return (
        gdn_layers(cfg) * (gdn_proj_macs_per_token(cfg)
                           + gdn_core_macs_per_token(cfg) + experts)
        + softmax_layers(cfg) * (attention_proj_macs_per_token(cfg)
                                 + attention_core_macs_per_token(cfg) + experts)
        + cfg["hidden_size"] * cfg["vocab_size"]
    )


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs_per_token(cfg) * cfg["seq_len"]
