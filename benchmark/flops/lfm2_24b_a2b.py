"""Operations the forward and backward passes of the configuration's cut of
LFM2-24B-A2B need for one sample (one packed row of ``seq_len`` tokens), from
its shapes: the matrix products of every layer that is held here (a
convolution layer's ``W_in`` and ``W_out``, the attention layer's four
projections and its score and value products over the causal half, the dense
feed-forward, the router), the held routed experts at their EXPECTED load (a
token picks ``num_experts_per_tok`` of ``router_width`` experts,
``num_experts`` of which live here: ``k * held / width`` of them a token on
average; the real load follows the routing), and the tied head over the
vocabulary slice. Training counts a forward and two backward products (2 FLOP
x MACs x 3). No norm, softmax, rotary, gate, convolution tap or activation
function, no optimizer, no recompute.
"""


def kinds(cfg):
    return [cfg["layer_types"][i] for i in cfg["layers_held"]]


def conv_layers(cfg):
    return kinds(cfg).count("conv")


def attention_layers(cfg):
    return kinds(cfg).count("full_attention")


def conv_macs_per_token(cfg):
    d = cfg["hidden_size"]
    return d * 3 * d + d * d


def attention_proj_macs_per_token(cfg):
    d = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * d // cfg["num_attention_heads"]
    return 2 * d * d + 2 * d * kv


def attention_core_macs_per_token(cfg):
    """Scores and ``P v`` of one token against the ``(T + 1) / 2`` keys it
    sees on average in a causal row of ``T``; the heads' widths add up to the
    hidden size."""
    return 2 * cfg["hidden_size"] * (cfg["seq_len"] + 1) / 2


def expert_layer_macs_per_token(cfg):
    d = cfg["hidden_size"]
    routed_here = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_width"]
    return d * cfg["router_width"] + 3 * d * cfg["moe_intermediate_size"] * routed_here


def forward_macs_per_token(cfg):
    d = cfg["hidden_size"]
    dense = cfg["num_dense_layers"]
    return (
        conv_layers(cfg) * conv_macs_per_token(cfg)
        + attention_layers(cfg) * (attention_proj_macs_per_token(cfg)
                                   + attention_core_macs_per_token(cfg))
        + dense * 3 * d * cfg["intermediate_size"]
        + (cfg["num_hidden_layers"] - dense) * expert_layer_macs_per_token(cfg)
        + d * cfg["vocab_size"]
    )


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs_per_token(cfg) * cfg["seq_len"]
