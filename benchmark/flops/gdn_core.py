"""Operations and bytes of the gated delta rule between its projections and
its gated norm: what the rule itself needs a token a value head at ANY chunk
length, three ``dk x dv`` products (the state decayed and read with ``k``,
the update ``k u^T``, the output read with ``q``), a forward and two backward
products each. The bytes are the least any form moves: ``q`` and ``k`` (the
key heads'), ``v`` and the output (the value heads') in bfloat16 and the two
gates ``g``, ``beta`` in float32, read or written once in the forward and,
with their gradients, twice more in the backward; the state and the chunks'
matrices never touch memory in that form. The bytes bind on a v5e (30 ns
against 16 ns a token a layer, forward).
"""


def core_per_round(cfg, rows, layers):
    """``(operations, bytes)`` of ``rows`` rows through ``layers`` gated
    DeltaNet layers, forward and backward."""
    t = cfg["seq_len"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    macs = t * hv * 3 * dk * dv
    tensors = t * ((2 * hk * dk + 2 * hv * dv) * 2 + 2 * hv * 4)
    return rows * layers * 2 * 3 * macs, rows * layers * 3 * tensors


def gdn_layers(cfg):
    return cfg["num_hidden_layers"] - (
        cfg["num_hidden_layers"] // cfg["full_attention_interval"])
