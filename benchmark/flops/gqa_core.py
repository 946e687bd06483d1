"""Operations and bytes of grouped-query softmax attention's core, the score
and value products between the projections: for a row of ``T`` tokens and
``H`` query heads of ``hidden_size / H`` on ``num_key_value_heads`` key-value
heads of the same width, ``q k^T`` and ``P v`` over the head's width, the
causal half of the ``T x T`` pairs, a forward and two backward products each.
The bytes are the least any form moves: q and the output a query head, k and
v a key-value head (a group's queries read ONE copy), read or written once in
the forward and, with their gradients, twice more in the backward, in
bfloat16; the scores never touch memory in that form. The operations bind at
every length a cell has (at 4,096 tokens by forty to one).
"""


def core_per_round(cfg, rows, attention_layers):
    """``(operations, bytes)`` of ``rows`` rows through ``attention_layers``
    layers, forward and backward."""
    t, h, kv = cfg["seq_len"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    width = cfg["hidden_size"] // h
    macs = h * 2 * width * t * (t + 1) / 2
    tensors = t * (2 * h + 2 * kv) * width * 2  # q, out; k, v in bfloat16
    return (rows * attention_layers * 2 * 3 * macs,
            rows * attention_layers * 3 * tensors)


def attention_layers(cfg):
    return [cfg["layer_types"][i] for i in cfg["layers_held"]].count("full_attention")
