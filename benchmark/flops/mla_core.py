"""Operations and bytes of latent attention's core, the score and value
products between the projections: for a row of ``T`` tokens and ``H`` heads,
``q k^T`` over ``qk_nope_head_dim + qk_rope_head_dim`` and ``P v`` over
``v_head_dim``, the causal half of the ``T x T`` pairs, a forward and two
backward products each. The bytes are the least any form moves: q, k, v and
the output read or written once in the forward and, with their gradients,
twice more in the backward, in bfloat16; the scores never touch memory in
that form. The operations bind at every length a cell has.
"""


def core_per_round(cfg, rows, attention_layers):
    """``(operations, bytes)`` of ``rows`` rows through ``attention_layers``
    layers, forward and backward."""
    t, h = cfg["seq_len"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    vd = cfg["v_head_dim"]
    macs = h * (qk + vd) * t * (t + 1) / 2
    tensors = t * h * (2 * qk + 2 * vd) * 2  # q, k, v, out in bfloat16
    return (rows * attention_layers * 2 * 3 * macs,
            rows * attention_layers * 3 * tensors)


def attention_layers(cfg):
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
