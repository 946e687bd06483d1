"""Operations the forward and backward passes of the configuration's cut of
granite-4.0-h-micro need for one sample (one packed row of ``seq_len``
tokens), from its shapes and at the heads HELD: the matrix products of every
layer that is held here, TWO halves a layer by ``layer_types`` (a ``mamba``
layer's two projections and its recurrence, three ``P x N`` multiply-adds a
token a held head: the state decayed, the update ``dt x B^T`` added, the state
read with ``C``; the ``attention`` layer's four projections and its score and
value products over the causal half; every layer's SwiGLU of
``shared_intermediate_size``, whole), and the tied head over the vocabulary
slice. Training counts a forward and two backward products (2 FLOP x MACs x
3). No norm, softmax, gate, convolution (4 taps a channel), multiplier or
activation function, no optimizer, no recompute.

And the two cores' operations and least bytes, from this file's own keys
(``flops/ssd_core.py`` and ``flops/gqa_core.py`` read Nemotron-H's and LFM2's
key names and whole head counts): the same work whatever implements it, at
ANY chunk.
"""


def kinds_held(cfg):
    return [cfg["layer_types"][i] for i in cfg["layers_held"]]


def mamba_proj_macs_per_token(cfg):
    """``W_in`` into ``[z | x | B | C | dt]`` at the heads held (``B`` and
    ``C`` the one group's, whole) and ``W_out``'s rows."""
    d, heads = cfg["hidden_size"], cfg["mamba_n_heads"]
    d_in = heads * cfg["mamba_d_head"]
    wide = d_in + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return d * (d_in + wide + heads) + d_in * d


def mamba_core_macs_per_token(cfg):
    """Three ``P x N`` multiply-adds a held head: decay, update, read."""
    return 3 * cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def attention_proj_macs_per_token(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def attention_core_macs_per_token(cfg):
    """Scores and ``P v`` of one token against the ``(T + 1) / 2`` keys it
    sees on average in a row of ``T``, a held query head."""
    return cfg["num_attention_heads"] * 2 * cfg["head_dim"] * (cfg["seq_len"] + 1) / 2


def mlp_macs_per_token(cfg):
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def parts_macs_per_token(cfg):
    """Forward multiply-adds a token by part of the model."""
    out = dict.fromkeys(("mamba_proj", "mamba_core", "attention_proj",
                         "attention_core", "mlp", "head"), 0.0)
    for kind in kinds_held(cfg):
        if kind == "mamba":
            out["mamba_proj"] += mamba_proj_macs_per_token(cfg)
            out["mamba_core"] += mamba_core_macs_per_token(cfg)
        else:
            out["attention_proj"] += attention_proj_macs_per_token(cfg)
            out["attention_core"] += attention_core_macs_per_token(cfg)
        out["mlp"] += mlp_macs_per_token(cfg)
    out["head"] = cfg["hidden_size"] * cfg["vocab_size"]
    return out


def forward_macs_per_token(cfg):
    return sum(parts_macs_per_token(cfg).values())


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs_per_token(cfg) * cfg["seq_len"]


def scan_per_round(cfg, rows):
    """``(operations, bytes)`` of ``rows`` rows through the ``mamba`` layers
    held, forward and backward: three ``P x N`` multiply-adds a token a held
    head; ``x`` and the output (the held heads') and ``B`` and ``C`` (the ONE
    group's, read once by all its heads) in bfloat16 and the step sizes in
    float32, read or written once in the forward and, with their gradients,
    twice more in the backward; the state and a chunk's matrices never touch
    memory in that form."""
    t, heads, p = cfg["seq_len"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, layers = cfg["mamba_d_state"], kinds_held(cfg).count("mamba")
    macs = t * mamba_core_macs_per_token(cfg)
    tensors = t * ((2 * heads * p + 2 * cfg["mamba_n_groups"] * n) * 2 + heads * 4)
    return rows * layers * 2 * 3 * macs, rows * layers * 3 * tensors


def attention_core_per_round(cfg, rows):
    """``(operations, bytes)`` of ``rows`` rows through the ``attention``
    layers held, forward and backward: ``q k^T`` and ``P v`` over the causal
    half a held query head; q and the output a query head, k and v a
    key-value head (a group's queries read ONE copy), bfloat16, read or
    written once in the forward and, with their gradients, twice more in the
    backward; the scores never touch memory in that form."""
    t, h, kv = cfg["seq_len"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers = kinds_held(cfg).count("attention")
    macs = t * attention_core_macs_per_token(cfg)
    tensors = t * (2 * h + 2 * kv) * cfg["head_dim"] * 2
    return rows * layers * 2 * 3 * macs, rows * layers * 3 * tensors


def least_seconds(work, peaks):
    """The least a chip with ``peaks`` could take for ``work = (operations,
    bytes)``: the larger of operations over the bf16 peak and bytes over the
    HBM peak."""
    flops, nbytes = work
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
