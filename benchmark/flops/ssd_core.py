"""Operations and bytes of Mamba-2's selective state-space recurrence between
its convolution and its gated norm: what the rule itself needs a token a head
at ANY chunk length, three ``P x N`` multiply-adds (the state decayed by
``exp(dt A)``, the update ``dt x B^T`` added, the output read with ``C``), a
forward and two backward products each. The bytes are the least any form
moves: ``x`` and the output (the heads') and ``B`` and ``C`` (the GROUPS',
read by their heads and never copied) in bfloat16 and the step sizes ``dt`` in
float32, read or written once in the forward and, with their gradients, twice
more in the backward; the state and the chunks' matrices never touch memory
in that form. The same work whatever implements it. The bytes bind on a v5e
(25 ns against 16 ns a token a layer, forward).
"""


def core_per_round(cfg, rows, layers):
    """``(operations, bytes)`` of ``rows`` rows through ``layers`` Mamba-2
    layers, forward and backward."""
    t, heads, p = cfg["seq_len"], cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    macs = t * heads * 3 * p * n
    tensors = t * ((2 * heads * p + 2 * groups * n) * 2 + heads * 4)
    return rows * layers * 2 * 3 * macs, rows * layers * 3 * tensors


def mamba_layers(cfg):
    """How many of the layers held are Mamba-2 mixers."""
    return sum(cfg["hybrid_override_pattern"][i] == "M" for i in cfg["layers_held"])


def least_seconds(cfg, rows, peaks):
    """The least a chip with ``peaks`` could take for a round's recurrences:
    the larger of operations over the bf16 peak and bytes over the HBM peak."""
    flops, nbytes = core_per_round(cfg, rows, mamba_layers(cfg))
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
