"""Operations and bytes of a gated short convolution between its two
projections, ``C x conv(B x X)``: a token's channel takes 5 multiply-adds
forward (the gate ``B x X``, one a tap of the ``conv_L_cache`` = 3, the gate
``C x .``) and, as every product here, twice that backward. The bytes are the
least ANY form moves, one that fuses the gates with the taps and makes the
forward again inside the backward without touching memory: forward ``B``,
``C``, ``X`` read and the product written, backward ``B``, ``C``, ``X`` and
the product's gradient read and the three gradients written, 11 tensors of
``[T, hidden]`` in bfloat16 (the taps and their gradient are ``[3, hidden]``:
nothing). The bytes bind on a v5e by two hundred to one (27 ps against 0.15 ps
a token's channel), so the share reads the passes over memory that the form
in the program makes beyond those eleven.
"""

TENSORS = 4 + 7  # forward: B, C, X in, C z out; backward: B, C, X, dy in, dB, dC, dX out


def core_per_round(cfg, rows, layers):
    """``(operations, bytes)`` of ``rows`` rows through ``layers`` short
    convolution layers, forward and backward."""
    channels = rows * layers * cfg["seq_len"] * cfg["hidden_size"]
    macs = 2 + cfg["conv_L_cache"]
    return channels * 2 * 3 * macs, channels * TENSORS * 2


def conv_layers(cfg):
    return [cfg["layer_types"][i] for i in cfg["layers_held"]].count("conv")
