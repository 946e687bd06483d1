"""Device-busy time under the Mamba-2 layers' convolution
(``fed.local_step.fwd_bwd.mamba.conv``: the shared causal depthwise
convolution of four taps over ``[x | B | C]``'s 6,144 channels with its bias
a channel, and the SiLU behind it, forward and backward). Nothing to read, so
nothing returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.mamba.conv")
