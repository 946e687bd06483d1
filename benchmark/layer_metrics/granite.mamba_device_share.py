"""Device-busy time under the dense hybrid's Mamba-2 layers
(``fed.local_step.fwd_bwd.mamba`` with ``.proj``, ``.conv``, ``.core``,
``.out``: the mixer's norm, the input projection at the 32 heads held, the
biased convolution and its SiLU, the step sizes and the chunked selective scan
in chunks of 256, the gate, the norm over the channels held and the output
product, forward and backward, of the nine such layers). The scope
``mamba.device_share`` reads, for a cell its list does not name. Nothing to
read, so nothing returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(ctx["trace"], "fed.local_step.fwd_bwd.mamba")
