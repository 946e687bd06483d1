"""Device-busy time under the Mamba-2 layers' scope
(``fed.local_step.fwd_bwd.mamba`` with ``.proj``, ``.conv``, ``.core``,
``.out``): the layer's norm, the input projection, the biased convolution and
its SiLU, the step sizes and the chunked selective scan, the gate, the grouped
norm and the output product, forward and backward, of every such layer.
Nothing to read, so nothing returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(ctx["trace"], "fed.local_step.fwd_bwd.mamba")
