"""Device-busy time under the gated DeltaNet layers' scope
(``fed.local_step.fwd_bwd.linear_attention`` with ``.proj``, ``.conv``,
``.core``, ``.out``): the projections, the convolution, the gates and the
chunked delta rule, the gated norm and the output product, forward and
backward, of every such layer. Nothing to read, so nothing returned, where
the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.linear_attention")
