"""Device-busy time under the grouped-query softmax layer's scope
(``fed.local_step.fwd_bwd.attention`` with ``.core``) in a model whose other
mixers are short convolutions: projections, head norms, rotary and the core,
the largest single scope of the cell while heads of 64 run the plain query
blocks. Nothing to read, so nothing returned, where the program has no such
scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.attention")
