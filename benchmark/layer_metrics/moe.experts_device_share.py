"""Device-busy time under the held experts' grouped products
(``fed.local_step.fwd_bwd.moe.experts``: the three batched products over
blocks of one expert's rows, forward and backward), the part of an expert
layer that grows with the rows a product. Nothing to read, so nothing
returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.moe.experts")
