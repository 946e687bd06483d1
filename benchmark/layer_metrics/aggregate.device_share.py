"""Device-busy time under the aggregation's scope (``fed.aggregate`` with its
``.psum`` / ``.all_gather``): the weighted mean of the clients' changes, on
one chip and across chips."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(ctx["trace"], "fed.aggregate")
