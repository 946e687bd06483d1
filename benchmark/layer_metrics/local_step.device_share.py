"""Device-busy time under the local step's scope (``fed.local_step`` with its
``.fwd_bwd`` and ``.optimizer``): every busy instant counted once, for the
innermost running operation. What is left is feed, pack, codec, aggregate,
server step and what the compiler inserted without a scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(ctx["trace"], "fed.local_step")
