"""Device-busy time under the expert layer's scope
(``fed.local_step.fwd_bwd.moe`` with ``.router``, ``.dispatch``, ``.experts``,
``.combine``; the shared expert is the scope's own time). Nothing to read,
so nothing returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(ctx["trace"], "fed.local_step.fwd_bwd.moe")
