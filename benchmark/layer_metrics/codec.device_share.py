"""Device-busy time under the codec's scope (``fed.codec`` with ``.rotate``,
``.quantize``, ``.feedback``, ``.select``). Nothing to read, so nothing
returned, in a cell whose traffic has no codec."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(ctx["trace"], "fed.codec")
