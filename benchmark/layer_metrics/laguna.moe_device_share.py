"""Device-busy time under the four sparse layers' feed-forwards of the
window-and-full attention stack (``fed.local_step.fwd_bwd.moe`` with
``.router``, ``.dispatch``, ``.experts``, ``.combine``; the ungated shared
expert is the scope's own time): the cell's largest scope. The scope
``moe.device_share`` reads, for a cell its list does not name. Nothing to
read, so nothing returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(ctx["trace"], "fed.local_step.fwd_bwd.moe")
