"""Device-busy time under the expert layers of the state-space hybrid
(``fed.local_step.fwd_bwd.moe`` with ``.router``, ``.dispatch``, ``.experts``,
``.combine``: the layer's norm, the router, the shared two-matrix expert, the
held experts' pairs laid out, multiplied and added back, forward and
backward). The scope ``moe.device_share`` reads, for a cell its list does not
name. Nothing to read, so nothing returned, where the program has no such
scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(ctx["trace"], "fed.local_step.fwd_bwd.moe")
