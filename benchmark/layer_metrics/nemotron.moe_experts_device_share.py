"""Device-busy time under the held experts' grouped products of the
state-space hybrid (``fed.local_step.fwd_bwd.moe.experts``: TWO batched
products over blocks of one expert's rows with ``relu2`` between them,
forward and backward), at the width 1,856 = 14.5 x 128, which takes the plain
batched body on a TPU; 384 expected rows an expert a product here. The scope
``moe.experts_device_share`` reads, for a cell its list does not name.
Nothing to read, so nothing returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.moe.experts")
