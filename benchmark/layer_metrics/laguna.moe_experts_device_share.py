"""Device-busy time under the held experts' grouped products of the
window-and-full attention stack (``fed.local_step.fwd_bwd.moe.experts``: the
three batched products over blocks of one expert's rows, forward and
backward), 320 expected rows an expert a product here. The scope
``moe.experts_device_share`` reads, for a cell its list does not name.
Nothing to read, so nothing returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.moe.experts")
