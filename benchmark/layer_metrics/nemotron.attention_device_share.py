"""Device-busy time under the grouped-query attention layer of the
state-space hybrid (``fed.local_step.fwd_bwd.attention`` with ``.core``: the
layer's norm, the four projections, the rotary turns, and scores, softmax and
``P v`` of 32 query heads on 2 key-value heads of 128, forward and backward).
The scope ``full_attention.device_share`` reads, for a cell its list does not
name. Nothing to read, so nothing returned, where the program has no such
scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.attention")
