"""Device-busy time under the dense hybrid's one attention layer
(``fed.local_step.fwd_bwd.attention`` with ``.core``: the mixer's norm, the
four projections at the heads held, and scores, softmax and ``P v`` of 16
query heads on 4 key-value heads of 64 with no position term, forward and
backward). The scope ``nemotron.attention_device_share`` reads, for a cell its
list does not name. Nothing to read, so nothing returned, where the program
has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.attention")
