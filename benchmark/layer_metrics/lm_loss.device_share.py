"""Device-busy time under ``fed.local_step.fwd_bwd.lm_loss``: the final
norm, the head's product over the vocabulary slice, log-softmax and their
backward, for the next-token head and the prediction module's alike. Nothing
to read, so nothing returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(ctx["trace"], "fed.local_step.fwd_bwd.lm_loss")
