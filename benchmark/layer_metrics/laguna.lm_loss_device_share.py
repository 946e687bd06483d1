"""Device-busy time under ``fed.local_step.fwd_bwd.lm_loss`` in the
window-and-full attention stack's cell: the final norm, the untied head's
product over the vocabulary slice, log-softmax and their backward. The scope
``lm_loss.device_share`` reads, for a cell its list does not name. Nothing to
read, so nothing returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(ctx["trace"], "fed.local_step.fwd_bwd.lm_loss")
