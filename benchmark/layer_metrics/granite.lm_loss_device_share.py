"""Device-busy time under the final norm, the tied head and the cross-entropy
of the dense hybrid (``fed.local_step.fwd_bwd.lm_loss``: a row at a time over
the 12,544 vocabulary rows held, the logits over ``logits_scaling``, forward
and backward). The scope ``lm_loss.device_share`` reads, for a cell its list
does not name. Nothing to read, so nothing returned, where the program has no
such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.lm_loss")
