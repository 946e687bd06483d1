"""Device-busy time under latent attention's scope
(``fed.local_step.fwd_bwd.attention`` with ``.core``): the projections, the
norms and rotary turns between them, scores, softmax and ``P v``, forward
and backward, of every block and of the prediction module's. Nothing to read,
so nothing returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.attention")
