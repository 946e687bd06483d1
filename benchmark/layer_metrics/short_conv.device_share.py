"""Device-busy time under the gated short convolution layers' scope
(``fed.local_step.fwd_bwd.short_conv`` with ``.proj``, ``.core``, ``.out``):
``W_in``, the two gates and the taps, ``W_out``, forward and backward, of
every such layer. Nothing to read, so nothing returned, where the program has
no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.short_conv")
