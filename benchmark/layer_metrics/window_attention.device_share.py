"""Device-busy time under the sliding-window attention layers' scope
(``fed.local_step.fwd_bwd.window_attention`` with ``.core``): projections,
gate, rotary turns, the banded core and the output product, forward and
backward, of every such layer. Nothing to read, so nothing returned, where
the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.window_attention")
