"""Device-busy time under the full-attention layers' scope
(``fed.local_step.fwd_bwd.attention`` with ``.core``) in a model whose other
mixers attend over a window: the layers that see the whole causal prefix,
one in four (and layer 0) of this stack. At 8,192 tokens a full layer's core
is 8.3 times a window layer's a head (16 times for the row's last query).
Nothing to read, so nothing returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.attention")
