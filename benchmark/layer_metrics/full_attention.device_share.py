"""Device-busy time under the softmax attention layer's scope
(``fed.local_step.fwd_bwd.attention`` with ``.core``) in a model whose other
layers are linear attention: the number that says when the one softmax layer
in four starts to hide the recurrent ones. Nothing to read, so nothing
returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.attention")
