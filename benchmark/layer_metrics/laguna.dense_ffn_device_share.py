"""Device-busy time under the dense layer's SwiGLU
(``fed.local_step.fwd_bwd.dense_ffn``: layer 0's three products at the whole
width of 12,288, forward and backward), 44 % of the cell's counted
operations. Nothing to read, so nothing returned, where the program has no
such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.dense_ffn")
