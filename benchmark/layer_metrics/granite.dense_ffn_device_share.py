"""Device-busy time under the dense hybrid's SwiGLUs
(``fed.local_step.fwd_bwd.dense_ffn``: every layer's norm and three products
at the whole width of 8,192, forward and backward, ten of them), three
quarters of the cell's counted operations. The scope
``laguna.dense_ffn_device_share`` reads, for a cell its list does not name.
Nothing to read, so nothing returned, where the program has no such scope."""


def read(ctx):
    from benchmark import trace_reduce

    return trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.dense_ffn")
