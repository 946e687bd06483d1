"""Seconds jax.monitoring reports for compiling and for loading programs from
the compilation cache during set-up (counter; the harness's CompileLog)."""


def read(ctx):
    return ctx["compile_setup_s"]
