"""Device-busy time under what an expert layer spends around its products:
``fed.local_step.fwd_bwd.moe.router`` (scores, the top choices, the gates),
``.dispatch`` (the sort of the pairs, the blocks' layout, the gather of their
rows) and ``.combine`` (the gates' product and the scatter-add), forward and
backward. Nothing to read, so nothing returned, where the program has none of
the three scopes."""

PARTS = ("router", "dispatch", "combine")


def read(ctx):
    from benchmark import trace_reduce

    shares = [trace_reduce.scope_share(
        ctx["trace"], "fed.local_step.fwd_bwd.moe." + part) for part in PARTS]
    found = [s for s in shares if s is not None]
    return sum(found) if found else None
