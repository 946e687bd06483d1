"""The dense hybrid's selective state-space recurrence against its roofline:
the least time the chip could take for a round's recurrences
(``flops/granite_4_0_h_micro.py``: three ``P x N`` multiply-adds a token a
held head, ``B`` and ``C`` read once for the one group, forward and backward,
the larger of operations over the bf16 peak and bytes over the HBM peak; the
bytes bind) over the device time under ``fed.local_step.fwd_bwd.mamba.core``,
whatever implements it (the plain chunks at the published 256 today: this is
their price), both for the rounds the trace holds. The pattern is
``mamba.core_roofline.py``'s. Nothing to read, so nothing returned, where the
program has no such scope."""


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    seconds = (t or {}).get("busy_by_scope", {}).get(
        "fed.local_step.fwd_bwd.mamba.core")
    if not seconds:
        return None
    flops = cell.code("flops", "granite_4_0_h_micro")
    least = flops.least_seconds(
        flops.scan_per_round(cell.config, cell.samples_per_round), ctx["peaks"])
    return 100.0 * least * ctx["traced_rounds"] / seconds
