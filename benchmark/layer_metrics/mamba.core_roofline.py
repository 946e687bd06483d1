"""The selective state-space recurrence against its roofline: the least time
the chip could take for a round's recurrences (``flops/ssd_core.py``: three
``P x N`` multiply-adds a token a head, forward and backward, the larger of
operations over the bf16 peak and bytes over the HBM peak; the bytes bind)
over the device time under ``fed.local_step.fwd_bwd.mamba.core``, whatever
implements it, both for the rounds the trace holds. The pattern is
``gdn.core_roofline.py``'s. Nothing to read, so nothing returned, where the
program has no such scope."""


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    seconds = (t or {}).get("busy_by_scope", {}).get(
        "fed.local_step.fwd_bwd.mamba.core")
    if not seconds:
        return None
    least = cell.code("flops", "ssd_core").least_seconds(
        cell.config, cell.samples_per_round, ctx["peaks"])
    return 100.0 * least * ctx["traced_rounds"] / seconds
