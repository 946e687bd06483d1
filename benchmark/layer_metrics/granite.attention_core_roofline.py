"""The dense hybrid's softmax core against its roofline: the least time the
chip could take for a round's score and value products
(``flops/granite_4_0_h_micro.py``: the causal half at the 16 query heads held
on 4 key-value heads of 64, forward and backward, the larger of operations
over the bf16 peak and bytes over the HBM peak; the operations bind) over the
device time under ``fed.local_step.fwd_bwd.attention.core``, whatever
implements it, both for the rounds the trace holds. The pattern is
``gqa.core_roofline.py``'s. Nothing to read, so nothing returned, where the
program has no such scope."""


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    seconds = (t or {}).get("busy_by_scope", {}).get(
        "fed.local_step.fwd_bwd.attention.core")
    if not seconds:
        return None
    flops = cell.code("flops", "granite_4_0_h_micro")
    least = flops.least_seconds(
        flops.attention_core_per_round(cell.config, cell.samples_per_round),
        ctx["peaks"])
    return 100.0 * least * ctx["traced_rounds"] / seconds
