"""Grouped-query attention's core against its roofline: the least time the
chip could take for a round's score and value products
(``flops/gqa_core.py``: the causal half, forward and backward, the larger of
operations over the bf16 peak and bytes over the HBM peak; the operations
bind) over the device time under ``fed.local_step.fwd_bwd.attention.core``,
whatever implements it (the plain query blocks at heads of 64 today), both
for the rounds the trace holds. The pattern is ``mla.core_roofline.py``'s."""


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    seconds = (t or {}).get("busy_by_scope", {}).get(
        "fed.local_step.fwd_bwd.attention.core")
    if not seconds:
        return None
    core = cell.code("flops", "gqa_core")
    flops, nbytes = core.core_per_round(
        cell.config, cell.samples_per_round, core.attention_layers(cell.config))
    least = max(flops / ctx["peaks"]["bf16_flops_per_s"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced_rounds"] / seconds
