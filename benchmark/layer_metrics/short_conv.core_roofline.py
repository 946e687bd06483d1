"""The gated short convolution's gates and taps against their roofline: the
least time the chip could take for a round's ``C x conv(B x X)``
(``flops/short_conv_core.py``: eleven passes over a ``[T, hidden]`` tensor in
bfloat16, forward and backward, the larger of operations over the bf16 peak
and bytes over the HBM peak; the bytes bind) over the device time under
``fed.local_step.fwd_bwd.short_conv.core``, whatever implements it, both for
the rounds the trace holds. The pattern is ``gdn.core_roofline.py``'s."""


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    seconds = (t or {}).get("busy_by_scope", {}).get(
        "fed.local_step.fwd_bwd.short_conv.core")
    if not seconds:
        return None
    core = cell.code("flops", "short_conv_core")
    flops, nbytes = core.core_per_round(
        cell.config, cell.samples_per_round, core.conv_layers(cell.config))
    least = max(flops / ctx["peaks"]["bf16_flops_per_s"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced_rounds"] / seconds
