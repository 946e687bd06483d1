"""The sliding-window layers' core against its roofline: the least time the
chip could take for a round's score and value products over the EXACT pairs
the layers' mask admits (``flops/window_attention_core.py``, kind
``sliding_attention``: the heads held, forward and backward, the larger of
operations over the bf16 peak and bytes over the HBM peak; the operations
bind) over the device time under
``fed.local_step.fwd_bwd.window_attention.core``, whatever implements it, both
for the rounds the trace holds. The pattern is ``gqa.core_roofline.py``'s."""


def read(ctx):
    return ctx["cell"].code("flops", "window_attention_core").roofline_share(
        ctx, "fed.local_step.fwd_bwd.window_attention.core", "sliding_attention")
