"""Model FLOP/s utilisation: the forward and backward operations per sample
(the configuration's FLOP function) times the samples a chip consumed per
second of the window's rounds, over the chip's bf16 peak."""


def read(ctx):
    cell = ctx["cell"]
    flops = cell.flops.train_flops_per_sample(cell.config)
    return 100.0 * flops * ctx["rate"] / ctx["peaks"]["bf16_flops_per_s"]
