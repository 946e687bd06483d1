"""Operations the forward and backward passes of ResNet-18 need at the
configuration's input size, from its shapes.

Multiply-accumulates of every convolution (3x3 stem, two 3x3 per BasicBlock,
a 1x1 projection where a block changes shape) and of the classifier, for one
image; training counts a forward and two backward products per layer
(2 FLOP x MACs x 3). BatchNorm, activations, the optimizer and whatever
rematerialisation recomputes are not counted.
"""


def forward_macs_per_sample(cfg):
    h, w, c = cfg["image_shape"]
    macs = 3 * 3 * c * cfg["stem_width"] * h * w
    cin = cfg["stem_width"]
    for stage, (width, n) in enumerate(zip(cfg["stage_widths"], cfg["blocks"])):
        for i in range(n):
            stride = (1 if stage == 0 else 2) if i == 0 else 1
            h, w = h // stride, w // stride
            macs += 3 * 3 * cin * width * h * w      # first 3x3 (strided)
            macs += 3 * 3 * width * width * h * w    # second 3x3
            if stride != 1 or cin != width:
                macs += cin * width * h * w          # 1x1 projection
            cin = width
    return macs + cin * cfg["num_classes"]


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs_per_sample(cfg)
