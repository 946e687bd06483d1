"""Operations the forward and backward passes of smallcnn need, from its shapes.

Multiply-accumulates of the two convolutions and the two dense layers for one
image; training counts a forward and two backward products per layer
(2 FLOP x MACs x 3). No pooling, no activation, no optimizer, no recompute.
"""


def forward_macs_per_sample(cfg):
    h, w, c = cfg["image_shape"]
    c1, c2 = cfg["conv_widths"]
    d, classes = cfg["dense_width"], cfg["num_classes"]
    conv1 = 3 * 3 * c * c1 * h * w
    conv2 = 3 * 3 * c1 * c2 * (h // 2) * (w // 2)
    dense1 = (h // 4) * (w // 4) * c2 * d
    return conv1 + conv2 + dense1 + d * classes


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs_per_sample(cfg)
