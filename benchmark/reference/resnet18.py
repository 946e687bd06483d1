"""Plain reference of ResNet-18 for 32x32 inputs (kuangliu/pytorch-cifar
``models/resnet.py``: BasicBlock, [2,2,2,2], 3x3 stem, BatchNorm, 1x1
projection shortcuts where the shape changes)."""

from __future__ import annotations

import jax

from benchmark.reference.layers import ident, batch_norm, conv, dense, max_pool2


def _resnet_blocks(cfg):
    """(name, in, out, stride) of every BasicBlock, in depth order."""
    out, k, cin = [], 0, cfg["stem_width"]
    for stage, (width, n) in enumerate(zip(cfg["stage_widths"], cfg["blocks"])):
        for i in range(n):
            stride = (1 if stage == 0 else 2) if i == 0 else 1
            out.append((f"BasicBlock_{k}", cin, width, stride))
            cin, k = width, k + 1
    return out


def spec(cfg):
    c = cfg["image_shape"][2]
    params, stats = [], []

    def bn(prefix, width):
        params.append((prefix + ("scale",), (width,), "ones"))
        params.append((prefix + ("bias",), (width,), "zeros"))
        stats.append((prefix + ("mean",), (width,), "zeros"))
        stats.append((prefix + ("var",), (width,), "ones"))

    params.append((("Conv_0", "kernel"), (3, 3, c, cfg["stem_width"]), "he"))
    bn(("BatchNorm_0",), cfg["stem_width"])
    for name, cin, cout, stride in _resnet_blocks(cfg):
        params.append(((name, "Conv_0", "kernel"), (3, 3, cin, cout), "he"))
        bn((name, "BatchNorm_0"), cout)
        params.append(((name, "Conv_1", "kernel"), (3, 3, cout, cout), "he"))
        bn((name, "BatchNorm_1"), cout)
        if stride != 1 or cin != cout:
            params.append(((name, "Conv_2", "kernel"), (1, 1, cin, cout), "he"))
            bn((name, "BatchNorm_2"), cout)
    last = cfg["stage_widths"][-1]
    params.append((("Dense_0", "kernel"), (last, cfg["num_classes"]), "head"))
    params.append((("Dense_0", "bias"), (cfg["num_classes"],), "zeros"))
    return params, stats


def make_forward(cfg):
    blocks = _resnet_blocks(cfg)

    def forward(params, stats, x, quant=ident):
        new = {}
        x = conv(x, params["Conv_0"]["kernel"], 1, 1, quant)
        x, new["BatchNorm_0"] = batch_norm(
            x, params["BatchNorm_0"], stats["BatchNorm_0"])
        x = jax.nn.relu(x)
        for name, cin, cout, stride in blocks:
            p, s, ns = params[name], stats[name], {}
            y = conv(x, p["Conv_0"]["kernel"], stride, 1, quant)
            y, ns["BatchNorm_0"] = batch_norm(y, p["BatchNorm_0"], s["BatchNorm_0"])
            y = jax.nn.relu(y)
            y = conv(y, p["Conv_1"]["kernel"], 1, 1, quant)
            y, ns["BatchNorm_1"] = batch_norm(y, p["BatchNorm_1"], s["BatchNorm_1"])
            r = x
            if "Conv_2" in p:
                r = conv(x, p["Conv_2"]["kernel"], stride, 0, quant)
                r, ns["BatchNorm_2"] = batch_norm(
                    r, p["BatchNorm_2"], s["BatchNorm_2"])
            x = jax.nn.relu(y + r)
            new[name] = ns
        x = x.mean(axis=(1, 2))
        return dense(x, params["Dense_0"], quant), new

    return forward
