"""Plain reference of ``fedtpu/models/smallcnn.py``: conv 32, pool, conv 64,
pool, dense 128, dense classes. Returns ``(param spec, stats spec)`` and the
forward pass ``(params, stats, x, quant) -> (logits, new stats)``."""

from __future__ import annotations

import jax

from benchmark.reference.layers import ident, batch_norm, conv, dense, max_pool2


def spec(cfg):
    h, w, c = cfg["image_shape"]
    c1, c2, d = cfg["conv_widths"][0], cfg["conv_widths"][1], cfg["dense_width"]
    flat = (h // 4) * (w // 4) * c2
    return [
        (("Conv_0", "kernel"), (3, 3, c, c1), "he"),
        (("Conv_0", "bias"), (c1,), "zeros"),
        (("Conv_1", "kernel"), (3, 3, c1, c2), "he"),
        (("Conv_1", "bias"), (c2,), "zeros"),
        (("Dense_0", "kernel"), (flat, d), "he"),
        (("Dense_0", "bias"), (d,), "zeros"),
        (("Dense_1", "kernel"), (d, cfg["num_classes"]), "head"),
        (("Dense_1", "bias"), (cfg["num_classes"],), "zeros"),
    ], []


def _forward(params, stats, x, quant=ident):
    x = jax.nn.relu(conv(x, params["Conv_0"]["kernel"], 1, 1, quant)
                    + params["Conv_0"]["bias"])
    x = max_pool2(x)
    x = jax.nn.relu(conv(x, params["Conv_1"]["kernel"], 1, 1, quant)
                    + params["Conv_1"]["bias"])
    x = max_pool2(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(dense(x, params["Dense_0"], quant))
    return dense(x, params["Dense_1"], quant), stats


def make_forward(cfg):
    return _forward
