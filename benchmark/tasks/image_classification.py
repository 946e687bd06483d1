"""The task of the image configurations: float images, one label a row,
mean cross-entropy over the batch.

A task is what a configuration trains ON, apart from the model it trains: the
kind of data (made from the seed), the reference's loss, and the fields of the
program's configuration that state this kind of data. A configuration names
its task (``"task"``; absent, this one); the harness asks the task and names
no kind of data itself. A sample, in ``samples_per_s_per_chip``, is one row
of ``inputs``: here one image.

What a task gives: ``make_data(seed, cfg) -> (inputs, targets)``, host arrays
whose first axis is the examples, in an order of the task's own (a traffic
file with ``"shards": "contiguous"`` deals client ``c`` the ``c``-th run of
rows, so the order decides how the clients differ); ``loss(logits, targets)
-> scalar`` in float32, of a whole batch; ``program_fields(cfg)``; and, only
where the loss is not a mean over rows that all weigh alike (positions masked
at a document's end), ``loss_parts(logits, targets) -> (sum, count)`` with
``loss == sum / count``: a configuration that states
``"reference_block_rows"`` has the reference take a step's gradient block by
block, and the blocks then weigh by their counts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def make_data(seed, cfg):
    """Class prototypes plus unit noise, scaled by 0.25, in float32 values
    that bfloat16 holds exactly (the program stores its dataset in its
    compute type): ``(images [n, H, W, C] f32, labels [n] int32)`` as host
    arrays (the program takes its dataset from the host)."""
    from benchmark import seeded

    n, num_classes = cfg["num_examples"], cfg["num_classes"]
    image_shape = tuple(cfg["image_shape"])

    @jax.jit
    def gen(key):
        k_proto, k_lab, k_noise = jax.random.split(key, 3)
        proto = jax.random.normal(k_proto, (num_classes,) + image_shape)
        labels = jax.random.randint(k_lab, (n,), 0, num_classes)
        x = 0.25 * (proto[labels] + jax.random.normal(k_noise, (n,) + image_shape))
        return x.astype(jnp.bfloat16).astype(jnp.float32), labels.astype(jnp.int32)

    images, labels = gen(seeded.key_of(seed, 1))
    return np.asarray(images), np.asarray(labels)


def loss(logits, targets):
    """The reference's loss, in float32: ``logits [B, C]``, ``targets [B]``."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, targets[:, None], axis=1).mean()


def program_fields(cfg):
    """The fields of the program's ``RoundConfig`` / ``DataConfig`` that state
    this kind of data, by dataclass field name."""
    return {
        "round": {"num_classes": cfg["num_classes"],
                  "image_size": tuple(cfg["image_shape"])},
        "data": {"dataset": cfg["dataset"], "augment": cfg["augment"],
                 "device_layout": cfg["device_layout"]},
    }
