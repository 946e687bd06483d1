"""Register a custom flax model and federate it.

Any ``flax.linen.Module`` whose ``__call__(x, train=...)`` returns logits
can join the zoo via ``fedtpu.models.register`` and then be selected by name
in ``RoundConfig.model`` — the same extension point the reference lacks (its
architecture is hardcoded in two places, ``src/main.py:69`` and
``src/server.py:158``).

    python examples/custom_model.py
"""

import sys

sys.path.insert(0, ".")

import flax.linen as nn

from fedtpu import DataConfig, FedConfig, Federation, OptimizerConfig, RoundConfig
from fedtpu.models import register


@register("tinynet")
class TinyNet(nn.Module):
    num_classes: int = 10

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Conv(16, (3, 3), padding=1)(x)
        x = nn.relu(x)
        x = x.mean(axis=(1, 2))
        return nn.Dense(self.num_classes)(x)


def main():
    if "--tpu" not in sys.argv:
        # CPU by default: the demo is seconds-scale and must run anywhere.
        import jax

        jax.config.update("jax_platforms", "cpu")
    cfg = RoundConfig(
        model="tinynet",
        num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05),
        data=DataConfig(dataset="synthetic", batch_size=16, num_examples=512,
                        partition="iid"),
        fed=FedConfig(num_clients=4),
        steps_per_round=4,
    )
    fed = Federation(cfg, seed=0)
    for r in range(5):
        m = fed.step()
        print(f"round {r}: loss={float(m.loss):.4f} acc={float(m.accuracy):.4f}")


if __name__ == "__main__":
    main()
