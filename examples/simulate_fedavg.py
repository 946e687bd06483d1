"""64-client FedAvg on CIFAR-10, all simulated in one XLA program.

The TPU-native deployment mode: clients are an array axis, the whole round
(local SGD for every client + weighted aggregation) is one jitted step.

    python examples/simulate_fedavg.py            # full run
    python examples/simulate_fedavg.py --smoke    # 30-second CPU check
"""

import argparse
import sys
import time

sys.path.insert(0, ".")

from fedtpu import DataConfig, FedConfig, Federation, OptimizerConfig, RoundConfig
from fedtpu.data import load


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true")
    p.add_argument(
        "--platform",
        default=None,
        choices=["cpu", "tpu"],
        help="pin the jax platform (--smoke implies cpu); without a pin the "
        "run uses whatever backend jax initialises",
    )
    args = p.parse_args()
    if args.platform or args.smoke:
        import jax

        jax.config.update("jax_platforms", args.platform or "cpu")

    cfg = RoundConfig(
        model="smallcnn" if args.smoke else "MobileNet",
        num_classes=10,
        opt=OptimizerConfig(learning_rate=0.1),
        data=DataConfig(
            dataset="cifar10",
            batch_size=32 if args.smoke else 128,
            partition="dirichlet",
            num_examples=2048 if args.smoke else None,
        ),
        fed=FedConfig(num_clients=8 if args.smoke else 64),
        steps_per_round=2 if args.smoke else 6,
    )
    fed = Federation(cfg, seed=0)
    test = load("cifar10", "test", num=cfg.data.num_examples)

    rounds = 3 if args.smoke else 20
    for r in range(rounds):
        t0 = time.time()
        m = fed.step()
        print(
            f"round {r}: loss={float(m.loss):.4f} acc={float(m.accuracy):.4f} "
            f"({time.time() - t0:.2f}s)"
        )
    print("test (loss, acc):", fed.evaluate(*test))


if __name__ == "__main__":
    main()
