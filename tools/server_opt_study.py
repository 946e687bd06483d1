#!/usr/bin/env python
"""Convergence comparison of the server-optimizer family (FedOpt).

Runs the same federated workload under server_optimizer = none (FedAvg,
reference semantics) / momentum (FedAvgM) / adam (FedAdam) and writes one
JSONL row per (optimizer, round) with train loss/acc and test accuracy to
``artifacts/SERVER_OPT_CONVERGENCE.jsonl``. CPU-friendly scale; data is the
deterministic synthetic surrogate (tagged in every row — no real datasets
exist in this environment).
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main():
    import jax

    # A CPU study by default. Pass --tpu to run on the chip.
    if "--tpu" not in sys.argv:
        jax.config.update("jax_platforms", "cpu")

    from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
    from fedtpu.core import Federation
    from fedtpu.data import load

    rows = []
    for name, server_lr in (("none", 1.0), ("momentum", 0.7), ("adam", 0.02)):
        cfg = RoundConfig(
            model="mlp",
            num_classes=10,
            opt=OptimizerConfig(learning_rate=0.02, weight_decay=0.0),
            data=DataConfig(
                dataset="cifar10", batch_size=32, partition="dirichlet",
                num_examples=4096,
            ),
            fed=FedConfig(
                num_clients=16, server_optimizer=name, server_lr=server_lr
            ),
            steps_per_round=4,
        )
        fed = Federation(cfg, seed=0)
        test = load("cifar10", "test", num=2048)
        for r in range(30):
            m = fed.step()
            row = {
                "server_optimizer": name,
                "server_lr": server_lr,
                "round": r,
                "loss": round(float(m.loss), 5),
                "acc": round(float(m.accuracy), 5),
                "dataset": cfg.data.dataset,
                "data_source": fed.data_source,
            }
            if (r + 1) % 5 == 0:
                tl, ta = fed.evaluate(*test)
                row["test_loss"], row["test_acc"] = round(tl, 5), round(ta, 5)
            rows.append(row)
        print(f"{name}: final loss {rows[-1]['loss']}", file=sys.stderr)

    out = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "artifacts",
        "SERVER_OPT_CONVERGENCE.jsonl",
    )
    with open(out, "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    print(f"wrote {len(rows)} rows to {out}")


if __name__ == "__main__":
    main()
