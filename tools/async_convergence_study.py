#!/usr/bin/env python
"""Sync-vs-FedBuff convergence comparison on the engine (VERDICT r3 #7).

Same task, same clients, same total LOCAL work per unit of wall-clock
(one tick == one synchronous round == every live client trains one local
epoch): the synchronous engine aggregates everyone at a barrier; the async
engine aggregates ``buffer_k`` staleness-discounted arrivals per tick under
heterogeneous client speeds (``speed_sigma``). Writes one JSONL row per
round/tick with the global model's test accuracy for each mode, plus a
summary row — the committed artifact is
``artifacts/ASYNC_SYNC_CONVERGENCE.jsonl``.

Run (CPU): ``python tools/async_convergence_study.py``
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax

jax.config.update("jax_platforms", "cpu")  # this is a CPU study

from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
from fedtpu.core import AsyncFederation, Federation
from fedtpu.data import load

ROUNDS = 25
ART = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "artifacts")


def cfg_for():
    return RoundConfig(
        model="smallcnn",
        num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05, schedule="constant"),
        data=DataConfig(
            dataset="cifar10_hard",
            batch_size=32,
            partition="dirichlet",
            dirichlet_alpha=0.5,
            num_examples=1024,
            augment=False,
        ),
        fed=FedConfig(num_clients=8),
        steps_per_round=4,
    )


def main():
    out_path = os.path.join(ART, "ASYNC_SYNC_CONVERGENCE.jsonl")
    test = load("cifar10_hard", "test", num=1024)
    rows = []
    cfg = cfg_for()

    sync = Federation(cfg, seed=0)
    for r in range(ROUNDS):
        sync.step()
        _, acc = sync.evaluate(*test)
        rows.append({"mode": "sync_barrier", "round": r,
                     "test_acc": round(acc, 4)})
        print(rows[-1], file=sys.stderr, flush=True)

    for sigma in (0.0, 1.0):
        # damping=False pinned: the fedbuff_k2_sigma* labels in the artifact
        # mean the round-4 weight-normalized semantics; the damped (now
        # engine-default) runs are fedbuff_stall_study.py --damped with
        # *_damped labels.
        asyn = AsyncFederation(cfg, seed=0, buffer_k=2, speed_sigma=sigma,
                               staleness_damping=False)
        stale_total = 0.0
        for r in range(ROUNDS):
            m = asyn.tick()
            stale_total += float(m.staleness_mean)
            _, acc = asyn.evaluate(*test)
            rows.append({"mode": f"fedbuff_k2_sigma{sigma:g}", "round": r,
                         "test_acc": round(acc, 4),
                         "staleness_mean": round(float(m.staleness_mean), 2)})
            print(rows[-1], file=sys.stderr, flush=True)
        rows.append({"mode": f"fedbuff_k2_sigma{sigma:g}",
                     "summary": True,
                     "mean_staleness": round(stale_total / ROUNDS, 2),
                     "final_test_acc": rows[-1]["test_acc"]})

    with open(out_path, "w") as fh:
        for r in rows:
            fh.write(json.dumps(r) + "\n")
    print(json.dumps({"written": out_path, "rows": len(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
