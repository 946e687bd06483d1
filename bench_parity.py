#!/usr/bin/env python
"""The 5 parity configs from BASELINE.md, end to end.

Each config runs through the simulated engine (the TPU-native path) and
reports rounds/sec + accuracies as one JSON line per config. ``--quick``
shrinks datasets/rounds for smoke runs on CPU; the full mode is sized for the
real chip. The reference publishes no numbers (BASELINE.md), so these are the
framework-side columns of the parity table.

Reference round semantics are preserved: one round = every client trains its
shard for `local_epochs` epochs (folded into steps_per_round), then one
weighted aggregation.
"""

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax

from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
from fedtpu.core import Federation
from fedtpu.data import load


_TRAIN_SIZE = {"mnist": 60000, "cifar10": 50000, "cifar100": 50000}


def cpu_scale_examples(clients: int) -> int:
    """Dataset truncation for cpu-scale parity runs: 64 examples/client."""
    return 64 * clients


def configs(quick: bool, cpu_scale: bool = False):
    # Quick mode is a CPU smoke pass: tiny data, batch 16, augmentation off,
    # client counts /16, a couple of steps per round — it checks the configs
    # *run*, not their numbers. Full mode preserves the reference's round
    # semantics: one round = `local_epochs` full passes over the client's
    # shard (steps_per_round computed from dataset size / clients / batch).
    #
    # cpu-scale mode (for the BASELINE.md table when no chip is reachable):
    # FULL client counts and true round semantics (partitioner, algorithm,
    # local epochs, compression), but the dataset truncated to 64
    # examples/client at batch 32 and the model pinned to MLP — measured on
    # this host, torch's oneDNN conv kernels are ~30x faster than XLA:CPU's,
    # so any conv config on CPU benchmarks kernel libraries rather than the
    # two systems; matmuls are same-order (~2.8x) on both. The conv-model
    # TPU story is carried by PALLAS_TPU_COMPILE.json and the driver bench.
    # bench_reference.py runs the gRPC/torch baseline at EXACTLY this sizing,
    # so the two columns are same-host, same-workload comparable.
    n = 512 if quick else None  # dataset truncation
    rounds = 4 if quick else 20
    scale = 16 if quick else 1
    if cpu_scale:
        rounds = 6
        scale = 1

    def mk(name, model, dataset, clients, quick_steps, partition="iid",
           local_epochs=1, **fed_kw):
        data_kw = {}
        if partition == "dirichlet":
            data_kw["dirichlet_alpha"] = 0.5
        clients = max(2, clients // scale)
        batch = 16 if quick else 128
        if cpu_scale:
            batch = 32
            n_local = cpu_scale_examples(clients)
            shard = n_local // clients
            # ONE epoch per steps_per_round; local_epochs rides FedConfig so
            # BOTH systems honor it (the engine folds it into steps, and
            # bench_reference's client loop repeats its epoch the same way —
            # multiplying here instead used to give fedtpu local_epochs x
            # the reference's local work).
            steps = max(1, math.ceil(shard / batch))
            return name, RoundConfig(
                model="mlp",
                num_classes=100 if dataset == "cifar100" else 10,
                opt=OptimizerConfig(learning_rate=0.05, schedule="constant"),
                data=DataConfig(
                    dataset=dataset,
                    batch_size=batch,
                    partition=partition,
                    num_examples=n_local,
                    augment=False,
                    # Committed parity artifacts were measured under the
                    # exact per-round permutation shuffle; pin it so re-runs
                    # reproduce them (the engine default is now the faster
                    # rotation layout, fedtpu/data/device.py).
                    device_layout="gather",
                    **data_kw,
                ),
                fed=FedConfig(num_clients=clients, num_rounds=rounds,
                              local_epochs=local_epochs, **fed_kw),
                steps_per_round=steps,
            )
        if quick:
            steps = max(1, quick_steps // 2)
        else:
            shard = _TRAIN_SIZE[dataset] // clients
            steps = max(1, math.ceil(shard / batch))
        return name, RoundConfig(
            model=model,
            num_classes=100 if dataset == "cifar100" else 10,
            # Constant LR: the reference never steps its cosine scheduler
            # (src/main.py:231-242), so parity runs pin the effective
            # constant-0.05 behavior.
            opt=OptimizerConfig(learning_rate=0.05, schedule="constant"),
            data=DataConfig(
                dataset=dataset,
                batch_size=batch,
                partition=partition,
                num_examples=n,
                augment=not quick,
                device_layout="gather",  # pin committed-artifact semantics
                **data_kw,
            ),
            fed=FedConfig(num_clients=clients, num_rounds=rounds,
                          local_epochs=1 if quick else local_epochs,
                          **fed_kw),
            steps_per_round=steps,
        )

    yield mk("1_fedavg_mlp_mnist_2c_iid", "mlp", "mnist", 2, 4)
    yield mk("2_fedavg_cnn_cifar10_8c_dirichlet", "smallcnn", "cifar10", 8, 4,
             partition="dirichlet")
    yield mk("3_fedprox_cnn_cifar10_32c", "smallcnn", "cifar10", 32, 2,
             algorithm="fedprox", fedprox_mu=0.01)
    # Config 4 is "5 local epochs": steps_per_round covers the whole shard
    # 5x (the engine folds local epochs into steps, fedtpu/core/engine.py).
    # Quick and cpu-scale modes swap resnet18 -> smallcnn: XLA's CPU compile
    # of the vmapped resnet18 train step alone takes ~10 min on this host
    # (the zoo tests cover resnet18 correctness; tools/compile_pallas_tpu.py
    # AOT-proves the 64-client resnet18/cifar100 round step for the v5e
    # target both sharded over 4 chips and on one chip with
    # remat + streaming gather — naively it exceeds one v5e's HBM).
    yield mk("4_fedavg_resnet18_cifar100_64c_5ep",
             "smallcnn" if (quick or cpu_scale) else "resnet18",
             "cifar100", 64, 5, local_epochs=5)
    yield mk("5_topk_compressed_fedavg_128c", "smallcnn", "cifar10", 128, 2,
             compression="topk", topk_fraction=0.01)


def acc_configs():
    """Accuracy/convergence parity at the SPECIFIED conv architectures
    (VERDICT r3 weak #2): BASELINE configs 2-4 with their real model
    families on the non-saturating ``*_hard`` tasks
    (:func:`fedtpu.data.datasets._synthetic_hard` — subspace signal + 10%
    label noise, so test-acc lands meaningfully below 1.0 and climbs over
    rounds). Scale is reduced only where XLA:CPU compile time forces it
    (client count for the vmapped resnet18) — never the model family. The
    speed columns for these configs remain the --cpu-scale MLP rows with
    their oneDNN-vs-XLA:CPU kernel-gap rationale (BASELINE.md)."""

    def mk(name, model, dataset, clients, ex_per_client, rounds,
           partition="iid", local_epochs=1, batch=32, **fed_kw):
        data_kw = {}
        if partition == "dirichlet":
            data_kw["dirichlet_alpha"] = 0.5
        # One epoch of steps; local_epochs rides FedConfig (both systems).
        steps = max(1, math.ceil(ex_per_client / batch))
        return name, RoundConfig(
            model=model,
            num_classes=100 if "cifar100" in dataset else 10,
            opt=OptimizerConfig(learning_rate=0.05, schedule="constant"),
            data=DataConfig(
                dataset=dataset,
                batch_size=batch,
                partition=partition,
                num_examples=ex_per_client * clients,
                augment=False,
                device_layout="gather",  # pin committed-artifact semantics
                **data_kw,
            ),
            fed=FedConfig(num_clients=clients, num_rounds=rounds,
                          local_epochs=local_epochs, **fed_kw),
            steps_per_round=steps,
        )

    yield mk("2_acc_smallcnn_cifar10h_8c_dirichlet", "smallcnn",
             "cifar10_hard", 8, 128, 25, partition="dirichlet")
    # 128 examples/client (4 batches/round): at 64 the averaged per-round
    # movement across 32 clients is too small to leave chance within the
    # round budget — both systems flatline at 0.11 and the parity column
    # would compare noise with noise (measured before this sizing).
    yield mk("3_acc_fedprox_smallcnn_cifar10h_32c", "smallcnn",
             "cifar10_hard", 32, 128, 30, algorithm="fedprox",
             fedprox_mu=0.01)
    # ResNet-18 on XLA:CPU costs ~30-60 s per batch-32 train step (single
    # core, measured) — the acc run keeps the config's defining trait
    # (5 local epochs) and shrinks everything else to the edge of
    # feasibility: 2 clients, 64 examples each, 4 rounds (20 train batches
    # per round; a 256-example sizing still needed multiple hours). The
    # full-scale TPU evidence for this config is the AOT-compiled
    # 64-client program (tools/compile_pallas_tpu.py, stream+remat).
    yield mk("4_acc_resnet18_cifar100h_2c_5ep", "resnet18",
             "cifar100_hard", 2, 64, 4, local_epochs=5)


def acc_full_configs():
    """Config 4 at a sizing whose curves actually climb — runnable when a
    REAL accelerator is live for the fedtpu side (the XLA:CPU fallback costs
    30-60 s per resnet18 batch; on a v5e the whole run is seconds of device
    time). The torch side stays on CPU where oneDNN convs are ~30x XLA:CPU
    (BASELINE.md kernel-gap note): 4 clients x 4 batches x 5 epochs x 12
    rounds = 960 batch-32 steps, ~20-40 min on this 1-core host.

    ``FEDTPU_SMOKE=1`` swaps in an MLP seconds-scale version of the same
    shape so the capture wrapper (``tools/run_accfull_tpu.py``) can be
    exercised end-to-end on CPU without burning a TPU window on a wrapper
    bug; the wrapper redirects its artifacts when smoking."""

    def mk4(name, model, classes, dataset, clients, ex_per_client, rounds,
            local_epochs):
        steps = max(1, math.ceil(ex_per_client / 32))
        return name, RoundConfig(
            model=model,
            num_classes=classes,
            opt=OptimizerConfig(learning_rate=0.05, schedule="constant"),
            data=DataConfig(
                dataset=dataset, batch_size=32, partition="iid",
                num_examples=ex_per_client * clients, augment=False,
                device_layout="gather",
            ),
            fed=FedConfig(num_clients=clients, num_rounds=rounds,
                          local_epochs=local_epochs),
            steps_per_round=steps,
        )

    if os.environ.get("FEDTPU_SMOKE"):
        yield mk4("4_accfull_SMOKE_mlp", "mlp", 10, "cifar10_hard",
                  2, 64, 3, 2)
        return
    yield mk4("4_accfull_resnet18_cifar100h_4c_5ep", "resnet18", 100,
              "cifar100_hard", 4, 128, 12, 5)


def run_one(name: str, cfg: RoundConfig, curve_out=None) -> dict:
    """``curve_out``: open file — appends one JSON line per round with the
    global model's test accuracy (per-round eval parity,
    ``src/main.py:167-191``). Evals run outside the timer."""
    fed = Federation(cfg, seed=0)
    test = load(cfg.data.dataset, "test", seed=cfg.data.seed,
                num=cfg.data.num_examples)

    def _curve(r):
        if curve_out is not None:
            _, ta = fed.evaluate(*test)
            curve_out.write(json.dumps(
                {"system": "fedtpu", "config": name, "round": r,
                 "test_acc": round(ta, 4)}) + "\n")
            curve_out.flush()

    # Warmup (compile) round, then timed rounds with a forced host sync.
    m = fed.step()
    float(m.loss)
    _curve(0)
    dt = 0.0
    for r in range(cfg.fed.num_rounds - 1):
        t0 = time.perf_counter()
        m = fed.step()
        float(m.loss)
        dt += time.perf_counter() - t0
        _curve(r + 1)
    test_loss, test_acc = fed.evaluate(*test)
    return {
        "config": name,
        "data_source": fed.data_source,
        "rounds_per_sec": round((cfg.fed.num_rounds - 1) / max(dt, 1e-9), 3),
        "train_acc": round(float(m.accuracy), 4),
        "test_acc": round(test_acc, 4),
        "num_clients": cfg.fed.num_clients,
        "model": cfg.model,
        "dataset": cfg.data.dataset,
        "algorithm": cfg.fed.algorithm,
        "compression": cfg.fed.compression,
        "devices": len(jax.devices()),
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="small data/rounds for CPU smoke runs")
    p.add_argument("--cpu-scale", action="store_true",
                   help="full client counts, 64 examples/client — the sizing "
                   "bench_reference.py mirrors for the BASELINE.md table")
    p.add_argument("--acc-scale", action="store_true",
                   help="accuracy/convergence parity at the SPECIFIED conv "
                   "models (configs 2-4) on the non-saturating *_hard tasks")
    p.add_argument("--acc-full", action="store_true",
                   help="config 4 (resnet18/cifar100_hard, 5 local epochs) "
                   "at climbing-curve sizing; fedtpu side wants a live "
                   "accelerator (platform NOT pinned to cpu)")
    p.add_argument("--curve-out", default=None,
                   help="append per-round test-acc JSONL rows to this file")
    p.add_argument("--only", default=None,
                   help="substring filter on config names")
    from fedtpu.cli.common import add_platform_flag, apply_platform_flag

    add_platform_flag(p)
    args = p.parse_args()
    # Quick/cpu-scale/acc-scale modes are CPU workloads by definition; pin
    # the platform so they never take the chip.
    if args.platform is None and (
        args.quick or args.cpu_scale or args.acc_scale
        or (args.acc_full and os.environ.get("FEDTPU_SMOKE"))
    ):
        args.platform = "cpu"
    apply_platform_flag(args)
    if args.acc_full:
        gen = acc_full_configs()
    elif args.acc_scale:
        gen = acc_configs()
    else:
        gen = configs(args.quick, cpu_scale=args.cpu_scale)
    curve = open(args.curve_out, "a") if args.curve_out else None
    try:
        for name, cfg in gen:
            if args.only and args.only not in name:
                continue
            print(json.dumps(run_one(name, cfg, curve_out=curve)), flush=True)
    finally:
        if curve is not None:
            curve.close()


if __name__ == "__main__":
    main()
