#!/usr/bin/env python
"""Diagnose the FedBuff k=2 sigma=0 smallcnn stall ON the stalling config.

Round-4's ``ASYNC_SYNC_CONVERGENCE.jsonl`` showed fedbuff_k2_sigma0 flat at
chance (0.103 after 25 ticks) on the smallcnn/cifar10_hard study config
while sigma=1 reached 0.718 and the sync barrier 0.89 — and the round-4
claim that this is "not an engine defect" rested on an MLP analogy, not on
an experiment on the stalling configuration (VERDICT r4 weak #2). This
sweeps the three levers FedBuff theory says govern staleness-induced
divergence, each as a single change from the stalling config:

  * ``staleness_power`` (arrival discount (1+s)^-p): 0.5 (stall) -> 1.0, 2.0
  * client ``learning_rate``: 0.05 (stall) -> 0.01
  * server discount (apply only a fraction of the buffer mean:
    ``server_optimizer='momentum'``, momentum 0, ``server_lr`` < 1):
    1.0 (stall) -> 0.25

(one point per lever at the theory-preferred setting, 15 ticks each — this
host has one core and XLA:CPU convs are ~30x oneDNN, see main()) plus the
unmodified stalling run extended to 30 ticks (does it EVER
recover?) with per-tick train loss and update norms — the divergence
signature (loss exploding vs hovering) distinguishes instability from a
too-discounted crawl. Appends rows to ``ASYNC_SYNC_CONVERGENCE.jsonl``.

Run (CPU): ``python tools/fedbuff_stall_study.py``
"""

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax

jax.config.update("jax_platforms", "cpu")  # this is a CPU study

from async_convergence_study import cfg_for  # the exact stalling config
from fedtpu.core import AsyncFederation
from fedtpu.data import load

ART = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "artifacts")
TICKS = 25


def run(mode, cfg, ticks=TICKS, staleness_power=0.5, out=None,
        speed_sigma=0.0, damping=False):
    asyn = AsyncFederation(cfg, seed=0, buffer_k=2,
                           staleness_power=staleness_power,
                           speed_sigma=speed_sigma,
                           staleness_damping=damping)
    test = load("cifar10_hard", "test", num=1024)
    accs = []
    for t in range(ticks):
        m = asyn.tick()
        _, acc = asyn.evaluate(*test)
        accs.append(round(acc, 4))
        row = {"mode": mode, "round": t, "test_acc": accs[-1],
               "train_loss": round(float(m.loss), 4),
               "update_norm": round(float(m.update_norm), 4),
               "staleness_mean": round(float(m.staleness_mean), 2)}
        print(row, file=sys.stderr, flush=True)
        if out is not None:
            out.write(json.dumps(row) + "\n")
            out.flush()
    summary = {"mode": mode, "summary": True, "ticks": ticks,
               "final_test_acc": accs[-1], "best_test_acc": max(accs)}
    if out is not None:
        out.write(json.dumps(summary) + "\n")
        out.flush()
    print(json.dumps(summary), flush=True)
    return summary


def main():
    # This host has ONE core and XLA:CPU convs are ~30x oneDNN (BASELINE.md
    # kernel-gap note): each tick+eval costs tens of seconds, so the sweep
    # keeps one point per lever at the theory-preferred setting and 15 ticks
    # per leg — enough to separate "recovers" from "still at chance" on a
    # task where the sync curve leaves chance by round ~8.
    #
    # Every leg here pins damping=False: this sweep DIAGNOSES the round-4
    # (weight-normalized) semantics. The fix the diagnosis led to —
    # staleness_damping, now the engine default — is measured by --damped.
    base = cfg_for()
    out_path = os.path.join(ART, "ASYNC_SYNC_CONVERGENCE.jsonl")
    if "--damped" in sys.argv:
        with open(out_path, "a") as out:
            # The stalling config under the engine-default damping, the
            # strong-damping point (with damping, sp is a true magnitude
            # knob), and sigma=1 under damping to check the healthy regime.
            run("fedbuff_k2_sigma0_damped", base, ticks=25, damping=True,
                out=out)
            run("fedbuff_k2_sigma0_damped_sp2", base, ticks=20,
                staleness_power=2.0, damping=True, out=out)
            run("fedbuff_k2_sigma1_damped", base, ticks=25, damping=True,
                speed_sigma=1.0, out=out)
        return
    with open(out_path, "a") as out:
        # The stalling config, longer — recovery or true stall?
        run("fedbuff_k2_sigma0_30ticks", base, ticks=30, out=out)
        # Lever 1: arrival staleness discount (sp=2 ~ quadratic damping).
        for sp in (1.0, 2.0):
            run(f"fedbuff_k2_sigma0_sp{sp:g}", base, ticks=15,
                staleness_power=sp, out=out)
        # Lever 2: client learning rate (the async-SGD stability knob).
        for lr in (0.01,):
            cfg = dataclasses.replace(
                base, opt=dataclasses.replace(base.opt, learning_rate=lr))
            run(f"fedbuff_k2_sigma0_lr{lr:g}", cfg, ticks=15, out=out)
        # Lever 3: server-side discount of the buffer mean.
        for slr in (0.25,):
            cfg = dataclasses.replace(
                base, fed=dataclasses.replace(
                    base.fed, server_optimizer="momentum",
                    server_momentum=0.0, server_lr=slr))
            run(f"fedbuff_k2_sigma0_serverlr{slr:g}", cfg, ticks=15, out=out)


if __name__ == "__main__":
    main()
