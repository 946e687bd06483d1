#!/usr/bin/env python
"""Convergence delta of the bf16-momentum mode vs f32 parity (VERDICT r4 #4a).

The bf16 momentum buffer halves optimizer-state HBM traffic (the BASELINE.md
roofline names f32 param+momentum traffic a leading bandwidth consumer); its
cost is one bf16 round-trip of the buffer per step. Whether that rounding
hurts LEARNING is an empirical question — this runs BASELINE config 2's
shape (smallcnn / cifar10_hard / 8 clients / dirichlet — the non-saturating
task used for every accuracy-parity row) once per momentum dtype, same seed
and data, and appends both curves + finals to
``artifacts/MOMENTUM_DTYPE_CONVERGENCE.jsonl``.

Runs on the CPU platform (pinned in-process; the decision is about
convergence, not speed — the SPEED side is the watcher's bench_mom_bf16 leg
on the real chip).
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "artifacts", "MOMENTUM_DTYPE_CONVERGENCE.jsonl")


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")  # this is a CPU study
    import dataclasses

    from bench_parity import acc_configs
    from fedtpu.core.engine import Federation
    from fedtpu.data import load

    (name, cfg), = [c for c in acc_configs()
                    if c[0].startswith("2_acc_smallcnn")]
    rows = []
    with open(OUT, "a") as out:
        for dtype in ("float32", "bfloat16"):
            run_cfg = dataclasses.replace(
                cfg, opt=dataclasses.replace(cfg.opt, momentum_dtype=dtype))
            fed = Federation(run_cfg, seed=0)
            test = load(run_cfg.data.dataset, "test", seed=run_cfg.data.seed,
                        num=run_cfg.data.num_examples)
            t0 = time.time()
            curve = []
            for r in range(run_cfg.fed.num_rounds):
                m = fed.step()
                float(m.loss)
                _, ta = fed.evaluate(*test)
                curve.append(round(ta, 4))
            row = {
                "study": "momentum_dtype", "config": name,
                "momentum_dtype": dtype, "rounds": run_cfg.fed.num_rounds,
                "final_test_acc": curve[-1], "curve": curve,
                "data_source": fed.data_source,
                "wall_s": round(time.time() - t0, 1),
                "at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            }
            rows.append(row)
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(json.dumps(row), flush=True)
    delta = rows[1]["final_test_acc"] - rows[0]["final_test_acc"]
    print(json.dumps({"study": "momentum_dtype", "final_acc_delta_bf16_minus_f32":
                      round(delta, 4)}))


if __name__ == "__main__":
    main()
