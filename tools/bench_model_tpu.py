#!/usr/bin/env python
"""Fused-round bench for an arbitrary zoo model on the chip.

``bench.py`` measures the parity smallcnn headline; this tool covers every
other model. The default is the reference's DEFAULT model, MobileNet
(hardcoded at ``/root/reference/src/main.py:69`` and ``src/server.py:158``;
``PALLAS_TPU_COMPILE.json``: 2.54 TFLOP/round, 64 clients, single chip).
The MXU-shaped config-4 model is ``FEDTPU_BM_MODEL=resnet18
FEDTPU_BM_DATASET=cifar100 FEDTPU_BM_CLASSES=100 FEDTPU_BM_REMAT=1``.

Same engine program as ``bench.py``: the fused multi-round scan at 64
clients / batch 128 / 6 steps, bf16 activations. Parameterised via env so
several models need no file each:

  FEDTPU_BM_MODEL    (default "mobilenet")
  FEDTPU_BM_DATASET  (default "cifar10")
  FEDTPU_BM_CLASSES  (default 10)
  FEDTPU_BM_REMAT    (default "0")
  FEDTPU_BM_ROUNDS   (fused rounds per dispatch, default 2)
  FEDTPU_BM_OUT      (artifact name, default "BENCH_<MODEL>_TPU.json")
  FEDTPU_BM_CLIENTS / FEDTPU_BM_BATCH / FEDTPU_BM_STEPS (64 / 128 / 6)
  FEDTPU_BM_PLATFORM (unset = default backend; "cpu" pins the CPU platform
                      in the child so the wrapper can be smoked end-to-end
                      without a chip)

The measurement runs in ONE child process — this parent never touches jax,
so the child owns the chip — bounded by a timeout; on timeout or failure
the artifact records the error and the exit code is non-zero.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(REPO, "artifacts")
MODEL = os.environ.get("FEDTPU_BM_MODEL", "mobilenet")
DATASET = os.environ.get("FEDTPU_BM_DATASET", "cifar10")
CLASSES = int(os.environ.get("FEDTPU_BM_CLASSES", "10"))
REMAT = os.environ.get("FEDTPU_BM_REMAT", "0") == "1"
ROUNDS = int(os.environ.get("FEDTPU_BM_ROUNDS", "2"))
OUT = os.path.join(ART, os.environ.get(
    "FEDTPU_BM_OUT", f"BENCH_{MODEL.upper()}_TPU.json"))
TIMEOUT_S = 2700

_INNER = r"""
import json, time, sys
import jax, jax.numpy as jnp, numpy as np
if %(platform)r:
    jax.config.update("jax_platforms", %(platform)r)
sys.path.insert(0, %(repo)r)
from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
from fedtpu.core.engine import Federation

NUM_CLIENTS=%(clients)d; BATCH=%(batch)d; STEPS=%(steps)d; ROUNDS=%(rounds)d; TRIALS=3
cfg = RoundConfig(model=%(model)r, num_classes=%(classes)d,
    opt=OptimizerConfig(),
    data=DataConfig(dataset=%(dataset)r, batch_size=BATCH, partition="iid",
                    num_examples=NUM_CLIENTS*STEPS*BATCH),
    fed=FedConfig(num_clients=NUM_CLIENTS), steps_per_round=STEPS,
    dtype="bfloat16", remat=%(remat)r)
fed = Federation(cfg, seed=0)
d = fed._ensure_device_data()
alive = jnp.ones((ROUNDS, NUM_CLIENTS), bool)
multi = fed._multi_step(ROUNDS)
print("compiling...", flush=True)
t0=time.time()
step = multi.lower(fed.state, *d, fed.weights, alive, fed._data_key).compile()
print("compiled in %%.1fs" %% (time.time()-t0), flush=True)
single = fed._data_step.lower(fed.state, *d, fed.weights,
    jnp.ones((NUM_CLIENTS,), bool), fed._data_key).compile()
flops = float(single.cost_analysis().get("flops",0.0)) or None
state = fed.state
state, m = step(state, *d, fed.weights, alive, fed._data_key)
np.asarray(m.loss)  # warmup; fetching a program output waits for the scan
rates=[]
for _ in range(TRIALS):
    t0=time.perf_counter()
    state, m = step(state, *d, fed.weights, alive, fed._data_key)
    np.asarray(m.loss)
    rates.append(ROUNDS/(time.perf_counter()-t0))
rps = sorted(rates)[len(rates)//2]
kind = jax.devices()[0].device_kind
out = {"metric":"fedavg_rounds_per_sec_%(dataset)s_%(model)s_%%dclients_1chip" %% NUM_CLIENTS,
  "rounds_per_sec": round(rps,4),
  "client_epochs_per_sec_per_chip": round(rps*NUM_CLIENTS,2),
  "num_clients":NUM_CLIENTS,"batch":BATCH,"steps_per_round":STEPS,
  "remat":%(remat)r,"dtype":"bfloat16","device_kind":kind,
  "backend":jax.default_backend()}
if flops:
    out["flops_per_round"]=flops
    from fedtpu.obs.profile import device_peaks
    peak = device_peaks(kind)[0]
    if peak:
        out["mfu"]=round(rps*flops/peak,4)
print(json.dumps(out), flush=True)
"""


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from jsontail import last_json_line

    inner = _INNER % {
        "repo": REPO, "model": MODEL, "dataset": DATASET,
        "classes": CLASSES, "remat": REMAT, "rounds": ROUNDS,
        "clients": int(os.environ.get("FEDTPU_BM_CLIENTS", "64")),
        "batch": int(os.environ.get("FEDTPU_BM_BATCH", "128")),
        "steps": int(os.environ.get("FEDTPU_BM_STEPS", "6")),
        "platform": os.environ.get("FEDTPU_BM_PLATFORM", ""),
    }
    proc = None
    try:
        proc = subprocess.run(
            [sys.executable, "-c", inner], capture_output=True, text=True,
            timeout=TIMEOUT_S, cwd=REPO,
        )
        out, err, note = proc.stdout, proc.stderr, None
    except subprocess.TimeoutExpired as exc:
        out = (exc.stdout or b"")
        out = out.decode() if isinstance(out, bytes) else out
        err, note = "", f"timeout after {TIMEOUT_S}s"
    n_clients = int(os.environ.get("FEDTPU_BM_CLIENTS", "64"))
    line = last_json_line(out)
    if line is None:
        line = {"metric":
                f"fedavg_rounds_per_sec_{DATASET}_{MODEL}_{n_clients}clients_1chip",
                "value": 0.0,
                "error": note or f"no JSON (rc={proc.returncode}): {err.strip()[-400:]}",
                "progress": (out or "").strip().splitlines()[-3:]}
    line["captured_at"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(line, f, indent=2)
    os.replace(tmp, OUT)
    print(json.dumps(line))
    return 0 if "error" not in line else 4


if __name__ == "__main__":
    raise SystemExit(main())
