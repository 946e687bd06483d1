#!/usr/bin/env python
"""Run the fedtpu side of parity config 4 at climbing-curve sizing on a live
accelerator (``bench_parity.py --acc-full``), appending curves and the
summary row next to the torch reference's (already-committed) run.

The torch side of ``4_accfull_resnet18_cifar100h_4c_5ep`` runs on CPU in
~40 min and was captured 2026-07-31 (``artifacts/PARITY_ACC_FULL.jsonl``,
``convergence_full_r04.jsonl``: chance 0.01 -> 0.1406 over 12 rounds). The
fedtpu side needs a chip (XLA:CPU resnet18 is 30-60 s/batch). The run is
bounded, happens in one child process (this parent never touches jax), and
the shared artifacts are only appended to AFTER a fully successful run
(curves go to a scratch file first — a run killed midway would otherwise
leave partial fedtpu curves that a later retry duplicates with conflicting
values).
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from jsontail import last_json_line  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(REPO, "artifacts")
if os.environ.get("FEDTPU_SMOKE"):
    # Smoke mode (CPU, seconds): exercise the whole capture path — scratch
    # curves, append-on-success — WITHOUT touching the committed artifacts.
    ART = os.path.join("/tmp", "fedtpu_accfull_smoke")
    os.makedirs(ART, exist_ok=True)
ROWS = os.path.join(ART, "PARITY_ACC_FULL.jsonl")
CURVES = os.path.join(ART, "convergence_full_r04.jsonl")
TIMEOUT_S = 3000


def main():
    scratch = CURVES + ".inflight"
    if os.path.exists(scratch):
        os.remove(scratch)
    cmd = [sys.executable, os.path.join(REPO, "bench_parity.py"),
           "--acc-full", "--curve-out", scratch]
    if os.environ.get("FEDTPU_SMOKE"):
        cmd += ["--platform", "cpu"]  # the smoke rehearses the path off-chip
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=TIMEOUT_S, cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        print(json.dumps({"error": f"timeout after {TIMEOUT_S}s"}))
        return 4
    row = last_json_line(proc.stdout)
    if row is None:
        print(json.dumps({"error": f"rc={proc.returncode}: "
                          + proc.stderr.strip()[-400:]}))
        return 4
    row["system"] = "fedtpu"
    row["captured_at"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(scratch) as f:
        curves = f.read()
    with open(CURVES, "a") as f:
        f.write(curves)
    os.remove(scratch)
    with open(ROWS, "a") as f:
        f.write(json.dumps(row) + "\n")
    print(json.dumps(row))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
