#!/usr/bin/env python3
"""What the plain reference holds on a chip at the size of a sequence model's
cut: the tests' bigram stand-in at vocabulary and width 16,384 (two tables,
537 M parameters, 2.15 GB a float32 copy), rows of 1,024 tokens, 2 steps of
4 rows, followed for 2 rounds by ``check.follow_reference`` alone (no
program: it has no token path yet).

    python3 tests/benchmark/reference_at_size.py [--root DIR] \
        [--clients 2,4,8] [--momentum 0.9,0] [--block-rows N] [--vocab V]

One process a reading, because a process's peak never falls and an
allocation that fails leaves its mark: a line each with the allocator's peak
(``run.device_bytes``, read while the epoch is loaded, so with its scratch),
the copies of the parameters that is, and ``reference_s``. ``--root`` is the
tree whose ``benchmark/`` is read (a ``git archive`` of another commit); the
toy cell's files are this tree's. ``--vocab`` is the vocabulary and the
width (a small one rehearses the script). Needs the chip, like a run; the
lines also go to ``chiprun_out/``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2147483659


def reading(root, clients, momentum, vocab, block_rows):
    sys.path.insert(0, root)
    from benchmark import check, run  # before the tests' file puts ROOT first

    sys.path.insert(0, HERE)
    import jax
    from test_benchmark import make_toy_cell

    if not check.__file__.startswith(root + os.sep):
        raise SystemExit(f"benchmark/ was read from {check.__file__}, not {root}")
    run.require_chips(1, lambda s: print(s, file=sys.stderr))
    run.place_cache()
    config = {"vocab": vocab, "width": vocab, "seq_len": 1024, "batch_size": 4,
              "num_examples": 64, "optimizer": {
                  "name": "sgd", "learning_rate": 0.3, "momentum": momentum,
                  "weight_decay": 0.0}}
    if block_rows:
        config["reference_block_rows"] = block_rows
    with tempfile.TemporaryDirectory() as tmp:
        cell = make_toy_cell(tmp, config, {"clients": clients, "check_rounds": 2})
    inputs = check.seeded_inputs(cell, SEED)
    one = sum(l.nbytes for l in jax.tree.leaves(inputs[3]["params"]))
    out = {"root": root, "clients": clients, "momentum": momentum,
           "block_rows": block_rows, "copy_bytes": one,
           "device": jax.devices()[0].device_kind}
    # The epoch's scratch lies in the allocator's reserved pool while the
    # program is loaded, which it no longer is when the reference returns: a
    # thread reads the two pools together meanwhile.
    done, with_scratch = threading.Event(), [0]

    def watch():
        while not done.wait(0.05):
            with_scratch[0] = max(with_scratch[0],
                                  run.device_bytes(jax.devices()[0].memory_stats()))

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    t0 = time.perf_counter()
    try:
        reference, _ = check.follow_reference(cell, SEED, inputs, jax.devices()[:1])
        out["losses"] = reference["losses"]
        out["moved"] = check.rel_diff(reference["last"]["params"], inputs[3]["params"])
    except (jax.errors.JaxRuntimeError, ValueError) as err:  # out of memory
        out["error"] = str(err).strip().splitlines()[0][:300]
    finally:
        out["reference_s"] = time.perf_counter() - t0
        done.set()
        watcher.join()
    stats = jax.devices()[0].memory_stats()
    out["buffers_peak_bytes"] = stats["peak_bytes_in_use"]
    out["peak_bytes"] = max(with_scratch[0], run.device_bytes(stats))
    out["peak_copies"] = out["peak_bytes"] / one
    out["bytes_limit"] = stats["bytes_limit"]
    print(json.dumps(out))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--clients", default="2,4,8")
    ap.add_argument("--momentum", default="0.9,0")
    ap.add_argument("--block-rows", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=16384)
    ap.add_argument("--one", nargs=2, metavar=("CLIENTS", "MOMENTUM"),
                    help="this process takes one reading (what the others start)")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    if args.one:
        return reading(root, int(args.one[0]), float(args.one[1]), args.vocab,
                       args.block_rows)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "reference_at_size.jsonl"), "a") as log:
        for momentum in args.momentum.split(","):
            for clients in args.clients.split(","):
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__), "--root", root,
                     "--vocab", str(args.vocab), "--block-rows", str(args.block_rows),
                     "--one", clients, momentum],
                    capture_output=True, text=True, timeout=1500)
                lines = proc.stdout.strip().splitlines()
                line = lines[-1] if proc.returncode == 0 and lines else json.dumps({
                    "root": root, "clients": int(clients), "momentum": float(momentum),
                    "block_rows": args.block_rows, "exit": proc.returncode,
                    "stderr": proc.stderr[-600:]})
                print(line, flush=True)
                log.write(line + "\n")


if __name__ == "__main__":
    main()
