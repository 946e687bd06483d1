#!/usr/bin/env python3
"""Readings of planted faults, the upper side of the limits that are held
against a fault and not against a precision.

    python3 benchmark/faults.py --workload <cell> --seeds 101,102

For each seed the plain reference's first rounds (float32), and in the
program's place, through the same ``check.follow_reference`` and
``check.numbers`` a run is compared by:

- ``unchanged_state``: a step that hands its state back as it came, so every
  round reads the first round's loss (no further reference run);
- ``client_left_out``: the last client's mask cleared, so the server
  averages the others;
- ``half_batch_left_out``: every step trains on the first half of its rows.

A limit that is held against these belongs above the sound runs' largest
(``control.py``) and below the smallest of the readings here. Cells without a
codec or batch statistics. Needs the chip, like a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(manifest_path, workload, seeds, need_tpu=True, out=print):
    sys.path.insert(0, ROOT)
    import jax

    from benchmark import check, run, seeded

    cell = run.Cell(manifest_path, workload)
    if cell.traffic.get("codec"):
        raise SystemExit("faults.py reads cells without a codec")
    if need_tpu:
        run.require_chips(cell.chips, lambda s: print(s, file=sys.stderr))
        run.place_cache()
    devices = jax.devices()[: cell.chips]
    rounds = cell.traffic["check_rounds"]
    rows = []
    for seed in seeds:
        inputs = check.seeded_inputs(cell, seed)
        examples, targets, (idx, mask), initial = inputs
        reference, _ = check.follow_reference(cell, seed, inputs, devices)
        row = {"seed": seed, "reference_losses": reference["losses"]}

        start = {"params": initial["params"], "stats": initial["stats"]}
        row["unchanged_state"] = check.numbers(initial, {
            "losses": [reference["losses"][0]] * rounds,
            "first": start, "last": start}, reference)

        fewer = mask.copy()
        fewer[-1] = False
        left_out, _ = check.follow_reference(
            cell, seed, (examples, targets, (idx, fewer), initial), devices)
        row["client_left_out"] = check.numbers(initial, left_out, reference)

        whole = seeded.client_rows

        def first_half(*args):
            rows = whole(*args)
            return rows[:, : rows.shape[1] // 2]

        seeded.client_rows = first_half  # follow_reference's feed asks it
        try:
            half, _ = check.follow_reference(cell, seed, inputs, devices)
        finally:
            seeded.client_rows = whole
        row["half_batch_left_out"] = check.numbers(initial, half, reference)
        out(json.dumps(row))
        rows.append(row)
    faults = [k for k in rows[0] if k not in ("seed", "reference_losses")]
    summary = {f: {k: min(r[f][k] for r in rows) for k in rows[0][f]} for f in faults}
    out("SMALLEST " + json.dumps(summary))
    return rows, summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    readings(os.path.join(ROOT, "BENCHMARK.json"), args.workload,
             [int(s) for s in args.seeds.split(",")])


if __name__ == "__main__":
    main()
