#!/usr/bin/env python3
"""Readings the check's limits are set from, many seeds in one process.

    python3 benchmark/control.py --workload <cell> --seeds 101,102,... \
        [--controls 3] [--program 1]

For each seed: the plain reference's first rounds (float32); with
``--program 1`` the program's, through the same engine and call a run times
(no measured window), compared as a run compares them: the SOUND readings;
and for the first ``--controls`` seeds the CONTROL readings: the reference
computed in the precision below the configuration's (fp8 operands for every
product; for a codec cell also the codec at half its bits) put in the
program's place. A limit belongs above the sound runs' largest and below the
controls' smallest (``limits/<cell>.json`` records both). Needs the chip,
like a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(manifest_path, workload, seeds, n_controls, program=True,
             need_tpu=True, out=print):
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np

    from benchmark import check, run, sut
    from benchmark.reference import lowprec

    cell = run.Cell(manifest_path, workload)
    if need_tpu:
        run.require_chips(cell.chips, lambda s: print(s, file=sys.stderr))
        run.place_cache()
    devices = jax.devices()[: cell.chips]
    traffic = cell.traffic
    rows = []
    for i, seed in enumerate(seeds):
        inputs = check.seeded_inputs(cell, seed)
        initial = inputs[3]
        row = {"seed": seed}
        if program:
            fed = sut.build(cell, inputs)
            prog = check.first_rounds(
                fed, traffic["check_rounds"],
                lambda: float(np.asarray(sut.step(fed).loss)))
            del fed
            sut.release()
            row["sound"], reference = check.against_reference(
                cell, seed, inputs, devices, prog)
        args = (cell, seed, inputs, devices)
        if not program:
            reference, _ = check.follow_reference(*args)
        if i < n_controls:
            low, _ = check.follow_reference(*args, quant=lowprec.fp8)
            row["control_fp8"] = check.numbers(initial, low, reference)
            if traffic.get("codec"):
                half, _ = check.follow_reference(
                    *args, bits=traffic["codec"]["bits"] // 2)
                row["control_half_bits"] = check.numbers(initial, half, reference)
        out(json.dumps(row))
        rows.append(row)
    summary = {}
    for kind in ("sound", "control_fp8", "control_half_bits"):
        got = [r[kind] for r in rows if kind in r]
        if got:
            summary[kind] = {
                k: {"min": min(g[k] for g in got), "max": max(g[k] for g in got)}
                for k in got[0]}
    out("SUMMARY " + json.dumps(summary))
    return rows, summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--program", type=int, default=1)
    args = ap.parse_args(argv)
    readings(os.path.join(ROOT, "BENCHMARK.json"), args.workload,
             [int(s) for s in args.seeds.split(",")], args.controls,
             bool(args.program))


if __name__ == "__main__":
    main()
