#!/usr/bin/env python
"""Perf-regression CI harness for the observability hot path.

The observatory's cost claims ("accounting is ≤1% of a round",
"telemetry=basic is sub-ppm") are measured once by ``bench.py`` legs that
take minutes. This harness keeps them true CONTINUOUSLY with a seconds-
scale microbench of every per-round instrument the framework executes —
span enter/exit, counter/gauge/histogram updates, MFU accounting,
client-latency summarization, round-record serialization, Prometheus
rendering, trace merge and gap analysis — compared against a committed
baseline (``artifacts/PERF_BASELINE.json``).

Machine-speed normalization: raw microsecond medians are not portable
across hosts, so every run also times a fixed pure-Python *calibration
workload*; ``--check`` scales the baseline by
``measured_calibration / baseline_calibration`` (clamped) before
comparing. Drift tolerance per metric is
``max(75%, 4 x noise_floor_pct)`` over the scaled baseline — wide enough
that scheduler jitter never flakes tier-1, tight enough that an
accidental O(n) regression on a per-round instrument (the 2x injected
slowdown the tests pin) reliably fails.

Usage:
    python tools/perf_ci.py --baseline     # (re)write the committed baseline
    python tools/perf_ci.py --check        # compare vs baseline, exit 1 on drift
    python tools/perf_ci.py                # measure + print, no comparison

Env:
    FEDTPU_PERF_CI_REPS    measurement repetitions (default 5)
    FEDTPU_PERF_CI_INJECT  "name=factor[,name=factor]" or "all=2.0":
                           multiply measured medians after measurement —
                           the test hook proving --check actually fails
                           on a regression (recorded in the output).

Mode-rotation discipline per bench.py: the metric measurement order is
rotated every rep so machine-wide drift within a rep cannot land on the
same metrics every time and read as regression.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import tempfile
import time
from typing import Callable, Dict, List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SCHEMA_VERSION = 1
BASELINE_PATH = os.path.join(REPO, "artifacts", "PERF_BASELINE.json")

# Relative drift always tolerated, on top of the calibration scaling.
MIN_BAND = 0.75
# ... widened by the larger of the two runs' own noise floors.
NOISE_BAND_MULT = 4.0
# Calibration scaling is a correction, not a free pass: a host claiming to
# be 10x slower is more likely a broken measurement than a real machine.
SCALE_CLAMP = (0.25, 4.0)


# --------------------------------------------------------------- workloads
def _calibration() -> None:
    """Fixed pure-Python workload: the machine-speed yardstick. Mixed
    arithmetic + hashing so neither interpreter dispatch nor memory
    bandwidth alone dominates."""
    acc = 0
    for i in range(2000):
        acc += i * i % 7
    hashlib.sha256(b"fedtpu-perf-ci" * 64).hexdigest()


def _synthetic_merged_doc(n_spans: int = 120, n_ops: int = 120) -> dict:
    """A small merged timeline (host lane + device lane) shaped like
    trace_merge.py output, for the merge/analyze workloads."""
    events = []
    for i in range(n_spans):
        events.append({
            "ph": "X", "pid": 1, "tid": 1, "name": f"phase_{i % 7}",
            "ts": i * 100.0, "dur": 60.0,
        })
    for i in range(n_ops):
        events.append({
            "ph": "X", "pid": 2, "tid": 1, "name": "fusion",
            "cat": "device", "ts": i * 100.0 + 30.0, "dur": 40.0,
        })
    return {"traceEvents": events, "metadata": {}}


def _build_workloads() -> List[Tuple[str, Callable[[], None], int, object]]:
    """[(metric name, one-iteration thunk, iterations per timing, optional
    post-batch reset)]. All
    imports are host-side fedtpu.obs + tools modules — no jax, so the
    harness runs in a couple of seconds and is safe for tier-1."""
    import gap_analyze
    import trace_merge
    import numpy as np
    import ml_dtypes
    from fedtpu.obs import (
        RoundRecordWriter,
        Telemetry,
        latency_summary,
        prometheus_text,
    )
    from fedtpu.obs.profile import CostModel, RoundProfiler

    tel = Telemetry("trace")
    counter = tel.counter("perf_ci_c")
    gauge = tel.gauge("perf_ci_g")
    hist = tel.histogram("perf_ci_h")

    profiler = RoundProfiler(tel, n_devices=1, device_kind="")
    profiler.set_cost_model(
        CostModel(xla_flops=1.0e12, xla_bytes=2.0e11, analytic=1.0e12)
    )
    profiler.peak_flops = 9.18e14  # fixed: no env / device dependence

    pairs = [(f"client_{i:03d}", 0.05 + (i % 13) * 0.01) for i in range(64)]

    rec_path = os.path.join(
        tempfile.mkdtemp(prefix="fedtpu_perf_ci_"), "records.jsonl"
    )
    writer = RoundRecordWriter(path=rec_path, echo=False)
    rec_fields = {
        "participants": 8, "loss": 1.234567, "t_round_s": 0.123456,
        "wire_bytes": 1 << 20, "mfu": 0.4321,
    }
    rec_step = [0]

    def record_one():
        writer.log(rec_step[0], **rec_fields)
        rec_step[0] += 1

    doc = _synthetic_merged_doc()
    host_doc = {
        "traceEvents": [e for e in doc["traceEvents"] if "cat" not in e],
        "metadata": {"wall_start": 1000.0, "role": "engine"},
    }
    dev_doc = {
        "traceEvents": [e for e in doc["traceEvents"] if "cat" in e],
        "metadata": {"wall_start": 1000.0, "role": "engine"},
    }

    # Mixed-precision host cost: the one-time f32 -> bf16 cast of the
    # resident dataset at device upload (RoundConfig.dtype="bfloat16") is
    # the ONLY host-side step the dtype adds outside the jitted round. It
    # must stay trivially cheap — a regression here means someone moved the
    # cast out of XLA into a per-round host loop. numpy + ml_dtypes stand in
    # for the jitted version so the harness stays jax-free and seconds-scale.
    cast_src = np.ones((64, 4096), dtype=np.float32)

    def cast_one():
        cast_src.astype(ml_dtypes.bfloat16)

    # Hierarchical-aggregation host costs (PR: sub-aggregator tier). The
    # two per-round steps the tier adds OUTSIDE the jitted reduce: the
    # leaf's [cohort, P] -> one-row weighted fold (numpy stands in for the
    # jitted fedtpu.ops.flat.partial_reduce_rows so the harness stays
    # jax-free), and assembling the FSP1 partial_flat record — one O(P)
    # row copy + header/CRC framing, the wire cost of SubmitPartial's
    # reply. A regression here means the leaf started re-materializing
    # rows per client or the record grew a per-coordinate encode loop.
    fold_rows = np.ones((16, 4096), dtype=np.float32)
    fold_w = np.arange(1.0, 17.0, dtype=np.float32)

    def partial_reduce_fold_one():
        (fold_rows * fold_w[:, None]).sum(axis=0)
        fold_w.sum()

    import struct
    import zlib

    partial_row = np.arange(32768, dtype=np.float32)

    def submit_partial_frame_one():
        payload = partial_row.tobytes()
        struct.pack("<4sBBI", b"FSP1", 1, 0,
                    zlib.crc32(payload) & 0xFFFFFFFF) + payload

    # Sketch-codec host costs (PR: rotated-sketch + random-k wire codecs).
    # The two hot loops the codecs add on the HOST side of the edge: the
    # in-place FWHT butterfly over the padded row (the encoder/decoder both
    # run it once per record — numpy stands in for transport.sparse._fwht_np
    # which IS numpy, so this times the real algorithm), and the seeded
    # Philox index draw + gather that builds a randk record. A regression
    # here means someone replaced the O(h log h) butterfly with a dense
    # h x h matmul, or the sorted no-replacement draw with a per-coordinate
    # Python loop.
    had_row = np.arange(4096, dtype=np.float32)

    def hadamard_rotate_one():
        x = had_row.copy()
        h = x.size
        step = 1
        while step < h:
            y = x.reshape(h // (2 * step), 2, step)
            a, b = y[:, 0, :], y[:, 1, :]
            x = np.concatenate([a + b, a - b], axis=1).reshape(h)
            step *= 2

    randk_x = np.arange(32768, dtype=np.float32)

    def randk_gather_one():
        rng = np.random.Generator(np.random.Philox(7))
        idx = np.sort(rng.choice(randk_x.size, size=1638, replace=False))
        randk_x[idx]

    def span_one():
        with tel.span("perf_ci", round=0):
            pass

    def span_reset():
        # The tracer buffers every finished span; drain it between timed
        # batches so buffer growth/GC pressure doesn't drift later reps.
        tel.tracer._events.clear()

    return [
        ("calibration_us", _calibration, 200, None),
        ("span_trace_us", span_one, 5000, span_reset),
        ("counter_inc_us", counter.inc, 20000, None),
        ("gauge_set_us", lambda: gauge.set(0.5), 20000, None),
        ("histogram_observe_us", lambda: hist.observe(0.01), 20000, None),
        ("mfu_observe_us",
         lambda: (profiler.observe_round(0.5), profiler.record_fields()),
         5000, None),
        ("latency_summary_us", lambda: latency_summary(pairs), 2000, None),
        ("round_record_us", record_one, 2000, None),
        ("prometheus_render_us", lambda: prometheus_text(tel.registry), 500,
         None),
        ("trace_merge_us",
         lambda: trace_merge.merge_docs([host_doc], device_docs=[dev_doc]),
         50, None),
        ("gap_analyze_us", lambda: gap_analyze.analyze(doc), 20, None),
        ("mixed_precision_cast_us", cast_one, 200, None),
        ("partial_reduce_fold_us", partial_reduce_fold_one, 500, None),
        ("submit_partial_frame_us", submit_partial_frame_one, 500, None),
        ("hadamard_rotate_us", hadamard_rotate_one, 200, None),
        ("randk_gather_us", randk_gather_one, 200, None),
    ]


# -------------------------------------------------------------- measuring
def measure(reps: int = None) -> Dict[str, object]:
    reps = reps or int(os.environ.get("FEDTPU_PERF_CI_REPS", "5"))
    workloads = _build_workloads()
    trials: Dict[str, List[float]] = {
        name: [] for name, _f, _n, _r in workloads
    }
    # Warmup: allocators, lazy imports and span machinery all pay a first-
    # call cost that would otherwise land in rep 0's noise floor.
    for _name, fn, n, reset in workloads:
        for _ in range(min(n, 200)):
            fn()
        if reset is not None:
            reset()
    for rep in range(reps):
        # Rotate the measurement order per rep (bench.py discipline).
        order = workloads[rep % len(workloads):] + \
            workloads[: rep % len(workloads)]
        for name, fn, n, reset in order:
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            trials[name].append((time.perf_counter() - t0) / n * 1e6)
            if reset is not None:
                reset()
    metrics: Dict[str, Dict[str, float]] = {}
    for name, ts in trials.items():
        med = sorted(ts)[len(ts) // 2]
        noise = (max(ts) - min(ts)) / med * 100.0 if med else 0.0
        metrics[name] = {
            "median_us": round(med, 4),
            "noise_floor_pct": round(noise, 2),
        }
    _apply_injection(metrics)
    return {
        "schema_version": SCHEMA_VERSION,
        "reps": reps,
        "metrics": metrics,
        "python": ".".join(map(str, sys.version_info[:3])),
    }


def _apply_injection(metrics: Dict[str, Dict[str, float]]) -> None:
    """FEDTPU_PERF_CI_INJECT test hook: inflate measured medians so the
    tests can prove --check fails on a real slowdown without depending on
    an actual regression being present."""
    spec = os.environ.get("FEDTPU_PERF_CI_INJECT", "")
    if not spec:
        return
    for part in spec.split(","):
        if "=" not in part:
            continue
        name, _eq, factor = part.partition("=")
        name, factor = name.strip(), float(factor)
        for key, row in metrics.items():
            if name in ("all", key):
                row["median_us"] = round(row["median_us"] * factor, 4)
                row["injected_factor"] = factor


# -------------------------------------------------------------- comparing
def compare(measured: dict, baseline: dict) -> dict:
    """The --check verdict: measured vs (calibration-scaled) baseline."""
    base_m = baseline["metrics"]
    now_m = measured["metrics"]
    base_cal = base_m.get("calibration_us", {}).get("median_us") or 1.0
    now_cal = now_m.get("calibration_us", {}).get("median_us") or base_cal
    scale = max(SCALE_CLAMP[0], min(SCALE_CLAMP[1], now_cal / base_cal))
    rows = {}
    failures = []
    for name, base in sorted(base_m.items()):
        if name == "calibration_us":
            continue
        now = now_m.get(name)
        if now is None:
            failures.append({
                "metric": name,
                "problem": "metric disappeared from the harness — update "
                           "the baseline deliberately, don't drop coverage",
            })
            continue
        band = max(
            MIN_BAND,
            NOISE_BAND_MULT
            * max(base["noise_floor_pct"], now["noise_floor_pct"]) / 100.0,
        )
        limit = base["median_us"] * scale * (1.0 + band)
        row = {
            "measured_us": now["median_us"],
            "baseline_us": base["median_us"],
            "limit_us": round(limit, 4),
            "band_pct": round(band * 100.0, 1),
            "ratio_vs_scaled_baseline": round(
                now["median_us"] / (base["median_us"] * scale), 3
            ),
        }
        if now["median_us"] > limit:
            row["regression"] = True
            failures.append({"metric": name, **row})
        rows[name] = row
    return {
        "pass": not failures,
        "calibration_scale": round(scale, 3),
        "calibration_us": {"baseline": base_cal, "measured": now_cal},
        "failures": failures,
        "metrics": rows,
        "injected": os.environ.get("FEDTPU_PERF_CI_INJECT", "") or None,
    }


def write_baseline(measured: dict, path: str = None) -> str:
    path = path or BASELINE_PATH
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(measured, fh, indent=2)
        fh.write("\n")
    os.replace(tmp, path)
    return path


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--baseline", action="store_true",
                   help="measure and (re)write artifacts/PERF_BASELINE.json")
    p.add_argument("--check", action="store_true",
                   help="measure and compare against the committed "
                        "baseline; exit 1 on drift")
    p.add_argument("--against", default=None, metavar="PATH",
                   help="baseline file for --check (default: committed)")
    p.add_argument("--reps", default=None, type=int)
    args = p.parse_args(argv)

    measured = measure(reps=args.reps)
    if args.baseline:
        path = write_baseline(measured)
        print(json.dumps(measured, indent=2))
        print(f"baseline written: {os.path.relpath(path, REPO)}",
              file=sys.stderr)
        return 0
    if args.check:
        path = args.against or BASELINE_PATH
        with open(path) as fh:
            baseline = json.load(fh)
        verdict = compare(measured, baseline)
        print(json.dumps(verdict, indent=2))
        if not verdict["pass"]:
            for f in verdict["failures"]:
                print(f"PERF REGRESSION: {json.dumps(f)}", file=sys.stderr)
            return 1
        print("perf check ok: "
              f"{len(verdict['metrics'])} metrics within "
              f"{int(MIN_BAND * 100)}%+ band of scaled baseline",
              file=sys.stderr)
        return 0
    print(json.dumps(measured, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
