#!/usr/bin/env python
"""Attribute device-idle gaps in a merged fedtpu timeline to host phases.

Input is ``tools/trace_merge.py`` output that includes at least one device
lane (``--device-trace``, events tagged ``cat="device"``). The analyzer:

1. unions the device-op intervals across every device lane into "device
   busy" time, bounded to the capture window (first to last device op);
2. finds the idle gaps — maximal sub-intervals of the window where no
   device lane is executing — longer than ``--min-gap-us``;
3. attributes each gap to the host spans that overlap it, deepest
   (innermost) span first: a gap microsecond is charged to the most
   specific host phase covering it (``h2d`` inside ``round``, not
   ``round``), and whatever no host span covers is reported as
   ``unattributed`` (blocking Python between spans, GC, scheduler);
4. emits a structured JSON report: the top-k gaps with per-gap
   attribution plus an aggregate ``by_phase`` table over ALL gaps — the
   ranked "where does device idleness come from" answer the ROADMAP's
   raw-speed item wants instead of guessing.

Import-free of fedtpu (stdlib only), like the other ``tools/`` readers.

Usage:
    python tools/gap_analyze.py merged.json -o artifacts/GAP_REPORT.json \
        [--top 10] [--min-gap-us 100] [--check] \
        [--roofline artifacts/MFU_PROFILE_r04.json]

``--check`` exits non-zero when the timeline has no device lane (the
acceptance gate for a --profile-rounds capture that silently produced no
device ops). An EMPTY gap list is not a failure — a fully-busy device is
the goal state.

``--roofline PROFILE`` additionally stamps roofline placement onto the
report: for each config row in an ``--mfu-profile`` artifact (or a flat
dict carrying ``flops_per_round``/``bytes_per_round``) it recomputes
arithmetic intensity, ridge point, bound and utilization through
``fedtpu.obs.profile.roofline``, so one report answers both "where does
the idle time go" (gaps) and "what is the busy time limited by"
(roofline). This is the only path that imports fedtpu — it is loaded
lazily inside the flag handler so the default invocation stays stdlib
only.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

SCHEMA_VERSION = 1

Interval = Tuple[float, float]


def load_doc(path: str) -> dict:
    with open(path) as fh:
        doc = json.load(fh)
    if isinstance(doc, list):
        doc = {"traceEvents": doc}
    return doc


def _events(doc: dict, device: bool) -> List[dict]:
    return [
        e for e in doc.get("traceEvents", [])
        if e.get("ph") == "X"
        and (e.get("cat") == "device") == device
        and "ts" in e and "dur" in e
    ]


def union_intervals(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping/adjacent ``(start, end)`` intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def find_gaps(
    busy: List[Interval], window: Interval, min_gap_us: float
) -> List[Interval]:
    """Maximal idle sub-intervals of ``window`` not covered by the merged
    ``busy`` union, at least ``min_gap_us`` long."""
    gaps: List[Interval] = []
    cur = window[0]
    for s, e in busy:
        if s > cur:
            gaps.append((cur, min(s, window[1])))
        cur = max(cur, e)
        if cur >= window[1]:
            break
    if cur < window[1]:
        gaps.append((cur, window[1]))
    return [(s, e) for s, e in gaps if e - s >= min_gap_us]


def _depths(spans: List[dict]) -> List[int]:
    """Nesting depth per span: the number of spans on the same lane that
    properly contain it (O(n^2) — host span counts are small)."""
    depths = []
    for i, a in enumerate(spans):
        a0, a1 = a["ts"], a["ts"] + a["dur"]
        d = 0
        for j, b in enumerate(spans):
            if i == j or b.get("pid") != a.get("pid"):
                continue
            b0, b1 = b["ts"], b["ts"] + b["dur"]
            if b0 <= a0 and a1 <= b1 and (b0 < a0 or a1 < b1):
                d += 1
        depths.append(d)
    return depths


def _subtract(intervals: List[Interval], cut: Interval) -> List[Interval]:
    out: List[Interval] = []
    c0, c1 = cut
    for s, e in intervals:
        if e <= c0 or s >= c1:
            out.append((s, e))
            continue
        if s < c0:
            out.append((s, c0))
        if e > c1:
            out.append((c1, e))
    return out


def attribute_gap(
    gap: Interval, spans: List[dict], depths: List[int]
) -> Tuple[List[dict], float]:
    """Charge a gap to overlapping host spans, innermost first. Returns
    ``(attribution rows, unattributed_us)``; rows carry the span name,
    charged microseconds and fraction of the gap."""
    g0, g1 = gap
    total = g1 - g0
    overlapping = [
        (depths[i], s) for i, s in enumerate(spans)
        if s["ts"] < g1 and s["ts"] + s["dur"] > g0
    ]
    # Deepest (most specific) spans claim their part of the gap first;
    # an enclosing span only gets what its children left uncovered.
    overlapping.sort(key=lambda ds: -ds[0])
    remaining: List[Interval] = [gap]
    charged: Dict[str, float] = {}
    for _d, s in overlapping:
        s0, s1 = s["ts"], s["ts"] + s["dur"]
        got = sum(
            min(e, s1) - max(b, s0)
            for b, e in remaining
            if b < s1 and e > s0
        )
        if got > 0:
            charged[s["name"]] = charged.get(s["name"], 0.0) + got
            remaining = _subtract(remaining, (max(g0, s0), min(g1, s1)))
    unattributed = sum(e - b for b, e in remaining)
    rows = [
        {
            "span": name,
            "us": round(us, 3),
            "fraction": round(us / total, 4) if total else 0.0,
        }
        for name, us in sorted(charged.items(), key=lambda kv: -kv[1])
    ]
    return rows, unattributed


def analyze(
    doc: dict, top: int = 10, min_gap_us: float = 100.0
) -> dict:
    """The GAP_REPORT dict for one merged timeline (see module docstring).
    Tolerates an empty device side: the report then carries
    ``device_lanes: 0`` and no gaps rather than failing."""
    device = _events(doc, device=True)
    host = _events(doc, device=False)
    lanes = sorted({e.get("pid") for e in device})
    report = {
        "schema_version": SCHEMA_VERSION,
        "device_lanes": len(lanes),
        "device_ops": len(device),
        "min_gap_us": min_gap_us,
        "gaps": [],
        "by_phase": [],
    }
    if not device:
        report.update(
            window_us=None, device_busy_us=0.0, device_idle_us=0.0,
            idle_fraction=None, n_gaps=0,
        )
        return report
    busy = union_intervals(
        [(e["ts"], e["ts"] + e["dur"]) for e in device]
    )
    window = (busy[0][0], busy[-1][1])
    busy_us = sum(e - s for s, e in busy)
    gaps = find_gaps(busy, window, min_gap_us)
    gaps.sort(key=lambda g: g[0] - g[1])  # longest first
    depths = _depths(host)
    by_phase: Dict[str, float] = {}
    unattributed_total = 0.0
    gap_rows = []
    for g in gaps:
        rows, unattr = attribute_gap(g, host, depths)
        for r in rows:
            by_phase[r["span"]] = by_phase.get(r["span"], 0.0) + r["us"]
        unattributed_total += unattr
        gap_rows.append({
            "start_us": round(g[0], 3),
            "end_us": round(g[1], 3),
            "dur_us": round(g[1] - g[0], 3),
            "attribution": rows,
            "unattributed_us": round(unattr, 3),
        })
    window_us = window[1] - window[0]
    idle_us = window_us - busy_us
    report.update(
        window_us=round(window_us, 3),
        device_busy_us=round(busy_us, 3),
        device_idle_us=round(idle_us, 3),
        idle_fraction=round(idle_us / window_us, 4) if window_us else None,
        n_gaps=len(gaps),
    )
    report["gaps"] = gap_rows[:top]
    if unattributed_total > 0:
        by_phase["(unattributed)"] = unattributed_total
    report["by_phase"] = [
        {"span": name, "us": round(us, 3)}
        for name, us in sorted(by_phase.items(), key=lambda kv: -kv[1])
    ]
    return report


def roofline_stamp(profile_path: str) -> dict:
    """Roofline placement rows for every config in a profile artifact.

    Accepts the ``--mfu-profile`` schema (``{"configs": [...]}`` where each
    row has ``flops_per_round``/``bytes_per_round``/``device_kind`` and
    usually ``rounds_per_sec``) or a flat dict with the same per-row keys.
    Peaks resolve through ``fedtpu.obs.profile.device_peaks`` (the one
    table); utilization is filled when the row carries an achieved rate. Imports fedtpu lazily — see module
    docstring."""
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from fedtpu.obs.profile import device_peaks, roofline

    doc = load_doc(profile_path)
    rows = doc.get("configs") if isinstance(doc.get("configs"), list) else [doc]
    out_rows = []
    for row in rows:
        if not isinstance(row, dict):
            continue
        flops = row.get("flops_per_round")
        nbytes = row.get("bytes_per_round")
        if flops is None and nbytes is None:
            continue
        peak_f, peak_b = device_peaks(row.get("device_kind") or "")
        achieved = None
        if flops and row.get("rounds_per_sec"):
            achieved = flops * row["rounds_per_sec"]
        placement = roofline(flops, nbytes, peak_f, peak_b, achieved)
        out_rows.append({
            "batch": row.get("batch"),
            "device_kind": row.get("device_kind"),
            "flops_per_round": flops,
            "bytes_per_round": nbytes,
            "mfu": row.get("mfu"),
            **placement,
        })
    return {
        "profile_artifact": profile_path,
        "rows": out_rows,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("merged", help="trace_merge.py output with device lanes")
    p.add_argument("-o", "--out", default=None,
                   help="write the JSON report here (default: stdout)")
    p.add_argument("--top", default=10, type=int,
                   help="how many gaps to detail, longest first")
    p.add_argument("--min-gap-us", default=100.0, type=float,
                   help="ignore device-idle gaps shorter than this")
    p.add_argument("--check", action="store_true",
                   help="fail when the timeline has no device lane at all")
    p.add_argument("--roofline", default=None, metavar="PROFILE",
                   help="stamp roofline placement (bound / intensity / "
                        "utilization) from this --mfu-profile artifact "
                        "onto the report (imports fedtpu lazily)")
    args = p.parse_args(argv)

    report = analyze(
        load_doc(args.merged), top=args.top, min_gap_us=args.min_gap_us
    )
    if args.roofline:
        report["roofline"] = roofline_stamp(args.roofline)
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    else:
        print(text)
    top_gap = report["gaps"][0] if report["gaps"] else None
    print(
        f"device lanes {report['device_lanes']}, "
        f"idle {report['idle_fraction']} of window, "
        f"{report['n_gaps']} gaps >= {args.min_gap_us}us"
        + (
            f"; top gap {top_gap['dur_us']}us -> "
            + (top_gap["attribution"][0]["span"]
               if top_gap["attribution"] else "(unattributed)")
            if top_gap else ""
        ),
        file=sys.stderr,
    )
    rl = report.get("roofline", {}).get("rows") or []
    if rl:
        r0 = rl[0]
        print(
            f"roofline: {r0['roofline_bound']} bound, "
            f"AI {r0['arith_intensity_flops_per_byte']} vs ridge "
            f"{r0['ridge_point_flops_per_byte']} "
            f"({len(rl)} config rows stamped)",
            file=sys.stderr,
        )
    if args.check and report["device_lanes"] == 0:
        print("CHECK FAILED: no device lane in the merged timeline "
              "(merge with --device-trace)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
