#!/usr/bin/env python
"""Where the device's time goes, from one ``jax.profiler`` capture: busy
time by layer of the round program, collective time exposed and hidden, and
every idle gap attributed to what the host was doing.

Input is a capture directory (the CLIs' ``--profile-rounds``, any
``jax.profiler.trace``): the ``*.trace.json.gz`` it writes beside its
``.xplane.pb`` holds the device's operations, each with its HLO
``op_name`` (``tf_op``), AND the program's own host spans (``fed.round`` /
``fed.plan`` / ``fed.enqueue``, the coordinator's ``collect`` / ``decode``
/ ...), on one clock, so nothing is aligned. (``jax.profiler.ProfileData``
reads the ``.xplane.pb`` but not the operations' metadata, where
``op_name`` lives: seen on jax 0.9.0 / TPU v5e.) A ``tools/trace_merge.py``
timeline with device lanes (several processes stitched by wall clock) is
read too.

The analyzer

1. assigns every busy instant of a chip to ONE operation, the innermost
   running one (a loop's body operation, not the ``while`` around it; of
   two overlapping operations the one that started later), so that times
   by scope add up to the busy time exactly;
2. sums that time by the operation's ``jax.named_scope`` (``fed.data``,
   ``fed.local_step`` with ``.fwd_bwd`` / ``.optimizer``, ``fed.pack``,
   ``fed.codec`` with ``.rotate`` / ``.quantize`` / ``.feedback`` /
   ``.select``, ``fed.aggregate`` with ``.psum`` / ``.all_gather``,
   ``fed.unpack``, ``fed.server_step``, ``fed.metrics``; see
   docs/OBSERVABILITY.md): ``by_scope`` per chip and as the mean over
   chips, with each scope's three longest operations by instruction name
   (what ties a profile's ``fusion.1316`` to a layer) and ``_unscoped_``
   for the rest. A fusion XLA built across a scope boundary is charged to
   the scope its own metadata names (its root's);
3. ``collectives``: all-reduce / all-gather / ... time per chip, the part
   of it during which no other operation ran there (exposed) and the rest
   (hidden), worst chip;
4. finds the idle gaps of each chip longer than ``--min-gap-us`` and
   charges each to the host spans over it, innermost first (``fed.plan``
   inside ``fed.round``, not ``fed.round``); what no span covers is the
   ``caller``'s (its sync and read between two ``step()`` calls):
   ``by_phase`` over all gaps and the ``--top`` longest in detail. A
   capture that holds set-up (a ``--profile-rounds`` window from round 0
   opens before the engine is built) charges the gaps between the model's
   init and the first round's execution to the ``fed.setup.*`` phases the
   same way: ``fed.setup.place_state``,
   ``fed.setup.first_dispatch.device_data.h2d``, the first
   ``fed.enqueue`` (the round program's compile or cache load).

A capture whose device time carries no scope at all was run from an
executable compiled by another commit: JAX's persistent compile cache keys
on the program WITHOUT its debug info, and scope names are debug info. The
command then fails and says so (empty the cache directory, capture again).

Stdlib only, except for ``--roofline`` (imports fedtpu inside its
handler).

Usage:
    python tools/gap_analyze.py <capture dir | merged.json> [-o report.json]
        [--top 10] [--min-gap-us 100] [--check]
        [--roofline artifacts/MFU_PROFILE_r04.json]

``--check`` exits non-zero when there is no device operation at all (a
``--profile-rounds`` window that closed before anything ran). An EMPTY gap
list is not a failure: a fully busy device is the goal state.

``--roofline PROFILE`` stamps roofline placement onto the report: for each
config row in an ``--mfu-profile`` artifact (or a flat dict carrying
``flops_per_round``/``bytes_per_round``) it recomputes arithmetic
intensity, ridge point, bound and utilization through
``fedtpu.obs.profile.roofline``.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import heapq
import json
import os
import re
import sys
from typing import Dict, List, Tuple

SCHEMA_VERSION = 2

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
CPU_PLANE = "/device:CPU:0"  # a CPU capture's XLA executor, standing in
OPS_LINE = "XLA Ops"
# A scope of the round program inside an HLO op_name, whatever
# jit(...)/vmap(...)/transpose(jvp(...))/remat/while/body wraps around it.
SCOPE = re.compile(r"fed\.[a-z_]+(?:\.[a-z_]+)*")
UNSCOPED = "_unscoped_"
UNSCOPED_LIMIT = 0.05  # of device-busy time
CALLER = "caller"
# Loop and branch containers run their bodies' operations inside them.
CONTAINER = re.compile(r"^(while|conditional|call)\b")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute")
# The host spans kept beside the ``fed.*`` ones: the coordinator's and the
# edge's (docs/OBSERVABILITY.md; tools/span_check.py holds the two equal).
SERVER_SPANS = frozenset((
    "round", "collect", "client_rpc", "submit_partial", "decode", "h2d",
    "screen", "aggregate", "partial_reduce", "replicate", "broadcast",
    "install_global", "client_train", "checkpoint", "cohort_sample",
    "async_tick", "fused_ticks", "async_update",
))


class StaleScopes(ValueError):
    """Device time that names no scope of the round program."""


# ------------------------------------------------------------ the capture
def find_capture(capture_dir: str) -> str:
    """The newest ``*.trace.json.gz`` under a ``jax.profiler`` output
    directory (layout: ``plugins/profile/<run>/<host>.trace.json.gz``,
    written beside the ``.xplane.pb`` when the capture closes)."""
    hits = glob.glob(os.path.join(capture_dir, "**", "*.trace.json.gz"),
                     recursive=True)
    if not hits:
        raise FileNotFoundError(
            f"no *.trace.json.gz under {capture_dir} (is this a "
            "--profile-rounds / jax.profiler output directory?)"
        )
    return max(hits, key=os.path.getmtime)


def scope_of(op_name: str) -> str:
    """The innermost ``fed.`` scope an HLO ``op_name`` passes through
    (``...vmap(fed.local_step)/while/body/transpose(jvp(fed.local_step.
    fwd_bwd))/conv`` -> ``fed.local_step.fwd_bwd``), ``""`` for none."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else ""


def load_capture(capture_dir: str) -> List[dict]:
    """A capture in the neutral form ``{"plane", "line", "name", "start_ns",
    "dur_ns", "scope"}``: every operation on the ``XLA Ops`` line of every
    ``/device:TPU:N`` process (``name`` the instruction, ``scope`` from its
    ``tf_op`` argument, which is the HLO ``op_name``; the ``Steps`` and
    ``XLA Modules`` lines span whole programs and are left out), and the
    host events named ``fed.*`` or like a coordinator span. A CPU capture
    has no device process: the operations of its XLA executor (host events
    that carry ``hlo_op``) stand in as ``/device:CPU:0``, without scopes
    (the CPU profiler records no ``op_name``)."""
    with gzip.open(find_capture(capture_dir), "rt") as fh:
        doc = json.load(fh)
    process, thread = {}, {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "process_name":
            process[e.get("pid")] = str(e.get("args", {}).get("name", ""))
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            thread[(e.get("pid"), e.get("tid"))] = str(
                e.get("args", {}).get("name", ""))
    on_tpu = any(DEVICE_PLANE.match(name) for name in process.values())
    events = []
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "X" or "ts" not in e or "dur" not in e:
            continue
        plane = process.get(e.get("pid"), "")
        line = thread.get((e.get("pid"), e.get("tid")), "")
        name, args, scope = e.get("name", ""), e.get("args", {}), ""
        if DEVICE_PLANE.match(plane):
            if line != OPS_LINE:
                continue
            scope = scope_of(args.get("tf_op", ""))
        elif name.startswith("fed.") or name in SERVER_SPANS:
            pass
        elif not on_tpu and "hlo_op" in args:
            plane, line, name = CPU_PLANE, OPS_LINE, str(args["hlo_op"])
        else:
            continue
        events.append({
            "plane": plane, "line": line, "name": name,
            "start_ns": round(e["ts"] * 1e3), "dur_ns": round(e["dur"] * 1e3),
            "scope": scope,
        })
    return events


def events_of_timeline(doc: dict) -> List[dict]:
    """The neutral form of a ``trace_merge.py`` timeline: its device lanes
    (``cat="device"``, one plane per lane) and its host spans."""
    events = []
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "X" or "ts" not in e or "dur" not in e:
            continue
        device = e.get("cat") == "device"
        events.append({
            "plane": f"/{'device' if device else 'host'}:lane{e.get('pid')}",
            "line": str(e.get("tid")), "name": e.get("name", ""),
            "start_ns": e["ts"] * 1e3, "dur_ns": e["dur"] * 1e3,
            "scope": e.get("args", {}).get("scope", "") if device else "",
        })
    return events


# ------------------------------------------------------ interval arithmetic
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
    busy: List[Interval], window: Interval, min_gap: float
) -> List[Interval]:
    """Maximal idle sub-intervals of ``window`` not covered by the merged
    ``busy`` union, at least ``min_gap`` long."""
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
    return [(s, e) for s, e in gaps if e - s >= min_gap]


def _overlap(intervals: List[Interval], cut: Interval) -> float:
    return sum(
        max(0.0, min(e, cut[1]) - max(s, cut[0])) for s, e in intervals
    )


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


def innermost_segments(intervals: List[Interval]) -> List[Tuple[float, float, int]]:
    """Cut the union of ``intervals`` into ``(start, end, index)`` pieces,
    each owned by the innermost interval over it: the one that started
    last (of equal starts, the one that ends first). Pieces do not overlap
    and cover the union exactly."""
    order = sorted(
        (i for i, (s, e) in enumerate(intervals) if e > s),
        key=lambda i: (intervals[i][0], -intervals[i][1]),
    )
    times = sorted({t for i in order for t in intervals[i]})
    active: list = []  # heap of (-start, end, index): innermost on top
    out, nxt = [], 0
    for t0, t1 in zip(times, times[1:]):
        while nxt < len(order) and intervals[order[nxt]][0] <= t0:
            i = order[nxt]
            heapq.heappush(active, (-intervals[i][0], intervals[i][1], i))
            nxt += 1
        while active and active[0][1] <= t0:
            heapq.heappop(active)
        if active:
            i = active[0][2]
            if out and out[-1][2] == i and out[-1][1] == t0:
                out[-1] = (out[-1][0], t1, i)
            else:
                out.append((t0, t1, i))
    return out


def _depths(spans: List[dict]) -> List[int]:
    """Nesting depth per span: the number of spans that properly contain
    it in time (O(n^2) — host span counts are small)."""
    depths = []
    for i, a in enumerate(spans):
        a0, a1 = a["start_ns"], a["start_ns"] + a["dur_ns"]
        d = 0
        for j, b in enumerate(spans):
            if i == j:
                continue
            b0, b1 = b["start_ns"], b["start_ns"] + b["dur_ns"]
            if b0 <= a0 and a1 <= b1 and (b0 < a0 or a1 < b1):
                d += 1
        depths.append(d)
    return depths


def attribute_gap(
    gap: Interval, spans: List[dict], depths: List[int]
) -> Dict[str, float]:
    """Charge a gap to the host spans over it, innermost first; an
    enclosing span only gets what its children left uncovered, and
    :data:`CALLER` what no span covers. ``{name: nanoseconds}``."""
    g0, g1 = gap
    overlapping = [
        (depths[i], s) for i, s in enumerate(spans)
        if s["start_ns"] < g1 and s["start_ns"] + s["dur_ns"] > g0
    ]
    overlapping.sort(key=lambda ds: -ds[0])
    remaining: List[Interval] = [gap]
    charged: Dict[str, float] = {}
    for _d, s in overlapping:
        cut = (max(g0, s["start_ns"]), min(g1, s["start_ns"] + s["dur_ns"]))
        got = _overlap(remaining, cut)
        if got > 0:
            charged[s["name"]] = charged.get(s["name"], 0.0) + got
            remaining = _subtract(remaining, cut)
    left = sum(e - b for b, e in remaining)
    if left > 0:
        charged[CALLER] = left
    return charged


# ------------------------------------------------------------ the reduction
def _us(ns: float) -> float:
    return round(ns / 1e3, 3)


def _top_level(scope: str) -> str:
    return ".".join(scope.split(".")[:2]) if scope else UNSCOPED


def _reduce_chip(ops: List[dict], spans, depths, min_gap_ns: float) -> dict:
    """One chip: busy time by scope, collectives, idle gaps by phase."""
    ivs = [(o["start_ns"], o["start_ns"] + o["dur_ns"]) for o in ops]
    segments = innermost_segments(ivs)
    busy = union_intervals([(a, b) for a, b, _ in segments])
    window = (busy[0][0], busy[-1][1])
    by_scope: Dict[str, float] = {}
    by_op: Dict[Tuple[str, str], float] = {}
    for a, b, i in segments:
        scope = ops[i]["scope"] or UNSCOPED
        by_scope[scope] = by_scope.get(scope, 0.0) + (b - a)
        key = (scope, ops[i]["name"])
        by_op[key] = by_op.get(key, 0.0) + (b - a)
    coll = union_intervals(
        [iv for iv, o in zip(ivs, ops) if COLLECTIVE.search(o["name"])]
    )
    others = union_intervals([
        iv for iv, o in zip(ivs, ops)
        if not COLLECTIVE.search(o["name"])
        and not CONTAINER.match(o["name"])
    ])
    coll_ns = sum(b - a for a, b in coll)
    hidden_ns = sum(_overlap(others, c) for c in coll)
    gaps = find_gaps(busy, window, min_gap_ns)
    by_phase: Dict[str, float] = {}
    gap_rows = []
    for g in gaps:
        charged = attribute_gap(g, spans, depths)
        for name, ns in charged.items():
            by_phase[name] = by_phase.get(name, 0.0) + ns
        gap_rows.append((g, charged))
    return {
        "window": window,
        "busy_ns": sum(b - a for a, b in busy),
        "by_scope": by_scope, "by_op": by_op,
        "collective_ns": coll_ns, "hidden_ns": hidden_ns,
        "by_phase": by_phase, "gaps": gap_rows,
    }


def reduce_events(
    events: List[dict], top: int = 10, min_gap_us: float = 100.0
) -> dict:
    """The report for one capture or timeline in the neutral form (module
    docstring; times in microseconds). Per chip and as the mean over
    chips. Tolerates an empty device side: ``device_lanes: 0``, no gaps."""
    chips: Dict[str, List[dict]] = {}
    spans = []
    for ev in events:
        if ev["plane"].startswith("/device:"):
            chips.setdefault(ev["plane"], []).append(ev)
        else:
            spans.append(ev)
    report = {
        "schema_version": SCHEMA_VERSION,
        "chips": sorted(chips),
        "device_lanes": len(chips),
        "device_ops": sum(len(v) for v in chips.values()),
        "min_gap_us": min_gap_us,
        "gaps": [], "by_phase": [], "by_scope": [], "collectives": None,
    }
    if not chips:
        report.update(
            window_us=None, device_busy_us=0.0, device_idle_us=0.0,
            idle_fraction=None, n_gaps=0,
        )
        return report
    depths = _depths(spans)
    per = {
        plane: _reduce_chip(ops, spans, depths, min_gap_us * 1e3)
        for plane, ops in sorted(chips.items())
    }
    n = len(per)

    def mean(f):
        return sum(f(c) for c in per.values()) / n

    window = mean(lambda c: c["window"][1] - c["window"][0])
    busy = mean(lambda c: c["busy_ns"])
    report.update(
        window_us=_us(window), device_busy_us=_us(busy),
        device_idle_us=_us(window - busy),
        idle_fraction=round(1.0 - busy / window, 6) if window else None,
        n_gaps=sum(len(c["gaps"]) for c in per.values()),
    )
    # ------------------------------------------------- busy time by scope
    scopes = sorted({s for c in per.values() for s in c["by_scope"]})
    rows: Dict[str, dict] = {}
    for scope in scopes:
        ns = mean(lambda c: c["by_scope"].get(scope, 0.0))
        ops: Dict[str, float] = {}
        for c in per.values():
            for (s, name), t in c["by_op"].items():
                if s == scope:
                    ops[name] = ops.get(name, 0.0) + t / n
        parent = rows.setdefault(_top_level(scope), {
            "scope": _top_level(scope), "us": 0.0, "children": [],
        })
        parent["us"] += ns
        parent["children"].append({
            "scope": scope, "us": _us(ns),
            "share": round(ns / busy, 6) if busy else 0.0,
            "top_ops": [
                [name, _us(t)] for name, t in
                sorted(ops.items(), key=lambda kv: -kv[1])[:3]
            ],
            "by_chip": {
                plane: _us(c["by_scope"].get(scope, 0.0))
                for plane, c in per.items()
            },
        })
    for row in rows.values():
        row["share"] = round(row["us"] / busy, 6) if busy else 0.0
        row["us"] = _us(row["us"])
    report["by_scope"] = sorted(rows.values(), key=lambda r: -r["us"])
    report["unscoped_share"] = rows.get(UNSCOPED, {}).get("share", 0.0)
    # -------------------------------------------------------- collectives
    worst = max(per, key=lambda p: per[p]["collective_ns"])
    report["collectives"] = {
        "total_us": _us(mean(lambda c: c["collective_ns"])),
        "exposed_us": _us(mean(lambda c: c["collective_ns"] - c["hidden_ns"])),
        "hidden_us": _us(mean(lambda c: c["hidden_ns"])),
        "worst_chip": worst,
        "worst_chip_total_us": _us(per[worst]["collective_ns"]),
        "worst_chip_exposed_us": _us(
            per[worst]["collective_ns"] - per[worst]["hidden_ns"]),
        "worst_chip_share_of_busy": round(
            per[worst]["collective_ns"] / per[worst]["busy_ns"], 6
        ) if per[worst]["busy_ns"] else 0.0,
    }
    # ---------------------------------------------------- idle by phase
    phases = sorted({p for c in per.values() for p in c["by_phase"]})
    report["by_phase"] = sorted(
        (
            {"span": p, "us": _us(mean(lambda c: c["by_phase"].get(p, 0.0)))}
            for p in phases
        ),
        key=lambda r: -r["us"],
    )
    gap_rows = [
        (g, charged, plane)
        for plane, c in per.items() for g, charged in c["gaps"]
    ]
    gap_rows.sort(key=lambda r: r[0][0] - r[0][1])  # longest first
    report["gaps"] = [
        {
            "chip": plane,
            "start_us": _us(g[0]), "end_us": _us(g[1]),
            "dur_us": _us(g[1] - g[0]),
            "attribution": [
                {"span": name, "us": _us(ns),
                 "fraction": round(ns / (g[1] - g[0]), 4)}
                for name, ns in sorted(charged.items(), key=lambda kv: -kv[1])
            ],
        }
        for g, charged, plane in gap_rows[:top]
    ]
    return report


def check_scoped(report: dict) -> None:
    """Raise :class:`StaleScopes` when more of the device's busy time than
    :data:`UNSCOPED_LIMIT` names no scope of the round program."""
    share = report.get("unscoped_share", 0.0)
    cpu = report["chips"] == [CPU_PLANE]  # its profiler records no op_name
    if report["device_ops"] and share > UNSCOPED_LIMIT and not cpu:
        raise StaleScopes(
            f"{100 * share:.1f} % of the device's busy time names no fed.* "
            f"scope (limit {100 * UNSCOPED_LIMIT:.0f} %). The scopes are in "
            "the source, so the executable that ran was very likely loaded "
            "from a STALE COMPILE CACHE: JAX's persistent cache keys on the "
            "program without its debug info, and scope names are debug "
            "info, so an executable compiled by a commit with other (or "
            "no) scopes is served as it is. Empty the cache directory "
            "(JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache) and "
            "capture again."
        )


def analyze(doc: dict, top: int = 10, min_gap_us: float = 100.0) -> dict:
    """The report for a ``trace_merge.py`` timeline."""
    return reduce_events(events_of_timeline(doc), top, min_gap_us)


def analyze_capture(
    capture_dir: str, top: int = 10, min_gap_us: float = 100.0
) -> dict:
    """The report for a capture directory; :class:`StaleScopes` when its
    device time names no scope."""
    report = reduce_events(load_capture(capture_dir), top, min_gap_us)
    check_scoped(report)
    return report


def load_doc(path: str) -> dict:
    with open(path) as fh:
        doc = json.load(fh)
    if isinstance(doc, list):
        doc = {"traceEvents": doc}
    return doc


def roofline_stamp(profile_path: str) -> dict:
    """Roofline placement rows for every config in a profile artifact.

    Accepts the ``--mfu-profile`` schema (``{"configs": [...]}`` where each
    row has ``flops_per_round``/``bytes_per_round``/``device_kind`` and
    usually ``rounds_per_sec``) or a flat dict with the same per-row keys.
    Peaks resolve through ``fedtpu.obs.profile.device_peaks`` (the one
    table); utilization is filled when the row carries an achieved rate. Imports fedtpu lazily — see module
    docstring."""
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


def summary_lines(report: dict) -> List[str]:
    """The report's few lines for a terminal."""
    lines = [
        f"device lanes {report['device_lanes']}, "
        f"idle {report['idle_fraction']} of window, "
        f"{report['n_gaps']} gaps >= {report['min_gap_us']}us"
    ]
    for row in report["by_scope"]:
        kids = ", ".join(
            f"{c['scope'].rsplit('.', 1)[-1]} {100 * c['share']:.2f}"
            for c in row["children"] if c["scope"] != row["scope"]
        )
        lines.append(
            f"  {row['scope']:<18} {100 * row['share']:6.2f} % of busy"
            + (f"  ({kids})" if kids else "")
        )
    coll = report.get("collectives")
    if coll and coll["total_us"]:
        lines.append(
            f"  collectives {coll['total_us']}us a chip, exposed "
            f"{coll['exposed_us']}us, hidden {coll['hidden_us']}us"
        )
    if report["by_phase"]:
        lines.append("  idle under " + ", ".join(
            f"{r['span']} {r['us']}us" for r in report["by_phase"]))
    return lines


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("source", help="a jax.profiler capture directory, or a "
                   "trace_merge.py timeline with device lanes")
    p.add_argument("-o", "--out", default=None,
                   help="write the JSON report here (default: stdout)")
    p.add_argument("--top", default=10, type=int,
                   help="how many gaps to detail, longest first")
    p.add_argument("--min-gap-us", default=100.0, type=float,
                   help="ignore device-idle gaps shorter than this")
    p.add_argument("--check", action="store_true",
                   help="fail when there is no device operation at all")
    p.add_argument("--roofline", default=None, metavar="PROFILE",
                   help="stamp roofline placement (bound / intensity / "
                        "utilization) from this --mfu-profile artifact "
                        "onto the report (imports fedtpu lazily)")
    args = p.parse_args(argv)

    if os.path.isdir(args.source):
        try:
            report = analyze_capture(args.source, args.top, args.min_gap_us)
        except StaleScopes as e:
            print(f"gap_analyze: {e}", file=sys.stderr)
            return 1
    else:
        report = analyze(load_doc(args.source), args.top, args.min_gap_us)
    if args.roofline:
        report["roofline"] = roofline_stamp(args.roofline)
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    else:
        print(text)
    print("\n".join(summary_lines(report)), file=sys.stderr)
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
        print("CHECK FAILED: no device operation in the capture",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
