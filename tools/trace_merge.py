#!/usr/bin/env python
"""Stitch per-process fedtpu Chrome-trace dumps into ONE Perfetto timeline.

Each federation process exports its own trace (``--trace-out`` /
``Telemetry.export_trace``) with a ``metadata`` block carrying the
federation ``trace_id``, its ``role`` ("primary", "client:<addr>", ...)
and ``wall_start`` (wall-clock time of its monotonic zero). This tool
merges any number of those files into a single Chrome trace where:

- every process gets its own lane: ``pid`` = a per-file lane id with a
  ``process_name`` metadata event naming the role (Perfetto renders one
  process track per role; ``tid`` stays the original worker thread);
- timestamps are aligned onto one wall-clock timeline via ``wall_start``
  deltas (files without the metadata keep their own zero and are listed
  under ``metadata.unaligned``);
- span ids are qualified ``<role>/<local id>`` so per-process counters
  can never collide, and the propagated cross-process links
  (``args.remote_parent`` + ``args.remote_role``, written by the
  receiving client from the ``fedtpu-trace-bin`` metadata) are resolved
  into ordinary ``args.parent_id`` references — after the merge a client
  ``client_train`` span's parent chain walks through the coordinator's
  ``client_rpc`` span up to its ``round`` span;
- ``--device-trace DIR`` ingests a ``jax.profiler`` capture (the CLIs'
  ``--profile-rounds``, fedtpu.obs.profile.CaptureWindow) through the one
  reader of captures, ``gap_analyze.load_capture``: XLA device-op
  executions land on extra ``device:*`` lanes — one per chip (TPU) or one
  for the XLA CPU executor — wall-clock aligned with the host spans via
  the capture's ``profile_meta.json`` sidecar, every event tagged
  ``cat="device"`` and carrying its ``args.scope`` so
  ``tools/gap_analyze.py`` can separate device busy time from host phases
  and split it by layer. For ONE process, skip the merge:
  ``python tools/gap_analyze.py DIR`` reads the capture's own spans.

Import-free of fedtpu (stdlib only), like the other ``tools/`` readers.

Usage:
    python tools/trace_merge.py primary.json client0.json client1.json \
        [--device-trace capture_dir] -o merged.json [--check]

``--check`` additionally verifies every ``client_train`` span reaches a
``round`` root through the merged parent chain (and, with
``--device-trace``, that at least one device lane carries ops) and exits
non-zero otherwise (the CI assertion, see tests/test_obs_propagation.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple


def load_doc(path: str) -> dict:
    """Read one Chrome-trace dump; bare-array files get an empty
    metadata block (both forms are valid Chrome trace JSON)."""
    with open(path) as fh:
        doc = json.load(fh)
    if isinstance(doc, list):
        doc = {"traceEvents": doc}
    doc.setdefault("metadata", {})
    return doc


def _qualify(role: str, span_id) -> str:
    return f"{role}/{span_id}"


# ------------------------------------------------------ device-trace input
PROFILE_META = "profile_meta.json"  # fedtpu.obs.profile sidecar name


def load_device_trace(capture_dir: str) -> dict:
    """A ``jax.profiler`` capture directory as a Chrome-trace doc of device
    lanes: the operations ``gap_analyze.load_capture`` reads from its
    ``*.trace.json.gz`` (one ``/device:...`` process per chip, each event with
    its ``args.scope``), on the capture's own clock, which starts when the
    capture opens, plus ``metadata.wall_start``/``role`` from the
    ``profile_meta.json`` sidecar (its wall clock is stamped at that same
    moment) for the merge's alignment. The capture's own host spans are
    not copied: the merge takes those from the per-process dumps."""
    import gap_analyze

    ops = [e for e in gap_analyze.load_capture(capture_dir)
           if e["plane"].startswith("/device:")]
    planes = sorted({e["plane"] for e in ops})
    events = [
        {"ph": "M", "name": "process_name", "pid": i,
         "args": {"name": plane}}
        for i, plane in enumerate(planes)
    ]
    events += [
        {"ph": "X", "pid": planes.index(e["plane"]), "tid": 0,
         "name": e["name"], "ts": e["start_ns"] / 1e3,
         "dur": e["dur_ns"] / 1e3, "args": {"scope": e["scope"]}}
        for e in ops
    ]
    doc = {"traceEvents": events, "metadata": {}}
    try:
        with open(os.path.join(capture_dir, PROFILE_META)) as fh:
            sidecar = json.load(fh)
    except (OSError, ValueError):
        return doc  # a bare jax.profiler capture: the merge lists it unaligned
    doc["metadata"]["wall_start"] = sidecar.get("wall_start")
    doc["metadata"]["role"] = sidecar.get("role") or "device"
    return doc


def extract_device_lanes(doc: dict) -> List[Tuple[str, List[dict]]]:
    """``[(lane_name, X-events)]`` of the ``/device:`` processes of a
    :func:`load_device_trace` doc: one lane per chip (a CPU capture's XLA
    executor operations arrive as ``/device:CPU:0``)."""
    pid_name = {
        e.get("pid"): str(e.get("args", {}).get("name", ""))
        for e in doc.get("traceEvents", [])
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    lanes: Dict[str, List[dict]] = {}
    for e in doc.get("traceEvents", []):
        name = pid_name.get(e.get("pid"), "")
        if e.get("ph") == "X" and "/device:" in name:
            lanes.setdefault(name, []).append(e)
    return sorted(lanes.items())


def merge_docs(docs: List[dict], device_docs: List[dict] = ()) -> dict:
    """Merge loaded trace docs (see module docstring). Order fixes lane
    numbering; roles are deduplicated with a ``#n`` suffix if two files
    claim the same one."""
    merged: List[dict] = []
    seen_roles: Dict[str, int] = {}
    roles: List[str] = []
    device_lanes: List[str] = []
    trace_ids = []
    unaligned = []
    wall_starts = [
        d["metadata"].get("wall_start")
        for d in list(docs) + list(device_docs)
        if d["metadata"].get("wall_start") is not None
    ]
    base_wall = min(wall_starts) if wall_starts else None

    for lane, doc in enumerate(docs, start=1):
        meta = doc["metadata"]
        role = str(meta.get("role") or f"proc{lane}")
        if role in seen_roles:
            seen_roles[role] += 1
            role = f"{role}#{seen_roles[role]}"
        else:
            seen_roles[role] = 0
        roles.append(role)
        tid = meta.get("trace_id")
        if tid and tid not in trace_ids:
            trace_ids.append(tid)
        offset_us = 0.0
        if base_wall is not None and meta.get("wall_start") is not None:
            offset_us = (meta["wall_start"] - base_wall) * 1e6
        elif base_wall is not None:
            unaligned.append(role)
        merged.append({
            "name": "process_name",
            "ph": "M",
            "pid": lane,
            "args": {"name": role},
        })
        for event in doc.get("traceEvents", []):
            if event.get("ph") == "M":
                continue  # per-file metadata is superseded by the lane's
            ev = dict(event)
            ev["pid"] = lane
            if "ts" in ev:
                ev["ts"] = round(ev["ts"] + offset_us, 3)
            args = dict(ev.get("args", {}))
            if "span_id" in args:
                args["span_id"] = _qualify(role, args["span_id"])
            if "parent_id" in args:
                args["parent_id"] = _qualify(role, args["parent_id"])
            elif "remote_parent" in args:
                # The propagated cross-process link becomes a first-class
                # parent reference in the merged id namespace.
                args["parent_id"] = _qualify(
                    str(args.get("remote_role", "")), args["remote_parent"]
                )
                args["parent_is_remote"] = True
            ev["args"] = args
            merged.append(ev)

    # Device lanes ride after the host lanes: one pid per chip (or the
    # synthetic XLA:CPU executor lane), events tagged cat="device" so
    # downstream readers (gap_analyze) can tell device busy time from
    # host spans without name heuristics.
    lane = len(docs)
    for doc in device_docs:
        meta = doc["metadata"]
        role = str(meta.get("role") or "device")
        offset_us = 0.0
        if base_wall is not None and meta.get("wall_start") is not None:
            offset_us = (meta["wall_start"] - base_wall) * 1e6
        elif base_wall is not None:
            unaligned.append(f"device:{role}")
        for lane_name, events in extract_device_lanes(doc):
            lane += 1
            full = f"device:{lane_name} ({role})"
            device_lanes.append(full)
            merged.append({
                "name": "process_name",
                "ph": "M",
                "pid": lane,
                "args": {"name": full},
            })
            for event in events:
                ev = dict(event)
                ev["pid"] = lane
                ev["cat"] = "device"
                if "ts" in ev:
                    ev["ts"] = round(ev["ts"] + offset_us, 3)
                merged.append(ev)

    return {
        "traceEvents": merged,
        "displayTimeUnit": "ms",
        "metadata": {
            "merged_roles": roles,
            "device_lanes": device_lanes,
            "trace_ids": trace_ids,
            "unaligned": unaligned,
        },
    }


def span_index(doc: dict) -> Dict[str, dict]:
    """{qualified span_id: event} over a merged doc's span events."""
    return {
        e["args"]["span_id"]: e
        for e in doc.get("traceEvents", [])
        if e.get("ph") == "X" and "span_id" in e.get("args", {})
    }


def root_of(index: Dict[str, dict], event: dict) -> Optional[dict]:
    """Walk the merged parent chain to its root (None on a dangling
    reference — e.g. a parent from a file that wasn't merged)."""
    seen = set()
    while True:
        parent = event.get("args", {}).get("parent_id")
        if parent is None:
            return event
        if parent in seen or parent not in index:
            return None
        seen.add(parent)
        event = index[parent]


def check_client_train_nesting(doc: dict) -> List[str]:
    """Problem strings (empty = pass): every ``client_train`` span must
    resolve through the merged parent chain to a ``round`` root."""
    index = span_index(doc)
    problems = []
    trains = [
        e for e in doc.get("traceEvents", [])
        if e.get("name") == "client_train"
    ]
    if not trains:
        problems.append("no client_train spans in merged trace")
    for e in trains:
        root = root_of(index, e)
        if root is None:
            problems.append(
                f"client_train {e['args'].get('span_id')}: dangling parent "
                "chain"
            )
        elif root.get("name") != "round":
            problems.append(
                f"client_train {e['args'].get('span_id')}: roots at "
                f"{root.get('name')!r}, not 'round'"
            )
    return problems


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("traces", nargs="+",
                   help="per-process Chrome-trace JSON dumps (put the "
                   "coordinator's first for lane ordering)")
    p.add_argument("-o", "--out", required=True, help="merged trace path")
    p.add_argument(
        "--device-trace", action="append", default=[], metavar="DIR",
        help="ingest a jax.profiler capture (--profile-rounds output dir) "
        "as wall-clock-aligned device lanes; repeatable",
    )
    p.add_argument("--check", action="store_true",
                   help="fail unless every client_train span roots in a "
                   "round span through the merged parent chain (and any "
                   "--device-trace contributed at least one device op)")
    args = p.parse_args(argv)

    doc = merge_docs(
        [load_doc(path) for path in args.traces],
        device_docs=[load_device_trace(p) for p in args.device_trace],
    )
    with open(args.out, "w") as fh:
        json.dump(doc, fh)
    n = sum(1 for e in doc["traceEvents"] if e.get("ph") == "X")
    n_dev = sum(
        1 for e in doc["traceEvents"]
        if e.get("ph") == "X" and e.get("cat") == "device"
    )
    print(
        f"merged {len(args.traces)} traces -> {args.out}: {n} spans "
        f"({n_dev} device ops), "
        f"lanes {doc['metadata']['merged_roles']}"
        f"{' + ' + str(doc['metadata']['device_lanes']) if doc['metadata']['device_lanes'] else ''}, "
        f"trace_ids {doc['metadata']['trace_ids']}",
        file=sys.stderr,
    )
    if args.check:
        problems = check_client_train_nesting(doc)
        if args.device_trace and n_dev == 0:
            problems.append(
                "device traces given but no device ops made it into the "
                "merge (empty capture window?)"
            )
        if doc["metadata"]["unaligned"]:
            problems.append(
                f"unaligned files (no wall_start): "
                f"{doc['metadata']['unaligned']}"
            )
        if len(doc["metadata"]["trace_ids"]) > 1:
            problems.append(
                f"multiple trace ids: {doc['metadata']['trace_ids']} "
                "(files from different federation runs?)"
            )
        for problem in problems:
            print(f"CHECK FAILED: {problem}", file=sys.stderr)
        return 1 if problems else 0
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
