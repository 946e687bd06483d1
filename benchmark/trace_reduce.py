"""From a profiler trace to numbers: device busy and idle time, idle gaps
attributed to the harness's host spans, collective time, the top operations.

Works on a neutral form so that it can be checked on a small recorded trace
(``tests/benchmark/trace_tpu_small.json``): a list of events
``{"plane", "line", "name", "start_ns", "dur_ns"}``. ``load_xplane`` makes
that form from the ``.xplane.pb`` the JAX profiler writes. Interval
arithmetic after ``tools/gap_analyze.py`` (union, gaps, attribution), copied
here so that the yardstick does not move with the program's tools.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPANS = ("dispatch", "sync", "record.read")
CONTAINER = re.compile(r"^(while|conditional|call)\b")  # their bodies' ops are listed too
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute")


def load_xplane(trace_dir):
    """Device operation events of every TPU plane, and the harness's host
    spans, from the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name in SPANS:
                    events.append({
                        "plane": plane.name, "line": line.name,
                        "name": op_name(ev.name) if device else ev.name,
                        "start_ns": int(ev.start_ns),
                        "dur_ns": int(ev.duration_ns),
                    })
    return events


def op_name(text):
    """The profiler names a device operation by its whole HLO line,
    ``%fusion.12 = bf16[...] fusion(...)``: keep ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def union_intervals(intervals):
    out = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def find_gaps(busy, window):
    """The parts of ``window`` that no interval of the union ``busy`` covers."""
    lo, hi = window
    gaps, at = [], lo
    for b_lo, b_hi in busy:
        if b_hi <= lo or b_lo >= hi:
            continue
        if b_lo > at:
            gaps.append((at, b_lo))
        at = max(at, b_hi)
    if at < hi:
        gaps.append((at, hi))
    return gaps


def overlap(intervals, cut):
    lo, hi = cut
    return sum(max(0, min(b, hi) - max(a, lo)) for a, b in intervals)


def attribute_gaps(gaps, spans):
    """Idle nanoseconds by what the host was doing: each span's share of the
    gaps, ``_no_span_`` for what no span covers. ``spans``: name -> intervals
    (the harness's spans do not nest or overlap)."""
    by = {name: sum(overlap(iv, g) for g in gaps) for name, iv in spans.items()}
    total = sum(hi - lo for lo, hi in gaps)
    by["_no_span_"] = max(0, total - sum(by.values()))
    return by


def reduce_trace(events):
    """All the trace gives, as one dict (times in seconds):

    ``window_s``    first span's start to last span's end
    ``busy_s``      union of device-operation intervals in the window, mean
                    over the device planes; ``busy_by_device`` per plane
    ``idle_by_span``  idle time of the mean device under each host span
    ``collective_share``  worst device's collective time over its busy time
    ``device_ops``  ten operations with most summed time (mean over devices;
                    loop and branch containers left out, their bodies counted)
    """
    spans, devices = {}, {}
    for ev in events:
        iv = (ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
        if DEVICE_PLANE.match(ev["plane"]):
            devices.setdefault(ev["plane"], []).append((ev["name"], iv))
        elif ev["name"] in SPANS:
            spans.setdefault(ev["name"], []).append(iv)
    if not spans or not devices:
        return None
    window = (min(a for iv in spans.values() for a, _ in iv),
              max(b for iv in spans.values() for _, b in iv))
    n = len(devices)
    busy_by, idle_by_span, op_time, coll_share = {}, {}, {}, 0.0
    for plane, ops in devices.items():
        inside = [(name, (max(a, window[0]), min(b, window[1])))
                  for name, (a, b) in ops if b > window[0] and a < window[1]]
        busy = union_intervals([iv for _, iv in inside])
        busy_ns = sum(b - a for a, b in busy)
        busy_by[plane] = busy_ns / 1e9
        for name, ns in attribute_gaps(find_gaps(busy, window), spans).items():
            idle_by_span[name] = idle_by_span.get(name, 0.0) + ns / 1e9 / n
        coll = union_intervals(
            [iv for name, iv in inside if COLLECTIVE.search(name)])
        if busy_ns:
            coll_share = max(coll_share, sum(b - a for a, b in coll) / busy_ns)
        for name, (a, b) in inside:
            if CONTAINER.match(name):
                continue
            op_time[name] = op_time.get(name, 0.0) + (b - a) / 1e9 / n
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy_by.values()) / n,
        "busy_by_device": busy_by,
        "idle_by_span": idle_by_span,
        "collective_share": coll_share,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [[k, v] for k, v in
                      sorted(idle_by_span.items(), key=lambda kv: -kv[1])][:10],
    }
