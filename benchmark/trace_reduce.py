"""From a profiler trace to numbers: device busy and idle time, busy time by
the program's scope, idle gaps attributed to host spans (the harness's and the
program's), collective time and its exposed part, the top operations.

Works on a neutral form so that it can be checked on small recorded traces
(``tests/benchmark/trace_tpu_small.json``): a list of events ``{"plane",
"line", "name", "start_ns", "dur_ns"}``, a device event also ``"scope"``.
``load_xplane`` makes that form from what ``jax.profiler.stop_trace`` writes:
times from the ``.xplane.pb``, each operation's scope from the
``.trace.json.gz`` beside it. Interval arithmetic after
``tools/gap_analyze.py`` (union, gaps, innermost owner), copied here so that
the yardstick does not move with the program's tools.

Which scope or span means what is not decided here: a per-layer metric's
reader (``layer_metrics/<name>.py``) names the ones it reads.
"""

from __future__ import annotations

import glob
import gzip
import heapq
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPANS = ("dispatch", "sync", "record.read")  # the harness's own, in run.py
# What the program names: its host spans start so, and a scope inside an HLO
# op_name reads so whatever jit(...)/vmap(...)/transpose(jvp(...)) wraps it.
PROGRAM_PREFIX = "fed."
SCOPE = re.compile(re.escape(PROGRAM_PREFIX) + r"[a-z_]+(?:\.[a-z_]+)*")
UNSCOPED = "_unscoped_"
UNSCOPED_LIMIT = 0.05  # of device-busy time
NO_SPAN = "_no_span_"
CONTAINER = re.compile(r"^(while|conditional|call)\b")  # their bodies' ops are listed too
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute")


def load_xplane(trace_dir):
    """Device operation events of every TPU plane, each with its scope, and
    the host spans (the harness's and the program's), from the newest trace
    under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    scopes = load_scopes(paths[-1][: -len(".xplane.pb")] + ".trace.json.gz")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not (device or is_span(ev.name)):
                    continue
                event = {
                    "plane": plane.name, "line": line.name,
                    "name": op_name(ev.name) if device else ev.name,
                    "start_ns": int(ev.start_ns),
                    "dur_ns": int(ev.duration_ns),
                }
                if device:
                    event["scope"] = scopes.get((plane.name, event["name"]), "")
                events.append(event)
    return events


def load_scopes(path):
    """``(device plane, instruction name) -> scope`` from the trace viewer's
    JSON: ``ProfileData`` exposes no ``op_name`` for a TPU operation, the JSON
    carries it as ``args.tf_op`` on the ``XLA Ops`` thread of each
    ``/device:TPU:N`` process. An instruction is one HLO operation, so all
    its events share a scope."""
    with gzip.open(path, "rt") as fh:
        doc = json.load(fh)
    process, thread = {}, {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "process_name":
            process[e.get("pid")] = str(e.get("args", {}).get("name", ""))
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            thread[(e.get("pid"), e.get("tid"))] = str(e.get("args", {}).get("name", ""))
    scopes = {}
    for e in doc.get("traceEvents", []):
        plane = process.get(e.get("pid"), "")
        if (e.get("ph") == "X" and DEVICE_PLANE.match(plane)
                and thread.get((e.get("pid"), e.get("tid"))) == OPS_LINE):
            scopes.setdefault((plane, e.get("name", "")),
                              scope_of(e.get("args", {}).get("tf_op", "")))
    return scopes


def op_name(text):
    """The profiler names a device operation by its whole HLO line,
    ``%fusion.12 = bf16[...] fusion(...)``: keep ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def scope_of(hlo_op_name):
    """The innermost scope of the program that an HLO ``op_name`` passes
    through; ``""`` for none."""
    found = SCOPE.findall(hlo_op_name or "")
    return found[-1] if found else ""


def is_span(name):
    return name in SPANS or name.startswith(PROGRAM_PREFIX)


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


def innermost_segments(intervals):
    """Cut the union of ``intervals`` into ``(start, end, index)`` pieces,
    each owned by the innermost interval over it: the one that started last
    (of equal starts, the one that ends first). A loop's body operation owns
    its time, not the ``while`` around it; a span nested in another owns its
    own. Pieces do not overlap and cover the union exactly."""
    order = sorted((i for i, (lo, hi) in enumerate(intervals) if hi > lo),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    times = sorted({t for i in order for t in intervals[i]})
    active, out, nxt = [], [], 0  # heap of (-start, end, index): innermost on top
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


def attribute_gaps(gaps, spans):
    """Idle nanoseconds by what the host was doing: every idle instant goes
    to the innermost span over it (the program's spans nest inside the
    harness's ``dispatch``: the deepest wins), ``_no_span_`` for what no span
    covers. ``spans``: name -> intervals; every name gets a key."""
    flat = [(name, iv) for name, ivs in spans.items() for iv in ivs]
    by = {name: 0 for name in spans}
    g = 0  # gaps and pieces are both in time order and disjoint: one sweep
    for lo, hi, i in innermost_segments([iv for _, iv in flat]):
        while g < len(gaps) and gaps[g][1] <= lo:
            g += 1
        k = g
        while k < len(gaps) and gaps[k][0] < hi:
            by[flat[i][0]] += min(gaps[k][1], hi) - max(gaps[k][0], lo)
            k += 1
    total = sum(hi - lo for lo, hi in gaps)
    by[NO_SPAN] = max(0, total - sum(by.values()))
    return by


def reduce_trace(events):
    """All the trace gives, as one dict (times in seconds):

    ``window_s``    first harness span's start to the last one's end
    ``busy_s``      union of device-operation intervals in the window, mean
                    over the device planes; ``busy_by_device`` per plane
    ``busy_by_scope``  that time by the scope of the innermost running
                    operation (``_unscoped_`` for one that names none), mean
                    over the planes: adds up to ``busy_s``
    ``idle_by_span``  idle time of the mean device under each host span
    ``collective_share``  worst device's collective time over its busy time
    ``collective_exposed_s``  collective time during which no other
                    operation ran on that device, mean over the planes
    ``device_ops``  ten operations with most summed time (mean over devices;
                    loop and branch containers left out, their bodies counted)
    """
    spans, devices = {}, {}
    for ev in events:
        iv = (ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
        if DEVICE_PLANE.match(ev["plane"]):
            devices.setdefault(ev["plane"], []).append(
                (ev["name"], iv, ev.get("scope") or UNSCOPED))
        elif is_span(ev["name"]):
            spans.setdefault(ev["name"], []).append(iv)
    if not any(name in SPANS for name in spans) or not devices:
        return None
    window = (min(a for name in SPANS for a, _ in spans.get(name, ())),
              max(b for name in SPANS for _, b in spans.get(name, ())))
    n = len(devices)
    busy_by, by_scope, idle_by_span, op_time = {}, {}, {}, {}
    coll_share, exposed_ns = 0.0, 0.0
    for plane, ops in devices.items():
        inside = [(name, (max(a, window[0]), min(b, window[1])), scope)
                  for name, (a, b), scope in ops if b > window[0] and a < window[1]]
        busy = union_intervals([iv for _, iv, _ in inside])
        busy_ns = sum(b - a for a, b in busy)
        busy_by[plane] = busy_ns / 1e9
        for a, b, i in innermost_segments([iv for _, iv, _ in inside]):
            scope = inside[i][2]
            by_scope[scope] = by_scope.get(scope, 0.0) + (b - a) / 1e9 / n
        for name, ns in attribute_gaps(find_gaps(busy, window), spans).items():
            idle_by_span[name] = idle_by_span.get(name, 0.0) + ns / 1e9 / n
        coll = union_intervals(
            [iv for name, iv, _ in inside if COLLECTIVE.search(name)])
        others = union_intervals(
            [iv for name, iv, _ in inside
             if not COLLECTIVE.search(name) and not CONTAINER.match(name)])
        coll_ns = sum(b - a for a, b in coll)
        exposed_ns += coll_ns - sum(overlap(others, c) for c in coll)
        if busy_ns:
            coll_share = max(coll_share, coll_ns / busy_ns)
        for name, (a, b), _ in inside:
            if CONTAINER.match(name):
                continue
            op_time[name] = op_time.get(name, 0.0) + (b - a) / 1e9 / n
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy_by.values()) / n,
        "busy_by_device": busy_by,
        "busy_by_scope": by_scope,
        "idle_by_span": idle_by_span,
        "collective_share": coll_share,
        "collective_exposed_s": exposed_ns / 1e9 / n,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [[k, v] for k, v in
                      sorted(idle_by_span.items(), key=lambda kv: -kv[1])][:10],
    }


def scope_share(traced, scope):
    """Percent of device-busy time under ``scope`` and the scopes inside it;
    ``None`` where the trace holds no operation of it."""
    if not traced:
        return None
    own = [v for k, v in traced["busy_by_scope"].items()
           if k == scope or k.startswith(scope + ".")]
    return 100.0 * sum(own) / traced["busy_s"] if own else None


def stale_scopes(traced):
    """A message when more of the device's busy time than ``UNSCOPED_LIMIT``
    names no scope of the program, else ``None``. The scopes are in the
    source, so such an executable was very likely loaded from a compile cache
    another commit wrote: the cache's key leaves out debug information, and
    scope names are debug information. Its scopes must never be read as this
    commit's."""
    unscoped = traced["busy_by_scope"].get(UNSCOPED, 0.0)
    if unscoped <= UNSCOPED_LIMIT * traced["busy_s"]:
        return None
    share = unscoped / traced["busy_s"]
    return (f"{100 * share:.1f} % of the device's busy time names no scope of "
            f"the program (limit {100 * UNSCOPED_LIMIT:.0f} %): the executable "
            "very likely came from a compile cache written by a commit with "
            "other scopes. Empty the cache directory "
            "(JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache) and run "
            "again; if it repeats from an empty cache, the compiler inserts "
            "that much work without metadata (copies), which is a finding.")
