#!/usr/bin/env python3
"""fedtpu's benchmark: one run of one cell.

    python3 benchmark/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``configs/<config>.json``, its task ``tasks/<task>.py``, plain reference
``reference/<model>.py``, FLOP function ``flops/<config>.py``) under a traffic
mix (``traffic/<traffic>.json``) with its check limits (``limits/<cell>.json``).
A per-layer metric is read by ``layer_metrics/<name>.py``. Nothing here names
a cell, a configuration, a task, a kind of data, a metric or a scope of the
program: a later PR adds files and manifest entries.

Order of a run: set-up (data and weights from the seed, the engine, its first
rounds, which compile, warm up and are kept for the check), the measured
window of whole rounds, the device's memory reading, then the program's
state is freed and the plain reference follows the same first rounds and is
compared. The last line of stdout is the result object.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 6.0  # a traced run profiles its first rounds, about this long


def load_py(path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


class Cell:
    """Everything the manifest and its files say about one workload."""

    def __init__(self, manifest_path, workload):
        from benchmark import tasks

        self.manifest = load_json(manifest_path)
        rows = [w for w in self.manifest["workloads"] if w["name"] == workload]
        if not rows:
            raise SystemExit(f"no workload {workload!r} in {manifest_path}")
        self.row = rows[0]
        self.name, self.chips = workload, self.row["chips"]
        base = os.path.dirname(os.path.abspath(manifest_path))
        conf = [c for c in self.manifest["configs"] if c["name"] == self.row["config"]][0]
        self.config = load_json(os.path.join(base, conf["file"]))
        self.data_dir = os.path.dirname(os.path.dirname(os.path.join(base, conf["file"])))
        self.traffic = load_json(os.path.join(
            self.data_dir, "traffic", self.row["traffic"] + ".json"))
        self.limits = load_json(os.path.join(
            self.data_dir, "limits", workload + ".json"))["numbers"]
        self.flops = self.code("flops", self.config.get("flops", self.row["config"]))
        self.reference = self.code("reference", self.config["model"])
        self.task = self.code("tasks", self.config.get("task", tasks.DEFAULT))

    def code(self, kind, name):
        """A code file of the cell (``flops/``, ``reference/``, ``tasks/``):
        beside its traffic/ and limits/ first, so that a manifest elsewhere
        (the tests' tiny one) can bring its own, else the benchmark's."""
        for root in (self.data_dir, HERE):
            path = os.path.join(root, kind, name + ".py")
            if os.path.isfile(path):
                return load_py(path)
        raise SystemExit(f"no {kind}/{name}.py beside {self.name}'s files "
                         f"or under {HERE}")

    def reports(self, metric):
        return "workloads" not in metric or self.name in metric["workloads"]

    def metrics(self, kind):
        return [m for m in self.manifest[kind] if self.reports(m)]

    @property
    def samples_per_round(self):
        t = self.traffic
        return t["clients"] * t["steps"] * self.config["batch_size"]


class CompileLog:
    """jax.monitoring's compile and cache-load durations, with their times."""

    def __init__(self):
        import jax.monitoring

        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if "backend_compile" in event or "cache_retrieval" in event:
            self.events.append((time.perf_counter(), event, float(duration)))

    def between(self, lo, hi):
        return [e for e in self.events if lo <= e[0] < hi]


def percentile(values, q):
    xs = sorted(values)
    pos = (len(xs) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def require_chips(chips, say):
    import jax

    if jax.default_backend() != "tpu":
        say(f"benchmark: JAX's backend is {jax.default_backend()!r}, not a TPU; "
            "no number is measured anywhere else")
        raise SystemExit(2)
    if len(jax.devices()) < chips:
        say(f"benchmark: the cell asks for {chips} chips, JAX sees "
            f"{len(jax.devices())}")
        raise SystemExit(2)


def place_cache():
    """JAX's persistent compilation cache, where the environment says or at
    ``<checkout>/.jax_cache``; every program goes in, the sub-second ones
    too, so that a warm run's set-up compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_bytes(stats):
    """The most a chip held, from its allocator's statistics. The TPU runtime
    keeps a loaded program's scratch in a reserved pool (``bytes_reserved``)
    apart from the buffers (``bytes_in_use``), so ``peak_bytes_in_use`` alone
    misses the local step's activations: the reading is the larger of that
    peak and the two pools together as they stand after the window."""
    return max(stats.get("peak_bytes_in_use", 0),
               stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0))


def device_peaks(kind):
    table = load_json(os.path.join(HERE, "peaks.json"))["device_kinds"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in benchmark/peaks.json; "
                         "add its published peaks there")
    return table[kind]


def run(manifest_path, workload, seed, seconds, trace, need_tpu=True,
        out=print, say=lambda s: print(s, file=sys.stderr)):
    cell = Cell(manifest_path, workload)
    stamps = {"start": _T0 if need_tpu else time.perf_counter()}
    import jax
    import numpy as np

    stamps["import"] = time.perf_counter()
    if need_tpu:
        require_chips(cell.chips, say)
        place_cache()
    devices = jax.devices()[: cell.chips]
    peaks = device_peaks(devices[0].device_kind) if need_tpu else None
    compiles = CompileLog()
    stamps["backend"] = time.perf_counter()

    from benchmark import check, sut, trace_reduce

    inputs = check.seeded_inputs(cell, seed)
    stamps["data"] = time.perf_counter()

    fed = sut.build(cell, inputs)
    stamps["build"] = time.perf_counter()

    def one_round(spans=False):
        """A federated round as its caller sees it: dispatched, finished, its
        loss read. Returns (seconds, loss)."""
        note = jax.profiler.TraceAnnotation if spans else (
            lambda _: contextlib.nullcontext())
        t0 = time.perf_counter()
        with note("dispatch"):
            m = sut.step(fed)
        with note("sync"):
            jax.block_until_ready(m.loss)
        with note("record.read"):
            loss = float(np.asarray(m.loss))
        return time.perf_counter() - t0, loss

    # The first rounds: they compile, warm up and are what `correct` compares.
    n_check = cell.traffic["check_rounds"]
    program = check.first_rounds(
        fed, n_check, lambda: one_round()[1],
        lambda: stamps.__setitem__("first_round", time.perf_counter()))
    stamps["warm"] = time.perf_counter()
    if trace:
        one_round(spans=True)  # the annotated path, once, outside the window
    gc.collect()
    gc.disable()
    setup_end = time.perf_counter()
    setup_s = setup_end - stamps["start"]

    # ------------------------------------------------------------ the window
    round_s, losses, traced, traced_rounds = [], [], None, 0
    with tempfile.TemporaryDirectory() as trace_dir:
        tracing = bool(trace)
        if tracing:
            # Device operations and the harness's spans; no Python call trace.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        w0 = time.perf_counter()
        while time.perf_counter() - w0 < seconds:
            dt, loss = one_round(spans=tracing)
            round_s.append(dt)
            losses.append(loss)
            if tracing and len(round_s) >= 2 and (
                    time.perf_counter() - w0 >= min(TRACE_SECONDS, seconds)):
                jax.profiler.stop_trace()
                tracing, traced_rounds = False, len(round_s)
                traced = trace_reduce.reduce_trace(
                    trace_reduce.load_xplane(trace_dir))
                # Writing the trace out is not the program's time: the rest
                # of the window starts now.
                seconds -= time.perf_counter() - w0
                w0 = time.perf_counter()
        w1 = time.perf_counter()
    gc.enable()
    elapsed = sum(round_s) if trace else w1 - w0
    rate = len(round_s) * cell.samples_per_round / elapsed / cell.chips
    in_window = compiles.between(setup_end, w1)
    mem = max(((d.memory_stats() or {}) for d in devices), key=device_bytes)
    peak_bytes = device_bytes(mem)
    failed = sum(1 for x in losses + program["losses"] if not np.isfinite(x))

    out("setup_items " + " ".join(
        f"{b}={stamps[b] - stamps[a]:.3f}" for a, b in zip(
            ["start", "import", "backend", "data", "build", "first_round"],
            ["import", "backend", "data", "build", "first_round", "warm"])))
    out(f"window rounds={len(round_s)} seconds={elapsed:.4f} "
        f"median_round_ms={1e3 * percentile(round_s, 0.5):.4f} "
        f"p95_round_ms={1e3 * percentile(round_s, 0.95):.4f} "
        f"max_round_ms={1e3 * max(round_s):.4f} "
        f"samples_per_round={cell.samples_per_round} "
        f"compiles_in_window={len(in_window)} last_loss={losses[-1]:.5f}")

    out("memory " + " ".join(f"{k}={v}" for k, v in sorted(mem.items())))

    # ------------------------------------------- the check, after the window
    del fed
    sut.release()
    t_ref = time.perf_counter()
    nums, reference = check.against_reference(cell, seed, inputs, devices, program)
    out(f"check rounds={n_check} reference_s={time.perf_counter() - t_ref:.2f} "
        f"program_losses={program['losses']} "
        f"reference_losses={reference['losses']}")
    correct = check.verdict(nums, cell.limits, out) and failed == 0

    # --------------------------------------------------------- the result line
    ctx = {
        "cell": cell, "chips": cell.chips, "trace": traced, "rate": rate,
        "peaks": peaks, "memory_peak_bytes": peak_bytes,
        "window_s": elapsed, "rounds": len(round_s), "traced_rounds": traced_rounds,
        "compile_setup_s": sum(e[2] for e in compiles.between(0, setup_end)),
        "compiles_in_window": len(in_window),
    }
    metrics = {}
    if trace:
        refusal = ("the trace holds no device operation or no span"
                   if traced is None else trace_reduce.stale_scopes(traced))
        if refusal:
            say("benchmark: " + refusal)
            raise SystemExit(3)
        for m in cell.metrics("per_layer"):
            value = load_py(os.path.join(
                HERE, "layer_metrics", m["name"] + ".py")).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {
            "samples_per_s_per_chip": rate,
            "round_ms_p95": 1e3 * percentile(round_s, 0.95),
            "setup_s": setup_s,
        }
        for m in cell.metrics("end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": int(peak_bytes),
    }
    result = {"correct": bool(correct), "attempted": len(round_s) + n_check,
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"], device["window_s"] = traced["busy_s"], traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    out(json.dumps(result))
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    run(os.path.join(ROOT, "BENCHMARK.json"), args.workload, args.seed,
        args.seconds, bool(args.trace))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
