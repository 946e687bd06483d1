"""Performance observatory (fedtpu.obs.profile + tools): MFU/roofline
accounting, compile observability, device-trace fusion and idle-gap
attribution.

Everything here is tier-1 cheap: pure-python math on synthetic inputs, a
few tiny jit compiles and one tiny-engine round. The full bench legs
(``--mfu-profile``, ``--mfu-microbench``) re-run as ``slow`` in
tests/test_bench.py; their committed artifacts are contract-checked here.
"""

import json
import os
import sys

import pytest

from fedtpu.obs import Telemetry, parse_prometheus_text, prometheus_text
from fedtpu.obs.profile import (
    CompileWatcher,
    CostModel,
    RoundProfiler,
    analytic_flops,
    device_peaks,
    latency_summary,
    parse_round_window,
    roofline,
    write_profile_meta,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import gap_analyze  # noqa: E402
import span_check  # noqa: E402
import trace_merge  # noqa: E402


# ------------------------------------------------------------ peaks/roofline
def test_device_peaks_table_and_cpu_stand_ins(monkeypatch):
    monkeypatch.delenv("FEDTPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("FEDTPU_PEAK_HBM_BYTES", raising=False)
    assert device_peaks("TPU v5 lite") == (197e12, 819e9)
    assert device_peaks("TPU v5e") == (197e12, 819e9)
    assert device_peaks("TPU v4") == (275e12, 1228e9)
    assert device_peaks("TPU v6e")[0] == 918e12
    assert device_peaks("cpu") == (None, None)
    assert device_peaks("") == (None, None)
    # The env stand-ins serve a backend with no peaks of its own ...
    monkeypatch.setenv("FEDTPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("FEDTPU_PEAK_HBM_BYTES", "5e10")
    assert device_peaks("cpu") == (1e12, 5e10)
    # ... and never displace a table row.
    assert device_peaks("TPU v4") == (275e12, 1228e9)


def test_device_peaks_unknown_kind_raises_on_tpu(monkeypatch):
    """On a TPU backend a device the table does not know is an error, not
    a silently dropped MFU — and no env stand-in rescues it."""
    import jax

    monkeypatch.setenv("FEDTPU_PEAK_FLOPS", "1e12")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="PEAK_TABLE"):
        device_peaks("TPU v9 hypothetical")
    assert device_peaks("TPU v5 lite") == (197e12, 819e9)


def test_roofline_classification():
    # High arithmetic intensity -> compute-bound; utilization vs peak flops.
    r = roofline(1e12, 1e9, 2e14, 1e12, achieved_flops_per_s=1e14)
    assert r["roofline_bound"] == "compute"
    assert r["arith_intensity_flops_per_byte"] == 1000.0
    assert r["ridge_point_flops_per_byte"] == 200.0
    assert r["roofline_utilization"] == pytest.approx(0.5)
    # Low intensity -> bandwidth-bound; ceiling = peak_bw * intensity.
    r = roofline(1e9, 1e9, 2e14, 1e12, achieved_flops_per_s=5e11)
    assert r["roofline_bound"] == "bandwidth"
    assert r["roofline_utilization"] == pytest.approx(0.5)
    # Schema-stable on missing inputs: keys present, values None.
    r = roofline(None, None, None, None)
    assert set(r) == {
        "arith_intensity_flops_per_byte", "ridge_point_flops_per_byte",
        "roofline_bound", "roofline_utilization",
    }
    assert all(v is None for v in r.values())


def test_analytic_flops_agrees_with_xla_on_matmul():
    import jax
    import jax.numpy as jnp

    def f(a, b):
        return a @ b

    a = jnp.ones((32, 48), jnp.float32)
    b = jnp.ones((48, 16), jnp.float32)
    expect = 2 * 32 * 48 * 16
    got = analytic_flops(f, a, b)
    assert got == expect
    an = jax.jit(f).lower(a, b).compile().cost_analysis()
    xla = float(an.get("flops", 0.0))
    if xla:  # cost analysis availability varies by backend
        assert got == pytest.approx(xla, rel=0.05)


def test_analytic_bytes_sees_dtype_and_skips_layout_ops():
    """analytic_bytes is the backend-independent byte model behind the
    mixed-precision microbench: fusion-group boundary bytes at the STATED
    aval dtypes (so bf16 halves traffic even where a CPU backend would
    emulate in f32), with pure layout ops (reshape/broadcast/transpose)
    free."""
    import jax.numpy as jnp

    from fedtpu.obs.profile import analytic_bytes

    def f(a, b):
        return a @ b

    a32 = jnp.ones((64, 128), jnp.float32)
    b32 = jnp.ones((128, 32), jnp.float32)
    got = analytic_bytes(f, a32, b32)
    # in (64*128 + 128*32) + out (64*32), 4 bytes each.
    assert got == (64 * 128 + 128 * 32 + 64 * 32) * 4
    a16, b16 = a32.astype(jnp.bfloat16), b32.astype(jnp.bfloat16)
    assert analytic_bytes(f, a16, b16) == got / 2

    def g(a, b):
        # The reshape/broadcast shuffle must add NOTHING over f.
        return a.reshape(64, 128) @ jnp.broadcast_to(b, b.shape)

    assert analytic_bytes(g, a32.reshape(128, 64), b32) == got


def test_analytic_bytes_fuses_elementwise_chains():
    """The model charges fusion-GROUP boundaries, not per-eqn I/O: a chain
    of elementwise ops is one pass over the data (intermediates are
    register traffic), and a reduction fuses with its producers but its
    output materializes. Without this, the f32 intermediates of e.g. a
    BatchNorm statistics chain would be charged at 5x activation size —
    biasing the model against the bf16 residency lever it exists to
    measure (tests the rationale in fedtpu/obs/profile.py)."""
    import jax.numpy as jnp

    from fedtpu.obs.profile import analytic_bytes

    a = jnp.ones((256, 128), jnp.float32)
    b = jnp.ones((256, 128), jnp.float32)

    def chain(a, b):
        return jnp.exp(a) * b + a

    # ONE group: reads {a, b}, writes {out} — the exp/mul intermediates
    # never count, and a's two uses inside the group charge once.
    n = 256 * 128 * 4
    assert analytic_bytes(chain, a, b) == 3 * n

    def stat(a):
        # square-then-reduce (the BN statistics shape): the reduce fuses
        # with its producers, so the whole chain is reads {a} + the tiny
        # reduced write.
        return jnp.square(a).sum(axis=0)

    assert analytic_bytes(stat, a) == n + 128 * 4

    def reduce_then_use(a):
        # A reduction OUTPUT materializes: its consumer starts a new pass,
        # re-reading both the reduced row and the full input.
        s = a.sum(axis=0)
        return a * s

    # group1 {sum}: read a, write s; group2 {mul}: read a + s, write out.
    assert analytic_bytes(reduce_then_use, a) == 2 * n + 2 * (128 * 4) + n


def test_cost_model_carries_analytic_bytes():
    cm = CostModel(xla_flops=1e10, xla_bytes=1e9, analytic=1.0e10,
                   analytic_bytes=8e8)
    assert cm.analytic_bytes == 8e8
    assert cm.as_dict()["analytic_bytes_per_round"] == 8e8
    # Optional: absent stays schema-stable None.
    cm = CostModel(xla_flops=None, xla_bytes=None, analytic=5e9)
    assert cm.analytic_bytes is None
    assert cm.as_dict()["analytic_bytes_per_round"] is None


# ----------------------------------------------------------- round profiler
def test_round_profiler_gauges_and_record_fields(monkeypatch):
    monkeypatch.setenv("FEDTPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("FEDTPU_PEAK_HBM_BYTES", "5e10")
    tel = Telemetry("basic")
    prof = RoundProfiler(tel, n_devices=2, device_kind="cpu")
    # Before a cost model: step-time only; no MFU stamps on records.
    out = prof.observe_round(0.5)
    assert out["step_time_s"] == 0.5
    assert out["achieved_flops_per_s"] is None and out["mfu"] is None
    assert prof.record_fields() == {}
    prof.set_cost_model(
        CostModel(xla_flops=1.01e10, xla_bytes=1e9, analytic=1e10)
    )
    out = prof.observe_round(0.5, rounds=5)
    assert out["step_time_s"] == pytest.approx(0.1)
    assert out["achieved_flops_per_s"] == pytest.approx(1e11)
    # MFU normalizes by ALL devices: 1e11 / (2 * 1e12).
    assert out["mfu"] == pytest.approx(0.05)
    fields = prof.record_fields()
    assert fields["mfu"] == pytest.approx(0.05)
    assert fields["achieved_flops_per_s"] == pytest.approx(1e11)
    parsed = parse_prometheus_text(prometheus_text(tel.registry))
    assert parsed["fedtpu_mfu_ratio"][""] == pytest.approx(0.05)
    assert parsed["fedtpu_step_time_seconds"][""] == pytest.approx(0.1)
    assert parsed["fedtpu_achieved_flops_per_sec"][""] == pytest.approx(1e11)
    snap = prof.snapshot()
    assert snap["mfu"] == pytest.approx(0.05)
    assert snap["flops_source"] == "analytic"
    # Roofline keys merge flat into the /statusz perf block: intensity
    # 10 FLOP/B vs ridge 20 -> bandwidth-bound; per-chip achieved 5e10
    # against a 5e11 ceiling at that intensity.
    assert snap["roofline_bound"] == "bandwidth"
    assert snap["roofline_utilization"] == pytest.approx(0.1)


def test_cost_model_prefers_analytic_and_reports_agreement():
    """XLA counts a scan body once, the analytic walk times its length:
    the gauge is priced with the walk, XLA's count is the cross-check."""
    cm = CostModel(xla_flops=1e10, xla_bytes=1e9, analytic=2.04e10)
    assert cm.flops == 2.04e10 and cm.source == "analytic"
    assert cm.agreement == pytest.approx(2.04)
    d = cm.as_dict()
    assert d["flops_source"] == "analytic"
    assert d["analytic_vs_xla"] == pytest.approx(2.04)
    cm = CostModel(xla_flops=5e9, xla_bytes=None, analytic=None)
    assert cm.flops == 5e9 and cm.source == "xla"
    assert cm.agreement is None


def test_analytic_flops_multiplies_scan_lengths():
    import jax
    import jax.numpy as jnp

    a = jnp.ones((8, 16), jnp.float32)
    b = jnp.ones((16, 16), jnp.float32)

    def steps(a, b):
        return jax.lax.scan(lambda c, _: (c @ b, None), a, None, length=6)[0]

    assert analytic_flops(steps, a, b) == 6 * 2 * 8 * 16 * 16


def test_engine_round_records_and_statusz_carry_mfu(monkeypatch):
    """Acceptance: per-round MFU lands on v1 round records and /statusz
    when accounting is enabled — at a seconds-scale engine config."""
    monkeypatch.setenv("FEDTPU_PEAK_FLOPS", "1e12")
    from fedtpu.config import DataConfig, FedConfig, RoundConfig
    from fedtpu.core.engine import Federation

    cfg = RoundConfig(
        model="mlp", num_classes=10,
        data=DataConfig(dataset="synthetic", batch_size=8, num_examples=64),
        fed=FedConfig(num_clients=2, num_rounds=2, telemetry="basic"),
        steps_per_round=1,
    )
    fed = Federation(cfg, seed=0)
    fed.enable_mfu_accounting(xla_check=False)
    assert fed.profiler is not None and fed.profiler.cost is not None

    recs = []

    class _Recorder:
        def log(self, r, **rec):
            recs.append(rec)

    fed.run(num_rounds=2, logger=_Recorder())
    assert len(recs) == 2
    for rec in recs:
        assert rec["mfu"] > 0
        assert rec["achieved_flops_per_s"] > 0
    snap = fed.status_snapshot()
    assert snap["perf"]["mfu"] > 0
    assert snap["perf"]["flops_per_round"] > 0

    # The wall handed to the profiler ends when the DEVICE has finished, not
    # at the (asynchronous) enqueue — the first run on a v5e reported an
    # MFU of 6.27 from enqueue walls. An unarmed engine stays asynchronous.
    import jax

    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready", lambda x: (waits.append(1), real(x))[1]
    )
    fed.run_on_device(2)
    assert waits
    fed.profiler = None
    waits.clear()
    fed.run_on_device(2)
    assert not waits


# -------------------------------------------------------- latency summary
def test_latency_summary_percentiles_and_slowest():
    assert latency_summary([]) == {}
    pairs = [(f"c{i}", (i + 1) / 100.0) for i in range(100)]
    lat = latency_summary(pairs)
    assert lat["n"] == 100
    assert lat["p50_s"] == pytest.approx(0.50)
    assert lat["p95_s"] == pytest.approx(0.95)
    assert lat["p99_s"] == pytest.approx(0.99)
    assert lat["max_s"] == pytest.approx(1.00)
    assert [c for c, _s in lat["slowest"]] == ["c99", "c98", "c97"]
    # Fewer clients than top-k: everyone listed, worst first.
    lat = latency_summary([("a", 0.2), ("b", 0.7)])
    assert lat["p50_s"] == pytest.approx(0.2)
    assert [c for c, _s in lat["slowest"]] == ["b", "a"]


# ------------------------------------------------------- compile watcher
def test_compile_watcher_counts_and_flags_steady_recompiles():
    import jax
    import jax.numpy as jnp

    tel = Telemetry("basic")
    watcher = CompileWatcher(telemetry=tel)
    watcher.install()
    try:
        # Second concurrent watcher is a bug, not a silent double-count.
        with pytest.raises(RuntimeError):
            CompileWatcher().install()
        jax.jit(lambda x: x * 2 + 1)(jnp.ones((7, 3))).block_until_ready()
        snap = watcher.snapshot()
        assert snap["compiles"] >= 1
        assert snap["compile_seconds"] > 0
        assert snap["steady"] is False
        assert snap["recompiles_after_steady"] == 0
        watcher.mark_steady()
        before = watcher.snapshot()["compiles"]
        # A fresh shape after steady state = the recompile failure mode.
        jax.jit(lambda x: x * 2 + 1)(jnp.ones((3, 7))).block_until_ready()
        snap = watcher.snapshot()
        assert snap["steady"] is True
        assert snap["compiles"] > before
        assert snap["recompiles_after_steady"] >= 1
        parsed = parse_prometheus_text(prometheus_text(tel.registry))
        assert parsed["fedtpu_xla_compiles_total"][""] == snap["compiles"]
        # The recompile counter is labelled by the program that recompiled.
        assert (sum(parsed["fedtpu_xla_recompiles_steady_total"].values())
                == snap["recompiles_after_steady"])
    finally:
        watcher.uninstall()
    # Uninstalled: a new watcher can install again.
    w2 = CompileWatcher()
    w2.install()
    w2.uninstall()


def test_compile_watcher_names_the_program_that_recompiled(caplog):
    """A compile after mark_steady() is reported with jax's ``fun_name``:
    in the warning, the flight record, the counter's label and the
    snapshot's ``recompiled`` list."""
    import jax
    import jax.numpy as jnp

    from fedtpu.obs import FlightRecorder

    tel = Telemetry("basic")
    flight = FlightRecorder(role="test")
    watcher = CompileWatcher(telemetry=tel, flight=flight).install()
    try:
        def drifting_program(x, scale):
            return x * scale

        step = jax.jit(drifting_program, static_argnums=1)
        step(jnp.ones(5), 2.0).block_until_ready()
        watcher.mark_steady()
        assert watcher.snapshot()["recompiled"] == []
        with caplog.at_level("WARNING", logger="fedtpu.obs.profile"):
            step(jnp.ones(5), 3.0).block_until_ready()  # a static drifted
    finally:
        watcher.uninstall()
    snap = watcher.snapshot()
    assert snap["recompiles_after_steady"] == 1
    (row,) = snap["recompiled"]
    name = "jit(drifting_program)"  # as jax names the compiled module
    assert row["fun_name"] == name and row["seconds"] >= 0
    assert f"steady-state XLA recompile of {name}" in caplog.text
    parsed = parse_prometheus_text(prometheus_text(tel.registry))
    assert parsed["fedtpu_xla_recompiles_steady_total"] == {
        f"fun_name={name}": 1.0}
    records = [e for e in flight.snapshot() if e["kind"] == "xla_recompile"]
    assert [e["fun_name"] for e in records] == [name]


def test_compile_watcher_keeps_the_last_few_recompiles_only():
    from fedtpu.obs.profile import BACKEND_COMPILE_EVENT, RECOMPILES_KEPT

    watcher = CompileWatcher()
    watcher._installed = True  # the listener alone, no jax registration
    watcher.mark_steady()
    for i in range(RECOMPILES_KEPT + 3):
        watcher._listener(BACKEND_COMPILE_EVENT, 0.5, fun_name=f"p{i}")
    # Other durations of jax's are not compiles; a compile without a name
    # (an older jax) is still counted.
    watcher._listener("/jax/core/compile/jaxpr_trace_duration", 9.0,
                      fun_name="traced")
    watcher._listener(BACKEND_COMPILE_EVENT, 0.25)
    snap = watcher.snapshot()
    assert snap["compiles"] == snap["recompiles_after_steady"] == (
        RECOMPILES_KEPT + 4)
    assert [r["fun_name"] for r in snap["recompiled"]] == [
        f"p{i}" for i in range(4, RECOMPILES_KEPT + 3)] + ["unknown"]


# ------------------------------------------------------- capture windows
def test_parse_round_window():
    assert parse_round_window("3:7") == (3, 7)
    assert parse_round_window("5") == (5, 6)
    assert parse_round_window(" 0:2 ") == (0, 2)
    for bad in ("", "a:b", "4:", "7:3", "-1:2"):
        with pytest.raises(ValueError):
            parse_round_window(bad)


def test_profile_meta_sidecar_roundtrip(tmp_path):
    d = str(tmp_path / "trace")
    write_profile_meta(d, role="engine", trace_id="abc123",
                       extra={"round_window": [1, 3]})
    with open(os.path.join(d, "profile_meta.json")) as fh:
        meta = json.load(fh)
    assert meta["role"] == "engine"
    assert meta["trace_id"] == "abc123"
    assert meta["round_window"] == [1, 3]
    assert meta["wall_start"] > 0
    assert meta["format"] == "jax.profiler"


# ------------------------------------------- trace_merge device ingestion
def _tpu_device_doc(wall_start=None):
    """Synthetic load_device_trace-shaped Chrome doc: device lanes are
    processes whose name carries '/device:'."""
    events = [
        {"ph": "M", "name": "process_name", "pid": 10,
         "args": {"name": "/device:TPU:0 (fake)"}},
        {"ph": "M", "name": "process_name", "pid": 11,
         "args": {"name": "host threads"}},
        {"ph": "X", "pid": 10, "tid": 1, "name": "fusion.1",
         "ts": 100.0, "dur": 50.0},
        {"ph": "X", "pid": 10, "tid": 1, "name": "fusion.2",
         "ts": 200.0, "dur": 25.0},
        {"ph": "X", "pid": 11, "tid": 5, "name": "py_thing",
         "ts": 100.0, "dur": 10.0},
    ]
    doc = {"traceEvents": events, "metadata": {"role": "engine"}}
    if wall_start is not None:
        doc["metadata"]["wall_start"] = wall_start
    return doc


def _cpu_device_doc():
    """CPU-backend shape: the capture reader hands the XLA executor's
    operations over as the stand-in process /device:CPU:0."""
    events = [
        {"ph": "M", "name": "process_name", "pid": 0,
         "args": {"name": gap_analyze.CPU_PLANE}},
        {"ph": "X", "pid": 0, "tid": 0, "name": "convolution",
         "ts": 10.0, "dur": 5.0, "args": {"scope": ""}},
    ]
    return {"traceEvents": events, "metadata": {"role": "engine"}}


def _host_doc(wall_start=1000.0):
    return {
        "traceEvents": [
            {"ph": "X", "pid": 1, "tid": 1, "name": "round",
             "ts": 0.0, "dur": 500.0, "args": {"span_id": 1}},
        ],
        "metadata": {"role": "engine", "wall_start": wall_start,
                     "trace_id": "t1", "pid": 123},
    }


def test_extract_device_lanes_tpu_and_cpu_shapes():
    lanes = trace_merge.extract_device_lanes(_tpu_device_doc())
    assert len(lanes) == 1
    name, evs = lanes[0]
    assert "/device:TPU:0" in name
    assert [e["name"] for e in evs] == ["fusion.1", "fusion.2"]
    lanes = trace_merge.extract_device_lanes(_cpu_device_doc())
    assert len(lanes) == 1
    name, evs = lanes[0]
    assert name == gap_analyze.CPU_PLANE
    assert [e["name"] for e in evs] == ["convolution"]
    # No device-looking content at all -> no lanes, no crash.
    assert trace_merge.extract_device_lanes(
        {"traceEvents": [{"ph": "X", "pid": 1, "name": "x", "ts": 0,
                          "dur": 1}]}
    ) == []


def test_merge_docs_fuses_device_lane_with_wall_alignment():
    host = _host_doc(wall_start=1000.0)
    dev = _tpu_device_doc(wall_start=1000.25)  # device session opens 250ms in
    merged = trace_merge.merge_docs([host], device_docs=[dev])
    evs = merged["traceEvents"]
    device_evs = [e for e in evs if e.get("cat") == "device"]
    host_evs = [e for e in evs if e.get("ph") == "X"
                and e.get("cat") != "device"]
    assert len(device_evs) == 2 and len(host_evs) == 1
    # Wall alignment: device ts are shifted onto the host clock.
    f1 = next(e for e in device_evs if e["name"] == "fusion.1")
    assert f1["ts"] == pytest.approx(250000.0 + 100.0)
    # The device lane is its own pid with a named process, after host lanes.
    assert {e["pid"] for e in device_evs} != {e["pid"] for e in host_evs}
    lanes = merged["metadata"]["device_lanes"]
    assert len(lanes) == 1 and "/device:TPU:0" in lanes[0]
    names = [
        e["args"]["name"] for e in evs
        if e.get("ph") == "M" and e.get("name") == "process_name"
    ]
    assert any("/device:TPU:0" in n for n in names)


def test_merge_docs_tolerates_empty_device_trace():
    merged = trace_merge.merge_docs(
        [_host_doc()],
        device_docs=[{"traceEvents": [], "metadata": {}}],
    )
    assert merged["metadata"]["device_lanes"] == []
    assert all(e.get("cat") != "device" for e in merged["traceEvents"])


# --------------------------------------------------------- gap analysis
def _merged_doc_with_gaps():
    """One device lane busy [0,100] and [1100,1200] and [1250,1300] (us):
    a 1000us gap and a 50us gap. Host spans: 'round' covers everything;
    'h2d' (nested) covers [100, 700] — the deepest span over most of the
    big gap."""
    evs = [
        {"ph": "X", "pid": 1, "tid": 1, "name": "round", "ts": 0.0,
         "dur": 1300.0},
        {"ph": "X", "pid": 1, "tid": 1, "name": "h2d", "ts": 100.0,
         "dur": 600.0},
        {"ph": "X", "pid": 9, "tid": 1, "name": "fusion", "cat": "device",
         "ts": 0.0, "dur": 100.0},
        {"ph": "X", "pid": 9, "tid": 1, "name": "fusion", "cat": "device",
         "ts": 1100.0, "dur": 100.0},
        {"ph": "X", "pid": 9, "tid": 1, "name": "fusion", "cat": "device",
         "ts": 1250.0, "dur": 50.0},
    ]
    return {"traceEvents": evs, "metadata": {}}


def test_gap_analyze_ranks_gaps_and_attributes_to_deepest_span():
    report = gap_analyze.analyze(_merged_doc_with_gaps(), min_gap_us=10.0)
    assert report["device_lanes"] == 1
    assert report["n_gaps"] == 2
    assert report["window_us"] == pytest.approx(1300.0)
    assert report["device_busy_us"] == pytest.approx(250.0)
    assert report["idle_fraction"] == pytest.approx(1050.0 / 1300.0, abs=1e-3)
    # Longest gap first.
    top = report["gaps"][0]
    assert top["dur_us"] == pytest.approx(1000.0)
    assert (top["start_us"], top["end_us"]) == (100.0, 1100.0)
    assert report["gaps"][1]["dur_us"] == pytest.approx(50.0)
    # Attribution: the DEEPEST host phase over the gap wins its share —
    # h2d claims [100,700], the enclosing round only the uncovered rest.
    rows = {r["span"]: r["us"] for r in top["attribution"]}
    assert rows["h2d"] == pytest.approx(600.0)
    assert rows["round"] == pytest.approx(400.0)
    assert top["attribution"][0]["span"] == "h2d"  # charged-most first
    assert gap_analyze.CALLER not in rows  # the spans cover the whole gap
    # Aggregate table mirrors the per-gap charges (small gap -> round too).
    by_phase = {r["span"]: r["us"] for r in report["by_phase"]}
    assert by_phase["h2d"] == pytest.approx(600.0)
    assert by_phase["round"] == pytest.approx(450.0)


def test_gap_analyze_charges_uncovered_idle_to_the_caller():
    doc = _merged_doc_with_gaps()
    # Shrink the round span so [900, 1100) of the big gap is uncovered.
    doc["traceEvents"][0]["dur"] = 900.0
    report = gap_analyze.analyze(doc, min_gap_us=10.0)
    rows = {r["span"]: r["us"] for r in report["gaps"][0]["attribution"]}
    assert rows[gap_analyze.CALLER] == pytest.approx(200.0)
    by_phase = {r["span"]: r["us"] for r in report["by_phase"]}
    assert by_phase[gap_analyze.CALLER] == pytest.approx(250.0)


def test_gap_analyze_tolerates_timeline_without_device_ops():
    report = gap_analyze.analyze(_host_doc())
    assert report["device_lanes"] == 0
    assert report["n_gaps"] == 0
    assert report["window_us"] is None
    assert report["device_busy_us"] == 0.0


def test_gap_analyze_roofline_stamp(tmp_path):
    """--roofline: recomputes placement from a profile artifact's
    flops/bytes rows through obs.profile.roofline — the gap report then
    answers both idle attribution AND what the busy time is limited by."""
    profile = {
        "configs": [{
            "batch": 128, "device_kind": "TPU v5 lite",
            "flops_per_round": 276329529344.0,
            "bytes_per_round": 14553602048.0,
            "rounds_per_sec": 9.333, "mfu": 0.0131,
        }]
    }
    path = tmp_path / "profile.json"
    path.write_text(json.dumps(profile))
    stamp = gap_analyze.roofline_stamp(str(path))
    assert stamp["profile_artifact"] == str(path)
    (row,) = stamp["rows"]
    assert row["roofline_bound"] == "bandwidth"
    assert row["arith_intensity_flops_per_byte"] == pytest.approx(
        18.99, abs=0.01)
    assert row["ridge_point_flops_per_byte"] == pytest.approx(
        240.54, abs=0.01)
    # Achieved rate present -> utilization filled (the r04 hbm_util ~0.166).
    assert row["roofline_utilization"] == pytest.approx(0.166, abs=0.01)
    # Flat dict (microbench analytic row) also accepted; no achieved rate
    # -> utilization stays None.
    flat = tmp_path / "flat.json"
    flat.write_text(json.dumps({
        "flops_per_round": 1e9, "bytes_per_round": 1e9,
        "device_kind": "TPU v5 lite",
    }))
    (frow,) = gap_analyze.roofline_stamp(str(flat))["rows"]
    assert frow["roofline_bound"] == "bandwidth"
    assert frow["roofline_utilization"] is None


def test_gap_report_contract_on_the_recorded_tpu_capture():
    """The report's schema, on a capture a TPU v5e really wrote (three
    rounds of sim192_rotq4's program, tests/data/): what the committed
    densenet CPU GAP_REPORT.json used to stand for."""
    path = os.path.join(REPO, "tests", "data", "capture_v5e_sim192_rotq4.json")
    with open(path) as fh:
        events = json.load(fh)["events"]
    report = gap_analyze.reduce_events(events)
    assert report["schema_version"] == gap_analyze.SCHEMA_VERSION
    assert report["chips"] == ["/device:TPU:0"] and report["device_lanes"] == 1
    assert report["device_ops"] == 1683
    assert 0.0 < report["idle_fraction"] < 0.01
    assert report["n_gaps"] == 2  # between the three rounds
    for gap in report["gaps"]:
        assert gap["dur_us"] >= report["min_gap_us"]
        assert sum(r["us"] for r in gap["attribution"]) == pytest.approx(
            gap["dur_us"], abs=0.01)


# ------------------------------------------------------- metric-name drift
def test_span_check_polices_metric_names(tmp_path):
    # Tier-1 enforcement for the real tree: every emitted fedtpu_* metric
    # is documented (the span half is asserted in test_obs_propagation).
    assert span_check.check_metrics() == []
    # Drift detection: an undocumented metric in a synthetic package.
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        'tel.gauge("fedtpu_fake_metric", "help").set(1)\n'
        'tel.counter("fedtpu_documented_total").inc()\n'
    )
    doc = tmp_path / "OBS.md"
    doc.write_text("| `fedtpu_documented_total` | fine |\n")
    problems = span_check.check_metrics(str(pkg), str(doc))
    assert len(problems) == 1
    assert "fedtpu_fake_metric" in problems[0]
    # Labeled doc mentions document the base name.
    doc.write_text("`fedtpu_documented_total` `fedtpu_fake_metric{x=\"y\"}`")
    assert span_check.check_metrics(str(pkg), str(doc)) == []


def test_mfu_microbench_committed_gate():
    """The committed densenet-scale artifact must actually pass the <=1%
    gate: per-round MFU accounting cost over the bare round wall."""
    path = os.path.join(REPO, "artifacts", "MFU_ACCOUNTING_MICROBENCH.json")
    assert os.path.exists(path), "MFU_ACCOUNTING_MICROBENCH.json missing"
    with open(path) as fh:
        result = json.load(fh)
    assert result["metric"] == "mfu_accounting_overhead"
    assert result["model"] == "densenet_cifar"
    assert result["passes_gate"] is True
    assert result["value"] <= 1.0
    assert result["flops_per_round"] > 0
