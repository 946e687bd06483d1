"""Device time by layer, from one capture: the round program names its
layers (``jax.named_scope``), the engine's host spans reach a profiler
session in the default telemetry mode, and ``tools/gap_analyze.py`` reduces a
capture (a recorded TPU v5e one, and hand-made ones) to time by scope,
collective time exposed and hidden, and idle time by host phase.
"""

import gzip
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
from fedtpu.core import Federation
from fedtpu.obs import Telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import gap_analyze  # noqa: E402
import trace_merge  # noqa: E402

FIXTURE = os.path.join(REPO, "tests", "data", "capture_v5e_sim192_rotq4.json")


def _federation(clients=4, mesh=None, telemetry="basic", **fed):
    cfg = RoundConfig(
        model="smallcnn",
        num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05),
        data=DataConfig(dataset="cifar10", batch_size=4, partition="iid",
                        num_examples=64, augment=False),
        fed=FedConfig(num_clients=clients, telemetry=telemetry, **fed),
        steps_per_round=2,
    )
    return Federation(cfg, seed=0, mesh=mesh)


# ------------------------------------------------ (a) scopes in the program
EVERY_ROUND = {
    "fed.data", "fed.local_step", "fed.local_step.fwd_bwd",
    "fed.local_step.optimizer", "fed.aggregate", "fed.server_step",
    "fed.metrics",
}
FLAT = {"fed.pack", "fed.unpack", "fed.codec", "fed.codec.feedback"}
VARIANTS = {
    "tree_no_codec": ({}, EVERY_ROUND),
    "flat_rotq4_feedback": (
        dict(delta_layout="flat", compression="rotq", rotq_bits=4,
             error_feedback=True),
        EVERY_ROUND | FLAT | {"fed.codec.rotate", "fed.codec.quantize"},
    ),
    "flat_topk": (
        dict(delta_layout="flat", compression="topk", topk_fraction=0.1),
        EVERY_ROUND | FLAT | {"fed.codec.select"},
    ),
    "client_mesh4": ({}, EVERY_ROUND | {"fed.aggregate.psum"}),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_round_program_names_its_layers(variant, eight_devices):
    """Every scope promised for the variant is in the compiled module's
    ``op_name`` metadata, also under the names the transformations give
    it (``vmap(...)``, ``transpose(jvp(...))``)."""
    fed_kwargs, promised = VARIANTS[variant]
    mesh = None
    if variant == "client_mesh4":
        from fedtpu.parallel import client_mesh

        mesh = client_mesh(4)
    fed = _federation(mesh=mesh, **fed_kwargs)
    data = fed._ensure_device_data()
    alive = fed._placed(np.ones((4,), bool), sharded=True)
    hlo = fed._data_step.lower(
        fed.state, *data, fed.weights, alive, fed._data_key
    ).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    found = {s for name in op_names for s in gap_analyze.SCOPE.findall(name)}
    assert promised <= found, sorted(promised - found)
    assert any("vmap(fed.local_step)" in n for n in op_names)
    assert any(
        "transpose(jvp(" in n
        and gap_analyze.scope_of(n) == "fed.local_step.fwd_bwd"
        for n in op_names
    )
    if variant == "client_mesh4":
        reduces = [n for n in op_names if n.endswith("/psum")]
        assert reduces and all(
            gap_analyze.scope_of(n) == "fed.aggregate.psum" for n in reduces
        )


def test_remat_keeps_the_scope():
    """Per-block remat wraps the forward in ``checkpoint``: the recompute
    still passes through the local step's scope."""
    @jax.jit
    def step(x):
        with jax.named_scope("fed.local_step.fwd_bwd"):
            return jax.grad(lambda y: jax.checkpoint(jax.numpy.sin)(y).sum())(x)

    hlo = step.lower(np.ones((8,), np.float32)).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    remat = [n for n in names if "checkpoint" in n or "remat" in n]
    assert remat and all(
        gap_analyze.scope_of(n) == "fed.local_step.fwd_bwd" for n in remat
    )


# --------------------------------------- (b) host spans reach the profiler
def _capture_two_steps(tmp_path, telemetry):
    fed = _federation(clients=2, telemetry=telemetry)
    fed.step()  # compile outside the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            jax.block_until_ready(fed.step().loss)
    finally:
        jax.profiler.stop_trace()
    return fed


def test_engine_spans_reach_a_profiler_session_by_default(tmp_path):
    fed = _capture_two_steps(tmp_path, "basic")
    assert fed.telemetry.tracer is None  # nothing is kept in memory
    spans = [e for e in gap_analyze.load_capture(str(tmp_path))
             if e["name"].startswith("fed.")]
    assert [e["name"] for e in sorted(spans, key=lambda e: e["start_ns"])] == [
        "fed.round", "fed.plan", "fed.enqueue"] * 2

    def inside(child, parent):
        return (parent["start_ns"] <= child["start_ns"] and
                child["start_ns"] + child["dur_ns"]
                <= parent["start_ns"] + parent["dur_ns"])

    rounds = [e for e in spans if e["name"] == "fed.round"]
    for rnd in rounds:
        plan, enqueue = [
            next(e for e in spans if e["name"] == name and inside(e, rnd))
            for name in ("fed.plan", "fed.enqueue")
        ]
        assert plan["start_ns"] + plan["dur_ns"] <= enqueue["start_ns"]
    # fed.round is a step annotation carrying the host-tracked round.
    with gzip.open(gap_analyze.find_capture(str(tmp_path)), "rt") as fh:
        raw = json.load(fh)["traceEvents"]
    steps = sorted(int(e["args"]["step_num"]) for e in raw
                   if e.get("name") == "fed.round")
    assert steps == [1, 2]
    # The same capture carries the device side: one clock, no alignment.
    report = gap_analyze.analyze_capture(str(tmp_path))
    assert report["chips"] == [gap_analyze.CPU_PLANE]
    assert report["device_ops"] > 0
    # trace_merge --device-trace goes through the same reader.
    (lane, ops), = trace_merge.extract_device_lanes(
        trace_merge.load_device_trace(str(tmp_path)))
    assert lane == gap_analyze.CPU_PLANE and len(ops) == report["device_ops"]


def test_engine_spans_off_mode_leaves_none(tmp_path):
    _capture_two_steps(tmp_path, "off")
    assert not [e for e in gap_analyze.load_capture(str(tmp_path))
                if e["name"].startswith("fed.")]


def test_a_session_over_a_build_holds_the_set_up_spans(tmp_path):
    """A session opened BEFORE the engine is built (what a round-0
    ``--profile-rounds`` window does) records set-up's phases on the device
    operations' clock, nested as the program nests them."""
    cfg = RoundConfig(
        model="mlp", num_classes=10, opt=OptimizerConfig(learning_rate=0.05),
        data=DataConfig(dataset="synthetic", batch_size=4, partition="iid",
                        num_examples=64),
        fed=FedConfig(num_clients=2), steps_per_round=2,
    )
    jax.profiler.start_trace(str(tmp_path))
    try:
        fed = Federation(cfg, seed=0)
        jax.block_until_ready(fed.step().loss)
        jax.block_until_ready(fed.step().loss)
    finally:
        jax.profiler.stop_trace()
    spans = [e for e in gap_analyze.load_capture(str(tmp_path))
             if e["name"].startswith("fed.")]
    names = [e["name"] for e in spans]
    for name in ("fed.setup.build", "fed.setup.build.partition",
                 "fed.setup.build.init_state", "fed.setup.build.programs",
                 "fed.setup.place_state", "fed.setup.first_dispatch",
                 "fed.setup.first_dispatch.device_data",
                 "fed.setup.first_dispatch.device_data.host",
                 "fed.setup.first_dispatch.device_data.h2d"):
        assert names.count(name) == 1, name
    assert names.count("fed.round") == names.count("fed.enqueue") == 2

    def inside(child, parent):
        return (parent["start_ns"] <= child["start_ns"] and
                child["start_ns"] + child["dur_ns"]
                <= parent["start_ns"] + parent["dur_ns"])

    one = {e["name"]: e for e in spans if e["name"].startswith("fed.setup.")}
    for child in ("partition", "init_state", "programs"):
        assert inside(one[f"fed.setup.build.{child}"], one["fed.setup.build"])
    first_round = min((e for e in spans if e["name"] == "fed.round"),
                      key=lambda e: e["start_ns"])
    assert inside(one["fed.setup.first_dispatch"], first_round)
    assert inside(one["fed.setup.first_dispatch.device_data"],
                  one["fed.setup.first_dispatch"])
    for name in ("fed.plan", "fed.enqueue"):
        first = min((e for e in spans if e["name"] == name),
                    key=lambda e: e["start_ns"])
        assert inside(first, one["fed.setup.first_dispatch"])
    # The device's side of set-up is in the same capture.
    assert gap_analyze.analyze_capture(str(tmp_path))["device_ops"] > 0


def test_basic_span_keeps_nothing_without_a_session():
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    tel = Telemetry("basic")
    with tel.span("fed.plan", round=3) as span:
        assert type(span) is TraceAnnotation and span.id is None
    with tel.span("fed.round", step_num=3) as span:
        assert type(span) is StepTraceAnnotation
    assert tel.tracer is None and tel.trace_events() == []
    # trace mode records the span AND enters the same annotation.
    trace = Telemetry("trace")
    with trace.span("fed.plan") as span:
        assert span.id is not None and type(span._ann) is TraceAnnotation
    assert [e["name"] for e in trace.trace_events()] == ["fed.plan"]


# ------------------------------------------------ (c) the reduction
@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as fh:
        return json.load(fh)["events"]


def _scope_rows(report):
    return {c["scope"]: c for row in report["by_scope"]
            for c in row["children"]}


def test_recorded_capture_by_scope_sums_to_busy_time(recorded):
    report = gap_analyze.reduce_events(recorded)
    tops = {row["scope"]: row for row in report["by_scope"]}
    assert sum(r["us"] for r in tops.values()) == pytest.approx(
        report["device_busy_us"], abs=1e-2)
    for row in tops.values():
        assert sum(c["us"] for c in row["children"]) == pytest.approx(
            row["us"], abs=1e-2)
    assert sum(r["share"] for r in tops.values()) == pytest.approx(1, abs=1e-4)
    # What the cell exists for: the codec is most of the device's time,
    # the rotations most of the codec.
    assert 0.7 < tops["fed.codec"]["share"] < 0.85
    rows = _scope_rows(report)
    assert rows["fed.codec.rotate"]["us"] > 0.9 * tops["fed.codec"]["us"]
    assert 0.15 < tops["fed.local_step"]["share"] < 0.25
    assert len(rows["fed.codec.rotate"]["top_ops"]) == 3
    assert report["unscoped_share"] < gap_analyze.UNSCOPED_LIMIT
    gap_analyze.check_scoped(report)  # does not raise


def test_recorded_capture_idle_is_all_attributed(recorded):
    report = gap_analyze.reduce_events(recorded, min_gap_us=0.0)
    assert sum(r["us"] for r in report["by_phase"]) == pytest.approx(
        report["device_idle_us"], abs=1e-2)
    phases = {r["span"]: r["us"] for r in report["by_phase"]}
    assert set(phases) <= {gap_analyze.CALLER, "fed.round", "fed.plan",
                           "fed.enqueue"}
    assert phases[gap_analyze.CALLER] > 0.9 * report["device_idle_us"]
    coll = report["collectives"]  # one chip: no collective at all
    assert coll["total_us"] == coll["exposed_us"] + coll["hidden_us"] == 0.0


def _op(name, start, dur, scope="", plane="/device:TPU:0"):
    return {"plane": plane, "line": "XLA Ops", "name": name,
            "start_ns": start, "dur_ns": dur, "scope": scope}


def _span(name, start, dur):
    return {"plane": "/host:CPU", "line": "python3", "name": name,
            "start_ns": start, "dur_ns": dur, "scope": ""}


def test_hand_made_overlap_and_nested_while():
    """A ``while`` container holds its body's operations; two operations of
    different scopes overlap. Every busy instant has ONE owner, so scopes
    sum to the busy time and the container is not counted twice."""
    events = [
        _op("while.1", 0, 1000, "fed.local_step"),
        _op("fusion.1", 100, 300, "fed.local_step.fwd_bwd"),
        _op("fusion.2", 500, 400, "fed.local_step.optimizer"),
        # starts inside the loop, ends after it: the later start wins
        _op("fusion.3", 800, 500, "fed.codec.rotate"),
        _op("copy.9", 2000, 100),  # the compiler's own, no metadata
        _span("fed.round", 1250, 700),
        _span("fed.plan", 1300, 200),
        _span("fed.enqueue", 1500, 400),
    ]
    report = gap_analyze.reduce_events(events, min_gap_us=0.0)
    # busy: [0, 1300) and [2000, 2100)
    assert report["device_busy_us"] == pytest.approx(1.4)
    rows = _scope_rows(report)
    assert rows["fed.local_step"]["us"] == pytest.approx(0.2)  # the loop's own
    assert rows["fed.local_step.fwd_bwd"]["us"] == pytest.approx(0.3)
    assert rows["fed.local_step.optimizer"]["us"] == pytest.approx(0.3)
    assert rows["fed.codec.rotate"]["us"] == pytest.approx(0.5)
    assert rows[gap_analyze.UNSCOPED]["us"] == pytest.approx(0.1)
    tops = {row["scope"]: row["us"] for row in report["by_scope"]}
    assert tops["fed.local_step"] == pytest.approx(0.8)
    assert sum(tops.values()) == pytest.approx(report["device_busy_us"])
    assert rows["fed.local_step"]["top_ops"] == [["while.1", 0.2]]
    # The idle gap [1300, 2000): plan 200, enqueue 400, the round's own 50
    # (after enqueue returned), and the last 50 the caller's.
    phases = {r["span"]: r["us"] for r in report["by_phase"]}
    assert phases == {"fed.plan": pytest.approx(0.2),
                      "fed.enqueue": pytest.approx(0.4),
                      "fed.round": pytest.approx(0.05),
                      gap_analyze.CALLER: pytest.approx(0.05)}
    assert sum(phases.values()) == pytest.approx(report["device_idle_us"])


def test_hand_made_set_up_idle_time_goes_to_the_set_up_phases():
    """Between the model's init and the first round's execution the device
    is idle while the host places state, uploads the dataset and loads the
    round program: each gap is charged to the innermost ``fed.setup.*``
    phase over it, like any ``fed.`` span."""
    events = [
        _op("fusion.init", 0, 100),  # init_state's program
        _span("fed.setup.build", 0, 400),
        _span("fed.setup.build.init_state", 0, 150),
        _span("fed.setup.place_state", 200, 150),
        _span("fed.round", 500, 1000),
        _span("fed.setup.first_dispatch", 500, 900),
        _span("fed.plan", 500, 300),
        _span("fed.setup.first_dispatch.device_data", 520, 250),
        _span("fed.setup.first_dispatch.device_data.h2d", 600, 170),
        _span("fed.enqueue", 800, 600),
        _op("while.1", 1450, 500, "fed.local_step"),
    ]
    report = gap_analyze.reduce_events(events, min_gap_us=0.0)
    phases = {r["span"]: r["us"] for r in report["by_phase"]}
    assert phases == {
        "fed.setup.build.init_state": pytest.approx(0.05),
        "fed.setup.build": pytest.approx(0.1),  # its own: [150,200), [350,400)
        "fed.setup.place_state": pytest.approx(0.15),
        gap_analyze.CALLER: pytest.approx(0.1),  # [400, 500): sut, harness
        "fed.plan": pytest.approx(0.05),  # [500,520) and [770,800)
        "fed.setup.first_dispatch.device_data": pytest.approx(0.08),
        "fed.setup.first_dispatch.device_data.h2d": pytest.approx(0.17),
        "fed.enqueue": pytest.approx(0.6),  # the cache load, the launch
        # fed.setup.first_dispatch keeps nothing: its children cover it.
        "fed.round": pytest.approx(0.05),  # until the device starts
    }
    assert sum(phases.values()) == pytest.approx(report["device_idle_us"])


def test_hand_made_collectives_exposed_and_hidden_per_chip():
    events = []
    for chip, hidden in (("/device:TPU:0", 0), ("/device:TPU:1", 60)):
        events += [
            _op("while.2", 0, 1000, "fed.local_step", plane=chip),
            _op("fusion.7", 0, 600 + hidden, "fed.local_step.fwd_bwd",
                plane=chip),
            _op("all-reduce.3", 600, 100, "fed.aggregate.psum", plane=chip),
        ]
    coll = gap_analyze.reduce_events(events)["collectives"]
    # chip 0: the loop around it is a container, not work: all exposed.
    # chip 1: fusion.7 runs over the first 60 ns of it.
    assert coll["total_us"] == pytest.approx(0.1)
    assert coll["hidden_us"] == pytest.approx(0.03)
    assert coll["exposed_us"] + coll["hidden_us"] == pytest.approx(
        coll["total_us"])
    assert coll["worst_chip_share_of_busy"] == pytest.approx(0.1)


def test_stale_cache_capture_fails_naming_the_cause(tmp_path, recorded):
    """An executable served by a compile cache another commit wrote has that
    commit's metadata: device time, no scope at all."""
    stale = [dict(e, scope="") for e in recorded]
    report = gap_analyze.reduce_events(stale)
    assert report["unscoped_share"] == pytest.approx(1.0)
    with pytest.raises(gap_analyze.StaleScopes, match="STALE COMPILE CACHE"):
        gap_analyze.check_scoped(report)
    # ... and the command says so instead of printing a report.
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    trace = [{"ph": "M", "name": "process_name", "pid": 3,
              "args": {"name": "/device:TPU:0"}},
             {"ph": "M", "name": "thread_name", "pid": 3, "tid": 3,
              "args": {"name": "XLA Ops"}},
             {"ph": "M", "name": "thread_name", "pid": 3, "tid": 2,
              "args": {"name": "XLA Modules"}},
             {"ph": "X", "pid": 3, "tid": 2, "name": "jit_step", "ts": 0.0,
              "dur": 50.0},
             {"ph": "X", "pid": 3, "tid": 3, "name": "fusion.1", "ts": 1.0,
              "dur": 9.0, "args": {"tf_op": "jit(step)/mul"}}]
    with gzip.open(run / "host.trace.json.gz", "wt") as fh:
        json.dump({"traceEvents": trace}, fh)
    assert [e["name"] for e in gap_analyze.load_capture(str(tmp_path))] == [
        "fusion.1"]  # the XLA Modules line spans programs and is left out
    assert gap_analyze.main([str(tmp_path)]) == 1
