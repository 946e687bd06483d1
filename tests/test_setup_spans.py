"""Set-up under the program's own spans and counters: every phase of an
engine's set-up is a span ``fed.setup.<phase>`` plus a gauge
``fedtpu_setup_seconds{phase}`` in the process-global registry; the phases
that can compile hear what jax says about a compile while they are open and
at no other time; the benchmark's six readers read the gauges.
"""

import logging
import os
import sys
import time

import jax
import numpy as np
import pytest
from jax import monitoring
from jax._src import monitoring as monitoring_src

from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
from fedtpu.core import Federation
from fedtpu.obs import MetricsRegistry, Telemetry
from fedtpu.obs import profile as obs_profile
from fedtpu.obs import registry as obs_registry
from fedtpu.obs.telemetry import setup_snapshot

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import gap_analyze  # noqa: E402
import span_check  # noqa: E402

BUILD = {"build", "build.partition", "build.init_state", "build.programs",
         "place_state"}
FIRST = {"first_dispatch", "first_dispatch.device_data",
         "first_dispatch.device_data.host", "first_dispatch.device_data.h2d"}
JAX_SAYS = ("trace", "lower", "compile", "cache_load")
COUNTS = ("cache_hits", "cache_misses", "state_leaves", "state_bytes",
          "device_data_bytes")


@pytest.fixture()
def fresh_registry(monkeypatch):
    """A process-global registry of this test's own."""
    reg = MetricsRegistry()
    monkeypatch.setattr(obs_registry, "_GLOBAL", reg)
    return reg


def _federation(telemetry="basic", mesh=None, clients=4):
    cfg = RoundConfig(
        model="mlp",
        num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05),
        data=DataConfig(dataset="synthetic", batch_size=4, partition="iid",
                        num_examples=64),
        fed=FedConfig(num_clients=clients, telemetry=telemetry),
        steps_per_round=2,
    )
    return Federation(cfg, seed=0, mesh=mesh)


def _listeners():
    return (len(monitoring_src.get_event_duration_listeners()),
            len(monitoring_src.get_event_listeners()))


def _host_params(state):
    return state._replace(params=jax.tree.map(np.asarray, state.params))


# ------------------------------------------------------------ the phases
def test_build_and_first_step_set_every_phase(fresh_registry):
    before = _listeners()
    fed = _federation()
    built = setup_snapshot()
    assert set(built["seconds"]) - {
        f"build.init_state.{k}" for k in JAX_SAYS} == BUILD
    assert not FIRST & set(built["seconds"])
    jax.block_until_ready(fed.step().loss)
    snap = setup_snapshot()
    s = snap["seconds"]
    assert BUILD | FIRST <= set(s)
    # On the CPU there is no persistent cache: every program compiles, none
    # is loaded, and jax fires neither cache event.
    assert s["first_dispatch.compile"] > 0 and s["first_dispatch.trace"] > 0
    assert s["first_dispatch.lower"] > 0
    assert "first_dispatch.cache_load" not in s
    assert snap["cache_hits"] == snap["cache_misses"] == 0
    # Children sum to no more than their parent.
    assert (s["build.partition"] + s["build.init_state"]
            + s["build.programs"]) <= s["build"]
    for parent in ("build.init_state", "first_dispatch"):
        heard = sum(s.get(f"{parent}.{k}", 0.0) for k in JAX_SAYS)
        assert 0 < heard <= s[parent]
    assert (heard + s["first_dispatch.device_data"]) <= s["first_dispatch"]
    assert (s["first_dispatch.device_data.host"]
            + s["first_dispatch.device_data.h2d"]
            ) <= s["first_dispatch.device_data"]
    assert snap["device_data_bytes"] > 64 * 32 * 32 * 3  # the rows, at least
    # No listener of the engine's is left with jax.monitoring.
    assert _listeners() == before


def test_a_second_step_adds_nothing_and_opens_the_parents_spans(
        fresh_registry):
    fed = _federation(telemetry="trace")
    fed.step()
    first = [e["name"] for e in fed.telemetry.trace_events()
             if e["name"].startswith("fed.") and "build" not in e["name"]
             and e["name"] != "fed.setup.place_state"]
    assert sorted(first) == sorted([
        "fed.round", "fed.setup.first_dispatch", "fed.plan",
        "fed.setup.first_dispatch.device_data",
        "fed.setup.first_dispatch.device_data.host",
        "fed.setup.first_dispatch.device_data.h2d", "fed.enqueue"])
    after_first = setup_snapshot()
    fed.telemetry.tracer.clear()
    jax.block_until_ready(fed.step().loss)
    assert setup_snapshot() == after_first
    # A steady step opens the parent's three spans, names and nesting.
    events = fed.telemetry.trace_events()
    assert sorted(e["name"] for e in events) == [
        "fed.enqueue", "fed.plan", "fed.round"]
    by_name = {e["name"]: e["args"] for e in events}
    for child in ("fed.plan", "fed.enqueue"):
        assert by_name[child]["parent_id"] == by_name["fed.round"]["span_id"]


def test_each_program_has_one_first_dispatch(fresh_registry):
    fed = _federation(telemetry="trace")

    def firsts():
        return sum(e["name"] == "fed.setup.first_dispatch"
                   for e in fed.telemetry.trace_events())

    fed.step()
    assert firsts() == 1
    fed.run_on_device(2)  # another compiled program: its own first call
    assert firsts() == 2
    fused = next(e for e in fed.telemetry.trace_events()
                 if e["name"] == "fed.fused_rounds")
    inner = [e for e in fed.telemetry.trace_events()
             if e["name"] == "fed.setup.first_dispatch"][-1]
    assert inner["args"]["parent_id"] == fused["args"]["span_id"]
    seconds = setup_snapshot()["seconds"]["first_dispatch"]
    fed.run_on_device(2)
    fed.step()
    assert firsts() == 2
    assert setup_snapshot()["seconds"]["first_dispatch"] == seconds
    fed.step(fed.round_batch(5))  # the explicit-batch program's first call
    assert firsts() == 3
    # The dataset went up once, under the first of them.
    assert sum(e["name"] == "fed.setup.first_dispatch.device_data"
               for e in fed.telemetry.trace_events()) == 1


def test_off_mode_records_nothing_and_opens_no_annotation(
        fresh_registry, monkeypatch):
    from fedtpu.obs import trace as obs_trace

    opened = []
    real = obs_trace.profiler_span
    monkeypatch.setattr(
        "fedtpu.obs.telemetry.profiler_span",
        lambda name, args: opened.append(name) or real(name, args))
    before = _listeners()
    fed = _federation(telemetry="off")
    fed.state = _host_params(fed.state)
    jax.block_until_ready(fed.step().loss)
    assert opened == [] and setup_snapshot() == {}
    assert fresh_registry.snapshot() == {}
    assert "setup" not in fed.status_snapshot()
    assert _listeners() == before
    assert Telemetry("off").phase("fed.setup.build") is obs_trace.NULL_SPAN
    # The same build with the default mode opens them.
    _federation().step()
    assert "fed.setup.build" in opened and "fed.setup.first_dispatch" in opened


def test_the_gauges_describe_the_newest_engine(fresh_registry):
    first = _federation()
    first.step()
    assert "first_dispatch" in setup_snapshot()["seconds"]
    second = _federation()
    snap = setup_snapshot()
    assert not FIRST & set(snap["seconds"])  # the first one's are forgotten
    assert "device_data_bytes" not in snap
    # An engine that records nothing leaves the newest recording one's.
    _federation(telemetry="off")
    assert setup_snapshot() == snap
    assert second.status_snapshot()["setup"] == snap


def test_a_phase_is_named_by_its_span():
    with pytest.raises(ValueError, match="fed.setup."):
        Telemetry("basic").phase("fed.plan")


# ------------------------------------------------------ the state's placement
def test_setter_puts_host_leaves_on_the_device(fresh_registry):
    fed = _federation()
    # Without a mesh the constructor's state is init_state's own arrays:
    # nothing to place, nothing counted.
    assert "state_leaves" not in setup_snapshot()
    built = setup_snapshot()["seconds"]["place_state"]
    params = jax.tree.leaves(fed.state.params)
    fed.state = _host_params(fed.state)
    assert all(isinstance(l, jax.Array) for l in jax.tree.leaves(fed.state))
    snap = setup_snapshot()
    assert snap["seconds"]["place_state"] > built
    assert snap["state_leaves"] == len(params)
    assert snap["state_bytes"] == sum(l.nbytes for l in params)
    # Arrays already on the device are left as they are, uncounted.
    same = fed.state
    fed.state = same
    assert all(a is b for a, b in zip(jax.tree.leaves(fed.state),
                                      jax.tree.leaves(same)))
    assert setup_snapshot()["state_leaves"] == len(params)


def test_setter_placement_on_a_mesh_lands_in_place_state(
        fresh_registry, eight_devices):
    from fedtpu.parallel import client_mesh

    fed = _federation(mesh=client_mesh(4, "clients"))
    built = setup_snapshot()
    # The constructor's placement: every leaf of init_state's state moved.
    n_leaves = len(jax.tree.leaves(fed.state))
    assert built["state_leaves"] == n_leaves
    assert built["seconds"]["place_state"] <= built["seconds"]["build"]
    params = jax.tree.leaves(fed.state.params)
    fed.state = _host_params(fed.state)
    snap = setup_snapshot()
    assert snap["seconds"]["place_state"] > built["seconds"]["place_state"]
    # Only the host leaves moved, each to all four devices (replicated).
    assert snap["state_leaves"] == n_leaves + len(params)
    assert snap["state_bytes"] - built["state_bytes"] == 4 * sum(
        l.nbytes for l in params)
    assert all(len(l.sharding.device_set) == 4
               for l in jax.tree.leaves(fed.state.params))
    jax.block_until_ready(fed.step().loss)
    assert FIRST <= set(setup_snapshot()["seconds"])


# ------------------------------------------- what jax says about a compile
def test_a_duration_is_charged_inside_a_compiling_phase_only(fresh_registry):
    before = _listeners()
    tel = Telemetry("basic")
    event = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    monitoring.record_event_duration_secs(event, 5.0, fun_name="before")
    with tel.phase("fed.setup.first_dispatch", compiles=True):
        assert _listeners() == (before[0] + 1, before[1] + 1)
        monitoring.record_event_duration_secs(event, 0.25, fun_name="inside")
        monitoring.record_event_duration_secs("/jax/some/other", 9.0)
    monitoring.record_event_duration_secs(event, 7.0, fun_name="after")
    assert _listeners() == before
    s = setup_snapshot()["seconds"]
    assert s["first_dispatch.lower"] == 0.25
    assert set(s) == {"first_dispatch", "first_dispatch.lower"}
    # A phase that cannot compile hears nothing.
    with tel.phase("fed.setup.place_state"):
        assert _listeners() == before
        monitoring.record_event_duration_secs(event, 3.0)
    assert "place_state.lower" not in setup_snapshot()["seconds"]


def test_compile_is_net_of_the_cache_load_and_of_nested_traces(
        fresh_registry):
    tel = Telemetry("basic")
    names = {v: k for k, v in obs_profile.COMPILE_DURATION_EVENTS.items()}
    with tel.phase("fed.setup.first_dispatch", compiles=True):
        # An inner jit's trace ends inside the outer one's.
        monitoring.record_event_duration_secs(names["trace"], 0.002)
        monitoring.record_event_duration_secs(names["trace"], 0.01)
        time.sleep(0.06)  # what follows began after the traces ended
        # A cache hit: the retrieval ends inside backend_compile_duration.
        monitoring.record_event(obs_profile.CACHE_HIT_EVENT)
        monitoring.record_event_duration_secs(names["cache_load"], 0.02)
        monitoring.record_event_duration_secs(names["compile"], 0.03)
        time.sleep(0.06)
        # A miss: compiled, written to the cache, nothing retrieved.
        monitoring.record_event(obs_profile.CACHE_MISS_EVENT)
        monitoring.record_event_duration_secs(names["compile"], 0.04)
    snap = setup_snapshot()
    s = snap["seconds"]
    assert s["first_dispatch.trace"] == pytest.approx(0.01)
    assert s["first_dispatch.cache_load"] == pytest.approx(0.02)
    assert s["first_dispatch.compile"] == pytest.approx(0.01 + 0.04)
    assert snap["cache_hits"] == 1 and snap["cache_misses"] == 1
    # After the phase the events go uncounted.
    monitoring.record_event(obs_profile.CACHE_MISS_EVENT)
    assert setup_snapshot()["cache_misses"] == 1


def test_one_table_of_jaxs_names_serves_both_listeners():
    from jax._src import dispatch

    table = obs_profile.COMPILE_DURATION_EVENTS
    assert table[dispatch.JAXPR_TRACE_EVENT] == "trace"
    assert table[dispatch.JAXPR_TO_MLIR_MODULE_EVENT] == "lower"
    assert table[dispatch.BACKEND_COMPILE_EVENT] == "compile"
    assert dispatch.BACKEND_COMPILE_EVENT == obs_profile.BACKEND_COMPILE_EVENT
    assert sorted(table.values()) == sorted(JAX_SAYS)


def test_registry_forgets_by_prefix():
    reg = MetricsRegistry()
    reg.gauge("fedtpu_setup_seconds", labels={"phase": "build"}).set(1)
    reg.gauge("fedtpu_setup_cache_hits").set(2)
    reg.counter("fedtpu_rounds_completed_total").inc()
    reg.forget("fedtpu_setup_")
    assert list(reg.snapshot()) == ["fedtpu_rounds_completed_total"]
    # A forgotten name stays bound to its kind.
    with pytest.raises(ValueError):
        reg.counter("fedtpu_setup_cache_hits")
    assert reg.gauge("fedtpu_setup_cache_hits").value == 0.0


# ----------------------------------------------------- the operator's view
def test_run_cli_round_zero_window_holds_set_up_and_logs_it(
        fresh_registry, tmp_path, caplog):
    from fedtpu.cli import run as cli_run

    trace_dir = str(tmp_path / "capture")
    with caplog.at_level(logging.INFO):
        rc = cli_run.main([
            "--platform", "cpu", "--model", "mlp", "--dataset", "synthetic",
            "--num-clients", "2", "--rounds", "2", "--num-examples", "64",
            "--batch-size", "4", "--steps-per-round", "2", "--lr", "0.05",
            "--partition", "iid", "--eval-every", "0", "--mfu", "off",
            "--profile-rounds", "0:1", "--profile-trace-dir", trace_dir,
        ])
    assert rc == 0
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("set-up: ")]
    assert len(lines) == 1 and '"first_dispatch"' in lines[0]
    # The window opened before Federation(...) was built and closed after
    # round 0: the capture holds the build and ONE round.
    names = [e["name"] for e in gap_analyze.load_capture(trace_dir)]
    assert names.count("fed.setup.build") == 1
    assert names.count("fed.setup.first_dispatch") == 1
    assert names.count("fed.round") == 1


# ------------------------------------------------- the benchmark's readers
READERS = {
    "engine.build_s": 6.5,
    "engine.state_place_s": 0.75,
    "engine.device_data_s": 2.0,
    "engine.first_dispatch_s": 12.0,
    "entry.trace_lower_s": 0.5 + 0.25 + 1.5 + 0.125,
    "entry.cache_misses": 3.0,
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_the_gauge_or_nothing(name, fresh_registry):
    from benchmark import run

    reader = run.load_py(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))
    assert reader.read({}) is None  # the parent's program records nothing
    for phase, seconds in {
        "build": 6.5, "place_state": 0.75, "first_dispatch": 12.0,
        "first_dispatch.device_data": 2.0,
        "build.init_state.trace": 0.5, "build.init_state.lower": 0.25,
        "first_dispatch.trace": 1.5, "first_dispatch.lower": 0.125,
        "first_dispatch.compile": 40.0, "first_dispatch.cache_load": 4.0,
    }.items():
        fresh_registry.gauge(
            "fedtpu_setup_seconds", labels={"phase": phase}).set(seconds)
    fresh_registry.gauge("fedtpu_setup_cache_misses").set(3)
    assert reader.read({}) == READERS[name]


def test_the_manifest_lists_the_six_under_setup_s():
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    # in the manifest and in this order, wherever later PRs' entries stand
    names = ["engine.build_s", "engine.state_place_s", "engine.device_data_s",
             "engine.first_dispatch_s", "entry.trace_lower_s",
             "entry.cache_misses"]
    mine = [m for m in manifest["per_layer"] if m["name"] in names]
    assert [m["name"] for m in mine] == names
    for m in mine:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["source"] == "program_counter" and "workloads" not in m
        assert m["layer"] == (
            "engine" if m["name"].startswith("engine.") else "process entry")


# ------------------------------------------------------------ name drift
def test_span_check_sees_phases_and_set_up_gauges(tmp_path):
    spans = span_check.emitted_span_names()
    for name in BUILD | FIRST:
        assert f"fed.setup.{name}" in spans
    metrics = span_check.emitted_metric_names()
    assert {f"fedtpu_setup_{c}" for c in COUNTS} | {
        "fedtpu_setup_seconds"} <= set(metrics)
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        'with tel.phase("fed.setup.undocumented", compiles=True): pass\n'
        'tel.setup_gauge("fedtpu_setup_undocumented", "help").inc()\n'
        'with tel.phase("setup.misnamed"): pass\n'
    )
    doc = tmp_path / "OBS.md"
    doc.write_text("nothing\n")
    problems = "\n".join(span_check.check(str(pkg), str(doc)))
    assert "span 'fed.setup.undocumented'" in problems
    assert "metric 'fedtpu_setup_undocumented'" in problems
    assert "span 'setup.misnamed'" in problems and "neither starts" in problems
