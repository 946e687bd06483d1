"""Federation-wide observability (PR 4): trace propagation over real gRPC,
the live introspection endpoints, the crash flight recorder, and the
crash-proofed exit exporters.

The acceptance spine: a 2-client federation over real gRPC produces
per-process traces whose client ``client_train`` spans carry the
coordinator's trace id and — after ``tools/trace_merge.py`` — parent
(via the propagated ``fedtpu-trace-bin`` context) under the coordinator's
``round`` span, while ``/statusz`` scraped DURING the run reports the live
round number and client liveness.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from fedtpu.obs import (
    FlightRecorder,
    MetricsRegistry,
    ObsServer,
    StatusBoard,
    parse_prometheus_text,
)
from fedtpu.obs import propagate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import span_check  # noqa: E402
import statusz  # noqa: E402
import trace_merge  # noqa: E402


def free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get(url: str):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, resp.read().decode()


# ----------------------------------------------------------- context codec
def test_trace_context_roundtrips_and_tolerates_garbage():
    ctx = propagate.TraceContext("a3f1", span_id=7, role="primary", round=12)
    blob = propagate.encode_context(ctx)
    assert propagate.decode_context(blob) == ctx
    assert propagate.from_metadata(
        [("other-key", b"x"), (propagate.METADATA_KEY, blob)]
    ) == ctx
    # Malformed payloads must never fail an RPC.
    assert propagate.decode_context(b"not json") is None
    assert propagate.decode_context(b'{"span_id": 1}') is None  # no trace_id
    assert propagate.from_metadata(None) is None
    assert propagate.from_metadata([]) is None
    # span_args: collision-proof keys, empty without a context.
    assert propagate.span_args(None) == {}
    args = propagate.span_args(ctx)
    assert args["trace_id"] == "a3f1" and args["remote_parent"] == 7
    assert "round" not in args  # receiver's own round= arg must win


# --------------------------------------- the acceptance spine (real gRPC)
@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_propagation_endpoints_and_merge_over_real_grpc(tmp_path):
    """One 2-client federation run covering the tentpole end to end:
    propagated contexts on the wire, live /statusz + /metrics + /healthz
    scraped DURING rounds, per-process trace export, and the merged
    Perfetto timeline with cross-process parent chains."""
    pytest.importorskip("grpc")
    from fedtpu.config import (
        DataConfig, FedConfig, OptimizerConfig, RoundConfig,
    )
    from fedtpu.transport.federation import PrimaryServer, serve_client

    cfg = RoundConfig(
        model="mlp",
        num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05, weight_decay=0.0),
        data=DataConfig(
            dataset="synthetic", batch_size=8, eval_batch_size=8,
            num_examples=256,
        ),
        fed=FedConfig(num_clients=2, num_rounds=3, telemetry="trace"),
        steps_per_round=2,
    )
    servers, agents, addrs = [], [], []
    obs = None
    try:
        for i in range(2):
            addr = f"localhost:{free_port()}"
            server, agent = serve_client(addr, cfg, seed=i)
            servers.append(server)
            agents.append(agent)
            addrs.append(addr)
        primary = PrimaryServer(cfg, addrs)
        obs = ObsServer(
            port=0,
            registry=primary.telemetry.registry,
            status_fn=primary.status_snapshot,
            flight=primary.flight,
        ).start()

        # Drive rounds on a background thread; scrape the live plane from
        # here while they run.
        runner = threading.Thread(target=lambda: primary.run(num_rounds=3))
        runner.start()
        statuses, prom_samples = [], []
        while runner.is_alive():
            code, body = _get(obs.url + "/healthz")
            assert code == 200 and body.strip() == "ok"
            code, body = _get(obs.url + "/statusz")
            assert code == 200
            statuses.append(json.loads(body))
            code, body = _get(obs.url + "/metrics")
            assert code == 200
            # Scrape-during-round consistency: every mid-run dump parses.
            prom_samples.append(parse_prometheus_text(body))
            time.sleep(0.05)
        runner.join()
        statuses.append(json.loads(_get(obs.url + "/statusz")[1]))

        # Live round number + client liveness showed up mid-run.
        assert any("round" in s and "phase" in s for s in statuses)
        final = statuses[-1]
        assert final["round"] >= 2
        assert final["clients"]["alive"] == addrs
        assert final["clients"]["dead"] == []
        assert final["last_round"]["participants"] == 2
        assert final["trace_id"] == primary.telemetry.tracer.trace_id
        # Counters in successive scrapes are monotone (consistent
        # snapshots, no torn reads).
        completed = [
            p["fedtpu_rounds_completed_total"][""]
            for p in prom_samples
            if "fedtpu_rounds_completed_total" in p
        ]
        assert completed == sorted(completed)
        assert json.loads(_get(obs.url + "/flightz")[1])  # ring non-empty

        # Per-process traces: clients adopted the coordinator's trace id
        # and stamped it (plus the remote parent) on their spans.
        coord_id = primary.telemetry.tracer.trace_id
        paths = []
        path = str(tmp_path / "primary.json")
        primary.telemetry.export_trace(path)
        paths.append(path)
        for i, agent in enumerate(agents):
            tel = agent.trainer.telemetry
            assert tel.tracer.trace_id == coord_id
            trains = [
                e for e in tel.tracer.events()
                if e["name"] == "client_train"
            ]
            assert trains
            for e in trains:
                assert e["args"]["trace_id"] == coord_id
                assert e["args"]["remote_role"] == "primary"
                assert e["args"]["remote_parent"] > 0
            path = str(tmp_path / f"client{i}.json")
            tel.export_trace(path)
            paths.append(path)
    finally:
        if obs is not None:
            obs.stop()
        for s in servers:
            s.stop(0)

    # Merge via the CLI surface (--check is the CI assertion) and then
    # re-verify the nesting by hand on the merged doc.
    merged_path = str(tmp_path / "merged.json")
    assert trace_merge.main(paths + ["-o", merged_path, "--check"]) == 0
    with open(merged_path) as fh:
        doc = json.load(fh)
    assert doc["metadata"]["trace_ids"] == [coord_id]
    assert doc["metadata"]["merged_roles"][0] == "primary"
    index = trace_merge.span_index(doc)
    trains = [
        e for e in doc["traceEvents"] if e.get("name") == "client_train"
    ]
    assert len(trains) >= 4  # 2 clients x >=2 traced rounds
    for e in trains:
        assert e["args"]["parent_is_remote"] is True
        root = trace_merge.root_of(index, e)
        assert root is not None and root["name"] == "round"
        # ...and the root lives in the coordinator's lane.
        assert root["args"]["span_id"].startswith("primary/")
        # The immediate remote parent is the collect worker's client_rpc.
        assert index[e["args"]["parent_id"]]["name"] == "client_rpc"


# ------------------------------------------------------------- endpoints
def test_obs_server_routes_and_404s():
    reg = MetricsRegistry()
    reg.counter("x_total").inc(2)
    board = StatusBoard(role="t")
    board.update(round=5, phase="collect")
    obs = ObsServer(port=0, registry=reg, status_fn=board.snapshot).start()
    try:
        assert _get(obs.url + "/healthz")[1] == "ok\n"
        parsed = parse_prometheus_text(_get(obs.url + "/metrics")[1])
        assert parsed["x_total"][""] == 2
        status = json.loads(_get(obs.url + "/statusz")[1])
        assert status["round"] == 5 and status["phase"] == "collect"
        assert status["updated_at"] > 0
        for path in ("/nope", "/flightz"):  # no flight attached either
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(obs.url + path)
            assert err.value.code == 404
    finally:
        obs.stop()


def test_statusz_tool_renders_live_and_offline():
    board = StatusBoard(role="primary")
    board.update(
        round=7, phase="aggregate",
        clients={"alive": ["a", "b"], "dead": ["c"]},
        heartbeat_misses=4.0,
        last_round={
            "participants": 2, "stragglers": 1,
            "t_collect_s": 1.25, "t_aggregate_s": 0.5,
        },
    )
    line = statusz.render_line(board.snapshot())
    for frag in ("role=primary", "round=7", "phase=aggregate", "alive=2/3",
                 "dead=c", "hb_miss=4", "part=2", "strag=1",
                 "collect=1.250s"):
        assert frag in line, line
    # Promoted backup: the nested acting status is what gets rendered.
    outer = {"role": "acting_primary", "acting": board.snapshot()}
    assert statusz.render_line(outer).startswith(
        "[acting_primary] role=primary"
    )
    obs = ObsServer(port=0, status_fn=board.snapshot).start()
    try:
        assert statusz.fetch(obs.url)["round"] == 7
        assert statusz.main([obs.url]) == 0
    finally:
        obs.stop()
    assert statusz.main([obs.url]) == 1  # server gone -> nonzero, no hang


# -------------------------------------------------------- flight recorder
def test_flight_recorder_ring_and_dump(tmp_path):
    fr = FlightRecorder(capacity=3, role="t", artifacts_dir=str(tmp_path))
    for i in range(5):
        fr.record("tick", i=i)
    snap = fr.snapshot()
    assert [e["i"] for e in snap] == [2, 3, 4]  # bounded, newest kept
    path = fr.dump(reason="manual")
    assert path == fr.dump_path() and os.path.exists(path)
    with open(path) as fh:
        doc = json.load(fh)
    assert doc["reason"] == "manual" and doc["role"] == "t"
    assert doc["num_events"] == 3
    assert [e["kind"] for e in doc["events"]] == ["tick"] * 3


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_flight_recorder_dumps_on_injected_exception(tmp_path):
    fr = FlightRecorder(role="crash", artifacts_dir=str(tmp_path))
    fr.install(signum=None)
    try:
        fr.record("work", step=1)
        try:
            raise ValueError("injected boom")
        except ValueError:
            sys.excepthook(*sys.exc_info())
        path = fr.dump_path()
        assert os.path.exists(path)
        with open(path) as fh:
            doc = json.load(fh)
        assert doc["reason"] == "unhandled:ValueError"
        kinds = [e["kind"] for e in doc["events"]]
        assert kinds == ["work", "exception"]
        assert "injected boom" in doc["events"][-1]["message"]
        assert "traceback" in doc["events"][-1]

        # Worker-thread crashes dump too (threading.excepthook chain).
        os.remove(path)

        def boom():
            raise RuntimeError("thread boom")

        t = threading.Thread(target=boom, name="worker")
        t.start()
        t.join()
        with open(path) as fh:
            doc = json.load(fh)
        assert doc["reason"] == "thread-unhandled:RuntimeError"
        assert doc["events"][-1]["thread"] == "worker"
    finally:
        fr.uninstall()


def test_flight_recorder_dumps_on_sigusr1(tmp_path):
    fr = FlightRecorder(role="sig", artifacts_dir=str(tmp_path))
    fr.install()
    try:
        fr.record("before_signal")
        os.kill(os.getpid(), signal.SIGUSR1)
        deadline = time.monotonic() + 5.0
        while (not os.path.exists(fr.dump_path())
               and time.monotonic() < deadline):
            time.sleep(0.01)
        with open(fr.dump_path()) as fh:
            doc = json.load(fh)
        assert doc["reason"] == "signal:SIGUSR1"
        assert doc["events"][0]["kind"] == "before_signal"
    finally:
        fr.uninstall()


def test_failover_transitions_dump_the_flight_recorder(tmp_path):
    """A forced promote (watchdog expiry) and the demote both write the
    black box — the moments PR 3's exit-time exporters always lost."""
    from fedtpu.ft import FailoverStateMachine

    fr = FlightRecorder(role="backup", artifacts_dir=str(tmp_path))
    reg = MetricsRegistry()
    clock = [0.0]
    machine = FailoverStateMachine(
        timeout=10.0, clock=lambda: clock[0], metrics=reg, flight=fr,
    )
    machine.on_ping(False)  # arm the watchdog
    clock[0] = 11.0
    assert machine.check_watchdog() is True  # forced promote
    assert os.path.exists(fr.dump_path())
    with open(fr.dump_path()) as fh:
        doc = json.load(fh)
    assert doc["reason"] == "failover:acting_primary"
    ft_events = [e for e in doc["events"] if e["kind"] == "failover"]
    assert ft_events[-1]["dst"] == "acting_primary"

    assert machine.on_ping(True) == 1  # primary back -> demote
    with open(fr.dump_path()) as fh:
        doc = json.load(fh)
    assert doc["reason"] == "failover:backup"
    ft_events = [e for e in doc["events"] if e["kind"] == "failover"]
    assert [e["dst"] for e in ft_events] == ["acting_primary", "backup"]


# ------------------------------------------------- FT control-plane RTTs
def test_ft_rpc_latency_histograms():
    from fedtpu.ft import ClientRegistry, HeartbeatMonitor
    from fedtpu.ft.failover import PrimaryPinger

    reg = MetricsRegistry()
    cr = ClientRegistry(["a", "b"], metrics=reg)
    cr.mark_failed("a")
    monitor = HeartbeatMonitor(
        cr, probe=lambda c: False, resync=lambda c: None, metrics=reg,
    )
    monitor.tick()
    monitor.tick()
    hb = reg.histogram("fedtpu_ft_rpc_seconds", labels={"rpc": "HeartBeat"})
    assert hb.count == 2  # both probes timed, not just counted as misses

    pinger = PrimaryPinger(lambda recovering: 0, metrics=reg)
    pinger.tick()
    ping = reg.histogram(
        "fedtpu_ft_rpc_seconds", labels={"rpc": "CheckIfPrimaryUp"}
    )
    assert ping.count == 1
    # Probes that raise RpcError map to None in the production probe()
    # wrapper; a None-returning send still times the attempt.
    PrimaryPinger(lambda recovering: None, metrics=reg).tick()
    assert ping.count == 2


# ---------------------------------------------------- span-name drift CI
def test_every_emitted_span_name_is_documented():
    emitted = span_check.emitted_span_names()
    assert len(emitted) >= 10  # the scanner actually sees the span calls
    assert "client_train" in emitted and "round" in emitted
    assert span_check.check() == []


def test_trace_vocabulary_is_pinned():
    """The scopes of the round program and the engine's spans are what a
    capture is reduced by: all emitted, prefixed and documented."""
    scopes = span_check.emitted_scope_names()
    assert {"fed.data", "fed.local_step", "fed.local_step.fwd_bwd",
            "fed.local_step.optimizer", "fed.pack", "fed.unpack",
            "fed.codec", "fed.codec.rotate", "fed.codec.quantize",
            "fed.codec.feedback", "fed.codec.select", "fed.aggregate",
            "fed.aggregate.psum", "fed.server_step",
            "fed.metrics"} <= set(scopes)
    spans = span_check.emitted_span_names()
    assert {"fed.round", "fed.plan", "fed.enqueue",
            "fed.fused_rounds"} <= set(spans)
    assert not set(span_check.HARNESS_SPANS) & (set(scopes) | set(spans))
    assert span_check.check_trace_vocabulary() == []


@pytest.mark.parametrize("source, documented, expect", [
    ('tel.span("fed.brand_new")', "`round`", ["no entry"]),
    ('tel.span("brand_new_span")', "`brand_new_span`",
     ["neither starts with 'fed.'"]),
    ('with jax.named_scope("fed.new_layer"):', "`fed.data`", ["no entry"]),
    ('@jax.named_scope("layer")', "`layer`", ["does not start with"]),
    ('with TraceAnnotation("fed.hand_made"):', "`fed.plan`", ["no entry"]),
    ('with jax.profiler.StepTraceAnnotation("sync", step_num=r):', "`sync`",
     ["does not start with", "harness's own"]),
    ('tel.span("dispatch")', "`dispatch`",
     ["neither starts with", "harness's own"]),
    ('tel.span("fed.ok")\nwith jax.named_scope("fed.ok.child"): pass',
     "`fed.ok` `fed.ok.child`", []),
])
def test_span_check_catches_drift(tmp_path, source, documented, expect):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(source + "\n")
    doc = tmp_path / "OBS.md"
    doc.write_text(f"documented: {documented} only\n")
    problems = span_check.check(str(pkg), str(doc))
    assert len(problems) == len(expect), problems
    for problem, text in zip(problems, expect):
        assert text in problem


# ----------------------------------------- crash-proofed exit exporters
@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_sigterm_mid_run_keeps_complete_records_and_prom_dump(tmp_path):
    """Kill the run CLI mid-flight: every already-logged round record must
    be complete v1 JSONL (per-record flush) and the SIGTERM flush must
    still write the --prom-out registry dump that previously only a clean
    exit produced."""
    from fedtpu.obs import SCHEMA_VERSION, read_round_records

    metrics_path = str(tmp_path / "m.jsonl")
    prom_path = str(tmp_path / "m.prom")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "fedtpu.cli.run",
            "--platform", "cpu",
            "--model", "mlp", "--dataset", "synthetic",
            "--num-clients", "2", "--rounds", "100000",
            "--steps-per-round", "1", "--batch-size", "8",
            "--eval-batch-size", "8", "--num-examples", "64",
            "--eval-every", "0",
            "--metrics", metrics_path, "--prom-out", prom_path,
        ],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            if (os.path.exists(metrics_path)
                    and len(read_round_records(metrics_path)) >= 3):
                break
            if proc.poll() is not None:
                pytest.fail(f"run CLI exited early: rc={proc.returncode}")
            time.sleep(0.2)
        else:
            pytest.fail("no round records appeared within 180s")
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    recs = read_round_records(metrics_path)
    assert len(recs) >= 3
    for rec in recs:  # complete v1 records, no torn tail
        assert rec["schema_version"] == SCHEMA_VERSION
        assert "loss" in rec and "t" in rec
    # With every line parseable, the raw line count must match too (a
    # truncated final line would have been silently skipped).
    with open(metrics_path) as fh:
        assert len([l for l in fh if l.strip()]) == len(recs)
    assert os.path.exists(prom_path), "SIGTERM lost the --prom-out dump"
    with open(prom_path) as fh:
        parsed = parse_prometheus_text(fh.read())
    assert parsed["fedtpu_rounds_completed_total"][""] >= 3
