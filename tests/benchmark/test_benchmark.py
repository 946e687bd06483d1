"""The benchmark's own tests: the manifest, the refusal to run without a
TPU, the FLOP functions, the trace reduction on recorded TPU traces, what the
harness hands the program at equal seeds (digests recorded at the parent of
PR 27), a task of another kind of data through the reference path, the plain
reference against ``Federation`` on the CPU, the lower-precision control, and
a run whose timed path is broken underneath.

Everything here runs on the CPU at a tiny size (``tiny/``: the real smallcnn,
4 clients, 2 steps of 32); times and rates come only from the chip.
"""

import dataclasses
import hashlib
import json
import math
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
TINY = os.path.join(HERE, "tiny_manifest.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(MANIFEST) as fh:
        return json.load(fh)


# ---------------------------------------------------------------- the manifest
def test_manifest_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for row in manifest[kind]:
            assert NAME.match(row["name"]), row["name"]
            names.append((kind in ("end_to_end", "per_layer"), row["name"]))
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in manifest["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)


def test_manifest_bounds(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_manifest_moves_and_cells(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:
        assert sum(cell in v for k, v in e2e.items() if k != "setup_s") >= 1
        assert any(cell in m.get("workloads", cells) for m in manifest["per_layer"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(cells) // 4)


def test_manifest_files_exist(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    assert len({c["file"] for c in configs.values()}) == len(configs)
    for w in manifest["workloads"]:
        conf = configs[w["config"]]
        assert conf["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, conf["file"])) as fh:
            cfg = json.load(fh)
        assert cfg["reduced"] == conf["reduced"]
        for rel in (f"benchmark/traffic/{w['traffic']}.json",
                    f"benchmark/limits/{w['name']}.json",
                    f"benchmark/flops/{w['config']}.py",
                    f"benchmark/reference/{cfg['model']}.py",
                    f"benchmark/tasks/{cfg.get('task', 'image_classification')}.py"):
            assert os.path.isfile(os.path.join(ROOT, rel)), rel
    for m in manifest["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py")), m["name"]


def test_limits_name_their_readings(manifest):
    """Every limit states the sound runs' largest and the control's smallest
    that it was set between."""
    for w in manifest["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "limits", w["name"] + ".json")) as fh:
            numbers = json.load(fh)["numbers"]
        held = {k: r for k, r in numbers.items() if r["limit"] is not None}
        for name, row in held.items():
            assert row["limit"] > row["sound_max"] and row["why"], (w["name"], name)
            if row["control_min"] is not None:
                assert row["limit"] < row["control_min"], (w["name"], name)
        # the lower precision fails at least one held number, with 3x to spare
        assert any(r["control_min"] is not None
                   and r["control_min"] >= 3 * r["sound_max"] for r in held.values())


# ------------------------------------------------------------- no TPU, no number
def _run_cli(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **dict(extra_env))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "smallcnn_cifar10.sim192", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_cpu_backend_exits_nonzero_and_prints_no_metric():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout and proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_bare_directory_exits_nonzero(tmp_path, manifest):
    shutil.copy(MANIFEST, tmp_path / "BENCHMARK.json")
    for p in manifest["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(str(tmp_path))
    assert proc.returncode != 0 and "metrics" not in proc.stdout


# ------------------------------------------------------------------- FLOP counts
def _cell(name, path=MANIFEST):
    from benchmark import run

    return run.Cell(path, name)


def test_resnet18_flops_are_the_known_count():
    cell = _cell("resnet18_cifar100.sim64")
    macs = cell.flops.forward_macs_per_sample(cell.config)
    # 3x3 stem 1.77 M, four stages 151.0 + 134.2 + 134.2 + 134.2 M, head 51 k.
    assert macs == 555_468_800  # about 0.56 GMAC forward per 32x32 image
    assert cell.flops.train_flops_per_sample(cell.config) == 6 * macs


def test_smallcnn_flops_from_its_shapes():
    cell = _cell("smallcnn_cifar10.sim192")
    assert cell.flops.forward_macs_per_sample(cell.config) == (
        27 * 32 * 1024 + 288 * 64 * 256 + 4096 * 128 + 128 * 10)
    params, _ = cell.reference.spec(cell.config)
    assert sum(int(np.prod(shape)) for _, shape, _ in params) == 545_098


def test_resnet18_reference_has_the_published_size():
    cell = _cell("resnet18_cifar100.sim64")
    params, stats = cell.reference.spec(cell.config)
    n = sum(int(np.prod(shape)) for _, shape, _ in params)
    assert 11.1e6 < n < 11.3e6  # about 11.2 M with a 100-class head
    assert len(stats) == 2 * 20  # twenty BatchNorms, mean and var each


# ------------------------------------------------------------------ the trace
def test_interval_arithmetic_on_a_hand_made_trace():
    from benchmark import trace_reduce as tr

    dev = "/device:TPU:0"
    ev = lambda plane, line, name, a, d: {
        "plane": plane, "line": line, "name": name, "start_ns": a, "dur_ns": d}
    events = [
        ev("/host:CPU", "t", "dispatch", 0, 100),
        ev("/host:CPU", "t", "sync", 100, 800),
        ev("/host:CPU", "t", "record.read", 900, 100),
        ev(dev, "XLA Ops", "fusion.1", 50, 400),
        ev(dev, "XLA Ops", "all-reduce.2", 400, 200),   # overlaps fusion.1 by 50
        ev(dev, "XLA Ops", "fusion.3", 700, 100),
    ]
    out = tr.reduce_trace(events)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx(650e-9)       # [50,600) + [700,800)
    gaps = dict(out["idle_gaps"])
    assert gaps["dispatch"] == pytest.approx(50e-9)      # [0,50)
    assert gaps["sync"] == pytest.approx(200e-9)         # [600,700) + [800,900)
    assert gaps["record.read"] == pytest.approx(100e-9)  # [900,1000)
    assert gaps["_no_span_"] == pytest.approx(0.0)
    assert out["collective_share"] == pytest.approx(200 / 650)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(400e-9)]
    assert tr.union_intervals([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tr.find_gaps([(1, 4), (5, 7)], (0, 10)) == [(0, 1), (4, 5), (7, 10)]


@pytest.mark.parametrize("fixture", ["trace_tpu_small.json"])
def test_reduction_of_a_recorded_tpu_trace(fixture):
    """Three rounds of smallcnn_cifar10.sim192 recorded on a v5e (PR 24)."""
    from benchmark import trace_reduce as tr

    with open(os.path.join(HERE, fixture)) as fh:
        recorded = json.load(fh)
    out = tr.reduce_trace(recorded["events"])
    assert out["window_s"] == pytest.approx(recorded["expect"]["window_s"], rel=1e-9)
    assert out["busy_s"] == pytest.approx(recorded["expect"]["busy_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    idle = out["window_s"] - out["busy_s"]
    assert sum(v for _, v in out["idle_gaps"]) == pytest.approx(idle, rel=1e-6)
    assert out["collective_share"] == 0.0  # one chip
    assert len(out["device_ops"]) == 10


def test_busy_by_scope_on_a_recorded_capture_agrees_with_gap_analyze():
    """Three rounds of sim192_rotq4's round program on a v5e (PR 25), with the
    shares ``tools/gap_analyze.py`` itself reduced them to (recorded in the
    fixture): every scope to 0.1 point, the idle time by innermost span."""
    from benchmark import trace_reduce as tr

    with open(os.path.join(HERE, "capture_v5e_sim192_rotq4.json")) as fh:
        recorded = json.load(fh)
    events, expect = recorded["events"], recorded["expect"]
    ops = [e for e in events if e["plane"].startswith("/device:")]
    lo = min(e["start_ns"] for e in ops)
    hi = max(e["start_ns"] + e["dur_ns"] for e in ops)
    # The capture was taken outside the harness: one harness span over it.
    events = events + [{"plane": "/host:CPU", "line": "t", "name": "sync",
                        "start_ns": lo, "dur_ns": hi - lo}]
    out = tr.reduce_trace(events)
    assert out["window_s"] * 1e6 == pytest.approx(expect["window_us"], abs=1e-2)
    assert out["busy_s"] * 1e6 == pytest.approx(expect["device_busy_us"], abs=1e-2)
    assert sum(out["busy_by_scope"].values()) == pytest.approx(out["busy_s"], rel=1e-9)
    assert set(out["busy_by_scope"]) == set(expect["scope_share"])
    for scope, share in expect["scope_share"].items():
        assert out["busy_by_scope"][scope] / out["busy_s"] == pytest.approx(
            share, abs=1e-3), scope
    assert expect["scope_share"]["fed.codec.rotate"] > 0.7  # PR 25's butterfly
    assert tr.scope_share(out, "fed.codec") == pytest.approx(100 * sum(
        v for k, v in expect["scope_share"].items() if k.startswith("fed.codec")),
        abs=1e-3)
    assert tr.scope_share(out, "fed.nothing") is None
    assert tr.stale_scopes(out) is None  # _unscoped_ 2.4 %
    idle = {k: v * 1e6 for k, v in out["idle_by_span"].items()}
    for span, us in expect["idle_us_by_span"].items():
        assert idle["sync" if span == "caller" else span] == pytest.approx(us, abs=1e-2)
    assert out["collective_exposed_s"] == 0.0 and out["collective_share"] == 0.0


def test_nested_spans_give_a_gap_to_the_deepest_and_collectives_their_exposed_part():
    from benchmark import trace_reduce as tr

    dev = "/device:TPU:0"
    ev = lambda plane, name, a, d, **kw: {
        "plane": plane, "line": "t", "name": name, "start_ns": a, "dur_ns": d, **kw}
    events = [
        ev("/host:CPU", "dispatch", 0, 400),
        ev("/host:CPU", "fed.round", 50, 300),       # inside dispatch
        ev("/host:CPU", "fed.plan", 60, 100),        # inside fed.round
        ev("/host:CPU", "fed.enqueue", 160, 150),    # inside fed.round
        ev("/host:CPU", "sync", 400, 600),
        ev(dev, "fusion.1", 200, 300, scope="fed.local_step.fwd_bwd"),
        ev(dev, "while.2", 500, 300, scope="fed.local_step"),
        ev(dev, "fusion.3", 550, 100, scope="fed.codec.rotate"),  # the loop's body
        ev(dev, "all-reduce.4", 800, 100, scope="fed.aggregate.psum"),
        ev(dev, "copy.5", 850, 100),                 # hides half the all-reduce
    ]
    out = tr.reduce_trace(events)
    assert out["window_s"] == pytest.approx(1000e-9) and out["busy_s"] == pytest.approx(750e-9)
    # device idle [0, 200) and [950, 1000)
    assert {k: round(v * 1e9) for k, v in out["idle_by_span"].items()} == {
        "dispatch": 50, "fed.round": 10, "fed.plan": 100, "fed.enqueue": 40,
        "sync": 50, "_no_span_": 0}
    assert {k: round(v * 1e9) for k, v in out["busy_by_scope"].items()} == {
        "fed.local_step.fwd_bwd": 300, "fed.local_step": 200,
        "fed.codec.rotate": 100, "fed.aggregate.psum": 50, "_unscoped_": 100}
    assert out["collective_share"] == pytest.approx(100 / 750)
    assert out["collective_exposed_s"] == pytest.approx(50e-9)
    assert tr.scope_share(out, "fed.local_step") == pytest.approx(100 * 500 / 750)
    assert "13.3 %" in tr.stale_scopes(out)  # 100 of 750 without a scope
    assert tr.scope_of("jit(round)/vmap(fed.local_step)/while/body/"
                       "transpose(jvp(fed.local_step.fwd_bwd))/conv") == "fed.local_step.fwd_bwd"
    assert tr.scope_of("jit(round)/copy") == "" and tr.scope_of(None) == ""


def test_scopes_are_read_from_the_trace_viewer_json(tmp_path):
    import gzip

    from benchmark import trace_reduce as tr

    meta = lambda pid, tid, kind, name: {
        "ph": "M", "pid": pid, "tid": tid, "name": kind, "args": {"name": name}}
    op = lambda pid, tid, name, tf_op: {
        "ph": "X", "pid": pid, "tid": tid, "name": name, "ts": 1.0, "dur": 2.0,
        "args": {"tf_op": tf_op} if tf_op else {}}
    doc = {"traceEvents": [
        meta(3, 0, "process_name", "/device:TPU:0"), meta(3, 1, "thread_name", "XLA Ops"),
        meta(3, 2, "thread_name", "Steps"), meta(9, 0, "process_name", "/host:CPU"),
        op(3, 1, "fusion.7", "jit(r)/fed.codec/fed.codec.rotate/dot_general"),
        op(3, 1, "copy.1", ""), op(3, 2, "step 0", "jit(r)/fed.data/x"),
        op(9, 5, "fusion.7", "jit(r)/fed.pack/x"),
    ]}
    path = tmp_path / "host.trace.json.gz"
    with gzip.open(path, "wt") as fh:
        json.dump(doc, fh)
    assert tr.load_scopes(str(path)) == {
        ("/device:TPU:0", "fusion.7"): "fed.codec.rotate",
        ("/device:TPU:0", "copy.1"): ""}


def test_layer_metric_readers_return_nothing_without_something_to_read(manifest):
    from benchmark import run

    cell = _cell("smallcnn_cifar10.sim192")
    ctx = {"cell": cell, "chips": 1, "trace": None, "rate": 5e5,
           "peaks": {"bf16_flops_per_s": 197e12}, "memory_peak_bytes": 0,
           "compile_setup_s": 1.5, "compiles_in_window": 0}
    got = {m["name"]: run.load_py(os.path.join(
        ROOT, "benchmark", "layer_metrics", m["name"] + ".py")).read(ctx)
        for m in manifest["per_layer"]}
    assert got["device.idle_share"] is None and got["mesh.collective_share"] is None
    for name in ("local_step.device_share", "aggregate.device_share",
                 "codec.device_share", "codec.rotate_roofline",
                 "engine.enqueue_gap_share", "mesh.collective_exposed_share"):
        assert name in got and got[name] is None, name
    assert got["engine.host_gap_share"] is None and got["device.peak_hbm_gb"] is None
    assert got["entry.compile_s"] == 1.5 and got["entry.compiles_in_window"] == 0.0
    # 6 x 6,128,896 FLOP x 5e5 samples/s over 197 TFLOP/s
    assert got["local_step.mfu"] == pytest.approx(100 * 36_773_376 * 5e5 / 197e12)
    ctx["trace"] = {"window_s": 2.0, "busy_s": 1.5, "collective_share": 0.25,
                    "collective_exposed_s": 0.03,
                    "idle_by_span": {"sync": 0.3, "_no_span_": 0.2},
                    "busy_by_scope": {"fed.local_step": 0.1, "fed.local_step.fwd_bwd": 0.8,
                                      "fed.aggregate.psum": 0.015, "_unscoped_": 0.585}}
    ctx["chips"] = 4
    read = lambda n: run.load_py(os.path.join(
        ROOT, "benchmark", "layer_metrics", n + ".py")).read(ctx)
    assert read("device.idle_share") == pytest.approx(25.0)
    assert read("engine.host_gap_share") == pytest.approx(15.0)
    assert read("mesh.collective_share") == pytest.approx(25.0)
    assert read("mesh.collective_exposed_share") == pytest.approx(2.0)
    assert read("local_step.device_share") == pytest.approx(60.0)
    assert read("aggregate.device_share") == pytest.approx(1.0)
    # no codec scope, no engine span in this trace: nothing, never 0
    assert read("codec.device_share") is None and read("codec.rotate_roofline") is None
    assert read("engine.enqueue_gap_share") is None
    ctx["trace"]["idle_by_span"]["fed.enqueue"] = 0.001
    assert read("engine.enqueue_gap_share") == pytest.approx(0.05)
    # two rotations of [192, 2^20] f32 a round, read once and written once,
    # at 819 GB/s: 3.933 ms a round; 20 ms under the scope in each of 3 rounds
    ctx["trace"]["busy_by_scope"]["fed.codec.rotate"] = 0.060
    ctx.update(traced_rounds=3, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               cell=_cell("smallcnn_cifar10.sim192_rotq4"))
    assert read("codec.rotate_roofline") == pytest.approx(
        100 * 3 * (2 * 2 * 192 * 2**20 * 4 / 819e9) / 0.060)
    assert read("codec.device_share") == pytest.approx(4.0)


# ------------------------------------------------------------- seeds and numbers
def test_any_seed_gives_the_same_inputs_again():
    from benchmark import seeded

    big = 2**31 + 12345
    task = _cell("smallcnn_cifar10.sim192").task
    cfg = {"num_examples": 64, "image_shape": [8, 8, 3], "num_classes": 10}
    a = task.make_data(big, cfg)
    b = task.make_data(big, cfg)
    c = task.make_data(big + 1, cfg)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert np.array_equal(a[0], a[0].astype("bfloat16").astype(np.float32))
    idx, mask = seeded.make_shards(big, 64, 4)
    assert sorted(idx.ravel()) == list(range(64)) and mask.all()
    rows = seeded.client_rows(idx[0], steps=3, batch=8)  # 24 rows from 16: cycles
    assert rows.shape == (3, 8) and np.array_equal(rows[2], idx[0][:8])


# ------------------- what the program is handed, against the parent of PR 27
def _digest(a):
    a = np.ascontiguousarray(np.asarray(a))
    h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
    h.update(a.tobytes())
    return h.hexdigest()[:16]


def _leaf_digests(tree, prefix=()):
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(_leaf_digests(tree[k], prefix + (k,)))
        return out
    return {"/".join(prefix): _digest(tree)}


CELLS = ["resnet18_cifar100.sim64", "smallcnn_cifar10.sim192",
         "smallcnn_cifar10.sim192_rotq4", "resnet18_cifar100.mesh4_sim256"]


@pytest.mark.parametrize("seed", [1, 2147485101])
@pytest.mark.parametrize("cell_name", CELLS)
def test_the_program_is_handed_the_parents_arrays(cell_name, seed):
    """Examples, targets, shards and every weight leaf that
    ``check.seeded_inputs`` draws equal, bit for bit, what the harness drew
    before tasks existed (``parent_digests.json``: commit 447791e, this
    backend, ``num_examples`` 1024: the generator is one code path at any
    size, and the chip runs of PR 27 hold the full size)."""
    from benchmark import check

    with open(os.path.join(HERE, "parent_digests.json")) as fh:
        recorded = json.load(fh)
    cell = _cell(cell_name)
    cell.config = dict(cell.config, num_examples=recorded["num_examples"])
    examples, targets, shards, initial = check.seeded_inputs(cell, seed)
    assert {
        "inputs": _digest(examples), "targets": _digest(targets),
        "shards": [_digest(shards[0]), _digest(shards[1])],
        "params": _leaf_digests(initial["params"]),
        "stats": _leaf_digests(initial["stats"]),
    } == recorded["cells"][cell_name][str(seed)]


def _parent_round_config(cell_name):
    """The configuration object the parent's ``sut.round_config`` built for
    each cell, written out."""
    from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig

    opt = OptimizerConfig(learning_rate=0.1, momentum=0.9, weight_decay=0.0005,
                          schedule="constant")
    resnet = dict(model="resnet18", num_classes=100, image_size=(32, 32, 3), opt=opt,
                  data=DataConfig(dataset="cifar100", batch_size=128,
                                  partition="round_robin", augment=False,
                                  device_layout="gather"),
                  steps_per_round=6, dtype="bfloat16", remat=True)
    small = dict(model="smallcnn", num_classes=10, image_size=(32, 32, 3), opt=opt,
                 data=DataConfig(dataset="cifar10", batch_size=128,
                                 partition="round_robin", augment=False,
                                 device_layout="presharded"),
                 steps_per_round=2, dtype="bfloat16", remat=False)
    plain = dict(weighted=True, delta_layout="per_leaf", compression="none",
                 rotq_bits=4, error_feedback=True)
    return {
        "resnet18_cifar100.sim64": RoundConfig(
            fed=FedConfig(num_clients=64, **plain), **resnet),
        "smallcnn_cifar10.sim192": RoundConfig(
            fed=FedConfig(num_clients=192, **plain), **small),
        "smallcnn_cifar10.sim192_rotq4": RoundConfig(
            fed=FedConfig(num_clients=192, weighted=True, delta_layout="flat",
                          compression="rotq", rotq_bits=4, error_feedback=True), **small),
        "resnet18_cifar100.mesh4_sim256": RoundConfig(
            fed=FedConfig(num_clients=256, **plain), **resnet),
    }[cell_name]


@pytest.mark.parametrize("cell_name", CELLS)
def test_round_config_is_the_parents_object_field_for_field(cell_name):
    from benchmark import sut

    cell = _cell(cell_name)
    got = sut.round_config(cell.config, cell.traffic, cell.task)
    want = _parent_round_config(cell_name)
    assert dataclasses.asdict(got) == dataclasses.asdict(want)
    assert got == want


def test_a_program_overlay_sets_a_real_field():
    """``"program"`` in a configuration file or a traffic file states fields
    of the program's configuration by dataclass field name, the traffic file
    last: a field the program gains later needs no edit to ``sut.py``."""
    from benchmark import sut

    cell = _cell("smallcnn_cifar10.sim192")
    base = sut.round_config(cell.config, cell.traffic, cell.task)
    assert base.opt.nesterov is False and base.fed.server_optimizer == "none"
    config = dict(cell.config, program={"opt": {"nesterov": True},
                                        "fed": {"server_optimizer": "adam"}})
    traffic = dict(cell.traffic, program={"fed": {"server_optimizer": "momentum"},
                                          "round": {"debug_per_batch": True}})
    got = sut.round_config(config, traffic, cell.task)
    assert got.opt.nesterov is True and got.fed.server_optimizer == "momentum"
    assert got.debug_per_batch is True
    assert dataclasses.replace(
        got, debug_per_batch=False, opt=base.opt, fed=base.fed) == base


@pytest.mark.parametrize("overlay,names", [
    ({"opt": {"nesterovv": True}}, ("opt.nesterovv", "OptimizerConfig")),
    ({"round": {"fed": {}}}, ("round.fed", "RoundConfig")),
    ({"optimizer": {"nesterov": True}}, ("'optimizer'",)),
])
def test_an_unknown_program_field_is_an_error_that_names_it(overlay, names):
    from benchmark import sut

    cell = _cell("smallcnn_cifar10.sim192")
    with pytest.raises(ValueError) as err:
        sut.round_config(dict(cell.config, program=overlay), cell.traffic, cell.task)
    assert all(n in str(err.value) for n in names), str(err.value)


def test_a_numeric_initialiser_is_a_standard_deviation():
    from benchmark import seeded

    spec = [(("embed", "table"), (512, 64), 0.5), (("experts", "w"), (4, 64, 64), 0.02),
            (("head", "kernel"), (64, 8), "head"), (("head", "bias"), (8,), "zeros")]
    params, stats = seeded.make_weights(3, spec, [])
    assert float(np.std(params["embed"]["table"])) == pytest.approx(0.5, rel=0.02)
    assert float(np.std(params["experts"]["w"])) == pytest.approx(0.02, rel=0.02)
    assert float(np.std(params["head"]["kernel"])) == pytest.approx(
        0.1 * math.sqrt(2 / 64), rel=0.1)
    assert not np.any(params["head"]["bias"]) and stats == {}
    with pytest.raises(ValueError, match="glorot"):
        seeded.make_weights(3, [(("a",), (2, 2), "glorot")], [])
    with pytest.raises(ValueError, match="True"):
        seeded.make_weights(3, [(("a",), (2, 2), True)], [])


# -------------------- a task of another kind of data, by files alone
TOY_TASK = '''
"""Next-token prediction on rows of a seeded affine walk over the vocabulary:
ids [n, T], targets the ids shifted by one, mean cross-entropy over positions.
A sample is one row of T tokens."""
import jax, jax.numpy as jnp, numpy as np


def make_data(seed, cfg):
    from benchmark import seeded
    n, t, v = cfg["num_examples"], cfg["seq_len"], cfg["vocab"]
    start = np.asarray(jax.random.randint(seeded.key_of(seed, 1), (n,), 0, v))
    walk = [start]
    for _ in range(t):
        walk.append((5 * walk[-1] + 7) % v)
    ids = np.stack(walk, axis=1).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def loss(logits, targets):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def program_fields(cfg):
    return {"round": {"num_classes": cfg["vocab"]}, "data": {"dataset": "synthetic"}}
'''

TOY_REFERENCE = '''
"""A bigram model: an embedding table and a head."""
from benchmark.reference.layers import dense, ident


def spec(cfg):
    v, w = cfg["vocab"], cfg["width"]
    return [(("embed", "table"), (v, w), 1.0),
            (("head", "kernel"), (w, v), "head"),
            (("head", "bias"), (v,), "zeros")], []


def make_forward(cfg):
    def forward(params, stats, x, quant=ident):
        return dense(params["embed"]["table"][x], params["head"], quant), stats
    return forward
'''


# A task whose loss is no mean over equal blocks of rows: positions whose
# target is a multiple of 3 do not count (as positions past a document's end).
TOY_TASK_WITH_PARTS = TOY_TASK + '''

def loss_parts(logits, targets):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    keep = targets % 3 != 0
    return jnp.sum(jnp.where(keep, nll, 0.0)), jnp.sum(keep).astype(jnp.float32)


def loss(logits, targets):  # in place of the mean over all positions above
    total, count = loss_parts(logits, targets)
    return total / count
'''


def make_toy_cell(root, config=(), traffic=(), task=TOY_TASK):
    """A configuration of another task, added by files alone: a manifest and,
    beside its traffic/ and limits/, a task, a reference, a FLOP function;
    ``config`` and ``traffic`` are laid over the two files' keys."""
    from benchmark import run

    files = {
        "manifest.json": json.dumps({
            "configs": [{"name": "bigram_toy", "file": "toy/configs/bigram_toy.json"}],
            "workloads": [{"name": "bigram_toy.sim4", "config": "bigram_toy",
                           "traffic": "sim4", "chips": 1}]}),
        "toy/configs/bigram_toy.json": json.dumps(dict({
            "model": "bigram", "task": "next_token", "vocab": 64, "width": 32,
            "seq_len": 16, "num_examples": 256, "batch_size": 16,
            "optimizer": {"name": "sgd", "learning_rate": 0.3, "momentum": 0.9,
                          "weight_decay": 0.0}}, **dict(config))),
        "toy/traffic/sim4.json": json.dumps(dict({
            "clients": 4, "steps": 2, "mesh": False, "delta_layout": "per_leaf",
            "codec": None, "check_rounds": 3}, **dict(traffic))),
        "toy/limits/bigram_toy.sim4.json": json.dumps({"numbers": {}}),
        "toy/tasks/next_token.py": task,
        "toy/reference/bigram.py": TOY_REFERENCE,
        "toy/flops/bigram_toy.py":
            "def train_flops_per_sample(cfg):\n"
            "    return 6 * cfg['seq_len'] * cfg['width'] * cfg['vocab']\n",
    }
    for rel, text in files.items():
        os.makedirs(os.path.dirname(os.path.join(root, rel)), exist_ok=True)
        with open(os.path.join(root, rel), "w") as fh:
            fh.write(text)
    return run.Cell(os.path.join(root, "manifest.json"), "bigram_toy.sim4")


@pytest.fixture(scope="module")
def toy_cell(tmp_path_factory):
    return make_toy_cell(str(tmp_path_factory.mktemp("toy")))


def test_a_token_task_goes_through_the_reference_path(toy_cell):
    import jax

    from benchmark import check

    examples, targets, shards, initial = inputs = check.seeded_inputs(toy_cell, 11)
    assert examples.shape == targets.shape == (256, 16) and examples.dtype == np.int32
    assert np.array_equal(examples[:, 1:], targets[:, :-1])
    assert toy_cell.samples_per_round == 4 * 2 * 16
    assert toy_cell.flops.train_flops_per_sample(toy_cell.config) == 6 * 16 * 32 * 64
    reference, codec = check.follow_reference(toy_cell, 11, inputs, jax.devices()[:1])
    losses = reference["losses"]
    assert codec is None and len(losses) == 3
    assert losses[0] == pytest.approx(math.log(64), rel=0.02)
    assert losses[-1] < losses[0] - 0.5
    moved = check.sub(reference["last"]["params"], initial["params"])
    assert all(float(np.abs(l).max()) > 0 for l in jax.tree.leaves(moved))
    # and the numbers the check compares are defined for such a tree
    nums = check.numbers(initial, reference, reference)
    assert nums == {"loss_gap": 0.0, "update1_gap": 0.0, "update1_diff": 0.0,
                    "change_gap": 0.0}


def test_a_token_task_draws_from_the_seed(toy_cell):
    from benchmark import check

    a, b = check.seeded_inputs(toy_cell, 11), check.seeded_inputs(toy_cell, 11)
    c = check.seeded_inputs(toy_cell, 2**31 + 12)
    for x, y in zip(a[:3], b[:3]):
        assert all(np.array_equal(p, q) for p, q in zip(np.atleast_1d(x), np.atleast_1d(y)))
    assert np.array_equal(a[3]["params"]["embed"]["table"], b[3]["params"]["embed"]["table"])
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[3]["params"]["embed"]["table"],
                              c[3]["params"]["embed"]["table"])
    assert float(np.std(a[3]["params"]["embed"]["table"])) == pytest.approx(1.0, rel=0.05)


def test_the_toy_task_states_its_program_fields(toy_cell):
    """The token task's fields reach the program's configuration through the
    same overlay (the program has no token path yet: nothing is built)."""
    from benchmark import sut

    toy_cell.config.update(remat=False, activation_dtype="float32")
    cfg = sut.round_config(toy_cell.config, toy_cell.traffic, toy_cell.task)
    assert cfg.num_classes == 64 and cfg.data.dataset == "synthetic"
    assert cfg.model == "bigram" and cfg.fed.num_clients == 4 and cfg.steps_per_round == 2


# ------------- the reference at size: memory, blocks of rows, shards, leaves
def _toy_reference(cell, inputs, feed_spy=lambda: None):
    """``fedavg.Reference`` on a toy cell's inputs, as ``follow_reference``
    builds it; ``feed_spy`` is called at the head of every client's turn."""
    import jax

    from benchmark import seeded
    from benchmark.reference import fedavg

    examples, targets, shards, initial = inputs
    cfg, traffic = cell.config, cell.traffic

    def feed(c):
        feed_spy()
        rows = seeded.client_rows(shards[0][c], traffic["steps"], cfg["batch_size"])
        return examples[rows], targets[rows]

    return fedavg.Reference(
        cell.reference.make_forward(cfg), cell.task.loss, initial["params"],
        initial["stats"], cfg["optimizer"], feed, shards[1].sum(axis=1),
        jax.devices()[:1], block_rows=cfg.get("reference_block_rows"),
        loss_parts=getattr(cell.task, "loss_parts", None))


def _tree_bytes(tree):
    import jax

    return sum(np.asarray(l).nbytes for l in jax.tree.leaves(tree))


def _device_bytes(host_trees):
    """The bytes of the buffers behind JAX's live arrays. On the CPU an array
    put from the host, or copied to it, shares the host's buffer: a buffer is
    counted once, and one that ``host_trees`` view is the host's, as it would
    be beside a chip, and is not counted."""
    import gc

    import jax

    gc.collect()  # JAX lets go of what dropped host views held when this runs
    host = {l.ctypes.data for l in jax.tree.leaves(host_trees)}
    buffers = {a.unsafe_buffer_pointer(): a.nbytes for a in jax.live_arrays()}
    return sum(n for at, n in buffers.items() if at not in host)


@pytest.mark.parametrize("momentum,copies", [(0.9, 5), (0.0, 4)])
def test_the_references_device_memory_does_not_grow_with_the_clients(
        tmp_path, momentum, copies):
    """Between the clients' turns a device holds the global model and the
    running sum, at 2 clients as at 8; in a turn the epoch's own arguments,
    outputs and scratch come on top: five copies of the parameters in all
    with momentum, four without, and a step's activations."""
    import jax

    from benchmark import check

    sizes = {"vocab": 512, "width": 256, "seq_len": 8, "batch_size": 4,
             "num_examples": 64, "optimizer": {
                 "name": "sgd", "learning_rate": 0.3, "momentum": momentum,
                 "weight_decay": 0.0}}
    between, ref = {}, None
    for clients in (2, 8):
        cell = make_toy_cell(str(tmp_path / str(clients)), sizes,
                             {"clients": clients, "check_rounds": 2})
        inputs = check.seeded_inputs(cell, 5)
        del ref
        base = _device_bytes([])
        seen = []
        ref = _toy_reference(cell, inputs, lambda: seen.append(
            _device_bytes(ref.mom) - base))
        ref.round()
        ref.round()
        between[clients] = max(seen)
        assert len(seen) == 2 * clients
        held = [m for m in ref.mom if m is not None]
        assert len(held) == (clients if momentum else 0)
        assert all(isinstance(l, np.ndarray) for l in jax.tree.leaves(held))
    one = _tree_bytes(inputs[3]["params"])
    assert between[2] == between[8] and 2 * one <= between[2] < 2.01 * one
    xs, ys = ref.feed(0)
    mom = jax.tree.map(np.zeros_like, ref.params) if momentum else None
    mem = ref.epoch.lower(ref.params, ref.stats, mom, xs, ys).compile().memory_analysis()
    epoch = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # With the running sum. On top of the copies: the logits [4, 8, 512] and
    # their gradient, 0.13 of a copy, and one table's gradient, 0.5, which the
    # CPU's compiler keeps apart at momentum 0 (the chip's does not: PERF.md).
    assert epoch + one <= (copies + 0.6) * one, (
        {k: getattr(mem, k) for k in dir(mem) if k.endswith("in_bytes")}, one)


@pytest.mark.parametrize("task", [TOY_TASK, TOY_TASK_WITH_PARTS],
                         ids=["mean", "loss_parts"])
def test_a_step_in_blocks_of_rows_gives_the_whole_batchs_update(tmp_path, task):
    import jax

    from benchmark import check

    got = {}
    for name, extra in (("whole", {}), ("blocks", {"reference_block_rows": 4})):
        cell = make_toy_cell(str(tmp_path / name), extra, task=task)
        inputs = check.seeded_inputs(cell, 13)
        got[name], _ = check.follow_reference(cell, 13, inputs, jax.devices()[:1])
    initial = inputs[3]["params"]
    update = lambda run, at: check.sub(run[at]["params"], initial)
    for at in ("first", "last"):
        assert check.rel_diff(update(got["blocks"], at), update(got["whole"], at)) < 1e-6
    assert got["blocks"]["losses"] == pytest.approx(got["whole"]["losses"], rel=1e-6)
    if task is TOY_TASK_WITH_PARTS:
        # the blocks of a step do not weigh alike: equal weights would be wrong
        counts = (inputs[1][:16] % 3 != 0).reshape(4, -1).sum(axis=1)
        assert len(set(counts)) > 1
    cell.config["reference_block_rows"] = 5  # 16 rows do not cut into fives
    with pytest.raises(TypeError, match="reshape"):
        check.follow_reference(cell, 13, inputs, jax.devices()[:1])


@pytest.mark.parametrize("how", ["contiguous", "iid"])
def test_the_traffic_file_says_how_the_clients_shards_are_cut(how):
    from benchmark import check, seeded

    cell = _cell("smallcnn_cifar10.sim192")
    cell.config = dict(cell.config, num_examples=1024)
    cell.traffic = dict(cell.traffic, shards=how)
    idx, mask = check.seeded_inputs(cell, 1)[2]
    assert idx.shape == (192, 5) and idx.dtype == np.int32 and mask.all()
    if how == "contiguous":
        assert np.array_equal(idx, np.arange(960).reshape(192, 5))
    else:
        with open(os.path.join(HERE, "parent_digests.json")) as fh:
            recorded = json.load(fh)["cells"][cell.name]["1"]
        assert [_digest(idx), _digest(mask)] == recorded["shards"]
        assert np.array_equal(idx, seeded.make_shards(1, 1024, 192)[0])
    with pytest.raises(ValueError, match="dirichlet"):
        seeded.make_shards(1, 1024, 192, "dirichlet")


def _whole_tree_numbers(initial, program, reference):
    """``check.numbers`` as the parent of PR 28 worked it out, on whole
    float64 trees: the arithmetic the leaf-by-leaf one has to repeat."""
    import jax

    from benchmark import check

    f64 = lambda x: np.asarray(x, np.float64)
    sub = lambda a, b: jax.tree.map(lambda x, y: f64(x) - f64(y), a, b)
    add = lambda a, b: jax.tree.map(lambda x, y: f64(x) + f64(y), a, b)

    def rel_diff(p, r):
        d = sum(float(np.sum((x - y) ** 2))
                for x, y in zip(jax.tree.leaves(p), jax.tree.leaves(r)))
        n = sum(float(np.sum(y ** 2)) for y in jax.tree.leaves(r))
        return float(np.sqrt(d / n))

    out = {}
    pl, rl = np.array(program["losses"]), np.array(reference["losses"])
    out["loss_gap"] = float(np.max(np.abs(pl - rl) / np.abs(rl)))
    p_upd = sub(program["first"]["params"], initial["params"])
    r_upd = sub(reference["first"]["params"], initial["params"])
    if "mean_residual" in program["first"]:
        p_upd = add(p_upd, program["first"]["mean_residual"])
        r_upd = add(r_upd, reference["first"]["mean_residual"])
        pn = f64(program["first"]["residual_norms"])
        rn = f64(reference["first"]["residual_norms"])
        out["codec_residual_gap"] = float(np.max(np.abs(pn - rn) / rn))
    out["update1_gap"] = check.worst_leaf_gap(p_upd, r_upd)
    out["update1_diff"] = rel_diff(p_upd, r_upd)
    out["change_gap"] = check.worst_leaf_gap(
        sub(program["last"]["params"], initial["params"]),
        sub(reference["last"]["params"], initial["params"]))
    return out


@pytest.mark.parametrize("cell_name", ["smallcnn_tiny.sim4", "smallcnn_tiny.sim4_rotq4"])
def test_the_numbers_leaf_by_leaf_are_the_whole_trees(cell_name):
    """The fp8 control against the reference on a tiny cell: every number
    the same, to the last bit, whichever way the float64 leaves are held."""
    import jax

    from benchmark import check, run
    from benchmark.reference import lowprec

    cell = run.Cell(TINY, cell_name)
    inputs = check.seeded_inputs(cell, 21)
    args = (cell, 21, inputs, jax.devices()[:1])
    reference, _ = check.follow_reference(*args)
    low, _ = check.follow_reference(*args, quant=lowprec.fp8)
    nums = check.numbers(inputs[3], low, reference)
    assert nums == _whole_tree_numbers(inputs[3], low, reference)
    assert ("codec_residual_gap" in nums) == cell_name.endswith("rotq4")
    assert 0 < nums["update1_gap"] < 1 and 0 < nums["update1_diff"] < 1


def test_momentum_0_keeps_no_buffer_and_is_plain_sgd(tmp_path):
    """A round of the reference at momentum 0 against a hand-written one:
    every client from the global model, ``p -= lr * (g + wd * p)`` a step,
    the mean of the changes added (the shards are equal)."""
    import jax
    import jax.numpy as jnp

    from benchmark import check, seeded

    opt = {"name": "sgd", "learning_rate": 0.3, "momentum": 0.0, "weight_decay": 0.01}
    cell = make_toy_cell(str(tmp_path), {"optimizer": opt}, {"clients": 2})
    examples, targets, shards, initial = inputs = check.seeded_inputs(cell, 17)
    ref = _toy_reference(cell, inputs)
    loss, upd, _, _ = ref.round()
    assert ref.mom == [None, None]

    forward = cell.reference.make_forward(cell.config)
    mean_ce = lambda p, x, y: cell.task.loss(forward(p, {}, x)[0], y)
    start = jax.tree.map(jnp.asarray, initial["params"])
    changes, first_losses = [], []
    with jax.default_matmul_precision("highest"):
        for c in range(2):
            p = start
            for rows in seeded.client_rows(shards[0][c], 2, 16):
                ce, g = jax.value_and_grad(mean_ce)(p, examples[rows], targets[rows])
                p = jax.tree.map(lambda p, g: p - 0.3 * (g + 0.01 * p), p, g)
                first_losses.append(float(ce))
            changes.append(jax.tree.map(jnp.subtract, p, start))
    want = jax.tree.map(lambda a, b: 0.5 * a + 0.5 * b, *changes)
    assert check.rel_diff(upd, want) < 1e-6
    assert loss == pytest.approx(np.mean(first_losses), rel=1e-6)
    assert check.rel_diff(ref.params, jax.tree.map(jnp.add, start, want)) < 1e-6


def test_worst_leaf_gap_and_percentile():
    from benchmark import check, run

    ref = {"a": np.full(4, 2.0), "b": np.full(4, 1e-6), "c": np.full(4, 1.0)}
    same = check.worst_leaf_gap(ref, ref)
    frozen = check.worst_leaf_gap({k: np.zeros(4) for k in ref}, ref)
    assert same == 0.0 and frozen == pytest.approx(1.0)
    # a leaf whose change is all but zero is measured against the median leaf
    off = dict(ref, b=np.full(4, 3e-6))
    assert check.worst_leaf_gap(off, ref) == pytest.approx(4e-6 / 2.0, rel=1e-3)
    assert run.percentile([1, 2, 3, 4, 5], 0.5) == 3
    assert run.percentile(list(range(101)), 0.95) == 95


# ------------------------------------------ the reference against Federation
@pytest.fixture(scope="module")
def tiny_runs():
    """One run of each tiny cell through the harness, chip look-up skipped."""
    from benchmark import run

    cache = {}

    def get(cell):
        if cell not in cache:
            lines = []
            cache[cell] = (run.run(TINY, cell, 7, 0.3, False, need_tpu=False,
                                   out=lines.append), lines)
        return cache[cell]

    return get


@pytest.mark.parametrize("cell", [
    "smallcnn_tiny_f32.sim4", "smallcnn_tiny_f32.sim4_rotq4",
    "smallcnn_tiny.sim4", "smallcnn_tiny.sim4_rotq4"])
def test_reference_agrees_with_federation(tiny_runs, cell):
    """In float32 the program's first rounds equal the reference's to
    rounding (limits 1e-5..1e-4 in tiny/limits); in bfloat16 within the
    tiny cell's limits. For rotq4 the uncompressed mean change is recovered
    from what the server applied plus what the clients kept."""
    result, lines = tiny_runs(cell)
    assert result["correct"], "\n".join(lines)
    checks = [l for l in lines if l.startswith("check ") and "limit" in l]
    assert len(checks) >= 4 and all(l.endswith("ok") for l in checks)
    assert json.loads(lines[-1]) == result
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result["metrics"]) == {
        "samples_per_s_per_chip", "round_ms_p95", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] > 3


def _broken(monkeypatch, how):
    import jax
    import jax.numpy as jnp

    from benchmark import sut

    real = sut.step
    if how == "state_unchanged":
        def step(fed):
            saved = jax.tree.map(jnp.copy, fed.state)
            m = real(fed)
            fed.state = saved
            return m
    else:  # half of the clients' batches left out of every round
        def step(fed):
            for c in range(fed.cfg.fed.num_clients // 2):
                fed.set_alive(c, False)
            return real(fed)
    monkeypatch.setattr(sut, "step", step)


@pytest.mark.parametrize("how,number", [
    ("state_unchanged", "change_gap"), ("half_the_batch", "loss_gap")])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, how, number):
    from benchmark import run

    _broken(monkeypatch, how)
    lines = []
    result = run.run(TINY, "smallcnn_tiny.sim4", 9, 0.3, False, need_tpu=False,
                     out=lines.append)
    assert result["correct"] is False, "\n".join(lines)
    over = [l.split()[1] for l in lines if l.startswith("check ") and l.endswith("OVER")]
    assert number in over, lines


def test_the_lower_precision_controls_come_out_not_correct():
    """The reference in fp8 operands (and, for the codec, at half its bits)
    put in the program's place fails the tiny cell's limits, on three seeds."""
    from benchmark import control, run

    limits = run.Cell(TINY, "smallcnn_tiny.sim4_rotq4").limits
    rows, _ = control.readings(TINY, "smallcnn_tiny.sim4_rotq4", [31, 32, 33], 3,
                               program=False, need_tpu=False, out=lambda s: None)
    for row in rows:
        fp8, half = row["control_fp8"], row["control_half_bits"]
        assert fp8["update1_diff"] > limits["update1_diff"]["limit"], row
        assert half["codec_residual_gap"] > limits["codec_residual_gap"]["limit"], row
        # and fp8 leaves the norms' gaps small: they are held against other faults
        assert fp8["change_gap"] < limits["change_gap"]["limit"]


def test_resnet18_reference_forward_equals_the_programs():
    """The plain ResNet-18 against the program's module on seeded weights:
    logits and the BatchNorm statistics it hands back, float32, 8x8 inputs."""
    import jax

    from benchmark import seeded, sut
    from fedtpu import models

    cell = _cell("resnet18_cifar100.sim64")
    cfg = dict(cell.config, image_shape=[8, 8, 3])
    params, stats = seeded.make_weights(5, *cell.reference.spec(cfg))
    x = cell.task.make_data(5, dict(cfg, num_examples=4))[0]
    ours, our_stats = cell.reference.make_forward(cfg)(params, stats, x)
    model = models.create("resnet18", num_classes=100, remat=True)
    variables = model.init(jax.random.PRNGKey(0), x[:1], train=False)
    theirs, updated = model.apply(
        {"params": sut._like(variables["params"], params),
         "batch_stats": sut._like(variables["batch_stats"], stats)},
        x, train=True, mutable=["batch_stats"])
    assert np.allclose(np.asarray(ours), np.asarray(theirs), rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(our_stats),
                    jax.tree.leaves(sut.named(updated["batch_stats"]))):
        assert np.allclose(np.asarray(a), b, rtol=1e-4, atol=1e-6)
