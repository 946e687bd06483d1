"""The benchmark's own tests: the manifest, the refusal to run without a
TPU, the FLOP functions, the trace reduction on a recorded TPU trace, the
plain reference against ``Federation`` on the CPU, the lower-precision
control, and a run whose timed path is broken underneath.

Everything here runs on the CPU at a tiny size (``tiny/``: the real smallcnn,
4 clients, 2 steps of 32); times and rates come only from the chip.
"""

import json
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
                    f"benchmark/reference/{cfg['model']}.py"):
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
    assert got["engine.host_gap_share"] is None and got["device.peak_hbm_gb"] is None
    assert got["entry.compile_s"] == 1.5 and got["entry.compiles_in_window"] == 0.0
    # 6 x 6,128,896 FLOP x 5e5 samples/s over 197 TFLOP/s
    assert got["local_step.mfu"] == pytest.approx(100 * 36_773_376 * 5e5 / 197e12)
    ctx["trace"] = {"window_s": 2.0, "busy_s": 1.5, "collective_share": 0.25,
                    "idle_by_span": {"sync": 0.3, "_no_span_": 0.2}}
    ctx["chips"] = 4
    read = lambda n: run.load_py(os.path.join(
        ROOT, "benchmark", "layer_metrics", n + ".py")).read(ctx)
    assert read("device.idle_share") == pytest.approx(25.0)
    assert read("engine.host_gap_share") == pytest.approx(15.0)
    assert read("mesh.collective_share") == pytest.approx(25.0)


# ------------------------------------------------------------- seeds and numbers
def test_any_seed_gives_the_same_inputs_again():
    from benchmark import seeded

    big = 2**31 + 12345
    a = seeded.make_data(big, 64, (8, 8, 3), 10)
    b = seeded.make_data(big, 64, (8, 8, 3), 10)
    c = seeded.make_data(big + 1, 64, (8, 8, 3), 10)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert np.array_equal(a[0], a[0].astype("bfloat16").astype(np.float32))
    idx, mask = seeded.make_shards(big, 64, 4)
    assert sorted(idx.ravel()) == list(range(64)) and mask.all()
    rows = seeded.client_rows(idx[0], steps=3, batch=8)  # 24 rows from 16: cycles
    assert rows.shape == (3, 8) and np.array_equal(rows[2], idx[0][:8])


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
    x = seeded.make_data(5, 4, (8, 8, 3), 100)[0]
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
