"""bench.py measurement-path regression.

bench.py is the driver's headline artifact; a silent breakage there costs a
whole round of evidence. This runs ``_measure`` at a shrunk configuration on
the CPU platform (same code path as the chip: engine construction, AOT
compile of the fused multi-round program, cost analysis, timed dispatches)
and checks the JSON contract.
"""

import sys

import pytest


@pytest.fixture()
def bench(monkeypatch):
    monkeypatch.syspath_prepend(".")
    import bench as bench_mod

    monkeypatch.setattr(bench_mod, "NUM_CLIENTS", 4)
    monkeypatch.setattr(bench_mod, "STEPS_PER_ROUND", 2)
    monkeypatch.setattr(bench_mod, "BATCH", 8)
    monkeypatch.setattr(bench_mod, "TIMED_ROUNDS", 3)
    monkeypatch.setattr(bench_mod, "TRIALS", 2)
    return bench_mod


def test_measure_contract(bench):
    result = bench._measure()
    assert result["metric"].startswith("fedavg_client_epochs_per_sec")
    assert result["unit"] == "client-epochs/sec/chip"
    assert result["value"] > 0
    assert result["rounds_per_sec"] > 0
    # Normalisation: value = rounds/sec * clients / devices.
    assert result["value"] == pytest.approx(
        result["rounds_per_sec"] * result["num_clients"] / result["n_devices"],
        rel=1e-2,
    )
    # Both fields are independently rounded in the JSON (value to 3 dp,
    # vs_baseline to 4 dp), so compare with an absolute slack of one ulp
    # of the coarser rounding.
    assert result["vs_baseline"] == pytest.approx(
        result["value"] / bench.TARGET_PER_CHIP, abs=1e-3
    )
    # FLOPs come from the single-round program (scan-body accounting).
    assert result.get("flops_per_round", 0) > 0


def test_variant_run_is_self_distinguishing(bench, monkeypatch):
    """A variant bench artifact must be unmistakable even to a consumer
    keyed on 'metric' alone (ADVICE r5): suffixed metric, no vs_baseline.
    Exercises the labeling helper directly — re-running a full _measure for
    this would cost ~1 min of tier-1 budget for no extra coverage."""
    base = {"metric": bench.METRIC, "value": 1.0, "vs_baseline": 0.005}
    # Parity config: labels untouched.
    assert bench._apply_variant_labels(dict(base)) == base
    monkeypatch.setattr(bench, "_TIMED_ROUNDS_ENV", "3")
    result = bench._apply_variant_labels(dict(base))
    assert result["metric"] == bench.METRIC + "_variant"
    assert "vs_baseline" not in result
    assert result["variant"]["timed_rounds"] == bench.TIMED_ROUNDS
    monkeypatch.setattr(bench, "_TIMED_ROUNDS_ENV", "")
    monkeypatch.setattr(bench, "MOMENTUM_DTYPE", "bfloat16")
    result = bench._apply_variant_labels(dict(base))
    assert result["metric"].endswith("_variant")
    assert result["variant"]["momentum_dtype"] == "bfloat16"
    assert "timed_rounds" not in result["variant"]


def test_compression_microbench_contract(bench, monkeypatch):
    """--compression-microbench JSON contract at a seconds-scale config:
    dispatch counts present and the flat stage strictly cheaper than the
    per-leaf stage (the <=10% acceptance gate itself is pinned on a
    many-leaf model in tests/test_flat_layout.py)."""
    monkeypatch.setenv("FEDTPU_MB_MODEL", "smallcnn")
    monkeypatch.setenv("FEDTPU_MB_CLIENTS", "2")
    monkeypatch.setenv("FEDTPU_MB_REPS", "1")
    result = bench._compression_microbench()
    assert result["metric"] == "compression_packed_vs_per_leaf"
    assert result["num_leaves"] > 0
    assert result["padded_row"] % 128 == 0
    for kind in ("topk", "int8"):
        c = result["codecs"][kind]
        assert 0 < c["flat_dispatches"] < c["per_leaf_dispatches"]
        assert c["dispatch_ratio"] == pytest.approx(
            c["flat_dispatches"] / c["per_leaf_dispatches"], abs=1e-3
        )
        assert c["per_leaf_host_ms"] > 0 and c["flat_host_ms"] > 0
    assert result["value"] == max(
        c["dispatch_ratio"] for c in result["codecs"].values()
    )


def test_server_pipeline_microbench_contract(bench, monkeypatch, tmp_path):
    """--server-pipeline-microbench at a seconds-scale config: schema,
    artifact emission, and the parity bit the acceptance criterion leans on
    (the >=2x densenet/64-client gate itself is pinned by the committed
    artifacts/SERVER_PIPELINE_MICROBENCH.json run)."""
    import json as json_mod
    import os

    art = tmp_path / "artifacts"
    monkeypatch.setattr(bench, "ARTIFACTS_DIR", str(art))
    monkeypatch.setenv("FEDTPU_SPB_MODELS", "smallcnn")
    monkeypatch.setenv("FEDTPU_SPB_CLIENTS", "4")
    monkeypatch.setenv("FEDTPU_SPB_REPS", "1")
    result = bench._server_pipeline_microbench()
    assert result["metric"] == "server_pipeline_post_barrier"
    assert result["num_clients"] == 4
    assert result["headline_model"] == "smallcnn"
    m = result["models"]["smallcnn"]
    assert m["padded_row"] % 128 == 0
    assert m["barrier"]["post_barrier_s"] > 0
    assert m["stream"]["post_barrier_s"] > 0
    assert m["barrier"]["decode_ms_per_reply"] > 0
    assert m["stream"]["decode_h2d_ms_per_reply"] > 0
    assert m["barrier"]["host_delta_bytes"] > 0
    assert m["stream"]["host_delta_bytes"] > 0
    assert m["post_barrier_speedup"] == pytest.approx(
        m["barrier"]["post_barrier_s"] / m["stream"]["post_barrier_s"],
        rel=0.02,
    )
    # The two paths must agree BITWISE on the aggregated params — the
    # stream pipeline is a perf change, never a numerics change.
    assert m["mean_bit_identical"] is True
    assert result["value"] == m["post_barrier_speedup"]
    # Artifact written atomically next to the JSON line.
    path = os.path.join(str(art), "SERVER_PIPELINE_MICROBENCH.json")
    assert os.path.exists(path)
    with open(path) as f:
        assert json_mod.load(f) == result


def test_peak_lookup_covers_observed_device_kinds():
    """The one peak table (bench.py resolves MFU through it) knows the
    device_kind strings PJRT reports; an unknown kind off-TPU has no peak
    (on a TPU it raises — tests/test_perf_obs.py)."""
    from fedtpu.obs.profile import device_peaks

    assert device_peaks("TPU v5 lite")[0] == 197e12
    assert device_peaks("TPU v5e")[0] == 197e12
    assert device_peaks("TPU v4")[0] == 275e12
    assert device_peaks("weird accelerator")[0] is None


def test_acc_full_config_shape(monkeypatch):
    """The --acc-full harness mode must keep config 4's defining traits
    (reference ``BASELINE.json`` config 4: resnet18, cifar100, 5 local
    epochs) at the climbing-curve sizing both harnesses share — the torch
    row in ``artifacts/PARITY_ACC_FULL.jsonl`` was measured against exactly
    this shape, and a silent drift would desync the comparison."""
    monkeypatch.syspath_prepend(".")
    monkeypatch.delenv("FEDTPU_SMOKE", raising=False)
    import bench_parity

    (name, cfg), = list(bench_parity.acc_full_configs())
    assert name == "4_accfull_resnet18_cifar100h_4c_5ep"
    assert cfg.model == "resnet18"
    assert cfg.num_classes == 100
    assert cfg.data.dataset == "cifar100_hard"
    assert cfg.fed.local_epochs == 5
    assert cfg.fed.num_clients == 4
    assert cfg.fed.num_rounds == 12
    assert cfg.data.device_layout == "gather"  # committed-artifact semantics


def test_headline_without_tpu_fails_and_prints_no_number(
    bench, monkeypatch, capsys
):
    """No chip -> non-zero exit and NOTHING on stdout: no value-0.0 line,
    no live_* pointer to a stored measurement, no predicted_* key — a run
    that did not use the chip cannot look like one that did."""
    monkeypatch.delenv("FEDTPU_PEAK_FLOPS", raising=False)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "needs a TPU" in str(exc.value.code)


def test_bench_model_wrapper_smoke(tmp_path, monkeypatch):
    """tools/bench_model_tpu.py end-to-end at a seconds-scale CPU config —
    the wrapper gates a chip job, so a wrapper bug costs real chip time.
    FEDTPU_BM_PLATFORM=cpu pins the platform in the child."""
    import json as json_mod
    import os
    import subprocess
    import sys as sys_mod

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               FEDTPU_BM_PLATFORM="cpu", FEDTPU_BM_MODEL="mlp",
               FEDTPU_BM_DATASET="synthetic", FEDTPU_BM_CLIENTS="4",
               FEDTPU_BM_BATCH="8", FEDTPU_BM_STEPS="2",
               FEDTPU_BM_ROUNDS="2", FEDTPU_BM_OUT="SMOKE_BM_TEST.json")
    try:
        proc = subprocess.run(
            [sys_mod.executable, os.path.join(repo, "tools", "bench_model_tpu.py")],
            capture_output=True, text=True, timeout=300, env=env, cwd=repo,
        )
        assert proc.returncode == 0, proc.stderr[-500:]
        line = json_mod.loads(proc.stdout.strip().splitlines()[-1])
        assert line["metric"] == "fedavg_rounds_per_sec_synthetic_mlp_4clients_1chip"
        assert line["rounds_per_sec"] > 0
        assert "error" not in line
        art = os.path.join(repo, "artifacts", "SMOKE_BM_TEST.json")
        assert os.path.exists(art)
    finally:
        try:
            os.remove(os.path.join(repo, "artifacts", "SMOKE_BM_TEST.json"))
        except OSError:
            pass


def test_obs_plane_microbench_contract(bench, monkeypatch, tmp_path):
    """--obs-plane-microbench at a seconds-scale config: schema + artifact
    emission (the <=1%-on-densenet acceptance gate itself is pinned by the
    committed artifacts/OBS_PLANE_MICROBENCH.json run)."""
    import json as json_mod
    import os

    art = tmp_path / "artifacts"
    monkeypatch.setattr(bench, "ARTIFACTS_DIR", str(art))
    monkeypatch.setenv("FEDTPU_OB_MODEL", "mlp")
    monkeypatch.setenv("FEDTPU_OB_ROUNDS", "2")
    monkeypatch.setenv("FEDTPU_OB_REPS", "2")
    result = bench._obs_plane_microbench()
    assert result["metric"] == "obs_plane_overhead"
    assert result["value"] > 0
    assert result["per_rpc_us"]["inject"] > 0
    assert result["per_rpc_us"]["extract"] > 0
    assert result["per_round_status_us"] > 0
    # The attributable arithmetic is auditable from its own parts.
    clients = result["num_clients"]
    per_round = clients * (
        result["per_rpc_us"]["inject"] + result["per_rpc_us"]["extract"]
    ) + result["per_round_status_us"]
    assert result["per_round_obs_us"] == pytest.approx(per_round, rel=1e-3)
    assert result["gate_pct"] == 1.0
    assert isinstance(result["passes_gate"], bool)
    assert result["noise_floor_pct"] >= 0
    assert set(result["round_ms"]) == {"bare", "obs"}
    assert all(v > 0 for v in result["round_ms"].values())
    path = os.path.join(str(art), "OBS_PLANE_MICROBENCH.json")
    with open(path) as f:
        assert json_mod.load(f) == result


def test_chaos_overhead_microbench_contract(bench, monkeypatch, tmp_path):
    """--chaos-overhead-microbench at a seconds-scale config: schema +
    artifact emission (the <=1%-on-densenet acceptance gate itself is
    pinned by the committed artifacts/CHAOS_OVERHEAD_MICROBENCH.json run).
    """
    import json as json_mod
    import os

    art = tmp_path / "artifacts"
    monkeypatch.setattr(bench, "ARTIFACTS_DIR", str(art))
    monkeypatch.setenv("FEDTPU_CH_MODEL", "mlp")
    monkeypatch.setenv("FEDTPU_CH_ROUNDS", "2")
    monkeypatch.setenv("FEDTPU_CH_REPS", "2")
    result = bench._chaos_overhead_microbench()
    assert result["metric"] == "chaos_overhead"
    assert result["value"] > 0
    assert result["per_rpc_us"]["decide"] > 0
    # The attributable arithmetic is auditable from its own parts:
    # two consults (StartTrain + SendModel) per client per round.
    per_round = result["num_clients"] * 2 * result["per_rpc_us"]["decide"]
    assert result["per_round_chaos_us"] == pytest.approx(per_round, rel=1e-3)
    assert result["gate_pct"] == 1.0
    assert isinstance(result["passes_gate"], bool)
    assert result["noise_floor_pct"] >= 0
    assert set(result["round_ms"]) == {"bare", "chaos"}
    assert all(v > 0 for v in result["round_ms"].values())
    path = os.path.join(str(art), "CHAOS_OVERHEAD_MICROBENCH.json")
    with open(path) as f:
        assert json_mod.load(f) == result


def test_screening_overhead_microbench_contract(bench, monkeypatch, tmp_path):
    """--screening-overhead-microbench at a seconds-scale config: schema +
    artifact emission (the <=1%-on-densenet acceptance gate itself is
    pinned by the committed artifacts/SCREENING_MICROBENCH.json run)."""
    import json as json_mod
    import os

    art = tmp_path / "artifacts"
    monkeypatch.setattr(bench, "ARTIFACTS_DIR", str(art))
    monkeypatch.setenv("FEDTPU_SC_MODEL", "mlp")
    monkeypatch.setenv("FEDTPU_SC_ROUNDS", "2")
    monkeypatch.setenv("FEDTPU_SC_REPS", "2")
    result = bench._screening_overhead_microbench()
    assert result["metric"] == "screening_overhead"
    assert result["value"] > 0
    assert result["per_round_screen_us"] > 0
    assert result["padded_row"] % 128 == 0
    # The attributable arithmetic is auditable from its own parts.
    assert result["value"] == pytest.approx(
        result["per_round_screen_us"]
        / (result["round_ms"]["bare"] * 1e3) * 100.0,
        rel=1e-2,
    )
    assert result["gate_pct"] == 1.0
    assert isinstance(result["passes_gate"], bool)
    assert result["noise_floor_pct"] >= 0
    assert set(result["round_ms"]) == {"bare", "screen"}
    assert all(v > 0 for v in result["round_ms"].values())
    path = os.path.join(str(art), "SCREENING_MICROBENCH.json")
    with open(path) as f:
        assert json_mod.load(f) == result


def test_fencing_overhead_microbench_contract(bench, monkeypatch, tmp_path):
    """--fencing-overhead-microbench at a seconds-scale config: schema +
    artifact emission (the <=1%-on-densenet acceptance gate itself is
    pinned by the committed artifacts/FENCING_MICROBENCH.json run)."""
    import json as json_mod
    import os

    art = tmp_path / "artifacts"
    monkeypatch.setattr(bench, "ARTIFACTS_DIR", str(art))
    monkeypatch.setenv("FEDTPU_FE_MODEL", "mlp")
    monkeypatch.setenv("FEDTPU_FE_ROUNDS", "2")
    monkeypatch.setenv("FEDTPU_FE_REPS", "2")
    result = bench._fencing_overhead_microbench()
    assert result["metric"] == "fencing_overhead"
    assert result["value"] > 0
    assert result["per_rpc_us"]["inject_validate"] > 0
    # The attributable arithmetic is auditable from its own parts:
    # StartTrain + SendModel per client, plus ping + replica push.
    assert result["rpcs_per_round"] == result["num_clients"] * 2 + 2
    per_round = result["rpcs_per_round"] * result["per_rpc_us"]["inject_validate"]
    assert result["per_round_fencing_us"] == pytest.approx(per_round, rel=1e-3)
    assert result["gate_pct"] == 1.0
    assert isinstance(result["passes_gate"], bool)
    assert result["noise_floor_pct"] >= 0
    assert set(result["round_ms"]) == {"bare", "fenced"}
    assert all(v > 0 for v in result["round_ms"].values())
    path = os.path.join(str(art), "FENCING_MICROBENCH.json")
    with open(path) as f:
        assert json_mod.load(f) == result


def test_fencing_microbench_committed_gate():
    """The committed densenet-scale artifact must actually pass the <=1%
    gate: per-RPC epoch inject + fence validation across every fenced RPC
    a synchronous round issues."""
    result = _committed_artifact("FENCING_MICROBENCH.json")
    assert result["metric"] == "fencing_overhead"
    assert result["model"] == "densenet_cifar"
    assert result["passes_gate"] is True
    assert result["value"] <= 1.0


def test_checkpoint_overhead_microbench_contract(bench, monkeypatch, tmp_path):
    """--checkpoint-overhead-microbench at a seconds-scale config: schema
    + artifact emission (the <=1%-on-densenet acceptance gate itself is
    pinned by the committed artifacts/CHECKPOINT_MICROBENCH.json run)."""
    import json as json_mod
    import os

    art = tmp_path / "artifacts"
    monkeypatch.setattr(bench, "ARTIFACTS_DIR", str(art))
    monkeypatch.setenv("FEDTPU_CK_MODEL", "mlp")
    monkeypatch.setenv("FEDTPU_CK_ROUNDS", "2")
    monkeypatch.setenv("FEDTPU_CK_REPS", "2")
    monkeypatch.setenv("FEDTPU_CK_SAVES", "4")
    result = bench._checkpoint_overhead_microbench()
    assert result["metric"] == "checkpoint_overhead"
    assert result["value"] > 0
    # The attributable arithmetic is auditable from its own parts.
    assert result["value"] == pytest.approx(
        result["per_save_ms"]["async_call"]
        / result["round_ms"]["bare"] * 100.0,
        rel=1e-2,
    )
    # The split the background writer exists for: the loop-side call must
    # be far cheaper than the full inline save it replaces, and the
    # writer-side write wall is reported so the overlap claim is
    # auditable.
    assert result["per_save_ms"]["async_call"] < result["per_save_ms"]["sync_full"]
    assert result["per_save_ms"]["writer_write"] > 0
    assert result["checkpoint_bytes"] > 0
    assert result["gate_pct"] == 1.0
    assert isinstance(result["passes_gate"], bool)
    assert result["noise_floor_pct"] >= 0
    assert set(result["round_ms"]) == {"bare", "ckpt"}
    assert all(v > 0 for v in result["round_ms"].values())
    path = os.path.join(str(art), "CHECKPOINT_MICROBENCH.json")
    with open(path) as f:
        assert json_mod.load(f) == result


def test_checkpoint_microbench_committed_gate():
    """The committed densenet-scale artifact must actually pass the <=1%
    gate: loop-side cost of one background save per round."""
    result = _committed_artifact("CHECKPOINT_MICROBENCH.json")
    assert result["metric"] == "checkpoint_overhead"
    assert result["model"] == "densenet_cifar"
    assert result["passes_gate"] is True
    assert result["value"] <= 1.0


def test_disaster_soak_artifact_contract():
    """Schema + gate contract of the committed total-process-loss drill
    (tools/chaos_soak.py --disaster): the durability PR's acceptance
    evidence. The soak re-runs as `slow` (tests/test_disaster.py); this
    pins what it must have proven."""
    result = _committed_artifact("DISASTER_SOAK.json")
    assert result["ok"] is True
    cfg = result["config"]
    assert cfg["rounds"] >= 16
    assert 4 <= cfg["kill_round"] <= cfg["rounds"] - 2
    # The restart fell back past BOTH silently-corrupted generations
    # (torn newest + bit-rotten next) to the newest verified one — the
    # restore-time verification counter proves the fallback path ran.
    assert result["checkpoint_fallbacks"] == 2
    assert result["resume_round"] == cfg["expected_resume_round"]
    # Exact-cover monotone lineage under supersession: the crash voided
    # the never-durable tail; durable history + restart covers 0..N-1.
    lineage = result["lineage"]
    assert lineage["strictly_monotone"] and lineage["exact_cover"]
    assert lineage["committed"] == cfg["rounds"]
    assert lineage["superseded"] == cfg["kill_round"] - result["resume_round"]
    # Survivors resynced with no re-registration and no manual cleanup.
    assert result["post_restart_joins"] == 0
    assert result["manual_interventions"] == 0
    assert result["gen1_rc"] != 0 and result["gen2_rc"] == 0
    # The recovery was trajectory-neutral: bit-identical final model.
    assert result["bit_identical_vs_control"] is True
    assert (
        result["model_fingerprint"]["disaster"]
        == result["model_fingerprint"]["control"]
    )
    assert result["final_round"]["disaster"] == cfg["rounds"] - 1
    for e in result["final_evals"]:
        assert e["loss"] == e["loss"]


def test_partition_soak_artifact_contract():
    """Schema + gate contract of the committed three-leg partition-heal
    soak (tools/chaos_soak.py --partition): the split-brain-elimination
    PR's acceptance evidence. The soak re-runs as `slow`
    (tests/test_fencing.py); this pins what it must have proven."""
    result = _committed_artifact("PARTITION_SOAK.json")
    assert result["ok"] is True and result["soak"] == "partition"
    legs = result["legs"]
    assert set(legs) == {"symmetric", "asymmetric", "gray"}
    for leg in legs.values():
        assert leg["ok"] is True
        # Zero transient client deaths; a real fence + live rejection.
        assert leg["client_deaths"] == 0
        assert leg["fences"] >= 1
        assert leg["stale_rejections"] >= 1
        assert leg["acting_rounds"] >= 1
        # Bounded failover churn, every promotion eventually demoted.
        assert 1 <= leg["promotions"] <= 8
        assert leg["demotions"] == leg["promotions"]
        # The fenced side re-based PAST the winner (1 -> 2 -> >= 3).
        assert leg["final_epoch"] >= 3
    # Symmetric: cut side never forked, and the heal was
    # trajectory-neutral (bit-identical to the no-partition control).
    sym = legs["symmetric"]
    assert sym["bit_identical_vs_control"] is True
    assert sym["stale_fork_rounds"] == 0 and sym["promotions"] == 1
    # Asymmetric: a REAL split-brain — the stale primary committed >= 1
    # forked round that the epoch-supersession fold voided.
    assert legs["asymmetric"]["stale_fork_rounds"] >= 1


def test_byzantine_soak_artifact_contract():
    """Schema + gate contract of the committed 100-round Byzantine soak
    (tools/chaos_soak.py --byzantine): the attack-harness PR's acceptance
    evidence. The soak re-runs as `slow` (tests/test_byzantine.py); this
    pins what it must have proven."""
    result = _committed_artifact("BYZANTINE_SOAK.json")
    assert result["ok"] is True
    cfg = result["config"]
    assert cfg["rounds"] >= 100
    assert cfg["malicious"] >= round(0.28 * cfg["clients"])  # ~30% regime
    assert cfg["error_p"] >= 0.10                            # + wire faults
    # Monotone lineage, no lost rounds.
    lineage = result["lineage"]
    assert lineage["committed"] == cfg["rounds"]
    assert lineage["exact_cover"]
    obs = result["observed"]
    # Zero honest deaths; every attacker quarantined AND evicted through
    # the live membership machinery; no honest eviction, no honest client
    # left quarantined.
    assert obs["client_deaths"] == 0
    assert obs["quarantines"] >= cfg["malicious"]
    assert obs["evictions_quarantine"] == cfg["malicious"]
    assert result["attackers_still_members"] == []
    assert result["honest_evicted"] == []
    assert result["honest_quarantined_at_end"] == []
    # Every layer demonstrably fired: attacks, screening, wire chaos,
    # retries.
    assert obs["attack_injected"] > 0
    assert obs["screening_rejected"] >= cfg["malicious"]
    assert obs["chaos_injected"] > 0 and obs["rpc_retries"] > 0
    # Honest clients finished with finite evals.
    assert len(result["honest_final_evals"]) == cfg["clients"] - cfg["malicious"]
    for e in result["honest_final_evals"]:
        assert e["loss"] == e["loss"]


def test_cohort_scale_contract(bench, monkeypatch, tmp_path):
    """--cohort-scale at a seconds-scale config: schema + artifact emission
    and the two claims the acceptance criterion leans on — per-seat device
    state grows with the cohort, and is byte-identical under a different
    population (O(cohort), not O(population)). The 10k-clients-per-round
    gate itself is pinned by the committed artifacts/COHORT_SCALE.json run.
    """
    import json as json_mod
    import os

    art = tmp_path / "artifacts"
    monkeypatch.setattr(bench, "ARTIFACTS_DIR", str(art))
    monkeypatch.setenv("FEDTPU_CS_MODEL", "mlp_tiny")
    monkeypatch.setenv("FEDTPU_CS_POPULATION", "256")
    monkeypatch.setenv("FEDTPU_CS_COHORTS", "16,32")
    monkeypatch.setenv("FEDTPU_CS_ROUNDS", "1")
    monkeypatch.setenv("FEDTPU_CS_EXAMPLES", "1024")
    result = bench._cohort_scale()
    assert result["metric"] == "cohort_scale"
    assert result["population"] == 256
    assert result["value"] == 32  # largest cohort actually ran, fully live
    assert [p["cohort"] for p in result["curve"]] == [16, 32]
    for p in result["curve"]:
        assert p["clients_per_round"] == p["cohort"]  # everyone available
        assert p["round_s"] > 0 and p["clients_per_sec"] > 0
        assert p["seat_state_bytes"] > 0 and p["host_table_bytes"] > 0
        assert p["heterogeneity_index"] > 0  # the default scenario is skewed
    a, b = result["curve"]
    assert b["seat_state_bytes"] == 2 * a["seat_state_bytes"]  # O(cohort)
    mm = result["memory_model"]
    assert mm["o_cohort"] is True
    assert (
        mm["seat_state_bytes_full_population"]
        == mm["seat_state_bytes_half_population"]
    )
    path = os.path.join(str(art), "COHORT_SCALE.json")
    with open(path) as f:
        assert json_mod.load(f) == result


def test_telemetry_microbench_contract(bench, monkeypatch, tmp_path):
    """--telemetry-microbench at a seconds-scale config: schema, artifact
    emission, and a valid trace-check leg (the <1%-on-densenet acceptance
    gate itself is pinned by the committed
    artifacts/TELEMETRY_MICROBENCH.json run)."""
    import json as json_mod
    import os

    art = tmp_path / "artifacts"
    monkeypatch.setattr(bench, "ARTIFACTS_DIR", str(art))
    monkeypatch.setenv("FEDTPU_TB_MODEL", "mlp")
    monkeypatch.setenv("FEDTPU_TB_ROUNDS", "2")
    monkeypatch.setenv("FEDTPU_TB_REPS", "1")
    result = bench._telemetry_microbench()
    assert result["metric"] == "telemetry_overhead"
    # Headline = attributable basic-mode cost: positive, and a real span
    # (trace) can never be cheaper than the no-op path it replaces.
    assert result["value"] == result["attributable_pct"]["basic"] > 0
    assert result["per_round_instrument_us"]["trace"] > \
        result["per_round_instrument_us"]["basic"]
    assert result["noise_floor_pct"] >= 0
    assert set(result["ab_delta_pct"]) == {"basic", "trace"}
    assert set(result["round_ms"]) == {"off", "basic", "trace"}
    assert all(v > 0 for v in result["round_ms"].values())
    assert result["instrument_ns"]["counter_inc"] > 0
    tc = result["trace_check"]
    assert tc["rounds"] == 2
    assert tc["nonnegative_durations"] is True
    assert tc["phases_nest_under_round"] is True
    assert all(v > 0 for v in tc["phase_span_counts"].values())
    # Both artifacts written.
    assert os.path.exists(os.path.join(str(art), "TELEMETRY_TRACE.json"))
    path = os.path.join(str(art), "TELEMETRY_MICROBENCH.json")
    with open(path) as f:
        assert json_mod.load(f) == result


def _committed_artifact(name):
    import json as json_mod
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "artifacts", name)
    assert os.path.exists(path), f"committed artifact {name} missing"
    with open(path) as f:
        return json_mod.load(f)


def test_churn_soak_artifact_contract():
    """Schema + gate contract of the committed 1k-round churn-soak
    artifact (tools/chaos_soak.py --churn): the elastic-membership PR's
    acceptance evidence. The soak itself re-runs as a `slow` test
    (tests/test_membership.py); this pins what it must have proven."""
    result = _committed_artifact("CHURN_SOAK.json")
    assert result["ok"] is True
    cfg = result["config"]
    assert cfg["rounds"] >= 1000
    assert 0 < cfg["upgrade_round"] < cfg["rounds"]
    # Monotone lineage: every round committed exactly once across the
    # three coordinator generations.
    lineage = result["lineage"]
    assert lineage["committed"] == cfg["rounds"]
    assert lineage["strictly_monotone"] and lineage["exact_cover"]
    gens = result["generations"]
    assert gens["gen1"] == cfg["upgrade_round"]
    assert gens["acting"] >= 1 and gens["gen2"] >= 1
    assert sum(gens.values()) == cfg["rounds"]
    # Zero transient deaths: every observed death is a scheduled silent
    # leave; the chaos layer injected + the retry layer absorbed.
    obs = result["observed"]
    assert obs["client_deaths"] == result["expected_silent_deaths"]
    assert obs["chaos_injected"] > 0 and obs["rpc_retries"] > 0
    assert obs["round_aborts"] == 0
    # Churn actually churned, through the real Join/Leave RPCs.
    sched = result["scheduled"]
    assert min(sched["join"], sched["silent_leave"],
               sched["stale_rejoin"], sched["leave"], sched["rejoin"]) > 0
    assert obs["membership_joins"] == sched["join"] + sched["rejoin"]
    assert obs["membership_evictions"] == sched["leave"]
    # Zero lost rounds across the upgrade: bit-identical to the
    # unupgraded control, per-client round counts equal.
    assert result["bit_identical_vs_control"] is True
    counts = result["client_round_counts"]
    assert counts["control"] == counts["upgraded"]
    # Flat memory profile from the /statusz RSS gauge.
    mem = result["memory"]
    assert mem["settled_samples"] >= 8
    assert mem["growth_pct"] < 8.0
    assert mem["gate"].endswith("(enforced)")


def test_rolling_upgrade_artifact_contract():
    """Schema contract of the committed rolling-upgrade drill artifact
    (tools/rolling_upgrade.py): zero-loss + bit-identical handover."""
    result = _committed_artifact("ROLLING_UPGRADE.json")
    assert result["ok"] is True
    cfg = result["config"]
    lineage = result["lineage"]
    assert lineage["committed"] == cfg["rounds"]
    assert lineage["strictly_monotone"] and lineage["exact_cover"]
    gens = result["generations"]
    assert gens["gen1"] == cfg["upgrade_round"] and gens["acting"] >= 1
    assert result["bit_identical"] is True
    counts = result["client_round_counts"]
    assert counts["control"] == counts["upgraded"]
    # The mid-run joiner is in the final roster (one more than startup).
    assert result["roster"]["upgraded"]["size"] == cfg["clients"] + 1


# ------------------------------------------- performance observatory legs
@pytest.mark.slow
def test_mfu_profile_schema_contract(monkeypatch, tmp_path):
    """``bench.py --mfu-profile`` schema at a CPU smoke config: the sweep
    rows carry the timing + cost-analysis + roofline keys the MFU_PROFILE_*
    consumers read. The wrapper reloads tools/bench_profile_tpu so the
    FEDTPU_SMOKE/PLATFORM knobs bind; here we drive run() directly at an
    even smaller shape and redirect its artifact dir via __file__."""
    import importlib
    import json as json_mod
    import os

    monkeypatch.setenv("FEDTPU_PLATFORM", "cpu")
    monkeypatch.setenv("FEDTPU_SMOKE", "1")  # float32, no traced dispatch
    # Peak overrides so the roofline block derives on the CPU backend.
    monkeypatch.setenv("FEDTPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("FEDTPU_PEAK_HBM_BYTES", "5e10")
    tools = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
    )
    monkeypatch.syspath_prepend(tools)
    import bench_profile_tpu as bpt

    bpt = importlib.reload(bpt)  # bind the smoke constants
    monkeypatch.setattr(bpt, "NUM_CLIENTS", 2)
    monkeypatch.setattr(bpt, "STEPS_PER_ROUND", 1)
    monkeypatch.setattr(bpt, "TIMED_ROUNDS", 2)
    monkeypatch.setattr(bpt, "BATCHES", (8,))
    monkeypatch.setattr(bpt, "TRIALS", 1)
    assert bpt.TRACE_DISPATCH is False  # smoke default: no CPU op-trace
    # run() roots the artifacts dir off __file__ — point it into tmp.
    monkeypatch.setattr(
        bpt, "__file__", str(tmp_path / "tools" / "bench_profile_tpu.py")
    )
    result = bpt.run(tag="pytest")
    assert result["timed_rounds_per_dispatch"] == 2
    assert result["num_clients"] == 2
    assert result["steps_per_round"] == 1
    assert len(result["configs"]) == 1
    row = result["configs"][0]
    assert row["batch"] == 8
    assert row["rounds_per_sec"] > 0
    assert row["sec_per_fused_dispatch"] > 0
    assert len(row["trial_times_s"]) == 1
    assert row["device_kind"]
    assert row["flops_per_round"] > 0 and row["bytes_per_round"] > 0
    # Shared peak-table/roofline path (fedtpu.obs.profile): with peaks
    # overridden the MFU + roofline placement must all derive.
    assert 0 < row["mfu"] < 1
    assert row["hbm_util"] > 0
    assert row["arith_intensity_flops_per_byte"] == pytest.approx(
        row["flops_per_round"] / row["bytes_per_round"], rel=1e-2
    )
    assert row["ridge_point_flops_per_byte"] == pytest.approx(20.0)
    assert row["roofline_bound"] in ("compute", "bandwidth")
    assert row["roofline_utilization"] > 0
    # Incremental artifact persist landed in the redirected dir.
    with open(tmp_path / "artifacts" / "MFU_PROFILE_pytest.json") as fh:
        assert json_mod.load(fh) == result


@pytest.mark.slow
def test_mfu_microbench_contract(bench, monkeypatch, tmp_path):
    """``bench.py --mfu-microbench`` at a seconds-scale mlp config: schema,
    artifact emission, and the estimator invariants (attributable cost =
    per-round accounting over the bare round wall; the densenet-scale <=1%
    gate itself is pinned by the committed artifact in test_perf_obs.py)."""
    import json as json_mod
    import os

    art = tmp_path / "artifacts"
    monkeypatch.setattr(bench, "ARTIFACTS_DIR", str(art))
    monkeypatch.setenv("FEDTPU_MF_MODEL", "mlp")
    monkeypatch.setenv("FEDTPU_MF_CLIENTS", "2")
    monkeypatch.setenv("FEDTPU_MF_ROUNDS", "2")
    monkeypatch.setenv("FEDTPU_MF_REPS", "1")
    monkeypatch.setenv("FEDTPU_MF_BATCH", "8")
    result = bench._mfu_microbench()
    assert result["metric"] == "mfu_accounting_overhead"
    assert result["gate_pct"] == 1.0
    assert result["value"] > 0
    assert result["passes_gate"] == (result["value"] <= 1.0)
    assert result["per_round_accounting_us"] > 0
    assert result["value"] == pytest.approx(
        result["per_round_accounting_us"]
        / (result["round_ms"]["off"] * 1e3) * 100.0,
        rel=0.05,
    )
    assert result["cost_model_build_s"] > 0
    assert result["flops_per_round"] > 0
    assert result["flops_source"] in ("analytic", "xla")
    # FEDTPU_PEAK_FLOPS defaulted in by the bench: the full gauge path ran.
    assert result["sample_mfu"] is not None and result["sample_mfu"] > 0
    assert result["model"] == "mlp" and result["num_clients"] == 2
    assert set(result["round_ms"]) == {"off", "mfu"}
    with open(art / "MFU_ACCOUNTING_MICROBENCH.json") as fh:
        assert json_mod.load(fh) == result


# ------------------------------------------------------ variant labels
def test_variant_labels_cover_perf_knobs(bench, monkeypatch):
    """A variant run must be self-distinguishing: suffixed metric, no
    vs_baseline, knob values recorded in the variant block — and the block
    says the dtype the headline config really computes in."""
    base = {"metric": bench.METRIC, "value": 1.0, "vs_baseline": 0.005}
    assert bench._apply_variant_labels(dict(base)) == base
    monkeypatch.setattr(bench, "MOMENTUM_DTYPE", "bfloat16")
    result = bench._apply_variant_labels(dict(base))
    assert result["metric"] == bench.METRIC + "_variant"
    assert "vs_baseline" not in result
    assert result["variant"]["momentum_dtype"] == "bfloat16"
    assert result["variant"]["dtype"] == bench.headline_config().dtype


# --------------------------------------------- hierarchical fan-in (PR 14)
def test_fanin_microbench_contract(bench, monkeypatch, tmp_path):
    """--fanin-microbench at a seconds-scale config: schema + artifact
    emission over REAL localhost gRPC aggregators (the 10k-clients/round
    acceptance gate itself is pinned by the committed
    artifacts/FANIN_MICROBENCH.json run)."""
    import json as json_mod
    import os

    art = tmp_path / "artifacts"
    monkeypatch.setattr(bench, "ARTIFACTS_DIR", str(art))
    monkeypatch.setenv("FEDTPU_FB_DIM", "4096")
    monkeypatch.setenv("FEDTPU_FB_COHORT", "40")
    monkeypatch.setenv("FEDTPU_FB_AGGS", "2,4")
    monkeypatch.setenv("FEDTPU_FB_FIXED_AGGS", "2")
    monkeypatch.setenv("FEDTPU_FB_COHORTS", "20,40")
    monkeypatch.setenv("FEDTPU_FB_ROUNDS", "2")
    result = bench._fanin_microbench()
    assert result["metric"] == "fanin_microbench"
    assert result["flat_coords"] == 4096
    assert result["rounds_per_config"] == 2
    scale_out = result["sweeps"]["scale_out_fixed_cohort"]
    fan_in = result["sweeps"]["fan_in_fixed_aggregators"]
    assert [r["aggregators"] for r in scale_out] == [2, 4]
    assert [r["cohort"] for r in scale_out] == [40, 40]
    assert [r["cohort"] for r in fan_in] == [20, 40]
    for row in scale_out + fan_in:
        # Every simulated client produced a decoded reply each round.
        assert row["clients"] == row["aggregators"] * row["cohort"]
        assert row["serial_wall_s"] > 0
        assert row["root_decode_combine_s"] > 0
        assert row["leaf_max_s"] > 0
        # The deployed-topology wall: root work + slowest single leaf.
        assert row["critical_path_s"] == pytest.approx(
            row["root_decode_combine_s"] + row["leaf_max_s"], rel=0.01
        )
        assert row["critical_path_s"] <= row["serial_wall_s"]
    gates = result["gates"]
    assert gates["critical_path_sublinear"] == (
        gates["critical_path_exponent_vs_clients"] < 1.0
    )
    assert gates["root_work_o_aggregators"] == (
        gates["root_work_ratio_across_cohort_growth"] < 2.0
    )
    assert result["value"] == gates["critical_path_exponent_vs_clients"]
    path = os.path.join(str(art), "FANIN_MICROBENCH.json")
    assert os.path.exists(path)
    with open(path) as f:
        assert json_mod.load(f) == result


def test_fanin_microbench_committed_gate():
    """The committed artifact is the PR's acceptance evidence: 10k
    simulated clients/round through a real-gRPC 2-tier topology, root
    decode+combine work O(aggregators) not O(clients), and round
    wall-clock sublinear in total clients."""
    result = _committed_artifact("FANIN_MICROBENCH.json")
    assert result["metric"] == "fanin_microbench"
    assert result["max_clients_per_round"] >= 10000
    gates = result["gates"]
    assert gates["critical_path_sublinear"] is True
    assert gates["critical_path_exponent_vs_clients"] < 1.0
    assert gates["root_work_o_aggregators"] is True
    assert gates["root_work_ratio_across_cohort_growth"] < 2.0
    # The fan-in sweep really grew clients ~4x while root work stayed flat.
    assert gates["root_client_growth_ratio"] >= 3.5


@pytest.mark.slow
def test_codec_frontier_microbench_contract(bench, monkeypatch, tmp_path):
    """--codec-frontier-microbench at a shrunk mlp config: schema, artifact
    emission, and the sweep invariants (dense is the 1.0x reference with
    zero error; rotq bytes scale ~linearly in bit width; randk/topk land
    near 1/fraction). The >=10x-at-parity gate itself is pinned by the
    committed-artifact test below."""
    import json as json_mod
    import os

    art = tmp_path / "artifacts"
    monkeypatch.setattr(bench, "ARTIFACTS_DIR", str(art))
    monkeypatch.setenv("FEDTPU_CF_MODEL", "mlp")
    monkeypatch.setenv("FEDTPU_CF_REPS", "1")
    monkeypatch.setenv("FEDTPU_CF_CONV_ROUNDS", "2")
    monkeypatch.setenv("FEDTPU_CF_CONV_CLIENTS", "2")
    result = bench._codec_frontier_microbench()
    assert result["metric"] == "codec_frontier"
    assert result["gate_reduction_x"] == 10.0
    sweep = result["sweep"]["codecs"]
    assert set(sweep) == {
        "dense", "int8", "topk", "rotq@1b", "rotq@2b", "rotq@4b",
        "rotq@8b", "randk",
    }
    dense = sweep["dense"]
    assert dense["reduction_x"] == 1.0 and dense["rel_l2_error"] == 0.0
    for row in sweep.values():
        assert row["wire_bytes"] > 0
        assert row["encode_host_ms"] > 0 and row["decode_host_ms"] > 0
    # rotq payloads are dominated by the packed code block: bytes must
    # scale ~linearly with bit width (pad ratio is common to all widths).
    b1 = sweep["rotq@1b"]["wire_bytes"]
    for bits in (2, 4, 8):
        assert sweep[f"rotq@{bits}b"]["wire_bytes"] == pytest.approx(
            bits * b1, rel=0.02
        )
    # Quantization fidelity improves monotonically with bit width.
    assert (
        sweep["rotq@8b"]["rel_l2_error"]
        < sweep["rotq@4b"]["rel_l2_error"]
        < sweep["rotq@1b"]["rel_l2_error"]
    )
    # int8 is ~4x (one code byte per f32) with small error.
    assert sweep["int8"]["reduction_x"] == pytest.approx(4.0, rel=0.05)
    assert sweep["int8"]["rel_l2_error"] < 0.05
    conv = result["convergence"]
    assert set(conv["runs"]) == {"none", "randk"}
    assert conv["bytes_up_dense"] > conv["bytes_up_randk"] > 0
    assert result["value"] == conv["reduction_x"]
    assert result["passes_gate"] == (
        conv["reduction_x"] >= 10.0 and conv["acc_gap"] <= result["gate_acc_tol"]
    )
    path = os.path.join(str(art), "CODEC_FRONTIER_MICROBENCH.json")
    assert os.path.exists(path)
    with open(path) as f:
        assert json_mod.load(f) == result


def test_codec_frontier_committed_gate():
    """The committed artifact is the PR's acceptance evidence: the randk
    operating point (small keep-fraction, EF on, flat layout) cuts per-round
    uplink bytes >=10x — real wire encoders, not an analytic byte model —
    while the engine run converges to accuracy parity with the uncompressed
    control within the stamped tolerance."""
    result = _committed_artifact("CODEC_FRONTIER_MICROBENCH.json")
    assert result["metric"] == "codec_frontier"
    assert result["sweep"]["model"] == "densenet_cifar"
    assert result["passes_gate"] is True
    assert result["value"] >= 10.0
    conv = result["convergence"]
    assert conv["error_feedback"] is True
    assert conv["acc_gap"] <= result["gate_acc_tol"]
    assert conv["reduction_x"] >= 10.0
    # The sweep really exercised the whole family at the profile shape.
    assert set(result["sweep"]["codecs"]) >= {
        "dense", "int8", "topk", "rotq@4b", "randk",
    }
