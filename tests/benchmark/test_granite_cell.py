"""The benchmark's files for ``granite_4_0_h_micro.fl4_seq8k`` (PR 51), with a
tiny manifest of their own (``granite_tiny_manifest.json``, ``granite_tiny/``:
hidden 64, four layers ``mamba, mamba, attention, mamba`` of two halves each
(the published 3-6 of a pattern of ten), 8 state-space heads of 16 on ONE
group and a state of 16 in chunks of 12, of which heads 4-7 are held, 4 query
heads on 2 key-value heads of 16 of which key-value head 1 is held, a SwiGLU
of 96, multipliers 5, 3, 0.3 and 0.11, vocabulary 97, T 32, micro-batches of
one row): the configuration against the published config, the cut's size, the
FLOP functions, the readers, and whole sequential rounds of
``Federation.step()`` against the plain reference through the harness itself,
with the lower-precision control and the planted faults. Everything on the
CPU; times and rates come only from the chip."""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
TINY = os.path.join(HERE, "granite_tiny_manifest.json")
CELL = "granite_4_0_h_micro.fl4_seq8k"
TINY_CELL = "granite_tiny.fl4_seq32"
PRE = "fed.local_step.fwd_bwd."
# reader -> the scope it reads (whatever lies inside it included)
SHARES = {
    "granite.mamba_device_share": "mamba",
    "granite.dense_ffn_device_share": "dense_ffn",
    "granite.attention_device_share": "attention",
    "granite.lm_loss_device_share": "lm_loss"}
READERS = ("granite.mamba_device_share", "granite.mamba_core_roofline",
           "granite.dense_ffn_device_share", "granite.attention_device_share",
           "granite.attention_core_roofline", "granite.lm_loss_device_share")
REDUCED = {"num_hidden_layers", "mamba_n_heads", "num_attention_heads",
           "num_key_value_heads", "vocab_size"}
TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# architectures.jsonl, row granite-4.0-h-micro, "config"
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": TYPES, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}


@pytest.fixture(scope="module")
def cell():
    from benchmark import run

    return run.Cell(MANIFEST, CELL)


# ------------------------------------------------------------ the configuration
def test_every_published_key_is_there_and_only_the_cut_differs(cell):
    cfg = cell.config
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == REDUCED
    assert {k: PUBLISHED[k] for k in differs} == cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["mamba_n_heads"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["vocab_size"]) == (10, 32, 16, 4, 12544)
    # every width and all four multipliers as published
    assert (cfg["hidden_size"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"], cfg["mamba_d_conv"], cfg["mamba_chunk_size"],
            cfg["shared_intermediate_size"], cfg["head_dim"]) == (
        2048, 64, 128, 1, 4, 256, 8192, 64)
    assert (cfg["embedding_multiplier"], cfg["logits_scaling"],
            cfg["residual_multiplier"], cfg["attention_multiplier"]) == (
        12, 8, 0.22, 0.015625)
    assert cfg["head_dim"] * PUBLISHED["num_attention_heads"] == cfg["hidden_size"]
    assert cfg["mamba_expand"] * cfg["hidden_size"] == 64 * cfg["mamba_d_head"]
    # the pattern stays the published forty; the cut names the layers it
    # holds: the period of ten whole, read from the embedding as published
    held = cfg["layers_held"]
    assert held == list(range(10)) and len(held) == cfg["num_hidden_layers"]
    assert len(TYPES) == 40 and [TYPES[i] for i in held] == TYPES[:10]
    assert [TYPES.count(k) for k in ("mamba", "attention")] == [36, 4]
    assert [TYPES[i] for i in held].count("attention") == 1  # 9 : 1, the published 36 : 4
    # the floors: a whole period, an eighth of the vocabulary; HALF of each
    # mixer's heads (two chips share a layer), no width cut
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for key in ("mamba_n_heads", "num_attention_heads", "num_key_value_heads"):
        assert cfg[key] * 2 == PUBLISHED[key]
    assert "TWO chips share each layer" in cfg["deployment"]
    assert "chip 0 of each" in cfg["deployment"] and "9 : 1" in cfg["deployment"]
    assert "weighs twice" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {
        "gated_norm", "head_dim", "no_positions", "attention_scale", "multipliers",
        "no_time_step_limit", "mlp", "init", "packing", "optimizer", "micro_batch",
        "remat", "memory"}
    assert "12,285,971,616" in cfg["assumed"]["memory"]
    assert "sum of squares" in cfg["assumed"]["gated_norm"]
    assert cfg["num_local_experts"] == cfg["num_experts_per_tok"] == 0
    assert cfg["described_as"]["moe"] == "dense (no MoE)"
    assert cfg["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json")
    entry = [c for c in cell.manifest["configs"] if c["name"] == cfg["name"]][0]
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "benchmark/configs/granite_4_0_h_micro.json"


def test_the_cell_is_the_issues(cell):
    t = cell.traffic
    assert (t["clients"], t["steps"], t["check_rounds"], t["shards"]) == (
        4, 2, 2, "contiguous")
    assert t["program"] == {"fed": {"client_schedule": "sequential"}}
    assert t["codec"] is None and t["delta_layout"] == "per_leaf" and not t["mesh"]
    assert cell.samples_per_round == 16 and cell.chips == 1
    assert cell.samples_per_round * cell.config["seq_len"] == 131072
    # the traffic file is the three other hybrids', file and all
    other = [w["name"] for w in cell.manifest["workloads"] if w["traffic"] == "fl4_seq8k"]
    assert other[:4] == ["qwen3_next_80b_a3b.fl4_seq8k", "laguna_s_2_1.fl4_seq8k",
                         "nemotron_3_nano_30b_a3b.fl4_seq8k", CELL]
    manifest = cell.manifest
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1
    assert len(manifest["workloads"]) >= 10 and len(manifest["configs"]) >= 8
    assert cell.row["traffic"] == "fl4_seq8k"
    assert cell.row["config"] == "granite_4_0_h_micro"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert tuple(m["name"] for m in mine) == READERS
    assert {m["moves"] for m in mine} == {"samples_per_s_per_chip"}
    assert {m["source"] for m in mine} == {"device_trace"}
    assert {m["layer"] for m in mine} == {"local step"} and {m["unit"] for m in mine} == {"%"}
    assert all(m["better"] == ("higher" if "roofline" in m["name"] else "lower")
               for m in mine)
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "samples_per_s_per_chip", "setup_s"}
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert {"local_step.mfu", "device.peak_hbm_gb", "device.idle_share"} <= reported
    assert set(READERS) <= reported
    # lists of other cells: they do not report here, this cell's own readers
    # of the same scopes do
    assert not {"mamba.device_share", "mamba.core_roofline", "lm_loss.device_share",
                "laguna.dense_ffn_device_share", "nemotron.attention_device_share",
                "gqa.core_roofline"} & reported
    assert "2 x its share" in cell.row["why"] and "no all-reduce" in cell.row["why"]
    assert all(len(e["why"]) <= 200 for e in manifest["workloads"] + manifest["configs"])


def test_the_earlier_cell_of_this_traffic_is_as_it_was():
    """Every line of ``test_nemotron_cell.py::test_the_cell_is_the_issues`` but
    the one that says "this traffic file has three cells and mine is the
    last" (which a cell appended after it ends; ``tests/conftest.py``):
    Nemotron's cell's traffic and size, a held expert's rows, the one
    four-chip cell, its seven metrics and what they state."""
    from benchmark import run

    name = "nemotron_3_nano_30b_a3b.fl4_seq8k"
    readers = ("mamba.device_share", "mamba.core_roofline", "mamba.conv_device_share",
               "nemotron.moe_device_share", "nemotron.moe_experts_device_share",
               "nemotron.attention_device_share", "nemotron.lm_loss_device_share")
    nemotron = run.Cell(MANIFEST, name)
    t = nemotron.traffic
    assert (t["clients"], t["steps"], t["check_rounds"], t["shards"]) == (
        4, 2, 2, "contiguous")
    assert t["program"] == {"fed": {"client_schedule": "sequential"}}
    assert t["codec"] is None and t["delta_layout"] == "per_leaf" and not t["mesh"]
    assert nemotron.samples_per_round == 16 and nemotron.chips == 1
    assert nemotron.samples_per_round * nemotron.config["seq_len"] == 131072
    c = nemotron.config
    args = c["program"]["round"]["model_args"]
    assert args["micro_batch_rows"] * c["seq_len"] * c["num_experts_per_tok"] / c[
        "router_width"] == 384 == 6144 / 16
    manifest = nemotron.manifest
    assert [w["name"] for w in manifest["workloads"]].count(name) == 1
    assert len(manifest["workloads"]) >= 9 and len(manifest["configs"]) >= 7
    assert nemotron.row["traffic"] == "fl4_seq8k"
    assert nemotron.row["config"] == "nemotron_3_nano_30b_a3b"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [name]]
    assert tuple(m["name"] for m in mine) == readers
    assert {m["moves"] for m in mine} == {"samples_per_s_per_chip"}
    assert {m["source"] for m in mine} == {"device_trace"}
    assert {m["layer"] for m in mine} == {"local step"} and {m["unit"] for m in mine} == {"%"}
    assert all(m["better"] == ("higher" if "roofline" in m["name"] else "lower")
               for m in mine)
    assert {m["name"] for m in nemotron.metrics("end_to_end")} == {
        "samples_per_s_per_chip", "setup_s"}
    reported = {m["name"] for m in nemotron.metrics("per_layer")}
    assert {"local_step.mfu", "device.peak_hbm_gb", "device.idle_share"} <= reported
    assert set(readers) <= reported and not set(READERS) & reported
    assert not {"moe.device_share", "moe.experts_device_share", "lm_loss.device_share",
                "full_attention.device_share", "laguna.moe_device_share"} & reported
    assert "1/16" in nemotron.row["why"] and "16 x" in nemotron.row["why"]
    assert all(len(e["why"]) <= 200 for e in manifest["workloads"] + manifest["configs"])


def test_the_round_config_states_the_schedule_and_the_sizes(cell):
    from benchmark import sut

    cfg = sut.round_config(cell.config, cell.traffic, cell.task)
    assert cfg.fed.client_schedule == "sequential" and cfg.fed.num_clients == 4
    assert cfg.model == "granite_hybrid" and cfg.num_classes == 12544
    args = dict(cfg.model_args)
    # the kinds reach the program from the file's own list, not from a rule
    assert args == {"num_hidden_layers": 10, "layers_held": tuple(range(10)),
                    "layer_types": tuple(cell.config["layer_types"]),
                    "mamba_heads_held": (0, 32), "kv_heads_held": (0, 4),
                    "micro_batch_rows": 1}
    assert len(args["layer_types"]) == 40
    assert cfg.data.batch_size == 2 and cfg.data.dataset == "tokens"
    assert cfg.opt.momentum == 0 and cfg.dtype == "bfloat16" and cfg.remat
    assert cfg.opt.learning_rate == cell.config["optimizer"]["learning_rate"]
    assert cfg.image_size == (8192,) and cfg.steps_per_round == 2


def test_the_cut_holds_652_970_080_parameters_in_the_programs_own_tree(cell):
    """The issue's table, part by part, in the reference's parameter list,
    and the program's tree equal to it name for name at the cell's sizes."""
    import jax
    import jax.numpy as jnp

    from benchmark import sut
    from fedtpu import models

    spec = cell.reference.spec(cell.config)[0]
    by_part, by_half = {}, {}
    for path, shape, _ in spec:
        by_part[path[0]] = by_part.get(path[0], 0) + math.prod(shape)
        by_half[path[:2]] = by_half.get(path[:2], 0) + math.prod(shape)
    assert sum(by_part.values()) == 652_970_080
    assert 4 * 652_970_080 == 2_611_880_320  # a float32 copy: 2.612 GB
    assert by_part["embed"] == 25_690_112 and "head" not in by_part  # tied
    assert by_part["final_norm"] == 2048
    mamba_layers = [f"layer_{i}" for i in (0, 1, 2, 3, 4, 6, 7, 8, 9)]
    assert {by_part[l] for l in mamba_layers} == {63_522_144}
    assert by_part["layer_5"] == 55_578_624
    assert by_half["layer_0", "mamba"] == 13_186_400
    assert by_half["layer_5", "self_attn"] == 5_242_880
    assert {by_half[f"layer_{i}", "shared_mlp"] for i in range(10)} == {50_331_648}
    shapes = {path: tuple(shape) for path, shape, _ in spec}
    # z and x of the 32 heads held, ALL of B and C, the 32 step sizes
    assert shapes["layer_0", "mamba", "in_proj", "kernel"] == (
        2048, 2048 + 2048 + 128 + 128 + 32)
    assert math.prod(shapes["layer_0", "mamba", "in_proj", "kernel"]) == 8_978_432
    assert shapes["layer_0", "mamba", "conv"] == (4, 2304)
    assert shapes["layer_0", "mamba", "conv_bias"] == (2304,)
    assert shapes["layer_0", "mamba", "dt_bias"] == shapes["layer_0", "mamba", "D"] == (32,)
    assert shapes["layer_0", "mamba", "norm"] == (2048,)
    assert shapes["layer_0", "mamba", "out_proj", "kernel"] == (2048, 2048)
    assert shapes["layer_5", "self_attn", "q_proj", "kernel"] == (2048, 1024)
    assert shapes["layer_5", "self_attn", "k_proj", "kernel"] == (2048, 256)
    assert shapes["layer_5", "self_attn", "o_proj", "kernel"] == (1024, 2048)
    assert shapes["layer_9", "shared_mlp", "down", "kernel"] == (8192, 2048)
    # every head on the chip would be 772 M: the driver's rough count
    whole = 9 * (63_522_144 - 13_186_400 + 2048 * 8512 + 4 * 4352 + 4352 + 192
                 + 4096 + 4096 * 2048) + 55_578_624 + 5_242_880 + 25_692_160
    assert round(whole / 1e6) == 772
    cfg = sut.round_config(cell.config, cell.traffic, cell.task)
    model = models.create(cfg.model, num_classes=cfg.num_classes, remat=cfg.remat,
                          **dict(cfg.model_args))
    ids = jnp.zeros((1, 64), jnp.int32)
    tree = jax.eval_shape(
        lambda k: model.init(k, ids, train=True, targets=ids)["params"],
        jax.random.PRNGKey(0))
    ours = {tuple(k.key for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert ours == shapes


def test_the_flop_functions_count_what_perf_md_states(cell):
    cfg, flops = cell.config, cell.flops
    assert flops.kinds_held(cfg) == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert flops.mamba_proj_macs_per_token(cfg) == 8_978_432 + 4_194_304
    assert flops.mamba_core_macs_per_token(cfg) == 3 * 32 * 64 * 128
    assert flops.attention_proj_macs_per_token(cfg) == 5_242_880
    assert flops.attention_core_macs_per_token(cfg) == 16 * 2 * 64 * 8193 / 2
    assert flops.mlp_macs_per_token(cfg) == 50_331_648
    parts = flops.parts_macs_per_token(cfg)
    # the ten SwiGLUs 503.3 (75 %); the nine mixers 125.6 = 118.6 projections
    # + 7.1 recurrence; the attention layer 13.6 = 5.2 + 8.4; the head 25.7
    assert {k: round(v / 1e6, 1) for k, v in parts.items()} == {
        "mamba_proj": 118.6, "mamba_core": 7.1, "attention_proj": 5.2,
        "attention_core": 8.4, "mlp": 503.3, "head": 25.7}
    total = flops.forward_macs_per_token(cfg)
    assert total == pytest.approx(668.3e6, rel=1e-3)
    assert parts["mlp"] / total == pytest.approx(0.753, abs=0.002)
    assert flops.train_flops_per_sample(cfg) == pytest.approx(32.85e12, rel=1e-3)
    assert 16 * flops.train_flops_per_sample(cfg) == pytest.approx(525.6e12, rel=1e-3)
    ops, nbytes = flops.scan_per_round(cfg, 16)
    assert ops == 16 * 9 * 6 * 8192 * 32 * 3 * 64 * 128
    assert ops == 6 * 16 * 8192 * parts["mamba_core"]
    # x and y the 32 heads held, B and C the ONE group's, bfloat16; dt float32; 3 x
    assert nbytes == 16 * 9 * 3 * 8192 * ((2 * 2048 + 2 * 128) * 2 + 32 * 4)
    # a round's least: 28.3 ms of operations against 38.2 ms of bytes
    assert ops / 197e12 == pytest.approx(28.3e-3, rel=0.01)
    assert nbytes / 819e9 == pytest.approx(38.2e-3, rel=0.01)
    assert flops.least_seconds((ops, nbytes), PEAKS) == nbytes / 819e9
    ops, nbytes = flops.attention_core_per_round(cfg, 16)
    assert ops == 6 * 16 * 8192 * parts["attention_core"]
    assert nbytes == 16 * 3 * 8192 * (2 * 16 + 2 * 4) * 64 * 2
    # 33.5 ms of operations against 2.5 ms of bytes
    assert ops / 197e12 == pytest.approx(33.5e-3, rel=0.01)
    assert flops.least_seconds((ops, nbytes), PEAKS) == ops / 197e12


# ----------------------------------------------------------------- the readers
def _read(name, ctx):
    from benchmark import run

    return run.load_py(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py")).read(ctx)


def test_the_new_readers_return_nothing_without_their_scopes(cell):
    """No trace, the parent's capture of another cell (the local step's scope
    alone) and a model whose mixers run under other names: nothing, never 0."""
    ctx = {"cell": cell, "chips": 1, "trace": None, "traced_rounds": 0, "peaks": PEAKS}
    assert all(_read(n, ctx) is None for n in READERS)
    ctx["trace"] = {"busy_s": 10.0, "busy_by_scope": {"fed.local_step.fwd_bwd": 1.9}}
    assert all(_read(n, ctx) is None for n in READERS)
    ctx["trace"]["busy_by_scope"].update({
        PRE + "linear_attention": 0.4, PRE + "linear_attention.core": 1.0,
        PRE + "moe": 0.3})
    ctx["traced_rounds"] = 2
    assert all(_read(n, ctx) is None for n in READERS)
    ctx["trace"]["busy_by_scope"].update({
        PRE + "mamba": 0.1, PRE + "mamba.proj": 0.9, PRE + "mamba.conv": 0.25,
        PRE + "mamba.core": 1.5, PRE + "mamba.out": 0.25,
        PRE + "attention": 0.1, PRE + "attention.core": 0.2})
    assert _read("granite.mamba_device_share", ctx) == pytest.approx(30.0)
    assert _read("granite.attention_device_share", ctx) == pytest.approx(3.0)
    # the scan: the bytes bind, 38.2 ms a round, two rounds traced, 1.5 s
    least = 16 * 9 * 3 * 8192 * 8832 / 819e9
    assert _read("granite.mamba_core_roofline", ctx) == pytest.approx(
        100 * 2 * least / 1.5)
    # the softmax core: the operations bind, 33.5 ms a round, 0.2 s
    least = 6 * 16 * 8192 * 16 * 2 * 64 * 8193 / 2 / 197e12
    assert _read("granite.attention_core_roofline", ctx) == pytest.approx(
        100 * 2 * least / 0.2)
    for name in ("granite.mamba_core_roofline", "granite.attention_core_roofline"):
        assert 0 < _read(name, ctx) < 100
    assert _read("granite.dense_ffn_device_share", ctx) is None
    assert _read("granite.lm_loss_device_share", ctx) is None


@pytest.mark.parametrize("name", sorted(SHARES))
def test_a_share_reads_its_scope_and_what_lies_inside(cell, name):
    """Each reads one scope of the program, whatever lies inside it included,
    and nothing of a scope beside it; nothing where the scope is absent."""
    scope = SHARES[name]
    busy = {PRE + "window_attention": 1.0, PRE + scope: 0.5, PRE + scope + ".inner": 1.5,
            PRE + scope + "_beside": 4.0}
    ctx = {"cell": cell, "chips": 1, "traced_rounds": 2,
           "trace": {"busy_s": 10.0, "busy_by_scope": busy}}
    assert _read(name, ctx) == pytest.approx(20.0)
    del busy[PRE + scope], busy[PRE + scope + ".inner"]
    assert _read(name, ctx) is None


def test_the_limits_were_set_between_their_two_readings(cell):
    """No reading that was taken is left out of the file (``sound``,
    ``control``). A number whose smallest fp8 control stands at three times
    the sound runs' largest or more is held against the control, as in the
    accepted cells; one that the precision hardly moves against the planted
    faults it sees (``fault_min``: the smallest reading of
    ``benchmark/faults.py`` over the limit). The limit lies between the sound
    largest and what it is held against, the more room on the sound side,
    where one run over the limit refuses a PR and fresh seeds read higher."""
    limits = cell.limits
    assert set(limits) == {"loss_gap", "update1_gap", "update1_diff", "change_gap"}
    for name, row in limits.items():
        assert all(row[k] is not None for k in ("limit", "sound_max", "fault_min")), name
        assert len(row["sound"]) >= 8 and row["sound_max"] == max(row["sound"])
        assert len(row["control"]) >= 4
        assert row["control_smallest"] == min(row["control"])
        assert set(row["faults"]) == {
            "unchanged_state", "client_left_out", "half_batch_left_out"}
        assert row["fault_min"] == min(
            v for v in row["faults"].values() if v > row["limit"]), name
        against = ("control" if row["control_smallest"] >= 3 * row["sound_max"]
                   else "faults")
        assert row["held_against"] == against, name
        assert row["control_min"] == (
            row["control_smallest"] if against == "control" else None), name
        upper = row["control_min"] if against == "control" else row["fault_min"]
        assert 2.5 * row["sound_max"] <= row["limit"] < upper, name
        if name != "loss_gap":  # the loss takes an accepted cell's limit: below
            assert row["limit"] / row["sound_max"] >= upper / row["limit"], name
    # bfloat16 -> fp8 fails BOTH numbers of the first update on EVERY control
    # seed, the one that reads rounding by half again at the least; every
    # planted fault fails them too
    diff, gap = limits["update1_diff"], limits["update1_gap"]
    assert diff["held_against"] == gap["held_against"] == "control"
    assert min(diff["control"]) >= 1.5 * diff["limit"]
    assert min(gap["control"]) >= 1.3 * gap["limit"]
    for row in (diff, gap):
        assert min(row["faults"].values()) > row["limit"]
    # two rounds' change: the precision hardly moves it (under three times),
    # so its limit lies between the reading and the 1 of an unchanged state
    assert limits["change_gap"]["held_against"] == "faults"
    for name in ("update1_gap", "change_gap"):
        assert limits[name]["faults"]["unchanged_state"] == 1.0
    # the loss: the limit of the accepted cell of this mixer, with three
    # times of room over the sound largest
    assert limits["loss_gap"]["limit"] == 6e-05 >= 3 * limits["loss_gap"]["sound_max"]


# --------------------------- Federation.step() against the reference's rounds
@pytest.mark.parametrize("name", ["granite_tiny_f32.fl4_seq32", TINY_CELL])
def test_sequential_rounds_agree_with_the_reference(name):
    """The whole model's loss, and the first update and two rounds' change of
    a federation of 4 clients in sequence, 2 steps of 2 rows in micro-batches
    of one: in float32 to rounding (limits 5e-4), in bfloat16 within the tiny
    cell's limits."""
    from benchmark import run

    lines = []
    result = run.run(TINY, name, 7, 0.2, False, need_tpu=False, out=lines.append)
    assert result["correct"] and result["failed"] == 0, "\n".join(lines)
    held = [l for l in lines if l.startswith("check ") and "limit" in l]
    assert len(held) == 4 and all(l.endswith("ok") for l in held)
    assert set(result["metrics"]) == {"samples_per_s_per_chip", "setup_s"}
    losses = [l for l in lines if l.startswith("check rounds=")][0]
    first = float(losses.split("program_losses=[")[1].split(",")[0])
    assert first == pytest.approx(math.log(97), rel=0.1)


def test_the_fp8_control_fails_the_tiny_cells_limits():
    from benchmark import control, run

    limits = run.Cell(TINY, TINY_CELL).limits
    rows, _ = control.readings(TINY, TINY_CELL, [31], 1, program=False,
                               need_tpu=False, out=lambda s: None)
    assert all(r["limit"] is not None for r in limits.values())
    held = [k for k, r in limits.items() if r["control_min"] is not None]
    assert "update1_diff" in held
    for row in rows:
        low = row["control_fp8"]
        assert all(low[k] > limits[k]["limit"] for k in held), row


def test_planted_faults_fail_the_tiny_cells_limits():
    """A state left unchanged, a client of the four left out and half of
    every step's rows left out, each put in the program's place through
    ``check.follow_reference``: all three read over the limit of the number
    that reads the first update whole, and the unchanged state over every
    limit."""
    from benchmark import faults, run

    limits = run.Cell(TINY, TINY_CELL).limits
    rows, smallest = faults.readings(TINY, TINY_CELL, [31], need_tpu=False,
                                     out=lambda s: None)
    assert set(smallest) == {"unchanged_state", "client_left_out",
                             "half_batch_left_out"}
    for fault, nums in smallest.items():
        assert nums["update1_diff"] > limits["update1_diff"]["limit"], (fault, nums)
    for k, row in limits.items():
        assert smallest["unchanged_state"][k] > row["limit"], k
    assert smallest["unchanged_state"]["change_gap"] == 1.0


# ------------------------------- "the other cells run the parent's programs"
def test_a_twins_lowered_round_program_names_no_file_of_its_checkout(tmp_path):
    """``tools/lowered_programs.py`` compares two checkouts' round programs
    as text, so the text of one may not depend on where the checkout lies or
    on when it was lowered: both cells of this model's twin, lowered by the
    tool's own ``--write`` in a process of its own, hold a ``main`` and no
    location, and a second lowering gives the same bytes."""
    import subprocess

    tool = os.path.join(ROOT, "tools", "lowered_programs.py")
    texts = []
    for out in ("a", "b"):
        os.mkdir(tmp_path / out)
        subprocess.run(
            [sys.executable, tool, "--write", str(tmp_path / out), ROOT,
             os.path.basename(TINY)],
            check=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert sorted(os.listdir(tmp_path / out)) == [
            "granite_tiny.fl4_seq32.txt", "granite_tiny_f32.fl4_seq32.txt"]
        texts.append({name: (tmp_path / out / name).read_text()
                      for name in os.listdir(tmp_path / out)})
    assert texts[0] == texts[1]
    for text in texts[0].values():
        assert "func.func public @main" in text
        assert "loc(" not in text and ROOT not in text
