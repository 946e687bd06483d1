"""The benchmark's files for ``nemotron_3_nano_30b_a3b.fl4_seq8k`` (PR 48),
with a tiny manifest of their own (``nemotron_tiny_manifest.json``,
``nemotron_tiny/``: hidden 64, seven layers ``E M E M E M *`` of one half each
(the published layers 6-12: the cell holds 7-13, ``M E M E M * E``, the same
kinds one layer on, so that no expert layer reads the bare embedding; the twin
keeps the order in which one does, the harder case for the check), 8
state-space heads of 8 on 2 groups and a state of 16 in chunks of 12, 4 query
heads on 2 key-value heads of 16, 32 two-matrix experts of 24 of which 2 held,
vocabulary 97, T 32, micro-batches of one row): the
configuration against the published config, the cut's size, the FLOP
functions, the readers, and whole sequential rounds of ``Federation.step()``
against the plain reference through the harness itself, with the
lower-precision control and the planted faults. Everything on the CPU; times
and rates come only from the chip."""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
TINY = os.path.join(HERE, "nemotron_tiny_manifest.json")
CELL = "nemotron_3_nano_30b_a3b.fl4_seq8k"
TINY_CELL = "nemotron_tiny.fl4_seq32"
PRE = "fed.local_step.fwd_bwd."
# reader -> the scope it reads (whatever lies inside it included)
SHARES = {
    "mamba.device_share": "mamba", "mamba.conv_device_share": "mamba.conv",
    "nemotron.moe_device_share": "moe",
    "nemotron.moe_experts_device_share": "moe.experts",
    "nemotron.attention_device_share": "attention",
    "nemotron.lm_loss_device_share": "lm_loss"}
READERS = ("mamba.device_share", "mamba.core_roofline", "mamba.conv_device_share",
           "nemotron.moe_device_share", "nemotron.moe_experts_device_share",
           "nemotron.attention_device_share", "nemotron.lm_loss_device_share")
REDUCED = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# architectures.jsonl, row NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, "config"
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688, "hybrid_override_pattern": PATTERN,
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 52, "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
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
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (
        7, 8, 16384)
    # the pattern stays the published 52; the cut names the layers it holds:
    # seven on end of the stretch that repeats one unit four times (layers
    # 6-33), begun at the unit's second layer, so that NO expert layer reads
    # the bare embedding (assumed.layers_held: there a token id, not a
    # position, chooses the experts, and one id's flip moves a tenth of the
    # held experts' rows in program and reference apart)
    held = cfg["layers_held"]
    assert held == list(range(7, 14)) and len(held) == cfg["num_hidden_layers"]
    assert len(PATTERN) == 52 and "".join(PATTERN[i] for i in held) == "MEMEM*E"
    assert PATTERN[6:34] == "EMEMEM*" * 4 and PATTERN[held[0]] != "E"
    assert sorted(PATTERN[i] for i in held) == sorted("EMEMEM*")
    assert [PATTERN.count(k) for k in "ME*"] == [23, 23, 6]
    # the floors: a whole period and more than four layers, 8 routed experts
    # a layer that has them, an eighth of the vocabulary
    assert cfg["n_routed_experts"] >= 8 and cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["router_width"] == PUBLISHED["n_routed_experts"] == 16 * cfg["n_routed_experts"]
    assert "16 chips share each layer" in cfg["deployment"]
    assert "chip 0 of each" in cfg["deployment"] and "3 : 3 : 1" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {
        "d_in", "router", "activation", "shared_expert", "rotary", "gated_norm",
        "init", "packing", "optimizer", "micro_batch", "memory", "moe_chunk",
        "layers_held"}
    assert "518617517" in cfg["assumed"]["layers_held"]
    assert "11.66 GB" in cfg["assumed"]["memory"]
    # d_in is heads x head size: expand x hidden would be 84 heads of 64, not 64
    assert cfg["expand"] * cfg["hidden_size"] == 84 * cfg["mamba_head_dim"]
    assert cfg["mamba_num_heads"] * cfg["mamba_head_dim"] == 4096
    assert cfg["moe_intermediate_size"] % 128 == 64  # 14.5 lane groups
    assert cfg["described_as"]["moe"].startswith("128 experts, top-6, 1 shared; relu")
    assert cfg["source"] == ("https://huggingface.co/nvidia/"
                             "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    entry = [c for c in cell.manifest["configs"] if c["name"] == cfg["name"]][0]
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "benchmark/configs/nemotron_3_nano_30b_a3b.json"


def test_the_cell_is_the_issues(cell):
    t = cell.traffic
    assert (t["clients"], t["steps"], t["check_rounds"], t["shards"]) == (
        4, 2, 2, "contiguous")
    assert t["program"] == {"fed": {"client_schedule": "sequential"}}
    assert t["codec"] is None and t["delta_layout"] == "per_leaf" and not t["mesh"]
    assert cell.samples_per_round == 16 and cell.chips == 1
    assert cell.samples_per_round * cell.config["seq_len"] == 131072
    # the traffic file is the hybrid's and Laguna's, file and all
    other = [w["name"] for w in cell.manifest["workloads"] if w["traffic"] == "fl4_seq8k"]
    assert other == ["qwen3_next_80b_a3b.fl4_seq8k", "laguna_s_2_1.fl4_seq8k", CELL]
    # a held expert's rows a product at uniform routing: a sixteenth of the
    # 6,144 of a deployment whose sixteen chips each run such a row
    c = cell.config
    args = c["program"]["round"]["model_args"]
    assert args["micro_batch_rows"] * c["seq_len"] * c["num_experts_per_tok"] / c[
        "router_width"] == 384 == 6144 / 16
    manifest = cell.manifest
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1
    assert len(manifest["workloads"]) >= 9 and len(manifest["configs"]) >= 7
    assert cell.row["traffic"] == "fl4_seq8k"
    assert cell.row["config"] == "nemotron_3_nano_30b_a3b"
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
    assert not {"moe.device_share", "moe.experts_device_share", "lm_loss.device_share",
                "full_attention.device_share", "laguna.moe_device_share"} & reported
    assert "1/16" in cell.row["why"] and "16 x" in cell.row["why"]
    assert all(len(e["why"]) <= 200 for e in manifest["workloads"] + manifest["configs"])


def test_the_earlier_cell_of_this_traffic_is_as_it_was():
    """Every line of ``test_laguna_cell.py::test_the_cell_is_the_issues`` but
    the four that say "my entries are the manifest's last" and "this traffic
    file has two cells" (which a cell appended after it ends;
    ``tests/conftest.py``): Laguna's cell's traffic and size, a held expert's
    rows, the one four-chip cell, its eight metrics and what they state."""
    from benchmark import run

    name = "laguna_s_2_1.fl4_seq8k"
    readers = (
        "window_attention.device_share", "window_attention.core_roofline",
        "window_attention.full_layers_share", "window_attention.full_core_roofline",
        "laguna.dense_ffn_device_share", "laguna.lm_loss_device_share",
        "laguna.moe_device_share", "laguna.moe_experts_device_share")
    laguna = run.Cell(MANIFEST, name)
    t = laguna.traffic
    assert (t["clients"], t["steps"], t["check_rounds"], t["shards"]) == (
        4, 2, 2, "contiguous")
    assert t["program"] == {"fed": {"client_schedule": "sequential"}}
    assert t["codec"] is None and t["delta_layout"] == "per_leaf" and not t["mesh"]
    assert laguna.samples_per_round == 16 and laguna.chips == 1
    assert laguna.samples_per_round * laguna.config["seq_len"] == 131072
    c = laguna.config
    args = c["program"]["round"]["model_args"]
    assert args["micro_batch_rows"] * c["seq_len"] * c["num_experts_per_tok"] / c[
        "router_width"] == 320
    manifest = laguna.manifest
    assert [w["name"] for w in manifest["workloads"]].count(name) == 1
    assert laguna.row["traffic"] == "fl4_seq8k" and laguna.row["config"] == "laguna_s_2_1"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [name]]
    assert sorted(m["name"] for m in mine) == sorted(readers)
    assert {m["moves"] for m in mine} == {"samples_per_s_per_chip"}
    assert {m["source"] for m in mine} == {"device_trace"}
    assert {m["layer"] for m in mine} == {"local step"} and {m["unit"] for m in mine} == {"%"}
    assert all(m["better"] == ("higher" if "roofline" in m["name"] else "lower")
               for m in mine)
    assert {m["name"] for m in laguna.metrics("end_to_end")} == {
        "samples_per_s_per_chip", "setup_s"}
    reported = {m["name"] for m in laguna.metrics("per_layer")}
    assert {"local_step.mfu", "device.peak_hbm_gb", "device.idle_share"} <= reported
    assert set(readers) <= reported and not set(READERS) & reported
    assert not {"moe.device_share", "moe.experts_device_share",
                "moe.routing_device_share", "lm_loss.device_share"} & reported
    assert "1/10 of device time" in laguna.row["why"]


def test_the_round_config_states_the_schedule_and_the_sizes(cell):
    from benchmark import sut

    cfg = sut.round_config(cell.config, cell.traffic, cell.task)
    assert cfg.fed.client_schedule == "sequential" and cfg.fed.num_clients == 4
    assert cfg.model == "nemotron_h" and cfg.num_classes == 16384
    args = dict(cfg.model_args)
    assert (args["num_hidden_layers"], args["layers_held"], args["experts_held"]) == (
        7, tuple(range(7, 14)), (0, 8))
    assert args["micro_batch_rows"] == 1 and cfg.data.batch_size == 2
    assert set(args) == {"num_hidden_layers", "layers_held", "experts_held",
                         "micro_batch_rows", "moe_chunk_pairs", "moe_block_rows"}
    assert cfg.data.dataset == "tokens"
    assert cfg.opt.momentum == 0 and cfg.dtype == "bfloat16" and cfg.remat
    assert cfg.opt.learning_rate == cell.config["optimizer"]["learning_rate"]
    assert cfg.image_size == (8192,) and cfg.steps_per_round == 2


def test_the_cut_holds_528_092_736_parameters_in_the_programs_own_tree(cell):
    """The issue's table, part by part, in the reference's parameter list,
    and the program's tree equal to it name for name at the cell's sizes."""
    import jax
    import jax.numpy as jnp

    from benchmark import sut
    from fedtpu import models

    spec = cell.reference.spec(cell.config)[0]
    by_part = {}
    for path, shape, _ in spec:
        by_part[path[0]] = by_part.get(path[0], 0) + math.prod(shape)
    assert sum(by_part.values()) == 528_092_736
    assert by_part["embed"] == by_part["head"] == 44_040_192  # untied
    assert by_part["final_norm"] == 2688
    # M E M E M * E
    expert_layers, mamba_layers = ("layer_1", "layer_3", "layer_6"), (
        "layer_0", "layer_2", "layer_4")
    assert {by_part[l] for l in mamba_layers} == {38_744_896}
    assert {by_part[l] for l in expert_layers} == {100_125_312}
    assert by_part["layer_5"] == 23_399_040
    shapes = {path: tuple(shape) for path, shape, _ in spec}
    assert shapes["layer_0", "mamba", "in_proj", "kernel"] == (2688, 4096 + 6144 + 64)
    assert math.prod(shapes["layer_0", "mamba", "in_proj", "kernel"]) == 27_697_152
    assert shapes["layer_0", "mamba", "conv"] == (4, 6144)
    assert shapes["layer_0", "mamba", "conv_bias"] == (6144,)
    assert shapes["layer_0", "mamba", "norm"] == (4096,)
    assert math.prod(shapes["layer_0", "mamba", "out_proj", "kernel"]) == 11_010_048
    assert shapes["layer_1", "moe", "experts_up"] == (8, 2688, 1856)
    assert shapes["layer_1", "moe", "experts_down"] == (8, 1856, 2688)
    assert math.prod(shapes["layer_1", "moe", "router"]) == 344_064
    assert math.prod(shapes["layer_1", "moe", "shared", "up", "kernel"]) == 9_977_856
    assert ("layer_1", "moe", "experts_gate") not in shapes  # two matrices
    assert math.prod(shapes["layer_5", "self_attn", "q_proj", "kernel"]) == 11_010_048
    assert math.prod(shapes["layer_5", "self_attn", "k_proj", "kernel"]) == 688_128
    # a whole expert layer of the published 128: 1,297 M
    assert 128 * 2 * 4_988_928 + 2 * 9_977_856 + 344_064 + 2688 == 1_297_468_032
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
    assert flops.kinds_held(cfg) == ["mamba", "moe"] * 2 + ["mamba", "attention", "moe"]
    assert flops.mamba_proj_macs_per_token(cfg) == 27_697_152 + 11_010_048
    assert flops.mamba_core_macs_per_token(cfg) == 3 * 64 * 64 * 128
    assert flops.attention_proj_macs_per_token(cfg) == 2 * 11_010_048 + 2 * 688_128
    assert flops.attention_core_macs_per_token(cfg) == 32 * 2 * 128 * 8193 / 2
    assert flops.sparse_macs_per_token(cfg) == (
        2688 * 128, 2 * 2688 * 3712, 2 * 2688 * 1856 * 6 * 8 / 128)
    parts = flops.parts_macs_per_token(cfg)
    # Mamba 120.8 (41 %) = 116.1 projections + 4.7 recurrence; experts 72.1 =
    # 1.0 routers + 59.9 shared + 11.2 held; attention 57.0 = 23.4 + 33.6; head 44.0
    assert {k: round(v / 1e6, 1) for k, v in parts.items()} == {
        "mamba_proj": 116.1, "mamba_core": 4.7, "attention_proj": 23.4,
        "attention_core": 33.6, "router": 1.0, "shared": 59.9, "experts": 11.2,
        "head": 44.0}
    total = flops.forward_macs_per_token(cfg)
    assert total == pytest.approx(293.9e6, rel=1e-3)
    assert (parts["mamba_proj"] + parts["mamba_core"]) / total == pytest.approx(0.41, abs=0.005)
    assert flops.train_flops_per_sample(cfg) == pytest.approx(14.45e12, rel=1e-3)
    assert 16 * flops.train_flops_per_sample(cfg) == pytest.approx(231.1e12, rel=1e-3)
    core = cell.code("flops", "ssd_core")
    assert core.mamba_layers(cfg) == 3
    ops, nbytes = core.core_per_round(cfg, 16, 3)
    assert ops == 16 * 3 * 6 * 8192 * 64 * 3 * 64 * 128
    assert ops == 6 * 16 * 8192 * parts["mamba_core"]
    # x and y the 64 heads', B and C the 8 groups', bfloat16; dt float32; 3 x
    assert nbytes == 16 * 3 * 3 * 8192 * ((2 * 4096 + 2 * 1024) * 2 + 64 * 4)
    # a round's least: 18.8 ms of operations against 29.9 ms of bytes
    assert ops / 197e12 == pytest.approx(18.8e-3, rel=0.01)
    assert nbytes / 819e9 == pytest.approx(29.9e-3, rel=0.01)
    assert core.least_seconds(cfg, 16, PEAKS) == nbytes / 819e9


# ----------------------------------------------------------------- the readers
def _read(name, ctx):
    from benchmark import run

    return run.load_py(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py")).read(ctx)


def test_the_new_readers_return_nothing_without_their_scopes(cell):
    """No trace, the parent's capture (the local step's scope alone) and the
    hybrid's (recurrent mixers under another name): nothing, never 0."""
    ctx = {"cell": cell, "chips": 1, "trace": None, "traced_rounds": 0, "peaks": PEAKS}
    assert all(_read(n, ctx) is None for n in READERS)
    ctx["trace"] = {"busy_s": 10.0, "busy_by_scope": {"fed.local_step.fwd_bwd": 1.9}}
    assert all(_read(n, ctx) is None for n in READERS)
    ctx["trace"]["busy_by_scope"].update({
        PRE + "linear_attention": 0.4, PRE + "linear_attention.core": 1.0,
        PRE + "linear_attention.conv": 0.3})
    ctx["traced_rounds"] = 2
    assert all(_read(n, ctx) is None for n in READERS)
    ctx["trace"]["busy_by_scope"].update({
        PRE + "mamba": 0.1, PRE + "mamba.proj": 0.9, PRE + "mamba.conv": 0.25,
        PRE + "mamba.core": 1.5, PRE + "mamba.out": 0.25})
    assert _read("mamba.device_share", ctx) == pytest.approx(30.0)
    assert _read("mamba.conv_device_share", ctx) == pytest.approx(2.5)
    # the bytes bind: 29.9 ms a round, two rounds traced, 1.5 s under the core
    least = 16 * 3 * 3 * 8192 * 20736 / 819e9
    assert _read("mamba.core_roofline", ctx) == pytest.approx(100 * 2 * least / 1.5)
    assert 0 < _read("mamba.core_roofline", ctx) < 100
    assert all(_read(n, ctx) is None for n in READERS if n.startswith("nemotron."))


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
    the sound runs' largest or more is held against the control; one that the
    precision hardly moves against the planted faults it sees (``fault_min``:
    the smallest reading of ``benchmark/faults.py`` over the limit). The limit
    lies between the sound largest and what it is held against, the more room
    on the sound side, where one run over the limit refuses a PR and fresh
    seeds read higher."""
    limits = cell.limits
    assert set(limits) == {"loss_gap", "update1_gap", "update1_diff", "change_gap"}
    for name, row in limits.items():
        assert all(row[k] is not None for k in ("limit", "sound_max", "fault_min")), name
        assert len(row["sound"]) >= 6 and row["sound_max"] == max(row["sound"])
        assert len(row["control"]) >= 3
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
        assert row["limit"] / row["sound_max"] >= upper / row["limit"], name
    # bfloat16 -> fp8 fails the number that reads rounding on EVERY control
    # seed; every planted fault fails it too
    diff = limits["update1_diff"]
    assert diff["held_against"] == "control"
    assert min(diff["control"]) >= 2 * diff["limit"]
    assert min(diff["faults"].values()) >= 3 * diff["limit"]
    for name in ("update1_gap", "change_gap"):
        assert limits[name]["faults"]["unchanged_state"] == 1.0
        # with every expert layer behind a mixer, fp8 hardly moves a leaf's norm
        assert limits[name]["held_against"] == "faults"
        assert limits[name]["limit"] > max(limits[name]["control"])
    assert limits["loss_gap"]["held_against"] == "control"


def test_the_readings_at_layers_6_12_are_kept_and_say_why_the_layers_moved():
    """The driver refused the cell as first handed in (layers 6-12, the expert
    layer first): one seed's ``change_gap`` read 0.094 where twelve read under
    0.007, the leaves ``layer_0``'s experts and router. Those readings stay in
    the file, set no limit, and the same seed is among the sound seeds of the
    layers held now."""
    with open(os.path.join(ROOT, "benchmark", "limits", CELL + ".json")) as fh:
        doc = json.load(fh)
    before = doc["readings_at_layers_6_12"]
    assert len(before["sound"]) == 13 and len(before["control_fp8"]) == 3
    gaps = sorted(r["change_gap"] for r in before["sound"].values())
    assert gaps[-1] == before["sound"]["518617517"]["change_gap"] == 0.0939544
    assert gaps[-2] < 0.007 and gaps[-1] > before["limits_then"]["change_gap"]
    assert "layer_0/moe/experts_up" in before["worst_leaves_of_seed_518617517"]
    assert "518617517" in doc["readings"]
    assert max(doc["numbers"]["change_gap"]["sound"]) < 0.007


# --------------------------- Federation.step() against the reference's rounds
@pytest.mark.parametrize("name", ["nemotron_tiny_f32.fl4_seq32", TINY_CELL])
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
