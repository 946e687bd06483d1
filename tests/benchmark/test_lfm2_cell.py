"""The benchmark's files for ``lfm2_24b_a2b.fl4_b8_seq4k`` (PR 42), with a tiny
manifest of their own (``lfm2_tiny_manifest.json``, ``lfm2_tiny/``: hidden 64,
the cut's five layers [conv, full_attention, conv, conv, conv] with the first
dense, 4 query heads on 2 key-value heads of 16, 16 experts of which 2 held,
vocabulary 97, T 32, the step's 2 rows in one micro-batch): the configuration
against the published config, the cut's size, the FLOP functions, the readers,
and whole sequential rounds of ``Federation.step()`` against the plain
reference through the harness itself, with the lower-precision control.
Everything on the CPU; times and rates come only from the chip."""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
TINY = os.path.join(HERE, "lfm2_tiny_manifest.json")
CELL = "lfm2_24b_a2b.fl4_b8_seq4k"
READERS = ("short_conv.device_share", "short_conv.core_roofline",
           "moe.experts_device_share", "moe.routing_device_share",
           "gqa.device_share", "gqa.core_roofline")
PATTERN = ["conv", "conv", "full_attention", "conv"]  # the period, ten times

# architectures.jsonl, row LFM2-24B-A2B, "config"
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": PATTERN * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}


@pytest.fixture(scope="module")
def cell():
    from benchmark import run

    return run.Cell(MANIFEST, CELL)


# ------------------------------------------------------------ the configuration
def test_every_published_key_is_there_and_only_the_cut_differs(cell):
    cfg = cell.config
    assert len(PUBLISHED["layer_types"]) == 40
    assert PUBLISHED["layer_types"].count("full_attention") == 10
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    assert {k: PUBLISHED[k] for k in differs} == cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 1, 8, 8192)
    # the floors: one leading dense layer, a whole period of the four layers
    # that follow the dense ones, 8 experts, an eighth of the vocabulary
    held = cfg["layers_held"]
    assert held == [0, 2, 3, 4, 5] and len(held) == cfg["num_hidden_layers"]
    assert [cfg["layer_types"][i] for i in held] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert sum(i < PUBLISHED["num_dense_layers"] for i in held) == cfg["num_dense_layers"]
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["router_width"] == PUBLISHED["num_experts"]
    assert cfg["num_experts"] * 8 == cfg["router_width"]
    assert "8 chips" in cfg["deployment"] and "chip 0 of both" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {"tied_head", "head_dim", "expert_bias", "packing",
                                   "init", "optimizer", "micro_batch"}
    assert cfg["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    entry = [c for c in cell.manifest["configs"] if c["name"] == cfg["name"]][0]
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "benchmark/configs/lfm2_24b_a2b.json"


def test_the_cell_is_the_issues(cell):
    t = cell.traffic
    assert (t["clients"], t["steps"], t["check_rounds"], t["shards"]) == (
        4, 2, 2, "contiguous")
    assert t["program"] == {"fed": {"client_schedule": "sequential"}}
    assert t["codec"] is None and t["delta_layout"] == "per_leaf" and not t["mesh"]
    assert cell.samples_per_round == 64 and cell.chips == 1
    assert cell.samples_per_round * cell.config["seq_len"] == 262144
    assert cell.config["batch_size"] * cell.config["seq_len"] == 32768
    # a held expert's rows a product at uniform routing: its deployment's
    c = cell.config
    args = c["program"]["round"]["model_args"]
    assert args["micro_batch_rows"] * c["seq_len"] * c["num_experts_per_tok"] / c[
        "router_width"] == 2048
    manifest = cell.manifest
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1
    assert cell.row["traffic"] == "fl4_b8_seq4k" and cell.row["config"] == "lfm2_24b_a2b"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert tuple(m["name"] for m in mine) == READERS
    assert {m["moves"] for m in mine} == {"samples_per_s_per_chip"}
    assert {m["source"] for m in mine} == {"device_trace"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "samples_per_s_per_chip", "setup_s"}
    assert "local_step.mfu" in {m["name"] for m in cell.metrics("per_layer")}
    assert all(len(e["why"]) <= 200 for e in manifest["workloads"] + manifest["configs"])


def test_the_round_config_states_the_schedule_and_the_sizes(cell):
    from benchmark import sut

    cfg = sut.round_config(cell.config, cell.traffic, cell.task)
    assert cfg.fed.client_schedule == "sequential" and cfg.fed.num_clients == 4
    assert cfg.model == "lfm2_moe" and cfg.num_classes == 8192
    args = dict(cfg.model_args)
    assert (args["num_hidden_layers"], args["num_dense_layers"], args["experts_held"],
            args["layer_types"]) == (
        5, 1, (0, 8), ("conv", "full_attention", "conv", "conv", "conv"))
    assert args["micro_batch_rows"] == 8 == cfg.data.batch_size
    assert set(args) == {"num_hidden_layers", "num_dense_layers", "layer_types",
                         "experts_held", "micro_batch_rows", "moe_chunk_pairs",
                         "moe_block_rows"}
    assert cfg.data.dataset == "tokens" and cfg.data.batch_size == 8
    assert cfg.opt.momentum == 0 and cfg.dtype == "bfloat16" and cfg.remat
    assert cfg.image_size == (4096,) and cfg.steps_per_round == 2


def test_the_cut_holds_469_3_million_parameters_in_the_programs_own_tree(cell):
    import jax
    import jax.numpy as jnp

    from benchmark import sut
    from fedtpu import models

    spec = cell.reference.spec(cell.config)[0]
    by_part, by_kind = {}, {}
    for path, shape, _ in spec:
        by_part[path[0]] = by_part.get(path[0], 0) + math.prod(shape)
        if len(path) > 1:
            by_kind[path[:2]] = by_kind.get(path[:2], 0) + math.prod(shape)
    assert sum(by_part.values()) == 469_284_992  # the issue's table
    assert by_part["embed"] == 16_777_216 and by_part["final_norm"] == 2048
    assert "head" not in by_part  # tied
    assert by_part["layer_0"] == 89_139_200
    assert by_kind["layer_0", "conv"] == 16_783_360
    assert by_kind["layer_0", "feed_forward"] == 72_351_744
    assert by_part["layer_1"] == 86_118_528
    assert by_kind["layer_1", "self_attn"] == 10_485_888
    assert by_kind["layer_1", "moe"] == 131_072 + 8 * 9_437_184
    assert by_part["layer_2"] == by_part["layer_3"] == by_part["layer_4"] == 92_416_000
    cfg = sut.round_config(cell.config, cell.traffic, cell.task)
    model = models.create(cfg.model, num_classes=cfg.num_classes, remat=cfg.remat,
                          **dict(cfg.model_args))
    ids = jnp.zeros((1, 64), jnp.int32)
    shapes = jax.eval_shape(
        lambda k: model.init(k, ids, train=True, targets=ids)["params"],
        jax.random.PRNGKey(0))
    ours = {tuple(k.key for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert ours == {path: tuple(shape) for path, shape, _ in spec}


def test_the_flop_functions_count_what_perf_md_states(cell):
    cfg, flops = cell.config, cell.flops
    assert (flops.conv_layers(cfg), flops.attention_layers(cfg)) == (4, 1)
    assert flops.conv_macs_per_token(cfg) == 4 * 2048 * 2048
    assert flops.attention_proj_macs_per_token(cfg) == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert flops.attention_core_macs_per_token(cfg) == 32 * 2 * 64 * 4097 / 2
    assert flops.expert_layer_macs_per_token(cfg) == 2048 * 64 + 3 * 2048 * 1536 * 0.5
    # 67.1 (conv) + 72.4 (dense) + 10.5 + 8.4 (attention) + 19.4 (experts) + 16.8 (head)
    assert flops.forward_macs_per_token(cfg) == pytest.approx(194.5e6, rel=1e-3)
    assert flops.train_flops_per_sample(cfg) == pytest.approx(4.78e12, rel=2e-3)
    assert 64 * flops.train_flops_per_sample(cfg) == pytest.approx(306e12, rel=2e-3)
    core = cell.code("flops", "short_conv_core")
    assert core.conv_layers(cfg) == 4 and core.TENSORS == 11
    ops, nbytes = core.core_per_round(cfg, 64, 4)
    channels = 64 * 4 * 4096 * 2048
    assert ops == channels * 5 * 2 * 3 and nbytes == channels * 11 * 2
    # 0.15 ps of operations and 27 ps of bytes a token's channel: the bytes bind
    assert ops / 197e12 / channels == pytest.approx(0.152e-12, rel=0.01)
    assert nbytes / 819e9 / channels == pytest.approx(26.9e-12, rel=0.01)
    assert nbytes / 819e9 == pytest.approx(57.7e-3, rel=0.01)  # a round's least
    gqa = cell.code("flops", "gqa_core")
    assert gqa.attention_layers(cfg) == 1
    ops, nbytes = gqa.core_per_round(cfg, 64, 1)
    # scores and P v over 64 a head, the causal half, forward and two backward
    assert ops == 64 * 2 * 3 * 32 * (64 + 64) * 4096 * 4097 / 2
    assert ops == 6 * 64 * 4096 * flops.attention_core_macs_per_token(cfg)
    # q and the output 32 heads, k and v the 8 a group reads, three times over
    assert nbytes == 64 * 3 * 4096 * (2 * 32 + 2 * 8) * 64 * 2
    # a round's least: 67.0 ms of operations against 9.8 ms of bytes
    assert ops / 197e12 == pytest.approx(67.0e-3, rel=0.01)
    assert nbytes / 819e9 == pytest.approx(9.8e-3, rel=0.01)


# ----------------------------------------------------------------- the readers
def _read(name, ctx):
    from benchmark import run

    return run.load_py(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py")).read(ctx)


def test_the_new_readers_return_nothing_without_their_scopes(cell):
    ctx = {"cell": cell, "chips": 1, "trace": None, "traced_rounds": 0,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert all(_read(n, ctx) is None for n in READERS)
    # the parent's capture: the local step's scope, none of the new ones
    ctx["trace"] = {"busy_s": 10.0, "busy_by_scope": {"fed.local_step.fwd_bwd": 1.9}}
    assert all(_read(n, ctx) is None for n in READERS)
    # the hybrid's capture: an expert layer's own time and a recurrent mixer,
    # neither a short convolution nor (the scope's own time apart) a routed part
    pre = "fed.local_step.fwd_bwd."
    ctx["trace"]["busy_by_scope"].update(
        {pre + "moe": 0.3, pre + "linear_attention.conv": 1.0})
    assert all(_read(n, ctx) is None for n in READERS)
    ctx["trace"]["busy_by_scope"].update({
        pre + "attention": 0.4, pre + "attention.core": 1.1,
        pre + "short_conv": 0.1, pre + "short_conv.proj": 1.0,
        pre + "short_conv.core": 0.5, pre + "short_conv.out": 0.4,
        pre + "moe.router": 0.2, pre + "moe.dispatch": 0.3, pre + "moe.combine": 0.1,
        pre + "moe.experts": 0.7})
    ctx["traced_rounds"] = 2
    assert _read("short_conv.device_share", ctx) == pytest.approx(20.0)
    assert _read("moe.experts_device_share", ctx) == pytest.approx(7.0)
    assert _read("moe.routing_device_share", ctx) == pytest.approx(6.0)
    least = 64 * 4 * 4096 * 2048 * 11 * 2 / 819e9  # the bytes bind
    assert _read("short_conv.core_roofline", ctx) == pytest.approx(100 * 2 * least / 0.5)
    assert _read("short_conv.core_roofline", ctx) < 100
    assert _read("gqa.device_share", ctx) == pytest.approx(15.0)
    least = 6 * 64 * 32 * 128 * 4096 * 4097 / 2 / 197e12  # the operations bind
    assert _read("gqa.core_roofline", ctx) == pytest.approx(100 * 2 * least / 1.1)
    assert 10 < _read("gqa.core_roofline", ctx) < 100
    # one of the three routing scopes is enough for that reader
    del ctx["trace"]["busy_by_scope"][pre + "moe.router"]
    del ctx["trace"]["busy_by_scope"][pre + "moe.combine"]
    assert _read("moe.routing_device_share", ctx) == pytest.approx(3.0)


def test_the_new_readers_read_a_small_stored_trace(cell):
    """``lfm2_trace_small.json``: device operations under the new scopes and
    the harness's spans, through the harness's own reduction; the parent's
    recorded capture (``trace_tpu_small.json``) gives the six nothing."""
    from benchmark import trace_reduce

    with open(os.path.join(HERE, "lfm2_trace_small.json")) as fh:
        recorded = json.load(fh)
    traced = trace_reduce.reduce_trace(recorded["events"])
    ctx = {"cell": cell, "chips": 1, "trace": traced, "traced_rounds": 1,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    got = {n: _read(n, ctx) for n in READERS}
    assert got == {n: pytest.approx(v) for n, v in recorded["expect"].items()}
    assert 0 < got["short_conv.core_roofline"] < 100
    with open(os.path.join(HERE, "trace_tpu_small.json")) as fh:
        ctx["trace"] = trace_reduce.reduce_trace(json.load(fh)["events"])
    assert all(_read(n, ctx) is None for n in READERS)


def test_the_limits_were_set_between_their_two_readings(cell):
    """Every number is held, above the sound runs' largest and below what it
    is held against: the fp8 control (``control_min``) or the planted faults
    (``fault_min``: ``benchmark/faults.py``), with room on both sides."""
    assert set(cell.limits) == {"loss_gap", "update1_gap", "update1_diff", "change_gap"}
    for name, row in cell.limits.items():
        upper = [row[k] for k in ("control_min", "fault_min") if row.get(k) is not None]
        assert row["limit"] is not None and upper, name
        assert 2 * row["sound_max"] <= row["limit"] <= min(upper) / 2, name
    assert cell.limits["update1_diff"]["control_min"] >= 3 * cell.limits[
        "update1_diff"]["sound_max"]
    # the loss is held against a state left unchanged, the one fault it sees
    loss = cell.limits["loss_gap"]
    assert 3 * loss["sound_max"] <= loss["limit"] <= loss["fault_min"] / 3


# --------------------------- Federation.step() against the reference's rounds
@pytest.mark.parametrize("name", ["lfm2_tiny_f32.fl4_b2_seq32", "lfm2_tiny.fl4_b2_seq32"])
def test_sequential_rounds_agree_with_the_reference(name):
    """The whole model's loss, and the first update and two rounds' change of
    a federation of 4 clients in sequence, 2 steps of 2 rows in ONE
    micro-batch: in float32 to rounding (limits 1e-4), in bfloat16 within the
    tiny cell's limits."""
    from benchmark import run

    lines = []
    result = run.run(TINY, name, 7, 0.2, False, need_tpu=False, out=lines.append)
    assert result["correct"] and result["failed"] == 0, "\n".join(lines)
    held = [l for l in lines if l.startswith("check ") and "limit" in l]
    assert len(held) >= 2 and all(l.endswith("ok") for l in held)
    assert set(result["metrics"]) == {"samples_per_s_per_chip", "setup_s"}
    losses = [l for l in lines if l.startswith("check rounds=")][0]
    first = float(losses.split("program_losses=[")[1].split(",")[0])
    assert first == pytest.approx(math.log(97), rel=0.1)


def test_the_fp8_control_fails_the_tiny_cells_limits():
    from benchmark import control, run

    limits = run.Cell(TINY, "lfm2_tiny.fl4_b2_seq32").limits
    rows, _ = control.readings(TINY, "lfm2_tiny.fl4_b2_seq32", [31], 1,
                               program=False, need_tpu=False, out=lambda s: None)
    assert all(r["limit"] is not None for r in limits.values())
    held = [k for k, r in limits.items() if r["control_min"] is not None]
    assert "update1_diff" in held and len(held) >= 2
    for row in rows:
        low = row["control_fp8"]
        assert all(low[k] > limits[k]["limit"] for k in held), row


def test_planted_faults_fail_the_tiny_cells_limits():
    """A state left unchanged, a client of the four left out and half of
    every step's rows left out, each put in the program's place through
    ``check.follow_reference``: all three read over the limits of the three
    update numbers, and the unchanged state over the loss's too, the one
    fault the loss sees."""
    from benchmark import faults, run

    limits = run.Cell(TINY, "lfm2_tiny.fl4_b2_seq32").limits
    rows, smallest = faults.readings(TINY, "lfm2_tiny.fl4_b2_seq32", [31],
                                     need_tpu=False, out=lambda s: None)
    assert set(smallest) == {"unchanged_state", "client_left_out",
                             "half_batch_left_out"}
    for fault, nums in smallest.items():
        for k in ("update1_gap", "update1_diff", "change_gap"):
            assert nums[k] > limits[k]["limit"], (fault, k, nums)
            assert nums[k] >= limits[k].get("fault_min", 0) * 0.99, (fault, k, nums)
    assert smallest["unchanged_state"]["loss_gap"] > limits["loss_gap"]["limit"]
    assert smallest["unchanged_state"]["change_gap"] == 1.0
