"""The benchmark's files for ``laguna_s_2_1.fl4_seq8k`` (PR 44), with a tiny
manifest of their own (``laguna_tiny_manifest.json``, ``laguna_tiny/``: hidden
64, the cut's five layers [full, sliding, sliding, sliding, full] with the
first dense, ONE of 8 key-value heads of 16 held with its 2 (full) or 3
(sliding) query heads, window 8, 16 experts of which 2 held, vocabulary 97, T
32, micro-batches of one row): the configuration against the published
config, the cut's size, the FLOP functions, the readers, and whole sequential
rounds of ``Federation.step()`` against the plain reference through the
harness itself, with the lower-precision control and the planted faults.
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
TINY = os.path.join(HERE, "laguna_tiny_manifest.json")
CELL = "laguna_s_2_1.fl4_seq8k"
TINY_CELL = "laguna_tiny.fl4_seq32"
ATTENTION_READERS = (
    "window_attention.device_share", "window_attention.core_roofline",
    "window_attention.full_layers_share", "window_attention.full_core_roofline")
# the cell's larger scopes, which the accepted readers of the same scopes do
# not report here (their lists name other cells and are not this PR's to widen)
FEED_FORWARD_READERS = {
    "laguna.moe_device_share": "moe", "laguna.moe_experts_device_share": "moe.experts",
    "laguna.dense_ffn_device_share": "dense_ffn", "laguna.lm_loss_device_share": "lm_loss"}
READERS = ATTENTION_READERS + tuple(FEED_FORWARD_READERS)
FULL, SLIDING = "full_attention", "sliding_attention"
PERIOD = [FULL, SLIDING, SLIDING, SLIDING]  # twelve times
REDUCED = {"num_hidden_layers", "num_experts", "num_key_value_heads",
           "num_attention_heads_per_layer", "vocab_size"}

# architectures.jsonl, row Laguna-S-2.1, "config"
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
               "original_max_position_embeddings": 8192, "beta_slow": 1,
               "beta_fast": 32, "attention_factor": 1.4852030263919618,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}},
    "layer_types": PERIOD * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0,
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
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["num_key_value_heads"],
            cfg["vocab_size"]) == (5, 8, 1, 12544)
    # an eighth of every layer's query heads: 6 of 48 full, 9 of 72 sliding
    assert cfg["num_attention_heads_per_layer"] == [6, 9, 9, 9] * 12
    # every width is the published one
    for width in ("hidden_size", "head_dim", "intermediate_size", "sliding_window",
                  "moe_intermediate_size", "shared_expert_intermediate_size",
                  "num_experts_per_tok", "rope_parameters", "moe_routed_scaling_factor"):
        assert cfg[width] == PUBLISHED[width], width
    # the floors: the leading dense layer and a whole period of the four
    # layers that follow it, 8 experts, an eighth of the vocabulary
    held = cfg["layers_held"]
    assert held == [0, 1, 2, 3, 4] and len(held) == cfg["num_hidden_layers"]
    assert [cfg["layer_types"][i] for i in held] == [
        FULL, SLIDING, SLIDING, SLIDING, FULL]
    assert [cfg["mlp_layer_types"][i] for i in held] == ["dense"] + ["sparse"] * 4
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["router_width"] == PUBLISHED["num_experts"] == 32 * cfg["num_experts"]
    assert cfg["num_key_value_heads"] * 8 == PUBLISHED["num_key_value_heads"]
    assert "32 chips share each layer" in cfg["deployment"]
    assert "chip 0 of each group" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {
        "gate", "router", "activation", "no_head_norms", "shared_expert", "packing",
        "init", "optimizer", "micro_batch", "memory", "moe_chunk"}
    assert cfg["described_as"]["moe"].startswith("256 experts, top-10, 1 shared")
    assert "3:1" in cfg["described_as"]["attention"]
    assert cfg["source"] == (
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json")
    entry = [c for c in cell.manifest["configs"] if c["name"] == cfg["name"]][0]
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "benchmark/configs/laguna_s_2_1.json"


def test_the_cell_is_the_issues(cell):
    t = cell.traffic
    assert (t["clients"], t["steps"], t["check_rounds"], t["shards"]) == (
        4, 2, 2, "contiguous")
    assert t["program"] == {"fed": {"client_schedule": "sequential"}}
    assert t["codec"] is None and t["delta_layout"] == "per_leaf" and not t["mesh"]
    assert cell.samples_per_round == 16 and cell.chips == 1
    assert cell.samples_per_round * cell.config["seq_len"] == 131072
    # the traffic file is the hybrid's, file and all
    other = [w for w in cell.manifest["workloads"] if w["traffic"] == "fl4_seq8k"]
    assert [w["name"] for w in other] == ["qwen3_next_80b_a3b.fl4_seq8k", CELL]
    # a held expert's rows a product at uniform routing: a quarter of the
    # 1,280 of a deployment whose four attention groups each run such a row
    c = cell.config
    args = c["program"]["round"]["model_args"]
    assert args["micro_batch_rows"] * c["seq_len"] * c["num_experts_per_tok"] / c[
        "router_width"] == 320
    manifest = cell.manifest
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == "laguna_s_2_1"
    assert cell.row["traffic"] == "fl4_seq8k" and cell.row["config"] == "laguna_s_2_1"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert tuple(m["name"] for m in mine) == READERS
    assert tuple(m["name"] for m in manifest["per_layer"][-8:]) == READERS
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
    # lists of other cells: they do not report here (ROADMAP Reach B1), and
    # this cell's own readers of the same scopes do
    assert not {"moe.device_share", "moe.experts_device_share",
                "moe.routing_device_share", "lm_loss.device_share"} & reported
    assert "1/10 of device time" in cell.row["why"]
    assert all(len(e["why"]) <= 200 for e in manifest["workloads"] + manifest["configs"])


def test_the_round_config_states_the_schedule_and_the_sizes(cell):
    from benchmark import sut

    cfg = sut.round_config(cell.config, cell.traffic, cell.task)
    assert cfg.fed.client_schedule == "sequential" and cfg.fed.num_clients == 4
    assert cfg.model == "laguna" and cfg.num_classes == 12544
    args = dict(cfg.model_args)
    assert (args["num_hidden_layers"], args["layers_held"], args["experts_held"],
            args["kv_heads_held"]) == (5, (0, 1, 2, 3, 4), (0, 8), (0, 1))
    assert args["micro_batch_rows"] == 1 and cfg.data.batch_size == 2
    assert set(args) == {"num_hidden_layers", "layers_held", "experts_held",
                         "kv_heads_held", "micro_batch_rows", "moe_chunk_pairs",
                         "moe_block_rows"}
    assert cfg.data.dataset == "tokens"
    assert cfg.opt.momentum == 0 and cfg.dtype == "bfloat16" and cfg.remat
    assert cfg.image_size == (8192,) and cfg.steps_per_round == 2


def test_the_cut_holds_567_957_504_parameters_in_the_programs_own_tree(cell):
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
    assert sum(by_part.values()) == 567_957_504  # the issue's table + the final norm
    assert by_part["embed"] == by_part["head"] == 38_535_168  # untied
    assert by_part["final_norm"] == 3072
    assert by_part["layer_0"] == 118_775_808
    assert by_kind["layer_0", "self_attn"] == by_kind["layer_4", "self_attn"] == 5_523_456
    assert by_kind["layer_0", "feed_forward"] == 113_246_208
    assert by_part["layer_1"] == by_part["layer_2"] == by_part["layer_3"] == 93_619_200
    assert by_kind["layer_1", "self_attn"] == 7_891_968
    assert by_kind["layer_1", "moe"] == 85_721_088 == 786_432 + 9 * 9_437_184
    assert by_part["layer_4"] == 91_250_688
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
    assert flops.attention_proj_macs_per_token(cfg, 6) == 5_523_456
    assert flops.attention_proj_macs_per_token(cfg, 9) == 7_891_968
    assert flops.attention_core_macs_per_token(cfg, FULL, 6) == 6 * 2 * 128 * 8193 / 2
    band = 512 * 513 // 2 + (8192 - 512) * 512  # sum_t min(t + 1, 512)
    assert band == sum(min(t + 1, 512) for t in range(8192))
    assert flops.attention_core_macs_per_token(cfg, SLIDING, 9) == 9 * 2 * 128 * band / 8192
    assert flops.dense_macs_per_token(cfg) == 3 * 3072 * 12288
    assert flops.sparse_macs_per_token(cfg) == (
        3072 * 256, 3 * 3072 * 1024, 3 * 3072 * 1024 * 10 * 8 / 256)
    parts = flops.parts_macs_per_token(cfg)
    # 34.7 (projections) + 12.6 (full cores) + 3.4 (window cores) + 113.2 (dense)
    # + 3.1 + 37.7 + 11.8 (routers, shared, held experts) + 38.5 (head)
    assert {k: round(v / 1e6, 1) for k, v in parts.items()} == {
        "attention_proj": 34.7, "full_core": 12.6, "window_core": 3.4, "dense": 113.2,
        "router": 3.1, "shared": 37.7, "experts": 11.8, "head": 38.5}
    assert flops.forward_macs_per_token(cfg) == pytest.approx(255.2e6, rel=1e-3)
    assert parts["dense"] / flops.forward_macs_per_token(cfg) == pytest.approx(0.44, abs=0.005)
    assert flops.train_flops_per_sample(cfg) == pytest.approx(12.54e12, rel=1e-3)
    assert 16 * flops.train_flops_per_sample(cfg) == pytest.approx(200.7e12, rel=1e-3)
    core = cell.code("flops", "window_attention_core")
    assert core.layers(cfg, FULL) == [0, 4] and core.layers(cfg, SLIDING) == [1, 2, 3]
    assert core.pairs(cfg, FULL) == 8192 * 8193 // 2 and core.pairs(cfg, SLIDING) == band
    # a full core is 8.3 times a window core a head over the row (16 times for its last query)
    assert core.pairs(cfg, FULL) / core.pairs(cfg, SLIDING) == pytest.approx(8.26, abs=0.01)
    assert core.pairs(dict(cfg, sliding_window=8192), SLIDING) == core.pairs(cfg, FULL)
    ops, nbytes = core.core_per_round(cfg, 16, FULL)
    assert ops == 16 * 2 * 6 * 6 * (128 + 128) * 8192 * 8193 // 2
    assert ops == 6 * 16 * 8192 * parts["full_core"]
    # q and the output the 6 heads held, k and v the one a group reads, 3 x
    assert nbytes == 16 * 2 * 3 * 8192 * (2 * 6 + 2 * 1) * 128 * 2
    ops_w, nbytes_w = core.core_per_round(cfg, 16, SLIDING)
    assert ops_w == 16 * 3 * 6 * 9 * (128 + 128) * band
    assert nbytes_w == 16 * 3 * 3 * 8192 * (2 * 9 + 2 * 1) * 128 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # a round's least: 50.2 ms (full) and 13.7 ms (window) of operations
    # against 3.4 and 7.4 ms of bytes: the operations bind in both
    assert core.least_seconds(cfg, 16, FULL, peaks) == pytest.approx(50.2e-3, rel=0.01)
    assert core.least_seconds(cfg, 16, SLIDING, peaks) == pytest.approx(13.7e-3, rel=0.01)
    assert nbytes / 819e9 == pytest.approx(3.4e-3, rel=0.02)
    assert nbytes_w / 819e9 == pytest.approx(7.4e-3, rel=0.01)
    with pytest.raises(ValueError, match="kind"):
        core.core_per_round(cfg, 16, "conv")


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
    pre = "fed.local_step.fwd_bwd."
    # the hybrid's capture: a softmax layer and recurrent mixers, no window
    ctx["trace"]["busy_by_scope"].update({
        pre + "attention": 0.4, pre + "attention.core": 1.1,
        pre + "linear_attention.core": 1.0})
    ctx["traced_rounds"] = 2
    assert _read("window_attention.device_share", ctx) is None
    assert _read("window_attention.core_roofline", ctx) is None
    assert _read("window_attention.full_layers_share", ctx) == pytest.approx(15.0)
    ctx["trace"]["busy_by_scope"].update({
        pre + "window_attention": 0.6, pre + "window_attention.core": 1.9})
    assert _read("window_attention.device_share", ctx) == pytest.approx(25.0)
    # the full layers' reader does not read the window layers' scope
    assert _read("window_attention.full_layers_share", ctx) == pytest.approx(15.0)
    band = 512 * 513 // 2 + (8192 - 512) * 512
    least = 6 * 16 * 3 * 9 * 256 * band / 197e12  # the operations bind
    assert _read("window_attention.core_roofline", ctx) == pytest.approx(
        100 * 2 * least / 1.9)
    least = 6 * 16 * 2 * 6 * 256 * (8192 * 8193 // 2) / 197e12
    assert _read("window_attention.full_core_roofline", ctx) == pytest.approx(
        100 * 2 * least / 1.1)
    assert 0 < _read("window_attention.core_roofline", ctx) < 100
    assert 0 < _read("window_attention.full_core_roofline", ctx) < 100


@pytest.mark.parametrize("name", sorted(FEED_FORWARD_READERS))
def test_a_feed_forward_reader_reads_its_scope_and_what_lies_inside(cell, name):
    """Each reads one scope of the program, whatever lies inside it included,
    and nothing of a scope beside it; nothing where the scope is absent."""
    pre = "fed.local_step.fwd_bwd."
    scope = FEED_FORWARD_READERS[name]
    busy = {pre + "window_attention": 1.0, pre + scope: 0.5, pre + scope + ".inner": 1.5,
            pre + scope + "_beside": 4.0}
    ctx = {"cell": cell, "chips": 1, "traced_rounds": 2,
           "trace": {"busy_s": 10.0, "busy_by_scope": busy}}
    assert _read(name, ctx) == pytest.approx(20.0)
    del busy[pre + scope], busy[pre + scope + ".inner"]
    assert _read(name, ctx) is None
    # the whole expert layer holds its grouped products; they do not hold it
    busy.update({pre + "moe": 1.0, pre + "moe.router": 0.5, pre + "moe.experts": 2.0})
    want = {"moe": 35.0, "moe.experts": 20.0}.get(scope)
    assert _read(name, ctx) == (pytest.approx(want) if want else None)


def test_the_new_readers_read_a_small_stored_trace(cell):
    """``laguna_trace_small.json``: device operations under the new scopes and
    the harness's spans, through the harness's own reduction; the parent's
    recorded capture (``trace_tpu_small.json``) gives the eight nothing."""
    from benchmark import trace_reduce

    with open(os.path.join(HERE, "laguna_trace_small.json")) as fh:
        recorded = json.load(fh)
    traced = trace_reduce.reduce_trace(recorded["events"])
    assert traced["busy_s"] == pytest.approx(1.0)
    ctx = {"cell": cell, "chips": 1, "trace": traced, "traced_rounds": 1,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    got = {n: _read(n, ctx) for n in READERS}
    assert got == {n: pytest.approx(v) for n, v in recorded["expect"].items()}
    assert got["window_attention.core_roofline"] < got["window_attention.full_core_roofline"] < 100
    with open(os.path.join(HERE, "trace_tpu_small.json")) as fh:
        ctx["trace"] = trace_reduce.reduce_trace(json.load(fh)["events"])
    assert all(_read(n, ctx) is None for n in READERS)


def test_the_limits_were_set_between_their_two_readings(cell):
    """No reading that was taken is left out of the file (``control``). A number whose
    smallest fp8 control stands at three times the sound runs' largest or more
    is held against the control; one that the precision hardly moves against
    the planted faults it sees (``fault_min``: the smallest reading of
    ``benchmark/faults.py`` over the limit). The limit lies between the sound
    largest and what it is held against, the more room on the sound side,
    where one run over the limit refuses a PR and fresh seeds read higher."""
    limits = cell.limits
    assert set(limits) == {"loss_gap", "update1_gap", "update1_diff", "change_gap"}
    for name, row in limits.items():
        assert all(row[k] is not None for k in ("limit", "sound_max", "fault_min")), name
        # every control seed's reading, whatever the number is held against
        assert len(row["control"]) >= 5
        assert row["control_smallest"] == min(row["control"])
        assert set(row["faults"]) == {
            "unchanged_state", "client_left_out", "half_batch_left_out"}
        assert row["fault_min"] == min(
            v for v in row["faults"].values() if v > row["limit"]), name
        against = ("control" if row["control_smallest"] >= 3 * row["sound_max"]
                   else "faults")
        assert row["held_against"] == against, name
        # ``control_min`` as the harness's accepted test reads it: the control
        # reading the limit was set under, null where that is no control's
        assert row["control_min"] == (
            row["control_smallest"] if against == "control" else None), name
        upper = row["control_min"] if against == "control" else row["fault_min"]
        assert 2.5 * row["sound_max"] <= row["limit"] < upper, name
        assert row["limit"] / row["sound_max"] >= upper / row["limit"], name
    assert {n: r["held_against"] for n, r in limits.items()} == {
        "update1_diff": "control", "loss_gap": "control",
        "update1_gap": "faults", "change_gap": "faults"}
    # bfloat16 -> fp8 fails the number that reads rounding on EVERY control
    # seed, by a wide margin, and the loss on every one too; every planted
    # fault fails both
    diff, loss = limits["update1_diff"], limits["loss_gap"]
    assert min(diff["control"]) >= 2.5 * diff["limit"]
    assert min(loss["control"]) > loss["limit"]
    assert min(diff["faults"].values()) >= 3 * diff["limit"]
    assert min(loss["faults"].values()) >= 3 * loss["limit"]
    # the update's and the change's worst leaf, which the precision hardly
    # moves (the smallest control reads about the sound largest, or under it):
    # between the sound reading and what a first update of the wrong size
    # reads, far under 1 (a state left unchanged)
    for name in ("update1_gap", "change_gap"):
        row = limits[name]
        assert row["control_smallest"] < 1.5 * row["sound_max"]
        assert row["limit"] <= row["faults"]["client_left_out"] / 2.5
        assert row["faults"]["unchanged_state"] == 1.0


# --------------------------- Federation.step() against the reference's rounds
@pytest.mark.parametrize("name", ["laguna_tiny_f32.fl4_seq32", TINY_CELL])
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
    ``check.follow_reference``: all three read over the limits of the three
    update numbers, and the unchanged state over the loss's too."""
    from benchmark import faults, run

    limits = run.Cell(TINY, TINY_CELL).limits
    rows, smallest = faults.readings(TINY, TINY_CELL, [31], need_tpu=False,
                                     out=lambda s: None)
    assert set(smallest) == {"unchanged_state", "client_left_out",
                             "half_batch_left_out"}
    for fault, nums in smallest.items():
        for k in ("update1_gap", "update1_diff", "change_gap"):
            assert nums[k] > limits[k]["limit"], (fault, k, nums)
    assert smallest["unchanged_state"]["loss_gap"] > limits["loss_gap"]["limit"]
    assert smallest["unchanged_state"]["change_gap"] == 1.0
