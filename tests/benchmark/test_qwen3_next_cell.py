"""The benchmark's files for ``qwen3_next_80b_a3b.fl4_seq8k`` (PR 36), with a
tiny manifest of their own (``qwen_tiny_manifest.json``, ``qwen_tiny/``:
hidden 64, one period of four layers, 2 key and 4 value heads of 64 and 32, 4
query heads on 2 key-value heads of 16, 16 experts of which 4 held,
vocabulary 97, T 32): the configuration against the published config, the
cut's size, the FLOP functions, the readers, and whole sequential rounds of
``Federation.step()`` against the plain reference through the harness itself,
with the lower-precision control. Everything on the CPU; times and rates come
only from the chip."""

import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
TINY = os.path.join(HERE, "qwen_tiny_manifest.json")
CELL = "qwen3_next_80b_a3b.fl4_seq8k"
READERS = ("gdn.device_share", "gdn.core_roofline", "full_attention.device_share")

# architectures.jsonl, row Qwen3-Next-80B-A3B-Instruct, "config"
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next", "moe_intermediate_size": 512,
    "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}


@pytest.fixture(scope="module")
def cell():
    from benchmark import run

    return run.Cell(MANIFEST, CELL)


# ------------------------------------------------------------ the configuration
def test_every_published_key_is_there_and_only_the_cut_differs(cell):
    cfg = cell.config
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert {k: PUBLISHED[k] for k in differs} == cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (
        4, 16, 18992)
    # the floors: a whole period, 8 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["router_width"] == PUBLISHED["num_experts"]
    assert cfg["num_experts"] * 32 == cfg["router_width"]
    assert "32 chips" in cfg["deployment"] and "8 chips" in cfg["deployment"]
    assert "chip 0 of both" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {"mtp", "packing", "init", "qkvz_layout", "optimizer"}
    assert cfg["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    entry = [c for c in cell.manifest["configs"] if c["name"] == cfg["name"]][0]
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]


def test_the_cell_is_the_issues(cell):
    t = cell.traffic
    assert (t["clients"], t["steps"], t["check_rounds"], t["shards"]) == (4, 2, 2, "contiguous")
    assert t["program"] == {"fed": {"client_schedule": "sequential"}}
    assert t["codec"] is None and t["delta_layout"] == "per_leaf" and not t["mesh"]
    assert cell.samples_per_round == 16 and cell.chips == 1
    assert cell.samples_per_round * cell.config["seq_len"] == 131072
    assert t["steps"] and cell.config["batch_size"] * cell.config["seq_len"] == 16384
    manifest = cell.manifest
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert tuple(m["name"] for m in mine) == READERS
    assert {m["moves"] for m in mine} == {"samples_per_s_per_chip"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "samples_per_s_per_chip", "setup_s"}
    assert all(len(e["why"]) <= 200 for e in manifest["workloads"] + manifest["configs"])


def test_the_earlier_language_cell_is_as_it_was(cell):
    """Every line of ``test_joyai_cell.py::test_the_cell_is_the_issues`` but
    "my cell is the manifest's last" (which a cell appended after it ends;
    ``tests/conftest.py``): the JoyAI cell's traffic and size, the one
    four-chip cell, its four metrics, no tail."""
    from benchmark import run

    joyai = run.Cell(MANIFEST, "joyai_llm_flash.fl4_seq4k")
    t = joyai.traffic
    assert (t["clients"], t["steps"], t["check_rounds"], t["shards"]) == (4, 2, 2, "contiguous")
    assert t["program"] == {"fed": {"client_schedule": "sequential"}}
    assert joyai.samples_per_round == 32 and joyai.chips == 1
    assert joyai.samples_per_round * joyai.config["seq_len"] == 131072
    workloads = joyai.manifest["workloads"]
    assert [w["name"] for w in workloads].count(joyai.name) == 1
    assert sum(w["chips"] == 4 for w in workloads) == 1
    mine = [m["name"] for m in joyai.manifest["per_layer"]
            if m.get("workloads") == [joyai.name]]
    assert mine == ["mla.device_share", "mla.core_roofline", "moe.device_share",
                    "lm_loss.device_share"]
    assert "round_ms_p95" not in [m["name"] for m in joyai.metrics("end_to_end")]


def test_the_round_config_states_the_schedule_and_the_sizes(cell):
    from benchmark import sut

    cfg = sut.round_config(cell.config, cell.traffic, cell.task)
    assert cfg.fed.client_schedule == "sequential" and cfg.fed.num_clients == 4
    assert cfg.model == "qwen3_next" and cfg.num_classes == 18992
    args = dict(cfg.model_args)
    assert (args["num_hidden_layers"], args["experts_held"], args["micro_batch_rows"]) == (
        4, (0, 16), 1)
    assert set(args) <= {"num_hidden_layers", "experts_held", "micro_batch_rows",
                         "gdn_chunk", "moe_chunk_pairs", "moe_block_rows"}
    assert cfg.data.dataset == "tokens" and cfg.data.batch_size == 2
    assert cfg.opt.momentum == 0 and cfg.dtype == "bfloat16" and cfg.remat
    assert cfg.image_size == (8192,) and cfg.steps_per_round == 2


def test_the_cut_holds_424_3_million_parameters_in_the_programs_own_tree(cell):
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
    assert sum(by_part.values()) == 424_340_544  # the issue's table
    assert by_part["layer_0"] == by_part["layer_2"] == 88_250_560
    assert by_part["layer_3"] == 81_795_584
    assert by_kind["layer_0", "linear_attn"] == 33_718_464
    assert by_kind["layer_3", "self_attn"] == 27_263_488
    assert by_kind["layer_0", "moe"] == by_kind["layer_3", "moe"] == 54_528_000
    assert by_part["embed"] == by_part["head"] == 38_895_616
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


def test_the_flop_functions_count_the_issues_numbers(cell):
    cfg, flops = cell.config, cell.flops
    assert flops.forward_macs_per_token(cfg) == pytest.approx(226.2e6, rel=1e-3)
    assert flops.train_flops_per_sample(cfg) == pytest.approx(11.1e12, rel=2e-3)
    assert 16 * flops.train_flops_per_sample(cfg) == pytest.approx(178e12, rel=2e-3)
    assert (flops.gdn_layers(cfg), flops.softmax_layers(cfg)) == (3, 1)
    # the softmax core is 33.6 M of the 226 M multiply-adds a token at T = 8192
    assert flops.attention_core_macs_per_token(cfg) == pytest.approx(33.6e6, rel=2e-3)
    assert flops.gdn_core_macs_per_token(cfg) == 32 * 3 * 128 * 128
    assert flops.expert_layer_macs_per_token(cfg) == (
        2048 * 512 + 2048 + 3 * 2048 * 512 * (1 + 10 * 16 / 512))
    core = cell.code("flops", "gdn_core")
    assert core.gdn_layers(cfg) == 3
    ops, nbytes = core.core_per_round(cfg, 16, 3)
    assert ops == 16 * 3 * 8192 * 32 * 3 * 128 * 128 * 2 * 3
    assert nbytes == 16 * 3 * 8192 * ((2 * 16 * 128 + 2 * 32 * 128) * 2 + 2 * 32 * 4) * 3
    # 16 ns of operations and 30 ns of bytes a token a layer forward: bytes bind
    per = 16 * 3 * 8192 * 3
    assert ops / 197e12 / per == pytest.approx(16e-9, rel=0.01)
    assert nbytes / 819e9 / per == pytest.approx(30.3e-9, rel=0.01)


# ----------------------------------------------------------------- the readers
def test_the_new_readers_return_nothing_without_their_scopes(cell):
    from benchmark import run

    read = lambda name, ctx: run.load_py(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py")).read(ctx)
    ctx = {"cell": cell, "chips": 1, "trace": None, "traced_rounds": 0,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert all(read(n, ctx) is None for n in READERS)
    # the parent's capture: the local step's scope, none of the new ones
    ctx["trace"] = {"busy_s": 10.0, "busy_by_scope": {"fed.local_step.fwd_bwd": 1.9}}
    assert all(read(n, ctx) is None for n in READERS)
    pre = "fed.local_step.fwd_bwd."
    ctx["trace"]["busy_by_scope"].update({
        pre + "linear_attention": 0.5, pre + "linear_attention.proj": 1.0,
        pre + "linear_attention.core": 2.0, pre + "linear_attention.out": 0.5,
        pre + "attention": 0.2, pre + "attention.core": 0.8, pre + "moe": 0.2})
    ctx["traced_rounds"] = 2
    assert read("gdn.device_share", ctx) == pytest.approx(40.0)
    assert read("full_attention.device_share", ctx) == pytest.approx(10.0)
    least = 16 * 3 * 8192 * 24832 * 3 / 819e9  # the bytes bind
    assert read("gdn.core_roofline", ctx) == pytest.approx(100 * 2 * least / 2.0)
    assert read("gdn.core_roofline", ctx) < 100
    # JoyAI's capture has an attention scope and no recurrent one
    del ctx["trace"]["busy_by_scope"][pre + "linear_attention.core"]
    assert read("gdn.core_roofline", ctx) is None


def test_the_limits_were_set_between_sound_and_control(cell):
    held = {k: r for k, r in cell.limits.items() if r["limit"] is not None}
    assert set(cell.limits) == {"loss_gap", "update1_gap", "update1_diff", "change_gap"}
    assert held, "no number is held"
    for name, row in held.items():
        assert row["sound_max"] < row["limit"], name
        assert row["control_min"] is None or row["limit"] < row["control_min"], name
    assert any(r["control_min"] is not None and r["control_min"] >= 3 * r["sound_max"]
               for r in held.values())


# --------------------------- Federation.step() against the reference's rounds
@pytest.mark.parametrize("name", ["qwen_tiny_f32.fl4_seq32", "qwen_tiny.fl4_seq32"])
def test_sequential_rounds_agree_with_the_reference(name):
    """The whole model's loss, and the first update and two rounds' change of
    a federation of 4 clients in sequence, 2 steps of 2 rows in micro-batches
    of 1: in float32 to rounding (limits 1e-4), in bfloat16 within the tiny
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

    limits = run.Cell(TINY, "qwen_tiny.fl4_seq32").limits
    rows, _ = control.readings(TINY, "qwen_tiny.fl4_seq32", [31], 1,
                               program=False, need_tpu=False, out=lambda s: None)
    for row in rows:
        low = row["control_fp8"]
        assert all(low[k] > limits[k]["limit"] for k in limits), row
