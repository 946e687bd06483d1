"""The benchmark's files for ``joyai_llm_flash.fl4_seq4k`` (PR 34), with a
tiny manifest of their own (``joyai_tiny_manifest.json``, ``joyai_tiny/``:
hidden 64, 2 heads, 16 experts of which 4 held, vocabulary 97, T 32): the
configuration against the published config, the cut's size, the FLOP
functions, the token task's data and loss, the readers, and the whole loss
and whole sequential rounds of ``Federation.step()`` against the plain
reference through the harness itself, with the lower-precision control.
Everything on the CPU; times and rates come only from the chip."""

import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
TINY = os.path.join(HERE, "joyai_tiny_manifest.json")
CELL = "joyai_llm_flash.fl4_seq4k"

# architectures.jsonl, row JoyAI-LLM-Flash, "config"
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 7168,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "joyai_llm_flash", "moe_intermediate_size": 768, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 32000000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}


@pytest.fixture(scope="module")
def cell():
    from benchmark import run

    return run.Cell(MANIFEST, CELL)


@pytest.fixture(scope="module")
def tiny_cell():
    from benchmark import run

    return run.Cell(TINY, "joyai_tiny_f32.fl4_seq32")


# ------------------------------------------------------------ the configuration
def test_every_published_key_is_there_and_only_the_cut_differs(cell):
    cfg = cell.config
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: PUBLISHED[k] for k in differs} == cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (
        5, 8, 16160)
    # the floors: 4 layers after the leading dense one, 8 experts, an eighth
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["router_width"] == PUBLISHED["n_routed_experts"]
    assert "32 chips" in cfg["deployment"] and "8 chips" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {"e_score_correction_bias", "mtp_loss_weight",
                                   "mtp_wiring", "packing"}
    assert cfg["source"].startswith("https://huggingface.co/jdopensource/JoyAI-LLM-Flash")


def test_the_cell_is_the_issues(cell):
    t = cell.traffic
    assert (t["clients"], t["steps"], t["check_rounds"], t["shards"]) == (4, 2, 2, "contiguous")
    assert t["program"] == {"fed": {"client_schedule": "sequential"}}
    assert cell.samples_per_round == 32 and cell.chips == 1
    assert cell.samples_per_round * cell.config["seq_len"] == 131072
    manifest = cell.manifest
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    mine = [m["name"] for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == ["mla.device_share", "mla.core_roofline", "moe.device_share",
                    "lm_loss.device_share"]
    assert "round_ms_p95" not in [m["name"] for m in cell.metrics("end_to_end")]


def test_the_round_config_states_the_schedule_and_the_sizes(cell):
    from benchmark import sut

    cfg = sut.round_config(cell.config, cell.traffic, cell.task)
    assert cfg.fed.client_schedule == "sequential" and cfg.fed.num_clients == 4
    assert cfg.model == "joyai_llm_flash" and cfg.num_classes == 16160
    assert dict(cfg.model_args) == {"num_hidden_layers": 5, "experts_held": (0, 8),
                                    "micro_batch_rows": 1, "moe_chunk_pairs": 4096}
    assert cfg.data.dataset == "tokens" and cfg.data.batch_size == 4
    assert cfg.opt.momentum == 0 and cfg.dtype == "bfloat16" and cfg.remat
    assert cfg.image_size == (4096,) and cfg.steps_per_round == 2


def test_the_cut_holds_491_7_million_parameters_in_the_programs_own_tree(cell):
    import jax
    import jax.numpy as jnp

    from benchmark import sut
    from fedtpu import models

    spec = cell.reference.spec(cell.config)[0]
    by_part = {}
    for path, shape, _ in spec:
        by_part[path[0]] = by_part.get(path[0], 0) + math.prod(shape)
    assert sum(by_part.values()) == 491_694_080  # the issue's table: 491.7 M
    assert by_part["layer_0"] == 70_391_808 and by_part["layer_1"] == 69_343_232
    assert by_part["embed"] + by_part["head"] == 2 * 16160 * 2048
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
    per_token = 6 * flops.forward_macs_per_token(cfg)
    assert per_token == pytest.approx(2.608e9, rel=1e-3)
    assert flops.train_flops_per_sample(cfg) == pytest.approx(10.68e12, rel=1e-3)
    # attention's scores and values: 42 MFLOP of a layer's forward at T = 4096
    assert 2 * flops.attention_core_macs_per_token(cfg) == pytest.approx(41.95e6, rel=1e-3)
    assert flops.expert_layer_macs_per_token(cfg) == 2048 * 256 + 3 * 2048 * 768 * (1 + 0.25)
    core = cell.code("flops", "mla_core")
    ops, nbytes = core.core_per_round(cfg, 32, core.attention_layers(cfg))
    assert core.attention_layers(cfg) == 6
    assert ops == 32 * 6 * 6 * 32 * 320 * 4096 * 4097 / 2
    assert ops / 197e12 > nbytes / 819e9  # the operations bind


# --------------------------------------------------------------------- the task
def test_the_corpora_are_packed_documents_of_the_clients_own_vocabularies(tiny_cell):
    from benchmark import check

    cfg = tiny_cell.config
    ids, targets = tiny_cell.task.make_data(11, cfg)
    again, _ = tiny_cell.task.make_data(11, cfg)
    other, _ = tiny_cell.task.make_data(2**31 + 12, cfg)
    assert ids.shape == targets.shape == (32, 32) and ids.dtype == np.int32
    assert np.array_equal(ids, again) and not np.array_equal(ids, other)
    assert ids.min() == 0 and ids.max() < cfg["vocab_size"]
    assert np.array_equal(targets[:, :-1], ids[:, 1:]) and np.all(targets[:, -1] == -1)
    # documents end in id 0 and are longer than two tokens
    ends = np.flatnonzero(ids.reshape(4, -1)[0] == 0)
    assert len(ends) >= 3 and np.all(np.diff(ends) >= 2)
    # each client's most frequent token is its own
    top = [np.bincount(ids[c * 8:(c + 1) * 8].ravel()[ids[c * 8:(c + 1) * 8].ravel() > 0]
                       ).argmax() for c in range(4)]
    assert len(set(top)) >= 3
    # the cell deals client c the c-th run of rows
    _, _, shards, _ = check.seeded_inputs(tiny_cell, 11)
    assert np.array_equal(shards[0], np.arange(32).reshape(4, 8))


def test_the_loss_weighs_the_prediction_module_and_its_parts_add_up(tiny_cell):
    import jax
    import jax.numpy as jnp

    task = tiny_cell.task
    key = jax.random.PRNGKey(0)
    logits = (jax.random.normal(key, (4, 8, 13)),
              jax.random.normal(jax.random.fold_in(key, 1), (4, 8, 13)))
    ids = jax.random.randint(jax.random.fold_in(key, 2), (4, 9), 0, 13)
    targets = jnp.concatenate([ids[:, 1:-1], -jnp.ones((4, 1), jnp.int32)], axis=1)
    logp = [jax.nn.log_softmax(l) for l in logits]
    next_token = -np.mean([logp[0][b, i, targets[b, i]] for b in range(4) for i in range(7)])
    further = -np.mean([logp[1][b, i, targets[b, i + 1]] for b in range(4) for i in range(6)])
    assert float(task.loss(logits, targets)) == pytest.approx(
        next_token + 0.3 * further, rel=1e-6)
    # a step in blocks of rows: the sums over the counts is the whole batch's loss
    parts = [task.loss_parts(tuple(l[b:b + 2] for l in logits), targets[b:b + 2])
             for b in (0, 2)]
    total, count = (sum(p[i] for p in parts) for i in (0, 1))
    assert float(total / count) == pytest.approx(float(task.loss(logits, targets)), rel=1e-6)
    assert float(count) == 4 * 7
    # the program's sequence loss is the same (sum, count), head by head
    from fedtpu.ops.losses import next_token_ce_parts, shift_targets

    s0, n0, _ = next_token_ce_parts(logits[0], targets)
    s1, n1, _ = next_token_ce_parts(logits[1], shift_targets(targets, 1))
    assert float(s0 / n0 + 0.3 * s1 / n1) == pytest.approx(
        float(task.loss(logits, targets)), rel=1e-6)
    assert (float(n0), float(n1)) == (28.0, 24.0)


# ----------------------------------------------------------------- the readers
def test_the_new_readers_return_nothing_without_their_scopes(cell):
    from benchmark import run

    read = lambda name, ctx: run.load_py(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py")).read(ctx)
    names = ("mla.device_share", "mla.core_roofline", "moe.device_share",
             "lm_loss.device_share")
    ctx = {"cell": cell, "chips": 1, "trace": None, "traced_rounds": 0,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert all(read(n, ctx) is None for n in names)
    # the parent's capture: the local step's scope, none of the new ones
    ctx["trace"] = {"busy_s": 10.0, "busy_by_scope": {"fed.local_step.fwd_bwd": 1.9}}
    assert all(read(n, ctx) is None for n in names)
    pre = "fed.local_step.fwd_bwd."
    ctx["trace"]["busy_by_scope"].update({
        pre + "attention": 0.2, pre + "attention.core": 4.0, pre + "moe.experts": 0.1,
        pre + "moe.router": 0.1, pre + "moe": 0.2, pre + "lm_loss": 0.3})
    ctx["traced_rounds"] = 2
    assert read("mla.device_share", ctx) == pytest.approx(42.0)
    assert read("moe.device_share", ctx) == pytest.approx(4.0)
    assert read("lm_loss.device_share", ctx) == pytest.approx(3.0)
    least = 32 * 6 * 6 * 32 * 320 * 4096 * 4097 / 2 / 197e12
    assert read("mla.core_roofline", ctx) == pytest.approx(100 * 2 * least / 4.0)
    assert read("mla.core_roofline", ctx) < 100


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
@pytest.fixture(scope="module")
def tiny_runs():
    from benchmark import run

    cache = {}

    def get(name, seed=7):
        if (name, seed) not in cache:
            lines = []
            cache[name, seed] = (run.run(TINY, name, seed, 0.2, False, need_tpu=False,
                                         out=lines.append), lines)
        return cache[name, seed]

    return get


@pytest.mark.parametrize("name", ["joyai_tiny_f32.fl4_seq32", "joyai_tiny.fl4_seq32"])
def test_sequential_rounds_agree_with_the_reference(tiny_runs, name):
    """The whole model's loss with the prediction module, and the first
    update and two rounds' change of a federation of 4 clients in sequence,
    2 steps of 2 rows in micro-batches of 1: in float32 to rounding (limits
    1e-4), in bfloat16 within the tiny cell's limits."""
    result, lines = tiny_runs(name)
    assert result["correct"] and result["failed"] == 0, "\n".join(lines)
    held = [l for l in lines if l.startswith("check ") and "limit" in l]
    assert len(held) >= 3 and all(l.endswith("ok") for l in held)
    assert set(result["metrics"]) == {"samples_per_s_per_chip", "setup_s"}
    losses = [l for l in lines if l.startswith("check rounds=")][0]
    first = float(losses.split("program_losses=[")[1].split(",")[0])
    assert first == pytest.approx(1.3 * math.log(97), rel=0.1)


def test_the_fp8_control_fails_the_tiny_cells_limits():
    from benchmark import control, run

    limits = run.Cell(TINY, "joyai_tiny.fl4_seq32").limits
    rows, _ = control.readings(TINY, "joyai_tiny.fl4_seq32", [31], 1,
                               program=False, need_tpu=False, out=lambda s: None)
    for row in rows:
        low = row["control_fp8"]
        assert low["update1_diff"] > limits["update1_diff"]["limit"], row
        assert low["change_gap"] > limits["change_gap"]["limit"], row
