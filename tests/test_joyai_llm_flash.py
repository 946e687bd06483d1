"""JoyAI-LLM-Flash in the program, at a small size on the CPU, held to the
plain reference (``benchmark/reference/joyai_llm_flash.py``: float32
jax.numpy, nothing of the program): each layer's forward and gradient from
the same seeded weights; the clients in sequence against the clients under
vmap; the token path of evaluation and of the run CLI's configuration. (The
shares of the routed experts adding up to the uncut layer, and routing so
skewed that every token lands on one held expert, are held for this model and
for ``qwen3_next`` alike in ``tests/test_lm_layers.py``.)

The whole model's loss and a whole sequential round through
``Federation.step()`` against the reference's rounds are in
``tests/benchmark/test_joyai_cell.py`` (the harness makes that comparison).
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
from fedtpu.core import Federation
from fedtpu.models import joyai_llm_flash as prog

TINY = os.path.join(ROOT, "tests", "benchmark", "joyai_tiny", "configs",
                    "joyai_tiny_f32.json")
T, D = 32, 64


@pytest.fixture(scope="module")
def cfg():
    with open(TINY) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def ref():
    from benchmark import run

    return run.load_py(os.path.join(
        ROOT, "benchmark", "reference", "joyai_llm_flash.py"))


def _sizes(cfg, **over):
    args = dict(cfg["program"]["round"]["model_args"])
    args.pop("micro_batch_rows")
    args.update(over)
    args = {k: tuple(v) if isinstance(v, list) else v for k, v in args.items()}
    return prog.Sizes(vocab_size=cfg["vocab_size"], **args)


def _weights(ref, cfg, seed=3):
    from benchmark import seeded

    params, _ = seeded.make_weights(seed, *ref.spec(cfg))
    return jax.tree.map(jnp.asarray, params)


@pytest.fixture(scope="module")
def weights(ref, cfg):
    """The tiny configuration's seeded weights, drawn once for the module's
    cases (a draw is the whole model's, three seconds)."""
    return _weights(ref, cfg)


def _x(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _close(a, b, tol=2e-5):
    """Norm of the difference over the reference's norm, leaf by leaf."""
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        scale = max(float(jnp.linalg.norm(y)), 1e-12)
        assert float(jnp.linalg.norm(x - y)) <= tol * scale, (x.shape, scale)


def _value_and_grads(f, *args):
    """``f``'s output contracted with a fixed cotangent, and its gradients."""
    def scalar(*a):
        out = f(*a)
        return jnp.sum(out * _x(99, *out.shape)), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return out, grads


def test_latent_attention_is_the_references_forward_and_gradient(cfg, ref, weights):
    from benchmark.reference.layers import ident

    p = weights["layer_0"]["attn"]
    x = _x(1, 2, T, D)
    layer = prog.LatentAttention(_sizes(cfg))
    ours = _value_and_grads(lambda p, x: layer.apply({"params": p}, x), p, x)
    forward = ref.make_forward(cfg)
    theirs = _value_and_grads(
        lambda p, x: jnp.stack([forward.attention(p, row, ident) for row in x]), p, x)
    _close(ours, theirs)


@pytest.mark.parametrize("name,layer,dense", [("layer_0", 0, True), ("layer_1", 1, False),
                                              ("mtp_0_block", 2, False)])
def test_a_block_is_the_references_forward_and_gradient(cfg, ref, weights, name, layer, dense):
    from benchmark.reference.layers import ident

    p = weights[name]
    h = _x(2, 2, T, D)
    block = prog.Block(_sizes(cfg), layer, dense)
    ours = _value_and_grads(lambda p, h: block.apply({"params": p}, h)[0], p, h)
    forward = ref.make_forward(cfg)
    theirs = _value_and_grads(
        lambda p, h: jnp.stack([forward.block(p, row, layer, ident) for row in h]), p, h)
    _close(ours, theirs)


def test_rope_turns_interleaved_pairs_as_the_reference_does(ref):
    x = _x(4, T, 8)
    np.testing.assert_allclose(prog.rope(x, 32e6), ref.rotate(x, 32e6), atol=1e-6)
    # position 0 is left as it is, and a turn keeps a pair's length
    np.testing.assert_allclose(prog.rope(x, 32e6)[0], x[0], atol=1e-7)
    pairs = lambda a: np.linalg.norm(np.asarray(a).reshape(T, 4, 2), axis=-1)
    np.testing.assert_allclose(pairs(prog.rope(x, 32e6)), pairs(x), rtol=1e-5)


def test_the_selection_bias_is_the_references_constant(cfg, ref):
    for layer in (1, 2):
        np.testing.assert_array_equal(
            prog.correction_bias(layer, _sizes(cfg)), ref.selection_bias(layer, cfg))
    assert float(jnp.std(ref.selection_bias(1, cfg))) == pytest.approx(0.01, rel=0.5)


def test_the_programs_tree_is_the_references_parameter_list(cfg, ref):
    from fedtpu import models

    args = dict(cfg["program"]["round"]["model_args"])
    args.pop("micro_batch_rows")
    model = models.create("joyai_llm_flash", num_classes=cfg["vocab_size"],
                          remat=True, **args)
    ids = jnp.zeros((1, T), jnp.int32)
    shapes = jax.eval_shape(
        lambda k: model.init(k, ids, train=True, targets=ids)["params"],
        jax.random.PRNGKey(0))
    ours = {tuple(k.key for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert ours == {path: tuple(shape) for path, shape, _ in ref.spec(cfg)[0]}
    with pytest.raises(ValueError, match="no size"):
        models.create("joyai_llm_flash", widht=3)
    with pytest.raises(ValueError, match="no range"):
        prog.experts(prog.Sizes(experts_held=(250, 260)), 1)


# ------------------------------------------------ the round, clients in sequence
def _smallcnn(schedule, **fed):
    return RoundConfig(
        model="smallcnn", num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05, momentum=0.9),
        data=DataConfig(dataset="synthetic", batch_size=8, num_examples=128,
                        partition="iid", augment=False),
        fed=FedConfig(num_clients=4, client_schedule=schedule, **fed),
        steps_per_round=2)


def test_clients_in_sequence_give_the_vmapped_rounds_update():
    """Same federation, same data, two rounds (the second with momentum from
    the first, and with a client down): the running sum is the stacked mean
    to float32 rounding."""
    feds = {s: Federation(_smallcnn(s), seed=1) for s in ("vmap", "sequential")}
    for r in range(2):
        for fed in feds.values():
            if r == 1:
                fed.set_alive(2, False)
            m = fed.step()
            assert float(m.num_active) == (4 if r == 0 else 3)
    a, b = feds["vmap"].state, feds["sequential"].state
    _close(b.params, a.params, tol=1e-6)
    _close(b.opt_state, a.opt_state, tol=1e-6)
    np.testing.assert_allclose(b.last_client_loss, a.last_client_loss, rtol=1e-5)
    assert m.tokens == () and m.moe_pairs_here == ()


@pytest.mark.parametrize("fed,names", [
    (dict(aggregator="median"), "aggregator"),
    (dict(aggregator="krum"), "aggregator"),
    (dict(compression="topk"), "compression"),
    (dict(compression="rotq", delta_layout="flat"), "compression"),
    (dict(delta_layout="flat"), "delta_layout"),
    (dict(dp_clip_norm=1.0, weighted=False), "DP clipping"),
])
def test_what_needs_all_the_rows_is_refused_with_the_fields_name(fed, names):
    with pytest.raises(ValueError, match="client_schedule='sequential'.*" + names):
        Federation(_smallcnn("sequential", **fed), seed=0)


def test_an_unknown_schedule_is_refused():
    with pytest.raises(ValueError, match="client_schedule"):
        Federation(_smallcnn("in_turn"), seed=0)


def test_clients_in_sequence_at_momentum_0_keep_no_buffers():
    cfg = _smallcnn("sequential")
    cfg = dataclasses.replace(cfg, opt=dataclasses.replace(cfg.opt, momentum=0.0))
    fed = Federation(cfg, seed=0)
    assert jax.tree.leaves(fed.state.opt_state) == []
    before = jax.tree.map(np.asarray, fed.state.params)
    fed.step()
    assert all(np.any(a != b) for a, b in zip(
        jax.tree.leaves(before), jax.tree.leaves(fed.state.params)))


# ------------------------------------------------------- the token task end to end
def _token_cfg(cfg, schedule="sequential", **args):
    model_args = dict(cfg["program"]["round"]["model_args"], **args)
    return RoundConfig(
        model="joyai_llm_flash", num_classes=256, image_size=(128,), remat=True,
        model_args=model_args,
        opt=OptimizerConfig(learning_rate=0.3, momentum=0.0, weight_decay=0.0),
        data=DataConfig(dataset="tokens", batch_size=2, num_examples=64,
                        partition="iid"),
        fed=FedConfig(num_clients=2, client_schedule=schedule), steps_per_round=2)


def test_a_language_model_federation_trains_counts_and_evaluates(cfg):
    from fedtpu.data import load

    fed = Federation(_token_cfg(cfg, attn_q_block=64, moe_chunk_pairs=512, moe_block_rows=64), seed=0)
    assert fed.state.params["embed"]["embedding"].shape == (256, 64)
    losses = [fed.step() for _ in range(4)]
    assert float(losses[-1].loss) < float(losses[0].loss)
    m = losses[-1]
    # 2 clients x 2 steps x 2 rows x 127 positions with a target
    assert float(m.tokens) == 2 * 2 * 2 * 127
    # 2 expert layers (the model's, the prediction module's) each route 4 of
    # 16 experts a token, 4 of them held: at most every pair, at least one
    assert 0 < int(m.moe_pairs_here) <= 2 * 2 * 2 * 128 * 4 * 2
    assert 1.0 <= float(m.moe_load_max_over_mean) <= 4.0
    loss, acc = fed.evaluate(*load("tokens", "test", num=200))
    assert 0 < loss < float(losses[0].loss) and 0 <= acc <= 1


def test_micro_batches_need_plain_sgd_and_a_batch_they_divide(cfg):
    bad = _token_cfg(cfg, attn_q_block=64, moe_chunk_pairs=512, moe_block_rows=64)
    with pytest.raises(ValueError, match="micro_batch_rows"):
        Federation(dataclasses.replace(
            bad, opt=dataclasses.replace(bad.opt, momentum=0.9)), seed=0)


def test_model_args_are_hashable_whatever_they_came_as():
    a = RoundConfig(model_args={"b": [1, 2], "a": 3})
    b = RoundConfig(model_args=[("a", 3), ("b", (1, 2))])
    assert a == b and hash(a) == hash(b) and a.model_args == (("a", 3), ("b", (1, 2)))
    assert RoundConfig().model_args == ()
