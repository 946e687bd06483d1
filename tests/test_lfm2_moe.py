"""LFM2-MoE in the program, at a small size on the CPU, held to the plain
reference (``benchmark/reference/lfm2_moe.py``: float32 jax.numpy, three
shifted sums, one head's whole scores at a time, nothing of the program): each
operator and a whole block of each kind, forward and gradient, from the same
seeded weights, in float32 and in bfloat16; the gated convolution against
``jax.lax.conv_general_dilated`` and against a recurrence over a two-token
cache; grouped-query attention at the published heads of 64 through either
body of its core; a token none of
whose experts is held; the program's tree; a step in one micro-batch against
micro-batches of one row through a federation.

The shares of the routed experts adding up to the uncut layer, and every pair
on one held expert, are held in ``tests/test_lm_layers.py`` for all three
language models; the whole model's loss and whole sequential rounds through
``Federation.step()`` are in ``tests/benchmark/test_lfm2_cell.py`` (the harness
makes that comparison).
"""

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
from fedtpu.models import lfm2_moe as prog
from fedtpu.models import lm_layers
from fedtpu.obs.registry import get_global_registry
from fedtpu.ops import attention_kernels as ak

TINY = os.path.join(ROOT, "tests", "benchmark", "lfm2_tiny", "configs",
                    "lfm2_tiny_f32.json")
T, D = 32, 64


@pytest.fixture(scope="module")
def cfg():
    with open(TINY) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def ref():
    from benchmark import run

    return run.load_py(os.path.join(ROOT, "benchmark", "reference", "lfm2_moe.py"))


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


def _rel(a, b):
    """Norm of the difference over the reference's norm, whole tree."""
    a, b = (jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                             for l in jax.tree.leaves(t)]) for t in (a, b))
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _value_and_grads(f, *args):
    """``f``'s output contracted with a fixed cotangent, and its gradients."""
    def scalar(*a):
        out = f(*a)
        return jnp.sum(out * _x(99, *out.shape)), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return out, grads


# What each precision may differ by from the float32 reference, (forward,
# gradient), as norms of the difference over the reference's norm:
#  float32  the order of float32 sums only: read 0 to 3e-7
#  bfloat16 8 bits of mantissa into every product and out of every layer: read
#           0.4-0.7 % forward and 0.5-1.1 % in the gradient of every layer and
#           block; the limits leave it twice that, far under what dropping a
#           term or a head would read.
TOLERANCE = {"float32": (2e-6, 1e-5), "bfloat16": (0.02, 0.03)}
LAYERS = {
    "short_conv": (("layer_0", "conv"), lambda s: prog.ShortConv(s),
                   lambda f: lambda p, x, q: f.short_conv(p, x, q)),
    "dense_conv_block": (("layer_0",), lambda s: prog.Block(s, 0),
                         lambda f: lambda p, x, q: f.block(p, x, 0, q)),
    "attention_expert_block": (("layer_1",), lambda s: prog.Block(s, 1, True),
                               lambda f: lambda p, x, q: f.block(p, x, 1, q)),
    "conv_expert_block": (("layer_2",), lambda s: prog.Block(s, 2, True),
                          lambda f: lambda p, x, q: f.block(p, x, 2, q)),
}


@pytest.mark.parametrize("name,dtype", [
    ("short_conv", "float32"), ("short_conv", "bfloat16"),
    ("dense_conv_block", "float32"),
    ("attention_expert_block", "float32"), ("attention_expert_block", "bfloat16"),
    ("conv_expert_block", "bfloat16")])
def test_a_layer_is_the_references_forward_and_gradient(cfg, ref, weights, name, dtype):
    from benchmark.reference.layers import ident

    path, make, of = LAYERS[name]
    p = weights
    for key in path:
        p = p[key]
    x = _x(1, 1, T, D)
    layer, theirs = make(_sizes(cfg)), of(ref.make_forward(cfg))

    def ours(p, x):
        cast = jax.tree.map(lambda a: a.astype(dtype), (p, x))
        y = layer.apply({"params": cast[0]}, cast[1])
        return (y[0] if isinstance(y, tuple) else y).astype(jnp.float32)

    got = _value_and_grads(ours, p, x)
    want = _value_and_grads(
        lambda p, x: jnp.stack([theirs(p, row, ident) for row in x]), p, x)
    forward, gradient = TOLERANCE[dtype]
    assert _rel(got[0], want[0]) <= forward
    assert _rel(got[1], want[1]) <= gradient


def test_the_gated_convolution_is_a_depthwise_convolution_and_a_two_token_cache():
    """``C x conv(B x X)`` against ``jax.lax.conv_general_dilated`` (a
    depthwise convolution of width 3, two zeros ahead of the row) and against
    the decoding form: a cache of the last two ``B x X``, one token at a time.
    It sees the past only."""
    b, c, x, taps = _x(1, T, 6), _x(2, T, 6), _x(3, T, 6), _x(4, 3, 6)
    got = prog.gated_short_conv(b, c, x, taps)
    conv = jax.lax.conv_general_dilated(
        (b * x).T[None], taps.T[:, None, :], (1,), ((2, 0),),
        dimension_numbers=("NCH", "OIH", "NCH"), feature_group_count=6,
        precision="highest")
    np.testing.assert_allclose(got, c * conv[0].T, rtol=1e-5, atol=1e-6)

    def one_token(cache, bcx):
        b_t, c_t, x_t = bcx
        window = jnp.concatenate([cache, (b_t * x_t)[None]])  # [3, channels]
        return window[1:], c_t * jnp.sum(window * taps, axis=0)

    _, stepped = jax.lax.scan(one_token, jnp.zeros((2, 6)), (b, c, x))
    np.testing.assert_allclose(got, stepped, rtol=1e-5, atol=1e-6)
    later = x.at[20:].set(0.0)
    np.testing.assert_array_equal(
        prog.gated_short_conv(b, c, later, taps)[:20], got[:20])
    # the two language models call ONE convolution
    from fedtpu.models import qwen3_next

    assert qwen3_next.causal_conv is lm_layers.causal_conv is prog.causal_conv
    assert qwen3_next.rope_half is lm_layers.rope_half is prog.rope_half


@pytest.mark.parametrize("t, body", [(T, "plain"), (ak.BLOCK, "kernel")])
def test_grouped_queries_at_heads_of_64_run_the_body_their_length_calls_for_and_agree(
        cfg, ref, monkeypatch, t, body):
    """4 query heads on 2 key-value heads of the published 64 (hidden 256).
    Where the program asks, the test says "a TPU" (the kernels interpreted):
    the fused kernels take heads of half a lane group as they take whole ones,
    at a length their blocks divide the layer's core is counted as theirs and
    at the tiny length as the plain query blocks', and either way the layer
    is the reference's, rotary over the whole head included."""
    from benchmark.reference.layers import ident

    wide = dict(cfg, hidden_size=256)
    assert wide["hidden_size"] // wide["num_attention_heads"] == 64
    p = _weights(ref, wide)["layer_1"]["self_attn"]
    x = _x(2, 1, t, 256)
    layer = prog.Attention(_sizes(cfg, hidden_size=256))
    monkeypatch.setattr(ak, "_mode", lambda interpret: "interpret")
    for width in (64, 128):
        q = jnp.zeros((ak.BLOCK, 2, 2, width))
        assert ak.takes(q, None, q[:, :, 0], None, q[:, :, 0])
        assert not ak.takes(q[:T], None, q[:T, :, 0], None, q[:T, :, 0])
    traced = lambda b: get_global_registry().counter(
        lm_layers.CORES_TRACED, labels={"body": b}).value
    before = {b: traced(b) for b in ("kernel", "plain")}
    got = _value_and_grads(lambda p, x: layer.apply({"params": p}, x), p, x)
    other = "plain" if body == "kernel" else "kernel"
    assert traced(body) > before[body] and traced(other) == before[other]
    theirs = ref.make_forward(wide).attention
    want = _value_and_grads(
        lambda p, x: jnp.stack([theirs(p, row, ident) for row in x]), p, x)
    assert _rel(got[0], want[0]) <= 2e-6 and _rel(got[1], want[1]) <= 1e-5
    # the rotary turn is the reference's, over all 64 dimensions
    h = _x(3, T, 64)
    np.testing.assert_allclose(
        lm_layers.rope_half(h, 1e6, 64), ref.rotate_half(h, 1e6), atol=2e-6)


def test_a_token_whose_experts_are_all_absent_keeps_its_residual(cfg, weights):
    """There is no shared expert: where none of a token's four experts is
    held, the layer adds exactly nothing and the block hands on ``h +
    Op(h)``; where one is, it adds something."""
    sizes = _sizes(cfg)
    p = weights["layer_2"]
    x = _x(7, 2, T, D)
    mixed = x + prog.ShortConv(sizes).apply(
        {"params": p["conv"]}, lm_layers._rms(x, p["operator_norm"]["scale"], 1e-5))
    u = lm_layers._rms(mixed, p["ffn_norm"]["scale"], 1e-5)
    s = jax.nn.sigmoid(u.reshape(-1, D) @ p["moe"]["router"])
    _, chosen = jax.lax.top_k(s + prog.selection_bias(2, sizes), 4)
    lo, hi = prog.experts(sizes, 2)["held"]
    here = np.asarray((chosen >= lo) & (chosen < hi))
    absent = ~here.any(axis=1)
    assert 0 < absent.sum() < absent.size
    y, pairs, _ = lm_layers.ExpertLayer(**prog.experts(sizes, 2)).apply(
        {"params": p["moe"]}, u)
    assert int(pairs) == here.sum()
    flat = np.asarray(y.reshape(-1, D))
    assert not flat[absent].any()
    assert np.abs(flat[~absent]).max(axis=1).min() > 0
    out, _, _ = prog.Block(sizes, 2).apply({"params": p}, x)
    np.testing.assert_array_equal(
        np.asarray(out.reshape(-1, D))[absent], np.asarray(mixed.reshape(-1, D))[absent])


def test_the_programs_tree_is_the_references_parameter_list(cfg, ref):
    from fedtpu import models

    args = dict(cfg["program"]["round"]["model_args"])
    args.pop("micro_batch_rows")
    model = models.create("lfm2_moe", num_classes=cfg["vocab_size"],
                          remat=True, **args)
    ids = jnp.zeros((1, T), jnp.int32)
    shapes = jax.eval_shape(
        lambda k: model.init(k, ids, train=True, targets=ids)["params"],
        jax.random.PRNGKey(0))
    ours = {tuple(k.key for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert ours == {path: tuple(shape) for path, shape, _ in ref.spec(cfg)[0]}
    assert "head" not in shapes  # tied: the embedding's transpose
    assert ["self_attn" in shapes[f"layer_{i}"] for i in range(5)] == [
        False, True, False, False, False]
    assert ["feed_forward" in shapes[f"layer_{i}"] for i in range(5)] == [
        True, False, False, False, False]
    # the published pattern is the default: attention at layers 2, 6, ..., 38
    kinds = prog.Sizes().kinds
    assert len(kinds) == 40 and kinds.count("full_attention") == 10
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == list(
        range(2, 40, 4))
    with pytest.raises(ValueError, match="no size"):
        models.create("lfm2_moe", widht=3)
    with pytest.raises(ValueError, match="layer_types"):
        prog.Sizes(num_hidden_layers=2, layer_types=("conv", "window")).kinds
    with pytest.raises(ValueError, match="no range"):
        prog.experts(prog.Sizes(experts_held=(60, 70)), 1)


def _round_config(cfg, micro_batch_rows, dtype="float32", momentum=0.0):
    # two layers, both operators and both feed-forwards: the step's path, not
    # the model, is what this holds
    model_args = dict(cfg["program"]["round"]["model_args"],
                      num_hidden_layers=2, layer_types=["conv", "full_attention"],
                      micro_batch_rows=micro_batch_rows)
    return RoundConfig(
        model="lfm2_moe", num_classes=256, image_size=(T,), remat=True,
        dtype=dtype, model_args=model_args,
        opt=OptimizerConfig(learning_rate=0.1, momentum=momentum, weight_decay=0.0),
        data=DataConfig(dataset="tokens", batch_size=2, num_examples=64,
                        partition="iid"),
        fed=FedConfig(num_clients=2, client_schedule="sequential"),
        steps_per_round=2)


def _federation(cfg, micro_batch_rows, dtype="float32"):
    return Federation(_round_config(cfg, micro_batch_rows, dtype), seed=0)


def test_one_micro_batch_of_the_step_and_micro_batches_of_a_row_give_one_update(cfg):
    """``micro_batch_rows`` equal to the batch is ONE micro-batch through the
    same plain-SGD path as rows of one (the parameters cast once, each
    gradient straight into the float32 parameters): the two federations'
    first updates agree to float32 rounding, they count alike, and the model
    trains."""
    whole, by_row = _federation(cfg, 2), _federation(cfg, 1)
    start = jax.tree.map(np.asarray, whole.state.params)
    first = [fed.step() for fed in (whole, by_row)]
    assert float(first[0].loss) == pytest.approx(float(first[1].loss), rel=1e-5)
    update = lambda fed: jax.tree.map(lambda a, b: a - b, fed.state.params, start)
    assert _rel(update(by_row), update(whole)) <= 1e-4
    for m in first:
        # 2 clients x 2 steps x 2 rows x 127 positions with a target
        assert float(m.tokens) == 2 * 2 * 2 * 127
        # the one expert layer routes 4 of 16 experts a token, 2 of them held
        assert 0 < int(m.moe_pairs_here) <= 2 * 2 * 2 * 128 * 2
        assert 1.0 <= float(m.moe_load_max_over_mean) <= 2.0
    assert int(first[0].moe_pairs_here) == int(first[1].moe_pairs_here)
    assert float(whole.step().loss) < float(first[0].loss)


def test_a_whole_step_micro_batch_with_momentum_is_the_whole_batch_step(cfg):
    """``micro_batch_rows`` equal to the batch with momentum ran as the
    whole-batch step before this model came, and still does: it builds, keeps
    its buffers and trains; micro-batches of a row still need plain SGD."""
    from fedtpu.core.client import make_local_update

    fed = Federation(_round_config(cfg, 2, momentum=0.9), seed=0)
    losses = [float(fed.step().loss) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    with pytest.raises(ValueError, match="micro_batch_rows"):
        make_local_update(None, _round_config(cfg, 1, momentum=0.9))
