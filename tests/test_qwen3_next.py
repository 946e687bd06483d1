"""Qwen3-Next in the program, at a small size on the CPU, held to the plain
reference (``benchmark/reference/qwen3_next.py``: float32 jax.numpy, the
recurrence token by token, nothing of the program): each mixer and a whole
block of each kind, forward and gradient, from the same seeded weights, in
float32 and in bfloat16; the chunked delta rule against the token-by-token
one at the gates' extremes; the rotary pairing; the program's tree; the token
path of a federation.

What the two language models share (the routed experts' path, the plain
attention body) is held in ``tests/test_lm_layers.py`` for both; the whole
model's loss and whole sequential rounds through ``Federation.step()`` are in
``tests/benchmark/test_qwen3_next_cell.py`` (the harness makes that
comparison).
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
from fedtpu.models import qwen3_next as prog

TINY = os.path.join(ROOT, "tests", "benchmark", "qwen_tiny", "configs",
                    "qwen_tiny_f32.json")
T, D = 32, 64


@pytest.fixture(scope="module")
def cfg():
    with open(TINY) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def ref():
    from benchmark import run

    return run.load_py(os.path.join(ROOT, "benchmark", "reference", "qwen3_next.py"))


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
#  float32  the order of float32 sums only (the chunked rule and the solve
#           against the token-by-token recurrence): read 1e-6 and 1e-5
#  bfloat16 8 bits of mantissa into every product: read 0.4-2.6 % forward and
#           0.7-11 % in the gradient over four seeds (the DeltaNet layers'
#           division by |q|, |k| over 16 dimensions amplifies it; the softmax
#           layer reads 0.6 % and 0.8 %). The reference with fp8 operands
#           reads 8-20 % and 26-93 %: the limits lie between.
TOLERANCE = {"float32": (2e-5, 1e-4), "bfloat16": (0.04, 0.15)}
LAYERS = {
    "delta_net": (("layer_0", "linear_attn"), lambda s: prog.GatedDeltaNet(s),
                  lambda f: f.delta_net),
    "softmax_attention": (("layer_3", "self_attn"), lambda s: prog.GatedAttention(s),
                          lambda f: f.attention),
    "delta_net_block": (("layer_1",), lambda s: prog.Block(s, 1), lambda f: f.block),
    "softmax_block": (("layer_3",), lambda s: prog.Block(s, 3, True),
                      lambda f: f.block),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(LAYERS))
def test_a_layer_is_the_references_forward_and_gradient(cfg, ref, weights, name, dtype):
    from benchmark.reference.layers import ident

    path, make, of = LAYERS[name]
    p = weights
    for key in path:
        p = p[key]
    x = _x(1, 2, T, D)
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


def _rule_inputs(t, heads=2, per_key=2, dk=8, dv=8):
    """Normalised q and k, values, and gates that visit their extremes:
    decays ``exp(g)`` of 2e-9, 0.9999 and between, ``beta`` of 1e-4, 0.9999 and
    between, in runs longer than a chunk and token by token."""
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(_x(11, t, heads, dk)) * dk ** -0.5
    k = unit(_x(12, t, heads, dk))
    v = _x(13, t, heads, per_key, dv)
    at = jnp.arange(t)[:, None, None] + jnp.arange(heads * per_key).reshape(
        1, heads, per_key)
    g = jnp.choose(at % 5, jnp.array([-20.0, -1e-4, -0.3, -1e-4, -3.0]))
    g = jnp.where((at // 20) % 2 == 1, -1e-4, g)  # a run of no decay at all
    beta = jnp.choose((at // 3) % 4, jnp.array([1e-4, 0.9999, 0.5, 0.9999]))
    return q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32)


@pytest.mark.parametrize("t,chunk", [(48, 16), (40, 16), (24, 64), (64, 8)])
def test_the_chunked_rule_is_the_token_by_token_recurrence(ref, t, chunk):
    """Forward and every gradient, over three chunks, over a length the chunk
    does not divide (padded with tokens that leave the state alone), over a
    row shorter than a chunk and over eight chunks: float32 to 1e-4 (sums in
    another order; the solve against 64 substitutions)."""
    args = _rule_inputs(t)
    per_key = args[2].shape[2]

    def token_by_token(q, k, v, g, beta):
        of_value = lambda a: jnp.repeat(a, per_key, axis=1)
        flat = lambda a: a.reshape((t, -1) + a.shape[3:])
        o = ref.delta_rule(of_value(q), of_value(k), flat(v), flat(g), flat(beta))
        return o.reshape(v.shape)

    got = _value_and_grads(lambda *a: prog.gated_delta_rule(*a, chunk), *args)
    want = _value_and_grads(token_by_token, *args)
    assert got[0].shape == args[2].shape
    assert _rel(got[0], want[0]) <= 1e-4
    for ours, theirs in zip(got[1], want[1], strict=True):
        assert _rel(ours, theirs) <= 1e-4
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in jax.tree.leaves(got))


def test_a_state_that_never_decays_remembers_its_first_token(ref):
    """``g = 0``, ``beta = 1`` and orthonormal keys: the rule stores each
    value under its key, and the key reads it back chunks later."""
    t, dk = 8, 8
    k = jnp.eye(dk)[:, None, :]                      # [T, 1, dk], orthonormal
    v = _x(3, t, 1, 1, 4)
    zeros, ones = jnp.zeros((t, 1, 1)), jnp.ones((t, 1, 1))
    q = jnp.broadcast_to(k[0], (t, 1, dk))           # every token asks for key 0
    o = prog.gated_delta_rule(q, k, v, zeros, ones, 2)
    np.testing.assert_allclose(o, jnp.broadcast_to(v[0], o.shape), atol=1e-6)


def test_rope_turns_the_first_quarter_in_half_pairs_as_the_reference_does(ref):
    x = _x(4, T, 16)
    got = prog.rope_half(x, 1e7, 4)
    np.testing.assert_allclose(got, ref.rotate_half(x, 1e7, 4), atol=1e-6)
    np.testing.assert_array_equal(got[:, 4:], x[:, 4:])  # three quarters pass
    np.testing.assert_allclose(got[0], x[0], atol=1e-7)   # position 0 stays
    # dimension i turns with i + 2, and a turn keeps the pair's length
    pairs = lambda a: np.hypot(np.asarray(a)[:, :2], np.asarray(a)[:, 2:4])
    np.testing.assert_allclose(pairs(got), pairs(x), rtol=1e-5)
    # heads in between are left to broadcasting
    np.testing.assert_allclose(
        prog.rope_half(x[:, None, None, :], 1e7, 4)[:, 0, 0], got, atol=1e-7)


def test_the_convolution_sees_the_past_only():
    x, kernel = _x(5, T, 6), _x(6, 4, 6)
    y = prog.causal_conv(x, kernel)
    want = sum(kernel[i] * jnp.pad(x, ((3, 0), (0, 0)))[i:i + T] for i in range(4))
    np.testing.assert_allclose(y, want, atol=1e-6)
    later = x.at[20:].set(0.0)
    np.testing.assert_array_equal(prog.causal_conv(later, kernel)[:20], y[:20])


def test_the_programs_tree_is_the_references_parameter_list(cfg, ref):
    from fedtpu import models

    args = dict(cfg["program"]["round"]["model_args"])
    args.pop("micro_batch_rows")
    model = models.create("qwen3_next", num_classes=cfg["vocab_size"],
                          remat=True, **args)
    ids = jnp.zeros((1, T), jnp.int32)
    shapes = jax.eval_shape(
        lambda k: model.init(k, ids, train=True, targets=ids)["params"],
        jax.random.PRNGKey(0))
    ours = {tuple(k.key for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert ours == {path: tuple(shape) for path, shape, _ in ref.spec(cfg)[0]}
    kinds = [("self_attn" if "self_attn" in shapes[f"layer_{i}"] else "linear_attn")
             for i in range(4)]
    assert kinds == ["linear_attn"] * 3 + ["self_attn"]
    with pytest.raises(ValueError, match="no size"):
        models.create("qwen3_next", widht=3)
    with pytest.raises(ValueError, match="no range"):
        prog.experts(prog.Sizes(experts_held=(500, 520)), 1)


def test_a_language_model_federation_trains_counts_and_evaluates(cfg):
    from fedtpu.data import load

    model_args = dict(cfg["program"]["round"]["model_args"],
                      attn_q_block=64, moe_chunk_pairs=512, moe_block_rows=64)
    fed = Federation(RoundConfig(
        model="qwen3_next", num_classes=256, image_size=(128,), remat=True,
        model_args=model_args,
        opt=OptimizerConfig(learning_rate=0.3, momentum=0.0, weight_decay=0.0),
        data=DataConfig(dataset="tokens", batch_size=2, num_examples=64,
                        partition="iid"),
        fed=FedConfig(num_clients=2, client_schedule="sequential"),
        steps_per_round=2), seed=0)
    assert fed.state.params["embed"]["embedding"].shape == (256, 64)
    losses = [fed.step() for _ in range(4)]
    assert float(losses[-1].loss) < float(losses[0].loss)
    m = losses[-1]
    # 2 clients x 2 steps x 2 rows x 127 positions with a target
    assert float(m.tokens) == 2 * 2 * 2 * 127
    # 4 expert layers each route 4 of 16 experts a token, 4 of them held
    assert 0 < int(m.moe_pairs_here) <= 2 * 2 * 2 * 128 * 4 * 4
    assert 1.0 <= float(m.moe_load_max_over_mean) <= 4.0
    loss, acc = fed.evaluate(*load("tokens", "test", num=200))
    assert 0 < loss < float(losses[0].loss) and 0 <= acc <= 1
