"""The fused causal core (``fedtpu/ops/attention_kernels.py``) against the
plain body it replaces on a TPU (``joyai_llm_flash.causal_attention``), on the
CPU through the Pallas interpreter: the forward output and the gradient of
every operand, at a length of three blocks (so a query block meets a skipped,
a full and a diagonal key block), the published head sizes (128 + 64 and
128) and two heads; float32 operands agree to float32 rounding, bfloat16
operands to bfloat16 rounding. The same for the grouped form without rotary
operands (``qwen3_next``'s softmax layer: a key head serves a group of query
heads) at a group of 1, 2 and 8 and a head of 128 and 256, and at a head of 64
(``lfm2_moe``'s: up to four heads of a group stacked in a grid step) at a
group of 1, 2, 4 and 8. Which body a sequence takes, and that the counter says
so.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu.models import joyai_llm_flash as prog
from fedtpu.models import lm_layers, qwen3_next
from fedtpu.obs.registry import get_global_registry
from fedtpu.ops import attention_kernels as ak

T, H, NOPE, ROPE, VD = 3 * ak.BLOCK, 2, 128, 64, 128
SCALE = 1.0 / math.sqrt(NOPE + ROPE)
OPERANDS = ("q_nope", "q_rope", "k_nope", "k_rope", "v")
# Largest difference over the plain body's largest magnitude.
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}


def _operands(dtype, t=T, seed=0, nope=NOPE, rope=ROPE, vd=VD):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(t, H, nope), (t, H, rope), (t, H, nope), (t, rope), (t, H, vd),
              (t, H, vd)]
    made = [jax.random.normal(k, s, jnp.float32).astype(dtype)
            for k, s in zip(keys, shapes)]
    return tuple(made[:5]), made[5]


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def both(request):
    """``(dtype name, kernel's, plain's)``: each ``{"out": ..., operand: its
    gradient}`` under one cotangent."""
    args, ct = _operands(jnp.dtype(request.param))

    def run(fn):
        out, vjp = jax.vjp(fn, *args)
        return dict(zip(OPERANDS, vjp(ct)), out=out)

    return (
        request.param,
        run(lambda *a: ak.causal_attention(*a, SCALE, interpret=True)),
        run(lambda *a: prog.causal_attention(*a, SCALE, ak.BLOCK)),
    )


@pytest.mark.parametrize("what", ("out",) + OPERANDS)
def test_the_kernels_are_the_plain_body(both, what):
    dtype, kernel, plain = both
    got, want = (np.asarray(x[what], np.float32) for x in (kernel, plain))
    assert got.shape == want.shape and kernel[what].dtype == plain[what].dtype
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= TOLERANCE[dtype] * np.abs(want).max()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_query_that_sees_one_key_returns_its_value(dtype):
    """The first softmax row has one unmasked score: probability 1, the
    output is ``v[0]``, and log-sum-exp and gradients stay finite."""
    args, ct = _operands(jnp.dtype(dtype), t=ak.BLOCK, seed=1)
    out, vjp = jax.vjp(
        lambda *a: ak.causal_attention(*a, SCALE, interpret=True), *args)
    np.testing.assert_array_equal(np.asarray(out[0], np.float32),
                                  np.asarray(args[4][0], np.float32))
    for g in vjp(ct):
        assert np.isfinite(np.asarray(g, np.float32)).all()


def _grouped_operands(dtype, group, width, t, seed=3):
    """``(q, k, v)`` and a cotangent: two key heads, each serving ``group``
    query heads (``0``: a query ``[T, H, d]`` with a key head each)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    of_q = (t, 2, group, width) if group else (t, 2, width)
    q, k, v, ct = (jax.random.normal(key, shape, jnp.float32).astype(dtype)
                   for key, shape in zip(keys, [of_q, (t, 2, width), (t, 2, width), of_q]))
    return (q, k, v), ct


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group, width", [
    (0, 128), (1, 128), (2, 128), (8, 128), (2, 256), (8, 256),
    # heads of half a lane group: a step takes up to four of a group, stacked
    (0, 64), (1, 64), (2, 64), (4, 64), (8, 64)])
def test_the_grouped_kernels_without_rotary_operands_are_the_plain_body(
        dtype, group, width):
    """Output and the gradients of q, k, v; two blocks (a full and two
    diagonal pairs a head), the eight heads of a group summed into their key
    head's ``dk`` and ``dv``."""
    args, ct = _grouped_operands(jnp.dtype(dtype), group, width, 2 * ak.BLOCK)
    scale = 1.0 / math.sqrt(width)

    def run(fn):
        out, vjp = jax.vjp(lambda q, k, v: fn(q, None, k, None, v), *args)
        return (out,) + vjp(ct)

    kernel = run(lambda *a: ak.causal_attention(*a, scale, interpret=True))
    plain = run(lambda *a: lm_layers.causal_attention(*a, scale, ak.BLOCK))
    for got, want in zip(kernel, plain, strict=True):
        assert got.shape == want.shape and got.dtype == want.dtype
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        assert np.isfinite(got).all()
        assert np.abs(got - want).max() <= TOLERANCE[dtype] * np.abs(want).max()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group, width", [(8, 256), (4, 64)])
def test_a_grouped_query_that_sees_one_key_returns_its_value(dtype, group, width):
    """Every query head of a group reads ITS key head's first value, the
    stacked heads of a narrow group too."""
    (q, k, v), ct = _grouped_operands(jnp.dtype(dtype), group, width, ak.BLOCK, seed=4)
    out, vjp = jax.vjp(lambda q, k, v: ak.causal_attention(
        q, None, k, None, v, width ** -0.5, interpret=True), q, k, v)
    np.testing.assert_array_equal(
        np.asarray(out[0], np.float32),
        np.broadcast_to(np.asarray(v[0], np.float32)[:, None], out.shape[1:]))
    for g in vjp(ct):
        assert np.isfinite(np.asarray(g, np.float32)).all()


def _traced(body):
    return get_global_registry().counter(
        prog.CORES_TRACED, labels={"body": body}).value


def _without(args, *names):
    return tuple(None if n in names else a for n, a in zip(OPERANDS, args))


def _grouped(args):
    """The operands with two key heads' queries as ``[T, 1, 2, .]``: one key
    head (the first's keys and values) serving both."""
    q_nope, q_rope, k_nope, k_rope, v = args
    return (q_nope[:, None], None if q_rope is None else q_rope[:, None],
            k_nope[:, :1], k_rope, v[:, :1])


@pytest.mark.parametrize("t, widths, shape, mode, body", [
    (2 * ak.BLOCK, {}, None, "interpret", "kernel"),
    (2 * ak.BLOCK, {}, None, "xla", "plain"),         # no TPU: the plain body
    (ak.BLOCK + 128, {}, None, "interpret", "plain"),  # a length the blocks do not divide
    (32, {}, None, "interpret", "plain"),
    (ak.BLOCK, dict(nope=16, rope=8, vd=16), None, "interpret", "plain"),  # narrow heads
    # a key head that serves a group, with the rotary operands and without
    (ak.BLOCK, {}, _grouped, "interpret", "kernel"),
    (ak.BLOCK, {}, lambda a: _without(_grouped(a), "q_rope", "k_rope"),
     "interpret", "kernel"),
    (ak.BLOCK, {}, lambda a: _without(a, "q_rope", "k_rope"), "interpret", "kernel"),
    (ak.BLOCK, {}, lambda a: _without(a, "q_rope", "k_rope"), "xla", "plain"),
    (ak.BLOCK + 128, {}, lambda a: _without(_grouped(a), "q_rope", "k_rope"),
     "interpret", "plain"),
    (ak.BLOCK, dict(nope=16, rope=8, vd=16),
     lambda a: _without(_grouped(a), "q_rope", "k_rope"), "interpret", "plain"),
    # heads of half a lane group without rotary operands: a key head each, a group
    (ak.BLOCK, dict(nope=64, vd=64), lambda a: _without(a, "q_rope", "k_rope"),
     "interpret", "kernel"),
    (ak.BLOCK, dict(nope=64, vd=64),
     lambda a: _without(_grouped(a), "q_rope", "k_rope"), "interpret", "kernel"),
    (ak.BLOCK, dict(nope=64, vd=64),
     lambda a: _without(_grouped(a), "q_rope", "k_rope"), "xla", "plain"),
    (ak.BLOCK + 128, dict(nope=64, vd=64),
     lambda a: _without(_grouped(a), "q_rope", "k_rope"), "interpret", "plain"),
    # ... with rotary operands, or beside values of another width: the plain body's
    (ak.BLOCK, dict(nope=64, vd=64), None, "interpret", "plain"),
    (ak.BLOCK, dict(nope=64, vd=128), lambda a: _without(a, "q_rope", "k_rope"),
     "interpret", "plain"),
])
def test_the_body_follows_backend_and_shapes_and_the_counter_says_which(
        monkeypatch, t, widths, shape, mode, body):
    monkeypatch.setattr(ak, "_mode", lambda interpret: mode)
    args, _ = _operands(jnp.float32, t=t, seed=2, **widths)
    if shape:
        args = shape(args)
    q_block = math.gcd(t, 256)  # the plain body's: it must divide the length
    before = {b: _traced(b) for b in ("kernel", "plain")}
    got = prog.attention_core(*args, SCALE, q_block)
    after = {b: _traced(b) for b in ("kernel", "plain")}
    other = "plain" if body == "kernel" else "kernel"
    assert after[body] == before[body] + 1 and after[other] == before[other]
    want = prog.causal_attention(*args, SCALE, q_block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("absent", ["q_rope", "k_rope"])
def test_a_rotary_operand_on_one_side_only_is_not_the_kernels(monkeypatch, absent):
    """Neither body has a meaning for it; the kernels say so before a trace
    and ``takes`` answers no."""
    monkeypatch.setattr(ak, "_mode", lambda interpret: "mosaic")
    args = _without(_operands(jnp.float32, t=ak.BLOCK)[0], absent)
    assert not ak.takes(*args)
    with pytest.raises(ValueError, match="rotary operands together"):
        ak.causal_attention(*args, SCALE, interpret=True)


def test_a_length_the_blocks_do_not_divide_is_refused_by_the_kernels():
    args, _ = _operands(jnp.float32, t=ak.BLOCK + 32)
    with pytest.raises(ValueError, match="a multiple of"):
        ak.causal_attention(*args, SCALE, interpret=True)


def test_the_layer_trains_the_same_through_either_body(monkeypatch):
    """``LatentAttention`` under ``nn.remat`` with the model's policy, two
    sequences of one block: output and every gradient through the kernels
    (interpreted) equal those through the plain body to float32 rounding."""
    import flax.linen as nn

    sizes = prog.Sizes(hidden_size=64, num_attention_heads=H, q_lora_rank=48,
                       kv_lora_rank=32, qk_nope_head_dim=NOPE,
                       qk_rope_head_dim=ROPE, v_head_dim=VD, attn_q_block=128)
    layer = nn.remat(
        prog.LatentAttention,
        policy=jax.checkpoint_policies.save_only_these_names(prog.KEEP))(sizes)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, ak.BLOCK, 64), jnp.float32)
    params = layer.init(jax.random.PRNGKey(6), x[:, :8])["params"]

    def loss(params, x):
        y = layer.apply({"params": params}, x)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y

    def run(mode):
        monkeypatch.setattr(ak, "_mode", lambda interpret: mode)
        before = _traced("kernel")
        (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            params, x)
        return jax.tree.leaves((y, grads)), _traced("kernel") - before

    kernel, cores = run("interpret")
    plain, none = run("xla")
    assert cores >= 1 and none == 0
    for got, want in zip(kernel, plain):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0,
            atol=5e-5 * float(jnp.abs(want).max()))


def _trains_the_same_through_either_body(monkeypatch, module, cls, sizes, seed):
    """``cls(sizes)`` under ``nn.remat`` with ``module``'s policy on two
    sequences of one block: output and every gradient through the kernels
    (interpreted) equal those through the plain body to float32 rounding, and
    the counter says which body a core took."""
    import flax.linen as nn

    layer = nn.remat(
        cls, policy=jax.checkpoint_policies.save_only_these_names(module.KEEP))(sizes)
    x = jax.random.normal(
        jax.random.PRNGKey(seed), (2, ak.BLOCK, sizes.hidden_size), jnp.float32)
    params = layer.init(jax.random.PRNGKey(seed + 1), x[:, :8])["params"]

    def loss(params, x):
        y = layer.apply({"params": params}, x)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y

    def run(mode):
        monkeypatch.setattr(ak, "_mode", lambda interpret: mode)
        before = {b: _traced(b) for b in ("kernel", "plain")}
        (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            params, x)
        return jax.tree.leaves((y, grads)), {
            b: _traced(b) - before[b] for b in before}

    kernel, by_kernel = run("interpret")
    plain, by_plain = run("xla")
    assert by_kernel["kernel"] >= 1 and by_kernel["plain"] == 0
    assert by_plain["plain"] >= 1 and by_plain["kernel"] == 0
    for got, want in zip(kernel, plain, strict=True):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0,
            atol=5e-5 * float(jnp.abs(want).max()))


def test_the_hybrid_softmax_layer_trains_the_same_through_either_body(monkeypatch):
    """``qwen3_next.GatedAttention``: two key-value heads of 128 with two
    query heads each and a quarter of each head turned."""
    _trains_the_same_through_either_body(
        monkeypatch, qwen3_next, qwen3_next.GatedAttention, qwen3_next.Sizes(
            hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=128, attn_q_block=128), seed=7)


def test_the_lfm2_softmax_layer_trains_the_same_through_either_body(monkeypatch):
    """``lfm2_moe.Attention``: two key-value heads of the published 64 with
    two query heads each (one grid step takes a key head's two, stacked), the
    whole head turned."""
    from fedtpu.models import lfm2_moe

    _trains_the_same_through_either_body(
        monkeypatch, lfm2_moe, lfm2_moe.Attention, lfm2_moe.Sizes(
            hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
            attn_q_block=128), seed=9)
