"""The fused causal core (``fedtpu/ops/attention_kernels.py``) against the
plain body it replaces on a TPU (``joyai_llm_flash.causal_attention``), on the
CPU through the Pallas interpreter: the forward output and the gradient of
every operand, at a length of three blocks (so a query block meets a skipped,
a full and a diagonal key block), the published head sizes (128 + 64 and
128) and two heads; float32 operands agree to float32 rounding, bfloat16
operands to bfloat16 rounding. Which body a sequence takes, and that the
counter says so.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu.models import joyai_llm_flash as prog
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


def _traced(body):
    return get_global_registry().counter(
        prog.CORES_TRACED, labels={"body": body}).value


@pytest.mark.parametrize("t, widths, mode, body", [
    (2 * ak.BLOCK, {}, "interpret", "kernel"),
    (2 * ak.BLOCK, {}, "xla", "plain"),         # no TPU: the plain body
    (ak.BLOCK + 128, {}, "interpret", "plain"),  # a length the blocks do not divide
    (32, {}, "interpret", "plain"),
    (ak.BLOCK, dict(nope=16, rope=8, vd=16), "interpret", "plain"),  # narrow heads
])
def test_the_body_follows_backend_and_shapes_and_the_counter_says_which(
        monkeypatch, t, widths, mode, body):
    monkeypatch.setattr(ak, "_mode", lambda interpret: mode)
    args, _ = _operands(jnp.float32, t=t, seed=2, **widths)
    q_block = math.gcd(t, 256)  # the plain body's: it must divide the length
    before = {b: _traced(b) for b in ("kernel", "plain")}
    got = prog.attention_core(*args, SCALE, q_block)
    after = {b: _traced(b) for b in ("kernel", "plain")}
    other = "plain" if body == "kernel" else "kernel"
    assert after[body] == before[body] + 1 and after[other] == before[other]
    want = prog.causal_attention(*args, SCALE, q_block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5 * float(jnp.abs(want).max()))


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
