"""The fused gated delta rule (``fedtpu/ops/delta_rule_kernels.py``) against
the plain chunks it replaces on a TPU (``qwen3_next._plain_chunks``) and
against the rule itself, a token at a time, on the CPU through the Pallas
interpreter: the output and the gradient of every operand (q, k, v, g, beta),
float32 operands to float32 rounding and bfloat16 to bfloat16 rounding, at the
published head widths (128), one and two value heads a key head, three chunks
of 64; a length the chunk does not divide; gates that never let the state
decay and gates that forget it within a chunk. Which body a sequence takes,
and that the counter says so.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu.models import qwen3_next as prog
from fedtpu.obs.registry import get_global_registry
from fedtpu.ops import delta_rule_kernels as dr

CHUNK, T, HK, D = 64, 3 * 64, 2, 128
NAMES = ("out", "q", "k", "v", "g", "beta")
# Largest difference over the yardstick's largest magnitude.
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}


def _operands(dtype, values, t=T, heads=HK, width=D, seed=0, decay=1.0):
    """``(q, k, v, g, beta)`` as the layer makes them (q and k of unit length,
    q scaled; ``g <= 0``, ``0 < beta < 1``) and a cotangent of the output.
    ``decay`` scales ``g``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q = (unit(jax.random.normal(keys[0], (t, heads, width))) * width ** -0.5
         ).astype(dtype)
    k = unit(jax.random.normal(keys[1], (t, heads, width))).astype(dtype)
    v = jax.random.normal(keys[2], (t, heads, values, width)).astype(dtype)
    g = -decay * jax.nn.softplus(jax.random.normal(keys[3], (t, heads, values)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (t, heads, values)))
    ct = jax.random.normal(keys[5], v.shape).astype(dtype)
    return (q, k, v, g, beta), ct


def _with_gradients(fn, args, ct):
    out, vjp = jax.vjp(fn, *args)
    return dict(zip(NAMES, (out,) + vjp(ct)))


def _token_by_token(q, k, v, g, beta):
    """The rule as the model's docstring states it, in float32: ``S' =
    exp(g_t) S``; ``u = beta_t (v_t - S'^T k_t)``; ``S = S' + k_t u^T``; ``o_t
    = S^T q_t``, a ``[dk, dv]`` state a value head."""
    f32 = lambda a: a.astype(jnp.float32)

    def step(state, x):
        q_t, k_t, v_t, g_t, beta_t = x  # [Hk, dk], [Hk, R, dv], [Hk, R]
        state = jnp.exp(g_t)[..., None, None] * state
        u = beta_t[..., None] * (v_t - jnp.einsum("hrdv,hd->hrv", state, k_t))
        state = state + jnp.einsum("hd,hrv->hrdv", k_t, u)
        return state, jnp.einsum("hrdv,hd->hrv", state, q_t)

    zero = jnp.zeros(v.shape[1:3] + (k.shape[-1], v.shape[-1]), jnp.float32)
    return jax.lax.scan(step, zero, (f32(q), f32(k), f32(v), g, beta))[1]


def _close(got, want, tolerance):
    assert got.shape == want.shape
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tolerance * np.abs(want).max()


@pytest.fixture(scope="module", params=[
    ("float32", 1), ("float32", 2), ("bfloat16", 1), ("bfloat16", 2)],
    ids=lambda p: f"{p[0]}-R{p[1]}")
def three(request):
    """``(dtype name, kernels', plain chunks', the recurrence's)``: each
    ``{"out": ..., operand: its gradient}`` under one cotangent."""
    name, values = request.param
    args, ct = _operands(jnp.dtype(name), values)
    return (
        name,
        _with_gradients(
            lambda *a: dr.gated_delta_rule(*a, CHUNK, interpret=True), args, ct),
        _with_gradients(lambda *a: prog._plain_chunks(*a, CHUNK), args, ct),
        _with_gradients(_token_by_token, args, ct.astype(jnp.float32)),
    )


@pytest.mark.parametrize("what", NAMES)
def test_the_kernels_are_the_plain_chunks(three, what):
    dtype, kernel, plain, _ = three
    assert kernel[what].dtype == plain[what].dtype
    _close(kernel[what], plain[what], TOLERANCE[dtype])


@pytest.mark.parametrize("what", NAMES)
def test_the_kernels_are_the_rule_a_token_at_a_time(three, what):
    dtype, kernel, _, rule = three
    _close(kernel[what], rule[what], TOLERANCE[dtype])


@pytest.mark.parametrize("heads", [3, 4])
def test_the_key_heads_of_a_grid_step_keep_their_own_states(heads):
    """Four key heads go two a grid step, three a head a step."""
    args, ct = _operands(jnp.float32, 2, t=2 * CHUNK, heads=heads, seed=1)
    kernel = _with_gradients(
        lambda *a: dr.gated_delta_rule(*a, CHUNK, interpret=True), args, ct)
    rule = _with_gradients(_token_by_token, args, ct)
    for what in NAMES:
        _close(kernel[what], rule[what], TOLERANCE["float32"])


@pytest.mark.parametrize("decay, why", [
    (1e-4, "a state that never decays"),
    (40.0, "a state forgotten within a chunk"),
])
def test_gates_near_zero_and_strongly_negative(decay, why):
    args, ct = _operands(jnp.float32, 2, t=2 * CHUNK, seed=2, decay=decay)
    kernel = _with_gradients(
        lambda *a: dr.gated_delta_rule(*a, CHUNK, interpret=True), args, ct)
    rule = _with_gradients(_token_by_token, args, ct)
    for what in NAMES:
        _close(kernel[what], rule[what], 5e-5)


def test_equal_keys_keep_the_inverse_exact():
    """Every key of a chunk the same, ``beta`` 1 and no decay: the system's
    matrix is all ones under the diagonal, whose powers grow as binomials.
    The inverse by substitution and merging stays at float32 rounding (the
    decay's gradient is itself rounding here, a millionth of the others: every
    token overwrites the one value the state holds)."""
    (q, k, v, g, beta), ct = _operands(jnp.float32, 1, t=CHUNK, seed=3)
    k = jnp.broadcast_to(k[:1], k.shape)
    args = (q, k, v, jnp.zeros_like(g), jnp.ones_like(beta))
    kernel = _with_gradients(
        lambda *a: dr.gated_delta_rule(*a, CHUNK, interpret=True), args, ct)
    rule = _with_gradients(_token_by_token, args, ct)
    for what in ("out", "q", "k", "v", "beta"):
        _close(kernel[what], rule[what], 1e-4)
    assert np.abs(np.asarray(kernel["g"])).max() <= 1e-4


def _traced(body):
    return get_global_registry().counter(
        prog.DELTA_CORES_TRACED, labels={"body": body}).value


@pytest.mark.parametrize("t, width, chunk, mode, body", [
    (2 * CHUNK, D, CHUNK, "interpret", "kernel"),
    (2 * CHUNK, D, CHUNK, "xla", "plain"),        # no TPU: the plain chunks
    (2 * CHUNK + 22, D, CHUNK, "interpret", "kernel"),  # padded to three chunks
    (2 * CHUNK + 22, D, CHUNK, "xla", "plain"),
    (2 * CHUNK, 64, CHUNK, "interpret", "plain"),  # heads of half the lanes
    (96, D, 24, "interpret", "plain"),             # no chunk of 16 doubled
    (64, D, 32, "interpret", "kernel"),
])
def test_the_body_follows_backend_and_shapes_and_the_counter_says_which(
        monkeypatch, t, width, chunk, mode, body):
    """Through the model's one function, output and gradients against the
    rule a token at a time; a length the chunk does not divide is padded
    before either body."""
    monkeypatch.setattr(dr, "_mode", lambda interpret: mode)
    args, ct = _operands(jnp.float32, 2, t=t, width=width, seed=4)
    before = {b: _traced(b) for b in ("kernel", "plain")}
    got = _with_gradients(
        lambda *a: prog.gated_delta_rule(*a, chunk), args, ct)
    after = {b: _traced(b) for b in ("kernel", "plain")}
    other = "plain" if body == "kernel" else "kernel"
    assert after[body] == before[body] + 1 and after[other] == before[other]
    want = _with_gradients(_token_by_token, args, ct)
    for what in NAMES:
        _close(got[what], want[what], 5e-5)


def test_takes_says_yes_at_the_published_shapes_and_no_off_a_tpu():
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    published = (shape(8192, 16, 128), shape(8192, 16, 128),
                 shape(8192, 16, 2, 128), shape(8192, 16, 2), shape(8192, 16, 2))
    assert dr.takes(*published, 64, interpret=True)
    assert dr.takes(*published, 64, interpret=False)  # a deviceless compile
    assert not dr.takes(*published, 64)  # the CPU backend: the plain chunks
    assert not dr.takes(*published, 48, interpret=True)
    assert not dr.takes(*published, 256, interpret=True)
    tiny = (shape(64, 2, 64), shape(64, 2, 64), shape(64, 2, 2, 64),
            shape(64, 2, 2), shape(64, 2, 2))  # the tiny twin's heads of 64
    assert not dr.takes(*tiny, 16, interpret=True)
    ragged = (shape(100, 2, 128), shape(100, 2, 128), shape(100, 2, 1, 128),
              shape(100, 2, 1), shape(100, 2, 1))
    assert not dr.takes(*ragged, 64, interpret=True)  # the caller pads first


def test_shapes_the_kernels_are_not_built_for_are_refused_by_them():
    args, _ = _operands(jnp.float32, 2, t=CHUNK, width=64)
    with pytest.raises(ValueError, match="heads of whole lanes"):
        dr.gated_delta_rule(*args, CHUNK, interpret=True)


def test_the_layer_trains_the_same_through_either_body(monkeypatch):
    """``GatedDeltaNet`` under ``nn.remat`` with the model's policy, two
    sequences of two chunks, two key heads of 128 with two value heads each:
    output and every gradient through the kernels (interpreted) equal those
    through the plain chunks to float32 rounding, and the counter says which
    body a core took."""
    sizes = prog.Sizes(
        hidden_size=64, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=D, linear_value_head_dim=D, gdn_chunk=CHUNK)
    layer = nn.remat(
        prog.GatedDeltaNet,
        policy=jax.checkpoint_policies.save_only_these_names(prog.KEEP))(sizes)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 2 * CHUNK, 64), jnp.float32)
    params = layer.init(jax.random.PRNGKey(6), x[:, :8])["params"]

    def loss(params, x):
        y = layer.apply({"params": params}, x)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y

    def run(mode):
        monkeypatch.setattr(dr, "_mode", lambda interpret: mode)
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
