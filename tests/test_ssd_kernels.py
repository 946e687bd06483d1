"""Mamba-2's fused selective scan (``fedtpu/ops/ssd_kernels.py``) against the
plain chunks it replaces on a TPU (``mamba2._plain_chunks``) and against
the rule itself, a token at a time, on the CPU through the Pallas interpreter:
the output and the gradient of every operand (x, dt, A, B, C, D), float32
operands to float32 rounding and bfloat16 to bfloat16 rounding, at the
published head sizes (heads of 64 on a state of 128, two heads a lane group)
and at heads of a whole lane group, three chunks of 128; groups that keep
their own states; steps near zero and steps that forget the state within a
chunk. Which body a sequence takes, and that the counter says so.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu.models import lm_layers
from fedtpu.models import mamba2 as prog
from fedtpu.obs.registry import get_global_registry
from fedtpu.ops import ssd_kernels as sk

CHUNK, T, N = 128, 3 * 128, 128
NAMES = ("y", "x", "dt", "A", "B", "C", "D")
# Largest difference over the yardstick's largest magnitude.
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}


def _operands(dtype, t=T, heads=4, p=64, groups=2, n=N, seed=0, step=None):
    """``(x, dt, A, B, C, D)`` as the layer makes them (steps of a hundredth
    to one, ``A`` of -0.2 to -5) and a cotangent of the output. ``step``: every
    step that size."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    draw = lambda key, *shape: jax.random.normal(key, shape)
    dt = jax.nn.softplus(draw(keys[1], t, heads) - 3.0)
    if step is not None:
        dt = jnp.full_like(dt, step)
    args = (draw(keys[0], t, heads, p).astype(dtype), dt,
            -jnp.exp(0.8 * draw(keys[2], heads)),
            draw(keys[3], t, groups, n).astype(dtype),
            draw(keys[4], t, groups, n).astype(dtype), draw(keys[5], heads))
    return args, draw(keys[6], t, heads, p).astype(dtype)


def _with_gradients(fn, args, ct):
    out, vjp = jax.vjp(fn, *args)
    return dict(zip(NAMES, (out,) + vjp(ct)))


def _kernels(*a, chunk=CHUNK):
    return sk.selective_scan(*a, chunk, interpret=True)


def _token_by_token(x, dt, a, b, c, skip):
    """The rule as the model's docstring states it, in float32: ``S_t =
    exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``; ``y_t = S_t C_t + D x_t``, a ``[P,
    N]`` state a head, a head's group read by its index."""
    f32 = lambda v: v.astype(jnp.float32)
    per_group = x.shape[1] // b.shape[1]

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        b_h, c_h = (jnp.repeat(v, per_group, axis=0) for v in (b_t, c_t))
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, jnp.sum(state * c_h[:, None, :], -1) + skip[:, None] * x_t

    zero = jnp.zeros(x.shape[1:] + (b.shape[-1],), jnp.float32)
    return jax.lax.scan(token, zero, (f32(x), dt, f32(b), f32(c)))[1]


def _close(got, want, tolerance):
    assert got.shape == want.shape
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tolerance * np.abs(want).max()


@pytest.fixture(scope="module", params=[
    ("float32", 64), ("float32", 128), ("bfloat16", 64)],
    ids=lambda p: f"{p[0]}-P{p[1]}")
def three(request):
    """``(dtype name, kernels', plain chunks', the recurrence's)``: each
    ``{"y": ..., operand: its gradient}`` under one cotangent. Four heads of
    64 on two groups (two heads a lane group, as published) or two heads of
    128."""
    name, p = request.param
    args, ct = _operands(jnp.dtype(name), heads=256 // p, p=p)
    return (
        name,
        _with_gradients(_kernels, args, ct),
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


@pytest.fixture(scope="module")
def three_groups():
    """Three groups of two heads, a grid row each: the kernels' and the
    rule's."""
    args, ct = _operands(jnp.float32, t=2 * CHUNK, heads=6, groups=3, seed=1)
    return (_with_gradients(_kernels, args, ct),
            _with_gradients(_token_by_token, args, ct))


@pytest.mark.parametrize("what", NAMES)
def test_the_groups_of_a_grid_keep_their_own_states(three_groups, what):
    """A head reads its own group's ``B`` and ``C`` and carries its own state
    over the chunks."""
    got, want = three_groups
    _close(got[what], want[what], TOLERANCE["float32"])


@pytest.mark.parametrize("step, why", [
    (1e-5, "a state that never decays"),
    (40.0, "a state forgotten within a chunk"),
])
def test_steps_near_zero_and_steps_of_forty(step, why):
    """Finite values and gradients (no exponent is positive, a masked entry
    no ``inf - inf``), and the rule's. At steps of 40 ``A``'s gradient is a
    sum of ``dL_t`` times running sums of ``dt`` of thousands whose
    differences of 40 carry it: any chunked form in float32 loses digits
    there (the plain chunks stand 1.2e-2 off the rule), so it is held to the
    plain chunks' distance and not the rule's."""
    args, ct = _operands(jnp.float32, t=2 * CHUNK, seed=2, step=step)
    kernel = _with_gradients(_kernels, args, ct)
    rule = _with_gradients(_token_by_token, args, ct)
    for what in NAMES:
        _close(kernel[what], rule[what],
               5e-2 if (what, step) == ("A", 40.0) else 5e-5)


def _traced(body):
    return get_global_registry().counter(
        prog.SSD_CORES_TRACED, labels={"body": body}).value


@pytest.mark.parametrize("t, heads, p, groups, n, chunk, mode, body", [
    (2 * CHUNK, 4, 64, 2, N, CHUNK, "interpret", "kernel"),
    (2 * CHUNK, 4, 64, 2, N, CHUNK, "xla", "plain"),  # no TPU: the plain chunks
    (2 * CHUNK + 22, 4, 64, 2, N, CHUNK, "interpret", "plain"),  # padded there
    (2 * CHUNK, 4, 64, 4, N, CHUNK, "interpret", "plain"),  # R x P half a lane group
    (2 * CHUNK, 8, 8, 2, 16, CHUNK, "interpret", "plain"),  # the tiny twin's widths
    (2 * CHUNK, 4, 64, 2, N, 64, "interpret", "plain"),  # another chunk
    (2 * CHUNK, 2, 128, 1, 2 * N, CHUNK, "interpret", "kernel"),
])
def test_the_body_follows_backend_and_shapes_and_the_counter_says_which(
        monkeypatch, t, heads, p, groups, n, chunk, mode, body):
    """Through the model's one function, output and gradients against the
    rule a token at a time."""
    monkeypatch.setattr(sk, "_mode", lambda interpret: mode)
    args, ct = _operands(jnp.float32, t=t, heads=heads, p=p, groups=groups,
                         n=n, seed=4)
    before = {b: _traced(b) for b in ("kernel", "plain")}
    got = _with_gradients(lambda *a: prog.selective_scan(*a, chunk), args, ct)
    after = {b: _traced(b) for b in ("kernel", "plain")}
    other = "plain" if body == "kernel" else "kernel"
    assert after[body] == before[body] + 1 and after[other] == before[other]
    want = _with_gradients(_token_by_token, args, ct)
    for what in NAMES:
        _close(got[what], want[what], 5e-5)


def _shapes(t=8192, heads=64, p=64, groups=8, n=128, dtype=jnp.bfloat16):
    shape = lambda dtype, *s: jax.ShapeDtypeStruct(s, dtype)
    return (shape(dtype, t, heads, p), shape(jnp.float32, t, heads),
            shape(jnp.float32, heads), shape(dtype, t, groups, n),
            shape(dtype, t, groups, n), shape(jnp.float32, heads))


def test_takes_says_yes_at_the_published_shapes_and_no_off_a_tpu():
    published = _shapes()
    assert sk.takes(*published, 128, interpret=True)
    assert sk.takes(*published, 128, interpret=False)  # a deviceless compile
    assert not sk.takes(*published, 128)  # the CPU backend: the plain chunks
    assert sk.takes(*_shapes(dtype=jnp.float32), 128, interpret=True)


@pytest.mark.parametrize("why, shapes, chunk", [
    ("a length the chunk does not divide", _shapes(t=8000), 128),
    ("eight tokens, as a model is initialised", _shapes(t=8), 128),
    ("heads no multiple of the groups", _shapes(heads=60), 128),
    ("a group's heads off the lanes", _shapes(heads=8), 128),
    ("heads of three quarters of a lane group", _shapes(p=96), 128),
    ("a state off the lanes", _shapes(n=64), 128),
    ("the tiny twin", _shapes(t=32, heads=8, p=8, groups=2, n=16), 12),
    ("a chunk of 64", _shapes(), 64),
    ("a chunk of 256", _shapes(), 256),
])
def test_shapes_the_kernels_are_not_built_for_take_the_plain_body(why, shapes, chunk):
    assert not sk.takes(*shapes, chunk, interpret=True), why


def test_shapes_the_kernels_are_not_built_for_are_refused_by_them():
    args, _ = _operands(jnp.float32, t=CHUNK, heads=8, p=8, n=16)
    with pytest.raises(ValueError, match="whole lanes"):
        sk.selective_scan(*args, CHUNK, interpret=True)


def test_the_layer_trains_the_same_through_either_body(monkeypatch):
    """``Mamba2`` under ``nn.remat`` with the model's policy, two sequences of
    two chunks, four heads of 64 on two groups of a state of 128: output and
    every gradient through the kernels (interpreted) equal those through the
    plain chunks to float32 rounding, and the counter says which body a core
    took."""
    layer = lm_layers.rematerialised(prog.Mamba2)(
        heads=4, head_dim=64, groups=2, state=N, conv_kernel=4, chunk=CHUNK,
        eps=1e-5)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 2 * CHUNK, 64), jnp.float32)
    params = layer.init(jax.random.PRNGKey(6), x[:, :8])["params"]

    def loss(params, x):
        y = layer.apply({"params": params}, x)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y

    def run(mode):
        monkeypatch.setattr(sk, "_mode", lambda interpret: mode)
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
