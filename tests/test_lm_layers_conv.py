"""``lm_layers.causal_conv``'s differentiation rule (one ``jax.custom_vjp``:
forward and backward each one pass of shifted slices over an operand kept in
its own dtype) against ``jax.grad`` of the form it replaced, written out here
as the plain reference: pad, ONE float32 copy of the padded operand, the taps'
sums. The order of every float32 sum is the same, so a lone call agrees to
the last bit, output and both gradients; where ``vmap`` adds a row axis the
taps' gradient is summed over rows in another order (still in float32, still
rounded once) and is held to that. The calls are the two models': LFM2's
double gate vmapped over rows on a float32 operand, the hybrid's SiLU under
``jax.checkpoint`` inside ``jax.lax.map``.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fedtpu.models import lfm2_moe, lm_layers

CHANNELS = 24


def plain_conv(x, kernel):
    """The convolution as it was before the rule: the reference."""
    t, width = x.shape[0], kernel.shape[0]
    padded = jnp.pad(x, ((width - 1, 0), (0, 0))).astype(jnp.float32)
    y = sum(padded[i:i + t] * kernel[i].astype(jnp.float32) for i in range(width))
    return y.astype(x.dtype)


def _operands(seed, t, width, dtype, rows=()):
    kx, kk, kd = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kx, rows + (t, CHANNELS), jnp.float32).astype(dtype),
            jax.random.normal(kk, (width, CHANNELS), jnp.float32).astype(dtype),
            jax.random.normal(kd, rows + (t, CHANNELS), jnp.float32).astype(dtype))


def _equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))


@pytest.mark.parametrize("t", [20, 4099])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("width", [3, 4])
def test_the_rule_is_the_plain_forms_output_and_gradients_to_the_last_bit(width, dtype, t):
    x, kernel, dy = _operands(width * t, t, width, dtype)
    _equal(lm_layers.causal_conv(x, kernel), plain_conv(x, kernel))
    weighed = lambda conv: lambda x, kernel: jnp.sum(
        conv(x, kernel).astype(jnp.float32) * dy.astype(jnp.float32))
    got = jax.grad(weighed(lm_layers.causal_conv), argnums=(0, 1))(x, kernel)
    want = jax.grad(weighed(plain_conv), argnums=(0, 1))(x, kernel)
    assert got[0].dtype == x.dtype and got[1].dtype == kernel.dtype
    _equal(got, want)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_the_rule_under_vmap_over_rows_is_lfm2s_gated_form(dtype, monkeypatch):
    """``gated_short_conv`` vmapped over 3 rows with the taps shared, as
    ``ShortConv`` calls it: the operand of the convolution is the float32
    product ``B x X`` whatever the compute dtype. The reference is the same
    function with the plain form in the rule's place."""
    b, taps, c = _operands(7, 50, 3, dtype, rows=(3,))
    x, _, dy = _operands(8, 50, 3, dtype, rows=(3,))

    def grads():
        gated = jax.vmap(lfm2_moe.gated_short_conv, in_axes=(0, 0, 0, None))
        loss = lambda *a: jnp.sum(gated(*a).astype(jnp.float32) * dy.astype(jnp.float32))
        return gated(b, c, x, taps), jax.grad(loss, argnums=(0, 1, 2, 3))(b, c, x, taps)

    got_y, got = grads()
    monkeypatch.setattr(lfm2_moe, "causal_conv", plain_conv)
    want_y, want = grads()
    _equal(got_y, want_y)
    _equal(got[:3], want[:3])
    # the taps' gradient: rows summed after time here, with it there
    assert got[3].dtype == want[3].dtype == taps.dtype
    np.testing.assert_allclose(np.asarray(got[3], np.float32), np.asarray(want[3], np.float32),
                               rtol=2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5, atol=1e-5)


@pytest.mark.parametrize("width", [3, 4])
def test_the_rule_under_checkpoint_inside_map_is_the_hybrids_call(width):
    """SiLU of the convolution, a row at a time through ``jax.lax.map``, the
    whole under ``jax.checkpoint``: the backward pass runs the rule's forward
    once more and then its backward, as a DeltaNet block does."""
    x, kernel, dy = _operands(11, 33, width, jnp.bfloat16, rows=(2,))

    def loss(conv):
        @jax.checkpoint
        def block(x, kernel):
            return jax.lax.map(lambda row: jax.nn.silu(conv(row, kernel)), x)
        return lambda x, kernel: jnp.sum(
            block(x, kernel).astype(jnp.float32) * dy.astype(jnp.float32))

    got = jax.jit(jax.grad(loss(lm_layers.causal_conv), argnums=(0, 1)))(x, kernel)
    want = jax.jit(jax.grad(loss(plain_conv), argnums=(0, 1)))(x, kernel)
    # compiled as one program, the two forms fuse differently and XLA keeps
    # float32 where a fusion spares a bfloat16 rounding: a rounding's distance
    for g, w in zip(got, want, strict=True):
        assert g.dtype == w.dtype == jnp.bfloat16
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.linalg.norm(g - w) <= 2.0 ** -8 * np.linalg.norm(w)


@pytest.mark.parametrize("width", [3, 4])
def test_the_zeros_before_the_rows_start_receive_no_cotangent(width):
    """A cotangent on one output row alone. Row 0 read ``width - 1`` zeros of
    the pad and ``x_0``: all of its cotangent that arrives anywhere arrives at
    ``x_0`` through the LAST tap, the rest fell on the pad. The last row read
    ``width`` real rows, so each tap hands its share to one of them: nothing
    is lost behind the row's end, where the rule pads the cotangent."""
    t = 9
    x, kernel, _ = _operands(3, t, width, jnp.float32)
    at = lambda row: jax.vjp(lm_layers.causal_conv, x, kernel)[1](
        jnp.zeros((t, CHANNELS)).at[row].set(1.0))
    dx, dk = at(0)
    np.testing.assert_array_equal(dx[0], kernel[width - 1])
    np.testing.assert_array_equal(dx[1:], 0.0)
    np.testing.assert_array_equal(dk[:width - 1], 0.0)  # those taps met zeros
    np.testing.assert_array_equal(dk[width - 1], x[0])
    dx, dk = at(t - 1)
    np.testing.assert_array_equal(dx[t - width:], kernel)
    np.testing.assert_array_equal(dx[:t - width], 0.0)
    np.testing.assert_array_equal(dk, x[t - width:])


def plain_biased_conv(x, kernel, bias):
    """The taps' float32 sums plus a bias a channel, rounded once."""
    t, width = x.shape[0], kernel.shape[0]
    padded = jnp.pad(x, ((width - 1, 0), (0, 0))).astype(jnp.float32)
    y = sum(padded[i:i + t] * kernel[i].astype(jnp.float32) for i in range(width))
    return (y + bias.astype(jnp.float32)).astype(x.dtype)


@pytest.mark.parametrize("t", [20, 4099])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_a_bias_a_channel_is_the_plain_forms_output_and_three_gradients(dtype, t):
    """Nemotron-H's call: four taps and a bias a channel (``use_conv_bias``).
    The bias joins the float32 sum before the one rounding, at every
    position, the row's first too; output and the gradients of the operand,
    the taps and the bias agree with ``jax.grad`` of the plain form to the
    last bit (the same float32 sums in the same order), each in its
    operand's dtype. Without a bias the rule is what it was: the other two
    models' calls, above."""
    x, kernel, dy = _operands(5 * t, t, 4, dtype)
    bias = jax.random.normal(jax.random.PRNGKey(t), (CHANNELS,), jnp.float32).astype(dtype)
    _equal(lm_layers.causal_conv(x, kernel, bias), plain_biased_conv(x, kernel, bias))
    weighed = lambda conv: lambda *a: jnp.sum(
        conv(*a).astype(jnp.float32) * dy.astype(jnp.float32))
    got = jax.grad(weighed(lm_layers.causal_conv), argnums=(0, 1, 2))(x, kernel, bias)
    want = jax.grad(weighed(plain_biased_conv), argnums=(0, 1, 2))(x, kernel, bias)
    assert [g.dtype for g in got] == [x.dtype, kernel.dtype, bias.dtype]
    _equal(got, want)
    # the row's first position reads zeros and the bias
    first = lm_layers.causal_conv(jnp.zeros_like(x), kernel, bias)
    np.testing.assert_array_equal(
        np.asarray(first, np.float32),
        np.broadcast_to(np.asarray(bias, np.float32), first.shape))


def test_a_biased_rule_under_checkpoint_inside_map_is_the_state_space_layers_call():
    """As ``Mamba2`` calls it: a sequence at a time under ``jax.lax.map``,
    SiLU behind it, rematerialised; the bias's gradient summed over rows."""
    x, kernel, dy = _operands(11, 16, 4, jnp.float32, rows=(3,))
    bias = jax.random.normal(jax.random.PRNGKey(12), (CHANNELS,), jnp.float32)

    def loss(conv):
        one = jax.checkpoint(lambda row, k, b: jax.nn.silu(conv(row, k, b)))
        return lambda x, k, b: jnp.sum(
            jax.lax.map(lambda row: one(row, k, b), x) * dy)

    got = jax.grad(loss(lm_layers.causal_conv), argnums=(0, 1, 2))(x, kernel, bias)
    want = jax.grad(loss(plain_biased_conv), argnums=(0, 1, 2))(x, kernel, bias)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
