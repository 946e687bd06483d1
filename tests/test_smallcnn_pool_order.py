"""smallcnn pools BEFORE it applies ReLU (max-pool only).

``relu(max_pool(y)) == max_pool(relu(y))`` bit for bit (both are monotone),
and the gradients agree too, so the reorder must be invisible in every number
the model produces and visible only in what its backward keeps: no ReLU'd
full-size copy of a convolution's output and a quarter-size sign mask. The
reference here is the order the module had before (``conv -> relu ->
max_pool``), written out as its own module with the same auto-names, so the
same variables drive both.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from fedtpu import checkpoint, models
from fedtpu.models.common import avg_pool, max_pool

BATCH = 4
LABELS = jnp.arange(BATCH) % 10
DTYPES = [jnp.float32, jnp.bfloat16]
KINDS = ["ties", "all_negative", "all_zero", "mixed"]


class ReluThenPool(nn.Module):
    """The module as it stood before the reorder, for both pools."""

    num_classes: int = 10
    pool: str = "max"

    @nn.compact
    def __call__(self, x, train: bool = False):
        pool = max_pool if self.pool == "max" else avg_pool
        x = pool(nn.relu(nn.Conv(32, (3, 3), padding=1)(x)), 2)
        x = pool(nn.relu(nn.Conv(64, (3, 3), padding=1)(x)), 2)
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128)(x))
        return nn.Dense(self.num_classes)(x)


def _variables(seed=0):
    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    return models.create("smallcnn", num_classes=10).init(
        jax.random.PRNGKey(seed), x, train=False
    )


def _constant_first_conv(variables, value):
    """The first convolution's output set to ``value`` everywhere: a zero
    kernel leaves every window at the bias."""
    conv = variables["params"]["Conv_0"]
    conv["kernel"] = jnp.zeros_like(conv["kernel"])
    conv["bias"] = jnp.full_like(conv["bias"], value)
    return variables


def _case(kind, dtype):
    """(variables, inputs) whose first-stage windows are of one ``kind``."""
    v = _variables()
    x = jax.random.normal(jax.random.PRNGKey(7), (BATCH, 32, 32, 3))
    if kind == "ties":
        # Inputs and weights on a coarse grid: few distinct sums, so equal
        # values inside a window abound, zeros and positive maxima alike.
        x = jnp.round(x)
        v = jax.tree.map(lambda a: jnp.round(a * 4) / 4, v)
    elif kind == "all_negative":
        v = _constant_first_conv(v, -1.0)
    elif kind == "all_zero":
        v = _constant_first_conv(v, 0.0)
    else:
        assert kind == "mixed"
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
    return cast(v), cast(x)


def _loss(module, variables, x, labels):
    logits = module.apply(variables, x, train=False).astype(jnp.float32)
    return -jnp.mean(
        jnp.take_along_axis(jax.nn.log_softmax(logits), labels[:, None], axis=1)
    )


def _grads(module, variables, x):
    """The loss's gradient for every parameter and for the input."""
    return jax.grad(
        lambda v, x: _loss(module, v, x, LABELS), argnums=(0, 1)
    )(variables, x)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def _assert_same_bits(got, want):
    flat_got, _ = jax.tree_util.tree_flatten_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        assert g.dtype == w.dtype and g.shape == w.shape, path
        np.testing.assert_array_equal(
            _bits(g), _bits(w), err_msg=jax.tree_util.keystr(path)
        )


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", KINDS)
def test_logits_are_the_relu_then_pool_formulations_bits(kind, dtype):
    v, x = _case(kind, dtype)
    got = models.create("smallcnn", num_classes=10).apply(v, x, train=False)
    want = ReluThenPool().apply(v, x, train=False)
    assert got.dtype == dtype
    _assert_same_bits(got, want)
    if kind == "ties":
        # The case is what it says: some 2x2 window of the first
        # convolution's output holds its maximum twice.
        y = nn.Conv(32, (3, 3), padding=1).apply(
            {"params": v["params"]["Conv_0"]}, x
        )
        win = np.asarray(y.astype(jnp.float32)).reshape(BATCH, 16, 2, 16, 2, 32)
        win = win.transpose(0, 1, 3, 5, 2, 4).reshape(-1, 4)
        assert ((win == win.max(axis=1, keepdims=True)).sum(axis=1) > 1).any()


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", KINDS)
def test_gradients_are_the_relu_then_pool_formulations_bits(kind, dtype):
    """Every parameter's gradient and the input's: a window whose maximum is
    not positive passes nothing back in either order, and a positive maximum
    routes to the same first maximum."""
    v, x = _case(kind, dtype)
    got = _grads(models.create("smallcnn", num_classes=10), v, x)
    want = _grads(ReluThenPool(), v, x)
    _assert_same_bits(got, want)
    if kind == "mixed":
        assert all(np.any(np.asarray(g, np.float32)) for g in jax.tree.leaves(got))


def _residuals(module, dtype):
    v, x = _case("mixed", dtype)
    return [
        (aval.shape, aval.size * aval.dtype.itemsize, where)
        for aval, where in saved_residuals(
            lambda v, x: _loss(module, v, x, LABELS), v, x
        )
    ]


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_backward_keeps_no_full_size_relu(dtype):
    """That the reorder engaged, read where it acts: the loss's VJP saves the
    convolutions' outputs as they are (``select_and_scatter``'s operand) and
    nothing else of their size: no ReLU'd copy, no sign mask."""
    full = {(BATCH, 32, 32, 32), (BATCH, 16, 16, 64)}
    new = _residuals(models.create("smallcnn", num_classes=10), dtype)
    old = _residuals(ReluThenPool(), dtype)

    new_full = [r for r in new if r[0] in full]
    assert sorted(r[0] for r in new_full) == sorted(full), new_full
    # (BATCH, 16, 16, 64)'s twin shape (BATCH, 16, 16, 32) is the first
    # stage's POOLED size; what is full-size comes out of the convolution.
    assert all("_Conv.__call__" in where for _, _, where in new_full), new_full
    assert len([r for r in old if r[0] in full]) > len(new_full)

    first_conv_output = BATCH * 32 * 32 * 32 * jnp.dtype(dtype).itemsize
    saved = sum(r[1] for r in old) - sum(r[1] for r in new)
    assert saved >= first_conv_output, (saved, first_conv_output)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_avgpool_variant_keeps_relu_first(dtype):
    """A mean does not commute with ReLU: ``smallcnn_avgpool`` is the module
    it was, logits and gradients."""
    v, x = _case("mixed", dtype)
    module = models.create("smallcnn_avgpool", num_classes=10)
    parent = ReluThenPool(pool="avg")
    _assert_same_bits(
        module.apply(v, x, train=False), parent.apply(v, x, train=False)
    )
    _assert_same_bits(_grads(module, v, x), _grads(parent, v, x))
    pooled_first = nn.relu(avg_pool(x, 2))
    assert not np.array_equal(
        np.asarray(pooled_first, np.float32),
        np.asarray(avg_pool(nn.relu(x), 2), np.float32),
    )


def test_parameter_tree_is_the_parents(tmp_path):
    """Names and shapes as before the reorder, so a checkpoint written by the
    old order restores into the module and gives the same logits."""
    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    parent_vars = ReluThenPool().init(jax.random.PRNGKey(3), x, train=False)
    ours = _variables(seed=4)
    shapes = lambda v: jax.tree.map(lambda a: (a.shape, str(a.dtype)), v)
    assert shapes(ours) == shapes(parent_vars)
    assert shapes(ours)["params"] == {
        "Conv_0": {"bias": ((32,), "float32"), "kernel": ((3, 3, 3, 32), "float32")},
        "Conv_1": {"bias": ((64,), "float32"), "kernel": ((3, 3, 32, 64), "float32")},
        "Dense_0": {"bias": ((128,), "float32"), "kernel": ((4096, 128), "float32")},
        "Dense_1": {"bias": ((10,), "float32"), "kernel": ((128, 10), "float32")},
    }
    checkpoint.save(str(tmp_path), 0, parent_vars, backend="wire")
    restored = checkpoint.restore(str(tmp_path), 0, ours, backend="wire")
    _assert_same_bits(restored, parent_vars)
    probe = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 32, 3))
    _assert_same_bits(
        models.create("smallcnn", num_classes=10).apply(restored, probe),
        ReluThenPool().apply(parent_vars, probe),
    )
