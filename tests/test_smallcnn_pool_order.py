"""smallcnn pools BEFORE it adds the bias and applies ReLU (max-pool only).

``relu(max_pool(y)) == max_pool(relu(y))`` bit for bit (both are monotone),
and the gradients agree too, so that reorder (PR 30) must be invisible in
every number the model produces and visible only in what its backward keeps:
no ReLU'd full-size copy of a convolution's output and a quarter-size sign
mask. The reference is the order the module had before (``conv -> relu ->
max_pool``), written out as its own module with the same auto-names, so the
same variables drive both.

``max_pool(c) + b == max_pool(c + b)`` bit for bit too (a per-channel
constant is monotone), so the bias's move behind the pool (PR 32) is
invisible in the forward and in the parameter tree, and visible in what the
bias gradient sums: the quarter-size pooled cotangent, not
``select_and_scatter``'s full-size result. The gradients are those of one
function and differ from the bias-inside module's (``BiasThenPool``, PR 30's
module written out) at rounding level in two places, each pinned below: the
order of the bias sum, and where the cotangent goes when ``c + b`` ties and
``c`` does not.
"""

import math
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from fedtpu import checkpoint, models
from fedtpu.models import smallcnn
from fedtpu.models.common import avg_pool, max_pool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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


class BiasThenPool(nn.Module):
    """The module as PR 30 left it: pooled before ReLU, the bias inside."""

    num_classes: int = 10

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.relu(max_pool(nn.Conv(32, (3, 3), padding=1)(x), 2))
        x = nn.relu(max_pool(nn.Conv(64, (3, 3), padding=1)(x), 2))
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


def _is_conv_bias(path):
    name = jax.tree_util.keystr(path)
    return "Conv_" in name and "bias" in name


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", KINDS)
def test_gradients_are_the_relu_then_pool_formulations_bits(kind, dtype):
    """Every parameter's gradient and the input's: a window whose maximum is
    not positive passes nothing back in either order, and a positive maximum
    routes to the same first maximum (the cases' biases are zero or one
    constant, so ``c + b`` ties only where ``c`` does). The convolutions'
    bias gradients are the same terms summed in another order since PR 32:
    equal to the rounding of their dtype, bit-equal where the sums are exact
    (``ties``: values on a coarse grid)."""
    v, x = _case(kind, dtype)
    got = _grads(models.create("smallcnn", num_classes=10), v, x)
    want = _grads(ReluThenPool(), v, x)
    if kind != "mixed":
        _assert_same_bits(got, want)
        return
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_flatten_with_path(want)[0]
    rest = lambda flat: [leaf for path, leaf in flat if not _is_conv_bias(path)]
    _assert_same_bits(rest(flat_got), rest(flat_want))
    biases = [
        (np.asarray(g, np.float32), np.asarray(w, np.float32))
        for (path, g), (_, w) in zip(flat_got, flat_want) if _is_conv_bias(path)
    ]
    assert len(biases) == 2
    for g, w in biases:
        assert np.linalg.norm(g - w) <= 4 * float(jnp.finfo(dtype).eps) * np.linalg.norm(w)
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
    assert all("Conv.__call__" in where for _, _, where in new_full), new_full
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


# --- PR 32: the bias behind the pool -------------------------------------


def _with_biases(variables, dtype, scale=0.1):
    """The convolutions' biases drawn away from zero, where ``init`` and the
    cells' first round leave them."""
    params = dict(variables["params"])
    for i, name in enumerate(("Conv_0", "Conv_1")):
        bias = scale * jax.random.normal(
            jax.random.PRNGKey(11 + i), params[name]["bias"].shape
        )
        params[name] = dict(params[name], bias=bias.astype(dtype))
    return {"params": params}


def _biased_case(dtype):
    v, x = _case("mixed", dtype)
    return _with_biases(v, dtype), x


def _stage_pair(v, x):
    """The first stage alone, both ways, on the same ``Conv_0``."""
    conv = {"params": v["params"]["Conv_0"]}
    ours = lambda conv, x: smallcnn.Conv(32).apply(conv, x)
    inside = lambda conv, x: nn.relu(
        max_pool(nn.Conv(32, (3, 3), padding=1).apply(conv, x), 2)
    )
    return ours, inside, conv


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("what", ["stage", "model"])
def test_forward_is_the_bias_inside_modules_bits(what, dtype):
    v, x = _biased_case(dtype)
    if what == "stage":
        ours, inside, conv = _stage_pair(v, x)
        got, want = ours(conv, x), inside(conv, x)
        assert got.shape == (BATCH, 16, 16, 32)
    else:
        got = models.create("smallcnn", num_classes=10).apply(v, x, train=False)
        want = BiasThenPool().apply(v, x, train=False)
    assert got.dtype == dtype
    _assert_same_bits(got, want)
    # The biases act: the same variables without them give other bits.
    zeroed = _with_biases(v, dtype, scale=0.0)
    assert not np.array_equal(
        _bits(BiasThenPool().apply(zeroed, x, train=False)),
        _bits(BiasThenPool().apply(v, x, train=False)),
    )


@pytest.mark.parametrize("seed", [0, 3])
def test_init_is_the_bias_inside_modules_values(seed):
    """Paths, shapes, dtypes and, at the same key, the values: flax derives a
    parameter's key from the module's path and the order of its
    declarations, and ``Conv`` declares ``nn.Conv``'s."""
    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    key = jax.random.PRNGKey(seed)
    ours = models.create("smallcnn", num_classes=10).init(key, x, train=False)
    want = BiasThenPool().init(key, x, train=False)
    assert jax.tree.structure(ours) == jax.tree.structure(want)
    _assert_same_bits(ours, want)
    assert np.any(np.asarray(ours["params"]["Conv_1"]["kernel"]))


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_a_bias_inside_checkpoint_restores_and_predicts_the_same(tmp_path, dtype):
    written, x = _biased_case(jnp.float32)
    checkpoint.save(str(tmp_path), 0, written, backend="wire")
    restored = checkpoint.restore(str(tmp_path), 0, _variables(seed=4), backend="wire")
    _assert_same_bits(restored, written)
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
    _assert_same_bits(
        models.create("smallcnn", num_classes=10).apply(cast(restored), cast(x)),
        BiasThenPool().apply(cast(written), cast(x)),
    )


def test_f32_gradients_are_the_bias_inside_modules_to_rounding():
    """One function, so one gradient: each leaf within 1e-6 of its norm. (In
    float32 a window whose two largest raw outputs round to one value under
    a bias of 0.1 does not occur at this size.)"""
    v, x = _biased_case(jnp.float32)
    got = _grads(models.create("smallcnn", num_classes=10), v, x)
    want = _grads(BiasThenPool(), v, x)
    flat_got, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat_got, jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert np.linalg.norm(w) > 0, path
        assert np.linalg.norm(g - w) <= 1e-6 * np.linalg.norm(w), (
            jax.tree_util.keystr(path)
        )


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_bias_gradient_is_the_sum_of_the_pooled_cotangent(dtype):
    """``d/db relu(p + b)`` under a cotangent ``w`` is ``sum(w * (p + b > 0))``
    over batch and space of the POOLED tensor."""
    v, x = _biased_case(dtype)
    ours, _, conv = _stage_pair(v, x)
    out, vjp = jax.vjp(ours, conv, x)
    w = jax.random.normal(jax.random.PRNGKey(13), out.shape).astype(dtype)
    bias_grad = vjp(w)[0]["params"]["bias"]
    pooled_cotangent = np.where(np.asarray(out, np.float32) > 0, np.asarray(w, np.float32), 0)
    assert pooled_cotangent.shape == (BATCH, 16, 16, 32)
    want = pooled_cotangent.sum(axis=(0, 1, 2))
    assert bias_grad.dtype == dtype and bias_grad.shape == (32,)
    # The sum is taken in ``dtype``: to its rounding of the terms' size.
    terms = np.abs(pooled_cotangent).sum(axis=(0, 1, 2)).max()
    np.testing.assert_allclose(
        np.asarray(bias_grad, np.float32), want,
        rtol=0, atol=float(jnp.finfo(dtype).eps) * terms,
    )


CLIENTS = 3


def _jaxprs_under(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _jaxprs_under(sub)


def _bias_gradient_sums(module, dtype):
    """{channels: spatial shapes of the operands} of every ``reduce_sum``
    that makes a convolution's per-client bias gradient (``[CLIENTS, 32]`` or
    ``[CLIENTS, 64]``) in the loss's gradient under a ``clients`` vmap. A sum
    over axes of length one (the transpose of the bias's own broadcast) adds
    nothing up and is left out."""
    v, x = _case("mixed", dtype)
    stack = lambda t: jax.tree.map(lambda a: jnp.stack([a] * CLIENTS), t)
    grad = jax.vmap(jax.grad(lambda v, x: _loss(module, v, x, LABELS)))
    found = {}
    for jaxpr in _jaxprs_under(jax.make_jaxpr(grad)(stack(v), stack(x)).jaxpr):
        for eqn in jaxpr.eqns:
            out = eqn.outvars[0].aval.shape
            if eqn.primitive.name == "reduce_sum" and out in {(CLIENTS, 32), (CLIENTS, 64)}:
                operand = eqn.invars[0].aval.shape
                if math.prod(operand) > math.prod(out):
                    assert operand[:2] == (CLIENTS, BATCH) and operand[-1] == out[-1]
                    found.setdefault(out[-1], []).append(operand[2:4])
    return found


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_no_bias_gradient_sums_a_full_size_tensor(dtype):
    """That the move engaged, read where it acts: the bias gradients reduce
    the pooled cotangents (16x16x32 and 8x8x64 a client), not
    ``select_and_scatter``'s results (32x32x32 and 16x16x64) as in the
    bias-inside module, whose sums this finds."""
    ours = _bias_gradient_sums(models.create("smallcnn", num_classes=10), dtype)
    assert ours == {32: [(16, 16)], 64: [(8, 8)]}
    assert _bias_gradient_sums(BiasThenPool(), dtype) == {
        32: [(32, 32)], 64: [(16, 16)]
    }


@pytest.mark.parametrize("bias", [512.0, 0.0], ids=["tie_after_bias", "no_bias"])
def test_where_the_cotangent_goes_when_the_bias_makes_a_tie(bias):
    """bfloat16, one window: raw outputs 1 and 1 + 2**-7, the smaller first.
    Under a bias of 512 both round to 512: the forward is the same bits, the
    bias-inside order sees a tie and routes the cotangent to its first
    element, and this order routes it to the larger raw output. Without the
    bias there is no tie and both route alike."""
    bf16 = jnp.bfloat16
    x = jnp.asarray([[1.0, 1.0078125], [0.0, 0.0]], bf16).reshape(1, 2, 2, 1)
    assert float(x[0, 0, 0, 0]) < float(x[0, 0, 1, 0])
    kernel = jnp.zeros((3, 3, 1, 1), bf16).at[1, 1, 0, 0].set(1.0)  # identity
    conv = {"params": {"kernel": kernel, "bias": jnp.full((1,), bias, bf16)}}
    ours = lambda x: smallcnn.Conv(1).apply(conv, x)
    inside = lambda x: nn.relu(
        max_pool(nn.Conv(1, (3, 3), padding=1).apply(conv, x), 2)
    )
    _assert_same_bits(ours(x), inside(x))
    assert float(ours(x).reshape(())) == (512.0 if bias else 1.0078125)
    route = lambda f: np.asarray(
        jax.grad(lambda x: f(x).astype(jnp.float32).sum())(x), np.float32
    ).reshape(2, 2)
    first, larger = [[1, 0], [0, 0]], [[0, 1], [0, 0]]
    np.testing.assert_array_equal(route(ours), larger)
    np.testing.assert_array_equal(route(inside), first if bias else larger)


@pytest.mark.parametrize(
    "cell_name", ["resnet18_cifar100.sim64", "resnet18_cifar100.mesh4_sim256"]
)
def test_the_resnet_cells_round_program_runs_nothing_of_smallcnn(
    cell_name, monkeypatch, eight_devices
):
    """The two ResNet cells are the control of every smallcnn change: their
    round program (the cell's own files through ``benchmark/sut.py``, cut to
    4 clients of batch 8) lowers with every module of ``smallcnn.py`` made to
    raise, so no line of that file reaches it, and it holds no max-pool."""
    from benchmark import check, run, sut

    def never(*a, **k):
        raise AssertionError("fedtpu/models/smallcnn.py ran")

    monkeypatch.setattr(smallcnn.Conv, "__call__", never)
    monkeypatch.setattr(smallcnn.SmallCNNModule, "__call__", never)
    with pytest.raises(AssertionError, match="smallcnn.py ran"):
        _variables()

    cell = run.Cell(os.path.join(ROOT, "BENCHMARK.json"), cell_name)
    cell.config = dict(cell.config, num_examples=64, batch_size=8)
    cell.traffic = dict(cell.traffic, clients=4)
    fed = sut.build(cell, check.seeded_inputs(cell, 5))
    assert (fed.mesh is not None) == (cell.chips == 4)
    data = fed._ensure_device_data()
    alive = fed._placed(np.ones((4,), bool), sharded=True)
    text = fed._data_step.lower(
        fed.state, *data, fed.weights, alive, fed._data_key
    ).as_text()
    assert "stablehlo.convolution" in text
    assert "select_and_scatter" not in text and "reduce_window" not in text
