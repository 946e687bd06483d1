"""Nemotron-H in the program, at a small size on the CPU, held to the plain
reference (``benchmark/reference/nemotron_h.py``: float32 jax.numpy, the
state-space recurrence ONE TOKEN AT A TIME, a dense ``[T, T]`` mask, a loop
over the held experts, nothing of the program). The tiny twin
(``tests/benchmark/nemotron_tiny``) has a twin of every width: 8 heads of 8
that share 2 groups of ``B`` and ``C`` on a state of 16, a chunk of 12 that
does not divide the 32 tokens, experts of 24 (no lane multiple) with ``relu2``
between TWO matrices, 4 query heads on 2 key-value heads, seven layers
``E M E M E M *`` (the published 6-12; the benchmark's cell holds 7-13, the
same kinds one layer on), one half and one norm each.

Each kind of layer against the reference's, forward and gradient, in float32
and in bfloat16 (``selective_scan`` against the token-at-a-time rule is in
``tests/test_mamba2.py``, beside the mixer, since PR 51); sixteen shares of the routed experts adding up to the uncut
two-matrix layer; the program's tree; a federation's micro-batches. The whole
model's loss and whole sequential rounds through ``Federation.step()`` are in
``tests/benchmark/test_nemotron_cell.py`` (the harness makes that comparison);
what the five language models share is in ``tests/test_lm_layers.py``.
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
from fedtpu.models import lm_layers
from fedtpu.models import nemotron_h as prog
from fedtpu.obs.registry import get_global_registry

TINY = os.path.join(ROOT, "tests", "benchmark", "nemotron_tiny", "configs",
                    "nemotron_tiny_f32.json")
T, D = 32, 64


@pytest.fixture(scope="module")
def cfg():
    with open(TINY) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def ref():
    from benchmark import run

    return run.load_py(os.path.join(ROOT, "benchmark", "reference", "nemotron_h.py"))


def _sizes(cfg, **over):
    args = dict(cfg["program"]["round"]["model_args"])
    args.pop("micro_batch_rows")
    args.update(over)
    args = {k: tuple(v) if isinstance(v, list) else v for k, v in args.items()}
    return prog.Sizes(vocab_size=cfg["vocab_size"], **args)


@pytest.fixture(scope="module")
def weights(cfg, ref):
    from benchmark import seeded

    params, _ = seeded.make_weights(3, *ref.spec(cfg))
    return jax.tree.map(jnp.asarray, params)


def _x(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _rel(a, b):
    """The worst leaf's norm of the difference over the reference's norm."""
    return max(float(jnp.linalg.norm(x.astype(jnp.float32) - y))
               / max(float(jnp.linalg.norm(y)), 1e-12)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True))


def _value_and_grads(f, *args):
    """``f``'s output contracted with a fixed cotangent, and its gradients."""
    def scalar(*a):
        out = f(*a)
        return jnp.sum(out.astype(jnp.float32) * _x(99, *out.shape)), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return out, grads


# ------------------------------------------------------------------ the layers
# float32: rounding (sums in another order). bfloat16 against the float32
# reference: operands of 8 bits of mantissa, (output, gradients).
TOLERANCE = {"float32": (2e-5, 5e-5), "bfloat16": (0.02, 0.04)}
LAYERS = {"moe": 0, "mamba": 1, "attention": 6}  # the stack's index of a kind


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_a_layer_is_the_references_forward_and_gradient(cfg, ref, weights, kind, dtype):
    """One layer of each kind, its norm and its residual included, from the
    same seeded weights on two rows: the program's ``Block`` (rematerialised,
    as the cell runs it) against the reference's ``layer``, output and the
    gradients of the stream and of every parameter."""
    from benchmark.reference.layers import ident

    at = LAYERS[kind]
    layer = cfg["layers_held"][at]
    c = _sizes(cfg)
    assert c.kind(layer) == kind
    p = weights[f"layer_{at}"]
    h = _x(7, 2, T, D)
    forward = ref.make_forward(cfg).layer
    theirs = _value_and_grads(
        lambda p, h: jnp.stack([forward(
            p, row, ref.KINDS[cfg["hybrid_override_pattern"][layer]], layer, ident)
            for row in h]), p, h)
    block = prog.Block(c, layer, remat=True)
    cast = lambda tree: jax.tree.map(lambda a: a.astype(dtype), tree)
    ours = _value_and_grads(
        lambda p, h: block.apply({"params": cast(p)}, cast(h))[0], p, h)
    out_tol, grad_tol = TOLERANCE[dtype]
    assert _rel(ours[0], theirs[0]) <= out_tol
    assert _rel(ours[1], theirs[1]) <= grad_tol
    _, pairs, load = block.apply({"params": p}, h)
    if kind == "moe":  # 2 of 32 held, 4 a token: some of 256 pairs fall here
        assert 0 < int(pairs) < 2 * T * 4 and float(load) >= 1.0
    else:  # a mixer alone routes nothing
        assert int(pairs) == 0 and float(load) == 0.0


def test_sixteen_shares_of_the_experts_add_up_to_the_uncut_two_matrix_layer(
        cfg, ref, weights):
    """Sixteen chips hold 2 of the 32 routed experts each. The routed parts
    that the sixteen shares compute, plus what every chip computes alike (the
    shared two-matrix expert) counted once, are the uncut reference layer's
    output and input gradient; every (token, chosen expert) pair is computed
    by exactly one share. The three-matrix case, model by model, is
    ``tests/test_lm_layers.py``'s."""
    from benchmark import seeded
    from benchmark.reference.layers import ident

    uncut = dict(cfg, experts_held_from=0, n_routed_experts=32)
    params, _ = seeded.make_weights(3, *ref.spec(uncut))
    p = jax.tree.map(jnp.asarray, params)["layer_0"]["moe"]
    assert set(p) == {"shared", "router", "experts_up", "experts_down"}
    assert set(p["shared"]) == {"up", "down"}  # no stack, no matrix, named gate
    x, layer = _x(5, 2 * T, D), cfg["layers_held"][0]
    theirs = _value_and_grads(
        lambda x: ref.make_forward(uncut).expert_layer(p, x, layer, ident), x)
    none_held = dict(cfg, n_routed_experts=0)
    alike = lambda x: ref.make_forward(none_held).expert_layer(p, x, layer, ident)

    def all_shares(x):
        """``(the shares' sum with the shared part once, the pairs computed)``."""
        once = alike(x)
        total, pairs = once, 0
        for lo in range(0, 32, 2):
            held = dict(p, **{k: p[k][lo:lo + 2] for k in ("experts_up", "experts_down")})
            y, n, _ = lm_layers.ExpertLayer(**prog.experts(
                _sizes(cfg, experts_held=(lo, lo + 2)), layer)).apply({"params": held}, x)
            total, pairs = total + (y - once), pairs + n
        return total, pairs

    ours = _value_and_grads(lambda x: all_shares(x)[0], x)
    assert _rel(ours, theirs) <= 2e-5
    assert int(jax.jit(lambda x: all_shares(x)[1])(x)) == 2 * T * 4


def test_an_experts_form_is_stated_and_a_plain_tpu_body_is_said_once(monkeypatch, caplog):
    """``routed_experts`` takes a gated expert's three stacks or a plain one's
    two with its activation, and refuses a mismatch; two products a layer are
    counted for the two-matrix form. Where the backend is a TPU (the test says
    so where the program asks), the published width 1,856 (14.5 lane groups,
    116 sublane tiles) goes through the kernels and nothing is said; at a
    width of a lane group or more that the kernels still refuse (1,000: no
    whole number of sublane tiles) the plain body is taken and ONE warning a
    process names the width; a tiny width or the CPU says nothing."""
    import logging

    from fedtpu.ops import expert_kernels as ek

    n, held = 16, 2  # a block of one whole sublane tile
    picked = jnp.zeros((n, held), bool).at[:, 0].set(True)
    gates = jnp.where(picked, 0.5, 0.0)
    traced = lambda body="plain": get_global_registry().counter(
        lm_layers.PRODUCTS_TRACED, labels={"body": body}).value

    def layer(d, width, activation, stacks=None):
        w = [jax.ShapeDtypeStruct((held,) + s, jnp.float32) for s in
             ([(d, width)] * ((stacks or (3 if activation is None else 2)) - 1)
              + [(width, d)])]
        return jax.eval_shape(lambda x, *w: lm_layers.routed_experts(
            x, None, gates, picked, w, 1, 64, 16, activation),
            jax.ShapeDtypeStruct((n, d), jnp.float32), *w)

    before = traced()
    assert layer(16, 24, lm_layers.relu2)[0].shape == (n, 16)
    assert traced() == before + 2
    assert layer(16, 24, None)[0].shape == (n, 16)
    assert traced() == before + 5
    with pytest.raises(ValueError, match="stacks of expert weights"):
        layer(16, 24, lm_layers.relu2, stacks=3)
    with pytest.raises(ValueError, match="stacks of expert weights"):
        layer(16, 24, None, stacks=2)

    monkeypatch.setattr(lm_layers, "_PLAIN_WIDTHS_WARNED", set())
    with caplog.at_level(logging.WARNING, logger=lm_layers.__name__):
        layer(128, 1000, lm_layers.relu2)  # the CPU: silent
        assert not caplog.records
        monkeypatch.setattr(ek, "_mode", lambda interpret: "mosaic")
        rows = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        assert ek.takes(
            rows, jax.ShapeDtypeStruct((held, 128, 1856), jnp.float32), 16)
        assert not ek.takes(
            rows, jax.ShapeDtypeStruct((held, 128, 1000), jnp.float32), 16)
        layer(128, 24, lm_layers.relu2)  # under a lane group: a test's width
        before = traced(), traced("kernel")
        layer(128, 1856, lm_layers.relu2)  # the published width: the kernels'
        assert (traced(), traced("kernel")) == (before[0], before[1] + 2)
        assert not caplog.records
        layer(128, 1000, lm_layers.relu2)
        layer(128, 1000, lm_layers.relu2)  # a second layer of the same width
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 1 and "1000" in said[0] and "plain" in said[0]


# ------------------------------------------------------------------ the model
def test_the_programs_tree_is_the_references_parameter_list(cfg, ref):
    from fedtpu import models

    args = dict(cfg["program"]["round"]["model_args"])
    args.pop("micro_batch_rows")
    model = models.create("nemotron_h", num_classes=cfg["vocab_size"], remat=True, **args)
    ids = jnp.zeros((1, T), jnp.int32)
    shapes = jax.eval_shape(
        lambda k: model.init(k, ids, train=True, targets=ids)["params"],
        jax.random.PRNGKey(0))
    ours = {tuple(k.key for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert ours == {path: tuple(shape) for path, shape, _ in ref.spec(cfg)[0]}
    assert "head" in shapes  # untied
    # ONE half and ONE norm a layer: E M E M E M *
    halves = [sorted(set(shapes[f"layer_{i}"]) - {"norm"}) for i in range(7)]
    assert halves == [["moe"], ["mamba"]] * 3 + [["self_attn"]]
    assert "experts_gate" not in shapes["layer_0"]["moe"]  # two matrices
    assert shapes["layer_1"]["mamba"]["conv_bias"].shape == (64 + 2 * 2 * 16,)
    # the published pattern is the default: 23 M, 23 E, 6 *
    c = prog.Sizes()
    kinds = [c.kind(i) for i in c.layers]
    assert len(kinds) == 52 and [kinds.count(k) for k in ("mamba", "moe", "attention")] == [
        23, 23, 6]
    assert [c.kind(i) for i in range(6, 13)] == ["moe", "mamba"] * 3 + ["attention"]
    assert prog.experts(c, 6)["held"] == (0, 128)
    assert prog.experts(c, 6)["activation"] is lm_layers.relu2
    # a layer's selection bias follows its PUBLISHED index, the reference's too
    np.testing.assert_array_equal(
        prog.selection_bias(8, _sizes(cfg, n_routed_experts=32)),
        ref.selection_bias(8, cfg))
    assert not np.array_equal(ref.selection_bias(8, cfg), ref.selection_bias(6, cfg))
    # the program's own initialiser: steps log-uniform on [0.001, 0.1], A on [1, 16]
    init = model.init(jax.random.PRNGKey(1), ids, train=True, targets=ids)["params"]
    mamba = init["layer_1"]["mamba"]
    steps = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert 0.001 <= steps.min() and steps.max() <= 0.1 + 1e-6
    assert 1.0 <= float(jnp.exp(mamba["A_log"]).min()) and float(
        jnp.exp(mamba["A_log"]).max()) <= 16.0
    assert not np.asarray(mamba["conv_bias"]).any() and np.asarray(mamba["D"]).all()
    with pytest.raises(ValueError, match="no size"):
        models.create("nemotron_h", widht=3)
    with pytest.raises(ValueError, match="layers_held"):
        prog.Sizes(num_hidden_layers=2, layers_held=(0, 1, 2)).layers
    with pytest.raises(ValueError, match="names no kind"):
        prog.Sizes(hybrid_override_pattern="ME-").kind(2)  # a dense layer: refused
    with pytest.raises(ValueError, match="no range"):
        prog.experts(prog.Sizes(experts_held=(120, 130)), 6)


def _round_config(cfg, micro_batch_rows, dtype="float32"):
    # three layers, one of each kind: the step's path, not the model, is what
    # this holds
    model_args = dict(cfg["program"]["round"]["model_args"],
                      num_hidden_layers=3, layers_held=[10, 11, 12],
                      micro_batch_rows=micro_batch_rows)
    return RoundConfig(
        model="nemotron_h", num_classes=256, image_size=(T,), remat=True,
        dtype=dtype, model_args=model_args,
        opt=OptimizerConfig(learning_rate=0.1, momentum=0.0, weight_decay=0.0),
        data=DataConfig(dataset="tokens", batch_size=2, num_examples=64,
                        partition="iid"),
        fed=FedConfig(num_clients=2, client_schedule="sequential"),
        steps_per_round=2)


def test_micro_batches_of_a_row_and_of_the_step_give_one_update(cfg):
    """Through ``Federation.step()`` with the clients in sequence and
    ``token_sgd_in_micro_batches``, by the entry points of the other four
    language models: rows of one and the step's two rows at once give the same
    first update to float32 rounding, they count alike (``RoundMetrics.tokens``,
    ``.moe_pairs_here``, ``.moe_load_max_over_mean``), and the model trains."""
    whole, by_row = (Federation(_round_config(cfg, n), seed=0) for n in (2, 1))
    start = jax.tree.map(np.asarray, whole.state.params)
    first = [fed.step() for fed in (whole, by_row)]
    assert float(first[0].loss) == pytest.approx(float(first[1].loss), rel=1e-5)
    # The whole tree's update (a leaf like ``dt_bias`` moves by 3e-5 from
    # values near -5: its own update is a few float32 steps of the VALUE), and
    # every leaf's parameters to float32 rounding.
    update = lambda fed: jnp.concatenate([
        (a - b).ravel() for a, b in zip(jax.tree.leaves(fed.state.params),
                                        jax.tree.leaves(start))])
    assert _rel(update(by_row), update(whole)) <= 1e-4
    for a, b in zip(jax.tree.leaves(by_row.state.params),
                    jax.tree.leaves(whole.state.params)):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-7)
    for m in first:
        assert float(m.tokens) == 2 * 2 * 2 * 127
        # the one expert layer routes 4 of 32 experts a token, 2 of them held
        assert 0 < int(m.moe_pairs_here) <= 2 * 2 * 2 * 128 * 2
        assert 1.0 <= float(m.moe_load_max_over_mean) <= 2.0
    assert int(first[0].moe_pairs_here) == int(first[1].moe_pairs_here)
    assert float(whole.step().loss) < float(first[0].loss)
