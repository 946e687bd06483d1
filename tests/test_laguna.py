"""Laguna in the program, at a small size on the CPU, held to the plain
reference (``benchmark/reference/laguna.py``: float32 jax.numpy, a dense ``[T,
T]`` mask a layer kind, one head's whole scores at a time, nothing of the
program): an attention layer of each kind and a whole block of each kind,
forward and gradient, from the same seeded weights, in float32 and in
bfloat16; the shares of the heads adding up to the uncut layer, for a full and
for a sliding layer; YaRN's table against a float64 transcription of the
formula; which body a windowed core takes and how it is counted; the
program's tree; micro-batches of a row through a federation.

The shares of the routed experts adding up to the uncut layer, and every pair
on one held expert, are held in ``tests/test_lm_layers.py`` for all four
language models, the window's plain body there too; the whole model's loss
and whole sequential rounds through ``Federation.step()`` are in
``tests/benchmark/test_laguna_cell.py`` (the harness makes that comparison).
"""

import json
import math
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
from fedtpu.models import laguna as prog
from fedtpu.models import lm_layers
from fedtpu.obs.registry import get_global_registry
from fedtpu.ops import attention_kernels as ak

TINY = os.path.join(ROOT, "tests", "benchmark", "laguna_tiny", "configs",
                    "laguna_tiny_f32.json")
T, D = 32, 64
FULL, SLIDING = prog.KINDS


@pytest.fixture(scope="module")
def cfg():
    with open(TINY) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def ref():
    from benchmark import run

    return run.load_py(os.path.join(ROOT, "benchmark", "reference", "laguna.py"))


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
#  float32  the order of float32 sums only
#  bfloat16 8 bits of mantissa into every product and out of every layer; the
#           limits leave the readings about twice their size, far under what
#           dropping a term, a head or a window's edge would read.
TOLERANCE = {"float32": (2e-6, 1e-5), "bfloat16": (0.02, 0.03)}
# (the held layer, what the program builds, what the reference computes)
LAYERS = {
    "full_attention": (("layer_0", "self_attn"), lambda s: prog.Attention(s, 0),
                       lambda f: lambda p, x, q: f.attention(p, x, FULL, q)),
    "sliding_attention": (("layer_1", "self_attn"), lambda s: prog.Attention(s, 1),
                          lambda f: lambda p, x, q: f.attention(p, x, SLIDING, q)),
    "full_dense_block": (("layer_0",), lambda s: prog.Block(s, 0),
                         lambda f: lambda p, x, q: f.block(p, x, FULL, q)),
    "sliding_sparse_block": (("layer_2",), lambda s: prog.Block(s, 2, True),
                             lambda f: lambda p, x, q: f.block(p, x, SLIDING, q)),
    "full_sparse_block": (("layer_4",), lambda s: prog.Block(s, 4, True),
                          lambda f: lambda p, x, q: f.block(p, x, FULL, q)),
}


@pytest.mark.parametrize("name,dtype", [
    ("full_attention", "float32"), ("full_attention", "bfloat16"),
    ("sliding_attention", "float32"), ("sliding_attention", "bfloat16"),
    ("full_dense_block", "float32"),
    ("sliding_sparse_block", "float32"), ("sliding_sparse_block", "bfloat16"),
    ("full_sparse_block", "bfloat16")])
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


@pytest.mark.parametrize("layer", [0, 1], ids=["full", "sliding"])
def test_the_shares_of_the_heads_add_up_to_the_uncut_layer(cfg, ref, layer):
    """Eight chips hold a key-value head each with its 2 (full) or 3 (sliding)
    query heads: the eight shares' ``o_proj`` outputs, each the partial sum
    tensor parallelism over heads would all-reduce, add up to the uncut
    reference layer's output and input gradient, and each share is the
    reference's at that share's weights."""
    from benchmark.reference.layers import ident

    args = cfg["program"]["round"]["model_args"]
    kind, hd = cfg["layer_types"][layer], cfg["head_dim"]
    uncut = dict(cfg, num_key_value_heads=args["num_key_value_heads"],
                 num_attention_heads_per_layer=args["num_attention_heads_per_layer"])
    p = _weights(ref, uncut)[f"layer_{layer}"]["self_attn"]
    heads = uncut["num_attention_heads_per_layer"][layer]
    group = heads // 8
    assert p["q_proj"]["kernel"].shape == (D, heads * hd) and group == (2, 3)[layer]
    x = _x(5, 2, T, D)
    theirs = ref.make_forward(uncut).attention
    want = _value_and_grads(
        lambda x: jnp.stack([theirs(p, row, kind, ident) for row in x]), x)

    def share(j):
        """Key-value head ``j``'s columns of every projection, and the rows
        of ``W_o`` that its query heads' outputs meet."""
        q = slice(j * group * hd, (j + 1) * group * hd)
        cols = {"q_proj": q, "k_proj": slice(j * hd, (j + 1) * hd),
                "v_proj": slice(j * hd, (j + 1) * hd),
                "g_proj": slice(j * group, (j + 1) * group)}
        held = {k: {"kernel": p[k]["kernel"][:, s]} for k, s in cols.items()}
        return dict(held, o_proj={"kernel": p["o_proj"]["kernel"][q]})

    def all_shares(x):
        return sum(prog.Attention(_sizes(cfg, kv_heads_held=(j, j + 1)), layer).apply(
            {"params": share(j)}, x) for j in range(8))

    got = _value_and_grads(all_shares, x)
    assert _rel(got[0], want[0]) <= 2e-6 and _rel(got[1], want[1]) <= 1e-5
    one = prog.Attention(_sizes(cfg, kv_heads_held=(5, 6)), layer).apply(
        {"params": share(5)}, x)
    np.testing.assert_allclose(
        one, jnp.stack([theirs(share(5), row, kind, ident) for row in x]),
        rtol=2e-5, atol=2e-6)
    assert 0.1 < _rel(one, want[0])  # a share is not the layer
    with pytest.raises(ValueError, match="no range of the 8 key-value heads"):
        _sizes(cfg, kv_heads_held=(7, 9)).kv_held


def test_a_window_of_one_returns_the_gated_value_through_the_output_product(cfg, weights):
    """A query that sees only itself: the softmax is 1 on the diagonal, so a
    sliding layer of window 1 is ``(sigmoid(W_g u)_h v_{h // G}) W_o``,
    whatever the rotary turn; and a window of ``T`` or more is the same
    layer as a full one given the sliding layer's rotary rule."""
    p = weights["layer_1"]["self_attn"]
    x = _x(6, 2, T, D)
    got = prog.Attention(_sizes(cfg, sliding_window=1), 1).apply({"params": p}, x)
    v = x @ p["v_proj"]["kernel"]  # [2, T, 16]: the one key-value head held
    gate = jax.nn.sigmoid(x @ p["g_proj"]["kernel"])  # [2, T, 3]
    want = (gate[..., None] * v[:, :, None, :]).reshape(2, T, -1) @ p["o_proj"]["kernel"]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    wide = [prog.Attention(_sizes(cfg, sliding_window=w), 1).apply({"params": p}, x)
            for w in (T, T + 7, 10 * T)]
    np.testing.assert_array_equal(wide[0], wide[1])
    np.testing.assert_array_equal(wide[0], wide[2])
    narrow = prog.Attention(_sizes(cfg), 1).apply({"params": p}, x)
    assert 0.05 < _rel(narrow, wide[0])  # the window of 8 is another function


def test_yarn_is_the_formulas_table_and_its_factor(cfg, ref):
    """The published full-attention group at the published head: 32 inverse
    frequencies over the first 64 of 128 dimensions, each a blend of
    ``theta^(-2i/64)`` and the same over 128 by a linear ramp between the
    dimensions that turn 32 times and once in 8,192 positions, against a
    float64 numpy transcription; ``attention_factor`` is ``0.1 ln(128) + 1``;
    program and reference hold the same table; the turn multiplies cos and sin
    by the factor and leaves the second half of the head alone."""
    rope = cfg["rope_parameters"]["full_attention"]
    c = prog.Sizes()
    assert (c.full_rope_theta, c.full_rope_factor, c.full_beta_fast, c.full_beta_slow,
            c.full_original_max_position_embeddings, c.full_partial_rotary_factor,
            c.full_attention_factor) == (
        rope["rope_theta"], rope["factor"], rope["beta_fast"], rope["beta_slow"],
        rope["original_max_position_embeddings"], rope["partial_rotary_factor"],
        rope["attention_factor"])
    assert c.full_attention_factor == pytest.approx(0.1 * math.log(128) + 1, abs=1e-12)
    assert c.sliding_rope_theta == cfg["rope_parameters"]["sliding_attention"]["rope_theta"]
    rot, theta = 64, np.float64(500000.0)
    table = lm_layers.yarn_inv_freq(500000.0, rot, 128.0, 8192, 32.0, 1.0)
    assert table.shape == (32,) and table.dtype == np.float32
    i = np.arange(32, dtype=np.float64)
    plain = theta ** (-2 * i / rot)
    dim = lambda turns: rot * np.log(8192 / (turns * 2 * np.pi)) / (2 * np.log(theta))
    low, high = np.floor(dim(32.0)), np.ceil(dim(1.0))
    assert (low, high) == (9, 18)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = plain / 128 * ramp + plain * (1 - ramp)
    np.testing.assert_allclose(table, want, rtol=1e-6)
    np.testing.assert_array_equal(table[:10], plain[:10].astype(np.float32))
    np.testing.assert_allclose(table[18:], plain[18:] / 128, rtol=1e-6)
    theirs, factor = ref.inverse_frequencies(rope, 128)
    np.testing.assert_array_equal(theirs, table)
    assert factor == rope["attention_factor"]
    h = _x(3, T, 128)
    got = lm_layers.rope_half(h, 500000.0, rot, table, factor)
    np.testing.assert_allclose(got, ref.rotate_half(h, theirs, factor), atol=2e-6)
    np.testing.assert_array_equal(got[:, rot:], h[:, rot:])
    np.testing.assert_allclose(got[0, :rot], factor * h[0, :rot], rtol=1e-6)
    # the plain rule is what it was: no table, no factor
    np.testing.assert_allclose(
        lm_layers.rope_half(h, 1e4, 128),
        ref.rotate_half(h, *ref.inverse_frequencies(
            cfg["rope_parameters"]["sliding_attention"], 128)), atol=2e-5)


def test_a_windowed_core_is_the_plain_bodys_and_is_counted_by_kind(monkeypatch):
    """At the published heads (a group of 9 on one key-value head of 128) and
    a length the kernels' blocks divide, on a TPU (here: the test says so),
    the full core goes to the kernels and the windowed one does not: their
    block pairs are the causal half. Both are counted by body and by kind in
    a series of their own, and the series by body alone still counts both."""
    t = ak.BLOCK
    q, kv = jnp.zeros((t, 1, 9, 128)), jnp.zeros((t, 1, 128))
    monkeypatch.setattr(ak, "_mode", lambda interpret: "mosaic")
    assert ak.takes(q, None, kv, None, kv)
    assert ak.takes(q[:, :, :6], None, kv, None, kv)  # a full layer's 6 on 1
    assert not ak.takes(q, None, kv, None, kv, window=512)
    assert not ak.takes(q, None, kv, None, kv, window=10 * t)
    assert not ak._fits(q, None, kv, None, kv, 512) and ak._fits(q, None, kv, None, kv)
    registry = get_global_registry()
    by_kind = lambda body, kind: registry.counter(
        lm_layers.CORES_BY_KIND, labels={"body": body, "kind": kind}).value
    by_body = lambda body: registry.counter(
        lm_layers.CORES_TRACED, labels={"body": body}).value
    series = [("plain", "window"), ("kernel", "window"), ("kernel", "full"),
              ("plain", "full")]
    before = {s: by_kind(*s) for s in series}
    bodies = {b: by_body(b) for b in ("plain", "kernel")}
    out = jax.eval_shape(
        lambda q, kv: lm_layers.attention_core(q, None, kv, None, kv, 0.1, 512, 512),
        q, kv)
    assert out.shape == q.shape
    jax.eval_shape(
        lambda q, kv: lm_layers.attention_core(q, None, kv, None, kv, 0.1, 512), q, kv)
    after = {s: by_kind(*s) for s in series}
    assert {s: after[s] - before[s] for s in series} == {
        ("plain", "window"): 1, ("kernel", "window"): 0, ("kernel", "full"): 1,
        ("plain", "full"): 0}
    assert by_body("plain") == bodies["plain"] + 1
    assert by_body("kernel") == bodies["kernel"] + 1


def test_the_programs_tree_is_the_references_parameter_list(cfg, ref):
    from fedtpu import models

    args = dict(cfg["program"]["round"]["model_args"])
    args.pop("micro_batch_rows")
    model = models.create("laguna", num_classes=cfg["vocab_size"], remat=True, **args)
    ids = jnp.zeros((1, T), jnp.int32)
    shapes = jax.eval_shape(
        lambda k: model.init(k, ids, train=True, targets=ids)["params"],
        jax.random.PRNGKey(0))
    ours = {tuple(k.key for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert ours == {path: tuple(shape) for path, shape, _ in ref.spec(cfg)[0]}
    assert "head" in shapes  # untied
    # one key-value head held, with its 2 query heads (full) or 3 (sliding)
    assert [shapes[f"layer_{i}"]["self_attn"]["g_proj"]["kernel"].shape[1]
            for i in range(5)] == [2, 3, 3, 3, 2]
    assert ["feed_forward" in shapes[f"layer_{i}"] for i in range(5)] == [
        True, False, False, False, False]
    # the published pattern is the default: a full layer every fourth from 0,
    # 48 query heads there and 72 in the sliding layers, layer 0 dense
    c = prog.Sizes()
    kinds = [c.kind(i) for i in c.layers]
    assert len(kinds) == 48 and kinds.count(FULL) == 12
    assert [i for i, k in enumerate(kinds) if k == FULL] == list(range(0, 48, 4))
    assert {(c.kind(i), c.query_heads(i)) for i in c.layers} == {
        (FULL, 48), (SLIDING, 72)}
    assert c.mlp_only_layers == (0,) and c.kv_held == (0, 8)
    assert prog.experts(c, 1)["held"] == (0, 256)
    # a cut names published layers: the kinds and head counts are read there
    cut = prog.Sizes(num_hidden_layers=2, layers_held=(4, 7))
    assert [(cut.kind(i), cut.query_heads(i)) for i in cut.layers] == [
        (FULL, 48), (SLIDING, 72)]
    with pytest.raises(ValueError, match="no size"):
        models.create("laguna", widht=3)
    with pytest.raises(ValueError, match="layers_held"):
        prog.Sizes(num_hidden_layers=2, layers_held=(0, 1, 2)).layers
    with pytest.raises(ValueError, match="names no kind"):
        prog.Sizes(layer_types=("full_attention", "conv")).kind(1)
    with pytest.raises(ValueError, match="no entry for layer 3"):
        prog.Sizes(num_attention_heads_per_layer=(48, 72)).query_heads(3)
    with pytest.raises(ValueError, match="no range"):
        prog.experts(prog.Sizes(experts_held=(250, 260)), 1)


def _round_config(cfg, micro_batch_rows, dtype="float32"):
    # three layers, both kinds of mixer and both feed-forwards: the step's
    # path, not the model, is what this holds
    model_args = dict(cfg["program"]["round"]["model_args"],
                      num_hidden_layers=3, layers_held=[0, 1, 4],
                      micro_batch_rows=micro_batch_rows)
    return RoundConfig(
        model="laguna", num_classes=256, image_size=(T,), remat=True,
        dtype=dtype, model_args=model_args,
        opt=OptimizerConfig(learning_rate=0.1, momentum=0.0, weight_decay=0.0),
        data=DataConfig(dataset="tokens", batch_size=2, num_examples=64,
                        partition="iid"),
        fed=FedConfig(num_clients=2, client_schedule="sequential"),
        steps_per_round=2)


def test_micro_batches_of_a_row_and_of_the_step_give_one_update(cfg):
    """Through ``Federation.step()`` with the clients in sequence and
    ``token_sgd_in_micro_batches``: rows of one and the step's two rows at
    once give the same first update to float32 rounding, they count alike
    (``RoundMetrics.tokens``, ``.moe_pairs_here``, ``.moe_load_max_over_mean``
    read for this model as for the others), and the model trains."""
    whole, by_row = (Federation(_round_config(cfg, n), seed=0) for n in (2, 1))
    start = jax.tree.map(np.asarray, whole.state.params)
    first = [fed.step() for fed in (whole, by_row)]
    assert float(first[0].loss) == pytest.approx(float(first[1].loss), rel=1e-5)
    update = lambda fed: jax.tree.map(lambda a, b: a - b, fed.state.params, start)
    assert _rel(update(by_row), update(whole)) <= 1e-4
    for m in first:
        # 2 clients x 2 steps x 2 rows x 127 positions with a target (the
        # token dataset's own rows of 128)
        assert float(m.tokens) == 2 * 2 * 2 * 127
        # the two sparse layers route 4 of 16 experts a token, 2 of them held
        assert 0 < int(m.moe_pairs_here) <= 2 * 2 * 2 * 2 * 128 * 2
        assert 1.0 <= float(m.moe_load_max_over_mean) <= 2.0
    assert int(first[0].moe_pairs_here) == int(first[1].moe_pairs_here)
    assert float(whole.step().loss) < float(first[0].loss)
