"""Granite-hybrid in the program, at a small size on the CPU, held to the plain
reference (``benchmark/reference/granite_hybrid.py``: float32 jax.numpy, the
state-space recurrence ONE TOKEN AT A TIME, a dense ``[T, T]`` mask with no
position term, the multipliers where the family's code has them, nothing of
the program). The tiny twin (``tests/benchmark/granite_tiny``) has a twin of
every width: hidden 64, 8 state-space heads of 16 (``mamba_expand`` 2) that
all read ONE group of ``B`` and ``C`` on a state of 16, a chunk of 12 that
does not divide the 32 tokens, 4 query heads on 2 key-value heads of 16, a
SwiGLU of 96, multipliers that are not 1 (5, 3, 0.3, 0.11), four layers
``mamba, mamba, attention, mamba`` (the published 3-6 of a pattern of ten),
and it holds HALF of each mixer's heads (state-space heads 4-7, key-value
head 1), as the benchmark's cell does.

Each kind of layer against the reference's, forward and gradient, in float32
and in bfloat16; the two shares of a ``mamba`` layer's heads, handed the sum
of squares both would exchange, adding up to the uncut layer, and each with
its own local statistic equal to the reference's share; the two key-value
shares of the attention layer likewise; the attention without a turn at its
own scale against a dense masked softmax; the stack's two factors; the
program's tree; what is refused; a federation's forward, loss and rounds. The
harness's own comparison, with the control and the faults, is in
``tests/benchmark/test_granite_cell.py``; the mixer's scan in
``tests/test_mamba2.py``; what the six language models share in
``tests/test_lm_layers.py``.
"""

import json
import math
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
from fedtpu.core import Federation
from fedtpu.models import granite_hybrid as prog
from fedtpu.models import lm_layers, mamba2

TINY_MANIFEST = os.path.join(ROOT, "tests", "benchmark", "granite_tiny_manifest.json")
TINY = os.path.join(ROOT, "tests", "benchmark", "granite_tiny", "configs",
                    "granite_tiny_f32.json")
T, D = 32, 64
WHOLE = dict(mamba_n_heads=8, num_attention_heads=4, num_key_value_heads=2)


@pytest.fixture(scope="module")
def cfg():
    with open(TINY) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def ref():
    from benchmark import run

    return run.load_py(os.path.join(ROOT, "benchmark", "reference", "granite_hybrid.py"))


def _sizes(cfg, **over):
    args = dict(cfg["program"]["round"]["model_args"])
    args.pop("micro_batch_rows")
    args.update(over)
    args = {k: tuple(v) if isinstance(v, list) else v for k, v in args.items()}
    return prog.Sizes(vocab_size=cfg["vocab_size"], **args)


def _weights(ref, cfg, seed=3):
    from benchmark import seeded

    return jax.tree.map(jnp.asarray, seeded.make_weights(seed, *ref.spec(cfg))[0])


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
# float32: rounding (sums in another order: a chunk's decay is a difference of
# running sums where the rule multiplies step by step). bfloat16 against the
# float32 reference: operands of 8 bits of mantissa, (output, gradients); a
# Mamba-2 layer's output is a product of four projected quantities, so the
# gradients' rounding adds up: the worst leaf (the held heads' ``A_log``,
# four numbers) reads 0.07 where every other leaf reads 0.01-0.025.
TOLERANCE = {"float32": (2e-5, 5e-5), "bfloat16": (0.02, 0.1)}
LAYERS = {"mamba": 0, "attention": 2}  # the stack's index of a kind


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_a_layer_is_the_references_forward_and_gradient(cfg, ref, kind, dtype):
    """One layer of each kind, both halves with their norms, the residual's
    multiplier twice, at the share of the heads the twin holds (the local
    statistic in the gated norm), from the same seeded weights on two rows:
    the program's ``Block`` (rematerialised, as the cell runs it) against the
    reference's ``layer``, output and the gradients of the stream and of every
    parameter; a dense layer routes nothing."""
    from benchmark.reference.layers import ident

    at = LAYERS[kind]
    layer = cfg["layers_held"][at]
    c = _sizes(cfg)
    assert c.kind(layer) == kind == cfg["layer_types"][layer]
    p = _weights(ref, cfg)[f"layer_{at}"]
    h = _x(7, 2, T, D)
    forward = ref.make_forward(cfg).layer
    theirs = _value_and_grads(
        lambda p, h: jnp.stack([forward(p, row, kind, ident) for row in h]), p, h)
    block = prog.Block(c, layer, remat=True)
    cast = lambda tree: jax.tree.map(lambda a: a.astype(dtype), tree)
    ours = _value_and_grads(
        lambda p, h: block.apply({"params": cast(p)}, cast(h))[0], p, h)
    out_tol, grad_tol = TOLERANCE[dtype]
    assert _rel(ours[0], theirs[0]) <= out_tol
    assert _rel(ours[1], theirs[1]) <= grad_tol
    _, pairs, load = block.apply({"params": p}, h)
    assert int(pairs) == 0 and float(load) == 0.0


def _mamba_share(p, lo, hi, head=16, heads=8, state=16):
    """Heads ``[lo, hi)`` of an uncut ``mamba`` layer's parameters: their
    columns of ``z``, ``x`` and ``dt`` and ALL of ``B`` and ``C``; the taps
    and bias those channels; ``W_out`` the matching rows."""
    d_in, own = heads * head, slice(lo * head, hi * head)
    w, wide = p["in_proj"]["kernel"], heads * head + 2 * state
    bc = slice(d_in, wide)  # within xBC
    return {
        "in_proj": {"kernel": jnp.concatenate([
            w[:, own], w[:, d_in:][:, own], w[:, d_in:][:, bc],
            w[:, d_in + wide:][:, lo:hi]], axis=1)},
        "conv": jnp.concatenate([p["conv"][:, own], p["conv"][:, bc]], axis=1),
        "conv_bias": jnp.concatenate([p["conv_bias"][own], p["conv_bias"][bc]]),
        "dt_bias": p["dt_bias"][lo:hi], "A_log": p["A_log"][lo:hi],
        "D": p["D"][lo:hi], "norm": p["norm"][own],
        "out_proj": {"kernel": p["out_proj"]["kernel"][own]},
    }


def test_two_shares_of_the_state_space_heads_add_up_to_the_uncut_layer(cfg, ref):
    """Two chips hold 4 of the 8 state-space heads each, and both compute the
    one group's ``B`` and ``C``. Each share's gated values have a sum of
    squares (the module sows its mean); handed the mean over ALL 128 channels
    that the all-reduce of the two sums would deliver, the two shares' outputs
    add up to the uncut reference layer's output, and so do the gradients of
    the input, the statistic's path included: ``B`` and ``C`` are counted
    once, for what both chips compute alike enters no sum twice, only each
    share's own heads' rows of ``W_out`` do. And each share with its OWN local
    statistic, which is what one chip without the exchange computes, is the
    reference's share (the reference norms over the channels it holds). 2e-5 /
    5e-5: float32 sums in another order."""
    from benchmark.reference.layers import ident

    uncut = dict(cfg, **WHOLE)
    p = _weights(ref, uncut)["layer_0"]["mamba"]
    x = _x(5, 2, T, D)
    rows = lambda f: lambda p, x: jnp.stack([f(p, row, ident) for row in x])
    theirs = _value_and_grads(rows(ref.make_forward(uncut).mamba), p, x)
    shares = [(0, 4), (4, 8)]
    mixer = lambda held: mamba2.Mamba2(**prog.mamba(_sizes(cfg, mamba_heads_held=held)))

    def all_shares(p, x):
        parts = [{"params": _mamba_share(p, *held)} for held in shares]
        sums = []
        for held, part in zip(shares, parts):  # what each chip would send
            _, sown = mixer(held).apply(part, x, mutable=["intermediates"])
            (mean,) = sown["intermediates"]["gated_mean_square"]
            sums.append(mean * (held[1] - held[0]) * 16)
        exchanged = sum(sums) / 128  # the all-reduce's sum over all d_in
        return sum(mixer(held).apply(part, x, mean_square=exchanged)
                   for held, part in zip(shares, parts))

    ours = _value_and_grads(all_shares, p, x)
    assert _rel(ours[0], theirs[0]) <= 2e-5
    assert _rel(ours[1], theirs[1]) <= 5e-5
    # B and C once: the two shares' leaves are the uncut layer's and one more
    # copy of what both compute alike (32 columns of W_in, taps and bias)
    count = lambda tree: sum(math.prod(l.shape) for l in jax.tree.leaves(tree))
    assert sum(count(_mamba_share(p, *held)) for held in shares) == count(p) + (
        D * 32 + 4 * 32 + 32)
    for held in shares:  # one chip, no exchange: the reference's share
        here = dict(cfg, mamba_n_heads=held[1] - held[0])
        part = _mamba_share(p, *held)
        mine = _value_and_grads(
            lambda p, x: mixer(held).apply({"params": p}, x), part, x)
        ref_share = _value_and_grads(rows(ref.make_forward(here).mamba), part, x)
        assert _rel(mine[0], ref_share[0]) <= 2e-5
        assert _rel(mine[1], ref_share[1]) <= 5e-5
    # the local statistic is NOT the exchanged one: the shares alone do not add up
    alone = sum(mixer(held).apply({"params": _mamba_share(p, *held)}, x)
                for held in shares)
    assert _rel(alone, theirs[0]) > 1e-2


def test_two_shares_of_the_key_value_heads_add_up_to_the_uncut_layer(cfg, ref):
    """Two chips hold one key-value head each with its two query heads
    (``W_q``, ``W_k``, ``W_v`` their columns, ``W_o`` the matching rows): the
    partial sums add up to the uncut reference layer's output and input
    gradient, and each share is the reference's share. No statistic crosses
    this share: the softmax is a head's own."""
    from benchmark.reference.layers import ident

    uncut = dict(cfg, **WHOLE)
    p = _weights(ref, uncut)["layer_2"]["self_attn"]
    x = _x(6, 2, T, D)
    rows = lambda f: lambda p, x: jnp.stack([f(p, row, ident) for row in x])
    theirs = _value_and_grads(rows(ref.make_forward(uncut).attention), p, x)

    def share(p, lo, hi, hd=16, group=2):
        q, kv = slice(lo * group * hd, hi * group * hd), slice(lo * hd, hi * hd)
        return {"q_proj": {"kernel": p["q_proj"]["kernel"][:, q]},
                "k_proj": {"kernel": p["k_proj"]["kernel"][:, kv]},
                "v_proj": {"kernel": p["v_proj"]["kernel"][:, kv]},
                "o_proj": {"kernel": p["o_proj"]["kernel"][q]}}

    layer = lambda held: prog.Attention(_sizes(cfg, kv_heads_held=held))
    ours = _value_and_grads(
        lambda p, x: sum(layer(held).apply({"params": share(p, *held)}, x)
                         for held in ((0, 1), (1, 2))), p, x)
    assert _rel(ours[0], theirs[0]) <= 2e-5
    assert _rel(ours[1], theirs[1]) <= 5e-5
    here = ref.make_forward(cfg).attention  # 2 query heads on 1 key-value head
    for held in ((0, 1), (1, 2)):
        part = share(p, *held)
        mine = _value_and_grads(lambda p, x: layer(held).apply({"params": p}, x), part, x)
        assert _rel(mine, _value_and_grads(rows(here), part, x)) <= 5e-5


@pytest.mark.parametrize("turn_in_core", [True, False])
def test_attention_without_a_turn_at_its_own_scale_is_a_dense_masked_softmax(
        turn_in_core):
    """``grouped_query_attention`` with no rotary rule and ``scale`` handed in:
    ``softmax(scale * q k^T)`` under a dense causal mask, nothing of position
    anywhere (rows permuted together with their mask give the permuted
    output), under either ``turn_in_core``; without a ``scale`` it is ``1 /
    sqrt(hd)`` as it was. 1e-5: float32 sums in another order."""
    b, t, kh, group, hd, scale = 2, T, 2, 2, 16, 0.11
    q, k, v = _x(1, b, t, kh, group, hd), _x(2, b, t, kh, hd), _x(3, b, t, kh, hd)

    def dense(q, k, v, scale):
        s = scale * jnp.einsum("bqhgd,bkhd->bhgqk", q, k)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v)
        return o.reshape(b, t, kh * group * hd)

    ours = lm_layers.grouped_query_attention(
        q, k, v, None, 16, turn_in_core=turn_in_core, scale=scale)
    np.testing.assert_allclose(ours, dense(q, k, v, scale), rtol=1e-5, atol=1e-6)
    plain = lm_layers.grouped_query_attention(
        q, k, v, None, 16, turn_in_core=turn_in_core)
    np.testing.assert_allclose(
        plain, dense(q, k, v, 1 / math.sqrt(hd)), rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(ours - plain).max()) > 1e-3  # the scale is the config's
    # a rotary rule still turns where a model hands one in
    turned = lm_layers.grouped_query_attention(
        q, k, v, lambda a: lm_layers.rope_half(a, 10000.0, hd), 16,
        turn_in_core=turn_in_core, scale=scale)
    assert float(jnp.abs(turned - ours).max()) > 1e-3


def test_the_stack_scales_the_stream_on_entry_and_the_logits_on_exit():
    """``DecoderStack`` at ``embedding_multiplier`` 12 and ``logits_scaling`` 8
    with the tied head between them, against the plain formula: the logits,
    the loss's sum, and the embedding's gradient, which has the 12 from below
    and the 1/8 from above; and without them the stack is what it was (the
    defaults emit no operation: its logits are the plain stack's). A block
    that adds a constant stands for the layers. 1e-5: float32 rounding."""
    vocab, d, eps = 23, 16, 1e-5

    class Shift(nn.Module):
        @nn.compact
        def __call__(self, h):
            return (h + 0.5,) + lm_layers.no_pairs()

    def stack(**factors):
        return lm_layers.DecoderStack(
            vocab_size=vocab, hidden_size=d, eps=eps, blocks=(Shift,),
            tied_head=True, **factors)

    table, scale = 0.3 * _x(1, vocab, d), 1.0 + 0.1 * _x(2, d)
    params = {"embed": {"embedding": table}, "final_norm": scale}
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 9), 0, vocab)
    targets = jnp.concatenate([ids[:, 1:], jnp.full((2, 1), -1)], axis=1)

    def plain_logits(table, mult, over):
        h = mult * table[ids] + 0.5
        h = scale * h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps)
        return (h @ table.T) / over

    def plain_sum(table, mult, over):
        logp = jax.nn.log_softmax(plain_logits(table, mult, over), axis=-1)
        picked = jnp.take_along_axis(logp, jnp.maximum(targets, 0)[..., None], -1)[..., 0]
        return -jnp.sum(jnp.where(targets >= 0, picked, 0.0))

    ours = stack(embedding_multiplier=12.0, logits_scaling=8.0)
    np.testing.assert_allclose(
        ours.apply({"params": params}, ids), plain_logits(table, 12.0, 8.0),
        rtol=1e-5, atol=1e-6)

    def our_sum(table):
        ((total, count, _),) = ours.apply(
            {"params": dict(params, embed={"embedding": table})}, ids, train=True,
            targets=targets, mutable=["counters"])[0]
        return total

    np.testing.assert_allclose(our_sum(table), plain_sum(table, 12.0, 8.0), rtol=1e-5)
    np.testing.assert_allclose(
        jax.grad(our_sum)(table), jax.grad(plain_sum)(table, 12.0, 8.0),
        rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        stack().apply({"params": params}, ids), plain_logits(table, 1.0, 1.0),
        rtol=1e-5, atol=1e-6)
    # no multiply and no divide is traced where the stack has neither factor
    text = lambda m: str(jax.make_jaxpr(lambda p: m.apply({"params": p}, ids))(params))
    count = lambda m: [text(m).count(f" {op} ") for op in ("mul", "div")]
    assert count(ours) == [n + 1 for n in count(stack())]


# ------------------------------------------------------------------ the model
def test_the_programs_tree_is_the_references_parameter_list(cfg, ref):
    from fedtpu import models

    args = dict(cfg["program"]["round"]["model_args"])
    args.pop("micro_batch_rows")
    model = models.create("granite_hybrid", num_classes=cfg["vocab_size"], remat=True,
                          **args)
    ids = jnp.zeros((1, T), jnp.int32)
    shapes = jax.eval_shape(
        lambda k: model.init(k, ids, train=True, targets=ids)["params"],
        jax.random.PRNGKey(0))
    ours = {tuple(k.key for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert ours == {path: tuple(shape) for path, shape, _ in ref.spec(cfg)[0]}
    assert "head" not in shapes  # tied
    # TWO halves and two norms a layer, a SwiGLU in every one, no router
    halves = [sorted(shapes[f"layer_{i}"]) for i in range(4)]
    mamba_layer = ["ffn_norm", "mamba", "mixer_norm", "shared_mlp"]
    assert halves == [mamba_layer] * 2 + [
        ["ffn_norm", "mixer_norm", "self_attn", "shared_mlp"]] + [mamba_layer]
    assert sorted(shapes["layer_0"]["shared_mlp"]) == ["down", "gate", "up"]
    # half of the heads: 4 of 8 heads of 16 and the one group's B and C
    assert shapes["layer_0"]["mamba"]["in_proj"]["kernel"].shape == (64, 64 + 64 + 32 + 4)
    assert shapes["layer_0"]["mamba"]["conv_bias"].shape == (64 + 32,)
    assert shapes["layer_2"]["self_attn"]["q_proj"]["kernel"].shape == (64, 32)
    assert shapes["layer_2"]["self_attn"]["k_proj"]["kernel"].shape == (64, 16)
    # the published pattern is the default: attention at 5, 15, 25, 35
    c = prog.Sizes()
    kinds = [c.kind(i) for i in c.layers]
    assert len(kinds) == 40 and [i for i, k in enumerate(kinds) if k == "attention"] == [
        5, 15, 25, 35]
    assert (c.embedding_multiplier, c.logits_scaling, c.residual_multiplier,
            c.attention_multiplier) == (12.0, 8.0, 0.22, 0.015625)
    assert prog.mamba(c)["groups"] == 1 and prog.mamba(c)["chunk"] == 256
    assert prog.mamba(c)["heads_held"] is None and c.kv_held == (0, 8)
    # the program's own initialiser: a first loss near ln(vocabulary)
    init = model.init(jax.random.PRNGKey(1), ids, train=True, targets=ids)["params"]
    assert float(jnp.std(init["embed"]["embedding"])) == pytest.approx(0.03, rel=0.1)
    with pytest.raises(ValueError, match="no size"):
        models.create("granite_hybrid", widht=3)
    with pytest.raises(ValueError, match="layers_held"):
        prog.Sizes(num_hidden_layers=2, layers_held=(0, 1, 2)).layers
    with pytest.raises(ValueError, match="names no kind"):
        prog.Sizes(layer_types=("mamba", "moe")).kind(1)
    # no rule makes up the kind of a layer the list does not name
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite_4_0_h_micro.json")) as fh:
        assert prog.Sizes().layer_types == tuple(json.load(fh)["layer_types"])
    with pytest.raises(ValueError, match="names no kind"):
        prog.Sizes(layer_types=("mamba",) * 6).kind(15)
    with pytest.raises(ValueError, match="no range"):
        prog.Sizes(kv_heads_held=(6, 9)).kv_held
    with pytest.raises(ValueError, match="mamba_expand x hidden_size"):
        prog.Sizes(mamba_n_heads=48)


def test_sizes_that_state_experts_or_positions_are_refused(cfg, ref):
    """``num_local_experts`` 0 is the model: a file that states experts would
    ask for the family's sparse form, which is not written here; program and
    reference both refuse it with the reason, and neither guesses. The same
    for a position rule the attention does not have."""
    from fedtpu import models

    for key in ("num_local_experts", "num_experts_per_tok"):
        with pytest.raises(ValueError, match="DENSE member"):
            models.create("granite_hybrid", **{key: 2})
        with pytest.raises(ValueError, match="DENSE member"):
            ref.spec(dict(cfg, **{key: 2}))
        with pytest.raises(ValueError, match="DENSE member"):
            ref.make_forward(dict(cfg, **{key: 2}))
    with pytest.raises(ValueError, match="no position term"):
        prog.Sizes(position_embedding_type="rope")
    with pytest.raises(ValueError, match="ONE group"):
        ref.spec(dict(cfg, mamba_n_groups=2))


def test_the_forward_and_the_loss_are_the_references(cfg, ref):
    """The whole model from seeded weights on two rows: evaluation logits and
    the training loss against the reference's forward and the task's loss.
    float32: 2e-5 (rounding); bfloat16 logits against the float32 reference:
    0.03 of their norm (four layers of 8-bit mantissas), the loss 2e-3."""
    from benchmark import run
    from fedtpu import models

    task = run.load_py(os.path.join(ROOT, "benchmark", "tasks", "next_token.py"))
    params = _weights(ref, cfg)
    args = dict(cfg["program"]["round"]["model_args"])
    args.pop("micro_batch_rows")
    model = models.create("granite_hybrid", num_classes=cfg["vocab_size"], remat=True,
                          **args)
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, T), 0, cfg["vocab_size"])
    targets = jnp.concatenate([ids[:, 1:], jnp.full((2, 1), -1)], axis=1)
    theirs, _ = ref.make_forward(cfg)(params, {}, ids)
    their_loss = float(task.loss(theirs, targets))
    assert their_loss == pytest.approx(math.log(97), rel=0.1)
    for dtype, tol, loss_tol in (("float32", 2e-5, 1e-6), ("bfloat16", 0.03, 2e-3)):
        cast = jax.tree.map(lambda a: a.astype(dtype), params)
        ours = model.apply({"params": cast}, ids)
        assert ours.dtype == jnp.float32 and _rel(ours, theirs) <= tol
        ((total, count, _),), _ = model.apply(
            {"params": cast}, ids, train=True, targets=targets, mutable=["counters"])
        assert float(total / count) == pytest.approx(their_loss, rel=loss_tol)


def test_a_federations_rounds_are_the_references(cfg):
    """Through ``Federation.step()`` by the harness's own comparison (four
    clients in sequence, two steps of two rows in micro-batches of one), in
    float32: the loss to 2e-5 and the first update and two rounds' change to
    5e-4 of the reference's (float32 sums in another order: the chunked scan,
    the micro-batches' sum, the clients' weighted mean)."""
    from benchmark import run

    lines = []
    result = run.run(TINY_MANIFEST, "granite_tiny_f32.fl4_seq32", 11, 0.2, False,
                     need_tpu=False, out=lines.append)
    assert result["correct"] and result["failed"] == 0, "\n".join(lines)
    read = {l.split()[1]: float(l.split()[3]) for l in lines
            if l.startswith("check ") and " = " in l}
    assert read["loss_gap"] <= 2e-5
    assert max(read[k] for k in ("update1_gap", "update1_diff", "change_gap")) <= 5e-4


def _round_config(cfg, micro_batch_rows, dtype="float32"):
    model_args = dict(cfg["program"]["round"]["model_args"],
                      micro_batch_rows=micro_batch_rows)
    return RoundConfig(
        model="granite_hybrid", num_classes=256, image_size=(T,), remat=True,
        dtype=dtype, model_args=model_args,
        opt=OptimizerConfig(learning_rate=0.1, momentum=0.0, weight_decay=0.0),
        data=DataConfig(dataset="tokens", batch_size=2, num_examples=64,
                        partition="iid"),
        fed=FedConfig(num_clients=2, client_schedule="sequential"),
        steps_per_round=2)


def test_micro_batches_of_a_row_and_of_the_step_give_one_update(cfg):
    """Through ``Federation.step()`` with the clients in sequence and
    ``token_sgd_in_micro_batches``, by the entry points of the other five
    language models: rows of one and the step's two rows at once give the same
    first update to float32 rounding, they count alike, a model that routes
    nothing reports no pairs and no load, and the model trains."""
    whole, by_row = (Federation(_round_config(cfg, n), seed=0) for n in (2, 1))
    start = jax.tree.map(np.asarray, whole.state.params)
    first = [fed.step() for fed in (whole, by_row)]
    assert float(first[0].loss) == pytest.approx(float(first[1].loss), rel=1e-5)
    update = lambda fed: jnp.concatenate([
        (a - b).ravel() for a, b in zip(jax.tree.leaves(fed.state.params),
                                        jax.tree.leaves(start))])
    assert _rel(update(by_row), update(whole)) <= 1e-4
    for m in first:
        assert float(m.tokens) == 2 * 2 * 2 * 127  # the token data's own rows of 128
        assert int(m.moe_pairs_here) == 0 and float(m.moe_load_max_over_mean) == 0.0
    assert float(whole.step().loss) < float(first[0].loss)
