"""What ``joyai_llm_flash``, ``qwen3_next``, ``lfm2_moe``, ``laguna`` and
``nemotron_h`` (its experts of TWO matrices beside the others' three) share
(``fedtpu/models/lm_layers.py``), each case for every model that runs it, at a
small size on the CPU against that model's plain reference: the shares of the routed
experts adding up to the uncut layer, routing so skewed that every token lands
on one held expert with nothing dropped, and the plain causal-attention body
at both models' shapes (a key head each with a separate rotary operand; a key
head a group of query heads with none) and which shapes the fused kernels take
of each, with the choice of body counted; the same body over a window (a
query's own position and the ``window - 1`` before it) against a dense mask.
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

from fedtpu.models import (
    joyai_llm_flash, laguna, lfm2_moe, lm_layers, nemotron_h, qwen3_next)
from fedtpu.obs.registry import get_global_registry
from fedtpu.ops import attention_kernels as ak

T, D = 32, 64


def _x(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _close(a, b, tol=2e-5):
    """Norm of the difference over the reference's norm, leaf by leaf."""
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        scale = max(float(jnp.linalg.norm(y)), 1e-12)
        assert float(jnp.linalg.norm(x - y)) <= tol * scale, (x.shape, scale)


def _value_and_grads(f, *args, more=False):
    """``f``'s output contracted with a fixed cotangent, and its gradients;
    with ``more``, ``f`` returns ``(output, what else the same program
    computes)`` and that comes back third: one compile, not two."""
    def scalar(*a):
        out, rest = f(*a) if more else (f(*a), None)
        return jnp.sum(out * _x(99, *out.shape)), (out, rest)
    (_, (out, rest)), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return (out, grads, rest) if more else (out, grads)


class Model:
    """One model's expert layer in the program and in its reference, under
    the names the two give the same things."""

    def __init__(self, name):
        from benchmark import run

        tiny = {"joyai_llm_flash": ("joyai_tiny", "joyai_tiny_f32"),
                "qwen3_next": ("qwen_tiny", "qwen_tiny_f32"),
                "lfm2_moe": ("lfm2_tiny", "lfm2_tiny_f32"),
                "laguna": ("laguna_tiny", "laguna_tiny_f32"),
                "nemotron_h": ("nemotron_tiny", "nemotron_tiny_f32")}[name]
        with open(os.path.join(ROOT, "tests", "benchmark", tiny[0], "configs",
                               tiny[1] + ".json")) as fh:
            self.cfg = json.load(fh)
        self.name = name
        self.prog = {"joyai_llm_flash": joyai_llm_flash, "qwen3_next": qwen3_next,
                     "lfm2_moe": lfm2_moe, "laguna": laguna,
                     "nemotron_h": nemotron_h}[name]
        self.ref = run.load_py(os.path.join(ROOT, "benchmark", "reference", name + ".py"))
        # a reference whose router has a selection bias drawn from the layer's
        # index takes that index
        self.biased = hasattr(self.ref, "selection_bias")
        # the configuration's key for the experts HELD (the reference's count)
        self.held_key = ("n_routed_experts" if name in (
            "joyai_llm_flash", "nemotron_h") else "num_experts")
        self.share = self.cfg[self.held_key]  # experts a chip holds
        self.routed = self.cfg["router_width"]  # of 16; Nemotron-H's twin: of 32
        # an expert's form: three stacks (a SwiGLU) or Nemotron-H's two
        self.two_matrices = name == "nemotron_h"
        self.stacks = ("experts_up", "experts_down") if self.two_matrices else (
            "experts_gate", "experts_up", "experts_down")
        # the first expert layer of the tiny twin's stack
        self.moe_at = "layer_0" if name == "nemotron_h" else "layer_1"
        self.drawn = {}

    def sizes(self, **over):
        args = dict(self.cfg["program"]["round"]["model_args"])
        args.pop("micro_batch_rows")
        args.update(over)
        args = {k: tuple(v) if isinstance(v, list) else v for k, v in args.items()}
        return self.prog.Sizes(vocab_size=self.cfg["vocab_size"], **args)

    def weights(self, cfg, seed=3):
        """The expert layer's of the model's seeded weights; a configuration
        two cases ask for is drawn once (a draw is the whole model's, three
        seconds)."""
        from benchmark import seeded

        key = json.dumps(cfg, sort_keys=True), seed
        if key not in self.drawn:
            params, _ = seeded.make_weights(seed, *self.ref.spec(cfg))
            self.drawn[key] = jax.tree.map(jnp.asarray, params)[self.moe_at]["moe"]
        return self.drawn[key]

    def layer(self, sizes):
        return lm_layers.ExpertLayer(**self.prog.experts(sizes, 1))

    def answering_with_a_one(self, p, alpha=1.5):
        """``p`` of zeros but for experts that each answer a token's entry 0
        with that entry in their own column of the output, whichever form an
        expert has: ``silu(alpha) / silu(alpha)`` through three matrices,
        ``relu(alpha)^2 / alpha^2`` through two."""
        at = jnp.arange(self.routed)
        p = jax.tree.map(jnp.zeros_like, p)
        if self.two_matrices:
            p["experts_up"] = p["experts_up"].at[:, 0, 0].set(alpha)
            through = alpha ** 2
        else:
            p["experts_gate"] = p["experts_gate"].at[:, 0, 0].set(alpha)
            p["experts_up"] = p["experts_up"].at[:, 0, 0].set(1.0)
            through = jax.nn.silu(alpha)
        p["experts_down"] = p["experts_down"].at[at, 0, at].set(1.0 / through)
        return p

    def reference(self, cfg):
        from benchmark.reference.layers import ident

        f = self.ref.make_forward(cfg).expert_layer
        return (lambda p, x: f(p, x, 1, ident)) if self.biased else (
            lambda p, x: f(p, x, ident))


@pytest.fixture(scope="module", params=["joyai_llm_flash", "qwen3_next", "lfm2_moe",
                                        "laguna", "nemotron_h"])
def model(request):
    return Model(request.param)


# ---------------------------------------------------------- the routed experts
def test_the_shares_of_the_routed_experts_add_up_to_the_uncut_layer(model):
    """The routed parts that all the shares compute (four of 4 experts; of
    ``lfm2_moe`` eight of 2; of ``nemotron_h`` sixteen of 2, two matrices an
    expert), plus what every chip computes alike (the shared expert, gated or
    not; ``lfm2_moe`` has none) counted once, are the uncut reference layer's
    output and input gradient."""
    uncut = dict(model.cfg, experts_held_from=0, **{model.held_key: model.routed})
    p = model.weights(uncut)
    x = _x(5, 2 * T, D)
    theirs = _value_and_grads(lambda x: model.reference(uncut)(p, x), x)
    # what a chip with no routed expert of its own would give: the shared part
    none_held = dict(model.cfg, **{model.held_key: 0})
    alike = lambda x: model.reference(none_held)(p, x)

    def all_shares(x):
        once = alike(x)
        total, pairs = once, 0
        for lo in range(0, model.routed, model.share):
            held = dict(p, **{k: p[k][lo:lo + model.share] for k in model.stacks})
            y, n, _ = model.layer(model.sizes(experts_held=(lo, lo + model.share))).apply(
                {"params": held}, x)
            total, pairs = total + (y - once), pairs + n
        return total, pairs

    *ours, pairs = _value_and_grads(all_shares, x, more=True)
    _close(tuple(ours), theirs)
    # every (token, chosen expert) pair is computed by exactly one share
    assert int(pairs) == 2 * T * model.cfg["num_experts_per_tok"]


def _everything_on_one_expert(model):
    """``(cfg, params, x, lo)`` under which every token picks ONE expert, the
    same one, of the held range ``[lo, lo + share)``. JoyAI, LFM2: a router of
    zeros scores every expert alike, so the selection bias alone picks. Qwen3-Next
    and Laguna have no bias: tokens of positive entries against a router whose one column
    of ones outscores the columns of zeros."""
    one = dict(model.cfg, num_experts_per_tok=1)
    if model.biased:
        busiest = int(jnp.argmax(model.ref.selection_bias(1, one)))
        x = _x(6, 2 * T, D)
    else:
        busiest, x = 9, jnp.abs(_x(6, 2 * T, D)) + 0.5
    lo = busiest // model.share * model.share
    one["experts_held_from"] = lo
    p = dict(model.weights(one))
    p["router"] = jnp.zeros_like(p["router"])
    if not model.biased:
        p["router"] = p["router"].at[:, busiest].set(1.0)
    return one, p, x, lo


@pytest.mark.parametrize("chunk", [48, 4096])
def test_every_token_on_one_held_expert_and_nothing_is_dropped(model, chunk):
    """All the pairs fall on ONE of the held experts, in as many chunks as it
    takes."""
    one, p, x, lo = _everything_on_one_expert(model)
    layer = model.layer(model.sizes(
        num_experts_per_tok=1, experts_held=(lo, lo + model.share), moe_chunk_pairs=chunk,
        moe_block_rows=16))

    def apply(x):
        y, pairs, load = layer.apply({"params": p}, x)
        return y, (pairs, load)

    *ours, (pairs, load) = _value_and_grads(apply, x, more=True)
    assert int(pairs) == 2 * T
    # one expert has it all: as many times the mean as experts are held
    assert float(load) == pytest.approx(float(model.share))
    reference = model.reference(one)
    _close(tuple(ours), _value_and_grads(lambda x: reference(p, x), x))


# A model's gate rule as its configuration states it, nothing of the model's
# file: (scores of the logits, the configuration's key for the scale, epsilon).
GATE_RULES = {
    "joyai_llm_flash": (jax.nn.sigmoid, "routed_scaling_factor", None),
    "qwen3_next": (jax.nn.softmax, None, None),
    "lfm2_moe": (jax.nn.sigmoid, "routed_scaling_factor", 1e-6),
    "laguna": (jax.nn.softmax, "moe_routed_scaling_factor", None),
    "nemotron_h": (jax.nn.sigmoid, "routed_scaling_factor", None),
}


def test_the_gate_rules_shared_tail_is_each_references_router(model):
    """``top_k_gates`` alone, with a model's rule stated here, against the
    reference's router read off the reference's uncut expert layer: tokens
    whose entries 1..R ARE the router's logits (R experts: 16, Nemotron-H's
    twin 32) and whose entry 0 is one,
    experts that each answer that one with a one in their own column, a shared
    expert of zeros, so column ``e`` of the layer's output is expert ``e``'s
    gate and its support is the chosen mask. A seeded batch, a row whose
    scores are all nearly nothing (sigmoid: the chosen scores sum to 2e-17,
    LFM2's epsilon is all of the divisor) and a row whose k-th and k+1-th
    largest logits are equal (one of the two is chosen, by both alike)."""
    routed = model.routed
    uncut = dict(model.cfg, experts_held_from=0, **{model.held_key: routed})
    k, at = uncut["num_experts_per_tok"], jnp.arange(routed)
    logits = jnp.concatenate([
        _x(21, 30, routed),
        (-40.0 - 0.1 * at)[None],
        # three above, experts 3 and 11 level at the k-th place, the rest below
        jnp.where(at < 3, 2.0 + at, jnp.where((at == 3) | (at == 11), 1.0, -1.0 - at))[None],
    ])
    x = jnp.zeros((32, D)).at[:, 0].set(1.0).at[:, 1:1 + routed].set(logits)
    p = model.answering_with_a_one(model.weights(uncut))
    p["router"] = p["router"].at[1 + at, at].set(1.0)
    theirs = model.reference(uncut)(p, x)
    assert not np.asarray(theirs[:, routed:]).any()
    theirs = theirs[:, :routed]

    scores, scale, eps = GATE_RULES[model.name]
    gates, picked = lm_layers.top_k_gates(
        scores(logits), k, scale=uncut[scale] if scale else None, eps=eps,
        bias=model.ref.selection_bias(1, uncut) if model.biased else None)
    np.testing.assert_array_equal(picked, theirs != 0)
    np.testing.assert_allclose(gates, theirs, rtol=1e-5, atol=0)
    assert (np.asarray(picked).sum(1) == k).all()
    assert int(picked[-1, 3]) + int(picked[-1, 11]) == 1 and bool(picked[-1, :3].all())
    # what a token's gates add up to: the scale, and in LFM2's thin row nearly nothing
    total = np.asarray(gates.sum(1))
    np.testing.assert_allclose(
        total[:30], uncut[scale] if scale else 1.0, rtol=1e-4)
    if eps:
        assert 0 < total[30] < 1e-10
    # and the program's own rule is that tail: the model file's closure
    ours = model.prog.experts(model.sizes(experts_held=(0, routed)), 1)["gate_rule"](logits, k)
    np.testing.assert_array_equal(ours[1], picked)
    np.testing.assert_array_equal(ours[0], gates)


def test_chunks_are_laid_out_for_the_pairs_a_token_can_have():
    """A token picks at most ``per_token`` experts, so the sorted pairs end by
    ``n * per_token``: 6 tokens x 8 held experts but 2 a token is 12 pairs at
    most, and a chunk that could hold more is laid out for those 12 (6 + 8
    blocks of 2 rows), not for the 48 of every (token, held expert) (24 + 8
    blocks); in chunks of 4 they take three turns of ONE loop (no ``cond`` a
    chunk the layout allows), and with every pair real nothing is lost."""
    n, held, d, width = 6, 8, 16, 8
    x = _x(1, n, d)
    picked = jnp.zeros((n, held), bool).at[jnp.arange(n), jnp.arange(n) % held].set(
        True).at[jnp.arange(n), (jnp.arange(n) + 3) % held].set(True)
    gates = jnp.where(picked, 0.5, 0.0)
    w = [_x(2 + i, held, *shape) for i, shape in
         enumerate([(d, width), (d, width), (width, d)])]
    run = lambda per_token, chunk=4: lm_layers.routed_experts(
        x, jnp.zeros_like(x), gates, picked, w, per_token, chunk, 2)
    dense = sum(gates[:, e, None] * (
        (jax.nn.silu(x @ w[0][e]) * (x @ w[1][e])) @ w[2][e]) for e in range(held))
    for per_token, chunk in ((2, 4), (8, 4), (2, 64)):
        y, pairs, _ = run(per_token, chunk)
        assert int(pairs) == 12
        np.testing.assert_allclose(y, dense, rtol=1e-5, atol=1e-5)
    program = lambda per_token, chunk: str(
        jax.make_jaxpr(lambda: run(per_token, chunk)[0])())
    for per_token in (2, 8):
        text = program(per_token, 4)
        assert " cond[" not in text and text.count(" while[") == 1
    rows = lambda blocks: f"f32[{2 * blocks},{d}]"  # a chunk's rows, laid out
    assert rows(6 + 8) in program(2, 64) and rows(24 + 8) not in program(2, 64)
    assert rows(24 + 8) in program(8, 64)


def test_the_expert_layer_is_the_same_through_either_body(model, monkeypatch):
    """A model's expert layer at widths of whole lanes (hidden 128, experts of
    128), blocks of 16 rows and chunks of ``T * k`` pairs: through the kernels
    (``fedtpu/ops/expert_kernels.py``, interpreted: the test says so where
    the program asks the backend) and through the plain batched product it
    gives the same ``(y, pairs, load)`` and the same gradients of the tokens
    and of every parameter, and the counter says which body the products of a
    trace took, one a stack of weights: three, of Nemotron-H's two."""
    from fedtpu.ops import expert_kernels as ek

    k = model.cfg["num_experts_per_tok"]
    layer = model.layer(model.sizes(
        hidden_size=128, moe_intermediate_size=128, moe_block_rows=16,
        moe_chunk_pairs=T * k, experts_held=(4, 4 + model.share)))
    x = _x(11, 2 * T, 128)
    params = layer.init(jax.random.PRNGKey(12), x)["params"]
    traced = lambda body: get_global_registry().counter(
        lm_layers.PRODUCTS_TRACED, labels={"body": body}).value

    def run(mode):
        monkeypatch.setattr(ek, "_mode", lambda interpret: mode)
        before = {b: traced(b) for b in ("kernel", "plain")}
        apply = lambda p, x: layer.apply({"params": p}, x)
        out = _value_and_grads(lambda p, x: apply(p, x)[0], params, x)
        _, pairs, load = jax.jit(apply)(params, x)
        return out, int(pairs), float(load), {
            b: traced(b) - before[b] for b in before}

    kernel, plain = run("interpret"), run("xla")
    products = len(model.stacks)
    assert kernel[3]["kernel"] >= products and kernel[3]["plain"] == 0
    assert plain[3]["plain"] >= products and plain[3]["kernel"] == 0
    assert kernel[3]["kernel"] % products == plain[3]["plain"] % products == 0
    assert kernel[1] == plain[1] > 0 and kernel[2] == plain[2]
    _close(kernel[0], plain[0])
    assert float(jnp.abs(plain[0][1][0]["experts_down"]).max()) > 0


# --------------------------------------------------- the plain attention body
def _attention_operands(grouped, t=T):
    """JoyAI's shapes (4 heads, each its own key head, a rotary operand whose
    key is every head's) or Qwen3-Next's (2 key heads of 2 query heads each,
    no rotary operand)."""
    if grouped:
        return (_x(1, t, 2, 2, 16), None, _x(2, t, 2, 16), None, _x(3, t, 2, 16))
    return (_x(1, t, 4, 16), _x(4, t, 4, 8), _x(2, t, 4, 16), _x(5, t, 8),
            _x(3, t, 4, 16))


def _one_head_at_a_time(q_nope, q_rope, k_nope, k_rope, v, scale):
    """Full ``[T, T]`` scores of one query head at a time, its key head
    copied out for it."""
    t = q_nope.shape[0]
    q_nope = q_nope.reshape(t, -1, q_nope.shape[-1])
    per_key = q_nope.shape[1] // k_nope.shape[1]
    out = []
    for h in range(q_nope.shape[1]):
        s = q_nope[:, h] @ k_nope[:, h // per_key].T
        if q_rope is not None:
            s = s + q_rope[:, h] @ k_rope.T
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s * scale, -jnp.inf)
        out.append(jax.nn.softmax(s, axis=-1) @ v[:, h // per_key])
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("grouped", [False, True], ids=["joyai_llm_flash", "qwen3_next"])
def test_the_plain_body_is_attention_one_head_at_a_time(grouped):
    args = _attention_operands(grouped)
    given = [a for a in args if a is not None]
    fill = lambda given: [None if a is None else given.pop(0) for a in args]
    got = _value_and_grads(
        lambda *g: lm_layers.causal_attention(*fill(list(g)), 0.25, 16).reshape(
            T, 4, 16), *given)
    want = _value_and_grads(
        lambda *g: _one_head_at_a_time(*fill(list(g)), 0.25), *given)
    _close(got, want)
    with pytest.raises(ValueError, match=r"attn_q_block=24 does not divide T=32 \(a full layer\)"):
        lm_layers.causal_attention(*args, 0.25, 24)
    with pytest.raises(ValueError, match=r"T=32 \(a window layer, window=8\)"):
        lm_layers.causal_attention(*args, 0.25, 24, 8)


def _under_a_dense_mask(q, k, v, scale, window):
    """Full ``[T, T]`` scores of one query head at a time under the dense
    mask ``t - window < j <= t``, its key head copied out for it."""
    t = q.shape[0]
    q = q.reshape(t, -1, q.shape[-1])
    per_key = q.shape[1] // k.shape[1]
    at = jnp.arange(t)
    seen = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - window)
    out = []
    for h in range(q.shape[1]):
        s = jnp.where(seen, q[:, h] @ k[:, h // per_key].T * scale, -jnp.inf)
        out.append(jax.nn.softmax(s, axis=-1) @ v[:, h // per_key])
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("t, q_block, window", [
    (32, 16, 8), (32, 16, 16), (32, 16, 20), (32, 8, 5), (24, 32, 7), (48, 16, 33),
    (32, 16, 1)])
def test_the_banded_body_is_attention_under_a_dense_window_mask(t, q_block, window):
    """Windows the query block divides, equals and does not divide, a length
    of one block, a window wider than two blocks and a window of one (a query
    sees itself: the output is its own value): output and gradients of the
    plain body cut to the band are a dense-mask softmax's, at a key head each
    and at a group of three query heads on one key head."""
    for q, k, v in ((_x(1, t, 2, 16), _x(2, t, 2, 16), _x(3, t, 2, 16)),
                    (_x(1, t, 1, 3, 16), _x(2, t, 1, 16), _x(3, t, 1, 16))):
        got = _value_and_grads(
            lambda q, k, v: lm_layers.causal_attention(
                q, None, k, None, v, 0.25, q_block, window).reshape(t, -1, 16), q, k, v)
        want = _value_and_grads(
            lambda q, k, v: _under_a_dense_mask(q, k, v, 0.25, window), q, k, v)
        _close(got, want)
        if window == 1:
            np.testing.assert_allclose(
                got[0], jnp.broadcast_to(
                    v.reshape(t, -1, 1, 16), (t, v.shape[1], got[0].shape[1] // v.shape[1], 16)
                ).reshape(t, -1, 16), rtol=1e-6)


def test_a_window_of_the_length_or_more_is_the_causal_body_bit_for_bit():
    args = _attention_operands(True)
    causal = jax.jit(lambda *a: lm_layers.causal_attention(
        a[0], None, a[1], None, a[2], 0.25, 16))(args[0], args[2], args[4])
    for window in (T, T + 1, 100 * T):
        wide = jax.jit(lambda *a: lm_layers.causal_attention(
            a[0], None, a[1], None, a[2], 0.25, 16, window))(args[0], args[2], args[4])
        np.testing.assert_array_equal(wide, causal)
    narrow = lm_layers.causal_attention(
        args[0], None, args[2], None, args[4], 0.25, 16, T - 1)
    assert float(jnp.max(jnp.abs(narrow[-1] - causal[-1]))) > 0  # the last query lost a key
    np.testing.assert_array_equal(narrow[:-1], causal[:-1])


@pytest.mark.parametrize("grouped", [False, True], ids=["joyai_llm_flash", "qwen3_next"])
def test_the_kernels_take_the_shapes_they_were_built_for_and_the_choice_is_counted(
        grouped, monkeypatch):
    """On a TPU (here: the test says so) at the kernels' block and lane
    widths, both models' operands go to the kernels: JoyAI's (a key head
    each, a rotary operand) and Qwen3-Next's (256-wide heads, a key head a
    group of eight, no rotary operand), counted as such; off a TPU both take
    the plain body."""
    t = ak.BLOCK
    if grouped:
        args = (_x(1, t, 2, 8, 256), None, _x(2, t, 2, 256), None, _x(3, t, 2, 256))
    else:
        args = (_x(1, t, 2, 128), _x(4, t, 2, 64), _x(2, t, 2, 128), _x(5, t, 64),
                _x(3, t, 2, 128))
    monkeypatch.setattr(ak, "_mode", lambda interpret: "mosaic")
    assert ak.takes(*args)
    # no rotary operand, a key head each: taken; more query heads than key
    # heads on one axis (no group axis to read them by): not
    assert ak.takes(_x(1, t, 2, 128), None, _x(2, t, 2, 128), None, _x(3, t, 2, 128))
    assert not ak.takes(_x(1, t, 4, 128), _x(4, t, 4, 64), _x(2, t, 2, 128),
                        _x(5, t, 64), _x(3, t, 2, 128))
    # a rotary operand on one side only, a length the blocks do not divide,
    # heads of part lanes
    assert not ak.takes(args[0], None if grouped else args[1], args[2],
                        _x(5, t, 64) if grouped else None, args[4])
    assert not ak.takes(*(None if a is None else a[:t - 128] for a in args))
    assert not ak.takes(*(None if a is None else a[..., :48] for a in args))
    count = lambda body: get_global_registry().counter(
        lm_layers.CORES_TRACED, labels={"body": body}).value
    for mode, body in (("mosaic", "kernel"), ("xla", "plain")):
        monkeypatch.setattr(ak, "_mode", lambda interpret, mode=mode: mode)
        before = count(body)
        out = jax.eval_shape(  # a function of its own each: traced each time
            lambda *a: lm_layers.attention_core(*a, 1 / 16, 256), *args)
        assert out.shape == args[0].shape[:-1] + (args[4].shape[-1],)
        assert count(body) == before + 1
