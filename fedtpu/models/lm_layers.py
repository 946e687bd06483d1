"""What the language models share (``joyai_llm_flash``, ``qwen3_next``,
``lfm2_moe``, ``laguna``, ``nemotron_h``, ``granite_hybrid``; the Mamba-2 mixer
the last two share is :mod:`fedtpu.models.mamba2`, beside this module): the
norm (with its statistic handed in where a share of the channels norms:
:func:`normed`), the plain layers, the rotate-half rotary
turn (plain or YaRN's frequencies), the causal depthwise convolution, one
sequence's causal softmax attention over the whole prefix or a window of it,
the grouped-query body around it, the expert layer with its routed experts'
path, a block's feed-forward half, the rematerialisation of a part, and the
decoder stack from the embedding to a row's head-and-loss. A model file holds
what the model alone has: its ``Sizes`` under the published config's key
names, its mixers, its router's scoring rule and the line that says which
layers are dense; what is here takes arrays and plain values, never a model's
``Sizes``, and names device time under ``fed.local_step.fwd_bwd.``.

- :func:`attention_core`: causal attention of one sequence by the body its
  shapes and the backend call for: the fused kernels of
  :mod:`fedtpu.ops.attention_kernels` (on a TPU: a length their blocks
  divide, head parts of whole lanes or all of half a lane group) or the
  plain query blocks of
  :func:`causal_attention`. Both are one function of every shape: a key head
  may serve a group of query heads (it is read by its group, never copied),
  and the rotary operands may be absent. A ``window`` (a query sees itself and
  the ``window - 1`` positions before it) is the plain body's alone: a query
  block cuts its keys to the band and masks both edges, so no score outside
  the band is formed.
- :func:`grouped_query_attention`: what every grouped-query softmax layer
  does between its projections and its output projection, whichever model's:
  the rotary rule handed in (its turns inside or outside the core's scope;
  none for a model without positions), the core a sequence at a time under
  the layer's ``.core`` scope at ``1 / sqrt(head)`` or the model's own scale,
  an optional sigmoid gate.
- :func:`routed_experts`: the (token, expert) pairs that fall on the HELD
  experts, sorted by expert and multiplied group by group, a chunk of
  ``chunk_pairs`` sorted pairs at a time: within a chunk each expert's pairs
  start at a boundary of ``block_rows`` rows, so every block has one expert,
  the used blocks first. The grouped product has two bodies, one function of
  the same operands, chosen by :func:`fedtpu.ops.expert_kernels.takes` from
  the backend and the shapes alone: on a TPU, at widths of a lane group or
  more in whole sublane tiles and blocks of whole sublane tiles (every
  published size: whole lanes, and Nemotron-H's 1,856 = 14.5 lane groups),
  the kernels of
  :mod:`fedtpu.ops.expert_kernels`, which read a block's weights in place
  through a prefetched block-to-expert map and skip the blocks no pair fell
  in, forward and backward; everywhere else (the CPU, the tiny test models'
  widths) a batched product over ALL the blocks, each with a copy of its
  expert's matrices picked by a one-hot product (not ``jax.lax.ragged_dot``
  in either: the chip's compiler turns that into kernels named
  ``ragged-dot-none``, which carry no scope of the program, and a capture
  would read the experts' time as ``_unscoped_``). Counted in the process's
  registry by the body taken, a product a stack of weights a layer traced
  (``fedtpu_expert_products_traced_total{body}``). The chunks are ONE loop
  that runs while pairs are left, under one differentiation rule whose
  backward pass is the same loop over the chunks' gradients (the first chunk
  nearly always holds every pair): the work follows the load and no pair is
  ever dropped. An expert has one of two forms, the layer's: gated, three
  matrices (``w_down (silu(w_gate u) * w_up u)``: a SwiGLU, four of the five
  models), or two with the activation handed in (``w_down act(w_up u)``:
  Nemotron-H's ``relu2``), and then two products a layer are counted and no
  stack stands where the second input matrix would be. Where a TPU run takes
  the plain body at a width of a lane group or more, one warning a process
  names the width.
- :class:`ExpertLayer`: router, held experts and shared expert under every
  model's parameter names; the model hands in its gate rule (its tail is
  :func:`top_k_gates`), what its shared expert is and the experts' form
  (:class:`SwiGLU`'s, or :class:`MLP`'s with its activation).
- :func:`feed_forward`: a feed-forward half, a dense SwiGLU or the expert
  layer, the second half of a block or a layer by itself;
  :func:`rematerialised`: the ONE place that says what a rematerialised part
  keeps.
- :class:`DecoderStack`: embedding, blocks, final norm, head and loss, made
  of :class:`Trunk`'s pieces, with a multiplier on the stream as it enters
  and a divisor of the logits as they leave where a model has them;
  :func:`register_language_model`.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
from typing import Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from fedtpu.models.registry import register
from fedtpu.obs.registry import get_global_registry
from fedtpu.ops import attention_kernels, expert_kernels
from fedtpu.ops.losses import next_token_ce_parts

SCOPE = "fed.local_step.fwd_bwd."
# What a rematerialised block keeps of its attention core: the output
# [T, heads, v] and, where the kernels run, the rows' log-sum-exp.
KEEP = attention_kernels.KEPT
CORES_TRACED = "fedtpu_attention_cores_traced_total"
# The same cores by body AND by what they attend over (kind = full | window):
# a series of its own, so that ``CORES_TRACED{body}`` stays what it was.
CORES_BY_KIND = "fedtpu_attention_cores_by_kind_total"
# An expert layer's three grouped products, by the body taken.
PRODUCTS_TRACED = "fedtpu_expert_products_traced_total"


def normed(xf, mean_square, scale, eps):
    """Float32 ``xf`` over the root of ``mean_square + eps``, times ``scale``:
    RMSNorm with its statistic handed in, float32 out. Whoever holds only a
    share of the channels a norm runs over hands in the mean over all of
    them (:class:`fedtpu.models.mamba2.Mamba2`)."""
    y = xf * jax.lax.rsqrt(mean_square + eps)
    return y * scale.astype(jnp.float32)


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    return normed(xf, jnp.mean(xf * xf, axis=-1, keepdims=True), scale,
                  eps).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(), (x.shape[-1],))
        return _rms(x, scale, self.eps)


class Linear(nn.Module):
    """``x @ kernel``, no bias."""

    features: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
            (x.shape[-1], self.features),
        )
        return jnp.dot(x, kernel.astype(x.dtype))


class SwiGLU(nn.Module):
    width: int

    @nn.compact
    def __call__(self, x):
        h = jax.nn.silu(Linear(self.width, name="gate")(x)) * Linear(
            self.width, name="up")(x)
        return Linear(x.shape[-1], name="down")(h)


class MLP(nn.Module):
    """SwiGLU's sibling of TWO matrices: ``down(activation(up(x)))``."""

    width: int
    activation: Callable

    @nn.compact
    def __call__(self, x):
        h = self.activation(Linear(self.width, name="up")(x))
        return Linear(x.shape[-1], name="down")(h)


def relu2(x):
    """``relu(x)^2`` (``mlp_hidden_act: relu2``)."""
    return jnp.square(jax.nn.relu(x))


def yarn_inv_freq(theta: float, rot: int, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's ``rot / 2`` inverse frequencies (``rope_type: yarn``): each a
    blend of the plain ``theta^(-2i/rot)`` and the same over ``factor``, by a
    linear ramp over the dimensions between the one that turns ``beta_fast``
    times in ``original_max`` positions (rounded down) and the one that turns
    ``beta_slow`` times (rounded up), clipped to ``[0, 1]``: the fast
    dimensions keep their frequency, the slow ones are stretched. A float32
    constant, computed on the host."""
    turns_at = lambda turns: rot * math.log(
        original_max / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), rot - 1)
    plain = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return np.asarray(plain / factor * ramp + plain * (1.0 - ramp), np.float32)


def rope_half(x, theta: float, rot: int, inv_freq=None, factor=None):
    """Rotary embedding on the first ``rot`` dimensions of the last axis of
    ``x [T, ..., d]`` in the rotate-half pairing: ``(x[i], x[i + rot/2])`` of
    position ``t`` turn by ``t * theta^(-2i/rot)``; the rest pass. With
    ``inv_freq [rot / 2]`` the pairs turn by ``t * inv_freq[i]`` instead
    (:func:`yarn_inv_freq`), and ``factor`` multiplies cos and sin (YaRN's
    ``attention_factor``)."""
    t, half = x.shape[0], rot // 2
    if inv_freq is None:
        inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor is not None:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, xf[..., rot:]], axis=-1
    ).astype(x.dtype)


def causal_conv(x, kernel, bias=None):
    """Depthwise causal convolution over time of ``x [T, channels]`` with
    ``kernel [width, channels]``: ``y_t = sum_i kernel_i x_{t - width + 1 +
    i}``, zeros before the row's start, plus ``bias [channels]`` where there
    is one (added to the float32 sum, every position's alike, the row's first
    too); float32 sums, the result in ``x.dtype``. One differentiation rule on
    every backend
    (:func:`_conv_rule`): forward and backward are each ONE pass of shifted
    slices over an operand that stays in its own dtype in memory (``x``
    forward, the cotangent backward: read once, written once), and what the
    backward pass keeps is ``x`` and the taps. ``jax.grad`` of the same sums
    writes a float32 copy of the padded operand and one of the cotangent a
    tap: six times the bytes at ``[8192, 8192]`` bfloat16 (PERF.md §6, PR 46).
    The taps (and the bias) enter the rule in float32, so their gradient
    leaves it in float32 and is rounded to ``kernel.dtype`` once, after
    ``vmap`` has summed it over rows."""
    return _conv_rule(x, kernel.astype(jnp.float32),
                      None if bias is None else bias.astype(jnp.float32))


def _shifted(x, width, lead):
    """The ``width`` slices ``x_{t - lead + i}`` of ``x [T, channels]`` in
    float32, ``x`` padded in its OWN dtype with ``lead`` zero rows in front
    and ``width - 1 - lead`` behind: a slice turns float32 where it is used,
    inside the fusion, so no float32 copy of the operand is written."""
    t = x.shape[0]
    padded = jnp.pad(x, ((lead, width - 1 - lead), (0, 0)))
    return [padded[i:i + t].astype(jnp.float32) for i in range(width)]


def _tap_sum(x, taps, lead):
    """``sum_i taps_i x_{t - lead + i}`` in float32."""
    return sum(rows * tap for rows, tap in zip(_shifted(x, taps.shape[0], lead), taps))


@jax.custom_vjp
def _conv_rule(x, taps, bias):
    y = _tap_sum(x, taps, taps.shape[0] - 1)
    return (y if bias is None else y + bias).astype(x.dtype)


def _conv_rule_fwd(x, taps, bias):
    return _conv_rule(x, taps, bias), (x, taps, bias)


def _conv_rule_bwd(kept, dy):
    """``d x_t = sum_i taps_i dy_{t + width - 1 - i}``: the taps reversed
    over the cotangent padded BEHIND, so the zeros before the row's start
    receive nothing. ``d taps_i = sum_t x_{t - width + 1 + i} dy_t``:
    ``width`` float32 reductions over time that read the padded ``x``. ``d
    bias = sum_t dy_t``, one more."""
    x, taps, bias = kept
    width = taps.shape[0]
    dx = _tap_sum(dy, taps[::-1], 0).astype(x.dtype)
    dy = dy.astype(jnp.float32)
    dtaps = jnp.stack([jnp.sum(rows * dy, axis=0) for rows in _shifted(x, width, width - 1)])
    return dx, dtaps, None if bias is None else jnp.sum(dy, axis=0)


_conv_rule.defvjp(_conv_rule_fwd, _conv_rule_bwd)


@functools.partial(jax.checkpoint, static_argnums=(5, 6, 7, 8))
def _attend_block(q_nope, q_rope, k_nope, k_rope, v, lo, hi, scale, window=None):
    """Queries ``[lo, hi)`` of a sequence against the keys up to ``hi``:
    float32 scores and softmax. ``q_nope [T, H, ..., d]``: whatever axes lie
    between a key head and the width are the query heads that read it;
    ``k_nope``, ``v [T, H, .]``. The rotary operands enter the scores as a
    second product (``k_rope [T, .]`` is every head's) or are ``None``. With a
    ``window`` the keys start at ``lo - window + 1`` (the first the block's
    first query sees) and the band's lower edge is masked too. The whole
    sequence comes in and is cut here, so that what the backward pass keeps of
    a block is the sequence itself and no copy of a prefix."""
    first = 0 if window is None else max(0, lo - window + 1)
    cut = lambda a, lo: None if a is None else a[lo:hi]
    q_nope, q_rope = cut(q_nope, lo), cut(q_rope, lo)
    k_nope, k_rope, v = cut(k_nope, first), cut(k_rope, first), cut(v, first)
    s = jnp.einsum("qh...d,khd->h...qk", q_nope, k_nope,
                   preferred_element_type=jnp.float32)
    if q_rope is not None:
        s = s + jnp.einsum("qh...d,kd->h...qk", q_rope, k_rope,
                           preferred_element_type=jnp.float32)
    if window is None:
        seen = jnp.arange(hi)[None, :] <= (lo + jnp.arange(hi - lo))[:, None]
    else:
        at, key = (lo + jnp.arange(hi - lo))[:, None], jnp.arange(first, hi)[None, :]
        seen = (key <= at) & (key > at - window)
    s = jnp.where(jnp.expand_dims(seen, tuple(range(s.ndim - 2))),
                  s * scale, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("h...qk,khd->qh...d", p.astype(v.dtype), v)


def causal_attention(q_nope, q_rope, k_nope, k_rope, v, scale, q_block,
                     window=None):
    """Causal attention of one sequence in query blocks (shapes as
    :func:`_attend_block` takes them), over the whole prefix or, with a
    ``window``, over a query's own position and the ``window - 1`` before."""
    t = q_nope.shape[0]
    qb = min(q_block, t)
    if t % qb:
        asked = ("a full layer" if window is None
                 else f"a window layer, window={window}")
        raise ValueError(
            f"attn_q_block={q_block} does not divide T={t} ({asked})")
    return jnp.concatenate([
        _attend_block(q_nope, q_rope, k_nope, k_rope, v, lo, lo + qb, scale,
                      window)
        for lo in range(0, t, qb)
    ], axis=0)


def attention_core(q_nope, q_rope, k_nope, k_rope, v, scale, q_block,
                   window=None):
    """One sequence's causal attention by the body its shapes and the backend
    call for: the fused kernels (:mod:`fedtpu.ops.attention_kernels`) or the
    plain query blocks above, one function of the same operands. The kernels
    refuse, and the plain body takes (``attention_kernels._fits`` is the same
    list): a ``window`` (their block pairs are the causal half: they would
    compute another function), a length their blocks do not divide, head
    parts that are no whole lane groups (but for queries, keys and values all
    of half a group WITHOUT rotary operands: a half-lane head beside rotary
    operands is refused), a rotary operand on one side only, more query heads
    than key heads without a group axis; and every call off a TPU. Counted in
    the process's registry by the body taken, once a core traced, and a
    second time by body and kind (``full`` | ``window``)."""
    kernel = attention_kernels.takes(
        q_nope, q_rope, k_nope, k_rope, v, window=window)
    body = "kernel" if kernel else "plain"
    registry = get_global_registry()
    registry.counter(
        CORES_TRACED, "attention cores traced, by the body taken",
        labels={"body": body}).inc()
    registry.counter(
        CORES_BY_KIND, "attention cores traced, by the body taken and by "
        "what they attend over (the whole prefix or a window)",
        labels={"body": body, "kind": "full" if window is None else "window"}
    ).inc()
    if kernel:
        return attention_kernels.causal_attention(
            q_nope, q_rope, k_nope, k_rope, v, scale)
    return checkpoint_name(causal_attention(
        q_nope, q_rope, k_nope, k_rope, v, scale, q_block, window), KEEP)


def grouped_query_attention(q, k, v, rotary, q_block, gate=None, window=None,
                            scope="attention", turn_in_core=True, scale=None):
    """A grouped-query softmax layer between its projections and its output
    projection: ``q [B, T, KH, G, hd]`` (key-value head ``j`` serves the ``G``
    query heads ``q[:, :, j]``), ``k``, ``v [B, T, KH, hd]``, already normed
    where the model norms them. A sequence at a time, under ``<scope>.core``:
    ``rotary`` (one sequence's ``[T, ..., hd]`` to the same, the model's own
    rule; ``None``: a model without positions, and nothing is turned) turns q
    and k, then :func:`attention_core` at ``scale`` (``None``: ``1 /
    sqrt(hd)``; Granite's is a config key).
    With ``turn_in_core=False`` the turns run under the caller's scope, and
    the core's holds scores, softmax and ``P v`` alone, which is what a core's
    roofline counts; the default keeps the hybrid's and LFM2's programs as
    they were lowered before they shared this body.
    ``gate`` (what broadcasts against ``[B, T, KH, G, hd]``: a number a head
    or a head's width of them) multiplies the output by its sigmoid, in
    float32. Returns ``[B, T, KH * G * hd]``, the heads side by side."""
    b, t, kh, group, hd = q.shape
    turn = rotary or (lambda a: a)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    def one_sequence(args):
        q, k, v = args
        if not turn_in_core:
            q, k = turn(q), turn(k)
        with jax.named_scope(SCOPE + scope + ".core"):
            if turn_in_core:
                q, k = turn(q), turn(k)
            return attention_core(q, None, k, None, v, scale, q_block, window)

    o = jax.lax.map(one_sequence, (q, k, v))  # [b, t, kh, group, hd]
    if gate is not None:
        o = (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
             ).astype(q.dtype)
    return o.reshape(b, t, kh * group * hd)


def sizes_from_keywords(cls, model: str, num_classes: int, sizes: dict):
    """``cls(vocab_size=num_classes, **sizes)`` for a model's ``Sizes``
    dataclass, lists from a JSON file as tuples; a keyword that is no field is
    refused with the fields' names."""
    fields = [f.name for f in dataclasses.fields(cls)]
    unknown = set(sizes) - set(fields)
    if unknown:
        raise ValueError(
            f"{model} has no size {sorted(unknown)}; the sizes are {fields}")
    return cls(vocab_size=num_classes, **{
        k: tuple(v) if isinstance(v, (list, tuple)) else v
        for k, v in sizes.items()})


def register_language_model(name: str, sizes_cls):
    """Decorates ``stack(sizes, remat) -> module`` into the registry's
    constructor of ``name``: ``num_classes`` is the vocabulary's rows held
    here, ``sizes`` any field of ``sizes_cls`` (the model's ``Sizes``)."""

    def constructor(stack):
        @register(name)
        def build(num_classes: int = sizes_cls.vocab_size, remat: bool = False,
                  **sizes) -> nn.Module:
            return stack(
                sizes_from_keywords(sizes_cls, name, num_classes, sizes), remat)

        return build

    return constructor


def held_range(held, total: int, name="experts_held", things="routed experts"):
    """A share ``held = (lo, hi)`` as a checked range of the ``total``
    ``things`` a layer has (the experts a router scores, a mixer's heads);
    ``None``: all of them. ``name``: the size that states it."""
    lo, hi = held or (0, total)
    if not 0 <= lo < hi <= total:
        raise ValueError(f"{name}={held} is no range of the {total} {things}")
    return int(lo), int(hi)


def _expert_init(key, shape, dtype=jnp.float32):
    """Stacked ``[experts, in, out]`` leaves: normal over the fan-in."""
    return jax.random.normal(key, shape, dtype) / math.sqrt(shape[1])


_PLAIN_WIDTHS_WARNED = set()


def _warn_of_plain_products(width: int):
    """One warning a process and width, at trace time, where a TPU run takes
    the plain grouped products at an expert width of a lane group or more
    (shapes the kernels refuse: a width that is no whole number of sublane
    tiles, weights of another dtype than the rows, a block of part tiles):
    the run is right and slower than its neighbours, and says so."""
    if (expert_kernels.on_a_tpu() and width >= expert_kernels.LANES
            and width not in _PLAIN_WIDTHS_WARNED):
        _PLAIN_WIDTHS_WARNED.add(width)
        logging.getLogger(__name__).warning(
            "expert layer of width %d: the held experts' grouped products "
            "take the plain batched body on this TPU (fedtpu.ops."
            "expert_kernels takes widths of %d lanes or more in whole sublane "
            "tiles, rows and weights of one dtype and blocks of whole sublane "
            "tiles)",
            width, expert_kernels.LANES)


def routed_experts(xf, shared, gates_here, picked_here, weights, per_token,
                   chunk_pairs, block_rows, activation=None):
    """An expert layer's sum: ``shared [n, d]`` (what every chip computes
    alike, the model's own; ``None`` where the model has no such part, and
    nothing stands in for it) plus the held experts' part (module docstring).
    ``xf [n, d]`` tokens; ``gates_here``, ``picked_here [n, held]``: each
    token's gate for each held expert (0 where not chosen) and whether it was
    chosen; ``per_token``: the most experts a token picks. ``weights``: the
    held experts' stacks in one of the two forms an expert has. Gated, three
    matrices, ``(w_gate, w_up [held, d, width], w_down [held, width, d])``
    with ``activation`` ``None``: ``w_down (silu(w_gate u) * w_up u)``. Plain,
    two, ``(w_up, w_down)`` with the ``activation`` handed in: ``w_down
    activation(w_up u)``; no stack stands where the second input matrix would
    be. Returns ``(y [n, d], pairs, load)``: the sum (added in float32), the
    pairs computed here and the busiest held expert's load over the held
    experts' mean load."""
    if len(weights) != (3 if activation is None else 2):
        raise ValueError(
            f"{len(weights)} stacks of expert weights: a gated expert has "
            "three (activation=None), one with an activation handed in two")
    w_up, w_down = weights[-2:]
    n, d = xf.shape
    held = gates_here.shape[1]
    with jax.named_scope(SCOPE + "moe.dispatch"):
        # Pair p = token * held + expert. Sorted by expert (then token),
        # the pairs on held experts first, the rest behind them.
        key = jnp.where(picked_here, jnp.arange(held)[None, :], held)
        order = jnp.argsort(key.reshape(-1), stable=True).astype(jnp.int32)
        counts = jnp.sum(picked_here, axis=0, dtype=jnp.int32)  # [held]
        ends = jnp.cumsum(counts)
        starts, pairs = ends - counts, ends[-1]

    # No token has more than ``per_token`` pairs, and the pairs that exist
    # come first: chunks for that many, not for every (token, held expert).
    most = n * min(per_token, held)
    chunk = min(chunk_pairs, most)
    n_chunks = -(-most // chunk)
    order = jnp.pad(order, (0, max(0, n_chunks * chunk - n * held)))
    flat_gates = gates_here.reshape(-1)

    block = min(block_rows, chunk)
    if chunk % block:
        raise ValueError(
            f"moe_block_rows={block_rows} does not divide the chunk "
            f"of {chunk} pairs")
    n_blocks = chunk // block + held  # every expert may end in a part block

    kernel = expert_kernels.takes(
        jax.ShapeDtypeStruct((n_blocks * block, d), xf.dtype), w_up, block
    ) and expert_kernels.takes(
        jax.ShapeDtypeStruct((n_blocks * block, w_down.shape[1]), xf.dtype),
        w_down, block)
    get_global_registry().counter(
        PRODUCTS_TRACED, "expert layers' grouped products traced (one a "
        "stack of weights: three a gated layer, two a plain one), by the "
        "body taken",
        labels={"body": "kernel" if kernel else "plain"}).inc(len(weights))
    if not kernel:
        _warn_of_plain_products(w_down.shape[1])

    def one_chunk(base, order, starts, ends, xf, flat_gates, *weights):
        """Sorted pairs ``[base, base + chunk)`` through their experts:
        ``(gated outputs [rows, d] float32, their tokens [rows])``. Each
        expert's pairs are laid out from a block boundary on, so a block of
        ``block`` rows has ONE expert, the used blocks first; rows past an
        expert's last pair are zeros. The grouped product is the kernels'
        (:mod:`fedtpu.ops.expert_kernels`: a block's weights read in place,
        the unused blocks skipped) or, off a TPU and at widths they refuse, a
        batched one over every block with a copy of its expert's weights."""
        with jax.named_scope(SCOPE + "moe.dispatch"):
            sizes = jnp.clip(
                jnp.minimum(ends, base + chunk) - jnp.maximum(starts, base),
                0, None)  # each expert's pairs in this chunk
            blocks = (sizes + block - 1) // block
            last = jnp.cumsum(blocks)
            expert = jnp.searchsorted(last, jnp.arange(n_blocks), side="right")
            used = expert < held
            expert = jnp.minimum(expert, held - 1)
            within = ((jnp.arange(n_blocks) - (last - blocks)[expert]) * block
                      )[:, None] + jnp.arange(block)[None, :]
            live = (used[:, None] & (within < sizes[expert][:, None])).reshape(-1)
            at = (jnp.cumsum(sizes) - sizes)[expert][:, None] + within
            src = jax.lax.dynamic_slice(order, (base,), (chunk,))[
                jnp.where(live, at.reshape(-1), 0)]
            token = src // held
            rows = jnp.where(live[:, None], xf[token], 0)  # [blocks x block, d]
            if kernel:
                product = functools.partial(
                    expert_kernels.grouped_product, expert=expert,
                    live_blocks=last[-1], block=block)
            else:
                pick = jax.nn.one_hot(expert, held, dtype=rows.dtype)

                def product(x, w, out_dtype=None):
                    return jnp.einsum(
                        "bri,bio->bro", x.reshape(n_blocks, block, -1),
                        jnp.einsum("be,eio->bio", pick, w.astype(x.dtype)),
                        preferred_element_type=out_dtype,
                    ).reshape(n_blocks * block, -1)
        with jax.named_scope(SCOPE + "moe.experts"):
            if activation is None:
                w_gate, w_up, w_down = weights
                hidden = jax.nn.silu(product(rows, w_gate)) * product(rows, w_up)
            else:
                w_up, w_down = weights
                hidden = activation(product(rows, w_up))
            out = product(hidden, w_down, out_dtype=jnp.float32)
        with jax.named_scope(SCOPE + "moe.combine"):
            gate = jnp.where(live, flat_gates[src], 0.0)
            return out * gate[:, None], token

    def chunk_by_chunk(pairs, first, and_chunk):
        """``first`` (what the chunk at 0 gave) and, while pairs are left,
        ``and_chunk(so far, base)`` for the chunks behind it: the first
        nearly always holds them all, the loop is there so that nothing is
        ever dropped."""
        return jax.lax.while_loop(
            lambda c: c[1] < pairs,
            lambda c: (and_chunk(c[0], c[1]), c[1] + chunk),
            (first, jnp.int32(chunk)))[0]

    # The chunks under ONE differentiation rule, the loops written out in
    # both directions: a loop whose length the pairs decide has no reverse
    # rule of jax's own, and a chain of ``cond``s (one a chunk the layout
    # allows) hands every operand through each branch as an output and fills
    # a skipped chunk's gradients with zeros, 200 MB a chunk at the published
    # sizes. The backward pass makes a chunk's rows and hidden rows again
    # (nothing block-sized is kept: the residuals are the operands).
    @jax.custom_vjp
    def chunks(ints, *operands):
        order, starts, ends, pairs = ints

        def and_chunk(routed, base):
            out, token = one_chunk(base, order, starts, ends, *operands)
            with jax.named_scope(SCOPE + "moe.combine"):
                return routed.at[token].add(out)

        return chunk_by_chunk(
            pairs, and_chunk(jnp.zeros((n, d), jnp.float32), jnp.int32(0)),
            and_chunk)

    def chunks_fwd(ints, *operands):
        return chunks(ints, *operands), (ints, operands)

    def chunks_bwd(kept, d_routed):
        (order, starts, ends, pairs), operands = kept

        def of_chunk(base):
            _, vjp, token = jax.vjp(
                lambda *operands: one_chunk(base, order, starts, ends, *operands),
                *operands, has_aux=True)
            with jax.named_scope(SCOPE + "moe.combine"):
                d_out = d_routed[token]  # the scatter-add, transposed
            return vjp(d_out)

        with jax.named_scope(SCOPE + "moe"):
            return (None,) + chunk_by_chunk(
                pairs, of_chunk(jnp.int32(0)),
                lambda so_far, base: jax.tree.map(jnp.add, so_far, of_chunk(base)))

    chunks.defvjp(chunks_fwd, chunks_bwd)
    routed = chunks((order, starts, ends, pairs), xf, flat_gates, *weights)
    with jax.named_scope(SCOPE + "moe.combine"):
        if shared is not None:
            routed = shared.astype(jnp.float32) + routed
        y = routed.astype(xf.dtype)
    load = jnp.max(counts) * held / jnp.maximum(pairs, 1)
    return y, pairs, load.astype(jnp.float32)


def top_k_gates(scores, k: int, bias=None, scale=None, eps=None):
    """The tail every model's gate rule ends in, from a token's float32
    ``scores [n, routed]`` over ALL the routed experts (what the model's own
    rule made of the logits: a sigmoid, a softmax) to ``(gates, picked) [n,
    routed]``: chosen = the ``k`` largest of ``scores + bias`` (``bias
    [routed]`` shifts choices and nothing else; a tie goes to the lower
    index); a chosen expert's gate is ``scale`` times its score over the sum
    of the token's chosen scores plus ``eps``, and 0 where not chosen. What is
    ``None`` is not traced: no ``+ 0.0``, no ``* 1.0``."""
    _, chosen = jax.lax.top_k(scores if bias is None else scores + bias, k)
    picked = (chosen[:, :, None] == jnp.arange(scores.shape[-1])).any(1)
    s_picked = jnp.where(picked, scores, 0.0)
    scaled = s_picked if scale is None else scale * s_picked
    total = jnp.sum(s_picked, axis=-1, keepdims=True)
    return scaled / (total if eps is None else total + eps), picked


class ExpertLayer(nn.Module):
    """A shared expert, where the model has one, plus this chip's share of
    the routed experts. Returns ``(y, pairs, load)``: the pairs computed here
    and the busiest held expert's load over the held experts' mean load.

    ``held = (lo, hi)``: the range of the ``routed`` experts that lives here;
    ``k``: experts a token picks; ``width``: a routed expert's. ``gate_rule(
    logits [n, routed] float32, k) -> (gates, picked)``: the model's own, a
    layer's selection bias in its closure. ``shared_width``: the shared
    expert's (0: none, and nothing stands in for it); ``shared_gated``:
    behind ``sigmoid(w_s . x)``, a number a token. ``activation``: the form
    of every expert here, routed and shared alike. ``None``: gated, a SwiGLU
    of three matrices (``experts_gate``, ``experts_up``, ``experts_down``;
    the shared one a :class:`SwiGLU`). A function: two matrices with it
    between them (``experts_up``, ``experts_down``; the shared one an
    :class:`MLP`), and no parameter where the second input matrix would
    be."""

    routed: int
    held: Tuple[int, int]
    k: int
    width: int
    chunk_pairs: int
    block_rows: int
    gate_rule: Callable
    shared_width: int = 0
    shared_gated: bool = False
    activation: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        lo, hi = self.held
        held, d, width = hi - lo, x.shape[-1], self.width
        gated = self.activation is None
        xf = x.reshape(-1, d)
        shared = None
        if self.shared_width:
            shared = (SwiGLU(self.shared_width, name="shared") if gated else MLP(
                self.shared_width, self.activation, name="shared"))(xf)
            if self.shared_gated:
                opened = jax.nn.sigmoid(
                    Linear(1, name="shared_gate")(xf).astype(jnp.float32))
                shared = (opened * shared.astype(jnp.float32)).astype(x.dtype)
        router = self.param(
            "router", nn.initializers.variance_scaling(2.0, "fan_in", "normal"),
            (d, self.routed))
        weights = tuple(
            self.param("experts_" + name, _expert_init,
                       (held, width, d) if name == "down" else (held, d, width))
            for name in (("gate", "up", "down") if gated else ("up", "down")))

        with jax.named_scope(SCOPE + "moe.router"):
            # Float32 out of the accumulator: exact products of the compute
            # dtype's operands, summed in float32.
            gates, picked = self.gate_rule(jnp.dot(
                xf, router.astype(xf.dtype),
                preferred_element_type=jnp.float32), self.k)
            # Held experts are a range: a token's gates for them are a slice.
            gates_here = gates[:, lo:hi]  # [n, held], 0 where not chosen
            picked_here = picked[:, lo:hi]

        y, pairs, load = routed_experts(
            xf, shared, gates_here, picked_here, weights, self.k,
            self.chunk_pairs, self.block_rows, self.activation)
        return y.reshape(x.shape), pairs, load


def rematerialised(cls, remat: bool = True):
    """``cls`` (a module class), its forward pass made again in the backward
    pass but for what the attention cores name (``KEEP``: no backward pass
    runs a core's forward kernel again); ``cls`` itself without ``remat``. The
    model says what: JoyAI a whole block, the others a block's halves."""
    return nn.remat(
        cls, policy=jax.checkpoint_policies.save_only_these_names(KEEP)
    ) if remat else cls


def no_pairs():
    """``(pairs, load)`` of a half that routes nothing: a dense feed-forward,
    a mixer that is a layer by itself."""
    return jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)


def feed_forward(x, remat: bool, experts, dense=None):
    """A feed-forward half on the normed ``x``, called inside the block, be
    the block two halves or this one alone: ``(y, pairs, load)``. ``dense =
    (parameter name, width)``: a SwiGLU under ``dense_ffn``, no pairs, no
    load; ``None``: under ``moe`` the :class:`ExpertLayer` of the fields
    ``experts`` (a dict). ``remat``: rematerialised by itself."""
    if dense is not None:
        name, width = dense
        with jax.named_scope(SCOPE + "dense_ffn"):
            y = rematerialised(SwiGLU, remat)(width, name=name)(x)
        return (y,) + no_pairs()
    with jax.named_scope(SCOPE + "moe"):
        return rematerialised(ExpertLayer, remat)(**experts, name="moe")(x)


def _logits(h, scale, kernel, eps, logits_scaling):
    """The final norm and the head: float32 logits, over ``logits_scaling``
    where the model has one (``None``: no operation)."""
    logits = jnp.dot(_rms(h, scale, eps), kernel.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    return logits if logits_scaling is None else logits / logits_scaling


@functools.partial(jax.checkpoint, static_argnums=(4, 5))
def _row_loss_parts(h, targets, scale, kernel, eps, logits_scaling=None):
    """One row's final norm, head and cross-entropy, ``(sum, count, hits)``;
    the row's float32 logits ``[T, vocab]`` are made again in the backward
    pass, so that no step holds a whole batch of them."""
    with jax.named_scope(SCOPE + "lm_loss"):
        return next_token_ce_parts(
            _logits(h, scale, kernel, eps, logits_scaling), targets)


class Trunk:
    """A :class:`DecoderStack`'s call on ``tokens [B, T]`` as far as the last
    block (``h``: its output, before the final norm), and the pieces the rest
    is made of. Built inside ``stack``'s compact ``__call__``: parameters,
    blocks (``layer_<i>``) and counters are that module's."""

    def __init__(self, stack, tokens):
        self.stack = stack
        self.embed = nn.Embed(stack.vocab_size, stack.hidden_size, name="embed",
                              embedding_init=stack.embedding_init)
        offset, ones = stack.final_norm_offset, nn.initializers.ones_init()
        weight = stack.param(
            "final_norm", ones if offset is None else nn.initializers.zeros_init(),
            (stack.hidden_size,))
        self.norm_scale = weight if offset is None else offset + weight
        if not stack.tied_head:
            self.head = stack.param(
                "head", nn.initializers.variance_scaling(0.02, "fan_in", "normal"),
                (stack.hidden_size, stack.vocab_size))
        with jax.named_scope(SCOPE + "embed"):
            h = self.embed(tokens)
            if stack.embedding_multiplier is not None:
                h = h * stack.embedding_multiplier
        if stack.tied_head:
            self.head = self.embed.embedding.T
        self.pairs, self.loads = [], []
        for i, block in enumerate(stack.blocks):
            h = self.run(block(name=f"layer_{i}"), h)
        self.h = h

    def run(self, block, h):
        """``block(h)``'s stream, its pairs and its load counted."""
        h, pairs, load = block(h)
        self.pairs.append(pairs)
        self.loads.append(load)
        return h

    def logits(self):
        """Evaluation: the next-token logits ``[B, T, vocab]`` in float32."""
        with jax.named_scope(SCOPE + "lm_loss"):
            return _logits(self.h, self.norm_scale, self.head, self.stack.eps,
                           self.stack.logits_scaling)

    def head_rows(self, h, targets):
        """The final norm, the head and the cross-entropy of ``h`` against
        ``targets [B, T]``, a row at a time: ``(sum, count, hits)``, each
        ``[B]``."""
        return jax.lax.map(
            lambda a: _row_loss_parts(
                a[0], a[1], self.norm_scale, self.head, self.stack.eps,
                self.stack.logits_scaling),
            (h, targets))

    def sow(self):
        """The ``counters`` collection: the pairs every block run so far
        computed here, and the worst of their loads."""
        self.stack.sow("counters", "moe_pairs_here", sum(self.pairs),
                       reduce_fn=lambda _, x: x, init_fn=lambda: 0)
        self.stack.sow("counters", "moe_load_max_over_mean",
                       functools.reduce(jnp.maximum, self.loads),
                       reduce_fn=lambda _, x: x, init_fn=lambda: 0)


def head_sums(rows):
    """One head's ``(cross-entropy sum, count, hits)`` of its rows'."""
    return tuple(jnp.sum(p) for p in rows)


class DecoderStack(nn.Module):
    """Embedding, blocks, final norm, head. ``blocks``: a constructor a layer,
    in order, each called with the block's ``name``; a block maps the stream
    to ``(stream, pairs, load)``, be it two halves (a mixer, then a
    feed-forward) or ONE (Nemotron-H: a mixer or a feed-forward alone, with
    one norm; :func:`no_pairs` for a half that routes nothing).
    ``tied_head``: the head is the embedding's
    transpose, else a parameter of its own. ``final_norm_offset``: ``None``
    for a scale that enters as it is, from ones; a number for a scale of that
    number plus a weight from zero (the hybrid's ``1 + w``).
    ``embedding_multiplier`` multiplies the stream as it enters and
    ``logits_scaling`` divides the logits as they leave (Granite's two; the
    tied head between them takes a gradient through both); ``None``: no
    operation is emitted."""

    vocab_size: int
    hidden_size: int
    eps: float
    blocks: Tuple[Callable[..., nn.Module], ...]
    tied_head: bool = False
    embedding_init: Callable = nn.initializers.normal(1.0)
    final_norm_offset: Optional[float] = None
    embedding_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None

    @nn.compact
    def __call__(self, tokens, train: bool = False, targets=None):
        """``tokens [B, T]`` int ids. In evaluation the next-token logits
        ``[B, T, vocab]`` in float32. In training, with ``targets [B, T]``
        (the next ids, negative where there is none), ``((cross-entropy sum,
        count, hits),)``: one head."""
        trunk = Trunk(self, tokens)
        if not train:
            return trunk.logits()
        rows = trunk.head_rows(trunk.h, targets)
        trunk.sow()
        return (head_sums(rows),)
