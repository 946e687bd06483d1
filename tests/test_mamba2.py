"""Mamba-2's mixer as the two models that have one share it
(``fedtpu/models/mamba2.py``; ``nemotron_h`` and ``granite_hybrid``), at small
sizes on the CPU. ``selective_scan`` against the token-at-a-time rule, values
and gradients (these two tests stood in ``tests/test_nemotron_h.py`` until the
mixer left that model's file in PR 51: the same cases, case for case); what a
share of the heads is refused for; the one warning a process where a TPU run
takes the plain chunks at shapes the kernels would take but for the chunk or
the length. The layers against their references are in
``tests/test_nemotron_h.py`` and ``tests/test_granite_hybrid.py``; the kernels
against the plain chunks in ``tests/test_ssd_kernels.py``.
"""

import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fedtpu.models import mamba2 as prog
from fedtpu.obs.registry import get_global_registry


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


# ------------------------------------------------------------- the recurrence
def _token_at_a_time(x, dt, a, b, c, skip):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``; ``y_t = S_t C_t + D
    x_t``, a head's group read by its index, written here and not borrowed."""
    t, heads, p = x.shape
    per_group = heads // b.shape[1]

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        b_h, c_h = (jnp.repeat(v, per_group, axis=0) for v in (b_t, c_t))
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, jnp.sum(state * c_h[:, None, :], -1) + skip[:, None] * x_t

    return jax.lax.scan(
        token, jnp.zeros((heads, p, b.shape[-1]), jnp.float32), (x, dt, b, c))[1]


def _scan_operands(t, heads=8, p=8, groups=2, n=16):
    dt = jax.nn.softplus(3.0 * _x(2, t, heads) - 2.0)  # 0.001 to 7: slow and fast heads
    return (_x(1, t, heads, p), dt, -jnp.exp(_x(3, heads)), _x(4, t, groups, n),
            _x(5, t, groups, n), _x(6, heads))


@pytest.mark.parametrize("t,chunk", [(32, 12), (32, 16), (37, 8), (24, 64), (9, 128)])
def test_the_chunked_scan_is_the_rule_token_by_token(t, chunk):
    """Values and all six gradients in float32, at heads that share groups
    (8 on 2), chunks that do and do not divide the length and one longer than
    the row. 2e-5: float32 sums in another order (a chunk's decay is a
    difference of running sums where the rule multiplies step by step)."""
    operands = _scan_operands(t)
    before = get_global_registry().counter(
        prog.SSD_CORES_TRACED, labels={"body": "plain"}).value
    ours = _value_and_grads(
        lambda *a: prog.selective_scan(*a, chunk), *operands)
    theirs = _value_and_grads(_token_at_a_time, *operands)
    assert ours[0].shape == (t, 8, 8)
    assert _rel(ours, theirs) <= 2e-5
    assert get_global_registry().counter(
        prog.SSD_CORES_TRACED, labels={"body": "plain"}).value > before


def test_the_scan_carries_a_state_that_outlives_its_chunks():
    """Heads that forget slowly (``dt A`` of -0.004 a step) see the FIRST
    token at the last, chunks later: the output's gradient with respect to
    ``x_0`` is nothing near zero there, and equals the rule's; no exponent is
    positive (steps of 40 do not overflow)."""
    t, chunk = 40, 8
    x, dt, a, b, c, skip = _scan_operands(t)
    dt = jnp.full_like(dt, 0.004)
    a = -jnp.ones_like(a)
    last = lambda f: jax.grad(lambda x: jnp.sum(f(x, dt, a, b, c, skip)[-1]))(x)[0]
    ours = last(lambda *o: prog.selective_scan(*o, chunk))
    theirs = last(_token_at_a_time)
    assert float(jnp.abs(theirs).max()) > 1e-3
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-7)
    y = prog.selective_scan(x, jnp.full_like(dt, 40.0), a, b, c, skip, chunk)
    assert bool(jnp.isfinite(y).all())
    with pytest.raises(ValueError, match="no multiple"):
        prog.selective_scan(x[:, :7], dt[:, :7], a[:7], b, c, skip[:7], chunk)


# ------------------------------------------------------------------- the mixer
def _mixer(**over):
    fields = dict(heads=8, head_dim=8, groups=1, state=16, conv_kernel=4,
                  chunk=12, eps=1e-5)
    fields.update(over)
    return prog.Mamba2(**fields)


def test_a_share_of_the_heads_is_a_range_of_one_groups_heads():
    """``heads_held`` is a range of the layer's heads, and is built where ONE
    group's ``B`` and ``C`` are what every chip computes alike; a share holds
    its heads' columns of ``z``, ``x`` and ``dt`` and all of ``B`` and ``C``."""
    x = _x(1, 1, 16, 32)
    shapes = lambda m: jax.tree.map(
        lambda l: l.shape, jax.eval_shape(m.init, jax.random.PRNGKey(0), x)["params"])
    whole, share = shapes(_mixer()), shapes(_mixer(heads_held=(2, 5)))
    assert whole["in_proj"]["kernel"] == (32, 64 + 64 + 32 + 8)
    assert share["in_proj"]["kernel"] == (32, 24 + 24 + 32 + 3)
    assert share["conv"] == (4, 24 + 32) and share["conv_bias"] == (24 + 32,)
    assert share["dt_bias"] == share["A_log"] == share["D"] == (3,)
    assert share["norm"] == (24,) and share["out_proj"]["kernel"] == (24, 32)
    for held in ((0, 9), (5, 5), (-1, 4)):
        with pytest.raises(ValueError, match="no range"):
            shapes(_mixer(heads_held=held))
    with pytest.raises(ValueError, match="ONE group"):
        shapes(_mixer(groups=2, heads_held=(0, 4)))
    assert shapes(_mixer(groups=2, heads_held=(0, 8)))["norm"] == (64,)  # all: any groups


def test_a_plain_scan_on_a_tpu_is_said_once_a_chunk_and_length(monkeypatch, caplog):
    """Where the backend is a TPU (the test says so where the program asks)
    and the heads and the state are whole lanes, a chunk of 256 (Granite's
    published one) or a length the kernels' chunk does not divide takes the
    plain chunks, and ONE warning a process names the chunk and the length;
    the kernels' own shapes, the CPU, heads of part lanes (the tiny twins) and
    the eight tokens a model is initialised on say nothing."""
    from fedtpu.ops import ssd_kernels as sk

    def trace(t, chunk, heads=2, p=64, n=128):
        s = jax.ShapeDtypeStruct
        return jax.eval_shape(
            lambda *a: prog.selective_scan(*a, chunk),
            s((t, heads, p), jnp.bfloat16), s((t, heads), jnp.float32),
            s((heads,), jnp.float32), s((t, 1, n), jnp.bfloat16),
            s((t, 1, n), jnp.bfloat16), s((heads,), jnp.float32))

    counted = lambda body: get_global_registry().counter(
        prog.SSD_CORES_TRACED, labels={"body": body}).value
    monkeypatch.setattr(prog, "_PLAIN_SCANS_WARNED", set())
    with caplog.at_level(logging.WARNING, logger=prog.__name__):
        trace(512, 256)  # the CPU: silent
        assert not caplog.records
        monkeypatch.setattr(sk, "_mode", lambda interpret: "mosaic")
        before = counted("kernel"), counted("plain")
        assert trace(512, 128).shape == (512, 2, 64)  # the kernels' own
        assert (counted("kernel"), counted("plain")) == (before[0] + 1, before[1])
        trace(8, 128)  # a model's initialisation
        trace(512, 256, heads=8, p=8, n=16)  # a tiny twin's widths
        assert not caplog.records
        trace(512, 256)
        trace(512, 256)  # a second layer of the same chunk and length
        assert counted("plain") == before[1] + 4
        said = [r.getMessage() for r in caplog.records]
        assert len(said) == 1 and "256" in said[0] and "512" in said[0]
        assert "plain" in said[0]
        trace(500, 128)  # the kernels' chunk, a length it does not divide
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 2 and "500" in said[1] and "128" in said[1]
