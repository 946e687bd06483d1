"""Update-compression codecs (fedtpu.ops) — the ``-c Y`` parity path.

Covers: top-k sparsity level, int8 quantization error bound, the
mass-conservation property of error feedback (compressed + residual ==
input + previous residual), the Pallas kernels vs a plain-jnp oracle, and a
full round step running with compression enabled (residuals carried in
FederatedState.comp_state).
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu import models
from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
from fedtpu.core import round as round_lib
from fedtpu.ops import compression, pallas_kernels as pk


def tree_of_deltas(rng, n=4):
    return {
        "w": jnp.asarray(rng.normal(size=(n, 16, 32)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(n, 32)).astype(np.float32)),
    }


# --------------------------------------------------------------- pallas units
def test_threshold_kernel_matches_oracle(rng):
    y = jnp.asarray(rng.normal(size=(3, 1000)).astype(np.float32))
    t = jnp.asarray([0.5, 1.0, 2.0], jnp.float32)
    # interpret=True forces the actual pallas_call body (the off-TPU default
    # is the plain-jnp equivalent); both paths are checked against the oracle.
    for kw in ({"interpret": True}, {}):
        out, new_e = pk.threshold_with_feedback(y, t, **kw)
        yn = np.asarray(y)
        keep = np.abs(yn) >= np.asarray(t)[:, None]
        np.testing.assert_allclose(np.asarray(out), yn * keep, atol=1e-6)
        np.testing.assert_allclose(np.asarray(new_e), yn * ~keep, atol=1e-6)


def test_quantdequant_kernel_matches_oracle(rng):
    x = jnp.asarray(rng.normal(size=(2, 513)).astype(np.float32))
    scale = jnp.max(jnp.abs(x), axis=1) / 127.0
    for kw in ({"interpret": True}, {}):
        out = pk.quantdequant_int8(x, scale, **kw)
        s = np.asarray(scale)[:, None]
        expected = np.clip(np.round(np.asarray(x) / s), -127, 127) * s
        np.testing.assert_allclose(np.asarray(out), expected, atol=1e-6)


def test_quantdequant_zero_leaf_is_safe():
    x = jnp.zeros((2, 64), jnp.float32)
    for kw in ({"interpret": True}, {}):
        out = pk.quantdequant_int8(x, jnp.zeros((2,), jnp.float32), **kw)
        assert np.all(np.isfinite(np.asarray(out)))
        np.testing.assert_allclose(np.asarray(out), 0.0)


# -------------------------------------------------------------------- codecs
def test_topk_sparsity_level(rng):
    deltas = tree_of_deltas(rng)
    comp = compression.make_topk(fraction=0.1, error_feedback=False)
    out, _ = comp.apply(deltas, {})
    frac = float(compression.nnz_fraction(out))
    # >= because ties keep extras; <= 2x because random gaussians rarely tie.
    assert 0.05 <= frac <= 0.2
    # Every kept entry must be at least as large as every dropped entry, per
    # client per leaf.
    for name in ("w", "b"):
        o = np.asarray(out[name]).reshape(4, -1)
        d = np.asarray(deltas[name]).reshape(4, -1)
        for c in range(4):
            kept = np.abs(d[c][o[c] != 0])
            dropped = np.abs(d[c][o[c] == 0])
            if len(kept) and len(dropped):
                assert kept.min() >= dropped.max() - 1e-6


def test_error_feedback_mass_conservation(rng):
    """compressed + new_residual == delta + old_residual, exactly."""
    deltas = tree_of_deltas(rng)
    comp = compression.make_topk(fraction=0.05, error_feedback=True)
    state = comp.init({k: v[0] for k, v in deltas.items()}, 4)
    # Seed nonzero residuals to exercise the carry.
    state = jax.tree.map(lambda e: e + 0.01, state)
    out, new_state = comp.apply(deltas, state)
    for k in deltas:
        lhs = np.asarray(out[k]) + np.asarray(new_state[k]).reshape(out[k].shape)
        rhs = np.asarray(deltas[k]) + 0.01
        np.testing.assert_allclose(lhs, rhs, atol=1e-5)


def test_error_feedback_recovers_dropped_mass(rng):
    """A constant delta stream through an aggressive top-k: with error
    feedback the cumulative compressed output tracks the cumulative input
    (residual stays bounded), so nothing is permanently lost."""
    comp = compression.make_topk(fraction=0.25, error_feedback=True)
    delta = {"w": jnp.asarray(rng.normal(size=(2, 64)).astype(np.float32))}
    state = comp.init({"w": delta["w"][0]}, 2)
    total_out = jax.tree.map(jnp.zeros_like, delta)
    rounds = 12
    for _ in range(rounds):
        out, state = comp.apply(delta, state)
        total_out = jax.tree.map(jnp.add, total_out, out)
    # total_in - total_out == final residual -> relative gap shrinks with T.
    gap = np.abs(
        rounds * np.asarray(delta["w"]) - np.asarray(total_out["w"])
    ).max()
    per_round = np.abs(np.asarray(delta["w"])).max()
    assert gap <= 4 * per_round  # residual bounded, not growing with rounds


def test_int8_error_bound(rng):
    deltas = tree_of_deltas(rng)
    comp = compression.make_int8(error_feedback=False)
    out, _ = comp.apply(deltas, {})
    for k in deltas:
        d = np.asarray(deltas[k]).reshape(4, -1)
        o = np.asarray(out[k]).reshape(4, -1)
        scale = np.abs(d).max(axis=1, keepdims=True) / 127.0
        assert np.all(np.abs(d - o) <= scale / 2 + 1e-7)


def test_make_compressor_dispatch():
    assert compression.make_compressor(FedConfig(compression="none")) is None
    assert compression.make_compressor(FedConfig(compression="topk")) is not None
    assert compression.make_compressor(FedConfig(compression="int8")) is not None
    with pytest.raises(ValueError):
        compression.make_compressor(FedConfig(compression="huffman"))
    # Sketch codecs are flat-layout only.
    for kind in ("rotq", "randk"):
        comp = compression.make_compressor(
            FedConfig(compression=kind, delta_layout="flat")
        )
        assert comp is not None and comp.layout == "flat"
        with pytest.raises(ValueError):
            compression.make_compressor(FedConfig(compression=kind))
    assert compression.make_compressor(
        FedConfig(compression="rotq", delta_layout="flat")
    ).pad_pow2
    with pytest.raises(ValueError):
        compression.make_rotq(bits=3)  # not a supported width


# -------------------------------------------------- end-to-end in round_step
def _round_setup(compression_kind, delta_layout="per_leaf"):
    cfg = RoundConfig(
        model="mlp",
        num_classes=4,
        opt=OptimizerConfig(learning_rate=0.05, weight_decay=0.0),
        data=DataConfig(dataset="synthetic", batch_size=8),
        fed=FedConfig(num_clients=4, compression=compression_kind,
                      topk_fraction=0.1, delta_layout=delta_layout),
        steps_per_round=3,
    )
    model = models.create(cfg.model, num_classes=cfg.num_classes)
    comp = compression.make_compressor(cfg.fed)
    state = round_lib.init_state(
        model, cfg, jax.random.PRNGKey(0), jnp.zeros((1, 6), jnp.float32), comp
    )
    step = jax.jit(round_lib.make_round_step(model, cfg, compressor=comp))
    rng = np.random.default_rng(0)
    n, s, b = 4, 3, 8
    batch = round_lib.RoundBatch(
        x=jnp.asarray(rng.normal(size=(n, s, b, 6)).astype(np.float32)),
        y=jnp.asarray(rng.integers(0, 4, size=(n, s, b)).astype(np.int32)),
        step_mask=jnp.ones((n, s), bool),
        weights=jnp.ones((n,), jnp.float32),
        alive=jnp.ones((n,), bool),
    )
    return cfg, state, step, batch


@pytest.mark.parametrize(
    "kind,layout",
    [
        ("topk", "per_leaf"),
        ("int8", "per_leaf"),
        # rotq exercises the pow2-padded flat path end-to-end through the
        # engine round step (tier-1); randk shares the plain flat wiring
        # already covered by the engine-codec units, so its full round step
        # rides the slow tier.
        ("rotq", "flat"),
        pytest.param("randk", "flat", marks=pytest.mark.slow),
    ],
)
def test_round_step_with_compression(kind, layout):
    cfg, state, step, batch = _round_setup(kind, delta_layout=layout)
    assert jax.tree_util.tree_leaves(state.comp_state)  # residuals allocated
    s1, m1 = step(state, batch)
    s2, m2 = step(s1, batch)
    # Model actually moves, and residuals become nonzero (lossy codec).
    moved = max(
        float(jnp.abs(a - b).max())
        for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(s2.params))
    )
    assert moved > 0
    res = max(float(jnp.abs(r).max()) for r in jax.tree.leaves(s2.comp_state))
    assert res > 0
    assert np.isfinite(float(m2.loss))


def test_dead_client_residual_preserved():
    """A dead client's error-feedback residual must be carried untouched —
    its (zeroed) delta contributes nothing, so draining the residual would
    permanently lose its correction mass."""
    cfg, state, step, batch = _round_setup("topk")
    s1, _ = step(state, batch)  # round 0: everyone alive, residuals fill
    dead = round_lib.RoundBatch(
        x=batch.x, y=batch.y, step_mask=batch.step_mask,
        weights=batch.weights,
        alive=jnp.asarray([True, True, True, False]),
    )
    s2, _ = step(s1, dead)
    for r1, r2 in zip(jax.tree.leaves(s1.comp_state), jax.tree.leaves(s2.comp_state)):
        # Client 3's residual row unchanged; a living client's moved.
        np.testing.assert_allclose(np.asarray(r1)[3], np.asarray(r2)[3], atol=0)
    moved = max(
        float(jnp.abs(np.asarray(r1)[0] - np.asarray(r2)[0]).max())
        for r1, r2 in zip(jax.tree.leaves(s1.comp_state), jax.tree.leaves(s2.comp_state))
    )
    assert moved > 0


def test_compressed_training_still_converges():
    """Short synthetic run: loss under top-k+EF decreases from round 0."""
    cfg, state, step, batch = _round_setup("topk")
    losses = []
    for _ in range(8):
        state, m = step(state, batch)
        losses.append(float(m.loss))
    assert losses[-1] < losses[0]


def test_pallas_blocks_are_mosaic_legal():
    """Block shapes must satisfy Mosaic's tiling rule: last two block dims
    divisible by (8, 128) or equal to the whole array dim (the constraint
    that rejected the original (1, N) row-tiling — see
    tools/compile_pallas_tpu.py for the deviceless TPU compile proof)."""
    from fedtpu.ops.pallas_kernels import _blocks

    for rows, cols in [(1, 7), (2, 100), (8, 128), (64, 3_217_226),
                       (12, 50_000), (64, 32 * 1024), (3, 129)]:
        rb, cb = _blocks(rows, cols)
        assert rb == rows or rb % 8 == 0, (rows, cols, rb)
        assert cb == cols or cb % 128 == 0, (rows, cols, cb)
        assert rb <= rows and cb <= cols


# ----------------------------------------------------- sketch codecs (flat)
# Every way the factor rule can split a width: one factor below / at the
# MXU tile, two factors with a small / large / full major one, three factors
# (the cell's 2^20 row, one row here: 6.7e8 FLOP as products).
@pytest.mark.parametrize("inverse", [False, True], ids=["forward", "inverse"])
@pytest.mark.parametrize(
    "rows,h",
    [(1, 2), (1, 8), (4, 64), (3, 128), (9, 256), (2, 2**13), (2, 2**14), (1, 2**20)],
)
def test_hadamard_rotate_matches_host_fwht(rows, h, inverse, rng):
    """The device rotation vs the wire codec's numpy butterfly
    (``transport.sparse._fwht_np`` — what the gRPC edge decodes with):
    identical up to float-associativity (the device sums each factor's
    terms in one matrix product, the host pairwise), forward and inverse.
    The chip smoke makes the same comparison on the TPU at the 2^20-column
    row."""
    from fedtpu.transport.sparse import _fwht_np

    y = rng.normal(size=(rows, h)).astype(np.float32)
    signs = rng.integers(0, 2, size=h).astype(np.float32) * 2 - 1
    norm = np.float32(1.0 / np.sqrt(h))
    fwht = lambda m: np.stack([_fwht_np(row) for row in m])  # 1-D twin
    ref = fwht(y) * norm * signs if inverse else fwht(y * signs) * norm
    got = pk.hadamard_rotate(jnp.asarray(y), jnp.asarray(signs), inverse=inverse)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "h,factors",
    [
        (1, (1,)), (2, (2,)), (64, (64,)), (128, (128,)), (256, (2, 128)),
        (2**13, (64, 128)), (2**14, (128, 128)), (2**20, (64, 128, 128)),
        (2**21, (128, 128, 128)), (2**22, (2, 128, 128, 128)),
    ],
)
def test_hadamard_factor_rule(h, factors):
    """The factor widths are a function of the width alone: at most 128 a
    factor, every factor but the major one exactly 128, the remainder in
    the major one."""
    assert pk._hadamard_factors(h) == factors
    assert int(np.prod(factors)) == h


def _jaxpr_eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (pjit, custom calls) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _jaxpr_eqns(sub)


@pytest.mark.parametrize("inverse", [False, True], ids=["forward", "inverse"])
@pytest.mark.parametrize("rows,h", [(8, 64), (8, 2**13), (192, 2**20)])
def test_hadamard_rotate_products_cannot_lose_precision(rows, h, inverse):
    """Nothing runs: the traced program is walked. A TPU's default for an
    f32 ``dot`` is one bf16 pass (8 bits of mantissa), which the benchmark's
    check would not catch, so every product must say HIGHEST on both
    operands or take bf16 operands (an exact split) into an f32 result.
    One product per Kronecker factor, and the butterfly's sub-lane shapes
    are gone, not moved: no intermediate is narrower than a vector
    register's 128 lanes once the row is."""
    jaxpr = jax.make_jaxpr(
        lambda y, s: pk.hadamard_rotate(y, s, inverse=inverse)
    )(
        jax.ShapeDtypeStruct((rows, h), jnp.float32),
        jax.ShapeDtypeStruct((h,), jnp.float32),
    )
    eqns = list(_jaxpr_eqns(jaxpr.jaxpr))
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == len(pk._hadamard_factors(h))
    for e in dots:
        operands = {str(v.aval.dtype) for v in e.invars}
        assert str(e.outvars[0].aval.dtype) == "float32"
        if operands == {"bfloat16"}:
            assert e.params["preferred_element_type"] == jnp.float32
        else:
            assert operands == {"float32"}
            assert e.params["precision"] == (
                jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST
            ), e.params["precision"]
    if h >= 128:
        for e in eqns:
            for v in e.outvars:
                shape = v.aval.shape
                if len(shape) >= 2:
                    assert shape[-1] >= 128, (e.primitive.name, shape)


def test_hadamard_rotation_pair_is_identity(rng):
    """inverse(forward(y)) == y exactly in math (fwht(fwht(x)) == h*x);
    f32 gives it back to ~1e-5."""
    y = jnp.asarray(rng.normal(size=(3, 128)).astype(np.float32))
    signs = jnp.asarray((rng.integers(0, 2, size=128) * 2 - 1).astype(np.float32))
    back = pk.hadamard_rotate(pk.hadamard_rotate(y, signs), signs, inverse=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(y),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        pk.hadamard_rotate(y[:, :100], signs[:100])  # not a power of two


def _flat_codec_setup(make, pow2, rng, n=3):
    from fedtpu.ops import flat as flat_ops

    template = {
        "w": np.zeros((16, 32), np.float32),
        "b": np.zeros((32,), np.float32),
    }
    lay = flat_ops.make_layout(template, pow2=pow2)
    y = jnp.asarray(
        rng.normal(size=(n, lay.padded)).astype(np.float32)
    ).at[:, lay.total:].set(0.0)
    comp = make()
    state = comp.init(template, n)
    return comp, lay, y, state


def test_rotq_engine_replay_is_deterministic(rng):
    """Same round_idx -> bit-identical compressed rows (the PRNG is keyed
    only on the round); a different round rotates differently."""
    comp, lay, y, state = _flat_codec_setup(
        lambda: compression.make_rotq(bits=4), True, rng
    )
    a1, _ = comp.apply_flat(y, state, lay, round_idx=3)
    a2, _ = comp.apply_flat(y, state, lay, round_idx=3)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    b, _ = comp.apply_flat(y, state, lay, round_idx=4)
    assert float(jnp.abs(a1 - b).max()) > 0


def test_rotq_engine_pad_stays_zero_and_ef_closes(rng):
    """The codec's output keeps the pad region exactly zero (the flat
    buffer invariant) and out + residual == input to f32 tolerance."""
    comp, lay, y, state = _flat_codec_setup(
        lambda: compression.make_rotq(bits=8), True, rng
    )
    out, res = comp.apply_flat(y, state, lay, round_idx=0)
    assert float(jnp.abs(out[:, lay.total:]).max()) == 0.0
    np.testing.assert_allclose(
        np.asarray(out + res), np.asarray(y), rtol=1e-4, atol=1e-4
    )


def test_rotq_engine_requires_pow2_row(rng):
    # error_feedback off so the check under test (the codec's own pow2
    # guard) fires rather than a residual-buffer shape mismatch.
    comp, lay, y, state = _flat_codec_setup(
        lambda: compression.make_rotq(bits=4, error_feedback=False), False, rng
    )
    if lay.padded & (lay.padded - 1):  # lane padding landed off a power of 2
        with pytest.raises(ValueError):
            comp.apply_flat(y, state, lay, round_idx=0)


def test_randk_engine_ef_keeps_exact_mass(rng):
    """EF on: kept coordinates ship unscaled and out + residual == y
    EXACTLY (disjoint supports — no rounding in the split)."""
    comp, lay, y, state = _flat_codec_setup(
        lambda: compression.make_randk(0.1), False, rng
    )
    out, res = comp.apply_flat(y, state, lay, round_idx=1)
    np.testing.assert_array_equal(np.asarray(out + res), np.asarray(y))
    # The kept support is shared across clients (one seeded draw per round).
    nz = np.asarray(out) != 0
    assert (nz.any(axis=0) == nz.all(axis=0))[np.asarray(y != 0).all(axis=0)].all()


def test_randk_engine_no_ef_is_rescaled(rng):
    """EF off: the kept values carry the total/k unbiasedness rescale."""
    frac = 0.1
    comp, lay, y, state = _flat_codec_setup(
        lambda: compression.make_randk(frac, error_feedback=False), False, rng
    )
    out, _ = comp.apply_flat(y, state, lay, round_idx=1)
    kept = np.asarray(out)
    mask = kept != 0
    import math as _math

    k = max(1, int(_math.ceil(frac * lay.total)))
    expect = np.asarray(y) * (lay.total / k)
    np.testing.assert_allclose(kept[mask], expect[mask], rtol=1e-5)
