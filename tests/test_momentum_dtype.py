"""bf16 momentum buffers (opt-in non-parity mode) + the avg-pool ablation.

Round-5 roofline experiments (VERDICT r4 #4): optimizer-state HBM traffic
(``OptimizerConfig.momentum_dtype='bfloat16'``) and pool cost
(``smallcnn_avgpool``). These tests pin the semantics the on-chip bench legs
rely on: the f32 default is BITWISE unchanged (parity must not move), the
bf16 mode differs only by one storage round-trip, and the avg-pool variant
is parameter-identical to smallcnn.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu import models
from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
from fedtpu.core import optim


def _params(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(8,)).astype(np.float32)),
    }


def _grads(seed=1):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(8,)).astype(np.float32)),
    }


def test_f32_default_is_bitwise_legacy():
    """momentum_dtype='float32' must be a no-op refactor: same bits as the
    pre-round-5 implementation (upcast of an f32 buffer and astype-f32 store
    are both identities)."""
    cfg = OptimizerConfig(learning_rate=0.1, momentum=0.9, weight_decay=5e-4)
    params, grads = _params(), _grads()
    state = optim.init(params, cfg)
    assert all(
        leaf.dtype == jnp.float32
        for leaf in jax.tree_util.tree_leaves(state.momentum)
    )

    # Legacy update, written out explicitly (the pre-momentum_dtype code).
    decayed = jax.tree.map(lambda g, p: g + cfg.weight_decay * p, grads, params)
    legacy_buf = jax.tree.map(lambda b, g: cfg.momentum * b + g,
                              state.momentum, decayed)
    legacy_params = jax.tree.map(lambda p, d: p - 0.1 * d, params, legacy_buf)

    new_params, new_state = optim.apply(params, grads, state, 0.1, cfg)
    for a, b in zip(jax.tree_util.tree_leaves(legacy_params),
                    jax.tree_util.tree_leaves(new_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(legacy_buf),
                    jax.tree_util.tree_leaves(new_state.momentum)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_momentum_is_one_storage_roundtrip():
    """bf16 mode: buffers stored bf16; the step equals the f32 step computed
    from the ROUNDED previous buffer — i.e. the only divergence source is
    the storage rounding, never low-precision accumulation."""
    cfg16 = OptimizerConfig(momentum_dtype="bfloat16", weight_decay=5e-4)
    cfg32 = dataclasses.replace(cfg16, momentum_dtype="float32")
    params, grads = _params(), _grads()

    state16 = optim.init(params, cfg16)
    assert all(
        leaf.dtype == jnp.bfloat16
        for leaf in jax.tree_util.tree_leaves(state16.momentum)
    )

    # Two steps in bf16 mode.
    p16, s16 = optim.apply(params, grads, state16, 0.1, cfg16)
    p16, s16 = optim.apply(p16, grads, s16, 0.1, cfg16)

    # Oracle: f32 mode, but manually rounding the carried buffer between
    # steps exactly once — must match the bf16 mode bit-for-bit.
    p32, s32 = optim.apply(params, grads, optim.init(params, cfg32), 0.1, cfg32)
    rounded = optim.SGDState(momentum=jax.tree.map(
        lambda b: b.astype(jnp.bfloat16).astype(jnp.float32), s32.momentum))
    p32b, s32b = optim.apply(p32, grads, rounded, 0.1, cfg32)
    for a, b in zip(jax.tree_util.tree_leaves(p16),
                    jax.tree_util.tree_leaves(p32b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(s16.momentum),
                    jax.tree_util.tree_leaves(s32b.momentum)):
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.bfloat16).astype(jnp.float32)),
        )

    # And the drift vs pure-f32 is small (bf16 has ~8 mantissa bits).
    p32_pure, _ = optim.apply(p32, grads, s32, 0.1, cfg32)
    for a, b in zip(jax.tree_util.tree_leaves(p16),
                    jax.tree_util.tree_leaves(p32_pure)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=5e-3)


def test_unknown_momentum_dtype_rejected_cheaply():
    with pytest.raises(ValueError, match="momentum_dtype"):
        optim.init(_params(), OptimizerConfig(momentum_dtype="float16"))

    from fedtpu.core.engine import Federation

    cfg = RoundConfig(
        model="mlp", num_classes=10,
        opt=OptimizerConfig(momentum_dtype="float16"),
        data=DataConfig(dataset="mnist", batch_size=8, num_examples=64),
        fed=FedConfig(num_clients=2), steps_per_round=2,
    )
    with pytest.raises(ValueError, match="momentum_dtype"):
        Federation(cfg, seed=0)


def test_bf16_momentum_trains_end_to_end():
    """Engine smoke in the non-parity mode: state carries bf16 buffers and
    the model still learns the easy synthetic task."""
    from fedtpu.core.engine import Federation

    cfg = RoundConfig(
        model="mlp", num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05, momentum_dtype="bfloat16"),
        data=DataConfig(dataset="mnist", batch_size=16, partition="iid",
                        num_examples=256),
        fed=FedConfig(num_clients=2), steps_per_round=4,
    )
    fed = Federation(cfg, seed=0)
    assert all(
        leaf.dtype == jnp.bfloat16
        for leaf in jax.tree_util.tree_leaves(fed.state.opt_state.momentum)
    )
    first = fed.run(num_rounds=1)
    last = fed.run(num_rounds=5)
    assert float(last.loss) < float(first.loss)
    assert all(
        leaf.dtype == jnp.bfloat16
        for leaf in jax.tree_util.tree_leaves(fed.state.opt_state.momentum)
    )


def test_avgpool_variant_is_parameter_identical():
    """smallcnn_avgpool: same param tree (pools are parameter-free), so its
    bench leg isolates the pooling op and nothing else."""
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    m_max = models.create("smallcnn", num_classes=10)
    m_avg = models.create("smallcnn_avgpool", num_classes=10)
    v_max = m_max.init(jax.random.PRNGKey(0), x, train=False)
    v_avg = m_avg.init(jax.random.PRNGKey(0), x, train=False)
    shapes = lambda v: jax.tree.map(lambda p: (p.shape, str(p.dtype)), v)
    assert shapes(v_max) == shapes(v_avg)
    # Same seed -> same weights; outputs must still differ (different op).
    x2 = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    out_max = m_max.apply(v_max, x2, train=False)
    out_avg = m_avg.apply(v_avg, x2, train=False)
    assert not np.allclose(np.asarray(out_max), np.asarray(out_avg))


def test_bench_variant_field(monkeypatch):
    """bench.py must label variant runs so an experiment artifact can never
    masquerade as the parity headline. mlp (not the real smallcnn variant)
    keeps this seconds-scale: the labeling logic is model-agnostic and the
    smallcnn path itself is covered by test_measure_contract."""
    monkeypatch.syspath_prepend(".")
    import bench as bench_mod

    monkeypatch.setattr(bench_mod, "NUM_CLIENTS", 4)
    monkeypatch.setattr(bench_mod, "STEPS_PER_ROUND", 2)
    monkeypatch.setattr(bench_mod, "BATCH", 8)
    monkeypatch.setattr(bench_mod, "TIMED_ROUNDS", 2)
    monkeypatch.setattr(bench_mod, "TRIALS", 1)
    monkeypatch.setattr(bench_mod, "BENCH_MODEL", "mlp")
    monkeypatch.setattr(bench_mod, "MOMENTUM_DTYPE", "bfloat16")
    result = bench_mod._measure()
    assert result["variant"] == {
        "model": "mlp", "momentum_dtype": "bfloat16",
        "dtype": "bfloat16",
    }
    assert result["value"] > 0
