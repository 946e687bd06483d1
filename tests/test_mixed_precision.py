"""The local step's compute dtype (``RoundConfig.dtype``).

``RoundConfig.dtype="bfloat16"`` is master-copy mixed precision with a
precisely scoped numerics contract, pinned here:

* The ONE local-update builder (``fedtpu.core.client.make_local_update``)
  returns float32 params, stats and optimizer state against float32 masters
  in every feed form and either dtype; ``num_steps`` counts the unmasked
  steps; a client whose every step is masked returns the global parameters
  bit for bit with its optimizer state untouched.
* Under ``bfloat16`` the AGGREGATION SURFACE stays f32: server params,
  optimizer state, the flat packed buffer and the checkpoint wire bytes are
  identical in dtype/size to a float32 run. Only the on-device compute and
  dataset residency change, and a round from the bf16-resident dataset
  equals a round from f32 data cast at use.
* ``augment_crop=False`` is flip-only with the SAME flip decisions as the
  crop path (shared rng split structure).
* bf16-vs-f32 convergence stays within a documented tolerance on the easy
  synthetic task (the analogue of MOMENTUM_DTYPE_CONVERGENCE for the
  compute dtype).
* Misconfigurations fail loudly at construction, not silently mid-run.
"""

import dataclasses
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
from fedtpu.core import Federation, optim
from fedtpu.core.client import make_local_update
from fedtpu.data.augment import augment_batch
from fedtpu.models.common import batch_norm


def _cfg(layout="gather", dtype="float32", clients=4,
         model="mlp", dataset="synthetic", augment=False, partition="iid",
         **kw):
    base = dict(
        model=model,
        num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05, weight_decay=0.0),
        data=DataConfig(
            dataset=dataset,
            batch_size=4,
            partition=partition,
            num_examples=32 * clients,
            augment=augment,
            device_layout=layout,
        ),
        fed=FedConfig(num_clients=clients),
        steps_per_round=2,
        dtype=dtype,
    )
    base.update(kw)
    return RoundConfig(**base)


def _state_leaves(fed):
    return (
        jax.tree_util.tree_leaves(fed.state.params)
        + jax.tree_util.tree_leaves(fed.state.batch_stats)
        + jax.tree_util.tree_leaves(fed.state.opt_state)
    )


def _assert_bitwise(fa, fb):
    for a, b in zip(_state_leaves(fa), _state_leaves(fb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(fa.state.last_client_loss),
        np.asarray(fb.state.last_client_loss),
    )


# ------------------------------------------------- the local-update contract
class _BNNet(nn.Module):
    """Dense -> BatchNorm -> ReLU -> Dense: the smallest model that has
    batch statistics for the local step to carry."""

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = nn.Dense(16)(x.reshape((x.shape[0], -1)))
        x = nn.relu(batch_norm(train)(x))
        return nn.Dense(10)(x)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("feed", ["presharded", "gather", "materialised"])
def test_local_update_contract(feed, dtype):
    steps, batch, shape = 3, 4, (4, 4, 1)
    cfg = RoundConfig(
        model="mlp", num_classes=10, image_size=shape, dtype=dtype,
        opt=OptimizerConfig(learning_rate=0.05, momentum=0.9),
        data=DataConfig(dataset="synthetic", batch_size=batch, augment=False),
        steps_per_round=steps,
    )
    model = _BNNet()
    rng = np.random.default_rng(0)
    images = rng.normal(size=(steps * batch, 16)).astype(np.float32)
    labels = rng.integers(0, 10, size=(steps * batch,)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((batch,) + shape))
    params, stats = variables["params"], variables["batch_stats"]
    # A non-zero momentum going in, so "untouched" is not "still zero".
    opt_state = jax.tree.map(
        lambda m: m + 0.5, optim.init(params, cfg.opt)
    )
    local_update = jax.jit(make_local_update(
        model.apply, cfg, stream=False if feed == "materialised" else feed,
        image_shape=shape,
    ))

    def run(step_mask):
        head = (params, stats, opt_state)
        tail = (jnp.asarray(step_mask), jax.random.PRNGKey(1), jnp.int32(0))
        if feed == "presharded":
            # This client's rows twice over ([2L, F]) and per-step offsets.
            data = (jnp.asarray(np.concatenate([images, images])),
                    jnp.asarray(np.concatenate([labels, labels])),
                    jnp.arange(steps, dtype=jnp.int32) * batch)
        elif feed == "gather":
            data = (jnp.asarray(images), jnp.asarray(labels),
                    jnp.arange(steps * batch, dtype=jnp.int32).reshape(
                        steps, batch))
        else:
            data = (jnp.asarray(images.reshape((steps, batch) + shape)),
                    jnp.asarray(labels.reshape(steps, batch)))
        return local_update(*head, *data, *tail)

    out = run([True, False, True])
    for leaf in jax.tree.leaves((out.params, out.batch_stats, out.opt_state)):
        assert leaf.dtype == jnp.float32
    assert float(out.num_steps) == 2.0
    assert np.isfinite(float(out.loss))
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(out.params), jax.tree.leaves(params))
    )

    idle = run([False, False, False])
    assert float(idle.num_steps) == 0.0 and float(idle.loss) == 0.0
    for got, want in zip(
        jax.tree.leaves((idle.params, idle.batch_stats, idle.opt_state)),
        jax.tree.leaves((params, stats, opt_state)),
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------- bf16 f32 surface pin
def test_bf16_mixed_keeps_aggregation_surface_f32(tmp_path):
    """dtype="bfloat16" changes device residency, never server semantics:
    master params/opt stay f32, the flat packed buffer stays f32, and a
    checkpoint of the bf16-mode state is byte-for-byte the SIZE of the f32
    mode's (the wire format must not notice the compute dtype)."""
    from fedtpu.checkpoint.checkpoint import save
    from fedtpu.ops import flat as flat_ops

    f32 = Federation(_cfg(dtype="float32"), seed=0)
    b16 = Federation(_cfg(dtype="bfloat16"), seed=0)
    f32.step()
    b16.step()

    for leaf in jax.tree_util.tree_leaves(
        (b16.state.params, b16.state.opt_state)
    ):
        assert leaf.dtype == jnp.float32
    # Device-resident dataset IS stored bf16 (the HBM footprint win)...
    assert b16._ensure_device_data()[0].dtype == jnp.bfloat16
    assert f32._ensure_device_data()[0].dtype == jnp.float32

    # ...but the flat aggregation buffer the screening/compression stack
    # sees is structurally f32 either way.
    lay = flat_ops.make_layout(jax.device_get(b16.state.params))
    packed = flat_ops.pack(lay, b16.state.params)
    assert packed.dtype == jnp.float32

    # Checkpoint wire: identical byte count between the two modes.
    p32 = save(str(tmp_path / "f32"), 0, jax.device_get(f32.state))
    p16 = save(str(tmp_path / "b16"), 0, jax.device_get(b16.state))
    assert os.path.getsize(p32) == os.path.getsize(p16)


def test_bf16_convergence_within_documented_tolerance():
    """The compute-dtype analogue of MOMENTUM_DTYPE_CONVERGENCE: bf16
    training tracks f32 on the easy synthetic task. Tolerance is loose by
    design — bf16 has ~8 mantissa bits and the trajectories genuinely
    diverge — but both must LEARN, and the final losses must agree to 25%
    relative (measured headroom ~5x on this config)."""
    losses = {}
    for dtype in ("float32", "bfloat16"):
        fed = Federation(
            _cfg(dtype=dtype, clients=2, steps_per_round=4), seed=0
        )
        first = fed.run(num_rounds=1)
        last = fed.run(num_rounds=3)
        assert float(last.loss) < float(first.loss)
        losses[dtype] = float(last.loss)
    # 25% relative with a small absolute floor: the synthetic task drives
    # the loss to ~0, where a relative bound alone is ill-conditioned.
    diff = abs(losses["bfloat16"] - losses["float32"])
    assert diff < max(0.25 * losses["float32"], 0.05), losses


@pytest.mark.parametrize("layout", ["presharded", "gather"])
def test_device_store_dtype_follows_round_dtype(layout):
    """The resident images are bf16 exactly when RoundConfig.dtype is, and a
    round from the bf16 store equals a round from f32 data cast at use (the
    materialised feed hands the local step f32 batches)."""
    # round_robin: every feed iterates a shard unshuffled from its head.
    kw = dict(layout=layout, model="smallcnn", dataset="cifar10", clients=2,
              partition="round_robin")
    f32 = Federation(_cfg(dtype="float32", **kw), seed=0)
    assert f32._ensure_device_data()[0].dtype == jnp.float32
    stored = Federation(_cfg(dtype="bfloat16", **kw), seed=0)
    assert stored._ensure_device_data()[0].dtype == jnp.bfloat16
    cast_at_use = Federation(_cfg(dtype="bfloat16", **kw), seed=0)
    batch = cast_at_use.round_batch(0)
    assert batch.x.dtype == jnp.float32
    m_stored = stored.step()
    m_cast = cast_at_use.step(batch)
    for a, b in zip(_state_leaves(stored), _state_leaves(cast_at_use)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert float(m_stored.loss) == pytest.approx(float(m_cast.loss), abs=1e-6)


# -------------------------------------------------------- crop toggle pin
def test_crop_off_is_flip_only_with_identical_flip_draws():
    """augment_crop=False must change ONLY the crop: the flip decisions
    come from the same split(rng) slot in both modes, so crop-off output
    equals a hand-built flip using that slot — and flipping a crop=True
    output uses the same mask (mode-coupled determinism)."""
    rng = jax.random.PRNGKey(3)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 32, 32, 3), jnp.float32)
    _crop_rng, flip_rng = jax.random.split(rng)
    flip = jax.random.bernoulli(flip_rng, 0.5, (8,))
    expect = jnp.where(flip[:, None, None, None], x[:, :, ::-1, :], x)
    got = augment_batch(rng, x, crop=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))
    assert bool(np.asarray(flip).any()) and not bool(np.asarray(flip).all())


def test_crop_flag_flows_from_data_config():
    """DataConfig.augment_crop=False is bit-identical to flip-only through
    the engine; crop on-vs-off genuinely differ (the flag is not dead)."""
    kw = dict(model="smallcnn", dataset="cifar10", augment=True, clients=2)
    on = Federation(_cfg(**kw), seed=0)
    off = Federation(
        _cfg(**kw, data=dataclasses.replace(
            _cfg(**kw).data, augment_crop=False)),
        seed=0,
    )
    on.step()
    off.step()
    a = jax.tree_util.tree_leaves(on.state.params)
    b = jax.tree_util.tree_leaves(off.state.params)
    assert any(
        not np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(a, b)
    )


# ------------------------------------------------------------- validation
@pytest.mark.parametrize("name", ["float16", "bf16", "bfloat16_mixed"])
def test_unknown_compute_dtype_rejected_cheaply(name, monkeypatch):
    """An unknown RoundConfig.dtype is refused at construction, with the two
    names there are, before anything is built or compiled."""
    import fedtpu.core.engine as engine

    def never(*a, **k):
        raise AssertionError("built a model for an invalid config")

    monkeypatch.setattr(engine.model_zoo, "create", never)
    with pytest.raises(ValueError, match="float32 \\| bfloat16"):
        Federation(_cfg(dtype=name), seed=0)


# ------------------------------------------------------ the CLI's one flag
def _parser():
    import argparse

    from fedtpu.cli import common

    p = argparse.ArgumentParser()
    common.add_model_flags(p)
    common.add_fed_flags(p)
    return p


_ARGV = ["--dataset", "synthetic", "--batch-size", "4", "--num-examples", "64"]


def test_build_config_threads_perf_knobs():
    """--compute-dtype writes RoundConfig.dtype; absent, float32."""
    from fedtpu.cli import common

    args = _parser().parse_args(_ARGV + ["--compute-dtype", "bfloat16"])
    cfg = common.build_config(args, num_clients=8, steps_per_round=2)
    assert cfg.dtype == "bfloat16"
    cfg = common.build_config(
        _parser().parse_args(_ARGV), num_clients=8, steps_per_round=2)
    assert cfg.dtype == "float32"


@pytest.mark.parametrize("argv", [
    ["--compute-dtype", "bfloat16_mixed"],
    ["--perf-preset", "fast"],
    ["--megabatch-clients", "2"],
])
def test_removed_perf_spellings_are_argparse_errors(argv, capsys):
    with pytest.raises(SystemExit) as e:
        _parser().parse_args(_ARGV + argv)
    assert e.value.code == 2
    capsys.readouterr()
