#!/usr/bin/env python
"""Deviceless AOT compilation check against a REAL TPU target (v5e).

jax + libtpu can compile for a TPU *topology* without any device attached
(``jax.experimental.topologies``), so "does this lower through Mosaic /
XLA:TPU, and how much HBM does it want?" can be answered from a CPU-only
sandbox without spending chip time. Whether the program then RUNS is
``chip_smoke.py``'s job. This script AOT-compiles, for a v5e:2x2 target:

1. the Pallas compression kernels at MobileNet scale (64 clients x ~3.2M
   params — the ``-c Y`` hot path) with ``interpret=False``, proving Mosaic
   lowering + VMEM fit; and the held experts' grouped product
   (``fedtpu/ops/expert_kernels.py``), forward and both backward kernels, at
   the language cells' chunks (Nemotron-H's two stacks among them: a width of
   14.5 lane groups as the output and as the contraction); and Mamba-2's
   selective scan (``fedtpu/ops/ssd_kernels.py``), forward and backward, at
   the state-space cell's shapes;
2. the full single-chip federated round step (bench.py's exact config);
3. the sharded 4-chip round step (shard_map + psum over the clients mesh) —
   the multichip program compiled for actual TPU hardware, not just the
   virtual CPU mesh.

Codec kernels nested inside a round program pick their lowering from
``jax.default_backend()`` (cpu here), so a compressed round step compiled
by this script would carry the plain-jnp bodies, not Mosaic — which is why
there is no such entry; the chip smoke's CLI leg runs that program for real.

Writes one JSON line per artifact to stdout and (with ``--out``) a combined
JSON file. Run: ``python tools/compile_pallas_tpu.py --out PALLAS_TPU_COMPILE.json``
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # deviceless: the host side is CPU

import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MOBILENET_PARAMS = 3_217_226  # param count of the reference default model
NUM_CLIENTS = 64


def _mem(compiled):
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
    }


def _flops(compiled):
    return float(compiled.cost_analysis().get("flops", 0.0)) or None


def compile_kernels(dev):
    from fedtpu.ops import pallas_kernels as pk

    s = jax.sharding.SingleDeviceSharding(dev)
    y = jax.ShapeDtypeStruct((NUM_CLIENTS, MOBILENET_PARAMS), jnp.float32, sharding=s)
    t = jax.ShapeDtypeStruct((NUM_CLIENTS,), jnp.float32, sharding=s)
    results = []
    for name, fn in (
        ("threshold_with_feedback", lambda a, b: pk.threshold_with_feedback(a, b, interpret=False)),
        ("quantdequant_int8", lambda a, b: pk.quantdequant_int8(a, b, interpret=False)),
    ):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(y, t).compile()
        results.append(
            {
                "artifact": f"pallas:{name}",
                "target": dev.device_kind,
                "shape": [NUM_CLIENTS, MOBILENET_PARAMS],
                "compile_s": round(time.perf_counter() - t0, 2),
                "ok": True,
                **_mem(compiled),
            }
        )
    return results


# The language cells' expert layers, a chunk of each: (block, in, out, held
# experts, blocks a chunk). Nemotron-H's two stacks are both here: 1,856 is no
# whole number of lanes, and it is the output width of one and the
# contraction of the other.
EXPERT_CHUNKS = {
    "laguna_s_2_1.fl4_seq8k": (128, 3072, 1024, 8, 72),
    "lfm2_24b_a2b.fl4_b8_seq4k": (1024, 2048, 1536, 8, 40),
    "qwen3_next_80b_a3b.fl4_seq8k": (128, 2048, 512, 16, 80),
    "joyai_llm_flash.fl4_seq4k": (256, 2048, 768, 8, 24),
    "nemotron_3_nano_30b_a3b.fl4_seq8k": (128, 2688, 1856, 8, 72),
    "nemotron_3_nano_30b_a3b.fl4_seq8k.down": (128, 1856, 2688, 8, 72),
}


def compile_expert_kernels(dev):
    """The held experts' grouped product (``fedtpu/ops/expert_kernels.py``)
    at each language cell's chunk, bfloat16: the forward product and the two
    backward kernels (``d rows`` on the transposed contraction, ``d w`` summed
    over an expert's run of blocks), through Mosaic; ``vmem_bytes`` is the
    most any of the three took of the kernels' scoped limit."""
    import re

    from fedtpu.ops import expert_kernels as ek

    s = jax.sharding.SingleDeviceSharding(dev)
    of = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=s)
    results = []
    for cell, (block, d, width, held, n_blocks) in EXPERT_CHUNKS.items():
        def product_and_gradients(rows, w, expert, live, ct):
            out, vjp = jax.vjp(lambda rows, w: ek.grouped_product(
                rows, w, expert, live, block, interpret=False), rows, w)
            return (out,) + vjp(ct)

        t0 = time.perf_counter()
        compiled = jax.jit(product_and_gradients).lower(
            of(jnp.bfloat16, n_blocks * block, d), of(jnp.bfloat16, held, d, width),
            of(jnp.int32, n_blocks), of(jnp.int32),
            of(jnp.bfloat16, n_blocks * block, width)).compile()
        text = compiled.as_text()
        kernels = sorted(set(re.findall(r"%(expert_\w+?)[.\d]* = ", text)))
        vmem = [int(n) for n in re.findall(
            r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', text)]
        results.append(
            {
                "artifact": f"pallas:expert_kernels:{cell}",
                "target": dev.device_kind,
                "shape": {"rows": [n_blocks * block, d], "weights": [held, d, width],
                          "block": block},
                "kernels": kernels,
                "vmem_bytes": max(vmem, default=0),
                "compile_s": round(time.perf_counter() - t0, 2),
                "ok": kernels == ["expert_product", "expert_product_transposed",
                                  "expert_weights_gradient"],
                **_mem(compiled),
            }
        )
    return results


def _bench_inputs(cfg, sharding_for):
    """ShapeDtypeStructs for (state, batch) under a sharding-assignment fn."""
    from fedtpu.core import round as round_lib
    from fedtpu import models

    model = models.create(cfg.model, num_classes=cfg.num_classes, remat=cfg.remat)
    state = jax.eval_shape(
        lambda r: round_lib.init_state(
            model, cfg, r, jnp.zeros((1, 32, 32, 3), jnp.float32)
        ),
        jax.random.PRNGKey(0),
    )
    n, s, b = cfg.fed.num_clients, cfg.steps_per_round, cfg.data.batch_size
    batch = round_lib.RoundBatch(
        x=jax.ShapeDtypeStruct((n, s, b, 32, 32, 3), jnp.float32),
        y=jax.ShapeDtypeStruct((n, s, b), jnp.int32),
        step_mask=jax.ShapeDtypeStruct((n, s), jnp.bool_),
        weights=jax.ShapeDtypeStruct((n,), jnp.float32),
        alive=jax.ShapeDtypeStruct((n,), jnp.bool_),
    )
    put = lambda tree, spec_tree: jax.tree.map(
        lambda l, sp: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding_for(sp)),
        tree,
        spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    return model, state, batch, put


def compile_round_step(
    dev,
    model_name="smallcnn",
    dataset="cifar10",
    num_classes=10,
    steps=391 // NUM_CLIENTS,
    batch=128,
    tag="bench_config",
    remat=False,
):
    """bench.py's exact single-chip config, AOT for the TPU target.
    ``model_name``/``steps`` overrides cover the parity configs (e.g.
    resnet18/cifar100 — config 4's TPU-side evidence, since XLA:CPU
    compiles it far too slowly to bench)."""
    from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
    from fedtpu.core import round as round_lib
    from fedtpu import models

    cfg = RoundConfig(
        model=model_name,
        num_classes=num_classes,
        opt=OptimizerConfig(),
        data=DataConfig(dataset=dataset, batch_size=batch),
        fed=FedConfig(num_clients=NUM_CLIENTS),
        steps_per_round=steps,
        dtype="bfloat16",
        remat=remat,
    )
    s = jax.sharding.SingleDeviceSharding(dev)
    model, state, batch, put = _bench_inputs(cfg, lambda spec: s)
    same = lambda tree: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    step = jax.jit(round_lib.make_round_step(model, cfg), donate_argnums=(0,))
    t0 = time.perf_counter()
    compiled = step.lower(same(state), same(batch)).compile()
    return {
        "artifact": f"round_step:{tag}_single_chip"
        + ("_remat" if remat else ""),
        "target": dev.device_kind,
        "model": model_name,
        "num_clients": NUM_CLIENTS,
        "compile_s": round(time.perf_counter() - t0, 2),
        "flops_per_round": _flops(compiled),
        "ok": True,
        **_mem(compiled),
    }


def _data_path_inputs(dev, cfg, model, total, num_rounds=None,
                      layout="presharded"):
    """ShapeDtypeStruct args for the device-resident data-path programs
    (``make_data_round_step`` / ``make_multi_round_step``): dataset in HBM
    (per-client ``[n, 2L, F]`` presharded rows by default, flat ``[N, F]``
    for the gather layout), per-client assignment, weights/alive/key.
    ``num_rounds`` switches ``alive`` to the fused scan's
    ``[rounds, clients]`` layout."""
    from fedtpu.core import round as round_lib

    state = jax.eval_shape(
        lambda r: round_lib.init_state(
            model, cfg, r, jnp.zeros((1, 32, 32, 3), jnp.float32)
        ),
        jax.random.PRNGKey(0),
    )
    s = jax.sharding.SingleDeviceSharding(dev)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=s)
    place = lambda tree: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    n = cfg.fed.num_clients
    shard = total // n
    alive = (
        sds((n,), jnp.bool_)
        if num_rounds is None
        else sds((num_rounds, n), jnp.bool_)
    )
    if layout == "presharded":
        images = sds((n, 2 * shard, 32 * 32 * 3), jnp.float32)
        labels = sds((n, 2 * shard), jnp.int32)
    else:
        images = sds((total, 32 * 32 * 3), jnp.float32)
        labels = sds((total,), jnp.int32)
    return (
        place(state),
        images,
        labels,
        sds((n, shard), jnp.int32),
        sds((n, shard), jnp.bool_),
        sds((n,), jnp.float32),
        alive,
        sds((2,), jnp.uint32),  # data key
    )


def compile_streaming_round_step(
    dev,
    model_name="resnet18",
    dataset="cifar100",
    num_classes=100,
    steps=40,
    batch=32,
    remat=True,
    tag="parity4_resnet18_cifar100_stream",
):
    """The engine's actual big-model path on ONE chip: device-resident
    dataset, per-step gather inside the scan (``stream``), per-block remat.
    This is the configuration that brings 64-client resnet18 rounds back
    under one v5e's HBM after the non-stream form measurably OOMed."""
    from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
    from fedtpu.data.device import make_data_round_step
    from fedtpu import models

    cfg = RoundConfig(
        model=model_name,
        num_classes=num_classes,
        opt=OptimizerConfig(),
        data=DataConfig(dataset=dataset, batch_size=batch),
        fed=FedConfig(num_clients=NUM_CLIENTS),
        steps_per_round=steps,
        dtype="bfloat16",
        remat=remat,
    )
    model = models.create(cfg.model, num_classes=cfg.num_classes, remat=cfg.remat)
    args = _data_path_inputs(dev, cfg, model, total=50000, layout="presharded")
    step_fn = jax.jit(
        make_data_round_step(
            model, cfg, steps, shuffle=True, stream=True,
            image_shape=(32, 32, 3),
        ),
        donate_argnums=(0,),
    )
    t0 = time.perf_counter()
    compiled = step_fn.lower(*args).compile()
    return {
        "artifact": f"round_step:{tag}_single_chip",
        "target": dev.device_kind,
        "model": model_name,
        "num_clients": NUM_CLIENTS,
        "remat": remat,
        "stream": True,
        "compile_s": round(time.perf_counter() - t0, 2),
        "flops_per_round": _flops(compiled),
        "ok": True,
        **_mem(compiled),
    }


def compile_fused_multi_round(
    dev,
    num_rounds=10,
    steps=391 // NUM_CLIENTS,
    batch=128,
    tag="bench_fused10",
):
    """bench.py's headline program: the engine's fused ``num_rounds``-round
    scan (per-round on-device gather + vmapped local SGD + aggregation as ONE
    XLA program), AOT for the TPU target. ``flops_per_round`` comes from the
    single-round program of the SAME config — XLA cost analysis counts a
    lax.scan body once regardless of trip count today, and deriving from the
    unfused program (bench.py does the same) keeps the field honest if that
    convention ever changes; the raw fused number is reported alongside."""
    from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
    from fedtpu.data.device import make_data_round_step, make_multi_round_step
    from fedtpu import models

    n = NUM_CLIENTS
    total = n * steps * batch
    cfg = RoundConfig(
        model="smallcnn",
        num_classes=10,
        opt=OptimizerConfig(),
        data=DataConfig(
            dataset="cifar10", batch_size=batch, partition="iid",
            num_examples=total,
        ),
        fed=FedConfig(num_clients=n),
        steps_per_round=steps,
        dtype="bfloat16",
    )
    model = models.create(cfg.model, num_classes=cfg.num_classes)
    multi_args = _data_path_inputs(dev, cfg, model, total,
                                   num_rounds=num_rounds, layout="presharded")
    single_args = _data_path_inputs(dev, cfg, model, total, layout="presharded")
    multi = jax.jit(
        make_multi_round_step(
            model, cfg, steps, num_rounds, shuffle=True,
            image_shape=(32, 32, 3),
        ),
        donate_argnums=(0,),
    )
    single = jax.jit(
        make_data_round_step(
            model, cfg, steps, shuffle=True, image_shape=(32, 32, 3)
        ),
        donate_argnums=(0,),
    )
    t0 = time.perf_counter()
    compiled = multi.lower(*multi_args).compile()
    compile_s = round(time.perf_counter() - t0, 2)
    single_flops = _flops(single.lower(*single_args).compile())
    return {
        "artifact": f"multi_round:{tag}_single_chip",
        "target": dev.device_kind,
        "model": "smallcnn",
        "num_clients": n,
        "num_rounds": num_rounds,
        "compile_s": compile_s,
        "flops_per_round": single_flops,
        "fused_program_flops": _flops(compiled),
        "ok": True,
        **_mem(compiled),
    }


def compile_async_tick(
    dev,
    num_ticks=10,
    steps=391 // NUM_CLIENTS,
    batch=128,
    tag="async_fused10",
):
    """The engine-side FedBuff program (fedtpu.core.async_engine): a fused
    ``num_ticks``-tick scan where every client trains its OWN diverged model
    copy and ``buffer_k`` staleness-discounted arrivals aggregate per tick —
    AOT for the TPU target, proving the async study tool lowers to the chip
    (it cannot be speed-tested on XLA:CPU at 64 clients)."""
    from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
    from fedtpu.core.async_engine import init_async_state, make_multi_async_step
    from fedtpu import models

    n = NUM_CLIENTS
    total = n * steps * batch
    cfg = RoundConfig(
        model="smallcnn",
        num_classes=10,
        opt=OptimizerConfig(),
        data=DataConfig(
            dataset="cifar10", batch_size=batch, partition="iid",
            num_examples=total,
        ),
        fed=FedConfig(num_clients=n),
        steps_per_round=steps,
        dtype="bfloat16",
    )
    model = models.create(cfg.model, num_classes=cfg.num_classes)
    state = jax.eval_shape(
        lambda r: init_async_state(
            model, cfg, r, jnp.zeros((1, 32, 32, 3), jnp.float32)
        ),
        jax.random.PRNGKey(0),
    )
    s = jax.sharding.SingleDeviceSharding(dev)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=s)
    place = lambda tree: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    shard = total // n
    args_ = (
        place(state),
        sds((n, 2 * shard, 32 * 32 * 3), jnp.float32),  # presharded rows
        sds((n, 2 * shard), jnp.int32),
        sds((n, shard), jnp.int32),
        sds((n, shard), jnp.bool_),
        sds((n,), jnp.float32),
        sds((num_ticks, n), jnp.bool_),  # arrive
        sds((num_ticks, n), jnp.bool_),  # alive
        sds((2,), jnp.uint32),
    )
    multi = jax.jit(
        make_multi_async_step(
            model, cfg, steps, num_ticks, shuffle=True,
            image_shape=(32, 32, 3),
        ),
        donate_argnums=(0,),
    )
    t0 = time.perf_counter()
    compiled = multi.lower(*args_).compile()
    return {
        "artifact": f"async_tick:{tag}_single_chip",
        "target": dev.device_kind,
        "model": "smallcnn",
        "num_clients": n,
        "num_ticks": num_ticks,
        "compile_s": round(time.perf_counter() - t0, 2),
        "fused_program_flops": _flops(compiled),
        "ok": True,
        **_mem(compiled),
    }


def compile_sharded_round_step(
    topo,
    model_name="smallcnn",
    dataset="cifar10",
    num_classes=10,
    steps=391 // NUM_CLIENTS,
    batch=128,
    tag="",
):
    """The multichip shard_map program compiled for real v5e chips."""
    from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
    from fedtpu.parallel import make_sharded_round_step
    from fedtpu.parallel.sharded import batch_specs, state_specs

    n_dev = len(topo.devices)
    cfg = RoundConfig(
        model=model_name,
        num_classes=num_classes,
        opt=OptimizerConfig(),
        data=DataConfig(dataset=dataset, batch_size=batch),
        fed=FedConfig(num_clients=NUM_CLIENTS),
        steps_per_round=steps,
        dtype="bfloat16",
    )
    mesh = Mesh(np.array(topo.devices), (cfg.mesh_axis,))
    from fedtpu import models

    model = models.create(cfg.model, num_classes=cfg.num_classes, remat=cfg.remat)
    _, state, batch, _ = _bench_inputs(cfg, None)
    state_in = _with_specs(state, state_specs(cfg.mesh_axis), mesh)
    batch_in = _with_specs(batch, batch_specs(cfg.mesh_axis), mesh)
    step = make_sharded_round_step(model, cfg, mesh, donate=False)
    t0 = time.perf_counter()
    compiled = step.lower(state_in, batch_in).compile()
    return {
        "artifact": f"round_step:{tag}sharded_{n_dev}chip",
        "target": topo.devices[0].device_kind,
        "model": model_name,
        "n_devices": n_dev,
        "num_clients": NUM_CLIENTS,
        "compile_s": round(time.perf_counter() - t0, 2),
        "flops_per_round": _flops(compiled),
        "ok": True,
        **_mem(compiled),
    }


def _with_specs(tree, specs, mesh):
    """Attach NamedShardings from a matching PartitionSpec tree. Spec trees
    are a prefix of the value tree (one spec per state field covers every
    leaf under it), so broadcast specs down to the leaves."""

    def attach(spec, sub):
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape, l.dtype, sharding=NamedSharding(mesh, spec)
            ),
            sub,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )

    return jax.tree.map(
        attach, specs, tree, is_leaf=lambda x: isinstance(x, P)
    )


def compile_ssd_kernels(dev):
    """Mamba-2's selective scan (``fedtpu/ops/ssd_kernels.py``) at the
    state-space cell's shapes, bfloat16: 8,192 tokens, 64 heads of 64 on 8
    groups of a state of 128 in chunks of 128, the forward kernel and the
    backward one through Mosaic; ``vmem_bytes`` is the most either took of
    the kernels' scoped limit."""
    import re

    from fedtpu.ops import ssd_kernels as sk

    s = jax.sharding.SingleDeviceSharding(dev)
    of = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=s)
    t, heads, p, groups, n, chunk = 8192, 64, 64, 8, 128, 128

    def scan_and_gradients(x, dt, A, B, C, D, ct):
        out, vjp = jax.vjp(lambda *a: sk.selective_scan(
            *a, chunk, interpret=False), x, dt, A, B, C, D)
        return (out,) + vjp(ct)

    t0 = time.perf_counter()
    compiled = jax.jit(scan_and_gradients).lower(
        of(jnp.bfloat16, t, heads, p), of(jnp.float32, t, heads),
        of(jnp.float32, heads), of(jnp.bfloat16, t, groups, n),
        of(jnp.bfloat16, t, groups, n), of(jnp.float32, heads),
        of(jnp.bfloat16, t, heads, p)).compile()
    text = compiled.as_text()
    kernels = sorted(set(re.findall(r"%(selective_scan_\w+?)[.\d]* = ", text)))
    vmem = [int(size) for line in text.splitlines() if "%selective_scan_" in line
            for size in re.findall(
                r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', line)]
    return [{
        "artifact": "pallas:ssd_kernels:nemotron_3_nano_30b_a3b.fl4_seq8k",
        "target": dev.device_kind,
        "shape": {"x": [t, heads, p], "B": [t, groups, n], "chunk": chunk},
        "kernels": kernels,
        "vmem_bytes": max(vmem, default=0),
        "compile_s": round(time.perf_counter() - t0, 2),
        "ok": kernels == ["selective_scan_bwd", "selective_scan_fwd"],
        **_mem(compiled),
    }]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--topology", default="v5e:2x2")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    topo = topologies.get_topology_desc(platform="tpu", topology_name=args.topology)
    dev = topo.devices[0]
    results = []
    for fn in (
        lambda: compile_kernels(dev),
        lambda: compile_expert_kernels(dev),
        lambda: compile_ssd_kernels(dev),
        lambda: [compile_round_step(dev)],
        # The flagship model (MobileNet — the reference's hardcoded default,
        # src/main.py:69) at the bench scale, single chip.
        lambda: [
            compile_round_step(
                dev, model_name="mobilenet", tag="flagship_mobilenet"
            )
        ],
        # Parity config 4's TPU-side evidence, two deployment shapes:
        # (a) single chip with per-block remat + per-step streaming gather —
        #     the engine's actual big-model path. Without these, this config
        #     measurably exceeds one v5e's 16 GB HBM (capacity result
        #     recorded in BASELINE.md);
        # (b) SHARDED over 4 chips (16 clients per chip), no remat needed.
        lambda: [compile_streaming_round_step(dev)],
        lambda: [
            compile_sharded_round_step(
                topo,
                model_name="resnet18",
                dataset="cifar100",
                num_classes=100,
                steps=40,  # 5 local epochs x 8 batches of 32 per shard
                batch=32,
                tag="parity4_resnet18_cifar100_",
            )
        ],
        lambda: [compile_sharded_round_step(topo)],
        # The headline-bench program: 10 fused rounds as one XLA program.
        lambda: [compile_fused_multi_round(dev)],
        # Engine-side FedBuff: 10 fused async ticks (per-client diverged
        # model copies, buffered staleness-weighted aggregation).
        lambda: [compile_async_tick(dev)],
    ):
        try:
            out = fn()
        except Exception as e:
            out = [{"artifact": "error", "ok": False, "error": f"{type(e).__name__}: {e}"[:800]}]
        for r in out:
            print(json.dumps(r), flush=True)
            results.append(r)
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(
                {"topology": args.topology, "results": results}, fh, indent=1
            )
    return 0 if all(r.get("ok") for r in results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
