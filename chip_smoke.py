#!/usr/bin/env python
"""chip_smoke.py — does the round program still start on the chip?

One process, one chip (or one four-chip host), a few minutes: the bench
headline configuration (``bench.headline_config``: smallcnn at full width,
64 clients, batch 128, 6 steps a round, bf16 activations, presharded data,
random weights from a seed) through the entry points a user calls, every
leg asserted, nothing caught and carried past. Any failed leg is a
traceback and a non-zero exit; the last line of stdout is the JSON verdict
only when every leg passed.

  engine   ``Federation``: fused ``run_on_device(10)`` dispatches, ``step()``,
           ``evaluate()``; each steady dispatch timed once to
           ``jax.block_until_ready`` and once to a fetched loss.
  cli      ``fedtpu.cli.run.main`` with ``--platform tpu --fused 5
           --delta-layout flat --compression topk``: flat pack -> codec (a
           Mosaic kernel) -> aggregate -> server step.
  kernels  every ``pallas_call`` reachable from ``fedtpu.ops.compression``
           through Mosaic at the shapes the codecs hand it, bitwise against
           the jnp bodies; the Hadamard rotation at the 2^20-column row
           against the wire codec's numpy butterfly; the attention core's and
           the gated delta rule's kernels against their plain bodies,
           bfloat16, 1,536 tokens, output and gradients within 2e-2 of the
           plain body's largest value; an expert layer's grouped products
           through the expert kernels against the plain batched product, at
           a width of whole lanes and at one of a lane group and a half;
           Mamba-2's selective scan through its two kernels against the plain
           chunks at the state-space cell's shape (8,192 tokens, 64 heads of
           64, 8 groups, a state of 128).
  rotq     ``Federation(compression="rotq", delta_layout="flat")`` rounds.
  grpc     an in-process ``PrimaryServer`` + four ``serve_client`` agents
           over real localhost gRPC, flat layout, stream pipeline, top-k.
  mesh     with more than one chip: the headline under ``client_mesh(n)``
           and the CLI under ``--mesh auto``, shardings checked.

There is no CPU mode: with no TPU, or a TPU the peak table does not know,
it says why and exits non-zero within seconds, before any model is built.
Times printed are smoke walls, not throughput claims.
"""

import json
import logging
import math
import os
import socket
import sys
import tempfile
import threading
import time

ROUNDS_FUSED = 10
GRPC_CLIENTS = 4
GRPC_ROUNDS = 3


def require(cond, message):
    """An assertion that survives ``python -O``."""
    if not cond:
        raise AssertionError(message)


def preflight():
    """Refuse anything but a known TPU, before fedtpu is even imported."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(
            f"chip_smoke: jax initialised the {backend!r} backend, not 'tpu' "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); this "
            "script has no CPU mode and nothing was run"
        )
    from importlib.metadata import version

    import jaxlib

    from fedtpu.obs.profile import device_peaks
    from fedtpu.utils.platform import enable_compile_cache

    devices = jax.devices()
    kind = devices[0].device_kind
    peak_flops, peak_hbm = device_peaks(kind)  # raises on an unknown TPU
    cache = enable_compile_cache()
    print(
        f"chip_smoke: backend=tpu device_kind={kind!r} devices={len(devices)} "
        f"peak_bf16={peak_flops:.3g}FLOP/s peak_hbm={peak_hbm:.3g}B/s"
    )
    print(
        f"chip_smoke: python={sys.version.split()[0]} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={version('libtpu')}"
    )
    print(f"chip_smoke: compile cache dir = {cache}")
    return {"platform": devices[0].platform, "kind": kind, "count": len(devices)}


class CompileStats:
    """Compile accounting from ``jax.monitoring``: the wall spent inside
    compile-or-fetch-from-cache, and how many of the cacheable requests the
    persistent cache answered. Listeners stay registered for the life of
    the process; ``take()`` returns the delta since the last call."""

    def __init__(self):
        from jax import monitoring

        self._lock = threading.Lock()  # the gRPC leg compiles on 4 threads
        self._seconds = 0.0
        self._requests = 0
        self._hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self._seconds += seconds

    def _on_event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                self._requests += 1
            elif event == "/jax/compilation_cache/cache_hits":
                self._hits += 1

    def take(self):
        with self._lock:
            out = {
                "compile_s": round(self._seconds, 2),
                "cache_requests": self._requests,
                "cache_hits": self._hits,
            }
            self._seconds, self._requests, self._hits = 0.0, 0, 0
        return out


def timed(fn, sync):
    """Wall seconds of ``fn()`` through ``sync(result)``, and the result."""
    t0 = time.perf_counter()
    out = fn()
    sync(out)
    return time.perf_counter() - t0, out


def finished(fed):
    """A ``sync`` for :func:`timed`: wait until the dispatch that returned
    these metrics (and the state it donated into) is done on the device."""
    import jax

    return lambda metrics: jax.block_until_ready((fed.state, metrics))


def require_on_tpu(tree, what):
    import jax

    for leaf in jax.tree.leaves(tree):
        platforms = {d.platform for d in leaf.devices()}
        require(platforms == {"tpu"}, f"{what} lives on {platforms}, not the TPU")


def require_learning(losses, what):
    require(all(math.isfinite(x) for x in losses), f"{what}: non-finite loss in {losses}")
    require(losses[-1] < losses[0], f"{what}: loss did not fall: {losses}")


def sync_pair(fed, dispatch, what):
    """Two steady dispatches, one ending in ``block_until_ready`` and one in
    a fetched loss. If the first did not really wait for the device it
    would come back in milliseconds; they must agree within 2x."""
    import numpy as np

    t_block, m_block = timed(dispatch, finished(fed))
    t_fetch, m_fetch = timed(dispatch, lambda m: np.asarray(m.loss))
    require(
        0.5 <= t_block / t_fetch <= 2.0,
        f"{what}: block_until_ready wall {t_block:.4f}s vs fetched-loss wall "
        f"{t_fetch:.4f}s disagree",
    )
    return t_block, t_fetch, [m_block, m_fetch]


def leg_engine():
    import numpy as np

    import bench
    from fedtpu.core import Federation
    from fedtpu.data import load

    cfg = bench.headline_config()
    fed = Federation(cfg, seed=0)
    fused = lambda: fed.run_on_device(ROUNDS_FUSED)
    t_fused_first, m0 = timed(fused, finished(fed))
    t_fused_block, t_fused_fetch, ms = sync_pair(fed, fused, "fused dispatch")
    losses = [float(x) for m in [m0] + ms for x in np.asarray(m.loss)]
    t_step_first, s0 = timed(fed.step, finished(fed))
    t_step_block, t_step_fetch, ss = sync_pair(fed, fed.step, "single-round dispatch")
    losses += [float(m.loss) for m in [s0] + ss]
    rounds = 3 * ROUNDS_FUSED + 3
    require(
        int(fed.state.round_idx) == rounds,
        f"round_idx {int(fed.state.round_idx)} after {rounds} rounds",
    )
    require_learning(losses, "engine")
    require_on_tpu(fed.state.params, "global model")
    t_eval, (test_loss, test_acc) = timed(
        lambda: fed.evaluate(*load("cifar10", "test", seed=cfg.data.seed, num=2000)),
        lambda _: None,  # evaluate() returns host floats: already synced
    )
    require(math.isfinite(test_loss), f"evaluate loss {test_loss}")
    return {
        "rounds": rounds,
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "test_acc": round(test_acc, 4),
        "fused10_first_s": round(t_fused_first, 3),
        "fused10_block_until_ready_s": round(t_fused_block, 4),
        "fused10_fetched_loss_s": round(t_fused_fetch, 4),
        "step_first_s": round(t_step_first, 3),
        "step_block_until_ready_s": round(t_step_block, 4),
        "step_fetched_loss_s": round(t_step_fetch, 4),
        "evaluate_first_s": round(t_eval, 3),
    }


def run_cli(extra, rounds=10):
    """``fedtpu.cli.run.main`` on the headline shapes; returns the per-round
    records it wrote and every log line it emitted."""
    import bench
    from fedtpu.cli import run as cli_run

    class Collect(logging.Handler):
        def __init__(self):
            super().__init__(logging.INFO)
            self.lines = []

        def emit(self, record):
            self.lines.append(record.getMessage())

    collect = Collect()
    logging.getLogger().addHandler(collect)
    try:
        with tempfile.TemporaryDirectory() as tmp:
            metrics = os.path.join(tmp, "rounds.jsonl")
            argv = [
                "--platform", "tpu",
                "--model", bench.BENCH_MODEL,
                "--dataset", "cifar10",
                "--partition", "iid",
                "--num-clients", str(bench.NUM_CLIENTS),
                "--batch-size", str(bench.BATCH),
                "--steps-per-round", str(bench.STEPS_PER_ROUND),
                "--num-examples",
                str(bench.NUM_CLIENTS * bench.STEPS_PER_ROUND * bench.BATCH),
                "--rounds", str(rounds),
                "--fused", "5",
                "--eval-every", "5",
                "--delta-layout", "flat",
                "--compression", "topk",
                "--metrics", metrics,
                *extra,
            ]
            t0 = time.perf_counter()
            rc = cli_run.main(argv)
            wall = time.perf_counter() - t0
            with open(metrics) as f:
                records = [json.loads(line) for line in f]
    finally:
        logging.getLogger().removeHandler(collect)
    require(rc == 0, f"fedtpu.cli.run exited {rc}")
    require(len(records) == rounds, f"{len(records)} round records, asked for {rounds}")
    require_learning([r["loss"] for r in records], "cli")
    require(
        all(0 < r["mfu"] <= 1 for r in records),
        f"cli: per-round MFU outside (0, 1]: {[r.get('mfu') for r in records]}",
    )
    require(
        any("backend=tpu" in line for line in collect.lines),
        "the CLI's device line does not say backend=tpu",
    )
    return records, collect.lines, wall


def leg_cli():
    records, _, wall = run_cli(["--mesh", "off"])
    return {
        "rounds": len(records),
        "loss_first": round(records[0]["loss"], 4),
        "loss_last": round(records[-1]["loss"], 4),
        "test_acc": records[-1].get("test_acc"),
        "wall_s": round(wall, 2),
    }


def leg_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from fedtpu import models
    from fedtpu.ops import flat as flat_ops
    from fedtpu.ops import pallas_kernels as pk
    from fedtpu.transport.sparse import _fwht_np

    def layouts(name):
        model = models.create(name, num_classes=10)
        params = jax.eval_shape(
            lambda r: model.init(r, jnp.zeros((1, 32, 32, 3)), train=False),
            jax.random.PRNGKey(0),
        )["params"]
        return flat_ops.make_layout(params), flat_ops.make_layout(params, pow2=True)

    lay, lay_pow2 = layouts(bench.BENCH_MODEL)
    rows = bench.NUM_CLIENTS
    rng = np.random.default_rng(0)
    out = {}
    # The two elementwise kernels: the flat row and the smallest leaf.
    for cols in (lay.padded, min(lay.sizes)):
        y = jnp.asarray(rng.normal(size=(rows, cols)).astype(np.float32))
        # Per-row thresholds that keep a few percent of N(0, 1) draws — what
        # a top-k threshold looks like, without compiling a sort to find it.
        thresh = jnp.linspace(1.5, 2.5, rows, dtype=jnp.float32)
        scale = jnp.max(jnp.abs(y), axis=1) / 127.0
        for name, kernel, body, arg in (
            ("threshold_with_feedback", pk.threshold_with_feedback,
             pk.threshold_with_feedback_jnp, thresh),
            ("quantdequant_int8", pk.quantdequant_int8,
             pk.quantdequant_int8_jnp, scale),
        ):
            require(
                "tpu_custom_call" in kernel.lower(y, arg).as_text(),
                f"{name} did not lower through Mosaic",
            )
            got = jax.tree.leaves(kernel(y, arg))
            want = jax.tree.leaves(jax.jit(body)(y, arg))
            for g, w in zip(got, want):
                require(
                    np.array_equal(np.asarray(g), np.asarray(w)),
                    f"{name} [{rows}, {cols}]: Mosaic and jnp differ",
                )
            t, _ = timed(lambda: kernel(y, arg), jax.block_until_ready)
            out[f"{name}[{rows},{cols}]_s"] = round(t, 5)
    # The rotation (matrix products on every backend) at the pow2-padded
    # rows, held to f32: a single-bf16-pass product of N(0, 1) rows is off
    # by ~1e-2, six passes by ~1e-6.
    widths = {lay_pow2.padded, layouts("densenet_cifar")[1].padded}
    for h in sorted(widths):
        y = rng.normal(size=(rows, h)).astype(np.float32)
        signs = (rng.integers(0, 2, size=h) * 2 - 1).astype(np.float32)
        yd, sd = jnp.asarray(y), jnp.asarray(signs)
        t_first, z = timed(lambda: pk.hadamard_rotate(yd, sd), jax.block_until_ready)
        t, _ = timed(lambda: pk.hadamard_rotate(yd, sd), jax.block_until_ready)
        norm = np.float32(1.0 / math.sqrt(h))
        err_fwd = max(  # host reference on two rows: 20 numpy passes each
            float(np.max(np.abs(np.asarray(z[r]) - _fwht_np(y[r] * signs) * norm)))
            for r in range(2)
        )
        back = pk.hadamard_rotate(z, sd, inverse=True)
        err_back = float(jnp.max(jnp.abs(back - yd)))
        require(
            err_fwd <= 2e-5,
            f"hadamard_rotate [{rows}, {h}] differs from the host butterfly "
            f"by {err_fwd:.3e}",
        )
        require(
            err_back <= 2e-5,
            f"hadamard_rotate [{rows}, {h}]: inverse(forward(y)) differs "
            f"from y by {err_back:.3e}",
        )
        out[f"hadamard_rotate[{rows},{h}]_first_s"] = round(t_first, 3)
        out[f"hadamard_rotate[{rows},{h}]_s"] = round(t, 5)
        out[f"hadamard_rotate[{rows},{h}]_max_abs_err"] = [err_fwd, err_back]
    # The attention core's kernels against the plain body, bfloat16 at the
    # language models' head sizes, three blocks long: output and gradients
    # within bfloat16 rounding of the plain body's largest value. Latent
    # attention's form (a key head each, a rotary operand), the hybrid's
    # (256-wide heads, a key head a group of eight, no rotary operand) and
    # LFM2's (64-wide heads, a key head a group of four whose blocks one grid
    # step takes stacked, no rotary operand).
    from fedtpu.models import lm_layers as lm
    from fedtpu.ops import attention_kernels as ak

    t, heads = 3 * ak.BLOCK, 4
    forms = {
        f"attention_core[{t},{heads}]": (192, [
            (t, heads, 128), (t, heads, 64), (t, heads, 128), (t, 64),
            (t, heads, 128), (t, heads, 128)]),
        f"attention_core[{t},2,8,256]": (256, [
            (t, 2, 8, 256), None, (t, 2, 256), None, (t, 2, 256), (t, 2, 8, 256)]),
        f"attention_core[{t},2,4,64]": (64, [
            (t, 2, 4, 64), None, (t, 2, 64), None, (t, 2, 64), (t, 2, 4, 64)]),
    }
    for name, (width, shapes) in forms.items():
        scale = 1.0 / math.sqrt(width)
        *ops, ct = (None if s is None else jnp.asarray(rng.normal(size=s), jnp.bfloat16)
                    for s in shapes)
        require(ak.takes(*ops), f"the attention kernels do not engage: {name}")
        both = [  # an absent operand is None all the way: a tree without leaves
            jax.jit(lambda *a, f=f, ct=ct: (lambda o, vjp: (o,) + vjp(ct))(
                *jax.vjp(f, *a)))
            for f in (lambda *a, scale=scale: ak.causal_attention(*a, scale),
                      lambda *a, scale=scale: lm.causal_attention(*a, scale, ak.BLOCK))
        ]
        require("tpu_custom_call" in both[0].lower(*ops).as_text(),
                f"the attention core did not lower through Mosaic: {name}")
        t_attn, got = timed(lambda: both[0](*ops), jax.block_until_ready)
        errs = [
            float(jnp.max(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)))
                  / jnp.max(jnp.abs(w.astype(jnp.float32))))
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(both[1](*ops)))
        ]
        require(max(errs) <= 2e-2,
                f"attention kernels differ from the plain body by {errs}: {name}")
        out[f"{name}_first_s"] = round(t_attn, 3)
        out[f"{name}_max_rel_err"] = errs
    # The gated delta rule's kernels against the plain chunks, bfloat16 at the
    # hybrid's head sizes (four key heads of 128 with two value heads each),
    # 1,536 tokens in chunks of 64, gates as the layer makes them: output and
    # the five gradients as shares of the plain chunks' largest value, held to
    # 2e-2 (bfloat16 rounding; the kernels alone read 0 to 0.0075 at 8,192
    # tokens, PERF.md, PR 41).
    from fedtpu.models import qwen3_next as qn
    from fedtpu.ops import delta_rule_kernels as dr

    t, heads, values, width, chunk = 1536, 4, 2, 128, 64
    unit = lambda a: a / np.sqrt(np.sum(a * a, -1, keepdims=True) + 1e-6)
    draw = lambda *shape: rng.normal(size=shape).astype(np.float32)
    ops = (
        jnp.asarray(unit(draw(t, heads, width)) * width ** -0.5, jnp.bfloat16),
        jnp.asarray(unit(draw(t, heads, width)), jnp.bfloat16),
        jnp.asarray(draw(t, heads, values, width), jnp.bfloat16),
        -jax.nn.softplus(jnp.asarray(draw(t, heads, values))),
        jax.nn.sigmoid(jnp.asarray(draw(t, heads, values))),
    )
    ct = jnp.asarray(draw(t, heads, values, width), jnp.bfloat16)
    name = f"gated_delta_rule[{t},{heads},{values},{width}]"
    require(dr.takes(*ops, chunk), f"the delta rule's kernels do not engage: {name}")
    both = [
        jax.jit(lambda *a, f=f: (lambda o, vjp: (o,) + vjp(ct))(*jax.vjp(f, *a)))
        for f in (lambda *a: dr.gated_delta_rule(*a, chunk),
                  lambda *a: qn._plain_chunks(*a, chunk))
    ]
    require("tpu_custom_call" in both[0].lower(*ops).as_text(),
            f"the delta rule did not lower through Mosaic: {name}")
    t_rule, got = timed(lambda: both[0](*ops), jax.block_until_ready)
    errs = [
        float(jnp.max(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)))
              / jnp.max(jnp.abs(w.astype(jnp.float32))))
        for g, w in zip(got, both[1](*ops))
    ]
    print(f"{name}: o, dq, dk, dv, dg, dbeta differ from the plain chunks by "
          f"{errs} of their largest value (limit 2e-2)", flush=True)
    require(max(errs) <= 2e-2,
            f"the delta rule's kernels differ from the plain chunks by {errs}: {name}")
    out[f"{name}_first_s"] = round(t_rule, 3)
    out[f"{name}_max_rel_err"] = errs
    # The held experts' grouped products through the kernels against the plain
    # batched product: a gated layer at widths of whole lanes, and a two-matrix
    # ``relu2`` layer whose width is a lane group and a half (192: Mosaic pads
    # the half group in VMEM, and the padding must enter no sum), a held
    # expert with no pair and one whose last block holds a single live row.
    out.update(experts_against_plain(1536, 256, 128, 4, chunk=1024))
    out.update(experts_against_plain(
        1536, 256, 192, 4, chunk=1024, gated=False, counts=(385, 0, 129, 600)))
    # Mamba-2's selective scan through its two kernels against the plain
    # chunks at the state-space cell's real shape.
    out.update(ssd_against_plain(8192, 64, 64, 8, 128, chunk=128))
    return out


def ssd_against_plain(t, heads, p, groups, n, chunk, seed=0):
    """Mamba-2's selective scan (``mamba2.selective_scan``) of ``t``
    tokens, ``heads`` heads of ``p`` on ``groups`` groups of a state of ``n``
    in chunks of ``chunk``, through the kernels of ``fedtpu.ops.ssd_kernels``
    against the plain chunks, bfloat16, steps from a thousandth to a few (slow
    and fast heads): ``y`` and the gradients of ``x, dt, A, B, C, D`` as shares
    of the plain body's largest value, held to 2e-2; returns them under the
    shape's name."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedtpu.models import mamba2 as nh
    from fedtpu.ops import ssd_kernels as sk

    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    ops = (
        draw(t, heads, p).astype(jnp.bfloat16),
        jax.nn.softplus(3.0 * draw(t, heads) - 2.0), -jnp.exp(draw(heads)),
        draw(t, groups, n).astype(jnp.bfloat16),
        draw(t, groups, n).astype(jnp.bfloat16), draw(heads),
    )
    ct = draw(t, heads, p).astype(jnp.bfloat16)
    name = f"selective_scan[{t},{heads},{p},{groups},{n}]"
    require(sk.takes(*ops, chunk), f"the scan's kernels do not engage: {name}")
    both = [
        jax.jit(lambda *a, f=f: (lambda y, vjp: (y,) + vjp(ct))(*jax.vjp(f, *a)))
        for f in (lambda *a: nh.selective_scan(*a, chunk),
                  lambda *a: nh._plain_chunks(*a, chunk))
    ]
    require("tpu_custom_call" in both[0].lower(*ops).as_text(),
            f"the selective scan did not lower through Mosaic: {name}")
    t_scan, got = timed(lambda: both[0](*ops), jax.block_until_ready)
    errs = [
        float(jnp.max(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)))
              / jnp.max(jnp.abs(w.astype(jnp.float32))))
        for g, w in zip(got, both[1](*ops))
    ]
    print(f"{name}: y, dx, ddt, dA, dB, dC, dD differ from the plain chunks by "
          f"{errs} of their largest value (limit 2e-2)", flush=True)
    require(all(np.isfinite(errs)) and max(errs) <= 2e-2,
            f"the scan's kernels differ from the plain chunks by {errs}: {name}")
    return {f"{name}_first_s": round(t_scan, 3), f"{name}_max_rel_err": errs}


def experts_against_plain(n, d, width, held, chunk, block=128, gated=True,
                          counts=None, seed=0):
    """An expert layer's routed part (``lm_layers.routed_experts``) on ``n``
    tokens of width ``d`` through the kernels of ``fedtpu.ops.expert_kernels``
    against the plain batched product, bfloat16, ``held`` experts of width
    ``width`` in blocks of ``block`` rows and chunks of ``chunk`` pairs: gated
    experts (three stacks) or ``relu2`` ones (two). A token picks two of
    ``2 * held`` scored experts (so some pairs fall on absent experts and a
    held expert may get none) or, with ``counts``, held expert ``e`` is picked
    by ``counts[e]`` tokens. Output and the gradients of the tokens, the gates
    and every weight stack as shares of the plain body's largest value, held
    to 2e-2; returns them under the shape's name."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedtpu.models import lm_layers as lm
    from fedtpu.ops import expert_kernels as ek

    rng = np.random.default_rng(seed)
    draw = lambda *shape: rng.normal(size=shape).astype(np.float32)
    if counts is None:
        chosen = np.argsort(draw(n, 2 * held), axis=1)[:, :2]
        picked, per_token = (chosen[:, :, None] == np.arange(held)).any(1), 2
    else:  # expert e's tokens start where expert e - 1's ended
        first = np.cumsum(counts) - counts
        at = (np.arange(n)[:, None] - first[None, :]) % n
        picked, per_token = at < np.asarray(counts)[None, :], held
    picked = jnp.asarray(picked)
    stacks = [(d, width)] * (2 if gated else 1) + [(width, d)]
    ops = (
        jnp.asarray(draw(n, d), jnp.bfloat16),
        jnp.where(picked, jax.nn.sigmoid(jnp.asarray(draw(n, held))), 0.0),
    ) + tuple(jnp.asarray(draw(held, a, b) * a ** -0.5, jnp.bfloat16)
              for a, b in stacks)
    ct = jnp.asarray(draw(n, d), jnp.bfloat16)
    name = f"routed_experts[{n},{d},{width},{held}]"
    layer = lambda x, gates, *w: lm.routed_experts(
        x, None, gates, picked, w, per_token, chunk, block,
        None if gated else lm.relu2)[0]
    n_blocks = min(chunk, n * min(per_token, held)) // block + held
    require(all(ek.takes(jax.ShapeDtypeStruct((n_blocks * block, w.shape[1]),
                                              jnp.bfloat16), w, block)
                for w in ops[2:]),
            f"the expert kernels do not engage: {name}")
    both, backend = [], ek._mode
    for mode in (backend, lambda interpret: "xla"):  # the kernels, the plain body
        ek._mode = mode
        try:
            f = jax.jit(lambda *a: (lambda o, vjp: (o,) + vjp(ct))(*jax.vjp(layer, *a)))
            text = f.lower(*ops).as_text()
            both.append(jax.block_until_ready(f(*ops)))
        finally:
            ek._mode = backend
        require(("tpu_custom_call" in text) == (not both[1:]),
                f"the expert products took the wrong body: {name}")
    errs = [
        float(jnp.max(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)))
              / jnp.max(jnp.abs(w.astype(jnp.float32))))
        for g, w in zip(*both)
    ]
    print(f"{name}: y, dx, dgates and the {len(stacks)} stacks' gradients differ "
          f"from the plain body by {errs} of their largest value (limit 2e-2)",
          flush=True)
    require(all(np.isfinite(errs)) and max(errs) <= 2e-2,
            f"the expert kernels differ from the plain body by {errs}: {name}")
    return {f"{name}_max_rel_err": errs}


def leg_rotq():
    import dataclasses

    import bench
    from fedtpu.core import Federation

    cfg = bench.headline_config()
    cfg = dataclasses.replace(
        cfg,
        fed=dataclasses.replace(cfg.fed, compression="rotq", delta_layout="flat"),
    )
    fed = Federation(cfg, seed=0)
    t_first, m0 = timed(fed.step, finished(fed))
    t_steady, m1 = timed(fed.step, finished(fed))
    losses = [float(m0.loss), float(m1.loss)]
    require(int(fed.state.round_idx) == 2, "rotq: round_idx did not reach 2")
    require_learning(losses, "rotq")
    require_on_tpu(fed.state.params, "rotq global model")
    return {
        "rounds": 2,
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[1], 4),
        "step_first_s": round(t_first, 3),
        "step_s": round(t_steady, 4),
    }


def free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def leg_grpc():
    import bench
    from fedtpu import native
    from fedtpu.config import DataConfig, FedConfig, RoundConfig
    from fedtpu.transport.federation import PrimaryServer, serve_client

    cfg = RoundConfig(
        model=bench.BENCH_MODEL,
        num_classes=10,
        data=DataConfig(
            dataset="cifar10",
            batch_size=bench.BATCH,
            partition="iid",
            num_examples=GRPC_CLIENTS * bench.STEPS_PER_ROUND * bench.BATCH,
        ),
        fed=FedConfig(
            num_clients=GRPC_CLIENTS,
            num_rounds=GRPC_ROUNDS,
            compression="topk",
            delta_layout="flat",
            server_pipeline="stream",
        ),
    )
    servers, agents, addrs = [], [], []
    evals = []
    try:
        for i in range(GRPC_CLIENTS):
            addr = f"localhost:{free_port()}"
            server, agent = serve_client(addr, cfg, seed=i)
            servers.append(server)
            agents.append(agent)
            addrs.append(addr)
        primary = PrimaryServer(cfg, addrs)
        require(primary.server_pipeline == "stream", "primary is not streaming")
        t0 = time.perf_counter()
        history = primary.run(
            on_round=lambda r, rec: evals.append([a.last_eval for a in agents])
        )
        wall = time.perf_counter() - t0
    finally:
        for server in servers:
            server.stop(0)
    require(len(history) == GRPC_ROUNDS, f"{len(history)} gRPC rounds committed")
    for rec in history:
        require(
            rec["participants"] == GRPC_CLIENTS and rec["pipeline"] == "stream",
            f"gRPC round record {rec}",
        )
    for per_round in evals:
        for loss, _ in per_round:
            require(math.isfinite(loss), f"client eval losses {evals}")
    require_on_tpu(primary.params, "gRPC primary's global model")
    return {
        "rounds": len(history),
        "participants": [rec["participants"] for rec in history],
        "host_codec": native.codec_name(),
        "bytes_up_by_codec": history[-1]["bytes_up_by_codec"],
        "eval_loss_first": round(evals[0][0][0], 4),
        "eval_loss_last": round(evals[-1][0][0], 4),
        "round_first_s": history[0]["t_round_s"],
        "round_last_s": history[-1]["t_round_s"],
        "t_h2d_last_s": history[-1]["t_h2d_s"],
        "t_post_barrier_last_s": history[-1]["t_post_barrier_s"],
        "wall_s": round(wall, 2),
    }


def leg_mesh(n_dev):
    import jax
    import numpy as np

    import bench
    from fedtpu.core import Federation
    from fedtpu.parallel import client_mesh

    cfg = bench.headline_config()
    n = cfg.fed.num_clients
    require(n % n_dev == 0, f"{n} clients do not divide over {n_dev} devices")
    fed = Federation(cfg, seed=0, mesh=client_mesh(n_dev, cfg.mesh_axis))
    fused = lambda: fed.run_on_device(ROUNDS_FUSED)
    t_first, m0 = timed(fused, finished(fed))
    t_steady, m1 = timed(fused, finished(fed))
    losses = [float(x) for m in (m0, m1) for x in np.asarray(m.loss)]
    require(int(fed.state.round_idx) == 2 * ROUNDS_FUSED, "mesh: round_idx")
    require_learning(losses, "mesh")

    def require_sharded(tree, what):
        for leaf in jax.tree.leaves(tree):
            shards = leaf.addressable_shards
            require(
                len({s.device for s in shards}) == n_dev
                and all(s.data.shape[0] == n // n_dev for s in shards),
                f"{what}: {[(str(s.device), s.data.shape) for s in shards]}",
            )

    state = fed.state
    require_sharded(state.opt_state, "momentum")
    require_sharded(
        (state.client_rng, state.last_client_loss), "per-client state"
    )
    require_sharded(fed._ensure_device_data(), "presharded dataset")
    for leaf in jax.tree.leaves((state.params, state.batch_stats)):
        require(
            leaf.sharding.is_fully_replicated
            and len(leaf.addressable_shards) == n_dev,
            f"global model leaf {leaf.shape} is not replicated",
        )
    records, lines, wall = run_cli(["--mesh", "auto"])
    require(
        any(f"clients axis sharded over {n_dev} devices" in line for line in lines),
        "the CLI under --mesh auto did not shard the clients axis",
    )
    return {
        "devices": n_dev,
        "clients_per_device": n // n_dev,
        "sharded": ["momentum", "per-client state", "presharded dataset"],
        "replicated": ["params", "batch_stats"],
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "fused10_first_s": round(t_first, 3),
        "fused10_s": round(t_steady, 4),
        "cli_mesh_auto_wall_s": round(wall, 2),
        "cli_loss_last": round(records[-1]["loss"], 4),
    }


def main():
    t_start = time.perf_counter()
    # Before the CLI leg's own basicConfig (a no-op once handlers exist):
    # every leg logs at INFO, and run_cli can read the CLI's log lines.
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
    )
    device = preflight()
    stats = CompileStats()
    legs = [
        ("engine", leg_engine),
        ("cli", leg_cli),
        ("kernels", leg_kernels),
        ("rotq", leg_rotq),
        ("grpc", leg_grpc),
    ]
    if device["count"] > 1:
        legs.append(("mesh", lambda: leg_mesh(device["count"])))
    for name, leg in legs:
        t0 = time.perf_counter()
        info = leg()
        info = {"leg_wall_s": round(time.perf_counter() - t0, 2), **stats.take(), **info}
        print(f"leg {name}: {json.dumps(info)}", flush=True)
    if device["count"] == 1:
        print("mesh: 1 device, leg not applicable")
    print(f"chip_smoke: every leg passed in {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
