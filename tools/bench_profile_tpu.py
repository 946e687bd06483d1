#!/usr/bin/env python
"""Profile the fused multi-round FedAvg program on the TPU chip.

A host sync every round leaves the chip idle between dispatches; this tool
answers "what does the chip actually do when the host is out of the way":

  1. Times the engine's fused 10-round scan (one dispatch = 10 complete
     FedAvg rounds, the same program ``bench.py`` measures) at the bench
     config (smallcnn, 64 clients, batch 128, bf16).
  2. Sweeps per-client batch size upward (256, 512) at fixed
     steps-per-round to show where the MXU saturates — the bench config's
     batch is pinned by reference parity (``src/main.py:47``, batch 128),
     not by what the hardware can do.
  3. Computes a roofline placement per config from XLA cost analysis
     (flops + bytes accessed vs the chip's peak FLOPs and HBM bandwidth):
     reported arithmetic intensity vs the ridge point says whether the
     program is compute- or bandwidth-bound, and utilization says how far
     from that bound the measurement landed.
  4. Captures a ``jax.profiler`` trace of one fused dispatch (bench config)
     under ``artifacts/profile_r04/`` for offline op-level inspection.

Writes ``artifacts/MFU_PROFILE_r04.json`` and prints it. Timing: operands
live on device, every timed dispatch ends by fetching a program output,
median of 3 trials.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

NUM_CLIENTS = 64
STEPS_PER_ROUND = 391 // NUM_CLIENTS
TIMED_ROUNDS = 10
TRIALS = 3
BATCHES = (128, 256, 512)

# FEDTPU_SMOKE=1: tiny shapes so the full code path (compile, time, roofline,
# incremental persist) can be exercised on the CPU backend in seconds. The
# op-trace leg defaults OFF in smoke mode: jax.profiler instrumentation of
# the fused program on the CPU backend runs >300x slower than untraced
# (observed >5 min on a <1 s dispatch); FEDTPU_PROFILE_TRACE=1/0
# overrides either default.
if os.environ.get("FEDTPU_SMOKE"):
    NUM_CLIENTS, STEPS_PER_ROUND, TIMED_ROUNDS, BATCHES = 8, 2, 2, (16, 32)
    # float32 + a single trial: CPU bf16 emulation is ~30x slower than f32
    # (measured 17.7 s for a 2-round smallcnn dispatch) — smoke is about
    # exercising the code path, not the MXU numerics.
    TRIALS, DTYPE = 1, "float32"
    TRACE_DISPATCH = os.environ.get("FEDTPU_PROFILE_TRACE", "0") == "1"
else:
    DTYPE = "bfloat16"
    TRACE_DISPATCH = os.environ.get("FEDTPU_PROFILE_TRACE", "1") == "1"

def _log(msg):
    print(f"[bench_profile_tpu] {msg}", file=sys.stderr, flush=True)


def _measure_config(batch, profile_dir=None):
    import jax
    import numpy as np

    from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig
    from fedtpu.core.engine import Federation
    from fedtpu.obs.profile import device_peaks, roofline

    cfg = RoundConfig(
        model="smallcnn",
        num_classes=10,
        opt=OptimizerConfig(),
        data=DataConfig(
            dataset="cifar10",
            batch_size=batch,
            partition="iid",
            num_examples=NUM_CLIENTS * STEPS_PER_ROUND * batch,
        ),
        fed=FedConfig(num_clients=NUM_CLIENTS),
        steps_per_round=STEPS_PER_ROUND,
        dtype=DTYPE,
    )
    fed = Federation(cfg, seed=0)
    d_images, d_labels, d_idx, d_mask = fed._ensure_device_data()
    import jax.numpy as jnp

    alive = jnp.ones((TIMED_ROUNDS, NUM_CLIENTS), bool)
    multi = fed._multi_step(TIMED_ROUNDS)
    args = (fed.state, d_images, d_labels, d_idx, d_mask, fed.weights,
            alive, fed._data_key)
    _log(f"batch={batch}: compiling fused {TIMED_ROUNDS}-round program")
    step = multi.lower(*args).compile()

    # Roofline inputs from the SINGLE-round program (scan bodies are counted
    # once by cost analysis regardless of trip count — bench.py's convention).
    an = fed._data_step.lower(
        fed.state, d_images, d_labels, d_idx, d_mask, fed.weights,
        jnp.ones((NUM_CLIENTS,), bool), fed._data_key,
    ).compile().cost_analysis()
    flops = float(an.get("flops", 0.0)) or None
    by = float(an.get("bytes accessed", 0.0)) or None

    state = fed.state

    def dispatch(state):
        state, m = step(state, d_images, d_labels, d_idx, d_mask,
                        fed.weights, alive, fed._data_key)
        np.asarray(m.loss)  # sync: fetch a program output
        return state

    _log(f"batch={batch}: warmup dispatch")
    state = dispatch(state)
    times = []
    for i in range(TRIALS):
        t0 = time.perf_counter()
        state = dispatch(state)
        times.append(time.perf_counter() - t0)
    if profile_dir and TRACE_DISPATCH:
        os.makedirs(profile_dir, exist_ok=True)
        _log(f"batch={batch}: tracing one dispatch -> {profile_dir}")
        with jax.profiler.trace(profile_dir):
            state = dispatch(state)
    times.sort()
    sec_per_dispatch = times[len(times) // 2]
    rounds_per_sec = TIMED_ROUNDS / sec_per_dispatch

    kind = jax.devices()[0].device_kind
    # Shared peak table + roofline math (fedtpu.obs.profile) — the same
    # numbers the engine's continuous MFU accounting uses, so a hand sweep
    # and the per-round fedtpu_mfu_ratio gauge can never disagree on peaks.
    peak_f, peak_b = device_peaks(kind)
    row = {
        "batch": batch,
        "rounds_per_sec": round(rounds_per_sec, 3),
        "client_epochs_per_sec_per_chip": round(rounds_per_sec * NUM_CLIENTS, 2),
        "sec_per_fused_dispatch": round(sec_per_dispatch, 4),
        "trial_times_s": [round(t, 4) for t in times],
        "device_kind": kind,
    }
    if flops:
        row["flops_per_round"] = flops
        if peak_f:
            row["mfu"] = round(rounds_per_sec * flops / peak_f, 4)
    if by:
        row["bytes_per_round"] = by
        if peak_b:
            row["hbm_util"] = round(rounds_per_sec * by / peak_b, 4)
    if flops and by and peak_f and peak_b:
        roof = roofline(
            flops, by, peak_f, peak_b,
            achieved_flops_per_s=rounds_per_sec * flops,
        )
        row.update({k: v for k, v in roof.items() if v is not None})
    return row


def run(tag=None):
    """The full sweep: measure every batch config, persist the artifact
    incrementally, return the result dict. ``bench.py --mfu-profile`` calls
    this; ``main()`` below is the standalone CLI wrapper."""
    # FEDTPU_PLATFORM=cpu pins the platform for smoke-testing this script
    # off-chip.
    plat = os.environ.get("FEDTPU_PLATFORM")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)
    # FEDTPU_PROFILE_TAG distinguishes re-measurements (e.g. the presharded
    # data layout vs the r04 gather-layout baseline) without overwriting the
    # earlier artifact.
    if tag is None:
        tag = os.environ.get("FEDTPU_PROFILE_TAG", "r04")
    art = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "artifacts")
    os.makedirs(art, exist_ok=True)
    result = {"timed_rounds_per_dispatch": TIMED_ROUNDS,
              "num_clients": NUM_CLIENTS,
              "steps_per_round": STEPS_PER_ROUND,
              "configs": []}
    profile_dir = os.path.join(art, f"profile_{tag}")
    for i, batch in enumerate(BATCHES):
        try:
            result["configs"].append(
                _measure_config(batch, profile_dir=profile_dir if i == 0 else None)
            )
        except Exception as exc:  # OOM at large batch is a finding, not a crash
            _log(f"batch={batch} failed: {exc!r}")
            result["configs"].append({"batch": batch, "error": repr(exc)[:500]})
        # Persist incrementally: a sweep killed midway keeps the rows
        # measured so far.
        out = os.path.join(art, f"MFU_PROFILE_{tag}.json")
        tmp = out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2)
        os.replace(tmp, out)
    return result


def main():
    print(json.dumps(run()))


if __name__ == "__main__":
    main()
