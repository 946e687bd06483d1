"""``python -m fedtpu.cli.run`` — TPU-native simulated federation.

The deployment mode the reference cannot do: all clients as one array axis in
a single jitted program on the device mesh (SURVEY §7 design stance). This is
the path that hits the rounds/sec north star; the gRPC server/client CLIs
exist for the reference's multi-process edge topology.
"""

from __future__ import annotations

import argparse
import json
import logging
import time

import numpy as np

from fedtpu.cli.common import (
    add_checkpoint_hardening_flags,
    add_fed_flags,
    add_model_flags,
    add_obs_flags,
    add_platform_flag,
    add_profile_flags,
    add_robustness_flags,
    add_sim_flags,
    add_telemetry_export_flags,
    apply_platform_flag,
    build_config,
    install_compile_watcher,
    install_final_flush,
    make_capture_window,
    make_chaos,
    make_checkpointer,
    make_flight_recorder,
    resolve_mfu_mode,
    start_obs_server,
)
from fedtpu.core import Federation
from fedtpu.data import load
from fedtpu.obs import RoundRecordWriter
from fedtpu.obs.telemetry import setup_snapshot


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    add_platform_flag(p)
    add_model_flags(p)
    add_fed_flags(p)
    p.add_argument("--num-clients", default=2, type=int)
    p.add_argument("--steps-per-round", default=8, type=int)
    add_sim_flags(p)
    p.add_argument(
        "--mesh",
        default="auto",
        choices=["auto", "off"],
        help="auto: when >1 device is visible and num-clients divides evenly, "
        "shard the clients axis over all devices (shard_map + psum FedAvg)",
    )
    p.add_argument(
        "--fused",
        default=1,
        type=int,
        metavar="N",
        help="run rounds in fused blocks of N: each block is ONE XLA program "
        "(lax.scan over the round body) with zero host involvement between "
        "rounds — numerically identical to per-round stepping. Eval and "
        "checkpointing happen at block boundaries. 1 = dispatch per round.",
    )
    p.add_argument(
        "--async-updates",
        default=0,
        type=int,
        metavar="N",
        help="run the ENGINE-side FedBuff async mode for N server updates "
        "instead of synchronous rounds: every live client trains its own "
        "model copy each tick, --buffer-k clients report per tick with "
        "staleness-discounted weights (fedtpu.core.async_engine; the "
        "simulated twin of the gRPC server's --async-updates)",
    )
    p.add_argument("--buffer-k", default=2, type=int)
    p.add_argument("--staleness-power", default=0.5, type=float)
    p.add_argument(
        "--staleness-damping", default="on", choices=["on", "off"],
        help="on (default): the staleness discount scales the applied "
        "update's magnitude (FedBuff-paper semantics — fixes the "
        "homogeneous-speed stall, see fedtpu.core.async_engine); off: "
        "weight-normalized mean (round-4 artifact semantics)",
    )
    p.add_argument(
        "--speed-sigma",
        default=0.0,
        type=float,
        help="client-speed heterogeneity for async arrivals (log-normal "
        "sigma; 0 = uniform). Larger -> slow clients accumulate staleness",
    )
    p.add_argument("--eval-every", default=5, type=int)
    p.add_argument(
        "--metrics", default=None,
        help="JSONL metrics path: one schema-versioned round record per "
        "round (fedtpu.obs.RoundRecordWriter; summarize with "
        "tools/metrics_report.py)",
    )
    add_telemetry_export_flags(p)
    add_obs_flags(p)
    add_profile_flags(p)
    add_robustness_flags(p)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", default=10, type=int)
    add_checkpoint_hardening_flags(p)
    p.add_argument("-r", "--resume", action="store_true")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of the rounds here")
    p.add_argument("--progress", action="store_true",
                   help="per-round progress bar (headless-safe)")
    args = p.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
    )
    apply_platform_flag(args)
    cfg = build_config(
        args,
        # --cohort is the device-buffer size in population mode; it IS
        # num_clients to everything downstream of the config.
        num_clients=args.cohort or args.num_clients,
        steps_per_round=args.steps_per_round,
    )
    if args.async_updates:
        if cfg.fed.sim.population:
            raise SystemExit(
                "--population composes with synchronous rounds only "
                "(the async FedBuff engine keeps per-client model copies — "
                "inherently O(clients) device state)"
            )
        return _run_async(args, cfg)
    # A --profile-rounds window that starts at round 0 opens BEFORE the
    # engine is built: set-up's spans (fed.setup.*) then lie in the capture
    # beside the device's transfers and first execution. A resumed run
    # learns its first round only from the checkpoint, after the build.
    capture = make_capture_window(args, role="engine")
    if capture is not None and not args.resume:
        capture.maybe_start(0)
    if cfg.fed.sim.population:
        from fedtpu.sim import SimFederation

        if _auto_mesh(args) is not None:
            logging.warning(
                "--population runs single-program for now; ignoring the "
                "device mesh"
            )
        fed = SimFederation(cfg, seed=args.seed)
        logging.info(
            "sim population=%d cohort=%d scenario=%s sampler=%s "
            "heterogeneity=%.3f",
            cfg.fed.sim.population, cfg.fed.num_clients, fed.scenario_spec,
            cfg.fed.sim.cohort_sampler, fed._hetero,
        )
    else:
        fed = Federation(cfg, seed=args.seed, mesh=_auto_mesh(args))

    # The simulated engine has no RPC edge; chaos here means crash/latency
    # drills — delay/kill rules on the pseudo-RPC "Round", once per block —
    # plus the ckpt_* disk faults against the checkpoint store below.
    chaos = make_chaos(args, role="engine")
    logger = RoundRecordWriter(path=args.metrics, echo=not args.progress)
    flight = make_flight_recorder("engine", telemetry=fed.telemetry)
    # Performance observatory (fedtpu.obs.profile): compile counting from
    # the very first jit, MFU accounting when the registry is live, and the
    # --profile-rounds device-trace window driven from the round loop.
    compile_w = install_compile_watcher(
        telemetry=fed.telemetry, flight=flight
    )
    if compile_w is not None:
        fed.compile_watcher = compile_w
    mfu_mode = resolve_mfu_mode(args)
    if mfu_mode != "off" and hasattr(fed, "enable_mfu_accounting"):
        profiler = fed.enable_mfu_accounting(xla_check=mfu_mode == "xla")
        logging.info("mfu cost model: %s", profiler.cost.as_dict())
    if capture is not None and fed.telemetry.tracer is not None:
        capture.stamp(fed.telemetry.tracer.trace_id)
    ckpt, start_round, state = _restore_from(
        args, like=fed.state, telemetry=fed.telemetry, flight=flight,
        chaos=chaos,
    )
    if state is not None:
        import jax
        import jax.numpy as jnp

        # Federation's state setter handles mesh re-placement.
        fed.state = jax.tree.map(jnp.asarray, state)
        logging.info("resumed from round %d", start_round)

    flush = install_final_flush(args, fed.telemetry, metrics=logger)
    obs = start_obs_server(
        args,
        registry=fed.telemetry.registry,
        status_fn=fed.status_snapshot,
        flight=flight,
    )
    eval_data = load(
        args.dataset, "test", seed=args.seed, num=args.num_examples
    )
    from fedtpu.utils.progress import ProgressBar, profile_rounds

    bar = (
        ProgressBar(cfg.fed.num_rounds - start_round) if args.progress else None
    )
    t0 = time.time()
    with profile_rounds(args.profile_dir):
        r = start_round
        while r < cfg.fed.num_rounds:
            if chaos is not None:
                chaos.tick_round(r)
            block = min(max(1, args.fused), cfg.fed.num_rounds - r)
            if capture is not None:
                # Fused blocks are captured whole — the profiler cannot cut
                # inside one XLA dispatch.
                capture.maybe_start(r, r + block - 1)
            if block > 1:
                stacked = fed.run_on_device(block)
                # Bulk transfers, not per-round scalar fetches — per-round
                # float() would re-add the host round-trips fusion removes.
                losses = np.asarray(stacked.loss)
                accs = np.asarray(stacked.accuracy)
                actives = np.asarray(stacked.num_active)
                worsts = np.asarray(stacked.per_client_loss).max(axis=1)
                screens = np.asarray(stacked.screened).sum(axis=1)
                per_round = [
                    (float(losses[i]), float(accs[i]), float(actives[i]),
                     float(worsts[i]), int(screens[i]))
                    for i in range(block)
                ]
            else:
                m = fed.step()
                per_round = [
                    (float(m.loss), float(m.accuracy), float(m.num_active),
                     float(np.asarray(m.per_client_loss).max()),
                     int(np.asarray(m.screened).sum()))
                ]
            # Eval/checkpoint cadences in fused mode: mid-block model states
            # never exist on the host, so a cadence point inside a block is
            # honored at the NEXT block boundary (interval-crossing test, not
            # exact alignment — --fused 4 --eval-every 5 still evals ~every 5
            # rounds instead of silently never).
            crossed_eval = args.eval_every and (
                (r + block) // args.eval_every > r // args.eval_every
            )
            from fedtpu.config import screening_enabled

            for i, (loss, acc, active, worst, screened) in enumerate(
                per_round
            ):
                ri = r + i
                rec = {
                    "loss": loss,
                    "acc": acc,
                    "active": active,
                    "worst_client_loss": worst,
                    "dataset": cfg.data.dataset,
                    # 'synthetic' marks loader-fallback runs: their accuracy
                    # curves are not comparable to real-data results.
                    "data_source": fed.data_source,
                }
                if screening_enabled(cfg.fed.screen):
                    rec["screened"] = screened
                    if screened:
                        fed.telemetry.counter(
                            "fedtpu_screening_rejected_total",
                            "client rows rejected by the fused screening "
                            "stage, by surface",
                            labels={"surface": "engine"},
                        ).inc(screened)
                if getattr(fed, "profiler", None) is not None:
                    rec.update(fed.profiler.record_fields())
                if crossed_eval and i == len(per_round) - 1:
                    rec["test_loss"], rec["test_acc"] = fed.evaluate(*eval_data)
                logger.log(ri, **rec)
                if bar is not None:
                    msg = f"loss {rec['loss']:.3f} acc {rec['acc']:.3f}"
                    if "test_acc" in rec:
                        msg += f" test_acc {rec['test_acc']:.3f}"
                    bar.update(ri - start_round, msg)
            if r == start_round and fed.telemetry.enabled:
                # What the engine's set-up cost, phase by phase (the
                # /statusz "setup" block; docs/OBSERVABILITY.md).
                logging.info("set-up: %s", json.dumps(
                    setup_snapshot(ndigits=3), sort_keys=True))
            if compile_w is not None and not compile_w.steady and (
                crossed_eval or not args.eval_every
            ):
                # Every program this loop runs has now compiled (round body
                # + eval); any further compile is a steady-state recompile.
                compile_w.mark_steady()
            prev = r
            r += block
            if capture is not None:
                capture.maybe_stop(r)
            if ckpt is not None and (
                r // args.checkpoint_every > prev // args.checkpoint_every
                or r == cfg.fed.num_rounds
            ):
                ckpt.save(r, fed.state)
    if capture is not None:
        capture.stop()  # idempotent: flush a window that spans the tail
    dt = time.time() - t0
    done = cfg.fed.num_rounds - start_round
    logging.info(
        "%d rounds in %.1fs (%.2f rounds/s)", done, dt, done / max(dt, 1e-9)
    )
    if ckpt is not None:
        ckpt.close()  # drain the background writer before reporting done
    if compile_w is not None:
        compile_w.uninstall()  # listeners are process-global
    # Idempotent with the atexit/SIGTERM registration — crash paths flush
    # the same way this clean exit does.
    flush()
    if obs is not None:
        obs.stop()
    return 0


def _restore_from(args, like, telemetry=None, flight=None, chaos=None):
    """Shared --checkpoint-dir/-r machinery for the sync and async loops:
    ``(checkpointer | None, start_index, restored_state | None)``. The
    checkpointer is the hardened store (fsync + manifests + generation
    fallback on restore, disk-chaos hooks), wrapped in the background
    writer unless --checkpoint-sync. Callers install the state themselves
    — the engines differ (Federation's state setter vs
    AsyncFederation.load_state), both mesh-aware — and own ``close()``."""
    ckpt = make_checkpointer(
        args, telemetry=telemetry, flight=flight, chaos=chaos,
    )
    if ckpt is None:
        return None, 0, None
    if not args.resume:
        return ckpt, 0, None
    latest = ckpt.restore_latest(like=like)
    if latest is None:
        return ckpt, 0, None
    return ckpt, latest[0], latest[1]


def _auto_mesh(args):
    """--mesh auto: shard the clients axis when >1 device is visible and the
    client count divides evenly. One rule for the sync AND async paths."""
    if args.mesh != "auto":
        return None
    import jax

    n_dev = len(jax.devices())
    if n_dev > 1 and args.num_clients % n_dev == 0:
        from fedtpu.parallel import client_mesh

        logging.info("clients axis sharded over %d devices", n_dev)
        return client_mesh()
    return None


def _run_async(args, cfg) -> int:
    """Engine-side FedBuff loop (fedtpu.core.async_engine): --async-updates
    server updates, --fused-sized scan blocks, eval at block boundaries."""
    from fedtpu.core import AsyncFederation

    if args.progress:
        logging.warning("--progress is ignored in async mode")
    fed = AsyncFederation(
        cfg,
        seed=args.seed,
        buffer_k=args.buffer_k,
        staleness_power=args.staleness_power,
        speed_sigma=args.speed_sigma,
        mesh=_auto_mesh(args),
        staleness_damping=args.staleness_damping == "on",
    )
    chaos = make_chaos(args, role="async_engine")
    logger = RoundRecordWriter(path=args.metrics, echo=True)
    flight = make_flight_recorder("async_engine", telemetry=fed.telemetry)
    ckpt, start_tick, state = _restore_from(
        args, like=fed.state, telemetry=fed.telemetry, flight=flight,
        chaos=chaos,
    )
    if state is not None:
        fed.load_state(state)  # async re-placement (mesh-aware)
        logging.info("resumed async state from update %d", start_tick)
    flush = install_final_flush(args, fed.telemetry, metrics=logger)
    obs = start_obs_server(
        args,
        registry=fed.telemetry.registry,
        status_fn=fed.status_snapshot,
        flight=flight,
    )
    eval_data = load(
        args.dataset, "test", seed=args.seed, num=args.num_examples
    )
    from fedtpu.utils.progress import profile_rounds

    t0 = time.time()
    with profile_rounds(args.profile_dir):
        _async_loop(args, fed, logger, eval_data, ckpt, start_tick, chaos)
    dt = time.time() - t0
    done = max(0, args.async_updates - start_tick)  # executed THIS run
    logging.info(
        "%d async updates in %.1fs (%.2f updates/s)",
        done, dt, done / max(dt, 1e-9),
    )
    if ckpt is not None:
        ckpt.close()
    flush()
    if obs is not None:
        obs.stop()
    return 0


def _async_loop(args, fed, logger, eval_data, ckpt=None, start_tick=0,
                chaos=None) -> None:
    # Same resume semantics as the sync loop: --async-updates is the TOTAL
    # update count, a resumed run finishes the remainder.
    t = start_tick
    while t < args.async_updates:
        if chaos is not None:
            chaos.tick_round(t)
        block = min(max(1, args.fused), args.async_updates - t)
        if block > 1:
            m = fed.run_on_device(block)
            losses = np.asarray(m.loss)
            stale = np.asarray(m.staleness_mean)
            rows = [
                (float(losses[i]), float(stale[i])) for i in range(block)
            ]
        else:
            m = fed.tick()
            rows = [(float(m.loss), float(m.staleness_mean))]
        crossed_eval = args.eval_every and (
            (t + block) // args.eval_every > t // args.eval_every
        )
        for i, (loss, stal) in enumerate(rows):
            rec = {
                "loss": loss,
                "staleness": stal,
                "buffer_k": args.buffer_k,
                "dataset": fed.cfg.data.dataset,
                "data_source": fed.data_source,
            }
            if crossed_eval and i == len(rows) - 1:
                rec["test_loss"], rec["test_acc"] = fed.evaluate(*eval_data)
            logger.log(t + i, **rec)
        t += block
        if ckpt is not None:
            crossed_ckpt = args.checkpoint_every and (
                t // args.checkpoint_every
                > (t - block) // args.checkpoint_every
            )
            if crossed_ckpt or t >= args.async_updates:
                # checkpoint.save owns the host transfer for every caller
                # (and the background writer snapshots before enqueue).
                ckpt.save(t, fed.state)


if __name__ == "__main__":
    raise SystemExit(main())
