"""``python -m fedtpu.cli.server`` — primary or backup federated server.

Parity with ``python3 server.py`` (``src/server.py:268-301``): ``--p y``
starts the primary round loop against the client registry; without it the
process is the backup (watchdog + promotion). The reference hardcodes the
registry (``src/server.py:281-282``); here it's ``--clients``. Adds what the
reference lacked: checkpoint/resume of the global model every round.
"""

from __future__ import annotations

import argparse
import logging
import time

from fedtpu.cli.common import (
    add_checkpoint_hardening_flags,
    add_fed_flags,
    add_model_flags,
    add_obs_flags,
    add_platform_flag,
    add_profile_flags,
    add_robustness_flags,
    add_telemetry_export_flags,
    apply_platform_flag,
    build_config,
    compress_enabled,
    install_compile_watcher,
    install_final_flush,
    make_capture_window,
    make_chaos,
    make_checkpointer,
    make_flight_recorder,
    start_obs_server,
)
from fedtpu.transport.federation import BackupServer, PrimaryServer


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    add_platform_flag(p)
    add_model_flags(p)
    add_fed_flags(p)
    p.add_argument("--p", default="N", help="y = run as primary")
    p.add_argument(
        "--role", default="auto",
        choices=["auto", "primary", "backup", "aggregator"],
        help="coordinator role. auto (default) keeps the legacy --p "
        "switch: y = primary, else backup. aggregator = a mid-tier leaf "
        "of the hierarchical topology (docs/ARCHITECTURE.md §Multi-tier): "
        "serves SubmitPartial/SendModel on --listen for the root named by "
        "--parent, fans StartTrain out to its --clients cohort, and "
        "forwards one pre-weighted partial sum per round upstream "
        "(requires --tier-fanout on BOTH tiers)",
    )
    p.add_argument(
        "--parent", default=None, metavar="HOST:PORT",
        help="aggregator role: the root's membership gate to announce "
        "this aggregator's --listen address to (omit when the root lists "
        "us statically in its --clients)",
    )
    p.add_argument("--backupAddress", default="localhost")
    p.add_argument("--backupPort", default="50060")
    p.add_argument("--listen", default="localhost:50060",
                   help="bind address (backup and aggregator roles)")
    p.add_argument(
        "--clients",
        default="localhost:50051,localhost:50052",
        help="comma-separated client registry (reference default)",
    )
    p.add_argument("--checkpoint-dir", default=None)
    add_checkpoint_hardening_flags(p)
    p.add_argument(
        "--gate", default=None, metavar="HOST:PORT",
        help="host the membership gate on this address (primary role): a "
        "gRPC listener answering Join/Leave, so clients can enter and "
        "exit the federation at runtime instead of being frozen into "
        "--clients at startup (docs/FAULT_TOLERANCE.md). Joiners are "
        "admitted into the versioned MembershipTable, resynced with the "
        "current global model, and sampled into rounds from then on; the "
        "roster replicates to the backup every round",
    )
    p.add_argument(
        "--metrics", default=None,
        help="JSONL metrics path: one schema-versioned round record "
        "(fedtpu.obs.RoundRecordWriter) per round — participants, wire "
        "bytes, and the collect/decode/H2D/aggregate phase timing the "
        "streaming pipeline reports (see --server-pipeline; summarize "
        "with tools/metrics_report.py)",
    )
    add_telemetry_export_flags(p)
    add_obs_flags(p)
    add_profile_flags(p)
    add_robustness_flags(p)
    p.add_argument("-r", "--resume", action="store_true",
                   help="resume the global model from the latest checkpoint")
    p.add_argument(
        "--watchdog-timeout", default=None, type=float,
        help="backup promotion watchdog window (seconds; default "
        "FedConfig.ft_watchdog_timeout_s = 10.0)",
    )
    p.add_argument(
        "--async-updates",
        default=0,
        type=int,
        metavar="N",
        help="run the FedBuff semi-asynchronous mode for N server updates "
        "instead of synchronous rounds: clients train continuously, the "
        "server aggregates every --buffer-k replies with staleness-"
        "discounted weights (the reference has no async mode)",
    )
    p.add_argument("--buffer-k", default=2, type=int)
    p.add_argument("--staleness-power", default=0.5, type=float)
    p.add_argument(
        "--staleness-damping", default="on", choices=["on", "off"],
        help="on (default): the staleness discount scales the applied "
        "update's magnitude (FedBuff-paper semantics); off: "
        "weight-normalized mean",
    )
    p.add_argument(
        "--round-deadline",
        default=None,
        type=float,
        metavar="SECONDS",
        help="straggler mitigation: aggregate whatever StartTrain replies "
        "arrived within this budget instead of blocking on the slowest "
        "client (stragglers stay alive and rejoin next round). Default: "
        "wait indefinitely (reference behavior, src/server.py:132-135)",
    )
    args = p.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
    )
    apply_platform_flag(args)
    clients = [c.strip() for c in args.clients.split(",") if c.strip()]
    cfg = build_config(args, num_clients=len(clients))
    compress = compress_enabled(args)
    role = args.role
    if role == "auto":
        role = "primary" if str(args.p).lower() == "y" else "backup"

    if role == "aggregator":
        from fedtpu.transport.aggregator import serve_aggregator

        flight = make_flight_recorder("aggregator")
        server, agg = serve_aggregator(
            args.listen,
            cfg,
            clients=clients,
            parent=args.parent,
            compress=compress,
            chaos=make_chaos(args, role="aggregator"),
        )
        agg.flight = flight
        obs = start_obs_server(
            args,
            registry=agg.telemetry.registry,
            status_fn=agg.status_snapshot,
            flight=flight,
        )
        flush = install_final_flush(args, agg.telemetry)
        logging.info(
            "aggregator serving on %s (cohort=%d, parent=%s)",
            args.listen, agg.cohort_size, args.parent or "static",
        )
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            pass
        finally:
            flush()
            agg.stop()
            if obs is not None:
                obs.stop()
            server.stop(0)
        return 0

    if role == "primary":
        # Process-wide black box: armed before anything can fail, handed to
        # the server so spans/rounds/FT events feed the same ring.
        flight = make_flight_recorder("primary")
        chaos = make_chaos(args, role="primary")
        primary = PrimaryServer(
            cfg,
            clients,
            backup_address=f"{args.backupAddress}:{args.backupPort}",
            compress=compress,
            round_deadline_s=args.round_deadline,
            flight=flight,
            chaos=chaos,
        )
        # One hardened checkpoint store (fsync + manifests + generation
        # fallback; background writer unless --checkpoint-sync), sharing
        # the primary's metrics registry, flight recorder and chaos
        # schedule — the disk is part of the same failure domain.
        ckpt = make_checkpointer(
            args, telemetry=primary.telemetry, flight=flight, chaos=chaos,
        )
        start_round = 0
        if ckpt is not None and args.resume:
            # Cold-start recovery: full server state (model + lineage
            # counter + membership roster incl. reputation + FedOpt
            # moments) from the newest VERIFIED generation, falling back
            # past torn/bit-rotten ones; pre-membership and legacy
            # model-only checkpoints restore through the template ladder.
            start_round = primary.restore_from_checkpoint(ckpt) or 0
            if start_round:
                logging.info(
                    "resumed global model from round %d", start_round - 1
                )
        from fedtpu.obs import RoundRecordWriter

        metrics = RoundRecordWriter(path=args.metrics) if args.metrics else None
        # Performance observatory: compile counting on /statusz (the server
        # jits decode/aggregate/screening programs too) + the
        # --profile-rounds device-trace window, driven from on_round below.
        compile_w = install_compile_watcher(
            telemetry=primary.telemetry, flight=flight
        )
        if compile_w is not None:
            primary.compile_watcher = compile_w
        capture = make_capture_window(
            args, role="primary", telemetry=primary.telemetry
        )
        if capture is not None:
            capture.maybe_start(0)
        # Exit-time exporters must survive SIGTERM, not just clean exits;
        # the same idempotent flush also serves the finally below.
        flush = install_final_flush(args, primary.telemetry, metrics=metrics)
        obs = start_obs_server(
            args,
            registry=primary.telemetry.registry,
            status_fn=primary.status_snapshot,
            flight=flight,
            health_fn=primary.health,
        )
        if args.gate:
            primary.start_gate(args.gate)

        def on_round(r: int, rec: dict) -> None:
            if capture is not None:
                # on_round fires AFTER round r: close the window once it is
                # past, (re)arm it for the round about to start.
                capture.maybe_stop(r + 1)
                capture.maybe_start(r + 1)
            if compile_w is not None and not compile_w.steady and r >= 1:
                # Round 0 compiles decode/aggregate (and screening, which
                # jits on its first armed round); by the end of round 1 the
                # steady set has run — later compiles are perf bugs.
                compile_w.mark_steady()
            if metrics is not None:
                metrics.log(start_round + r, **rec)
            # No checkpoint on a sub-quorum abort: the state is unchanged
            # by construction, and the save would just churn the dir.
            if ckpt is not None and not rec.get("aborted"):
                ckpt.save(start_round + r, primary.state_tree())

        # run() (not a bare round() loop) so the heartbeat recovery thread
        # and the backup liveness pinger actually run in the CLI deployment.
        try:
            if args.async_updates:
                primary.run_async(
                    num_updates=args.async_updates,
                    buffer_k=args.buffer_k,
                    staleness_power=args.staleness_power,
                    staleness_damping=args.staleness_damping == "on",
                    on_update=on_round,
                )
            else:
                primary.run(
                    num_rounds=cfg.fed.num_rounds - start_round,
                    on_round=on_round,
                )
        finally:
            if capture is not None:
                capture.stop()  # idempotent: flush a tail-spanning window
            if compile_w is not None:
                compile_w.uninstall()  # listeners are process-global
            if ckpt is not None:
                # Drain the background writer FIRST: the final generation
                # must be durable before the process reports done.
                ckpt.close()
            flush()
            primary.stop_gate()
            if obs is not None:
                obs.stop()
        return 0

    flight = make_flight_recorder("backup")
    backup = BackupServer(
        cfg, clients, compress=compress,
        watchdog_timeout=args.watchdog_timeout,
        round_deadline_s=args.round_deadline,
        flight=flight,
        chaos=make_chaos(args, role="backup"),
    )
    server = backup.start(args.listen)
    obs = start_obs_server(
        args,
        registry=backup.telemetry.registry,
        status_fn=backup.status_snapshot,
        flight=flight,
        health_fn=backup.health,
    )
    logging.info("backup serving on %s", args.listen)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        backup.watchdog.stop()
        if obs is not None:
            obs.stop()
        server.stop(0)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
