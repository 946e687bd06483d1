"""``python -m fedtpu.cli.client`` — federated client agent.

Parity with ``python3 client.py -a localhost:50051`` (``src/client.py:55-71``):
hosts the ``Trainer`` gRPC server and trains on StartTrain. Unlike the
reference there are no import-time side effects (``src/client.py:9`` imports
``main``, which parses argv, downloads CIFAR, and builds the model at import —
SURVEY §3.2); everything is constructed explicitly here.
"""

from __future__ import annotations

import argparse
import logging

from fedtpu.cli.common import (
    add_compression_flags,
    add_model_flags,
    add_obs_flags,
    add_platform_flag,
    add_robustness_flags,
    add_telemetry_export_flags,
    apply_platform_flag,
    build_config,
    compress_enabled,
    install_final_flush,
    make_chaos,
    make_flight_recorder,
    start_obs_server,
)
from fedtpu.transport.federation import serve_client


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    add_platform_flag(p)
    add_model_flags(p)
    add_compression_flags(p)
    p.add_argument(
        "--telemetry",
        default="basic",
        choices=["off", "basic", "trace"],
        help="client-side self-measurement level (fedtpu.obs). At 'trace' "
        "the client's spans adopt the coordinator's propagated trace "
        "context (fedtpu-trace-bin metadata), so its --trace-out dump "
        "merges under the coordinator's rounds via tools/trace_merge.py",
    )
    add_telemetry_export_flags(p)
    add_obs_flags(p)
    add_robustness_flags(p)
    p.add_argument("-a", "--address", default="localhost:50051",
                   help="bind address (doubles as the client's identity)")
    p.add_argument("--world", default=2, type=int,
                   help="total client count (for config only; actual world "
                   "arrives with each StartTrain)")
    p.add_argument(
        "--join", default=None, metavar="HOST:PORT",
        help="announce this client to the coordinator's membership gate "
        "(--gate on the server CLI) instead of requiring it in the "
        "server's --clients list: sends Join(address) with retries until "
        "admitted, after which the coordinator resyncs the global model "
        "and samples this client into rounds (docs/FAULT_TOLERANCE.md)",
    )
    p.add_argument(
        "--join-timeout", default=60.0, type=float, metavar="SECONDS",
        help="give up announcing after this long (the gate may start "
        "after the client; Join retries with backoff until then)",
    )
    p.add_argument(
        "--state-dir", default=None, metavar="DIR",
        help="persist this client's local training state (round counter, "
        "optimizer moments, PRNG stream, error-feedback residual) per "
        "round under DIR via the hardened generational checkpoint store, "
        "and restore it on startup: a restarted client then RESUMES its "
        "trajectory instead of silently diverging (fresh residual, "
        "replayed batch draws). The server still resyncs the weights; "
        "this covers the state only this process holds "
        "(docs/OPERATIONS.md §Disaster recovery)",
    )
    p.add_argument(
        "--leave-on-exit", action="store_true",
        help="send Leave(address) to the --join gate on shutdown, so the "
        "coordinator evicts this client (freeing its seat) instead of "
        "probing a silent departure forever",
    )
    args = p.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
    )
    apply_platform_flag(args)
    cfg = build_config(args, num_clients=args.world)
    server, agent = serve_client(
        args.address, cfg, seed=args.seed, compress=compress_enabled(args),
        chaos=make_chaos(args, role=f"client-{args.address}"),
        state_dir=args.state_dir,
    )
    # A client agent exits via signal (it serves until terminated), so the
    # exporters ONLY fire through the SIGTERM/atexit flush.
    install_final_flush(args, agent.trainer.telemetry)
    flight = make_flight_recorder(
        f"client-{args.address}", telemetry=agent.trainer.telemetry
    )
    obs = start_obs_server(
        args, registry=agent.trainer.telemetry.registry,
        status_fn=agent.status_snapshot, flight=flight,
    )
    logging.info("client agent serving on %s", args.address)
    gate_stub = None
    if args.join:
        from fedtpu.transport import announce_join

        gate_stub = announce_join(
            args.join, args.address, timeout_s=args.join_timeout,
        )
        if gate_stub is None:
            logging.error("never admitted by gate %s; serving anyway "
                          "(the coordinator may still list us statically)",
                          args.join)
    try:
        server.wait_for_termination()
    finally:
        if args.leave_on_exit and gate_stub is not None:
            from fedtpu.transport import announce_leave

            announce_leave(gate_stub, args.address)
        if obs is not None:
            obs.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
