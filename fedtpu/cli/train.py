"""``python -m fedtpu.cli.train`` — standalone single-node training.

Parity with the reference's original trainer surface (``src/main.py``:
``--lr``, ``-r/--resume``, per-epoch test with best-accuracy checkpointing)
without its import-time side effects. LR schedule defaults to constant —
the reference's effective behavior, since its cosine scheduler is never
stepped (``src/main.py:231-242``); pass ``--schedule cosine`` for the
schedule it intended.
"""

from __future__ import annotations

import argparse
import logging

from fedtpu.cli.common import (
    add_model_flags,
    add_obs_flags,
    add_platform_flag,
    add_robustness_flags,
    apply_platform_flag,
    build_config,
    make_chaos,
    make_flight_recorder,
    start_obs_server,
)
from fedtpu.core.solo import run_solo
from fedtpu.obs import RoundRecordWriter, StatusBoard


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    add_platform_flag(p)
    add_model_flags(p)
    add_obs_flags(p)
    add_robustness_flags(p)
    p.add_argument("--epochs", default=200, type=int,
                   help="training epochs (reference default: 200)")
    p.add_argument("--checkpoint", default="./checkpoint/solo.fckpt",
                   help="best-accuracy checkpoint path")
    p.add_argument("-r", "--resume", action="store_true")
    p.add_argument("--metrics", default=None, help="JSONL metrics path")
    p.add_argument(
        "--mesh",
        default="off",
        choices=["auto", "off"],
        help="auto: when >1 device is visible and the batch divides evenly, "
        "shard each batch across all devices with pmean'd grads (intra-node "
        "data parallelism — the reference's DataParallel, src/main.py:79-81)",
    )
    args = p.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
    )
    apply_platform_flag(args)
    cfg = build_config(args, num_clients=1)
    mesh = None
    if args.mesh == "auto":
        import jax

        n_dev = len(jax.devices())
        if n_dev > 1 and cfg.data.batch_size % n_dev == 0:
            from fedtpu.parallel import client_mesh

            mesh = client_mesh(axis_name="batch")
            logging.info("batch axis sharded over %d devices", n_dev)
    # Solo has no Telemetry registry; its /statusz feed is the per-epoch
    # record mirrored onto a StatusBoard by the logger wrapper below.
    status = StatusBoard(role="solo", phase="train", round=0)
    flight = make_flight_recorder("solo")
    obs = start_obs_server(args, status_fn=status.snapshot, flight=flight)
    # Solo has no RPC edge either: chaos delay/kill rules fire once per
    # epoch via the per-epoch logger hook (crash-recovery drills for the
    # best-accuracy checkpoint path).
    chaos = make_chaos(args, role="solo")

    class _StatusLogger(RoundRecordWriter):
        def log(self, step: int, **fields) -> None:
            if chaos is not None:
                chaos.tick_round(step)
            status.update(
                round=step,
                **{k: v for k, v in fields.items()
                   if isinstance(v, (int, float))},
            )
            super().log(step, **fields)

    trainer = run_solo(
        cfg,
        epochs=args.epochs,
        seed=args.seed,
        checkpoint_path=args.checkpoint,
        resume=args.resume,
        logger=_StatusLogger(path=args.metrics),
        mesh=mesh,
    )
    logging.info("best test accuracy: %.4f", trainer.best_acc)
    if obs is not None:
        obs.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
