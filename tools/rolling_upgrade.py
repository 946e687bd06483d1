#!/usr/bin/env python
"""Rolling coordinator upgrade drill: primary -> backup -> primary with
ZERO lost rounds and a final global model BIT-IDENTICAL to an unupgraded
control run.

The scripted handover an operator performs to upgrade a coordinator in
place (docs/FAULT_TOLERANCE.md runbook):

1. **Drain gen 1.** The old primary finishes its current round completely
   (aggregate + replicate + broadcast) and stops cleanly at a round
   boundary — no round is half-done, and the backup holds a replica of the
   exact post-round state (model, FedOpt moments, lineage round counter,
   membership roster).
2. **Backup bridges.** The backup's watchdog notices the silence, promotes,
   and keeps committing rounds from the replicated state while the new
   binary rolls out — the federation never stops training.
3. **Gen 2 takes over.** The upgraded primary announces itself
   (recovering ping), the acting primary drains at a round boundary and
   demotes, gen 2 pulls the newer state via FetchModel and finishes the
   run.

What the drill asserts:

- **Zero lost, zero repeated rounds.** Committed round records across all
  three generations carry the LINEAGE round index (the counter rides the
  replica); their concatenation must be exactly ``0..rounds-1``, strictly
  monotone. Every client's local round count equals ``rounds`` — no round
  was retrained either.
- **Bit-identical model.** The final global model equals an unupgraded
  control run byte-for-byte (same seeds, same fleet, same mid-run join) —
  the upgrade is invisible to the training trajectory.
- **Membership rides the replica.** A client admitted mid-run through
  ``admit_client`` (the Join path) must appear in gen 2's roster after the
  two handovers.

Topology: client agents, backup, and both primary generations in THIS
process over real gRPC on localhost — generations are separate
PrimaryServer instances (the process-shaped drill with a SIGKILL instead
of a drain is ``tools/chaos_soak.py``; this drill is about *exactness*,
which needs readable coordinator state).

Usage::

    python tools/rolling_upgrade.py                    # default 12 rounds
    python tools/rolling_upgrade.py --rounds 8 --upgrade-round 3

Writes ``artifacts/ROLLING_UPGRADE.json`` and exits non-zero on any failed
assertion. The tier-1 leg runs this at a reduced scale
(``tests/test_membership.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def tiny_cfg(num_clients: int, rounds: int, **fed_kw):
    from fedtpu.config import (
        DataConfig, FedConfig, OptimizerConfig, RoundConfig,
    )

    return RoundConfig(
        model="mlp",
        num_classes=10,
        opt=OptimizerConfig(learning_rate=0.05, weight_decay=0.0),
        data=DataConfig(
            dataset="synthetic", batch_size=8, eval_batch_size=8,
            num_examples=256,
        ),
        fed=FedConfig(
            num_clients=num_clients, num_rounds=rounds,
            # The background heartbeat thread must not revive clients at
            # wall-clock-dependent moments: drills tick the monitor
            # explicitly so churn stays deterministic (and bit-comparable
            # against a control run).
            ft_heartbeat_period_s=1e6,
            **fed_kw,
        ),
        steps_per_round=2,
    )


def build_fleet(cfg, n: int, seed0: int = 0):
    """n in-process client agents over real gRPC; (addrs, servers, agents)."""
    from fedtpu.transport.federation import serve_client

    addrs, servers, agents = [], [], []
    for i in range(n):
        addr = f"localhost:{free_port()}"
        server, agent = serve_client(addr, cfg, seed=seed0 + i)
        addrs.append(addr)
        servers.append(server)
        agents.append(agent)
    return addrs, servers, agents


def stop_fleet(servers) -> None:
    for s in servers:
        s.stop(0)


def model_fingerprint(primary):
    """Flat host copy of the global model for exact comparison."""
    import jax
    import numpy as np

    leaves = jax.tree_util.tree_leaves(
        {"params": primary.params, "batch_stats": primary.batch_stats}
    )
    return [np.asarray(leaf) for leaf in leaves]


def bit_identical(a, b) -> bool:
    import numpy as np

    return len(a) == len(b) and all(
        x.shape == y.shape and np.array_equal(x, y) for x, y in zip(a, b)
    )


def run_upgrade_drill(
    rounds: int = 12,
    upgrade_round: int = 5,
    clients: int = 3,
    join_round: int = 1,
    acting_window: int = 2,
    watchdog_s: float = 1.5,
    seed: int = 0,
    verbose: bool = True,
) -> dict:
    """The drill + its control run; returns the assertion/result dict."""
    from fedtpu.transport.federation import BackupServer, PrimaryServer

    assert 0 < upgrade_round < rounds, "upgrade must fall inside the run"
    # FedAvgM: the drill must prove the MOMENTS ride the handover too — a
    # plain-FedAvg drill would pass even if they were dropped.
    fed_kw = dict(server_optimizer="momentum")

    def note(msg):
        if verbose:
            print(f"[upgrade] {msg}", flush=True)

    t_start = time.monotonic()
    result: dict = {"config": {
        "rounds": rounds, "upgrade_round": upgrade_round,
        "clients": clients, "join_round": join_round,
        "watchdog_s": watchdog_s, "seed": seed,
    }}

    def run_one(upgraded: bool):
        """One full federation run over a fresh fleet; returns
        (records, fingerprint, agents' round counts, roster, extras)."""
        cfg = tiny_cfg(clients, rounds, **fed_kw)
        addrs, servers, agents = build_fleet(cfg, clients, seed0=seed)
        # The mid-run joiner: a real serving agent NOT in the startup
        # roster; admitted through the membership path at join_round in
        # both runs (so the control stays bit-comparable).
        j_addrs, j_servers, j_agents = build_fleet(cfg, 1, seed0=seed + clients)
        join_addr = j_addrs[0]
        servers.append(j_servers[0])
        agents.append(j_agents[0])
        records = []
        gens: dict = {"gen1": 0, "acting": 0, "gen2": 0}

        def on_round(which):
            def cb(r, rec):
                if not rec.get("aborted"):
                    records.append(rec)
                    gens[which] += 1
                    if rec["round"] == join_round:
                        current[0].admit_client(join_addr)
            return cb

        backup_srv = backup = None
        try:
            if not upgraded:
                primary = PrimaryServer(cfg, addrs)
                current = [primary]
                primary.run(num_rounds=rounds, on_round=on_round("gen1"))
                roster = primary.registry.status()
                return (records, model_fingerprint(primary),
                        [a.trainer.round_idx for a in agents], roster,
                        join_addr, gens)

            backup_addr = f"localhost:{free_port()}"
            record_acting = on_round("acting")

            def on_acting_round(r, rec):
                record_acting(r, rec)
                if gens["acting"] >= acting_window:
                    # Hold the acting primary at this round boundary until
                    # gen 2's recovering ping demotes it: how many rounds
                    # it commits while gen 2 starts up is the host's speed,
                    # and past `rounds` there is no lineage left to hand
                    # over.
                    backup._acting_stop.wait(timeout=60)

            backup = BackupServer(
                cfg, addrs, watchdog_timeout=watchdog_s,
                on_acting_round=on_acting_round,
            )
            # The watchdog measures gen 1's silence on the DRILL's clock,
            # which stands still until gen 1 has drained: on the wall clock
            # a ping held up by a loaded host (1 Hz pings against a ~1 s
            # window) promotes the backup under a primary that is still
            # training, and the drill then exercises split-brain fencing
            # instead of a handover.
            silence = [0.0]
            backup.machine.clock = lambda: silence[0]
            backup_srv = backup.start(backup_addr)
            note(f"gen 1: {upgrade_round} rounds, then drain")
            gen1 = PrimaryServer(cfg, addrs, backup_address=backup_addr)
            current = [gen1]
            gen1.run(num_rounds=upgrade_round, on_round=on_round("gen1"))
            # gen 1 stopped pinging -> the watchdog bridges the gap.
            silence[0] += watchdog_s + 1e-3
            note("waiting for backup promotion + acting rounds")
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                acting = backup.acting
                if acting is not None:
                    current[0] = acting
                    if gens["acting"] >= acting_window:
                        break
                time.sleep(0.1)
            assert backup.acting is not None, "backup never promoted"
            assert gens["acting"] >= 1, "acting primary committed no rounds"
            note("gen 2: recovering ping -> demote, pull state, finish")
            gen2 = PrimaryServer(cfg, addrs, backup_address=backup_addr)
            gen2.pinger.tick()  # demote + FetchModel drain + install
            current[0] = gen2
            remaining = rounds - gen2._round_counter
            assert remaining >= 0, gen2._round_counter
            gen2.run(num_rounds=remaining, on_round=on_round("gen2"))
            roster = gen2.registry.status()
            return (records, model_fingerprint(gen2),
                    [a.trainer.round_idx for a in agents], roster,
                    join_addr, gens)
        finally:
            if backup is not None:
                backup.watchdog.stop()
                backup._stop_acting(wait=30.0)
            if backup_srv is not None:
                backup_srv.stop(0)
            stop_fleet(servers)

    note(f"control run ({rounds} rounds, no upgrade)")
    (c_records, c_model, c_counts, c_roster, _, _) = run_one(upgraded=False)
    note(f"upgrade run (drain at round {upgrade_round})")
    (u_records, u_model, u_counts, u_roster, u_join_addr, gens) = run_one(
        upgraded=True
    )

    lineage = [int(r["round"]) for r in u_records]
    result["lineage"] = {
        "committed": len(lineage),
        "strictly_monotone": lineage == sorted(set(lineage)),
        "exact_cover": lineage == list(range(rounds)),
    }
    result["generations"] = gens
    result["client_round_counts"] = {
        "control": c_counts, "upgraded": u_counts,
    }
    result["roster"] = {"control": c_roster, "upgraded": u_roster}
    result["bit_identical"] = bit_identical(c_model, u_model)
    result["wall_s"] = round(time.monotonic() - t_start, 2)

    assert result["lineage"]["exact_cover"], (
        f"lineage rounds not exactly 0..{rounds - 1}: {lineage}"
    )
    assert gens["gen1"] == upgrade_round and gens["acting"] >= 1, gens
    assert u_counts == c_counts == [rounds] * clients + [
        rounds - 1 - join_round
    ], (
        "client round counts diverged (a round was lost or retrained): "
        f"{c_counts} vs {u_counts}"
    )
    assert result["bit_identical"], (
        "post-upgrade global model differs from the unupgraded control"
    )
    # The mid-run join survived both handovers: gen 2's roster (addresses
    # are fleet-local, so compare shape + the joiner's presence).
    assert u_roster["size"] == c_roster["size"] == clients + 1, (
        c_roster, u_roster,
    )
    assert u_join_addr in u_roster["alive"], (
        "mid-run joiner missing from gen 2's roster after the upgrade"
    )
    result["ok"] = True
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rounds", default=12, type=int)
    ap.add_argument("--upgrade-round", default=5, type=int)
    ap.add_argument("--clients", default=3, type=int)
    ap.add_argument("--watchdog", default=1.5, type=float)
    ap.add_argument("--seed", default=0, type=int)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        result = run_upgrade_drill(
            rounds=args.rounds, upgrade_round=args.upgrade_round,
            clients=args.clients, watchdog_s=args.watchdog, seed=args.seed,
        )
    except AssertionError as exc:
        print(json.dumps({"ok": False, "error": str(exc)}))
        return 1
    art = os.path.join(REPO, "artifacts")
    os.makedirs(art, exist_ok=True)
    with open(os.path.join(art, "ROLLING_UPGRADE.json"), "w") as fh:
        json.dump(result, fh, indent=2)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
