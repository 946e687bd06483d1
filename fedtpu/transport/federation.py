"""Distributed (multi-process) federation over the gRPC edge.

This is the process topology the reference implements (``src/server.py`` /
``src/client.py``): a primary server dialing out to client agents that each
host a ``Trainer`` gRPC server, with a backup server for failover. fedtpu
keeps the topology — it is the cross-pod/DCN deployment shape — but every
host-side sin is replaced:

- model payloads are raw wire bytes, not base64 pickle files on disk
  (:mod:`fedtpu.transport.wire` vs ``src/client.py:19-29``);
- aggregation is one jitted weighted mean on device, not a host loop over
  checkpoint files (vs ``src/server.py:155-179``), and it never averages in
  stale state from dead clients (reference bug, ``src/server.py:157``);
- client local training is the same jitted ``local_update`` the simulated
  engine uses (:mod:`fedtpu.core.client`), so single-process simulation and
  multi-process deployment run identical math;
- failure detection/failover is the event-driven machinery of
  :mod:`fedtpu.ft`, not signal handlers.

For intra-pod scale the simulated engine (:class:`fedtpu.core.Federation`)
is strictly faster — this module exists for the reference's deployment model:
genuinely separate processes/hosts federating over a network edge.
"""

from __future__ import annotations

import json
import logging
import math
import os
import random
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import grpc
import jax
import jax.numpy as jnp
import numpy as np

from fedtpu import models as model_zoo
from fedtpu.config import (
    RoundConfig,
    resolve_server_pipeline,
    screening_enabled,
    validate_retry_policy,
    validate_screen_config,
    validate_tier_config,
)
from fedtpu.core.client import make_eval_fn, make_local_update
from fedtpu.core import optim
from fedtpu.data import load, dataset_info
from fedtpu.data import partition
from fedtpu.ft import (
    ClientRegistry,
    FailoverStateMachine,
    HeartbeatMonitor,
    MembershipTable,
    PrimaryPinger,
    WatchdogRunner,
)
from fedtpu.obs import (
    FlightRecorder,
    StatusBoard,
    Telemetry,
    process_rss_bytes,
)
from fedtpu.obs import propagate
from fedtpu.obs.registry import Counter
from fedtpu.transport import proto, sparse, wire
from fedtpu.transport.codec_policy import AdaptiveCodecPolicy
from fedtpu.transport.retry import call_with_retry, is_stale_coordinator
from fedtpu.transport.service import (
    TrainerServicer,
    TrainerStub,
    create_channel,
    create_server,
    probe,
    trace_context_of,
)
from fedtpu.utils.platform import enable_compile_cache

log = logging.getLogger("fedtpu.federation")


def _model_template(model, cfg: RoundConfig):
    """(params, batch_stats) zero-templates for wire decode."""
    shape = dataset_info(cfg.data.dataset)[0]
    variables = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1,) + shape, jnp.float32), train=False),
        jax.random.PRNGKey(0),
    )
    zeros = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), variables)
    return zeros["params"], zeros.get("batch_stats", {})


def _payload_template(model, cfg: RoundConfig):
    params, stats = _model_template(model, cfg)
    return {
        "params": params,
        "batch_stats": stats,
        "num_examples": np.zeros((), np.float32),
    }


# FSP1 record kind -> codec name, for the per-codec wire accounting
# (fedtpu_rpc_bytes_*_total{codec=...} and the /statusz byte table). Dense
# FTP1 payloads carry no kind and count as "none".
_CODEC_OF_KIND = {
    "topk": "topk",
    "topk_flat": "topk",
    "int8": "int8",
    "int8_flat": "int8",
    "rotq_flat": "rotq",
    "randk_flat": "randk",
    "partial_flat": "partial",
}


def _sum_codec_bytes(pairs) -> Dict[str, int]:
    """Fold (codec_name, nbytes) pairs into a {codec: total_bytes} dict."""
    out: Dict[str, int] = {}
    for codec_name, nb in pairs:
        out[codec_name] = out.get(codec_name, 0) + int(nb)
    return out


# --------------------------------------------------------------------- client
class LocalTrainer:
    """Client-side training engine: the jitted single-client local update.

    Mirrors the reference client's semantics (``src/main.py:128-165``): on
    StartTrain the *weights* are whatever the last SendModel delivered, while
    the optimizer state persists locally across rounds (the reference keeps
    its torch optimizer alive in the module global, ``src/main.py:99``).
    """

    # Per-round local-state snapshots retained for coordinator-replay
    # rollback (see _train_round_impl): bounded ring, newest rounds win.
    SNAPSHOT_KEEP = 4

    def __init__(self, cfg: RoundConfig, seed: int = 0,
                 state_dir: Optional[str] = None):
        self.cfg = cfg
        self.telemetry = Telemetry(cfg.fed.telemetry, role="client")
        n_classes = dataset_info(cfg.data.dataset)[1]
        if cfg.num_classes != n_classes:
            raise ValueError(
                f"cfg.num_classes={cfg.num_classes} but dataset "
                f"'{cfg.data.dataset}' has {n_classes} classes"
            )
        enable_compile_cache()
        self.model = model_zoo.create(cfg.model, num_classes=cfg.num_classes)
        self.images, self.labels = load(
            cfg.data.dataset, "train", seed=cfg.data.seed, num=cfg.data.num_examples
        )
        self.eval_images, self.eval_labels = load(
            cfg.data.dataset, "test", seed=cfg.data.seed, num=cfg.data.num_examples
        )
        sample = jnp.zeros((1,) + tuple(self.images.shape[1:]), jnp.float32)
        variables = self.model.init(jax.random.PRNGKey(seed), sample, train=False)
        self.params = variables["params"]
        self.batch_stats = variables.get("batch_stats", {})
        self.opt_state = optim.init(self.params, cfg.opt)
        self.rng = jax.random.PRNGKey(seed + 1)
        self.round_idx = 0
        self._local_update = jax.jit(make_local_update(self.model.apply, cfg))
        self._evaluate = make_eval_fn(self.model.apply, cfg)
        # Sparse-delta mode needs the client's round-start model to equal the
        # server's global; until the first SendModel lands we fall back to
        # dense full-weight payloads.
        self.synced = False
        # Edge error feedback: mass dropped by top-k is carried locally into
        # the next round's delta (the host-side analogue of
        # fedtpu.ops.compression residuals).
        self.edge_residual = None
        # Byzantine self: an armed FaultSchedule whose ATTACK_KINDS rules
        # make THIS client adversarial (fedtpu.ft.chaos.decide_attack —
        # keyed on `identity`, the client's serving address). None = honest.
        self.chaos = None
        self.identity = "self"
        # Dense f32 wire size of one full model payload — the denominator
        # of the compression-ratio gauge (codec bytes / dense bytes).
        self._dense_bytes = sum(
            int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(
                {"params": self.params, "batch_stats": self.batch_stats}
            )
        )
        # Cold-start client durability (docs/OPERATIONS.md §Disaster
        # recovery): the server resyncs a restarted client's WEIGHTS, but
        # the local round counter, optimizer moments, PRNG stream, and the
        # edge error-feedback residual live only in this process — losing
        # them silently diverges the client (a fresh residual re-injects
        # mass top-k already shipped; a reset round counter replays old
        # batch draws). With ``state_dir`` set, that local state persists
        # per round through the hardened generational Checkpointer (fsync
        # + manifest + fallback) and restores on construction.
        self._snapshots: Dict[int, dict] = {}
        self._state_ckpt = None
        if state_dir:
            from fedtpu.checkpoint import Checkpointer

            self._state_ckpt = Checkpointer(
                state_dir, keep=3, backend="wire",
                metrics=self.telemetry.registry if self.telemetry.enabled
                else None,
            )
            self._restore_client_state()

    def _shard(self, rank: int, world: int):
        """This client's rows of the deterministic ``world``-way partition.
        All clients compute the same global partition from the shared data
        seed, so shards are disjoint without any coordination — the
        distributed analogue of the engine's partitioner dispatch
        (``fedtpu/core/engine.py``)."""
        cfg = self.cfg
        if cfg.data.partition == "round_robin":
            idx, mask = partition.round_robin(
                len(self.images), world, cfg.data.batch_size
            )
        elif cfg.data.partition == "iid":
            idx, mask = partition.iid(len(self.images), world, seed=cfg.data.seed)
        elif cfg.data.partition == "dirichlet":
            idx, mask = partition.dirichlet(
                self.labels, world, alpha=cfg.data.dirichlet_alpha, seed=cfg.data.seed
            )
        else:
            raise ValueError(f"unknown partition {cfg.data.partition}")
        return idx[rank : rank + 1], mask[rank : rank + 1]

    # ------------------------------------------------- local-state durability
    def _residual_template(self) -> dict:
        return {
            "params": jax.tree.map(
                lambda l: np.zeros(l.shape, l.dtype), self.params
            ),
            "batch_stats": jax.tree.map(
                lambda l: np.zeros(l.shape, l.dtype), self.batch_stats
            ),
        }

    def _client_state(self) -> dict:
        """The client-local state one wire blob must capture for a cold
        restart to RESUME rather than diverge: the local round counter,
        PRNG key, optimizer moments, and the error-feedback residual
        (``has_residual`` distinguishes "no residual yet" from a zero
        residual)."""
        residual = self.edge_residual
        return {
            "round_idx": np.asarray(self.round_idx, np.int64),
            "rng": np.asarray(self.rng),
            "opt_state": jax.tree.map(np.asarray, self.opt_state),
            "has_residual": np.asarray(
                0 if residual is None else 1, np.int8
            ),
            "residual": (
                jax.tree.map(np.asarray, residual)
                if residual is not None else self._residual_template()
            ),
        }

    def _install_client_state(self, tree: dict) -> None:
        self.round_idx = int(tree["round_idx"])
        self.rng = jnp.asarray(tree["rng"])
        self.opt_state = jax.tree.map(jnp.asarray, tree["opt_state"])
        self.edge_residual = (
            jax.tree.map(np.asarray, tree["residual"])
            if int(tree["has_residual"]) else None
        )

    def _restore_client_state(self) -> None:
        try:
            latest = self._state_ckpt.restore_latest(self._client_state())
        except (ValueError, OSError) as exc:
            log.warning(
                "client state in %s unusable (%s); starting fresh",
                self._state_ckpt.directory, exc,
            )
            return
        if latest is None:
            return
        r, tree = latest
        self._install_client_state(tree)
        # Seed the rollback ring with the restored cut, so a coordinator
        # replaying exactly this round (the common recovery alignment)
        # needs no further unwinding.
        self._snapshot_round(self.round_idx)
        log.info(
            "client state restored: resuming at local round %d "
            "(residual=%s)", self.round_idx,
            "yes" if self.edge_residual is not None else "no",
        )

    def _persist_client_state(self) -> None:
        if self._state_ckpt is not None:
            # Non-fatal by construction (hardened Checkpointer): a full
            # state disk degrades the client's restartability, never its
            # participation in the current round.
            self._state_ckpt.save(self.round_idx, self._client_state())

    def _snapshot_round(self, round_idx: int) -> None:
        """Host snapshot of the round-START local state, for replay
        rollback. Ring-bounded: older than SNAPSHOT_KEEP rounds falls off
        (a deeper replay than the checkpoint keep-window cannot happen —
        the coordinator's own fallback is bounded by its retention)."""
        self._snapshots[round_idx] = {
            "params": jax.tree.map(np.asarray, self.params),
            "batch_stats": jax.tree.map(np.asarray, self.batch_stats),
            "rng": np.asarray(self.rng),
            "opt_state": jax.tree.map(np.asarray, self.opt_state),
            "residual": (
                jax.tree.map(np.asarray, self.edge_residual)
                if self.edge_residual is not None else None
            ),
        }
        for r in sorted(self._snapshots):
            if len(self._snapshots) <= self.SNAPSHOT_KEEP:
                break
            del self._snapshots[r]

    def _rollback(self, target_round: int) -> bool:
        snap = self._snapshots.get(target_round)
        if snap is None:
            # Deeper than the in-memory ring (e.g. this client ALSO cold-
            # restarted and only seeded its newest cut): the on-disk state
            # generations under state_dir may still hold the target round.
            # They carry no params — the coordinator's pre-round broadcast
            # re-bases the weights in every recovery flow.
            if self._state_ckpt is None:
                return False
            try:
                tree = self._state_ckpt.restore(
                    target_round, self._client_state()
                )
            except (ValueError, OSError):
                return False
            self._install_client_state(tree)
            for r in [r for r in self._snapshots if r > target_round]:
                del self._snapshots[r]
            return True
        self.round_idx = target_round
        self.params = jax.tree.map(jnp.asarray, snap["params"])
        self.batch_stats = jax.tree.map(jnp.asarray, snap["batch_stats"])
        self.rng = jnp.asarray(snap["rng"])
        self.opt_state = jax.tree.map(jnp.asarray, snap["opt_state"])
        self.edge_residual = (
            jax.tree.map(np.asarray, snap["residual"])
            if snap["residual"] is not None else None
        )
        # Everything after the restored cut is now an alternate history.
        for r in [r for r in self._snapshots if r > target_round]:
            del self._snapshots[r]
        return True

    def train_round(self, rank: int, world: int,
                    trace_ctx: Optional[propagate.TraceContext] = None,
                    coord_round: int = -1,
                    codec_override: Optional[str] = None) -> bytes:
        """One local epoch on this client's shard; returns the wire payload
        (trained weights + stats + example count). ``trace_ctx`` — the
        coordinator's propagated trace context, when the StartTrain carried
        one: the span below then records the federation ``trace_id`` plus
        ``remote_parent``/``remote_role`` so ``tools/trace_merge.py`` can
        nest this client's work under the coordinator's round span, and the
        tracer adopts the federation trace id. ``coord_round`` — the
        coordinator's lineage round from the TrainRequest (-1 from older
        peers): a value BEHIND this client's local counter means the
        coordinator recovered from a checkpoint older than the rounds this
        client already trained, and the local state rolls back to match
        (see _train_round_impl). ``codec_override`` — the coordinator's
        per-round codec choice from ``TrainRequest.codec`` (the adaptive
        policy); None keeps the static configured codec."""
        tel = self.telemetry
        propagate.adopt(tel.tracer, trace_ctx)
        with tel.span("client_train", rank=rank, round=self.round_idx,
                      **propagate.span_args(trace_ctx)):
            payload = self._train_round_impl(
                rank, world, coord_round, codec_override
            )
        self._persist_client_state()
        tel.counter(
            "fedtpu_client_tx_bytes_total",
            "StartTrain reply payload bytes shipped by this client",
        ).inc(len(payload))
        tel.gauge(
            "fedtpu_client_compression_ratio",
            "last reply's wire bytes / dense model payload bytes",
        ).set(len(payload) / max(self._dense_bytes, 1))
        return payload

    def _train_round_impl(self, rank: int, world: int,
                          coord_round: int = -1,
                          codec_override: Optional[str] = None) -> bytes:
        cfg = self.cfg
        # Coordinator-replay rollback (disaster recovery): a StartTrain
        # whose lineage round is BEHIND our local counter means the
        # coordinator cold-restarted from a checkpoint generation older
        # than the rounds we already trained (its fallback past corrupt
        # generations rewound the lineage). Training "forward" from our
        # newer local state would silently fork the trajectory — instead
        # rewind to the round-start snapshot of the replayed round, so the
        # re-run reproduces the original round bit-for-bit. A coordinator
        # AHEAD of us (participation sampling, stragglers) is ordinary
        # drift and keeps the existing semantics.
        if 0 <= coord_round < self.round_idx:
            local_was = self.round_idx
            if self._rollback(coord_round):
                log.warning(
                    "coordinator replays round %d (local counter was %d): "
                    "rolled local state back to the matching snapshot",
                    coord_round, local_was,
                )
            else:
                log.warning(
                    "coordinator replays round %d but no local snapshot "
                    "survives (local counter %d); training forward — "
                    "trajectories may diverge", coord_round, self.round_idx,
                )
        self._snapshot_round(self.round_idx)
        # Model-level attack consult (fedtpu.ft.chaos ATTACK_KINDS): one
        # decision per training round, keyed on this client's identity and
        # local round. label_flip poisons THIS round's training labels;
        # delta kinds poison only the SUBMITTED payload below — the
        # attacker's own local state stays its honest trajectory, exactly
        # like a real adversary running an unmodified trainer with a
        # poisoned send hook.
        atk_round = self.round_idx
        atk = (
            self.chaos.decide_attack(self.identity, atk_round)
            if self.chaos is not None else None
        )
        own, own_mask = self._shard(rank, world)
        num_examples = float(own_mask.sum())
        # One epoch = the shard's batch count; local_epochs multiplies it
        # (same fold as the simulated engine, fedtpu/core/engine.py).
        steps = max(1, int(own_mask[0].sum()) // cfg.data.batch_size) * max(
            1, cfg.fed.local_epochs
        )
        x, y, step_mask = partition.make_client_batches(
            self.images,
            self.labels,
            own,
            own_mask,
            cfg.data.batch_size,
            steps,
            seed=cfg.data.seed + self.round_idx,
        )
        if atk is not None and atk.kind == "label_flip":
            y = (np.asarray(y) + atk.label_offset) % cfg.num_classes
        self.rng, step_rng = jax.random.split(self.rng)
        start_params, start_stats = self.params, self.batch_stats
        out = self._local_update(
            start_params,
            start_stats,
            self.opt_state,
            jnp.asarray(x[0]),
            jnp.asarray(y[0]),
            jnp.asarray(step_mask[0]),
            step_rng,
            jnp.asarray(self.round_idx, jnp.int32),
        )
        self.params = out.params
        self.batch_stats = out.batch_stats
        self.opt_state = out.opt_state
        self.round_idx += 1
        send_params, send_stats = out.params, out.batch_stats
        if atk is not None and atk.kind in ("sign_flip", "scale", "noise"):
            honest = jax.tree.map(
                lambda a, b: np.asarray(a) - np.asarray(b),
                {"params": out.params, "batch_stats": out.batch_stats},
                {"params": start_params, "batch_stats": start_stats},
            )
            hostile = self.chaos.apply_attack_delta(
                atk, honest, self.identity, atk_round
            )
            sent = jax.tree.map(
                lambda s, d: (np.asarray(s) + d).astype(np.asarray(s).dtype),
                {"params": start_params, "batch_stats": start_stats},
                hostile,
            )
            send_params, send_stats = sent["params"], sent["batch_stats"]

        # Per-round codec: the coordinator's adaptive choice when the
        # StartTrain carried one (TrainRequest.codec), else the static
        # configured codec — a legacy coordinator never sends the field and
        # nothing changes.
        codec = codec_override or cfg.fed.compression
        if codec in ("topk", "int8", "rotq", "randk") and self.synced:
            # Ship the sparse/quantized *delta* — the wire actually shrinks,
            # unlike the reference's gzip-over-dense (src/server.py:104-107).
            delta = jax.tree.map(
                lambda a, b: np.asarray(a) - np.asarray(b),
                {"params": send_params, "batch_stats": send_stats},
                {"params": start_params, "batch_stats": start_stats},
            )
            extra = {"num_examples": np.float32(num_examples)}
            ef = cfg.fed.error_feedback
            # delta_layout='flat' ships ONE contiguous record (index/value
            # or int8 block + offsets table) instead of a per-leaf map —
            # the wire twin of the engine's flat pipeline. The server's
            # template-based sparse.decode dispatches on the record kind,
            # so mixed fleets decode either form. The seeded sketch codecs
            # (rotq / randk) are inherently flat records — there is no
            # per-leaf variant.
            if cfg.fed.delta_layout == "flat":
                enc_topk, enc_int8 = sparse.encode_topk_flat, sparse.encode_int8_flat
            else:
                enc_topk, enc_int8 = sparse.encode_topk, sparse.encode_int8
            # Seeded codecs: the record seed is a pure function of (round,
            # rank) so a replayed round re-encodes byte-identically (the
            # coordinator-replay recovery path, and the bit-identical-replay
            # pins in tests/test_properties.py) while distinct clients draw
            # decorrelated rotations/index sets. atk_round is the
            # round-START counter captured above.
            sketch_seed = (atk_round << 16) | (rank & 0xFFFF)
            if codec == "topk":
                encode = lambda d, r: enc_topk(
                    d, cfg.fed.topk_fraction, residuals=r, extra=extra,
                    collect_residual=ef)
            elif codec == "int8":
                encode = lambda d, r: enc_int8(
                    d, residuals=r, extra=extra, collect_residual=ef)
            elif codec == "rotq":
                encode = lambda d, r: sparse.encode_rotq_flat(
                    d, bits=cfg.fed.rotq_bits, residuals=r, extra=extra,
                    collect_residual=ef, seed=sketch_seed)
            else:  # randk
                encode = lambda d, r: sparse.encode_randk_flat(
                    d, cfg.fed.topk_fraction, residuals=r, extra=extra,
                    collect_residual=ef, seed=sketch_seed)
            payload, residual = encode(delta, self.edge_residual if ef else None)
            if ef:
                # The residual is a dense model-space tree, so it carries
                # UNCHANGED across adaptive lossy->lossy codec switches —
                # no rescale needed (the rescale-or-reset rule,
                # docs/OPERATIONS.md §Adaptive codec).
                self.edge_residual = residual
            return payload

        payload = {
            "params": send_params,
            "batch_stats": send_stats,
            "num_examples": np.float32(num_examples),
        }
        if (
            self.edge_residual is not None
            and self.synced
            and cfg.fed.error_feedback
        ):
            # The other half of the rescale-or-reset rule: switching to the
            # dense codec FLUSHES the accumulated error-feedback residual
            # into this round's full-weight payload (weights + residual ==
            # what the lossy stream would eventually have delivered), then
            # resets it — dropped mass is never silently lost across a
            # switch to 'none'.
            res = self.edge_residual
            payload["params"] = jax.tree.map(
                lambda w, r: (np.asarray(w) + np.asarray(r)).astype(
                    np.asarray(w).dtype
                ),
                payload["params"], res["params"],
            )
            payload["batch_stats"] = jax.tree.map(
                lambda w, r: (np.asarray(w) + np.asarray(r)).astype(
                    np.asarray(w).dtype
                ),
                payload["batch_stats"], res["batch_stats"],
            )
            self.edge_residual = None
        return wire.encode(payload, compress=codec != "none")

    def set_global(self, data: bytes,
                   trace_ctx: Optional[propagate.TraceContext] = None) -> None:
        propagate.adopt(self.telemetry.tracer, trace_ctx)
        with self.telemetry.span("install_global",
                                 **propagate.span_args(trace_ctx)):
            params, stats = _model_template(self.model, self.cfg)
            tree = wire.decode(data, {"params": params, "batch_stats": stats})
            self.params = jax.tree.map(jnp.asarray, tree["params"])
            self.batch_stats = jax.tree.map(jnp.asarray, tree["batch_stats"])
            self.synced = True
        self.telemetry.counter(
            "fedtpu_client_rx_bytes_total",
            "global-model broadcast bytes received by this client",
        ).inc(len(data))

    def evaluate(self) -> Tuple[float, float]:
        bs = self.cfg.data.eval_batch_size
        nb = max(1, len(self.eval_images) // bs)
        xs = self.eval_images[: nb * bs].reshape(
            (nb, bs) + self.eval_images.shape[1:]
        )
        ys = self.eval_labels[: nb * bs].reshape((nb, bs))
        loss, acc = self._evaluate(
            self.params, self.batch_stats, jnp.asarray(xs), jnp.asarray(ys)
        )
        return float(loss), float(acc)


class ClientAgent(TrainerServicer):
    """The gRPC servicer a federated client hosts (parity:
    ``src/client.py:15-35``). StartTrain trains and returns weights; SendModel
    installs the global model and evaluates it; HeartBeat answers liveness."""

    def __init__(self, cfg: RoundConfig, seed: int = 0,
                 state_dir: Optional[str] = None):
        self.trainer = LocalTrainer(cfg, seed=seed, state_dir=state_dir)
        self.last_eval: Optional[Tuple[float, float]] = None
        # Coordinator fencing (docs/FAULT_TOLERANCE.md §Fencing): the max
        # coordinator epoch this client has ever seen. A coordinator-
        # originated RPC carrying a LOWER epoch comes from a superseded
        # primary (a healed partition's stale side) and is rejected with a
        # typed STALE_COORDINATOR status — accepting it would fork the
        # lineage. -1 until any epoch-carrying peer speaks (pre-fencing
        # coordinators never advertise one and are never rejected).
        self._max_epoch = -1
        self._epoch_lock = threading.Lock()

    def _fence_check(self, epoch: int, rpc: str, context) -> None:
        """Track the max coordinator epoch; abort a stale sender. Aborting
        raises, so callers just invoke this first."""
        if epoch < 0:
            return  # pre-fencing peer: no epoch advertised
        with self._epoch_lock:
            if epoch >= self._max_epoch:
                self._max_epoch = epoch
                return
            newest = self._max_epoch
        log.warning(
            "%s from stale coordinator epoch %d rejected (newest seen %d)",
            rpc, epoch, newest,
        )
        self.trainer.telemetry.counter(
            "fedtpu_ft_stale_rejected_total",
            "coordinator RPCs rejected for a stale fencing epoch, by rpc",
            labels={"rpc": rpc},
        ).inc()
        context.abort(
            grpc.StatusCode.FAILED_PRECONDITION,
            f"STALE_COORDINATOR: epoch {epoch} < {newest}",
        )

    def StartTrain(self, request: proto.TrainRequest, context) -> proto.TrainReply:
        self._fence_check(request.epoch, "StartTrain", context)
        payload = self.trainer.train_round(
            request.rank, request.world,
            trace_ctx=trace_context_of(context),
            coord_round=request.round,
            # Adaptive-codec choice (field 5): 0/unknown ids fall back to
            # the static configured codec, so an unrecognized id from a
            # newer coordinator degrades safely instead of crashing.
            codec_override=proto.CODEC_NAMES.get(request.codec),
        )
        return proto.TrainReply(message=payload)

    def SendModel(self, request: proto.SendModelRequest, context) -> proto.SendModelReply:
        self._fence_check(request.epoch, "SendModel", context)
        self.trainer.set_global(
            request.model, trace_ctx=trace_context_of(context)
        )
        self.last_eval = self.trainer.evaluate()
        log.info("global model installed: eval %s", self.last_eval)
        return proto.SendModelReply(reply=f"{self.last_eval[1]:.4f}".encode())

    def HeartBeat(self, request: proto.Request, context) -> proto.HeartBeatResponse:
        return proto.HeartBeatResponse(status=1)

    def status_snapshot(self) -> dict:
        """``/statusz`` feed for a client agent process."""
        t = self.trainer
        return {
            "role": t.telemetry.role or "client",
            "pid": os.getpid(),
            "round": t.round_idx,
            "synced": t.synced,
            "last_eval": (
                {"loss": self.last_eval[0], "acc": self.last_eval[1]}
                if self.last_eval else None
            ),
        }


def serve_client(
    address: str, cfg: RoundConfig, seed: int = 0, compress: bool = False,
    chaos=None, state_dir: Optional[str] = None,
):
    """Build + start a client agent server on ``address`` (parity:
    ``serve``, ``src/client.py:38-52``). Returns (server, agent).
    ``chaos`` (a :class:`fedtpu.ft.chaos.FaultSchedule`) arms fault
    injection on this agent's INBOUND RPCs — the client-side half of a
    chaos drill. ``state_dir`` persists the client's local training state
    per round so a restarted agent resumes instead of silently diverging
    (``--state-dir`` on the client CLI; docs/OPERATIONS.md)."""
    agent = ClientAgent(cfg, seed=seed, state_dir=state_dir)
    # The bind address doubles as the client's trace/flight identity.
    agent.trainer.telemetry.role = f"client:{address}"
    agent.trainer.identity = address
    if chaos is not None:
        chaos.attach(metrics=agent.trainer.telemetry.registry
                     if agent.trainer.telemetry.enabled else None)
        # ATTACK_KINDS rules in the schedule make this client Byzantine:
        # the trainer consults them per round (decide_attack) and poisons
        # its submissions/labels accordingly.
        agent.trainer.chaos = chaos
    server = create_server(address, agent, compress=compress, chaos=chaos)
    server.start()
    return server, agent


# -------------------------------------------------------------------- primary
class PrimaryServer:
    """The FedAvg orchestrator (parity: ``run()``, ``src/server.py:113-153``).

    Per round: fan out StartTrain(rank, world) to active clients, aggregate
    the returned weights with one jitted weighted mean, replicate to the
    backup, broadcast to clients. RpcErrors mark clients dead; the heartbeat
    monitor revives + resyncs them.
    """

    def __init__(
        self,
        cfg: RoundConfig,
        clients: List[str],
        backup_address: Optional[str] = None,
        compress: bool = False,
        seed: int = 0,
        initial_model: Optional[bytes] = None,
        rpc_timeout: Optional[float] = None,
        round_deadline_s: Optional[float] = None,
        flight: Optional[FlightRecorder] = None,
        chaos=None,
    ):
        """``round_deadline_s``: straggler mitigation — wait at most this
        long for StartTrain replies each round, then aggregate whatever
        arrived. Stragglers stay ALIVE (they still get the broadcast and
        rejoin next round), unlike RpcError failures; the reference's
        barrier blocks on its slowest client unconditionally
        (``src/server.py:132-135``). None = reference behavior.

        ``rpc_timeout``: legacy blanket deadline — when given it overrides
        the per-RPC data-plane deadlines of ``cfg.fed.retry`` (the typed
        :class:`fedtpu.config.RetryPolicy` that replaced the old scattered
        constants). ``chaos``: a :class:`fedtpu.ft.chaos.FaultSchedule` —
        every outbound channel then carries the fault-injection
        interceptor (deterministic, seeded; see docs/FAULT_TOLERANCE.md).
        """
        self.cfg = cfg
        self.compress = compress
        self.round_deadline_s = round_deadline_s
        rp = validate_retry_policy(cfg.fed.retry)
        self.retry_policy = rp
        # Per-RPC deadlines from the policy; an explicit rpc_timeout= keeps
        # the old blanket-override surface for the data-plane RPCs.
        self._deadlines = {
            "StartTrain": rpc_timeout if rpc_timeout is not None
            else rp.start_train_timeout_s,
            "SendModel": rpc_timeout if rpc_timeout is not None
            else rp.send_model_timeout_s,
            "FetchModel": rpc_timeout if rpc_timeout is not None
            else rp.fetch_model_timeout_s,
            "HeartBeat": rp.probe_timeout_s,
            "CheckIfPrimaryUp": rp.backup_ping_timeout_s,
        }
        # Legacy attribute: the data-plane deadline some callers/tests read.
        self.rpc_timeout = self._deadlines["SendModel"]
        if not 0.0 <= cfg.fed.round_quorum <= 1.0:
            raise ValueError(
                f"round_quorum must be in [0, 1], got {cfg.fed.round_quorum}"
            )
        self.chaos = chaos
        # The resolved timing surface, logged once so operators can read a
        # run's effective deadlines off the startup log instead of chasing
        # constants through the source (docs/OPERATIONS.md).
        log.info(
            "transport timings: start_train=%.1fs send_model=%.1fs "
            "fetch_model=%.1fs probe=%.1fs backup_ping=%.1fs "
            "heartbeat_period=%.1fs retries=%d backoff=%.2fs*%.1f<=%.1fs "
            "round_quorum=%.2f chaos=%s",
            self._deadlines["StartTrain"], self._deadlines["SendModel"],
            self._deadlines["FetchModel"], self._deadlines["HeartBeat"],
            self._deadlines["CheckIfPrimaryUp"],
            cfg.fed.ft_heartbeat_period_s, rp.max_attempts, rp.backoff_s,
            rp.backoff_multiplier, rp.backoff_max_s, cfg.fed.round_quorum,
            chaos.describe() if chaos is not None else "off",
        )
        self.telemetry = Telemetry(cfg.fed.telemetry, role="primary")
        # Flight recorder: bounded black box of recent spans, round marks,
        # and warning+ events — dumpable at any moment (obs/flight.py). The
        # CLI passes one with the process hooks armed; library users get a
        # buffer they can dump by hand / read over /flightz.
        self.flight = flight if flight is not None else FlightRecorder(
            role="primary"
        )
        if self.telemetry.tracer is not None:
            self.telemetry.tracer.sink = self.flight.record_span
        # Live status feed for /statusz (obs/http.py): the round loop
        # updates round/phase as it moves; status_snapshot() adds the
        # registry-backed liveness/failure context.
        self.status = StatusBoard(role="primary", phase="init", round=0)
        # XLA compile observability (obs/profile.py): the CLI installs a
        # CompileWatcher and hands it over so /statusz can surface compile
        # counts + steady-state recompile warnings.
        self.compile_watcher = None
        enable_compile_cache()
        self.model = model_zoo.create(cfg.model, num_classes=cfg.num_classes)
        shape = dataset_info(cfg.data.dataset)[0]
        variables = self.model.init(
            jax.random.PRNGKey(seed), jnp.zeros((1,) + shape, jnp.float32), train=False
        )
        self.params = variables["params"]
        self.batch_stats = variables.get("batch_stats", {})
        from fedtpu.core import server_opt as server_opt_lib

        if cfg.fed.aggregator not in ("mean", "median", "trimmed_mean", "krum"):
            raise ValueError(
                f"unknown aggregator {cfg.fed.aggregator!r}; "
                "have mean | median | trimmed_mean | krum"
            )
        # Robust aggregators silently ignore example-count weights; say it
        # once at startup and stamp every round record (satellite of the
        # Byzantine PR — the silence read as a bug to operators).
        self._weights_ignored = False
        if cfg.fed.weighted:
            from fedtpu.core.round import warn_weighted_robust

            self._weights_ignored = warn_weighted_robust(cfg.fed.aggregator)
        if cfg.fed.aggregator != "mean":
            if cfg.fed.compression != "none":
                raise ValueError(
                    f"aggregator={cfg.fed.aggregator!r} cannot compose with "
                    "delta compression: sparse deltas zero out coordinate-"
                    "wise robust statistics. Use compression='none'."
                )
            if not 0.0 <= cfg.fed.trim_fraction < 0.5:
                raise ValueError(
                    f"trim_fraction must be in [0, 0.5), got "
                    f"{cfg.fed.trim_fraction}"
                )
        if cfg.fed.dp_clip_norm > 0:
            # Same soundness guards as the simulated engine
            # (fedtpu.core.round.make_round_step / init_state).
            if cfg.fed.compression != "none":
                raise ValueError(
                    "DP clipping cannot compose with delta compression. "
                    "Use compression='none'."
                )
            if cfg.fed.weighted:
                raise ValueError(
                    "DP requires uniform weighting (FedConfig(weighted=False))."
                )
            if cfg.fed.aggregator != "mean":
                raise ValueError(
                    "DP noise accounting assumes aggregator='mean'."
                )
            if jax.tree_util.tree_leaves(self.batch_stats):
                raise ValueError(
                    "DP requires a BatchNorm-free model: batch statistics "
                    "are released unclipped. Pick a model without "
                    "batch_stats (e.g. mlp)."
                )
        if cfg.fed.compression not in ("none", "topk", "int8", "rotq", "randk"):
            raise ValueError(
                f"unknown compression {cfg.fed.compression!r}; "
                "have none | topk | int8 | rotq | randk"
            )
        if cfg.fed.codec_policy not in ("static", "adaptive"):
            raise ValueError(
                f"unknown codec_policy {cfg.fed.codec_policy!r}; "
                "have static | adaptive"
            )
        # Adaptive codec selection (docs/OPERATIONS.md §Adaptive codec): the
        # round loop ships a per-client codec choice in TrainRequest.codec,
        # learned from observed bytes x RTT. Lossy codecs may be chosen any
        # round, so the combination must satisfy the same constraints a
        # static lossy codec would.
        self._codec_policy: Optional[AdaptiveCodecPolicy] = None
        if cfg.fed.codec_policy == "adaptive":
            if cfg.fed.delta_layout != "flat":
                raise ValueError(
                    "codec_policy='adaptive' requires delta_layout='flat': "
                    "the sketch codecs it selects among (rotq/randk) only "
                    "exist as flat records"
                )
            if cfg.fed.aggregator != "mean" or cfg.fed.dp_clip_norm > 0:
                raise ValueError(
                    "codec_policy='adaptive' can select lossy codecs, so it "
                    "needs aggregator='mean' and no DP clipping (the same "
                    "constraints as a static lossy codec)"
                )
            self._codec_policy = AdaptiveCodecPolicy()
        # Cumulative per-codec wire-byte ledger for /statusz (the labeled
        # twins of the unlabeled rpc byte counters; kinds map to codec
        # names via _CODEC_OF_KIND). Guarded by its own lock: collect
        # workers write while /statusz reads.
        self._codec_bytes_up: Dict[str, int] = {}
        self._codec_bytes_lock = threading.Lock()
        self._server_opt = server_opt_lib.make_server_optimizer(cfg.fed)
        self._server_opt_state = server_opt_lib.init(cfg.fed, self.params)
        # Monotonic count of aggregations performed across this model
        # lineage's *entire* life — seeds DP noise and participation
        # subsampling, rides in the replica payload, and is restored by
        # _install so a promoted backup (or recovering primary) never
        # replays earlier rounds' PRNG draws. len(self.history) cannot
        # serve: history restarts at 0 in every new server process.
        self._round_counter = 0
        # --- Coordinator fencing (docs/FAULT_TOLERANCE.md §Fencing) ------
        # role: 1 = configured primary, 2 = acting (promoted backup) — rides
        # on SendModelRequest.role so receivers/flight can attribute the
        # sender without decoding the payload. epoch: minted monotonically
        # on every promotion or post-fence re-base; replicated in the
        # replica payload and persisted in the checkpoint template ladder,
        # so a lineage's epoch survives restarts. _fenced flips when any
        # receiver rejects us with STALE_COORDINATOR — the round loop then
        # voids the in-flight round and re-bases (handle_fence).
        # _epoch_seen: the largest epoch any rejection has told us about,
        # so the re-base mints PAST the winner even if the backup is
        # unreachable during the heal.
        self._role = 1
        self._fenced = False
        self._epoch_seen = -1
        self._fence_lock = threading.Lock()
        # Pacing between re-base attempts while the winning lineage is
        # still unreachable (handle_fence keeps the fence up until the
        # recovering handshake actually lands).
        self._fence_retry_s = 0.5
        self._set_epoch(1)
        # Seeded retry jitter: when chaos is armed, backoff jitter draws
        # from a schedule-seeded stream instead of the global random, so a
        # soak's retry timing replays deterministically under one seed.
        self._retry_rand = (
            random.Random(chaos.seed ^ 0xFE17CE).random
            if chaos is not None else None
        )

        _metrics = self.telemetry.registry if self.telemetry.enabled else None
        if chaos is not None:
            chaos.attach(metrics=_metrics, flight=self.flight)
        # The mutable, versioned roster (fedtpu.ft.membership): `clients`
        # is only the STARTUP roster — members join/leave at runtime
        # through the membership gate (start_gate / admit_client /
        # remove_client), and a replica payload installed below may replace
        # the roster wholesale with the previous primary's current one.
        self.registry = MembershipTable(clients, metrics=_metrics)
        # Every outbound channel (StartTrain/SendModel fan-out, heartbeat
        # probes, backup pings/replication/FetchModel) carries the
        # trace-propagation interceptor; _trace_source yields None below
        # trace mode, so the interceptor is a single no-op call then. The
        # chaos interceptor (when armed) wraps outermost, keyed by peer.
        # Guarded by _member_lock: the gate's admit/evict mutates this dict
        # while collect workers read it.
        self._member_lock = threading.Lock()
        self._stubs: Dict[str, TrainerStub] = {
            c: self._make_stub(c) for c in clients
        }
        self._gate_server = None
        self.backup_stub = (
            TrainerStub(create_channel(
                backup_address, compress=compress,
                trace_source=self._trace_source, chaos=chaos))
            if backup_address
            else None
        )
        self.monitor = HeartbeatMonitor(
            self.registry,
            probe=self._probe_member,
            resync=self._resync,
            period=cfg.fed.ft_heartbeat_period_s,
            metrics=_metrics,
            # Concurrent probes are bounded per tick by the worst-case
            # single probe: per-attempt deadline plus the backoff budget.
            probe_deadline_s=(
                rp.max_attempts
                * (rp.probe_timeout_s + rp.backoff_max_s) + 1.0
            ),
        )
        self.pinger = (
            PrimaryPinger(self._ping_backup, metrics=_metrics)
            if self.backup_stub else None
        )
        self._aggregate = jax.jit(self._aggregate_impl)
        # Streaming collect pipeline (server_pipeline="stream", resolved
        # from the config — "auto" streams for the flat delta layout):
        # replies decode into rows of ONE flat [clients, P] buffer and ship
        # to the device as they arrive, so the post-barrier work is a
        # single fused finalize instead of per-leaf decode/stack/transfer
        # behind the slowest client. See round() and docs/PERF_ANALYSIS.md.
        self.server_pipeline = resolve_server_pipeline(cfg.fed)
        if self.server_pipeline == "stream":
            from fedtpu.ops import flat as flat_ops

            params_t, stats_t = _model_template(self.model, cfg)
            self._flat_layout = flat_ops.make_layout(
                {"params": params_t, "batch_stats": stats_t}
            )
            # Donated row write: XLA aliases input and output, so each
            # arriving row is an in-place update of the device buffer, not
            # a [clients, P] copy.
            self._set_row = jax.jit(
                lambda buf, row, i: jax.lax.dynamic_update_slice(
                    buf, row[None], (i, 0)
                ),
                donate_argnums=0,
            )
            self._finalize_stream = jax.jit(self._finalize_stream_impl)
        # Hierarchical multi-tier aggregation (docs/ARCHITECTURE.md
        # §Multi-tier): tier_fanout > 0 flips this server into the ROOT of
        # a two-tier topology — the roster holds leaf AggregatorServer
        # addresses, each round's fan-out is one SubmitPartial pull per
        # aggregator, the stream buffer holds [aggregators, P] pre-weighted
        # partial SUMS (row-axis sharded across local devices), and the
        # finalize divides ONCE over the summed weights
        # (_finalize_partial_impl — the exact-associativity contract that
        # keeps the 2-tier mean bit-identical to the flat one).
        self.tier_fanout = cfg.fed.tier_fanout
        if self.tier_fanout:
            validate_tier_config(cfg.fed, "PrimaryServer")
            # The pull shares the training-RPC deadline: a SubmitPartial
            # blocks on the leaf's whole cohort collect, i.e. the same
            # critical path StartTrain bounds one tier down.
            self._deadlines["SubmitPartial"] = self._deadlines["StartTrain"]
            self._finalize_partial = jax.jit(self._finalize_partial_impl)
        # Fused update screening (ScreenConfig, docs/FAULT_TOLERANCE.md):
        # one jitted stats pass over the round's [participants, P] rows —
        # the SAME device-resident buffer the stream finalize reads, so the
        # collect path gains zero extra device syncs — whose verdicts (a)
        # drop rejected rows from the combine through the existing
        # exclusion-by-order mask and (b) feed the per-client suspicion
        # EWMA driving quarantine -> eviction on the MembershipTable.
        self._screen_jit = None
        if screening_enabled(cfg.fed.screen):
            from fedtpu.ops import flat as flat_ops

            sc = validate_screen_config(cfg.fed.screen)
            self._screen_cfg = sc
            params_t, stats_t = _model_template(self.model, cfg)
            self._screen_layout = flat_ops.make_layout(
                {"params": params_t, "batch_stats": stats_t}
            )
            self._screen_jit = jax.jit(
                lambda rows, live: flat_ops.screen_rows(
                    rows, live, sc.norm_max, sc.zmax, sc.cos_min
                )
            )
        self.history: List[dict] = []
        self._did_initial_sync = False
        # Straggler StartTrain threads still in flight from earlier rounds,
        # keyed by client (see round()).
        self._inflight: Dict[str, threading.Thread] = {}
        # Broadcast SendModel threads still in flight from earlier rounds —
        # tracked like _inflight so next round's send to the same client
        # cannot race a stale one and install an older model last.
        self._sends: Dict[str, threading.Thread] = {}
        # Install the seed state LAST: a replica payload carries the
        # previous primary's membership roster, and adopting it needs the
        # registry and stub plumbing above to exist.
        if initial_model is not None:
            self._install(initial_model)

    # ----------------------------------------------------------- aggregation
    def _aggregate_impl(
        self, global_tree, stacked_deltas, weights, opt_state, round_idx
    ):
        """global + combined client deltas over the stacked axis — one jitted
        program, same math as the simulated engine's aggregator; dead clients
        never enter the stack so no mask is needed here. ``cfg.fed.aggregator``
        selects the combine (weighted mean, or coordinate-wise median /
        trimmed mean — robust combiners ignore the example-count weights).
        DP (clip per client, seeded noise on the combined delta) mirrors the
        engine's round step. The optional server optimizer (FedOpt family,
        fedtpu.core.server_opt) consumes the combined params-delta; BN stats
        combine the same way, mirroring the simulated round step."""
        from fedtpu.core import server_opt as server_opt_lib
        from fedtpu.core.round import _dp_clip, _dp_noise

        fed = self.cfg.fed
        total = jnp.maximum(jnp.sum(weights), 1e-9)

        def mean(d):
            w = weights.reshape((-1,) + (1,) * (d.ndim - 1)).astype(d.dtype)
            return jnp.sum(d * w, axis=0) / total.astype(d.dtype)

        def robust(d):
            xf = d.astype(jnp.float32)
            if fed.aggregator == "median":
                out = jnp.median(xf, axis=0)
            else:  # trimmed_mean; data-point bounds so the band is never empty
                lo = jnp.quantile(
                    xf, fed.trim_fraction, axis=0, keepdims=True,
                    method="lower",
                )
                hi = jnp.quantile(
                    xf, 1.0 - fed.trim_fraction, axis=0, keepdims=True,
                    method="higher",
                )
                band = (xf >= lo) & (xf <= hi)
                out = jnp.sum(jnp.where(band, xf, 0.0), axis=0) / jnp.maximum(
                    jnp.sum(band, axis=0), 1
                )
            return out.astype(d.dtype)

        if fed.dp_clip_norm > 0:
            stacked_deltas = dict(
                stacked_deltas,
                params=_dp_clip(stacked_deltas["params"], fed.dp_clip_norm),
            )
        if fed.aggregator == "krum":
            from fedtpu.core.round import _krum_over_clients

            # Joint selection over params + stats; the stack holds only
            # successful replies, so every row is "alive".
            deltas = _krum_over_clients(
                stacked_deltas,
                jnp.ones((weights.shape[0],), jnp.float32),
                None,
                fed.trim_fraction,
            )
        else:
            combine = mean if fed.aggregator == "mean" else robust
            deltas = jax.tree.map(combine, stacked_deltas)
        if fed.dp_clip_norm > 0 and fed.dp_noise_multiplier > 0:
            n = jnp.asarray(weights.shape[0], jnp.float32)
            std = fed.dp_clip_norm * fed.dp_noise_multiplier / jnp.maximum(n, 1.0)
            deltas = dict(
                deltas,
                params=_dp_noise(
                    deltas["params"], std, round_idx,
                    seed=self.cfg.data.seed ^ 0x5F5E5F,
                ),
            )
        new_params, new_opt = server_opt_lib.apply(
            self._server_opt, global_tree["params"], deltas["params"], opt_state
        )
        new_stats = jax.tree.map(
            lambda g, d: g + d, global_tree["batch_stats"], deltas["batch_stats"]
        )
        return {"params": new_params, "batch_stats": new_stats}, new_opt

    def _finalize_stream_impl(self, global_tree, rows, weights, opt_state):
        """Post-barrier finalize of the streaming pipeline: ONE fused
        program over the device-resident ``[participants, P]`` row buffer —
        weighted mean, unpack to the delta pytree, server-optimizer step,
        BN-stats add. The mean is :func:`fedtpu.core.round.flat_weighted_mean`,
        whose stacked axis-0 reduce is bit-identical to
        :meth:`_aggregate_impl`'s per-leaf mean (the stream/barrier parity
        the tests pin); everything downstream is the same per-leaf math.
        Robust aggregators and DP never reach here — config validation
        routes them to the barrier path (fedtpu.config.resolve_server_pipeline).
        """
        from fedtpu.core import server_opt as server_opt_lib
        from fedtpu.core.round import flat_weighted_mean
        from fedtpu.ops import flat as flat_ops

        mean_row = flat_weighted_mean(rows, weights)
        deltas = flat_ops.unpack(self._flat_layout, mean_row)
        new_params, new_opt = server_opt_lib.apply(
            self._server_opt, global_tree["params"], deltas["params"], opt_state
        )
        new_stats = jax.tree.map(
            lambda g, d: g + d, global_tree["batch_stats"], deltas["batch_stats"]
        )
        return {"params": new_params, "batch_stats": new_stats}, new_opt

    def _finalize_partial_impl(
        self, global_tree, sum_rows, weight_sums, opt_state
    ):
        """Tier-mode finalize: the stream buffer's rows are the leaf tiers'
        PRE-WEIGHTED sums, so the combine is sum-of-sums divided ONCE by
        the global weight total (:func:`fedtpu.ops.flat.combine_partial_rows`)
        — NOT :func:`fedtpu.core.round.flat_weighted_mean`, which would
        re-multiply each partial by its own weight sum and silently square
        the weighting. The single division is the exact-associativity
        contract: for inputs whose f32 adds are exact, the 2-tier result is
        bit-identical to the flat one-tier weighted mean
        (tests/test_aggregator.py parity pins). Everything downstream
        (unpack, server-optimizer step, BN add) is the flat path's code.
        """
        from fedtpu.core import server_opt as server_opt_lib
        from fedtpu.ops import flat as flat_ops

        mean_row = flat_ops.combine_partial_rows(sum_rows, weight_sums)
        deltas = flat_ops.unpack(self._flat_layout, mean_row)
        new_params, new_opt = server_opt_lib.apply(
            self._server_opt, global_tree["params"], deltas["params"], opt_state
        )
        new_stats = jax.tree.map(
            lambda g, d: g + d, global_tree["batch_stats"], deltas["batch_stats"]
        )
        return {"params": new_params, "batch_stats": new_stats}, new_opt

    # ------------------------------------------------------------- transport
    def model_bytes(self) -> bytes:
        """Client-broadcast payload: the global model only."""
        return wire.encode(
            {"params": self.params, "batch_stats": self.batch_stats},
            compress=self.compress,
        )

    def _set_epoch(self, epoch: int) -> None:
        """Adopt a coordinator epoch and mirror it on the gauge — one path
        for mint (promotion / post-fence re-base) and restore (replica /
        checkpoint), so the observable epoch can never lag the wire one."""
        self._coord_epoch = int(epoch)
        self.telemetry.gauge(
            "fedtpu_ft_coordinator_epoch",
            "this coordinator's fencing epoch (minted on promotion or "
            "post-fence re-base)",
        ).set(float(self._coord_epoch))

    def state_tree(self) -> dict:
        """Full resumable server state as one pytree: the model, the
        monotonic round counter, the coordinator fencing epoch, the
        membership roster (as a JSON uint8 leaf — variable-length, so a
        growing federation still replicates), and (when a server optimizer
        is configured) its moments. This is both the replica payload body
        and the checkpoint state — one format, so failover and resume can
        never drift apart."""
        tree = {
            "params": self.params,
            "batch_stats": self.batch_stats,
            "round_counter": np.asarray(self._round_counter, np.int64),
            "coord_epoch": np.asarray(self._coord_epoch, np.int64),
            "membership": self._membership_bytes(),
        }
        if self._server_opt is not None:
            tree["server_opt"] = self._server_opt_state
        return tree

    def state_template(self, membership: bool = True,
                       epoch: bool = True) -> dict:
        """Decode template matching :meth:`state_tree`'s structure.
        ``membership=False`` yields the pre-elastic-membership layout and
        ``epoch=False`` the pre-fencing one, so replicas/checkpoints
        written by older coordinators still restore (with the startup
        roster / current epoch kept)."""
        from fedtpu.core import server_opt as server_opt_lib

        params, stats = _model_template(self.model, self.cfg)
        tree = {
            "params": params,
            "batch_stats": stats,
            "round_counter": np.zeros((), np.int64),
        }
        if epoch:
            tree["coord_epoch"] = np.zeros((), np.int64)
        if membership:
            tree["membership"] = np.zeros((0,), np.uint8)
        if self._server_opt is not None:
            tree["server_opt"] = server_opt_lib.init(self.cfg.fed, params)
        return tree

    def install_state(self, tree: dict) -> None:
        """Adopt a restored :meth:`state_tree` (from replica or checkpoint).
        When the tree carries a membership roster, the CURRENT roster — not
        the startup list — is adopted with it (failover inherits joins,
        leaves, and alive flags). The fencing epoch adopts by MAX: a
        replica can only raise our epoch, never demote us below one we
        already minted."""
        self._round_counter = int(tree["round_counter"])
        if self._server_opt is not None:
            self._server_opt_state = jax.tree.map(
                jnp.asarray, tree["server_opt"]
            )
        self.params = jax.tree.map(jnp.asarray, tree["params"])
        self.batch_stats = jax.tree.map(jnp.asarray, tree["batch_stats"])
        if "coord_epoch" in tree:
            self._set_epoch(max(self._coord_epoch, int(tree["coord_epoch"])))
        if "membership" in tree:
            self._adopt_membership(tree["membership"])

    def restore_from_checkpoint(self, ckpt) -> Optional[int]:
        """Cold-start recovery protocol, coordinator side
        (docs/OPERATIONS.md §Disaster recovery): restore the full server
        state — model, monotone lineage counter, membership roster
        including suspicion/reputation, FedOpt moments — from the newest
        VERIFIED on-disk generation (``ckpt`` is a
        :class:`fedtpu.checkpoint.Checkpointer` or the background wrapper;
        its ``restore_latest`` falls back past corrupt generations and
        counts ``fedtpu_checkpoint_fallback_total``). Adopting the
        membership leaf re-resolves the roster and rebuilds the stub table
        (``_adopt_membership``), and the initial-sync flag is cleared so
        the first round after recovery pushes the restored global to every
        surviving client through the existing ``sync_clients``/seat-resync
        path — no client re-registers, nothing is lost from the roster.

        Template ladder: current layout -> pre-fencing layout (epoch kept)
        -> pre-elastic-membership layout (startup roster kept) -> legacy
        model-only checkpoints (counter estimated from the generation
        index). Returns the next round index to run (``start_round``), or
        None for an empty directory (fresh start). Raises
        :class:`wire.WireError` when generations exist but none verifies —
        a disaster the operator must see, never a silent restart from
        round 0."""
        try:
            latest = ckpt.restore_latest(self.state_template())
        except wire.WireError:
            raise
        except ValueError:
            try:
                latest = ckpt.restore_latest(self.state_template(epoch=False))
            except wire.WireError:
                raise
            except ValueError:
                try:
                    latest = ckpt.restore_latest(
                        self.state_template(membership=False, epoch=False)
                    )
                except wire.WireError:
                    raise
                except ValueError:
                    latest = None
        if latest is None:
            params, stats = _model_template(self.model, self.cfg)
            legacy = ckpt.restore_latest(
                {"params": params, "batch_stats": stats}
            )
            if legacy is None:
                return None
            r, tree = legacy
            self.params = jax.tree.map(jnp.asarray, tree["params"])
            self.batch_stats = jax.tree.map(jnp.asarray, tree["batch_stats"])
            self._round_counter = r + 1
            self._did_initial_sync = False
            log.info("resumed legacy model-only checkpoint from round %d", r)
            return r + 1
        r, tree = latest
        self.install_state(tree)
        # Survivors hold weights from rounds the restored lineage may not
        # know about; the pre-round broadcast re-bases everyone on the
        # restored global (and the lineage round in their next StartTrain
        # tells them to roll back local state to match).
        self._did_initial_sync = False
        log.info(
            "cold start: restored round %d from %s (lineage continues at "
            "%d; roster size %d, membership v%d)",
            r, getattr(ckpt, "directory", "?"), self._round_counter,
            self.registry.size, self.registry.version,
        )
        self.flight.record(
            "checkpoint", event="restore", round=r,
            members=self.registry.size,
        )
        return r + 1

    def replica_bytes(self) -> bytes:
        """Backup-replication payload: the model plus (when a server
        optimizer is configured) its moments, so a promotion or a recovering
        primary resumes the FedOpt trajectory instead of applying stale/zero
        moments to a model they were never computed against. Also carries
        the monotonic round counter so a promoted backup continues the DP
        noise / participation-subsampling PRNG sequence instead of replaying
        round 0's draws (which would let an observer difference two releases
        and cancel the noise). The frame is stamped kind="replica"."""
        return wire.encode(self.state_tree(), compress=self.compress,
                           kind="replica")

    def _install(self, data: bytes) -> None:
        """Install a replica payload or a plain model payload, dispatched on
        the frame's explicit payload-kind flag (never by trying templates
        and catching exceptions): a corrupted or config-mismatched replica
        raises instead of silently downgrading to "model-only, keep current
        moments"."""
        if wire.payload_kind(data) == "replica":
            try:
                tree = wire.decode(data, self.state_template())
            except wire.WireError:
                raise
            except ValueError:
                # Older coordinator's replica: try the pre-fencing layout
                # (epoch kept), then the pre-membership one (startup roster
                # kept). Any OTHER mismatch fails every template and raises
                # below.
                try:
                    tree = wire.decode(
                        data, self.state_template(epoch=False)
                    )
                except wire.WireError:
                    raise
                except ValueError:
                    try:
                        tree = wire.decode(
                            data,
                            self.state_template(membership=False, epoch=False),
                        )
                    except wire.WireError:
                        raise
                    except ValueError as exc:
                        raise wire.WireError(
                            "replica payload does not match this server's "
                            f"configuration ({exc}); refusing to install a "
                            "partial state"
                        ) from exc
            self.install_state(tree)
        else:
            params, stats = _model_template(self.model, self.cfg)
            try:
                tree = wire.decode(
                    data, {"params": params, "batch_stats": stats}
                )
            except wire.WireError:
                raise
            except ValueError as exc:
                raise wire.WireError(
                    "model payload does not match this server's "
                    f"configuration ({exc})"
                ) from exc
            self.params = jax.tree.map(jnp.asarray, tree["params"])
            self.batch_stats = jax.tree.map(jnp.asarray, tree["batch_stats"])

    def _resync(self, client: str) -> None:
        """Push the current global model to a recovered client (parity:
        ``sendOptimizedModel`` from the recovery loop, ``src/server.py:95-99``).

        Raises (deferring the revive to the next heartbeat tick) while a
        stale broadcast send to this client is still in flight — a resync
        racing it could land first and leave the OLDER payload installed
        last, silently desyncing the client the moment it is revived."""
        stale = self._sends.get(client)
        if stale is not None and stale.is_alive():
            raise RuntimeError(
                f"stale broadcast to {client} still in flight; "
                "deferring resync"
            )
        stub = self._stub(client)
        if stub is None:
            raise RuntimeError(f"{client} evicted; nothing to resync")
        # A transient blip mid-resync retries here instead of bouncing the
        # client back to dead for another full heartbeat cycle.
        try:
            call_with_retry(
                self.retry_policy, "SendModel",
                lambda: stub.SendModel(
                    proto.SendModelRequest(
                        model=self.model_bytes(),
                        epoch=self._coord_epoch, role=self._role,
                    ),
                    timeout=self._deadlines["SendModel"],
                ),
                peer=client, telemetry=self.telemetry,
                rand=self._retry_rand,
            )
        except grpc.RpcError as e:
            if is_stale_coordinator(e):
                self._handle_stale("SendModel", client, e)
            raise

    def sync_clients(self) -> None:
        """Broadcast the current global model to all active clients.

        Runs automatically before the first round (see :meth:`round`):
        clients may hold baselines from a previous server generation, and in
        sparse-delta mode an unsynced baseline would silently corrupt
        aggregation. Transient failures retry under the policy — one blip
        here used to kill the client before round 1 ever ran.
        """
        payload = self.model_bytes()
        for client in self.registry.active_clients():
            stub = self._stub(client)
            if stub is None:
                continue  # evicted since active_clients() snapshot
            try:
                call_with_retry(
                    self.retry_policy, "SendModel",
                    lambda s=stub: s.SendModel(
                        proto.SendModelRequest(
                            model=payload,
                            epoch=self._coord_epoch, role=self._role,
                        ),
                        timeout=self._deadlines["SendModel"],
                    ),
                    peer=client, telemetry=self.telemetry,
                    rand=self._retry_rand,
                )
            except grpc.RpcError as e:
                if is_stale_coordinator(e):
                    # We are the superseded side of a healed partition —
                    # the client is NOT failed; WE must re-base. Leave the
                    # client alive and let the round loop fence us.
                    self._handle_stale("SendModel", client, e)
                    continue
                log.warning("client %s failed during initial sync", client)
                self.telemetry.counter(
                    "fedtpu_rpc_failures_total",
                    "RpcErrors by failing RPC",
                    labels={"rpc": "SendModel"},
                ).inc()
                self.registry.mark_failed(client)
        self._did_initial_sync = True

    def _ping_backup(self, recovering: bool) -> Optional[int]:
        try:
            resp = call_with_retry(
                self.retry_policy, "CheckIfPrimaryUp",
                lambda: self.backup_stub.CheckIfPrimaryUp(
                    proto.PingRequest(
                        req=b"1" if recovering else b"0",
                        epoch=self._coord_epoch,
                    ),
                    timeout=self._deadlines["CheckIfPrimaryUp"],
                ),
                telemetry=self.telemetry,
                rand=self._retry_rand,
            )
        except grpc.RpcError as e:
            if is_stale_coordinator(e):
                # The backup promoted past us while we were partitioned;
                # our liveness probe may no longer reset its watchdog.
                self._handle_stale("CheckIfPrimaryUp", "backup", e)
            return None
        if resp.value == 1:
            # The backup acted as primary while we were down; its model is
            # ahead of ours. Pull it before training another round (the
            # reference silently reverts the backup's progress here). The
            # retry also re-requests a CRC-corrupted replica payload.
            try:
                def fetch():
                    fetched = self.backup_stub.FetchModel(
                        proto.Request(),
                        timeout=self._deadlines["FetchModel"],
                    )
                    if fetched.model:
                        self._install(fetched.model)
                        log.info("recovered newer global model from backup")

                call_with_retry(
                    self.retry_policy, "FetchModel", fetch,
                    telemetry=self.telemetry, rand=self._retry_rand,
                )
            except grpc.RpcError:
                log.warning("backup demoted but FetchModel failed")
            except wire.WireError:
                log.warning(
                    "backup demoted but its model payload stayed corrupt "
                    "after retries; keeping the local model"
                )
        return resp.value

    # --------------------------------------------------------------- fencing
    def _handle_stale(self, rpc: str, peer: str, exc: grpc.RpcError) -> None:
        """A receiver rejected us with STALE_COORDINATOR: another
        coordinator minted a higher epoch while we were partitioned. Record
        the winner's epoch (parsed from the rejection details, so the
        re-base can mint past it even if the backup is unreachable) and
        flip the fence flag — the round loop voids the in-flight round and
        re-bases (:meth:`handle_fence`). Never marks ``peer`` failed: the
        peer is healthy, WE are stale."""
        try:
            details = exc.details() or ""
            self._epoch_seen = max(
                self._epoch_seen, int(details.rsplit("<", 1)[1])
            )
        except Exception:
            pass  # malformed details: re-base still mints past our own epoch
        with self._fence_lock:
            first = not self._fenced
            self._fenced = True
        if not first:
            return
        log.warning(
            "FENCED by %s via %s: our epoch %d is stale (newest seen %d); "
            "voiding the in-flight round and re-basing",
            peer, rpc, self._coord_epoch, self._epoch_seen,
        )
        self.telemetry.counter(
            "fedtpu_ft_fenced_total",
            "times this coordinator was fenced by a STALE_COORDINATOR "
            "rejection (superseded by a higher epoch)",
        ).inc()
        self.flight.record(
            "fence", rpc=rpc, peer=peer, epoch=self._coord_epoch,
            epoch_seen=self._epoch_seen,
        )
        self.flight.dump(reason="fence")

    def handle_fence(self) -> None:
        """Post-fence re-base (docs/FAULT_TOLERANCE.md §Fencing heal
        timeline): demote the acting backup through the recovering
        handshake (``CheckIfPrimaryUp(req=b"1")`` passes the backup's
        stale check by design — the heal must work), adopt its state via
        the existing FetchModel/_install path (install_state raises our
        epoch to the winner's), then mint an epoch PAST everything seen
        and re-broadcast on the next round's initial sync. Our forked
        rounds are already voided — the fenced round never committed.

        The fence only drops once the handshake is DELIVERED: minting past
        the winner without adopting its state would re-fork the lineage —
        the exact split-brain this protocol eliminates. While the winner
        stays unreachable (an asymmetric partition healed client-side
        first, or no backup channel exists at all) the coordinator holds
        the fence — ``health()`` keeps reporting 503 — and retries every
        ``_fence_retry_s``; an acting primary in that position simply
        waits for the demotion the re-basing primary's handshake
        delivers."""
        if not self._fenced:
            return
        log.info("re-basing after fence (epoch %d, seen %d)",
                 self._coord_epoch, self._epoch_seen)
        if self.pinger is None:
            # No channel to the winning lineage: state adoption is
            # impossible from here, so resuming would fork. Hold the fence
            # until demoted (acting primary) or restarted by the operator.
            time.sleep(self._fence_retry_s)
            return
        self.pinger.recovering = True
        if self.pinger.tick() is None:
            # The heal is still partial (we are fenced via clients but the
            # backup link is down). Stay fenced and retry.
            time.sleep(self._fence_retry_s)
            return
        self._set_epoch(max(self._coord_epoch, self._epoch_seen) + 1)
        self._did_initial_sync = False
        with self._fence_lock:
            self._fenced = False
        self.flight.record("fence", event="rebased", epoch=self._coord_epoch)
        log.info("re-based: continuing as epoch %d", self._coord_epoch)

    def health(self) -> Tuple[bool, str]:
        """Honest /healthz verdict: (ok, reason). 503-worthy while fenced
        (stale coordinator pending re-base) or while the latest round
        aborted under quorum — orchestrator probes can then act instead of
        reading an unconditional 200."""
        if self._fenced:
            return False, "fenced: stale coordinator pending re-base"
        if self.history and self.history[-1].get("aborted"):
            return False, "quorum unmet: last round aborted"
        return True, "ok"

    # ------------------------------------------------------------ membership
    def _make_stub(self, address: str) -> TrainerStub:
        return TrainerStub(create_channel(
            address, compress=self.compress,
            trace_source=self._trace_source, chaos=self.chaos,
        ))

    def _stub(self, client: str) -> Optional[TrainerStub]:
        """The member's stub, or None for an (already-evicted) non-member —
        collect/broadcast workers treat None as an ordinary failure."""
        with self._member_lock:
            return self._stubs.get(client)

    def _probe_member(self, client: str) -> bool:
        stub = self._stub(client)
        if stub is None:
            return False  # evicted between dead_clients() and the probe
        return probe(
            stub, timeout=self._deadlines["HeartBeat"],
            policy=self.retry_policy, telemetry=self.telemetry,
        ) is not None

    def admit_client(self, address: str) -> dict:
        """Admit (or re-admit) a member — the Join RPC's implementation.

        The joiner is admitted DEAD and resynced through the same
        model-push path a heartbeat revival uses (:meth:`_resync` →
        ``sync_clients`` semantics): a stale joiner — fresh process, or a
        returning client whose weights predate many rounds — must hold the
        CURRENT global model before its first StartTrain, or in
        sparse-delta mode its first delta would silently corrupt the
        aggregate. If the inline resync fails the member stays dead and
        the heartbeat monitor finishes the revival on a later tick; the
        join itself still succeeded.
        """
        with self._member_lock:
            rejoin = self.registry.is_member(address)
            seat = self.registry.admit(address)
            if address not in self._stubs:
                self._stubs[address] = self._make_stub(address)
        resynced = False
        try:
            self._resync(address)
            self.registry.mark_alive(address)
            resynced = True
        except (grpc.RpcError, RuntimeError) as exc:
            log.warning(
                "join: %s admitted at seat %d but resync failed (%s); "
                "heartbeat monitor will revive it", address, seat, exc,
            )
        self.flight.record(
            "membership", event="join", client=address, seat=seat,
            version=self.registry.version, rejoin=rejoin,
        )
        return {
            "admitted": True,
            "seat": seat,
            "world": self.registry.capacity(),
            "version": self.registry.version,
            "resynced": resynced,
        }

    def remove_client(self, address: str, reason: str = "leave") -> dict:
        """Evict a member (graceful Leave, or operator action): frees its
        seat for later joiners and closes its channel. A late RPC from the
        evicted client is ignored by the tolerant registry."""
        left = self.registry.evict(address, reason=reason)
        with self._member_lock:
            stub = self._stubs.pop(address, None)
        if stub is not None:
            try:
                stub._channel.close()
            except Exception:
                pass  # a late in-flight RPC owns the channel a bit longer
        if left:
            self.flight.record(
                "membership", event="leave", client=address,
                version=self.registry.version, reason=reason,
            )
        return {"left": left, "version": self.registry.version}

    def _update_reputation(
        self, order: List[str], flagged: set, quarantined_now: set
    ) -> None:
        """Close the detection -> eviction loop: fold this round's
        screening verdicts into each participant's suspicion EWMA and run
        the escalation ladder (flagged -> quarantined -> evicted) against
        the live :class:`~fedtpu.ft.membership.MembershipTable`.

        - suspicion >= ``quarantine_at``: quarantine (the member is still
          served and screened — it can redeem itself — but its updates are
          ignored; counted into ``fedtpu_membership_quarantine_total``).
        - a quarantined member whose suspicion decays below ``release_at``
          is released (the false-positive exit).
        - ``evict_after`` consecutive quarantined rounds escalates to
          :meth:`remove_client` with reason ``quarantine`` — the roster
          change replicates to the backup like any other eviction.
        """
        sc = self._screen_cfg
        for c in order:
            s = self.registry.observe_screening(c, c in flagged, ewma=sc.ewma)
            if c in quarantined_now:
                rounds_q = self.registry.tick_quarantine(c)
                if s < sc.release_at:
                    if self.registry.release(c):
                        self.flight.record(
                            "membership", event="release", client=c,
                            suspicion=round(s, 4),
                        )
                elif sc.evict_after and rounds_q >= sc.evict_after:
                    log.warning(
                        "client %s evicted after %d quarantined rounds "
                        "(suspicion %.3f)", c, rounds_q, s,
                    )
                    self.remove_client(c, reason="quarantine")
            elif s >= sc.quarantine_at:
                if self.registry.quarantine(c):
                    self.flight.record(
                        "membership", event="quarantine", client=c,
                        suspicion=round(s, 4),
                    )

    def _membership_bytes(self) -> np.ndarray:
        """The roster snapshot as a uint8 JSON leaf for the replica/
        checkpoint pytree (flax msgpack carries variable-length arrays)."""
        blob = json.dumps(self.registry.snapshot()).encode()
        return np.frombuffer(blob, np.uint8)

    def _adopt_membership(self, leaf) -> None:
        """Adopt a replicated roster (inverse of :meth:`_membership_bytes`)
        and rebuild the stub table to match — a promoted backup then dials
        the CURRENT fleet, not the startup list it was constructed with."""
        blob = np.asarray(leaf, np.uint8).tobytes()
        if not blob:
            return  # template placeholder / membership-less checkpoint
        self.registry.restore(json.loads(blob.decode()))
        members = set(self.registry.clients)
        with self._member_lock:
            for address in members - set(self._stubs):
                self._stubs[address] = self._make_stub(address)
            for address in set(self._stubs) - members:
                self._stubs.pop(address)

    def start_gate(self, address: str):
        """Host the membership gate — a gRPC server answering Join/Leave on
        ``address`` (``--gate`` on the server CLI). The coordinator
        otherwise only DIALS OUT; this is its sole inbound surface, so the
        round loop never competes with admissions for a listener."""
        gate = _MembershipGate(self)
        self._gate_server = create_server(
            address, gate, compress=self.compress, chaos=self.chaos
        )
        self._gate_server.start()
        log.info("membership gate serving on %s", address)
        return self._gate_server

    def stop_gate(self) -> None:
        if self._gate_server is not None:
            self._gate_server.stop(0)
            self._gate_server = None

    # ---------------------------------------------------------- observability
    def _trace_source(self) -> Optional[propagate.TraceContext]:
        """Per-RPC propagation context (runs on the issuing thread, so the
        innermost open span — the collect worker's ``client_rpc`` — becomes
        the remote parent). None below trace mode: the interceptor then
        forwards the call untouched."""
        tracer = self.telemetry.tracer
        if tracer is None:
            return None
        return propagate.TraceContext(
            trace_id=tracer.trace_id,
            span_id=tracer.current_id() or 0,
            role=self.telemetry.role or "primary",
            round=self._round_counter,
        )

    def status_snapshot(self) -> dict:
        """``/statusz`` feed: live round/phase (from the round loop's
        :class:`StatusBoard` updates) + client liveness + FT counters +
        the last round record's phase timings."""
        snap = self.status.snapshot()
        reg = self.registry
        snap.update(
            pid=os.getpid(),
            clients={
                "alive": reg.active_clients(),
                "dead": reg.dead_clients(),
            },
            # The full membership block: epoch/size/capacity + roster —
            # what a churn soak (or an operator watching tools/statusz.py)
            # audits joins and evictions against.
            membership=reg.status(),
            # Leak axes (also exported as gauges): current RSS and the
            # last round's flat collect-buffer footprint.
            mem={
                "rss_bytes": process_rss_bytes(),
                "buffer_bytes": (
                    int(self.history[-1].get("buffer_bytes", 0))
                    if self.history else 0
                ),
                # Tier accounting (docs/ARCHITECTURE.md §Multi-tier):
                # which tier's buffer this is, and the partial rows held
                # toward an in-flight root combine (0 between rounds).
                "tier": "root" if self.tier_fanout else "flat",
                "partial_rows_buffered": (
                    int(
                        self.telemetry.registry.gauge(
                            "fedtpu_partial_rows_buffered", ""
                        ).value
                    )
                    if self.tier_fanout and self.telemetry.enabled else 0
                ),
            },
            stragglers_in_flight=sorted(
                c for c, t in self._inflight.items() if t.is_alive()
            ),
            rounds_completed=sum(
                1 for rec in self.history if not rec.get("aborted")
            ),
            rounds_aborted=sum(
                1 for rec in self.history if rec.get("aborted")
            ),
            # Fencing block (docs/FAULT_TOLERANCE.md §Fencing): which
            # lineage this coordinator is, and whether it has been
            # superseded and is pending re-base.
            fencing={
                "epoch": self._coord_epoch,
                "role": "acting" if self._role == 2 else "primary",
                "fenced": self._fenced,
            },
        )
        tel = self.telemetry
        if tel.enabled:
            snap["heartbeat_misses"] = tel.registry.counter(
                "fedtpu_ft_heartbeat_misses_total",
                "heartbeat probes of dead clients that stayed dead",
            ).value
        if tel.tracer is not None:
            snap["trace_id"] = tel.tracer.trace_id
        if self.history:
            last = self.history[-1]
            snap["last_round"] = {
                k: last[k]
                for k in (
                    "participants", "stragglers", "bytes_up", "bytes_down",
                    "bytes_up_by_codec",
                    "t_collect_s", "t_decode_s", "t_h2d_s", "t_aggregate_s",
                    "t_post_barrier_s", "t_round_s", "pipeline",
                    "client_latency",
                )
                if k in last
            }
        # Per-codec wire-byte table (cumulative across rounds) and, under
        # the adaptive policy, the live per-client cost table (docs/
        # OPERATIONS.md §Adaptive codec).
        with self._codec_bytes_lock:
            if self._codec_bytes_up:
                snap["codec_bytes_up"] = dict(self._codec_bytes_up)
        if self._codec_policy is not None:
            snap["codec_policy"] = self._codec_policy.snapshot()
        if self.compile_watcher is not None:
            snap["compile"] = self.compile_watcher.snapshot()
        return snap

    # ------------------------------------------------------------ round loop
    def round(self) -> dict:
        """One synchronous FedAvg round; returns the round record.

        Wraps :meth:`_round_body` in the top-level ``round`` span and feeds
        the cumulative registry (bytes, phase histograms, straggler counts)
        after the record is built — both no-ops below their telemetry mode.
        """
        tel = self.telemetry
        with tel.span("round", round=self._round_counter) as rspan:
            rec = self._round_body(rspan)
        self.status.update(phase="idle")
        if tel.enabled:
            # Leak axes for the long-haul soaks (docs/OBSERVABILITY.md):
            # flat over a healthy 1k-round churn soak, monotone growth is
            # the failure signature. Sampled once per round — a /proc read
            # is microseconds against a round.
            tel.gauge(
                "fedtpu_process_rss_bytes",
                "current resident set size of this process",
            ).set(process_rss_bytes())
            tel.gauge(
                "fedtpu_buffer_bytes",
                "flat collect-buffer bytes held by the last round "
                "(host rows + device twin; 0 on the barrier path), by "
                "tier: 'flat' = one-tier federation, 'root' = the tiered "
                "root's [aggregators, P] surface, 'leaf' = a sub-"
                "aggregator's [cohort, P] buffer",
                labels={"tier": "root" if self.tier_fanout else "flat"},
            ).set(rec.get("buffer_bytes", 0))
            if self.tier_fanout:
                # The round's partial rows are combined and released.
                tel.gauge(
                    "fedtpu_partial_rows_buffered",
                    "partial-sum rows (one per sub-aggregator) buffered "
                    "toward this round's root combine",
                ).set(0)
        if rec.get("aborted"):
            # Sub-quorum abort: the abort already logged its own flight
            # event and counter inside _round_body; it is NOT a completed
            # round (the counter below would lie to dashboards).
            return rec
        # Cumulative per-codec byte ledger for /statusz — independent of
        # the telemetry mode (the round record is API either way).
        by_codec = rec.get("bytes_up_by_codec", {})
        if by_codec:
            with self._codec_bytes_lock:
                for codec_name, nb in by_codec.items():
                    self._codec_bytes_up[codec_name] = (
                        self._codec_bytes_up.get(codec_name, 0) + nb
                    )
        self.flight.record(
            "round",
            round=self._round_counter - 1,
            participants=rec["participants"],
            stragglers=rec["stragglers"],
            t_collect_s=rec["t_collect_s"],
            t_aggregate_s=rec["t_aggregate_s"],
        )
        if tel.enabled:
            tel.counter(
                "fedtpu_rounds_completed_total",
                "synchronous FedAvg rounds completed by this server",
            ).inc()
            tel.counter(
                "fedtpu_rpc_bytes_up_total",
                "client -> server StartTrain reply bytes (successful)",
            ).inc(rec["bytes_up"])
            tel.counter(
                "fedtpu_rpc_bytes_down_total",
                "server -> client/backup broadcast bytes (successful)",
            ).inc(rec["bytes_down"])
            # Per-codec twins of the unlabeled byte counter above (the
            # unlabeled series stays the authoritative total — dashboards
            # and tests pin it — the labeled series adds the breakdown).
            for codec_name, nb in rec.get("bytes_up_by_codec", {}).items():
                tel.counter(
                    "fedtpu_rpc_bytes_up_total",
                    "client -> server StartTrain reply bytes (successful)",
                    labels={"codec": codec_name},
                ).inc(nb)
            tel.counter(
                "fedtpu_stragglers_total",
                "client-rounds lost to stragglers (deadline, in-flight)",
            ).inc(rec["stragglers"])
            for ph in ("collect", "decode", "h2d", "aggregate"):
                tel.histogram(
                    "fedtpu_round_phase_seconds",
                    "per-round phase wall time by phase label",
                    labels={"phase": ph},
                ).observe(rec[f"t_{ph}_s"])
            if "t_round_s" in rec:
                tel.gauge(
                    "fedtpu_step_time_seconds",
                    "wall time of the last round dispatch, per round",
                ).set(rec["t_round_s"])
        return rec

    def _round_body(self, rspan) -> dict:
        cfg = self.cfg
        tel = self.telemetry
        # Captured ONCE for the whole round: collect workers (including a
        # straggler's late retry after the counter advanced) must all
        # advertise the same lineage round in their TrainRequests — it is
        # the client-side replay-detection signal of disaster recovery.
        lineage_round = self._round_counter
        self.status.update(round=self._round_counter, phase="collect")
        if self.chaos is not None:
            # Advertise the lineage round so rounds= fault windows key on it.
            self.chaos.set_round(self._round_counter)
        if not self._did_initial_sync:
            self.sync_clients()
        # Roster snapshot for this round: cohort selection runs over the
        # LIVE set of the CURRENT membership; a join/leave landing mid-round
        # takes effect next round. Quarantined members stay in the launch —
        # they are SERVED (broadcasts, StartTrain) and keep generating
        # screening evidence so they can redeem themselves — but their
        # updates are dropped before the combine, whatever arrives.
        active = self.registry.active_clients()
        quarantined_now = set(self.registry.quarantined_clients())
        members_now = self.registry.size
        membership_version = self.registry.version
        # The round record's alive mask spans THIS snapshot's roster — a
        # mid-round admit would otherwise tear the record (mask longer
        # than `world`). Alive state itself is read at record time, so a
        # member dying mid-round (retry exhaustion) still shows.
        roster_now = self.registry.clients
        # Random client subsampling (engine parity: _alive_for_round; the
        # reference always uses every live client). Sampled-out clients skip
        # this round's StartTrain but still receive the broadcast.
        frac = cfg.fed.participation_fraction
        if frac < 1.0 and active:
            # Seeded from the lineage-wide round counter (not len(history),
            # which restarts at 0 after failover and would re-correlate the
            # subsampling draws across server generations).
            rng = np.random.default_rng(
                cfg.data.seed * 7919 + self._round_counter
            )
            k = max(1, int(round(frac * len(active))))
            active = sorted(
                rng.choice(np.asarray(active), size=k, replace=False).tolist()
            )
        # Partition width = SEAT capacity (freed seats included): stable
        # under steady churn — a joiner reuses an evicted member's seat, so
        # every other client's shard stays put — and grows only when the
        # roster genuinely outgrows it.
        world = self.registry.capacity()
        tiered = self.tier_fanout > 0
        if tiered:
            # Tier mode: world spans the CLIENT data partition, not the
            # aggregator roster — aggregator seat j relays ranks
            # [j*fanout, (j+1)*fanout) to its cohort, so the tiers tile the
            # dataset without coordination and a flat federation of the
            # same world trains identical shards (the parity pins rely on
            # this).
            world = world * self.tier_fanout
        # Host copies of the global model are only needed for dense replies /
        # sparse templates; build them lazily (in topk steady state the full
        # device->host transfer would otherwise run every round for nothing).
        cache: Dict[str, Any] = {}
        cache_lock = threading.Lock()

        def global_host():
            with cache_lock:
                if "g" not in cache:
                    cache["g"] = {
                        "params": jax.tree.map(np.asarray, self.params),
                        "batch_stats": jax.tree.map(np.asarray, self.batch_stats),
                    }
                return cache["g"]

        def delta_template():
            with cache_lock:
                if "d" not in cache:
                    cache["d"] = {
                        "params": jax.tree.map(
                            lambda s: np.zeros(s.shape, s.dtype), self.params
                        ),
                        "batch_stats": jax.tree.map(
                            lambda s: np.zeros(s.shape, s.dtype), self.batch_stats
                        ),
                    }
                return cache["d"]

        # results[client] = (delta_tree | row_index, num_examples)
        results: Dict[str, tuple] = {}
        # Straggler attribution: per-client StartTrain wall (RPC + decode,
        # retries included) recorded by each collect worker under its own
        # key (GIL-atomic single-key writes, same pattern as `results`).
        # Summarised to p50/p95/p99 + top-k slowest on the round record.
        latencies: Dict[str, float] = {}
        # Wire + phase accounting: thread-safe counters (fedtpu.obs), NOT
        # bare mutable cells — collect workers increment them concurrently,
        # and unsynchronised `x[0] += n` read-modify-writes can drop
        # updates. Always on (the round record is API, whatever the
        # telemetry mode).
        bytes_up = Counter()  # client -> server payload bytes this round
        bytes_down = Counter()  # only successful sends count
        # Per-codec wire accounting (docs/OBSERVABILITY.md §Codec bytes):
        # which codec each surviving reply ACTUALLY used (the decode-side
        # `_codec` record tag; dense payloads count as 'none') and its
        # payload bytes. Single-key writes per collect worker (the
        # `results` pattern); feeds the labeled rpc byte counters, the
        # /statusz per-codec table and the adaptive policy's observations.
        codec_of: Dict[str, tuple] = {}  # client -> (codec_name, bytes)
        # Tier mode: total leaf clients behind this round's partials (each
        # SubmitPartialReply reports its cohort's contributor count) — the
        # round record's participants stay the DIRECT peers (aggregators).
        clients_in = Counter()
        stream = self.server_pipeline == "stream"
        # Per-round phase timing (satellite of the streaming pipeline):
        # decode / H2D are summed across clients; collect and the
        # post-barrier gap are wall-clock marks in this thread. Reported
        # on the round record so the overlap win shows up in ordinary run
        # logs, not just the microbench.
        decode_s = Counter()
        h2d_s = Counter()
        # Streaming collect state: one preallocated host row per launched
        # client (decode target) and ONE device [launch, P] buffer that
        # arriving rows are written into in place (donated
        # dynamic_update_slice), so by the time the last reply lands the
        # whole delta block is already device-resident. All of it is
        # PER-ROUND (like `results`): a straggler from an earlier round
        # still holds references to ITS round's buffers, so its late write
        # can never corrupt this round's rows.
        row_of: Dict[str, int] = {}
        host_rows: List[np.ndarray] = []
        dev_buf: List[Any] = []
        stream_lock = threading.Lock()

        def train_one(rank: int, client: str, stub: TrainerStub) -> None:
            # Runs on a collect worker thread: the client span parents to
            # this round's span EXPLICITLY (thread-local nesting cannot
            # cross threads); decode/h2d spans below nest under it via the
            # worker's own stack.
            # Adaptive codec: ONE choice per client per round, made before
            # the attempt so retries re-request the same codec (a retried
            # reply must match its observation).
            codec_req = (
                self._codec_policy.choose(rank)
                if self._codec_policy is not None else None
            )

            def attempt():
                # One full RPC attempt INCLUDING reply decode: a payload
                # that fails the wire CRC (corrupted in flight) raises
                # WireError here and is re-requested by the retry wrapper
                # — reject-and-retry, never "silently lose the client's
                # round" (the pre-policy behavior: the worker thread died
                # with the exception and the reply just vanished).
                if tiered:
                    # One pulled partial reduce: the aggregator fans
                    # StartTrain out to its cohort, folds the replies to a
                    # pre-weighted sum and answers with ONE FSP1
                    # partial_flat record — the root's per-peer work below
                    # is a single straight-copy decode, whatever the
                    # cohort size (bench.py --fanin-microbench).
                    reply = stub.SubmitPartial(
                        proto.SubmitPartialRequest(
                            rank_base=rank * self.tier_fanout, world=world,
                            round=lineage_round, epoch=self._coord_epoch,
                        ),
                        timeout=self._deadlines["SubmitPartial"],
                    )
                    data = reply.record
                    clients_in.inc(reply.clients)
                else:
                    reply = stub.StartTrain(
                        proto.TrainRequest(
                            rank=rank, world=world, round=lineage_round,
                            epoch=self._coord_epoch,
                            codec=proto.CODEC_IDS.get(codec_req, 0),
                        ),
                        timeout=self._deadlines["StartTrain"],
                    )
                    data = reply.message
                if stream:
                    # Decode straight into this client's row — no
                    # per-leaf template trees, no later leaf-by-leaf
                    # stacking. A retried attempt rewrites the row from
                    # scratch (both decoders write every real coordinate).
                    row = host_rows[0][row_of[client]]
                    t0 = time.monotonic()
                    with tel.span("decode", client=client):
                        if sparse.is_sparse_payload(data):
                            extra = sparse.decode_into_row(
                                data, self._flat_layout.sizes, row
                            )
                        else:
                            # Dense full weights -> delta against the
                            # round's global, written into the row leaf
                            # slices.
                            extra = wire.decode_into_row(
                                data,
                                _payload_template(self.model, cfg),
                                global_host(),
                                row,
                            )
                    t1 = time.monotonic()
                    kind = extra.pop("_codec", None)
                    # Ship the row NOW: the transfer (and the in-place
                    # device-buffer write) overlaps the remaining
                    # clients' network wait instead of queueing behind
                    # the barrier. A deadline straggler landing AFTER
                    # the round closed its buffer (the pop in the
                    # finalize below) skips the device write: its reply
                    # is excluded from this round anyway, and writing
                    # would donate a buffer handle the finalize may
                    # still be reading.
                    with tel.span("h2d", client=client):
                        dev_row = jax.device_put(row)
                        with stream_lock:
                            if dev_buf:
                                dev_buf[0] = self._set_row(
                                    dev_buf[0], dev_row, row_of[client]
                                )
                    t2 = time.monotonic()
                    decode_s.inc(t1 - t0)
                    h2d_s.inc(t2 - t1)
                    # Tier mode: the combine weight is the partial's summed
                    # example weight (the leaf already applied cfg.fed
                    # weighting per client), not a per-client count.
                    out = (
                        row_of[client],
                        float(extra["weight_sum" if tiered
                                    else "num_examples"]),
                    )
                elif sparse.is_sparse_payload(data):
                    t0 = time.monotonic()
                    with tel.span("decode", client=client):
                        deltas, extra = sparse.decode(
                            data, delta_template()
                        )
                    decode_s.inc(time.monotonic() - t0)
                    kind = extra.pop("_codec", None)
                    out = (deltas, float(extra["num_examples"]))
                else:
                    t0 = time.monotonic()
                    with tel.span("decode", client=client):
                        tree = wire.decode(
                            data, _payload_template(self.model, cfg)
                        )
                        # Dense full weights -> delta against the
                        # round's global, so dense and sparse replies
                        # aggregate uniformly.
                        delta = jax.tree.map(
                            lambda a, g: np.asarray(a) - g,
                            {"params": tree["params"],
                             "batch_stats": tree["batch_stats"]},
                            global_host(),
                        )
                    decode_s.inc(time.monotonic() - t0)
                    kind = None  # dense full-weight payload
                    out = (delta, float(tree["num_examples"]))
                # Count only the attempt that survived decode.
                bytes_up.inc(len(data))
                codec_of[client] = (_CODEC_OF_KIND.get(kind, "none"), len(data))
                return out

            rpc_name = "SubmitPartial" if tiered else "StartTrain"
            try:
                t_rpc = time.monotonic()
                with tel.span("submit_partial" if tiered else "client_rpc",
                              parent=rspan.id, client=client):
                    results[client] = call_with_retry(
                        self.retry_policy, rpc_name, attempt,
                        peer=client, telemetry=tel,
                        rand=self._retry_rand,
                    )
                latencies[client] = time.monotonic() - t_rpc
                tel.histogram(
                    "fedtpu_client_rpc_seconds",
                    "per-client StartTrain wall time (RPC + decode, "
                    "retries included; successful rounds only)",
                ).observe(latencies[client])
                if self._codec_policy is not None and client in codec_of:
                    # Teach the policy the codec the reply ACTUALLY used
                    # (a legacy client ignoring the request still updates
                    # the right codec's estimate).
                    used, nbytes = codec_of[client]
                    self._codec_policy.observe(
                        rank, used, nbytes, latencies[client]
                    )
            except (grpc.RpcError, wire.WireError) as e:
                if is_stale_coordinator(e):
                    # The peer has seen a higher coordinator epoch: WE are
                    # the stale side of a healed partition. (In tier mode
                    # the aggregator RELAYS a cohort client's rejection
                    # upstream on the same typed status, so the evidence
                    # reaches here whichever tier observed the newer
                    # lineage.) The peer is healthy — never mark it
                    # failed; flip the fence and let the round loop void
                    # this round and re-base.
                    self._handle_stale(rpc_name, client, e)
                    return
                # Only a FATAL status or an exhausted retry budget lands
                # here — the designed path to mark_failed. In tier mode
                # that includes an aggregator's typed SUB_QUORUM /
                # UNSYNCED_AGGREGATOR aborts (FAILED_PRECONDITION, never
                # retried): the whole cohort becomes ONE masked row and
                # the heartbeat/resync machinery revives the aggregator.
                if isinstance(e, grpc.RpcError):
                    log.warning(
                        "%s %s failed during %s: %s %s",
                        "aggregator" if tiered else "client", client,
                        rpc_name, e.code(), e.details(),
                    )
                else:
                    log.warning(
                        "%s %s reply still corrupt after retries: %s",
                        client, rpc_name, e,
                    )
                tel.counter(
                    "fedtpu_rpc_failures_total",
                    "RpcErrors by failing RPC",
                    labels={"rpc": rpc_name},
                ).inc()
                self.registry.mark_failed(client)

        # A straggler whose previous-round StartTrain is STILL in flight must
        # not be handed a second concurrent StartTrain (the two handlers
        # would race on the client's trainer state / error-feedback
        # residual); it sits this round out and rejoins once its old call
        # drains.
        still_busy = [
            c for c in active
            if c in self._inflight and self._inflight[c].is_alive()
        ]
        if still_busy:
            log.warning("stragglers still in flight, skipping: %s", still_busy)
        # In sparse-delta mode a client whose LAST broadcast is still in
        # flight has a stale baseline: its top-k delta (and error-feedback
        # residual) would be computed against a model the server has since
        # replaced, silently corrupting aggregation (the hazard
        # sync_clients' docstring warns about). It sits training out until
        # its send drains. Dense mode keeps training: full weights are
        # delta'd against the CURRENT global server-side, so a stale base
        # is ordinary bounded staleness, not corruption.
        unsynced = []
        if cfg.fed.compression != "none":
            unsynced = [
                c for c in active
                if c not in still_busy
                and c in self._sends and self._sends[c].is_alive()
            ]
            if unsynced:
                log.warning(
                    "sparse mode: broadcast still in flight, baselines "
                    "stale, sitting out: %s", unsynced,
                )
        # Stub snapshot for the launch (under the member lock): an eviction
        # landing after this point still completes the already-launched
        # RPC on the old channel; one landing before it drops the client
        # from the launch list.
        with self._member_lock:
            stub_of = dict(self._stubs)
        # Each client trains its OWN seat's shard, regardless of which
        # clients were sampled or skipped this round: rank is the client's
        # stable membership SEAT, not its position in the launch list.
        # Positional ranks would retrain shards 0..k-1 every round under
        # participation sampling (shards k.. never trained) and move a
        # client's shard between rounds — breaking engine parity (the
        # engine's alive-mask semantics) and run_async, which already
        # assigns seat ranks.
        rank_of = self.registry.seat_map()
        launch = [
            c for c in active
            if c not in still_busy and c not in unsynced
            and c in stub_of and c in rank_of
        ]
        if stream and launch:
            row_of.update({c: i for i, c in enumerate(launch)})
            padded = self._flat_layout.padded
            host_rows.append(np.zeros((len(launch), padded), np.float32))
            buf = jnp.zeros((len(launch), padded), jnp.float32)
            if tiered:
                # Tier mode: the combine surface is [aggregators, P] —
                # shard it on the ROW axis so each local device owns whole
                # partial rows and the finalize's axis-0 sum becomes one
                # cross-device reduce (no-op on a single device, where the
                # helper degrades to ordinary placement).
                from fedtpu.parallel.mesh import partial_row_sharding

                buf = jax.device_put(
                    buf, partial_row_sharding(len(launch))
                )
            dev_buf.append(buf)
            if tiered and tel.enabled:
                tel.gauge(
                    "fedtpu_partial_rows_buffered",
                    "partial-sum rows (one per sub-aggregator) buffered "
                    "toward this round's root combine",
                ).set(len(launch))
        t_launch = time.monotonic()
        with tel.span("collect", launched=len(launch)):
            threads = {
                client: threading.Thread(
                    target=train_one,
                    args=(rank_of[client], client, stub_of[client]),
                )
                for client in launch
            }
            for t in threads.values():
                t.start()
            if self.round_deadline_s is None:
                for t in threads.values():
                    t.join()
                stragglers = still_busy + unsynced
            else:
                deadline = time.monotonic() + self.round_deadline_s
                for t in threads.values():
                    t.join(max(0.0, deadline - time.monotonic()))
                stragglers = still_busy + unsynced + [
                    c for c, t in threads.items() if t.is_alive()
                ]
                if stragglers:
                    log.warning(
                        "round deadline %.1fs hit; aggregating without %s",
                        self.round_deadline_s, stragglers,
                    )
        t_barrier = time.monotonic()
        # Merge this round's threads over the surviving prior entries: a
        # straggler launched two rounds ago can still be running even though
        # it was never in THIS round's `threads` — dropping it would hand
        # the client a second concurrent StartTrain next round.
        self._inflight = {
            c: t
            for c, t in {**self._inflight, **threads}.items()
            if t.is_alive()
        }

        # Snapshot completed replies under a NEW name: train_one writes to
        # the `results` free variable, so a straggler finishing
        # mid-aggregation lands its late write in the discarded per-round
        # dict, never in this round's inputs.
        completed = {
            c: results[c]
            for c in active
            if c in results and c not in stragglers
        }

        # Fenced mid-round (a collect worker hit STALE_COORDINATOR): VOID
        # the round before anything commits — same clean-abort contract as
        # the quorum path below (global model and optimizer state untouched,
        # lineage counter frozen). Whatever replies arrived belong to a
        # superseded lineage; run() re-bases via handle_fence before the
        # next attempt.
        if self._fenced:
            with stream_lock:
                dev_buf.clear()
            self._did_initial_sync = False
            log.warning(
                "round %d voided: coordinator fenced mid-round (epoch %d "
                "superseded); global model untouched",
                self._round_counter, self._coord_epoch,
            )
            tel.counter(
                "fedtpu_round_aborts_total",
                "rounds aborted below quorum (global model untouched)",
            ).inc()
            self.flight.record(
                "round_abort", round=self._round_counter,
                participants=len(completed), fenced=True,
            )
            rec = {
                "round": self._round_counter,
                "epoch": self._coord_epoch,
                "participants": len(completed),
                "stragglers": len(stragglers),
                "world": world,
                "alive": [self.registry.is_alive(c) for c in roster_now],
                "membership_version": membership_version,
                "aborted": True,
                "fenced": True,
                "bytes_up": int(bytes_up.value),
                "bytes_down": 0,
                "pipeline": self.server_pipeline,
                "t_collect_s": round(t_barrier - t_launch, 6),
                "t_decode_s": round(decode_s.value, 6),
                "t_h2d_s": round(h2d_s.value, 6),
                "t_aggregate_s": 0.0,
                "t_post_barrier_s": 0.0,
            }
            self.history.append(rec)
            return rec

        # Round quorum (cfg.fed.round_quorum, fraction of this round's
        # SAMPLED clients): below it the round aborts CLEANLY — the global
        # model and server-optimizer state are left bit-identical to their
        # pre-round values (nothing below this point runs, so there is no
        # partial average to undo), the lineage counter does not advance,
        # and the caller re-runs the round (run()'s abort loop). Clearing
        # _did_initial_sync forces a re-broadcast of the unchanged global
        # before the re-run: clients that DID train this round have
        # advanced their local weights, and in sparse-delta mode their next
        # delta must be computed against the server's global, not that
        # drift.
        quorum = cfg.fed.round_quorum
        # Quorum counts against the CURRENT membership (post join/evict),
        # never the startup roster: a federation where half the members
        # are dead-but-not-evicted must abort rather than quietly commit
        # with the survivors, and EVICTING the departed (shrinking the
        # denominator) is the operator's way to move on. Under
        # participation sampling (frac < 1) the sampled subset is the
        # round's electorate, so the base stays the sampled count.
        quorum_base = len(active) if frac < 1.0 else members_now
        needed = max(1, math.ceil(quorum * quorum_base)) if quorum > 0 else 0
        if needed and len(completed) < needed:
            with stream_lock:
                dev_buf.clear()  # close the stream buffer; rows discarded
            self._did_initial_sync = False
            log.warning(
                "round %d aborted: %d/%d replies below quorum %.2f of %d "
                "members; global model untouched, will re-run",
                self._round_counter, len(completed), needed, quorum,
                quorum_base,
            )
            tel.counter(
                "fedtpu_round_aborts_total",
                "rounds aborted below quorum (global model untouched)",
            ).inc()
            self.flight.record(
                "round_abort", round=self._round_counter,
                participants=len(completed), quorum_needed=needed,
            )
            rec = {
                "round": self._round_counter,
                "epoch": self._coord_epoch,
                "participants": len(completed),
                "stragglers": len(stragglers),
                "world": world,
                "alive": [self.registry.is_alive(c) for c in roster_now],
                "membership_version": membership_version,
                "aborted": True,
                "quorum_needed": needed,
                "bytes_up": int(bytes_up.value),
                "bytes_down": 0,
                "pipeline": self.server_pipeline,
                "t_collect_s": round(t_barrier - t_launch, 6),
                "t_decode_s": round(decode_s.value, 6),
                "t_h2d_s": round(h2d_s.value, 6),
                "t_aggregate_s": 0.0,
                "t_post_barrier_s": 0.0,
            }
            self.history.append(rec)
            return rec

        self.status.update(phase="aggregate")
        order = [c for c in active if c in completed]
        srows = None
        if stream and dev_buf:
            # Close the round's buffer under the lock first: a deadline
            # straggler must not donate-invalidate the handle we are about
            # to read. When a launched client failed or straggled, gather
            # the surviving rows so the reduce runs over EXACTLY the rows
            # the barrier path would stack (same [k, P] shape -> the same
            # order-stable reduce -> bit parity).
            with stream_lock:
                srows = dev_buf.pop()
            if order != launch:
                srows = srows[
                    jnp.asarray([row_of[c] for c in order], jnp.int32)
                ]
        # ---- fused screening + reputation (docs/FAULT_TOLERANCE.md) ----
        screened_names: List[str] = []
        if self._screen_jit is not None and order:
            with tel.span("screen", participants=len(order)):
                if stream:
                    rows_in = srows  # already device-resident, zero syncs
                else:
                    from fedtpu.ops import flat as flat_ops

                    host = np.zeros(
                        (len(order), self._screen_layout.padded), np.float32
                    )
                    for i, c in enumerate(order):
                        flat_ops.pack_row_host(
                            self._screen_layout, completed[c][0], out=host[i]
                        )
                    rows_in = jnp.asarray(host)
                # Quarantined rows must not pollute the reference stats
                # (median direction, median/MAD) but still get verdicts.
                live = jnp.asarray(
                    [c not in quarantined_now for c in order], jnp.float32
                )
                keep, _sstats = self._screen_jit(rows_in, live)
                keep = np.asarray(keep)
            screened_names = [
                c for i, c in enumerate(order) if not bool(keep[i])
            ]
            self._update_reputation(
                order, set(screened_names), quarantined_now
            )
            if screened_names:
                log.warning(
                    "round %d: screening rejected %s",
                    self._round_counter, screened_names,
                )
                tel.counter(
                    "fedtpu_screening_rejected_total",
                    "client rows rejected by the fused screening stage, "
                    "by surface",
                    labels={"surface": "server"},
                ).inc(len(screened_names))
        # Drop screened rows AND anything a quarantined client delivered —
        # a quarantined (or just-screened) late reply is log-and-ignored
        # exactly like an evicted id's, never aggregated.
        dropped = set(screened_names) | (quarantined_now & set(completed))
        if quarantined_now & set(completed):
            log.info(
                "round %d: ignoring quarantined updates from %s",
                self._round_counter, sorted(quarantined_now & set(completed)),
            )
        if dropped:
            keep_idx = [
                i for i, c in enumerate(order) if c not in dropped
            ]
            if stream and srows is not None and len(keep_idx) != len(order):
                srows = srows[jnp.asarray(keep_idx, jnp.int32)]
            order = [c for c in order if c not in dropped]

        if order:
            with tel.span("aggregate", participants=len(order)):
                if cfg.fed.weighted or tiered:
                    # Tier mode always takes this arm: completed[c][1] is
                    # the partial's WEIGHT SUM — the leaf already applied
                    # the configured weighting (example counts or 1.0 per
                    # client), so an unweighted federation's partials carry
                    # the cohort's contributor count here.
                    weights = jnp.asarray(
                        [completed[c][1] for c in order], jnp.float32
                    )
                else:
                    weights = jnp.ones((len(order),), jnp.float32)
                if stream:
                    # The rows are already device-resident (shipped on
                    # arrival) — the only post-barrier work is ONE fused
                    # finalize over the surviving rows. Tier mode's rows
                    # are pre-weighted partial SUMS and take the
                    # single-division combine (_finalize_partial_impl).
                    rows = srows
                    finalize = (
                        self._finalize_partial if tiered
                        else self._finalize_stream
                    )
                    new_global, self._server_opt_state = (
                        finalize(
                            {"params": self.params,
                             "batch_stats": self.batch_stats},
                            rows,
                            weights,
                            self._server_opt_state,
                        )
                    )
                else:
                    stacked = jax.tree.map(
                        lambda *leaves: jnp.stack(leaves),
                        *[completed[c][0] for c in order],
                    )
                    new_global, self._server_opt_state = self._aggregate(
                        {"params": self.params,
                         "batch_stats": self.batch_stats},
                        stacked,
                        weights,
                        self._server_opt_state,
                        jnp.asarray(self._round_counter, jnp.int32),
                    )
                self.params = new_global["params"]
                self.batch_stats = new_global["batch_stats"]
                # Block for the timing marks: the broadcast below needs the
                # values host-side moments later anyway (model_bytes), so
                # this costs nothing and makes the post-barrier gap honest.
                jax.block_until_ready(self.params)
        t_done = time.monotonic()
        # Advance the lineage counter BEFORE replication: the replica must
        # carry the next round's index, or a promoted backup would redraw
        # this round's DP noise key against a different aggregate.
        self._round_counter += 1

        self.status.update(phase="broadcast")
        payload = self.model_bytes()
        # Backup first (parity: replication before client broadcast,
        # src/server.py:141-153). The backup gets the replica payload —
        # model + server-optimizer moments — not the client payload.
        if self.backup_stub is not None:
            replica = self.replica_bytes()
            try:
                with tel.span("replicate", parent=rspan.id):
                    call_with_retry(
                        self.retry_policy, "SendModel",
                        lambda: self.backup_stub.SendModel(
                            proto.SendModelRequest(
                                model=replica,
                                epoch=self._coord_epoch, role=self._role,
                            ),
                            timeout=self._deadlines["SendModel"],
                        ),
                        peer="backup", telemetry=tel,
                        rand=self._retry_rand,
                    )
                bytes_down.inc(len(replica))
            except grpc.RpcError as e:
                if is_stale_coordinator(e):
                    self._handle_stale("Replicate", "backup", e)
                else:
                    log.warning("backup unreachable during replication")
                    tel.counter(
                        "fedtpu_rpc_failures_total",
                        "RpcErrors by failing RPC",
                        labels={"rpc": "Replicate"},
                    ).inc()

        def send_one(client: str) -> None:
            stub = self._stub(client)
            if stub is None:
                return  # evicted since the broadcast list was drawn
            try:
                with tel.span("broadcast", parent=rspan.id, client=client):
                    call_with_retry(
                        self.retry_policy, "SendModel",
                        lambda: stub.SendModel(
                            proto.SendModelRequest(
                                model=payload,
                                epoch=self._coord_epoch, role=self._role,
                            ),
                            timeout=self._deadlines["SendModel"],
                        ),
                        peer=client, telemetry=tel,
                        rand=self._retry_rand,
                    )
                bytes_down.inc(len(payload))
            except grpc.RpcError as e:
                if is_stale_coordinator(e):
                    self._handle_stale("SendModel", client, e)
                    return  # WE are stale; the client stays alive
                log.warning(
                    "client %s failed during SendModel: %s %s",
                    client, e.code(), e.details(),
                )
                tel.counter(
                    "fedtpu_rpc_failures_total",
                    "RpcErrors by failing RPC",
                    labels={"rpc": "SendModel"},
                ).inc()
                self.registry.mark_failed(client)

        # A client whose PREVIOUS round's broadcast is still in flight sits
        # this broadcast out: two concurrent SendModels to one client can
        # land out of order and install the older model last, silently
        # desyncing it for a round. (Mirrors the _inflight guard for
        # StartTrain.) The skipped client catches up next round — same
        # at-most-one-round-stale guarantee a straggler already has.
        send_busy = [
            c for c in self.registry.active_clients()
            if c in self._sends and self._sends[c].is_alive()
        ]
        if send_busy:
            log.warning("previous broadcast still in flight, skipping: %s",
                        send_busy)
        send_threads = {
            c: threading.Thread(target=send_one, args=(c,))
            for c in self.registry.active_clients()
            if c not in send_busy
        }
        for t in send_threads.values():
            t.start()
        if self.round_deadline_s is None:
            for t in send_threads.values():
                t.join()
        else:
            # The broadcast gets its own deadline budget too — an overloaded
            # client's slow SendModel+eval must not re-introduce the
            # blocking-on-slowest behavior the flag removes. A send still in
            # flight simply keeps running; RpcError marks failure as usual.
            deadline = time.monotonic() + self.round_deadline_s
            for t in send_threads.values():
                t.join(max(0.0, deadline - time.monotonic()))
        self._sends = {
            c: t
            for c, t in {**self._sends, **send_threads}.items()
            if t.is_alive()
        }

        rec = {
            # The LINEAGE round index (monotone across failovers and
            # rolling upgrades — the replica carries the counter), vs
            # "step", each generation's local 0-based count. The churn
            # soak's monotone-counter gate reads this field.
            "round": self._round_counter - 1,
            # The fencing epoch this round committed under: lineage
            # accounting across a healed partition keys on it (a stale
            # fork's records carry the superseded epoch).
            "epoch": self._coord_epoch,
            "participants": len(completed),
            "stragglers": len(stragglers),
            "world": world,
            # Rows that actually entered the combine (participants minus
            # screening rejections and ignored quarantined deliveries).
            "aggregated": len(order),
            "alive": [self.registry.is_alive(c) for c in roster_now],
            "membership_version": membership_version,
            # Flat-buffer footprint of this round's streaming collect (host
            # rows + the device twin; 0 on the barrier path) — with
            # process RSS, the leak axes the long-haul soaks watch.
            "buffer_bytes": (
                2 * int(host_rows[0].nbytes) if stream and host_rows else 0
            ),
            # Wire accounting (successful transfers only) — the reference
            # can't report this at all; its payloads are opaque base64 blobs
            # (src/client.py:21).
            "bytes_up": int(bytes_up.value),
            "bytes_down": int(bytes_down.value),
            "pipeline": self.server_pipeline,
            # Per-codec breakdown of bytes_up (successful replies only;
            # codec = what the record actually was, 'none' = dense).
            "bytes_up_by_codec": _sum_codec_bytes(
                codec_of[c] for c in completed if c in codec_of
            ),
            # Phase timing: collect is launch->last join; decode/h2d are
            # summed per-client (overlapped with network wait under
            # "stream", so they can exceed nothing of the wall clock);
            # post_barrier is the last-reply -> new-global gap the
            # streaming pipeline exists to shrink.
            "t_collect_s": round(t_barrier - t_launch, 6),
            "t_decode_s": round(decode_s.value, 6),
            "t_h2d_s": round(h2d_s.value, 6),
            "t_aggregate_s": round(t_done - t_barrier, 6),
            "t_post_barrier_s": round(t_done - t_barrier, 6),
            "t_round_s": round(t_done - t_launch, 6),
        }
        if tiered:
            # Topology accounting: participants above counts DIRECT peers
            # (aggregators); clients_aggregated is the leaf-client total
            # behind this round's partials — the fan-in bench's
            # work-vs-clients gate reads both.
            rec["tier_fanout"] = self.tier_fanout
            rec["clients_aggregated"] = int(clients_in.value)
        from fedtpu.obs.profile import latency_summary

        lat = latency_summary(
            [(c, latencies[c]) for c in completed if c in latencies]
        )
        if lat:
            # Straggler attribution: percentile spread + named top-3
            # slowest — the "which client is dragging the barrier" readout
            # the per-phase sums can't give (collect is launch->LAST join).
            rec["client_latency"] = lat
        if self._weights_ignored:
            # Operator-facing flag (satellite): the robust aggregator ran
            # UNWEIGHTED even though weighted=True — by design, not a bug.
            rec["weights_ignored"] = True
        if self._screen_jit is not None:
            rec["screened"] = screened_names
            rec["quarantined"] = sorted(
                self.registry.quarantined_clients()
            )
        self.history.append(rec)
        return rec

    # -------------------------------------------------------- async (FedBuff)
    def run_async(
        self,
        num_updates: int,
        buffer_k: int = 2,
        staleness_power: float = 0.5,
        stop: Optional[Callable[[], bool]] = None,
        on_update: Optional[Callable[[int, dict], None]] = None,
        staleness_damping: bool = True,
    ) -> List[dict]:
        """Semi-asynchronous orchestration (FedBuff, Nguyen et al. 2022).

        Instead of the synchronous round barrier, every live client loops
        independently: receive the current global model, train, reply. The
        server buffers incoming deltas and applies an aggregation as soon as
        ``buffer_k`` have arrived, weighting each by
        ``num_examples / (1 + staleness)**staleness_power`` where staleness
        is how many server updates landed since that client's base model.
        Fast clients contribute often; a slow client's (stale) delta still
        counts, just discounted — no one blocks anyone.

        ``staleness_damping`` (default True): the discount scales the
        applied update's MAGNITUDE (paper semantics, sum(disc*w*d)/sum(w));
        False is the weight-normalized mean, where a uniform-staleness
        buffer cancels the discount entirely — the mechanism behind the
        engine-side homogeneous-speed stall measured in round 5
        (:mod:`fedtpu.core.async_engine` docstring, the engine twin).

        The reference has no async mode at all (its barrier is
        ``src/server.py:132-135``); this composes with the plain mean
        aggregator + server optimizer only: compression (sparse deltas
        against stale baselines), robust aggregators (buffer_k is too small
        a population), and DP (per-update participation accounting differs)
        are rejected.

        Returns per-update records; runs until ``num_updates`` aggregations
        (or ``stop()``).
        """
        import queue

        fed = self.cfg.fed
        tel = self.telemetry
        if fed.compression != "none":
            raise ValueError(
                "run_async requires compression='none': sparse deltas "
                "against stale baselines corrupt aggregation."
            )
        if fed.aggregator != "mean":
            raise ValueError(
                "run_async requires aggregator='mean': a buffer of "
                f"{buffer_k} is too small a population for robust statistics."
            )
        if fed.dp_clip_norm > 0:
            raise ValueError(
                "run_async does not support DP: per-update participation "
                "accounting differs from the synchronous analysis."
            )
        if self._screen_jit is not None:
            raise ValueError(
                "run_async does not support update screening: the "
                f"buffer of {buffer_k} is too small a population for the "
                "median/MAD reference statistics. Use the synchronous "
                "round loop."
            )
        if buffer_k < 1:
            raise ValueError(f"buffer_k must be >= 1, got {buffer_k}")

        replies: "queue.Queue" = queue.Queue()
        done = threading.Event()
        version_lock = threading.Lock()
        self._async_version = 0

        def snapshot():
            """(version, payload, host base) for the CURRENT global model —
            computed ONCE per version (a full encode + device->host copy per
            worker iteration would serialize everyone on version_lock)."""
            return (
                self._async_version,
                self.model_bytes(),
                {
                    "params": jax.tree.map(np.asarray, self.params),
                    "batch_stats": jax.tree.map(np.asarray, self.batch_stats),
                },
            )

        current = [snapshot()]  # guarded by version_lock

        def worker(client: str, rank: int) -> None:
            """One client's loop: sync -> train -> enqueue, until done."""
            while not done.is_set():
                if not self.registry.is_alive(client):
                    time.sleep(0.2)  # heartbeat monitor may revive it
                    continue
                stub = self._stub(client)
                if stub is None:
                    return  # evicted mid-run: this worker retires
                try:
                    with version_lock:
                        base_version, payload, base = current[0]
                    call_with_retry(
                        self.retry_policy, "SendModel",
                        lambda: stub.SendModel(
                            proto.SendModelRequest(
                                model=payload,
                                epoch=self._coord_epoch, role=self._role,
                            ),
                            timeout=self._deadlines["SendModel"],
                        ),
                        peer=client, telemetry=tel,
                        rand=self._retry_rand,
                    )
                    tel.counter(
                        "fedtpu_rpc_bytes_down_total",
                        "server -> client/backup broadcast bytes (successful)",
                    ).inc(len(payload))

                    def train_attempt():
                        # RPC + decode as one retryable unit: a corrupt
                        # reply (WireError) is re-requested like any
                        # transient (see round()'s train_one).
                        reply = stub.StartTrain(
                            proto.TrainRequest(
                                # Each client keeps its OWN seat's shard;
                                # the synchronous path assigns the same
                                # stable seat ranks (see round()'s rank_of).
                                rank=rank, world=self.registry.capacity(),
                                epoch=self._coord_epoch,
                            ),
                            timeout=self._deadlines["StartTrain"],
                        )
                        tree = wire.decode(
                            reply.message,
                            _payload_template(self.model, self.cfg),
                        )
                        return reply, tree

                    reply, tree = call_with_retry(
                        self.retry_policy, "StartTrain", train_attempt,
                        peer=client, telemetry=tel,
                        rand=self._retry_rand,
                    )
                    tel.counter(
                        "fedtpu_rpc_bytes_up_total",
                        "client -> server StartTrain reply bytes (successful)",
                    ).inc(len(reply.message))
                    delta = jax.tree.map(
                        lambda a, g: np.asarray(a) - g,
                        {"params": tree["params"],
                         "batch_stats": tree["batch_stats"]},
                        base,
                    )
                    replies.put(
                        (client, delta, float(tree["num_examples"]),
                         base_version)
                    )
                except (grpc.RpcError, wire.WireError) as e:
                    if is_stale_coordinator(e):
                        # We are superseded: the client stays alive; this
                        # worker retires and the caller re-bases.
                        self._handle_stale("AsyncWorker", client, e)
                        return
                    if isinstance(e, grpc.RpcError):
                        log.warning(
                            "async client %s failed: %s %s",
                            client, e.code(), e.details(),
                        )
                    else:
                        log.warning(
                            "async client %s reply still corrupt after "
                            "retries: %s", client, e,
                        )
                    tel.counter(
                        "fedtpu_rpc_failures_total",
                        "RpcErrors by failing RPC",
                        labels={"rpc": "AsyncWorker"},
                    ).inc()
                    self.registry.mark_failed(client)

        self.monitor.start()
        if self.pinger is not None:
            self.pinger.tick()
            self.pinger.start()
        # One worker per member AT START; members admitted mid-run are
        # replicated/heartbeat-managed but only join the training loop on
        # the next run_async invocation (documented in FAULT_TOLERANCE.md).
        workers = [
            threading.Thread(target=worker, args=(c, rank), daemon=True)
            for c, rank in sorted(self.registry.seat_map().items())
        ]
        for w in workers:
            w.start()
        all_dead_since: List[Optional[float]] = [None]

        def hopeless() -> bool:
            """True when no reply can plausibly ever arrive again: every
            client dead (workers sleep-loop awaiting heartbeat revival, so
            thread liveness can't signal this), nothing buffered, and the
            state has persisted past several heartbeat cycles."""
            if self.registry.active_clients() or not replies.empty():
                all_dead_since[0] = None
                return False
            if all_dead_since[0] is None:
                all_dead_since[0] = time.monotonic()
            return time.monotonic() - all_dead_since[0] > 10.0

        poll_s = fed.async_poll_s
        # Async quorum (cfg.fed.round_quorum): an update only applies while
        # at least that fraction of the CURRENT membership (not the startup
        # roster — members join and leave) is alive — below it the
        # buffered deltas are held (global untouched) until the heartbeat
        # monitor revives enough clients, the async analogue of the
        # synchronous round abort. 0 = apply whenever buffer_k arrive.
        quorum_n = (
            max(1, math.ceil(fed.round_quorum * self.registry.size))
            if fed.round_quorum > 0 else 0
        )
        try:
            while self._async_version < num_updates:
                if stop is not None and stop():
                    break
                buf = []
                while len(buf) < buffer_k:
                    try:
                        buf.append(replies.get(timeout=poll_s))
                    except queue.Empty:
                        if (stop is not None and stop()) or hopeless():
                            break
                if len(buf) < buffer_k:
                    if hopeless():
                        log.warning("all async clients dead; stopping")
                        break
                    continue
                if quorum_n and len(self.registry.active_clients()) < quorum_n:
                    log.warning(
                        "async update held: %d alive < quorum %d; waiting "
                        "for recovery",
                        len(self.registry.active_clients()), quorum_n,
                    )
                    tel.counter(
                        "fedtpu_round_aborts_total",
                        "rounds aborted below quorum (global model untouched)",
                    ).inc()
                    while (len(self.registry.active_clients()) < quorum_n
                           and not hopeless()
                           and not (stop is not None and stop())):
                        time.sleep(poll_s)
                    if len(self.registry.active_clients()) < quorum_n:
                        log.warning("quorum never recovered; stopping")
                        break
                with tel.span("async_update"), version_lock:
                    v = self._async_version
                    stalenesses = [v - b for _, _, _, b in buf]
                    raw = [n if fed.weighted else 1.0 for _, _, n, _ in buf]
                    disc = [
                        w / (1.0 + s) ** staleness_power
                        for w, s in zip(raw, stalenesses)
                    ]
                    weights = jnp.asarray(disc, jnp.float32)
                    stacked = jax.tree.map(
                        lambda *leaves: jnp.stack(leaves),
                        *[d for _, d, _, _ in buf],
                    )
                    if staleness_damping:
                        # sum(disc*w*d)/sum(w): rescale so the discount
                        # damps the applied magnitude (see docstring).
                        # Scale in f32 and cast the PRODUCT back: rounding
                        # the factor itself to a narrow leaf dtype (bf16
                        # wire payloads) would silently diverge from the
                        # engine's f32 damping math.
                        damp = jnp.asarray(
                            sum(disc) / max(sum(raw), 1e-9), jnp.float32
                        )
                        stacked = jax.tree.map(
                            lambda l: (
                                l.astype(jnp.float32) * damp
                            ).astype(l.dtype),
                            stacked,
                        )
                    new_global, self._server_opt_state = self._aggregate(
                        {"params": self.params,
                         "batch_stats": self.batch_stats},
                        stacked,
                        weights,
                        self._server_opt_state,
                        jnp.asarray(v, jnp.int32),
                    )
                    self.params = new_global["params"]
                    self.batch_stats = new_global["batch_stats"]
                    self._async_version = v + 1
                    # Keep the lineage counter monotone across modes so a
                    # backup promoted from async replicas (which runs the
                    # synchronous loop) continues the PRNG sequence.
                    self._round_counter += 1
                    current[0] = snapshot()
                if self.backup_stub is not None:
                    try:
                        call_with_retry(
                            self.retry_policy, "SendModel",
                            lambda: self.backup_stub.SendModel(
                                proto.SendModelRequest(
                                    model=self.replica_bytes(),
                                    epoch=self._coord_epoch, role=self._role,
                                ),
                                timeout=self._deadlines["SendModel"],
                            ),
                            peer="backup", telemetry=tel,
                            rand=self._retry_rand,
                        )
                    except grpc.RpcError as e:
                        if is_stale_coordinator(e):
                            self._handle_stale("Replicate", "backup", e)
                        else:
                            log.warning(
                                "backup unreachable during replication"
                            )
                rec = {
                    "update": self._async_version,
                    "contributors": [c for c, _, _, _ in buf],
                    "staleness": stalenesses,
                    "alive": self.registry.alive_mask().tolist(),
                }
                self.history.append(rec)
                self.status.update(
                    round=self._round_counter, phase="async",
                    async_update=self._async_version,
                )
                self.flight.record(
                    "async_update",
                    update=self._async_version,
                    contributors=len(buf),
                )
                if tel.enabled:
                    tel.counter(
                        "fedtpu_async_updates_total",
                        "FedBuff server updates applied",
                    ).inc()
                    stale_hist = tel.histogram(
                        "fedtpu_async_staleness",
                        "staleness (server updates) of buffered deltas at "
                        "apply time",
                        buckets=(0, 1, 2, 4, 8, 16, 32, 64),
                    )
                    for s in stalenesses:
                        stale_hist.observe(s)
                log.info("async update %s", rec)
                if on_update is not None:
                    on_update(self._async_version, rec)
            # Deliver the FINAL model: workers stop syncing once done is
            # set, and without this every client would end at least one
            # update stale (the synchronous path broadcasts every round).
            done.set()
            for w in workers:
                w.join(timeout=self.rpc_timeout)
            self.sync_clients()
        finally:
            done.set()
            self.monitor.stop()
            if self.pinger is not None:
                self.pinger.stop()
        return self.history

    def run(
        self,
        num_rounds: Optional[int] = None,
        stop: Optional[Callable[[], bool]] = None,
        on_round: Optional[Callable[[int, dict], None]] = None,
    ) -> List[dict]:
        """Drive rounds with background heartbeat + backup ping threads.
        ``stop()`` is polled between rounds (used by failover demotion);
        ``on_round(r, record)`` runs after each round (checkpointing,
        metrics)."""
        if num_rounds is None:
            num_rounds = self.cfg.fed.num_rounds
        self.monitor.start()
        if self.pinger is not None:
            # First ping synchronously: if the backup was acting primary, the
            # demotion + model fetch must land before we train round 0.
            self.pinger.tick()
            self.pinger.start()
        # The first round() call broadcasts the global model before training
        # (see sync_clients) — after the pinger tick above, so a model
        # fetched from a demoting backup is what gets synced.
        try:
            r = 0
            consecutive_aborts = 0
            while r < num_rounds:
                if stop is not None and stop():
                    log.info("round loop stopped (demotion) after %d rounds", r)
                    break
                if self._fenced:
                    # Superseded by a higher epoch (healed partition):
                    # re-base on the winning lineage before training again.
                    self.handle_fence()
                    continue
                rec = self.round()
                if rec.get("aborted"):
                    # Sub-quorum round: the global is untouched; re-run it
                    # once the heartbeat monitor (running in this loop) has
                    # had a chance to revive clients. The abort IS reported
                    # (an ``aborted: true`` record in the round log — an
                    # operator must see it), it just doesn't count toward
                    # num_rounds. A federation that NEVER recovers must not
                    # spin forever.
                    if on_round is not None:
                        on_round(r, rec)
                    consecutive_aborts += 1
                    if consecutive_aborts >= 50:
                        log.error(
                            "round %d aborted %d times in a row below "
                            "quorum; giving up", r, consecutive_aborts,
                        )
                        break
                    if rec.get("fenced"):
                        continue  # re-base immediately, no heartbeat wait
                    time.sleep(self.monitor.period)
                    continue
                consecutive_aborts = 0
                log.info("round %d: %s", r, rec)
                if on_round is not None:
                    on_round(r, rec)
                r += 1
        finally:
            self.monitor.stop()
            if self.pinger is not None:
                self.pinger.stop()
        return self.history


# ----------------------------------------------------------------------- gate
class _MembershipGate(TrainerServicer):
    """The coordinator's inbound membership surface: Join admits the
    caller's advertised serving address into the primary's
    :class:`~fedtpu.ft.membership.MembershipTable` (and resyncs it with the
    current global model through the heartbeat-revival path), Leave evicts
    it gracefully. Hosted by :meth:`PrimaryServer.start_gate`; all other
    RPCs stay UNIMPLEMENTED — the gate is not a Trainer."""

    def __init__(self, primary: "PrimaryServer"):
        self.primary = primary

    def Join(self, request: proto.JoinRequest, context) -> proto.JoinReply:
        address = request.address.decode()
        if not address:
            return proto.JoinReply(admitted=0, message=b"empty address")
        out = self.primary.admit_client(address)
        return proto.JoinReply(
            admitted=1, seat=out["seat"], world=out["world"],
            version=out["version"],
            message=b"resynced" if out["resynced"] else b"pending resync",
        )

    def Leave(self, request: proto.LeaveRequest, context) -> proto.LeaveReply:
        address = request.address.decode()
        out = self.primary.remove_client(address, reason="leave")
        return proto.LeaveReply(
            left=1 if out["left"] else 0, version=out["version"]
        )

    def HeartBeat(self, request: proto.Request, context) -> proto.HeartBeatResponse:
        return proto.HeartBeatResponse(status=1)


# --------------------------------------------------------------------- backup
class BackupServer(TrainerServicer):
    """Backup-side servicer + failover driver (parity:
    ``src/server.py:235-264``): absorbs model replication, answers primary
    pings, and promotes to acting primary on watchdog expiry. On promotion it
    runs the primary round loop seeded with the replicated model; a
    recovering primary's first ping demotes it back."""

    def __init__(
        self,
        cfg: RoundConfig,
        clients: List[str],
        compress: bool = False,
        watchdog_timeout: Optional[float] = None,
        round_deadline_s: Optional[float] = None,
        flight: Optional[FlightRecorder] = None,
        chaos=None,
        on_acting_round: Optional[Callable[[int, dict], None]] = None,
    ):
        """``on_acting_round(r, record)``: forwarded to the acting
        primary's round loop after a promotion — the hook rolling-upgrade
        and churn drills use to keep their per-round bookkeeping (round
        records, scripted churn) running across the failover window."""
        self.cfg = cfg
        self.clients = clients
        self.compress = compress
        # Forwarded to the acting PrimaryServer on promotion, so straggler
        # mitigation (and fault injection) survive failover.
        self.round_deadline_s = round_deadline_s
        self.chaos = chaos
        self.on_acting_round = on_acting_round
        if watchdog_timeout is None:
            watchdog_timeout = cfg.fed.ft_watchdog_timeout_s
        log.info(
            "backup timings: watchdog=%.1fs chaos=%s",
            watchdog_timeout,
            chaos.describe() if chaos is not None else "off",
        )
        self.latest_model: Optional[bytes] = None
        self.acting: Optional[PrimaryServer] = None
        self.telemetry = Telemetry(cfg.fed.telemetry, role="backup")
        # The black box this module exists for: the state machine dumps it
        # on EVERY promote/demote, so the run-up to a role flip survives
        # even if the promoted process dies seconds later.
        self.flight = flight if flight is not None else FlightRecorder(
            role="backup"
        )
        self.machine = FailoverStateMachine(
            timeout=watchdog_timeout,
            on_promote=self._promote,
            on_demote=self._demote,
            metrics=(
                self.telemetry.registry if self.telemetry.enabled else None
            ),
            flight=self.flight,
        )
        self.watchdog = WatchdogRunner(self.machine)
        # Per-promotion stop event: a primary flap must not re-arm a stopped
        # acting primary (each promotion gets a fresh event + thread).
        self._acting_stop: Optional[threading.Event] = None
        self._promote_thread: Optional[threading.Thread] = None
        # Fencing (docs/FAULT_TOLERANCE.md §Fencing): the max coordinator
        # epoch this backup has seen — on replication, on pings, and on its
        # own promotions (each mint advances it). A lower-epoch replication
        # or steady-state ping is a superseded primary and gets the typed
        # STALE_COORDINATOR rejection.
        self._epoch_seen = -1

    # ------------------------------------------------------------- servicer
    def _fence_check(self, epoch: int, rpc: str, context) -> None:
        """Track the max coordinator epoch; abort a stale sender (same
        contract as ClientAgent._fence_check)."""
        if epoch < 0:
            return  # pre-fencing peer
        if epoch >= self._epoch_seen:
            self._epoch_seen = epoch
            return
        log.warning(
            "%s from stale coordinator epoch %d rejected (newest seen %d)",
            rpc, epoch, self._epoch_seen,
        )
        self.telemetry.counter(
            "fedtpu_ft_stale_rejected_total",
            "coordinator RPCs rejected for a stale fencing epoch, by rpc",
            labels={"rpc": rpc},
        ).inc()
        context.abort(
            grpc.StatusCode.FAILED_PRECONDITION,
            f"STALE_COORDINATOR: epoch {epoch} < {self._epoch_seen}",
        )

    def SendModel(self, request: proto.SendModelRequest, context) -> proto.SendModelReply:
        # A stale primary's replica must never overwrite the replication
        # slot: after we promoted past it, its lineage is void.
        self._fence_check(request.epoch, "Replicate", context)
        self.latest_model = request.model
        return proto.SendModelReply(reply=b"replicated")

    def CheckIfPrimaryUp(self, request: proto.PingRequest, context) -> proto.PingResponse:
        recovering = request.req == b"1"
        # Steady-state pings from a superseded primary are fenced — they
        # must not keep resetting our watchdog (that would let a stale
        # coordinator suppress re-promotion forever). The RECOVERING ping
        # is the heal handshake (demote + FetchModel re-base) and must
        # pass whatever its epoch, or a fenced ex-primary could never
        # re-base through us.
        if not recovering:
            self._fence_check(request.epoch, "CheckIfPrimaryUp", context)
        elif request.epoch > self._epoch_seen:
            self._epoch_seen = request.epoch
        return proto.PingResponse(value=self.machine.on_ping(recovering))

    def HeartBeat(self, request: proto.Request, context) -> proto.HeartBeatResponse:
        return proto.HeartBeatResponse(status=1)

    def FetchModel(self, request: proto.Request, context) -> proto.SendModelRequest:
        """Hand the newest model we hold to a recovered primary — the acting
        primary's final model if we trained in its absence, else the last
        replicated blob. Waits for a draining acting round to finish so the
        returned model is settled, not mid-aggregation (the caller's fetch
        timeout is generous)."""
        self._stop_acting(wait=300.0)
        acting = self.acting
        if acting is not None and acting.history:
            return proto.SendModelRequest(model=acting.replica_bytes())
        return proto.SendModelRequest(model=self.latest_model or b"")

    def Join(self, request: proto.JoinRequest, context) -> proto.JoinReply:
        """Membership during a failover window: the backup's address is the
        STABLE join target — while it is acting primary, joins land in the
        acting coordinator's roster (and replicate back to the recovered
        primary through the state tree); in the backup role it refuses,
        pointing the joiner back at the primary's gate."""
        from fedtpu.ft import Role

        acting = self.acting
        if self.machine.role is Role.ACTING_PRIMARY and acting is not None:
            return _MembershipGate(acting).Join(request, context)
        return proto.JoinReply(admitted=0, message=b"not primary")

    def Leave(self, request: proto.LeaveRequest, context) -> proto.LeaveReply:
        from fedtpu.ft import Role

        acting = self.acting
        if self.machine.role is Role.ACTING_PRIMARY and acting is not None:
            return _MembershipGate(acting).Leave(request, context)
        return proto.LeaveReply(left=0)

    def status_snapshot(self) -> dict:
        """``/statusz`` feed for the backup role: failover state + (when
        promoted) the acting primary's own status nested under
        ``acting``."""
        machine = self.machine
        since = machine.seconds_since_ping()
        snap = {
            "role": machine.role.value,
            "pid": os.getpid(),
            "watchdog_timeout_s": machine.timeout,
            "seconds_since_primary_ping": (
                None if since == float("inf") else round(since, 3)
            ),
            "has_replica": self.latest_model is not None,
            "epoch_seen": self._epoch_seen,
        }
        acting = self.acting
        if acting is not None and machine.role.value == "acting_primary":
            snap["acting"] = acting.status_snapshot()
        return snap

    def health(self) -> Tuple[bool, str]:
        """Honest /healthz for the backup role: while acting primary,
        delegate to the acting coordinator's verdict (fenced / quorum);
        in the backup role the process is healthy by construction."""
        from fedtpu.ft import Role

        acting = self.acting
        if self.machine.role is Role.ACTING_PRIMARY and acting is not None:
            return acting.health()
        return True, "ok"

    # -------------------------------------------------------------- failover
    def _promote(self) -> None:
        log.warning("watchdog expired: promoting to acting primary")
        self._stop_acting()
        stop_event = threading.Event()
        self._acting_stop = stop_event
        try:
            acting = PrimaryServer(
                self.cfg,
                self.clients,
                compress=self.compress,
                initial_model=self.latest_model,
                round_deadline_s=self.round_deadline_s,
                flight=self.flight,
                chaos=self.chaos,
            )
        except wire.WireError:
            # A corrupted replica must fail loudly — but not by silently
            # killing the watchdog thread and leaving the federation with NO
            # primary at all. Promote with a fresh model: degraded (the
            # trajectory restarts) but live, and the log says exactly why.
            log.exception(
                "replicated model is corrupted or config-mismatched; "
                "promoting with a freshly initialised model"
            )
            acting = PrimaryServer(
                self.cfg,
                self.clients,
                compress=self.compress,
                round_deadline_s=self.round_deadline_s,
                flight=self.flight,
                chaos=self.chaos,
            )
        # Mint the promotion epoch: strictly past both the replicated
        # lineage's epoch (installed above from the replica payload) and
        # anything this backup has ever seen on the wire. From now on the
        # old primary's epoch is stale everywhere this coordinator speaks.
        acting._set_epoch(max(acting._coord_epoch, self._epoch_seen) + 1)
        acting._role = 2
        self._epoch_seen = acting._coord_epoch
        log.warning("promotion minted coordinator epoch %d",
                    acting._coord_epoch)
        self.acting = acting

        def run_acting():
            acting.run(stop=stop_event.is_set,
                       on_round=self.on_acting_round)
            # Whatever the acting primary trained becomes the replication
            # state, so a later re-promotion (or FetchModel from the
            # recovered primary) starts from its progress, not from the
            # pre-failover snapshot.
            if acting.history:
                self.latest_model = acting.replica_bytes()

        self._promote_thread = threading.Thread(target=run_acting, daemon=True)
        self._promote_thread.start()

    def _demote(self) -> None:
        # Runs inside the CheckIfPrimaryUp handler: signal only, never join —
        # the recovering primary's ping has a 2 s deadline. The drain is
        # awaited by FetchModel (or the next promotion).
        log.warning("primary recovered: demoting to backup")
        if self._acting_stop is not None:
            self._acting_stop.set()

    def _stop_acting(self, wait: float = 120.0) -> None:
        if self._acting_stop is not None:
            self._acting_stop.set()
        # Read the thread once: FetchModel (a gRPC worker), the next
        # promotion (the watchdog thread) and a caller's shutdown can all be
        # in here at once, and one of them clears the field while another
        # is still joining.
        thread = self._promote_thread
        if thread is not None:
            thread.join(timeout=wait)
            if not thread.is_alive() and self._promote_thread is thread:
                self._promote_thread = None

    def start(self, address: str):
        """Host the backup servicer + watchdog; returns the grpc server."""
        server = create_server(
            address, self, compress=self.compress, chaos=self.chaos
        )
        server.start()
        self.watchdog.start()
        return server
