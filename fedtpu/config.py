"""Central configuration for fedtpu.

The reference scatters configuration across three argparse surfaces and many
hardcoded constants (reference: ``src/server.py:270-274``, ``src/client.py:56-59``,
``src/main.py:20-26``; hardcoded round count at ``server.py:120``, model choice at
``main.py:69``, optimizer at ``main.py:99-101``). fedtpu centralises everything in
typed, hashable dataclasses so configs can be closed over by jitted functions.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Transient-fault handling for every RPC the federation issues.

    The pre-policy transport treated each RPC as one shot: a single
    transient ``grpc.RpcError`` (a TCP reset, a brief listener restart, an
    overloaded peer) marked the client dead for the round and handed it to
    the heartbeat/resync machinery — the failure path the paper reserves
    for *real* failures. Under this policy an RPC whose status code is in
    ``transient_codes`` (or whose reply payload fails the wire CRC — see
    :mod:`fedtpu.transport.wire`) is retried with exponential backoff +
    jitter up to ``max_attempts`` total attempts; only EXHAUSTED retries
    reach ``ClientRegistry.mark_failed``. Fatal codes (UNIMPLEMENTED,
    INVALID_ARGUMENT, ...) never retry — a config-mismatched peer must
    fail loudly, not thrash.

    Per-RPC deadlines live here too, replacing the constants that were
    scattered through the transport (StartTrain/SendModel 600 s at the old
    ``PrimaryServer(rpc_timeout=...)`` default, backup ping 2.0 s,
    heartbeat probe 1.0 s). Defaults reproduce the old values exactly, so
    a default-config federation behaves bit-identically in the absence of
    faults (retries only ever run where the old code failed).
    """

    # Total attempts per logical RPC (1 = the old single-shot behavior).
    max_attempts: int = 3
    backoff_s: float = 0.05          # sleep before attempt 2
    backoff_multiplier: float = 2.0  # growth per further attempt
    backoff_max_s: float = 2.0
    # Fraction of each backoff randomized (decorrelates retry storms;
    # irrelevant to determinism — fault *injection* is seeded, not retry
    # spacing).
    jitter: float = 0.2
    # grpc.StatusCode names treated as transient (retryable). Everything
    # else — UNIMPLEMENTED, INVALID_ARGUMENT, FAILED_PRECONDITION, ... —
    # is fatal and fails the call on the first attempt.
    transient_codes: Tuple[str, ...] = (
        "UNAVAILABLE",
        "DEADLINE_EXCEEDED",
        "RESOURCE_EXHAUSTED",
        "ABORTED",
        "INTERNAL",
        "UNKNOWN",
    )
    # Per-RPC deadlines (seconds). The data-plane deadlines default to the
    # old blanket rpc_timeout=600.0; the control-plane ones to the old
    # hardcoded constants they replace.
    start_train_timeout_s: float = 600.0
    send_model_timeout_s: float = 600.0
    fetch_model_timeout_s: float = 600.0
    probe_timeout_s: float = 1.0        # HeartBeat (was probe() default)
    backup_ping_timeout_s: float = 2.0  # CheckIfPrimaryUp (was literal 2.0)


def validate_retry_policy(rp: RetryPolicy) -> RetryPolicy:
    if rp.max_attempts < 1:
        raise ValueError(f"retry max_attempts must be >= 1, got {rp.max_attempts}")
    if rp.backoff_s < 0 or rp.backoff_max_s < 0:
        raise ValueError("retry backoff seconds must be >= 0")
    if rp.backoff_multiplier < 1.0:
        raise ValueError(
            f"retry backoff_multiplier must be >= 1, got {rp.backoff_multiplier}"
        )
    if not 0.0 <= rp.jitter <= 1.0:
        raise ValueError(f"retry jitter must be in [0, 1], got {rp.jitter}")
    return rp


@dataclasses.dataclass(frozen=True)
class ScreenConfig:
    """Fused update screening + client reputation (docs/FAULT_TOLERANCE.md).

    Screening is the defense for the DEFAULT fast path: median/trimmed_mean/
    krum protect the aggregate but are barrier-only and rewrite its math;
    screening instead REJECTS suspicious client rows before any combine, as
    one fused stats pass over the flat ``[clients, P]`` delta buffer
    (:func:`fedtpu.ops.flat.screen_rows`) — so it composes with
    ``server_pipeline='stream'``, with the plain mean, and with the robust
    aggregators (screened rows simply drop out of the weighted/robust
    combine through the existing exclusion mask, bit-cleanly).

    Three per-row statistics, each gated by its own threshold (0 / -1 =
    that check off; screening as a whole is off when all three are off):

    - ``norm_max``: absolute L2 bound on the update row — the blunt
      norm-bound defense against boosted/scaled updates.
    - ``zmax``: modified z-score bound on the row norms, computed against
      the live cohort's median/MAD (robust to the attackers inflating the
      spread, unlike a mean/std z-score); rejects norm outliers without an
      absolute calibration.
    - ``cos_min``: minimum cosine of the row against the live cohort's
      coordinate-wise median direction; rejects sign-flipped/contrarian
      updates whose norms look ordinary.

    Reputation closes the loop from per-round verdicts to membership
    action: every screening verdict feeds a per-client suspicion EWMA
    (``s' = (1-ewma)*s + ewma*flagged``) held on the
    :class:`~fedtpu.ft.membership.MembershipTable` and replicated to the
    backup. ``s >= quarantine_at`` escalates flagged -> QUARANTINED (the
    client still receives broadcasts and StartTrain — it can redeem itself
    — but its updates are ignored unconditionally); dropping back below
    ``release_at`` releases it; ``evict_after`` consecutive quarantined
    rounds escalates to eviction through the live membership machinery
    (``remove_client(reason='quarantine')``). ``evict_after=0`` = never
    auto-evict (quarantine is already containment).
    """

    norm_max: float = 0.0
    zmax: float = 0.0
    cos_min: float = -1.0
    ewma: float = 0.5
    quarantine_at: float = 0.75
    release_at: float = 0.25
    evict_after: int = 0


def screening_enabled(screen: ScreenConfig) -> bool:
    """True when any screening statistic is armed."""
    return screen.norm_max > 0 or screen.zmax > 0 or screen.cos_min > -1.0


def validate_screen_config(screen: ScreenConfig) -> ScreenConfig:
    if screen.norm_max < 0:
        raise ValueError(f"screen norm_max must be >= 0, got {screen.norm_max}")
    if screen.zmax < 0:
        raise ValueError(f"screen zmax must be >= 0, got {screen.zmax}")
    if not -1.0 <= screen.cos_min <= 1.0:
        raise ValueError(
            f"screen cos_min must be in [-1, 1], got {screen.cos_min}"
        )
    if not 0.0 < screen.ewma <= 1.0:
        raise ValueError(f"screen ewma must be in (0, 1], got {screen.ewma}")
    if not 0.0 <= screen.release_at <= screen.quarantine_at <= 1.0:
        raise ValueError(
            "screen thresholds must satisfy 0 <= release_at <= "
            f"quarantine_at <= 1, got release_at={screen.release_at} "
            f"quarantine_at={screen.quarantine_at}"
        )
    if screen.evict_after < 0:
        raise ValueError(
            f"screen evict_after must be >= 0, got {screen.evict_after}"
        )
    return screen


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Per-client local optimizer.

    Defaults mirror the reference trainer's *effective* behavior:
    SGD(lr=0.1, momentum=0.9, weight_decay=5e-4) at a CONSTANT learning rate.
    The reference constructs CosineAnnealingLR(T_max=200)
    (``src/main.py:101``) but never steps it — the driver loop containing
    ``scheduler.step()`` is commented out (``src/main.py:231-242``) and the
    federated ``train(epoch, rank, world)`` path (``src/main.py:128-165``)
    doesn't step it either — so its effective LR is always 0.1.
    ``schedule='cosine'`` implements the schedule the reference *intended*;
    parity runs pin ``schedule='constant'``.
    """

    learning_rate: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 5e-4
    # constant (reference effective behavior) | cosine (reference intent).
    schedule: str = "constant"
    # Cosine annealing horizon in *rounds* (the reference steps its scheduler
    # per epoch; in federated mode one round == one local epoch).
    cosine_t_max: int = 200
    nesterov: bool = False
    # HBM dtype of the per-client momentum buffers. "float32" is reference
    # parity (torch SGD buffers are f32). "bfloat16" is an opt-in NON-PARITY
    # mode that halves optimizer-state HBM traffic — BASELINE.md's bandwidth
    # roofline names f32 param+momentum traffic (~0.5 GB/step at the
    # 64-client bench) as a leading consumer. The buffer update is always
    # computed in f32; only the stored buffer is rounded, so the mode's
    # entire effect is one bf16 round-trip per step per buffer.
    momentum_dtype: str = "float32"  # float32 | bfloat16

    def lr_at(self, round_idx) -> float:
        """Learning rate for a given round (traceable)."""
        import jax.numpy as jnp

        if self.schedule == "constant":
            return jnp.asarray(self.learning_rate, jnp.float32)
        if self.schedule != "cosine":
            raise ValueError(f"unknown schedule: {self.schedule!r}")
        t = jnp.minimum(round_idx, self.cosine_t_max)
        return self.learning_rate * 0.5 * (
            1.0 + jnp.cos(jnp.pi * t / self.cosine_t_max)
        )


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Dataset + partitioning.

    ``partition='round_robin'`` reproduces the reference's shard rule where
    client ``rank`` keeps batch ``i`` iff ``(i + 1) % world == rank``
    (reference: ``src/main.py:141-144``). Other partitioners (iid, dirichlet)
    cover the BASELINE.md parity configs.
    """

    dataset: str = "cifar10"  # cifar10 | cifar100 | mnist | synthetic
    batch_size: int = 128  # reference: src/main.py:51
    eval_batch_size: int = 100  # reference: src/main.py:56
    partition: str = "round_robin"  # round_robin | iid | dirichlet
    dirichlet_alpha: float = 0.5
    augment: bool = True  # random crop + flip (reference: src/main.py:37-42)
    # The random-crop half of the augmentation (the horizontal flip always
    # applies while ``augment`` is on). The crop is the shift-accumulate
    # "fastcrop" formulation (fedtpu.data.augment, default-on; measured 2.0x
    # on-chip vs the dynamic-slice crop, artifacts/BENCH_LIVE_r04_fastcrop).
    # ``augment_crop=False`` skips the crop entirely — flip-only, with a
    # bit-parity pin in tests (the rng split structure is shared, so the
    # flip draw is identical either way).
    augment_crop: bool = True
    seed: int = 0
    # Truncate the loaded dataset (None = full). Mainly for tests and quick
    # runs; the reference always trains on the full set.
    num_examples: Optional[int] = None
    # HBM layout of the device-resident dataset (fedtpu.data.device).
    #   "presharded": the dataset is reorganised ONCE at upload into
    #     [clients, 2*shard_len, features] (each client's shard, cycled to
    #     pad and stored twice along the shard axis), so each round's batch
    #     extraction is ONE contiguous dynamic-slice at a per-round rotation
    #     offset. Measured motivation: the gather layout's computed-index
    #     row-gather lowers on TPU to ~2 us dynamic-slice loops per example
    #     (~250k ops/dispatch at the 64-client CIFAR bench,
    #     artifacts/MFU_PROFILE_r04.json) and dominates the fused round.
    #   "gather": dataset stays [N, features]; batches come from a per-round
    #     index gather (exact per-round permutation shuffling, arbitrary
    #     shard-length raggedness, no 2x data HBM). The exact semantics of
    #     rounds 1-3 artifacts.
    device_layout: str = "presharded"  # presharded | gather


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Massive-cohort simulation (:mod:`fedtpu.sim`): decouple the simulated
    **population** from the per-round **cohort**.

    With ``population > 0`` the engine CLI runs a
    :class:`fedtpu.sim.engine.SimFederation`: ``population`` clients exist
    as lightweight host-side rows (dataset assignment, last-seen loss,
    availability, sampling bookkeeping) while the device keeps only the
    engine's fixed ``FedConfig.num_clients``-sized buffers — the cohort. A
    seeded sampler draws each round's cohort and its rows are gathered into
    those buffers, so device memory is O(cohort), not O(population)
    (FedJAX-style, arXiv:2108.02117). ``population == num_clients`` with the
    uniform sampler reproduces the resident engine bit-for-bit (test-pinned).
    """

    # 0 = off (resident engine: every client is a live device slot).
    population: int = 0
    # How each round's cohort is drawn from the available population:
    # "uniform" (without replacement) | "loss" (proportional to last-seen
    # training loss, optimistic prior for never-sampled clients).
    cohort_sampler: str = "uniform"
    # Scenario spec for the POPULATION partition (fedtpu.sim.scenario), e.g.
    # "pathological:shards=2" or "dirichlet:alpha=0.1+quantity_skew:power=1.5".
    # "" = use DataConfig.partition unchanged.
    scenario: str = ""
    # Optimistic loss prior for never-sampled clients under the "loss"
    # sampler; < 0 = the max observed loss (the engine's existing fill rule).
    loss_prior: float = -1.0
    # Availability/churn trace (fedtpu.sim.population.Population): stationary
    # up-fraction and per-round P(up -> down). availability=1, churn=0 =
    # everyone always available.
    availability: float = 1.0
    churn: float = 0.0
    # Extra sampler seed (folded with data.seed so two sim runs over the
    # same data can draw different cohort sequences).
    seed: int = 0
    # Adversarial-participant axis (fedtpu.sim.adversary): this fraction of
    # the simulated population (or of num_clients on the resident engine)
    # is seeded Byzantine — their client ids are a deterministic function
    # of (data.seed, sim.seed), so attack runs replay bit-identically.
    malicious_fraction: float = 0.0
    # What the attackers DO, as an attack spec
    # "kind[:key=val,...]": sign_flip | scale:factor=F | noise:std=S |
    # label_flip:offset=K, with shared options p= (per-round fire
    # probability), rounds=lo-hi (half-open round window) and collude=1
    # (colluding-cohort mode: one shared draw/noise vector for the whole
    # malicious set — the coordinated attack that defeats distance-based
    # defenses like krum when uncoordinated noise would not).
    attack: str = "sign_flip"


def validate_sim_config(fed: "FedConfig") -> None:
    """Raise on inconsistent sim settings (cheap, before any build work)."""
    sim = fed.sim
    if not 0.0 <= sim.malicious_fraction < 1.0:
        raise ValueError(
            f"sim.malicious_fraction must be in [0, 1), got "
            f"{sim.malicious_fraction}"
        )
    if sim.malicious_fraction > 0:
        from fedtpu.sim.adversary import parse_attack

        parse_attack(sim.attack)  # raises on a malformed spec
    if sim.population <= 0:
        return
    if sim.population < fed.num_clients:
        raise ValueError(
            f"sim.population={sim.population} < cohort "
            f"(num_clients={fed.num_clients}); the cohort is drawn FROM the "
            "population"
        )
    if sim.cohort_sampler not in ("uniform", "loss"):
        raise ValueError(
            f"unknown cohort_sampler {sim.cohort_sampler!r}; "
            "have uniform | loss"
        )
    if fed.participation_fraction != 1.0:
        raise ValueError(
            "sim.population and participation_fraction are mutually "
            "exclusive: the cohort sampler IS the participation model "
            "(set participation_fraction=1.0)"
        )
    if not 0.0 < sim.availability <= 1.0:
        raise ValueError(
            f"sim.availability must be in (0, 1], got {sim.availability}"
        )
    if not 0.0 <= sim.churn <= 1.0:
        raise ValueError(f"sim.churn must be in [0, 1], got {sim.churn}")


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """Federated topology + algorithm."""

    num_clients: int = 2  # reference default: two clients (src/server.py:281-282)
    num_rounds: int = 20  # reference: src/server.py:120
    local_epochs: int = 1  # reference: one epoch per StartTrain (src/client.py:17)
    algorithm: str = "fedavg"  # fedavg | fedprox
    fedprox_mu: float = 0.0
    # Uniform (unweighted) averaging matches the reference aggregator
    # (src/server.py:163-171); weighted=True uses per-client example counts.
    weighted: bool = True
    # Client sampling fraction per round (1.0 == all clients, reference behavior).
    participation_fraction: float = 1.0
    # How the sampled subset is drawn: "uniform", or "loss" — importance
    # sampling proportional to each client's last observed training loss
    # (clients the model serves worst get picked more often; see e.g.
    # arXiv:2306.03240). Falls back to uniform until a loss is observed.
    participation_sampling: str = "uniform"  # uniform | loss
    # Compression of client deltas before aggregation (parity with -c Y,
    # reference: src/server.py:104-107).
    #   none | topk | int8, any delta_layout; plus the seeded sketch codecs
    #   rotq (rotated b-bit quantization, rotq_bits below) and randk
    #   (random-coordinate subsampling, reusing topk_fraction as the keep
    #   fraction) — flat-layout only (docs/FLAT_DELTA.md §Codec matrix).
    compression: str = "none"
    topk_fraction: float = 0.01
    error_feedback: bool = True
    # Bit width for compression='rotq' (1 | 2 | 4 | 8): wire cost is
    # rotq_bits * pow2(P) / 8 bytes per client per round.
    rotq_bits: int = 4
    # Codec selection on the distributed edge (fedtpu.transport.federation):
    #   "static": every client uses `compression` every round (the default).
    #   "adaptive": the coordinator picks a codec per client per round from
    #     {none, int8, topk, rotq, randk} by observed bytes x RTT
    #     (fedtpu.transport.codec_policy.AdaptiveCodecPolicy), shipping the
    #     choice in StartTrain. Requires delta_layout='flat' (the sketch
    #     codecs only exist there). Engine-side federation ignores this.
    codec_policy: str = "static"  # static | adaptive
    # HOW the per-client delta travels through compression/aggregation.
    #   "per_leaf": every codec stage + the FedAvg reduction run once per
    #     pytree leaf (the original path; the parity default).
    #   "flat": all leaves are packed once per round into one lane-aligned
    #     [clients, P] buffer (fedtpu.ops.flat) — one top_k / one quantize /
    #     one reduction per round instead of hundreds on deep zoo models.
    #     Bit-identical aggregates for compression='none' and 'int8'; for
    #     'topk' the keep budget becomes GLOBAL across the model instead of
    #     per-leaf (documented in docs/FLAT_DELTA.md).
    delta_layout: str = "per_leaf"  # per_leaf | flat
    # Server-side optimizer applied to the aggregated delta (the FedOpt
    # family, Reddi et al. 2021 — "adaptive federated optimization"). The
    # reference applies the mean delta directly (src/server.py:170-179),
    # which is server_optimizer="none" (== FedAvg). "momentum" = FedAvgM,
    # "adam" = FedAdam, "yogi" = FedYogi; the mean client delta acts as the
    # pseudo-gradient.
    server_optimizer: str = "none"  # none | momentum | adam | yogi
    server_lr: float = 1.0
    server_momentum: float = 0.9
    server_beta2: float = 0.999
    server_eps: float = 1e-8
    # How client deltas combine. "mean" is the reference's (weighted) FedAvg;
    # "median" / "trimmed_mean" are coordinate-wise Byzantine-robust
    # aggregators (Yin et al. 2018); "krum" is selection-based (Blanchard et
    # al. 2017, f = floor(trim_fraction * n) assumed Byzantine, pairwise
    # distances as one MXU matmul). Robust aggregators ignore example-count
    # weights by construction and tolerate ~trim_fraction adversaries.
    aggregator: str = "mean"  # mean | median | trimmed_mean | krum
    trim_fraction: float = 0.1
    # HOW the round program runs its clients (fedtpu.core.round).
    #   "vmap" (default): all at once under jax.vmap — every client's model
    #     copy, gradient and activations live side by side, which is what
    #     fills a chip with 64-192 small models.
    #   "sequential": one after another in a lax.scan inside the same jitted
    #     round; the carry is the running weighted sum of the clients'
    #     changes, so the round holds ONE client's copies whatever the
    #     number of clients — a model of which a chip holds a single local
    #     copy. The rows are never stacked, so everything that needs all of
    #     them at once is refused at construction: aggregator != 'mean',
    #     update screening, DP clipping, delta compression, the flat delta
    #     layout, the adversarial harness, a mesh.
    client_schedule: str = "vmap"  # vmap | sequential
    # Differential privacy (DP-FedAvg, McMahan et al. 2018): clip each
    # client's delta to L2 norm dp_clip_norm (0 = off), then add Gaussian
    # noise with std = dp_clip_norm * dp_noise_multiplier / n_participants
    # to the aggregated delta. Requires uniform weighting (weighted=False)
    # and compression='none' — both enforced — so the per-client
    # sensitivity bound clip/n actually holds.
    dp_clip_norm: float = 0.0
    dp_noise_multiplier: float = 0.0
    # HOW the distributed server (fedtpu.transport.federation.PrimaryServer)
    # consumes StartTrain replies.
    #   "barrier": decode every reply into per-leaf host pytrees, stack
    #     leaf-by-leaf after the LAST reply, then transfer + aggregate in
    #     one jitted program (the original path; per-leaf parity reference).
    #   "stream": decode each reply directly into its row of one
    #     preallocated flat [clients, P] buffer and ship it to the device
    #     as it arrives (decode + H2D overlap the remaining clients'
    #     network wait); the post-barrier work is a single fused
    #     mean/unpack/server-opt finalize over the already-resident rows.
    #     Mean aggregation is bit-identical to "barrier" (the finalize runs
    #     the same order-stable stacked reduce — see
    #     docs/PERF_ANALYSIS.md). Requires aggregator='mean' and no DP
    #     clipping (validated in resolve_server_pipeline).
    #   "auto" (default): "stream" whenever the flat delta layout is on and
    #     the combination supports it, else "barrier".
    # Engine-side (simulated) federation ignores this knob: there is no
    # network edge to overlap.
    server_pipeline: str = "auto"  # auto | barrier | stream
    # How much the framework measures itself (fedtpu.obs; see
    # docs/OBSERVABILITY.md):
    #   "off":   no registry metrics, no spans. Round records keep their
    #     wire/phase fields (that accounting is part of the round API).
    #   "basic" (default): thread-safe counters/gauges/histograms (RPC
    #     bytes, compression ratio, phase times, retries, heartbeat misses,
    #     failover transitions, rounds completed), exportable as Prometheus
    #     text. Overhead <1% of round wall time (bench.py
    #     --telemetry-microbench, artifacts/TELEMETRY_MICROBENCH.json).
    #   "trace": basic plus the span tracer — nested round/client/phase
    #     spans exported as Chrome trace-event JSON (Perfetto-loadable) and
    #     bridged to jax.profiler.TraceAnnotation so XLA device activity
    #     nests under framework spans when a profiler session is active.
    telemetry: str = "basic"  # off | basic | trace
    # Transient-fault handling on the gRPC edge: retry/backoff + per-RPC
    # deadlines (see RetryPolicy). Defaults reproduce the old constants;
    # the engine (simulated) path has no RPC edge and ignores this.
    retry: RetryPolicy = dataclasses.field(default_factory=RetryPolicy)
    # Minimum fraction of this round's SAMPLED clients that must deliver
    # updates for the round to commit. Below quorum the round aborts
    # cleanly: the global model (and server-optimizer state) is left
    # bit-identical to its pre-round value — no partial average — the
    # clients are re-synced to that global, and the round re-runs.
    # 0.0 (default) = the old behavior: aggregate whatever arrived.
    round_quorum: float = 0.0
    # FT timing constants, previously hardcoded in the transport/ft stack
    # (docs/FAULT_TOLERANCE.md): the backup's promotion watchdog window,
    # the dead-client re-probe period, and the async reply-queue poll.
    ft_watchdog_timeout_s: float = 10.0
    ft_heartbeat_period_s: float = 1.0
    async_poll_s: float = 1.0
    # Massive-cohort simulation (population >> cohort decoupling): see
    # SimConfig / fedtpu.sim. num_clients doubles as the COHORT size when
    # sim.population > 0 — the engine's device buffers stay that size.
    sim: SimConfig = dataclasses.field(default_factory=SimConfig)
    # Fused update screening + reputation/quarantine (ScreenConfig;
    # docs/FAULT_TOLERANCE.md). Off by default (all thresholds disarmed) —
    # arming any statistic turns on per-round row rejection and, on the
    # distributed server, the suspicion EWMA -> quarantine -> evict
    # escalation. Unlike the robust aggregators this composes with
    # server_pipeline='stream' and with aggregator='mean'.
    screen: ScreenConfig = dataclasses.field(default_factory=ScreenConfig)
    # Hierarchical multi-tier aggregation (docs/ARCHITECTURE.md
    # §Multi-tier): 0 (default) = flat one-tier federation. N >= 1 turns
    # the distributed server into a two-tier ROOT whose roster entries are
    # leaf AggregatorServer addresses, each fronting a cohort of up to N
    # clients: the root pulls ONE pre-weighted partial sum per aggregator
    # per round (SubmitPartial), so its per-round decode+combine work is
    # O(aggregators), not O(clients). Root world = capacity * tier_fanout;
    # aggregator seat j owns data-partition ranks [j*N, (j+1)*N). Requires
    # the streaming pipeline with aggregator='mean', no DP and no
    # screening (validate_tier_config) — partial sums destroy the
    # per-client rows those need. Exactness: the root divides the summed
    # partials ONCE, so the 2-tier result is bit-identical to the flat
    # weighted mean (tests/test_aggregator.py parity pins).
    tier_fanout: int = 0


def validate_tier_config(fed: FedConfig, face: str) -> None:
    """Raise on FedConfig combinations hierarchical aggregation cannot
    honour, naming the requesting ``face`` (root or leaf — BOTH tiers run
    this, so a misconfigured topology fails at construction on every
    process rather than silently changing semantics mid-federation).

    A partial SUM destroys per-client structure: anything that needs
    individual client rows at the combine — robust aggregators, DP
    clipping, Byzantine screening — is incompatible with tiering.
    """
    if fed.tier_fanout < 0:
        raise ValueError(
            f"tier_fanout must be >= 0, got {fed.tier_fanout}"
        )
    if fed.aggregator != "mean":
        raise ValueError(
            f"hierarchical aggregation ({face}) requires aggregator='mean': "
            f"{fed.aggregator!r} needs every client row at the combine, "
            "but tiers forward only pre-weighted sums"
        )
    if fed.dp_clip_norm > 0:
        raise ValueError(
            f"hierarchical aggregation ({face}) cannot compose with DP "
            "clipping: per-client sensitivity bounds need individual rows "
            "at the root"
        )
    if screening_enabled(fed.screen):
        raise ValueError(
            f"hierarchical aggregation ({face}) cannot compose with update "
            "screening: screening statistics need individual client rows "
            "(screen at a future leaf tier instead)"
        )
    if resolve_server_pipeline(fed) != "stream":
        raise ValueError(
            f"hierarchical aggregation ({face}) requires the streaming "
            "pipeline: partial sums arrive as flat rows and fold through "
            "the [rows, P] stream buffer (server_pipeline='barrier' has "
            "no flat layout to decode them into)"
        )


def resolve_server_pipeline(fed: FedConfig) -> str:
    """Resolve ``FedConfig.server_pipeline`` to ``"barrier"`` or
    ``"stream"``, naming WHY a combination cannot stream.

    The streaming collect path folds rows into the aggregate as they
    arrive, so it only supports combines that are per-coordinate sums:
    the (weighted) mean. Robust aggregators and DP clipping need every
    client's full row on device at once — they stay on the stacked
    barrier path.
    """
    if fed.server_pipeline not in ("auto", "barrier", "stream"):
        raise ValueError(
            f"unknown server_pipeline {fed.server_pipeline!r}; "
            "have auto | barrier | stream"
        )
    streamable = fed.aggregator == "mean" and fed.dp_clip_norm == 0
    if fed.server_pipeline == "stream":
        if fed.aggregator != "mean":
            raise ValueError(
                f"server_pipeline='stream' cannot compose with "
                f"aggregator={fed.aggregator!r}: median/trimmed_mean/krum "
                "are not per-coordinate sums, so they need every client "
                "row at once — use server_pipeline='barrier' (the stacked "
                "[clients, ...] path)."
            )
        if fed.dp_clip_norm > 0:
            raise ValueError(
                "server_pipeline='stream' cannot compose with DP clipping: "
                "DP-FedAvg clips each client's full delta before the "
                "combine, so rows cannot fold into a running aggregate — "
                "use server_pipeline='barrier'."
            )
        return "stream"
    if fed.server_pipeline == "barrier":
        return "barrier"
    # auto: stream is the default for the flat delta layout (the perf
    # config the layout exists for); per_leaf keeps the parity path.
    return "stream" if (fed.delta_layout == "flat" and streamable) else "barrier"


@dataclasses.dataclass(frozen=True)
class RoundConfig:
    """Everything a single jitted round step needs, bundled + hashable."""

    model: str = "MobileNet"  # reference default: src/main.py:69
    num_classes: int = 10
    image_size: Tuple[int, int, int] = (32, 32, 3)
    opt: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    fed: FedConfig = dataclasses.field(default_factory=FedConfig)
    # Steps of local SGD per round per client; with static shapes this is the
    # padded maximum — shorter shards are masked (see fedtpu.core.client).
    steps_per_round: int = 8
    # The dtype the local step COMPUTES in. "float32": full-precision parity
    # (the seed default). "bfloat16": master-copy mixed precision —
    # FederatedState.params stays f32 and the local step casts params and
    # inputs to bf16 at use, so the whole forward/backward runs in bf16
    # while gradients, the [clients, P] flat aggregation surface, FedOpt
    # moments, screening statistics and checkpoints keep f32 semantics
    # (test-pinned); the engine's device-resident images are stored bf16
    # too (Federation._store_dtype). Every benchmark cell runs "bfloat16"
    # (benchmark/sut.py), so the ledger's numbers are this path's. What the
    # momentum buffers are STORED in is OptimizerConfig.momentum_dtype.
    dtype: str = "float32"  # float32 | bfloat16
    # The model's sizes where its constructor takes any: keyword arguments
    # of the registered constructor (fedtpu.models), as a mapping or a
    # sequence of (name, value) pairs — a JSON object or a literal dict;
    # kept as a sorted tuple of pairs so that the config stays hashable.
    # () (default): the constructor's own defaults, as every CNN takes them.
    model_args: Tuple[Tuple[str, Any], ...] = ()
    mesh_axis: str = "clients"
    # Per-block rematerialisation for models that support it (resnet*):
    # trades recompute FLOPs for HBM so big vmapped-client configs fit one
    # chip (measured: BASELINE.md config 4 OOMs one v5e without it).
    remat: bool = False
    # Per-batch console feedback from INSIDE the jitted local epoch
    # (jax.debug.print) — the reference prints loss/acc per batch mid-epoch
    # (src/utils.py:51-92, called at src/main.py:124,158). Off by default:
    # each print is a host callback that serialises the device against the
    # host, so this is a debugging aid, never a benchmarking mode.
    debug_per_batch: bool = False

    def __post_init__(self):
        def frozen(v):
            return tuple(frozen(x) for x in v) if isinstance(v, (list, tuple)) else v

        args = self.model_args
        pairs = args.items() if isinstance(args, dict) else args
        object.__setattr__(
            self, "model_args",
            tuple(sorted((str(k), frozen(v)) for k, v in pairs)),
        )


DEFAULT_ROUND_CONFIG = RoundConfig()
