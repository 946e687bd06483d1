"""Shared CLI flag surface.

The reference splits flags across three argparse parsers with cross-process
coupling (``src/server.py:270-274``, ``src/client.py:56-59``,
``src/main.py:20-26`` — the trainer's parser runs inside the client process
because of import-time side effects). fedtpu keeps the reference's flag
*names* where they exist (``-c/--compressFlag``, ``-a/--address``,
``-r/--resume``, ``--lr``, ``--p``) and adds explicit flags for everything
the reference hardcodes (model, dataset, rounds, client registry).
"""

from __future__ import annotations

import argparse
import json

from fedtpu.config import (
    DataConfig,
    FedConfig,
    OptimizerConfig,
    RetryPolicy,
    RoundConfig,
    ScreenConfig,
    SimConfig,
)
from fedtpu.data import dataset_info


def add_platform_flag(p: argparse.ArgumentParser) -> None:
    p.add_argument(
        "--platform",
        default=None,
        choices=["cpu", "tpu", "cuda"],
        help="pin the jax platform (jax.config.update, which wins over "
        "JAX_PLATFORMS in the environment). Default: whatever backend jax "
        "initialises — the first log line names it. 'tpu' is fatal when "
        "no chip can be acquired; a chip belongs to ONE process, so on a "
        "one-chip host every other role runs with 'cpu'",
    )
    p.add_argument(
        "--fake-devices",
        default=None,
        type=int,
        metavar="N",
        help="with --platform cpu: present N virtual CPU devices "
        "(the standard mesh-testing trick, SURVEY.md §4)",
    )


def apply_platform_flag(args) -> None:
    """Apply --platform/--fake-devices, then initialise the backend: place
    the compile cache (before anything compiles) and log which device this
    process actually got. Must run before any other jax device query; safe
    because fedtpu modules import jax lazily enough that the backend is
    uninitialised until here. Raises when the pinned platform is absent."""
    import jax

    from fedtpu.utils.platform import (
        enable_compile_cache,
        force_host_device_count,
        log_devices,
    )

    if getattr(args, "fake_devices", None):
        force_host_device_count(args.fake_devices)
    if getattr(args, "platform", None):
        jax.config.update("jax_platforms", args.platform)
    enable_compile_cache()
    log_devices()


def add_model_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument(
        "--model",
        default="MobileNet",
        help="architecture (reference hardcodes MobileNet, src/main.py:69)",
    )
    p.add_argument(
        "--dataset",
        default="cifar10",
        choices=["cifar10", "cifar100", "mnist", "synthetic", "tokens"],
        help="'tokens': rows of token ids for a language model "
        "(docs/OPERATIONS.md, language-model federations)",
    )
    p.add_argument(
        "--model-args", default="{}", type=json.loads, metavar="JSON",
        help="the model constructor's sizes as a JSON object "
        "(RoundConfig.model_args), e.g. for joyai_llm_flash "
        '\'{"num_hidden_layers": 3, "experts_held": [0, 8]}\'',
    )
    p.add_argument("--lr", default=0.1, type=float, help="learning rate")
    p.add_argument(
        "--momentum", default=0.9, type=float,
        help="local SGD momentum (reference: 0.9). 0 with "
        "--client-schedule sequential keeps no buffers",
    )
    p.add_argument(
        "--schedule",
        default="constant",
        choices=["constant", "cosine"],
        help="LR schedule. 'constant' matches the reference's effective "
        "behavior (its cosine scheduler is constructed but never stepped, "
        "src/main.py:231-242); 'cosine' is the schedule it intended",
    )
    p.add_argument("--batch-size", default=128, type=int)
    p.add_argument(
        "--momentum-dtype", default="float32",
        choices=["float32", "bfloat16"],
        help="HBM dtype of the per-client momentum buffers. bfloat16 is a "
        "flagged NON-PARITY mode that halves optimizer-state bandwidth "
        "(update math stays f32; see OptimizerConfig.momentum_dtype)",
    )
    p.add_argument(
        "--eval-batch-size", default=100, type=int,
        help="test-set batch size (reference: src/main.py:56). Must not "
        "exceed the eval set size — lower it for small/truncated datasets",
    )
    p.add_argument("--seed", default=0, type=int)
    p.add_argument(
        "--num-examples",
        default=None,
        type=int,
        help="truncate the dataset (for smoke runs)",
    )
    p.add_argument(
        "-c",
        "--compressFlag",
        default="N",
        help="Y enables update compression (reference: transport gzip; here "
        "additionally top-k delta compression on the TPU path)",
    )


def parse_compression(spec: str):
    """Parse a ``--compression`` spec into ``(codec, rotq_bits | None)``.

    Accepts a bare codec name or the parameterized ``rotq:bits=B`` form
    (argparse ``type=`` hook, so a bad spec fails at parse time with a
    usage error instead of deep inside config validation)."""
    codec, _, rest = spec.partition(":")
    bits = None
    if rest:
        if codec != "rotq" or not rest.startswith("bits="):
            raise argparse.ArgumentTypeError(
                f"bad compression spec {spec!r}: only rotq takes a "
                "parameter, as rotq:bits=B"
            )
        try:
            bits = int(rest[len("bits="):])
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"bad compression spec {spec!r}: bits must be an integer"
            )
        if bits not in (1, 2, 4, 8):
            raise argparse.ArgumentTypeError(
                f"rotq bits must be 1, 2, 4 or 8, got {bits}"
            )
    if codec not in ("none", "topk", "int8", "rotq", "randk"):
        raise argparse.ArgumentTypeError(
            f"unknown codec {codec!r}; have none | topk | int8 | "
            "rotq[:bits=B] | randk"
        )
    return codec, bits


def add_compression_flags(p: argparse.ArgumentParser) -> None:
    """Delta-codec flags, shared by the simulated engine CLI, the gRPC
    server AND the gRPC client (the client encodes its own wire payloads,
    so it needs the codec + layout choice too)."""
    p.add_argument(
        "--compression",
        default=None,
        type=parse_compression,
        help="delta codec: none | topk | int8 | rotq[:bits=B] | randk "
        "(rotq/randk are the seeded flat sketch codecs, "
        "docs/FLAT_DELTA.md §Codec matrix; B in {1,2,4,8}, default 4; "
        "randk reuses --topk-fraction as its keep fraction); "
        "default: topk when -c Y, none otherwise",
    )
    p.add_argument("--topk-fraction", default=0.01, type=float)
    p.add_argument(
        "--codec-policy",
        default="static",
        choices=["static", "adaptive"],
        help="codec selection on the gRPC edge: static = every client uses "
        "--compression every round; adaptive = the coordinator picks a "
        "codec per client per round from observed bytes x RTT "
        "(docs/OPERATIONS.md §Adaptive codec; requires --delta-layout "
        "flat)",
    )
    p.add_argument(
        "--delta-layout",
        default="per_leaf",
        choices=["per_leaf", "flat"],
        help="how client deltas travel through compression/aggregation and "
        "the wire: per_leaf = one codec/reduce dispatch (and one wire "
        "record) per pytree leaf (parity default); flat = pack all leaves "
        "into one lane-aligned [clients, P] buffer per round "
        "(fedtpu.ops.flat) — one top_k / quantize / reduce for the whole "
        "model, ONE contiguous wire record, global top-k budget "
        "(see docs/FLAT_DELTA.md)",
    )


def add_fed_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--rounds", default=20, type=int,
                   help="federated rounds (reference hardcodes 20)")
    p.add_argument("--algorithm", default="fedavg", choices=["fedavg", "fedprox"])
    p.add_argument(
        "--client-schedule", default="vmap", choices=["vmap", "sequential"],
        help="how the round program runs its clients: all at once under "
        "vmap, or one after another with a running weighted sum "
        "(FedConfig.client_schedule) — a model of which the chip holds one "
        "local copy; mean aggregation only",
    )
    p.add_argument("--fedprox-mu", default=0.01, type=float)
    p.add_argument(
        "--partition",
        default="round_robin",
        choices=["round_robin", "iid", "dirichlet"],
    )
    p.add_argument("--dirichlet-alpha", default=0.5, type=float)
    add_compression_flags(p)
    p.add_argument(
        "--server-pipeline",
        default="auto",
        choices=["auto", "barrier", "stream"],
        help="how the distributed server consumes StartTrain replies: "
        "barrier = decode into per-leaf host pytrees and stack/transfer/"
        "aggregate after the LAST reply (parity path); stream = decode "
        "each reply into its row of one flat [clients, P] buffer and ship "
        "it to the device as it arrives, leaving a single fused finalize "
        "post-barrier (mean aggregation bit-identical to barrier; "
        "requires --aggregator mean, no DP). auto = stream for "
        "--delta-layout flat when the combination supports it "
        "(see docs/PERF_ANALYSIS.md). Ignored by the simulated engine",
    )
    p.add_argument(
        "--tier-fanout",
        default=0,
        type=int,
        metavar="N",
        help="hierarchical multi-tier aggregation "
        "(docs/ARCHITECTURE.md §Multi-tier): 0 = flat one-tier federation "
        "(default). N >= 1 makes the primary the ROOT of a two-tier "
        "topology whose --clients entries are sub-aggregator addresses "
        "(fedtpu.cli.server --role aggregator), each fronting a cohort of "
        "up to N clients; the root pulls ONE pre-weighted partial sum per "
        "aggregator per round, so its decode+combine work scales with "
        "aggregators, not clients. Requires --delta-layout flat with "
        "--aggregator mean, no DP and no screening; both tiers must agree "
        "on the value",
    )
    p.add_argument(
        "--aggregator",
        default="mean",
        choices=["mean", "median", "trimmed_mean", "krum"],
        help="delta combine rule: mean = (weighted) FedAvg (reference "
        "semantics); median / trimmed_mean = coordinate-wise "
        "Byzantine-robust aggregation; krum = selection-based "
        "(Blanchard et al. 2017)",
    )
    p.add_argument("--trim-fraction", default=0.1, type=float)
    p.add_argument(
        "--server-optimizer",
        default="none",
        choices=["none", "momentum", "adam", "yogi"],
        help="server-side optimizer over the aggregated delta (FedOpt "
        "family): none = FedAvg (reference semantics), momentum = FedAvgM, "
        "adam = FedAdam, yogi = FedYogi",
    )
    p.add_argument("--server-lr", default=1.0, type=float)
    p.add_argument(
        "--unweighted",
        action="store_true",
        help="uniform averaging over active clients instead of "
        "example-count weighting (required for DP)",
    )
    p.add_argument(
        "--dp-clip-norm",
        default=0.0,
        type=float,
        help="DP-FedAvg: clip each client delta to this L2 norm (0 = off; "
        "requires --unweighted, no compression, and a BatchNorm-free model)",
    )
    p.add_argument("--dp-noise-multiplier", default=0.0, type=float)
    p.add_argument(
        "--participation-fraction",
        default=1.0,
        type=float,
        help="random fraction of live clients sampled each round "
        "(1.0 = all, reference behavior)",
    )
    p.add_argument(
        "--participation-sampling",
        default="uniform",
        choices=["uniform", "loss"],
        help="how the sampled subset is drawn: uniform, or importance "
        "sampling proportional to each client's last training loss",
    )
    p.add_argument(
        "--telemetry",
        default="basic",
        choices=["off", "basic", "trace"],
        help="self-measurement level (fedtpu.obs; docs/OBSERVABILITY.md): "
        "off = nothing; basic (default) = thread-safe metrics registry "
        "(RPC bytes, compression ratio, phase times, FT transitions; "
        "dump with --prom-out), <1%% round overhead; trace = basic plus "
        "nested round/client/phase spans exported as Perfetto-loadable "
        "Chrome trace JSON (--trace-out) and bridged to "
        "jax.profiler.TraceAnnotation under --profile-dir",
    )
    add_screening_flags(p)
    p.add_argument(
        "--compute-dtype",
        dest="dtype",
        default="float32",
        choices=["float32", "bfloat16"],
        help="dtype the local step computes in (RoundConfig.dtype): "
        "float32 = full-precision parity (default); bfloat16 = bf16 "
        "params/activations/dataset on device over an f32 master copy — "
        "aggregation, FedOpt, screening and checkpoints keep f32 semantics",
    )
    p.add_argument(
        "--debug-per-batch",
        action="store_true",
        help="print per-batch loss/acc from inside the jitted local epoch "
        "(the reference's mid-epoch console lines, src/utils.py:51-92). "
        "Host callback per batch — debugging only, ruins throughput",
    )


def add_screening_flags(p: argparse.ArgumentParser) -> None:
    """Fused update screening + reputation/quarantine (ScreenConfig;
    docs/FAULT_TOLERANCE.md). All checks default OFF; arming any one turns
    screening on. Composes with --server-pipeline stream and every
    aggregator (unlike median/krum, which are barrier-only)."""
    p.add_argument(
        "--screen-norm",
        default=0.0,
        type=float,
        metavar="L2",
        help="reject client updates whose L2 norm exceeds this absolute "
        "bound (0 = off) — the blunt defense against boosted updates",
    )
    p.add_argument(
        "--screen-z",
        default=0.0,
        type=float,
        metavar="Z",
        help="reject updates whose norm's modified z-score (median/MAD of "
        "the live cohort — robust to the attackers inflating the spread) "
        "exceeds this bound (0 = off; ~3.5 is the textbook outlier cut)",
    )
    p.add_argument(
        "--screen-cos",
        default=-1.0,
        type=float,
        metavar="COS",
        help="reject updates whose cosine against the live cohort's "
        "coordinate-wise median direction falls below this (-1 = off; "
        "0 rejects sign-flipped/contrarian updates)",
    )
    p.add_argument(
        "--quarantine-at",
        default=ScreenConfig.quarantine_at,
        type=float,
        metavar="S",
        help="suspicion EWMA threshold (of per-round screening verdicts) "
        "at which a client is quarantined: still served, updates ignored, "
        "release when suspicion decays below the release threshold",
    )
    p.add_argument(
        "--quarantine-evict-after",
        default=ScreenConfig.evict_after,
        type=int,
        metavar="ROUNDS",
        help="consecutive quarantined rounds before the client is evicted "
        "through the live membership machinery (0 = never auto-evict)",
    )


def screen_config(args) -> ScreenConfig:
    """ScreenConfig from the screening flags (defaults = screening off)."""
    return ScreenConfig(
        norm_max=getattr(args, "screen_norm", 0.0),
        zmax=getattr(args, "screen_z", 0.0),
        cos_min=getattr(args, "screen_cos", -1.0),
        quarantine_at=getattr(
            args, "quarantine_at", ScreenConfig.quarantine_at
        ),
        evict_after=getattr(
            args, "quarantine_evict_after", ScreenConfig.evict_after
        ),
    )


def add_sim_flags(p: argparse.ArgumentParser) -> None:
    """Massive-cohort simulation surface (fedtpu.sim; docs/SIMULATION.md).
    Engine CLI only — the population/cohort split is a property of the
    simulated path (the gRPC topology's population is its real clients)."""
    p.add_argument(
        "--population",
        default=0,
        type=int,
        metavar="N",
        help="simulate N clients total while the device holds only "
        "--cohort of them per round (fedtpu.sim.SimFederation): per-client "
        "dataset assignment + last-seen loss + availability live as host "
        "tables, each round's cohort is gathered into the engine's "
        "fixed-size buffers — device memory O(cohort), not O(population). "
        "0 (default) = resident engine (every client a live device slot)",
    )
    p.add_argument(
        "--cohort",
        default=0,
        type=int,
        metavar="K",
        help="clients per round when --population is set (the engine's "
        "device-buffer size; overrides --num-clients). population == "
        "cohort with uniform sampling reproduces the resident engine "
        "bit-for-bit (test-pinned)",
    )
    p.add_argument(
        "--scenario",
        default="",
        metavar="SPEC",
        help="population heterogeneity scenario (fedtpu.sim.scenario): "
        "base[:k=v,...][+quantity_skew:power=P] with bases iid | "
        "dirichlet:alpha=A | pathological:shards=S | label_skew:classes=C "
        "| quantity_skew:power=P | round_robin. Empty = use --partition "
        "unchanged. Example: 'dirichlet:alpha=0.1+quantity_skew:power=1.5'",
    )
    p.add_argument(
        "--cohort-sampler",
        default="uniform",
        choices=["uniform", "loss"],
        help="how each round's cohort is drawn from the available "
        "population: uniform without replacement, or loss = proportional "
        "to last-seen training loss (never-sampled clients draw at an "
        "optimistic prior, so exploration never starves)",
    )
    p.add_argument(
        "--availability",
        default=1.0,
        type=float,
        metavar="FRACTION",
        help="stationary fraction of the population that is online "
        "(seeded two-state Markov trace; 1.0 = everyone always up)",
    )
    p.add_argument(
        "--churn",
        default=0.0,
        type=float,
        metavar="P",
        help="per-round P(online -> offline) of the availability trace "
        "(P(offline -> online) is derived to keep --availability "
        "stationary); 0 = a frozen availability draw",
    )
    p.add_argument(
        "--loss-prior",
        default=-1.0,
        type=float,
        metavar="LOSS",
        help="optimistic sampling prior for never-sampled clients under "
        "--cohort-sampler loss; negative (default) = the max observed loss",
    )
    p.add_argument(
        "--malicious-fraction",
        default=0.0,
        type=float,
        metavar="FRACTION",
        help="seed this fraction of the simulated population (or of "
        "--num-clients on the resident engine) as Byzantine clients "
        "executing --attack (fedtpu.sim.adversary); attacker identity and "
        "every per-round decision replay bit-identically from the seed",
    )
    p.add_argument(
        "--attack",
        default="sign_flip",
        metavar="SPEC",
        help="what seeded attackers do: kind[:key=val,...] with kinds "
        "sign_flip | scale:factor=F | noise:std=S | label_flip:offset=K "
        "and shared options p= (fire probability), rounds=lo-hi, "
        "collude=1 (one shared draw/noise vector for the whole malicious "
        "set), seed=",
    )


def sim_config(args) -> SimConfig:
    """SimConfig from the sim flags (defaults when a CLI doesn't expose
    them — server/train CLIs build sim-off configs)."""
    return SimConfig(
        population=getattr(args, "population", 0),
        cohort_sampler=getattr(args, "cohort_sampler", "uniform"),
        scenario=getattr(args, "scenario", ""),
        loss_prior=getattr(args, "loss_prior", -1.0),
        availability=getattr(args, "availability", 1.0),
        churn=getattr(args, "churn", 0.0),
        seed=getattr(args, "sim_seed", 0),
        malicious_fraction=getattr(args, "malicious_fraction", 0.0),
        attack=getattr(args, "attack", "sign_flip"),
    )


def add_robustness_flags(p: argparse.ArgumentParser) -> None:
    """Transient-fault resilience + chaos surface (docs/FAULT_TOLERANCE.md),
    shared by all four CLIs. The retry/quorum flags configure the typed
    ``RetryPolicy`` / ``round_quorum`` in FedConfig; ``--chaos-spec`` arms
    the deterministic fault-injection schedule (fedtpu.ft.chaos)."""
    p.add_argument(
        "--chaos-spec",
        default=None,
        metavar="SPEC",
        help="arm deterministic fault injection: JSON "
        '({"seed":7,"rules":[{"kind":"error","rpc":"StartTrain","p":0.3}]}) '
        "or mini-DSL 'kind@rpc:p=0.3,seed=7' with rules joined by ';'. "
        "Kinds: delay|drop|error|corrupt|kill; options p, peer, delay "
        "(seconds), code, rounds=lo-hi, max, seed. Applied via gRPC "
        "interceptors on the server/client CLIs; the RPC-less run/train "
        "CLIs honor delay/kill rules on the pseudo-RPC 'Round'. Every "
        "injection is counted (fedtpu_chaos_injected_total) and flight-"
        "recorded; same spec + seed = same faults (tools/chaos_soak.py)",
    )
    p.add_argument(
        "--rpc-retries",
        default=RetryPolicy.max_attempts,
        type=int,
        metavar="N",
        help="total attempts per RPC before the failure is treated as "
        "real (mark_failed); 1 = the old single-shot behavior. Transient "
        "status codes (UNAVAILABLE, DEADLINE_EXCEEDED, ...) and corrupt "
        "payloads (wire CRC) retry; fatal codes never do",
    )
    p.add_argument(
        "--rpc-backoff",
        default=RetryPolicy.backoff_s,
        type=float,
        metavar="SECONDS",
        help="initial retry backoff; doubles per attempt (jittered, "
        f"capped at {RetryPolicy.backoff_max_s:.1f}s)",
    )
    p.add_argument(
        "--rpc-timeout",
        default=None,
        type=float,
        metavar="SECONDS",
        help="deadline for the data-plane RPCs (StartTrain / SendModel / "
        "FetchModel). Default: the RetryPolicy per-RPC deadlines (600s, "
        "the old hardcoded constant)",
    )
    p.add_argument(
        "--round-quorum",
        default=0.0,
        type=float,
        metavar="FRACTION",
        help="minimum fraction of the round's sampled clients that must "
        "deliver updates for the round to commit; below it the round "
        "aborts with the global model untouched and re-runs. 0 (default) "
        "= aggregate whatever arrived (old behavior)",
    )
    p.add_argument(
        "--backup-ping-timeout",
        default=RetryPolicy.backup_ping_timeout_s,
        type=float,
        metavar="SECONDS",
        help="deadline of the primary's CheckIfPrimaryUp backup ping "
        "(was hardcoded 2.0s)",
    )
    p.add_argument(
        "--heartbeat-period",
        default=FedConfig.ft_heartbeat_period_s,
        type=float,
        metavar="SECONDS",
        help="dead-client re-probe period of the heartbeat monitor "
        "(was hardcoded 1.0s)",
    )
    p.add_argument(
        "--async-poll",
        default=FedConfig.async_poll_s,
        type=float,
        metavar="SECONDS",
        help="reply-queue poll timeout of the async (FedBuff) server loop "
        "(was hardcoded 1.0s)",
    )


def robustness_config(args) -> dict:
    """FedConfig kwargs from the robustness flags (defaults when a CLI
    doesn't expose them)."""
    rpc_timeout = getattr(args, "rpc_timeout", None)
    base = RetryPolicy()
    retry = RetryPolicy(
        max_attempts=getattr(args, "rpc_retries", base.max_attempts),
        backoff_s=getattr(args, "rpc_backoff", base.backoff_s),
        start_train_timeout_s=(
            rpc_timeout if rpc_timeout is not None
            else base.start_train_timeout_s
        ),
        send_model_timeout_s=(
            rpc_timeout if rpc_timeout is not None
            else base.send_model_timeout_s
        ),
        fetch_model_timeout_s=(
            rpc_timeout if rpc_timeout is not None
            else base.fetch_model_timeout_s
        ),
        backup_ping_timeout_s=getattr(
            args, "backup_ping_timeout", base.backup_ping_timeout_s
        ),
    )
    return {
        "retry": retry,
        "round_quorum": getattr(args, "round_quorum", 0.0),
        "ft_watchdog_timeout_s": (
            getattr(args, "watchdog_timeout", None)
            or FedConfig.ft_watchdog_timeout_s
        ),
        "ft_heartbeat_period_s": getattr(
            args, "heartbeat_period", FedConfig.ft_heartbeat_period_s
        ),
        "async_poll_s": getattr(args, "async_poll", FedConfig.async_poll_s),
    }


def add_checkpoint_hardening_flags(p: argparse.ArgumentParser) -> None:
    """Durability knobs shared by the CLIs that own a --checkpoint-dir
    (docs/OPERATIONS.md §Disaster recovery)."""
    p.add_argument(
        "--checkpoint-keep",
        default=3,
        type=int,
        metavar="N",
        help="checkpoint generations retained on disk (pruned only after "
        "the newest verifies). Resume requires >= 2: restore-time "
        "generation fallback needs a previous snapshot to fall back to "
        "when the newest is torn or bit-rotten. <= 0 keeps everything",
    )
    p.add_argument(
        "--checkpoint-sync",
        action="store_true",
        help="write checkpoints synchronously on the round loop instead "
        "of the default background writer thread (the loop then blocks "
        "for encode + fsync + verify each save; the writer path blocks "
        "only for the device->host snapshot — bench.py "
        "--checkpoint-overhead-microbench)",
    )


def make_checkpointer(args, telemetry=None, flight=None, chaos=None):
    """Honor --checkpoint-dir: a hardened Checkpointer (fsync'd atomic
    writes, digest manifests, verify-on-read generation fallback,
    non-fatal saves), wrapped in the BackgroundCheckpointer writer thread
    unless --checkpoint-sync. None when the flag is absent. The caller
    owns ``close()`` (drains the writer so the final generation is durable
    before exit). ``chaos`` arms the seeded ckpt_fail/ckpt_torn/ckpt_rot
    disk faults of --chaos-spec against this store."""
    directory = getattr(args, "checkpoint_dir", None)
    if not directory:
        return None
    from fedtpu.checkpoint import BackgroundCheckpointer, Checkpointer

    inner = Checkpointer(
        directory,
        keep=getattr(args, "checkpoint_keep", 3),
        backend="wire",
        metrics=(
            telemetry.registry
            if telemetry is not None and telemetry.enabled else None
        ),
        flight=flight,
        chaos=chaos,
    )
    if getattr(args, "checkpoint_sync", False):
        return inner
    return BackgroundCheckpointer(inner, telemetry=telemetry)


def make_chaos(args, role: str = ""):
    """Honor --chaos-spec: parse + arm a FaultSchedule (None when absent).
    The armed rules are logged so a soak's transcript names its faults."""
    import logging

    spec = getattr(args, "chaos_spec", None)
    if not spec:
        return None
    from fedtpu.ft import parse_chaos_spec

    chaos = parse_chaos_spec(spec)
    logging.warning(
        "CHAOS ARMED%s: %s", f" ({role})" if role else "", chaos.describe()
    )
    return chaos


def add_telemetry_export_flags(p: argparse.ArgumentParser) -> None:
    """End-of-run exporter paths, shared by the run and server CLIs (the
    per-round JSONL exporter is the existing ``--metrics`` flag)."""
    p.add_argument(
        "--prom-out",
        default=None,
        metavar="PATH",
        help="write the cumulative metrics registry as a Prometheus "
        "text-format dump at exit (the file-shaped /metrics endpoint; "
        "requires --telemetry basic or trace)",
    )
    p.add_argument(
        "--trace-out",
        default=None,
        metavar="PATH",
        help="write the collected spans as Chrome trace-event JSON at exit "
        "(load in Perfetto / chrome://tracing; requires --telemetry trace)",
    )


def export_telemetry(args, telemetry) -> None:
    """Honor --prom-out/--trace-out against a component's Telemetry."""
    import logging

    if getattr(args, "prom_out", None):
        if telemetry.enabled:
            telemetry.export_prometheus(args.prom_out)
        else:
            logging.warning(
                "--prom-out ignored: --telemetry off collects no metrics"
            )
    if getattr(args, "trace_out", None):
        if telemetry.tracing:
            telemetry.export_trace(args.trace_out)
        else:
            logging.warning(
                "--trace-out ignored: spans need --telemetry trace"
            )


def install_final_flush(args, telemetry, metrics=None):
    """Crash-proof the exit-time exporters: --prom-out/--trace-out (and the
    --metrics JSONL close) used to run only on a clean fall-through to the
    CLI's ``finally`` — a SIGTERM (scheduler preemption, ``timeout``,
    ``kill``) bypassed them and lost the whole registry/trace. Registers
    ONE idempotent flush on ``atexit`` + SIGTERM (the handler re-raises
    ``SystemExit`` so the normal ``finally`` path still unwinds), and
    returns it so the CLI's own ``finally`` calls the same function —
    whoever fires first wins, everyone else no-ops.

    Per-record durability needs no handler at all: ``RoundRecordWriter``
    appends + flushes every line, so even SIGKILL keeps all completed
    round records (tested: tests/test_obs_propagation.py kills a run
    mid-flight and parses complete v1 records).
    """
    import atexit
    import logging
    import signal
    import threading

    done = threading.Event()

    def flush() -> None:
        if done.is_set():
            return
        done.set()
        try:
            if metrics is not None:
                metrics.close()
        except Exception:
            logging.exception("final metrics close failed")
        try:
            export_telemetry(args, telemetry)
        except Exception:
            logging.exception("final telemetry export failed")

    atexit.register(flush)

    def _on_term(signum, frame):
        flush()
        raise SystemExit(128 + signum)

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:
        pass  # not the main thread (library/test use); atexit still covers
    return flush


def add_obs_flags(p: argparse.ArgumentParser) -> None:
    """The live introspection plane (fedtpu.obs.http; docs/OBSERVABILITY.md)."""
    p.add_argument(
        "--obs-port",
        default=None,
        type=int,
        metavar="PORT",
        help="serve live introspection HTTP on 127.0.0.1:PORT: /metrics "
        "(Prometheus text from the cumulative registry), /healthz, "
        "/statusz (JSON: round, phase, client liveness, failover role, "
        "last-round phase timings — render live with tools/statusz.py), "
        "/flightz (the crash flight recorder's ring buffer). Off by "
        "default; 0 binds an ephemeral port (logged)",
    )


def add_profile_flags(p: argparse.ArgumentParser) -> None:
    """The performance observatory's capture/accounting controls
    (fedtpu.obs.profile; docs/OBSERVABILITY.md 'Profiling')."""
    p.add_argument(
        "--profile-rounds",
        default=None,
        metavar="N[:M]",
        help="capture a jax.profiler device trace covering rounds [N, M) "
        "(half-open; bare N = that one round) into --profile-trace-dir. "
        "The capture writes a wall-clock sidecar so tools/trace_merge.py "
        "--device-trace aligns device ops with the host span timeline",
    )
    p.add_argument(
        "--profile-trace-dir",
        default="profile_trace",
        metavar="DIR",
        help="output directory for --profile-rounds captures",
    )
    p.add_argument(
        "--mfu",
        default="auto",
        choices=["auto", "off", "analytic", "xla"],
        help="per-round MFU/roofline accounting: fedtpu_mfu_ratio / "
        "achieved-FLOPs/s / step-time gauges + round-record stamps. "
        "'analytic' prices the program by walking its jaxpr (cheap); "
        "'xla' additionally cross-checks against the compiled "
        "executable's cost_analysis (one extra AOT compile at startup); "
        "'auto' = analytic when --telemetry is on, else off",
    )


def resolve_mfu_mode(args) -> str:
    """Collapse --mfu auto against --telemetry: the gauges land in the
    telemetry registry, so accounting without a registry is pure cost."""
    mode = getattr(args, "mfu", "off")
    if mode == "auto":
        return "analytic" if getattr(args, "telemetry", "off") != "off" else "off"
    return mode


def make_capture_window(args, role: str, telemetry=None):
    """Honor --profile-rounds: an armed CaptureWindow, or None. The caller
    drives it with maybe_start(round)/maybe_stop(round) and must stop() it
    at exit (idempotent) so a window open past the last round still closes."""
    spec = getattr(args, "profile_rounds", None)
    if spec is None:
        return None
    from fedtpu.obs.profile import CaptureWindow

    trace_id = None
    if telemetry is not None and telemetry.tracer is not None:
        trace_id = telemetry.tracer.trace_id
    return CaptureWindow(
        spec, getattr(args, "profile_trace_dir", "profile_trace"),
        role=role, trace_id=trace_id,
    )


def install_compile_watcher(telemetry=None, flight=None):
    """Arm the XLA compile observer for a CLI process. Best-effort: an
    already-active watcher (tests driving main() in-process) or a jax
    without the monitoring hook degrades to None, never to a crash."""
    from fedtpu.obs.profile import CompileWatcher

    try:
        return CompileWatcher(telemetry=telemetry, flight=flight).install()
    except Exception:
        import logging

        logging.debug("compile watcher unavailable", exc_info=True)
        return None


def start_obs_server(args, registry=None, status_fn=None, flight=None,
                     health_fn=None):
    """Honor --obs-port: start (and return) the endpoint, or None when the
    flag is absent. The caller owns stop(). ``health_fn`` (() -> (ok,
    reason)) makes /healthz honest — 503 while fenced or quorum is unmet."""
    import logging

    port = getattr(args, "obs_port", None)
    if port is None:
        return None
    from fedtpu.obs import ObsServer

    obs = ObsServer(
        port=port, registry=registry, status_fn=status_fn, flight=flight,
        health_fn=health_fn,
    ).start()
    logging.info(
        "obs endpoint on %s (/metrics /healthz /statusz /flightz)", obs.url
    )
    return obs


def make_flight_recorder(role: str, telemetry=None):
    """One process-wide flight recorder for a CLI entrypoint: ring buffer +
    dump hooks armed (unhandled exception, SIGUSR1), warning+ log capture,
    and — in trace mode — span completions via the tracer sink."""
    from fedtpu.obs import FlightRecorder

    flight = FlightRecorder(role=role).install()
    if telemetry is not None and telemetry.tracer is not None:
        telemetry.tracer.sink = flight.record_span
    return flight


def build_config(args, num_clients: int, steps_per_round: int = 8) -> RoundConfig:
    compress = str(getattr(args, "compressFlag", "N")).upper() == "Y"
    compression = getattr(args, "compression", None)
    rotq_bits = None
    if isinstance(compression, tuple):  # parse_compression (codec, bits)
        compression, rotq_bits = compression
    if compression is None:
        compression = "topk" if compress else "none"
    shape, n_classes = dataset_info(args.dataset)
    return RoundConfig(
        model=args.model,
        num_classes=n_classes,
        image_size=shape,
        opt=OptimizerConfig(
            learning_rate=args.lr,
            momentum=getattr(args, "momentum", 0.9),
            schedule=getattr(args, "schedule", "constant"),
            momentum_dtype=getattr(args, "momentum_dtype", "float32"),
        ),
        data=DataConfig(
            dataset=args.dataset,
            batch_size=args.batch_size,
            eval_batch_size=getattr(args, "eval_batch_size", 100),
            partition=getattr(args, "partition", "round_robin"),
            dirichlet_alpha=getattr(args, "dirichlet_alpha", 0.5),
            seed=args.seed,
            num_examples=args.num_examples,
        ),
        fed=FedConfig(
            num_clients=num_clients,
            num_rounds=getattr(args, "rounds", 20),
            algorithm=getattr(args, "algorithm", "fedavg"),
            client_schedule=getattr(args, "client_schedule", "vmap"),
            fedprox_mu=(
                getattr(args, "fedprox_mu", 0.0)
                if getattr(args, "algorithm", "fedavg") == "fedprox"
                else 0.0
            ),
            compression=compression,
            topk_fraction=getattr(args, "topk_fraction", 0.01),
            rotq_bits=rotq_bits if rotq_bits is not None else 4,
            codec_policy=getattr(args, "codec_policy", "static"),
            delta_layout=getattr(args, "delta_layout", "per_leaf"),
            server_pipeline=getattr(args, "server_pipeline", "auto"),
            aggregator=getattr(args, "aggregator", "mean"),
            trim_fraction=getattr(args, "trim_fraction", 0.1),
            server_optimizer=getattr(args, "server_optimizer", "none"),
            server_lr=getattr(args, "server_lr", 1.0),
            dp_clip_norm=getattr(args, "dp_clip_norm", 0.0),
            dp_noise_multiplier=getattr(args, "dp_noise_multiplier", 0.0),
            weighted=not getattr(args, "unweighted", False),
            participation_fraction=getattr(
                args, "participation_fraction", 1.0
            ),
            participation_sampling=getattr(
                args, "participation_sampling", "uniform"
            ),
            telemetry=getattr(args, "telemetry", "basic"),
            tier_fanout=getattr(args, "tier_fanout", 0),
            sim=sim_config(args),
            screen=screen_config(args),
            **robustness_config(args),
        ),
        steps_per_round=steps_per_round,
        dtype=getattr(args, "dtype", "float32"),
        model_args=getattr(args, "model_args", None) or (),
        debug_per_batch=getattr(args, "debug_per_batch", False),
    )


def compress_enabled(args) -> bool:
    return str(getattr(args, "compressFlag", "N")).upper() == "Y"
