"""High-level federated training engine.

The user-facing replacement for the reference's ``run()`` orchestration
(``src/server.py:113-153``): builds model + data + round step from a
:class:`fedtpu.config.RoundConfig`, then drives rounds. Each round is one
jitted call. The dataset and client-assignment matrix live in HBM
(:mod:`fedtpu.data.device`): per-round batch gathering happens inside the
jitted program, so the host contributes only the tiny ``alive`` mask per
round — no per-round host data rebuild, no bulk H2D transfer.
"""

from __future__ import annotations

import time
from typing import TYPE_CHECKING, Callable, Optional, Tuple

if TYPE_CHECKING:
    from fedtpu.ops.compression import Compressor

import jax
import jax.numpy as jnp
import numpy as np

from fedtpu import models as model_zoo
from fedtpu.config import RoundConfig
from fedtpu.core.round import (
    FederatedState,
    RoundBatch,
    RoundMetrics,
    init_state,
    make_round_step,
)
from fedtpu.core.client import make_eval_fn
from fedtpu.data import (
    data_source,
    dataset_info,
    is_token_dataset,
    load,
    partition,
)
from fedtpu.obs import StatusBoard, Telemetry
from fedtpu.obs.telemetry import setup_snapshot
from fedtpu.utils.metrics import MetricsLogger

# NOTE: fedtpu.data.device imports from fedtpu.core.round, whose package
# __init__ imports this module — so every data.device import below is
# deferred to call time to keep the package import-order insensitive.


class Federation:
    """Synchronous federated training over simulated clients on one program.

    Capabilities map (reference → here):
      - client registry + ranks (``src/server.py:281-282,126-129``) →
        the ``clients`` array axis; ``alive`` mask ↔ heartbeat status.
      - StartTrain fan-out / join barrier (``src/server.py:124-135``) →
        ``vmap`` inside one jitted round step.
      - ``allreduce()`` checkpoint averaging (``src/server.py:155-179``) →
        on-device masked weighted mean.
    """

    def __init__(
        self,
        cfg: RoundConfig,
        seed: int = 0,
        compressor: Optional["Compressor"] = None,
        data: Optional[Tuple[np.ndarray, np.ndarray]] = None,
        mesh=None,
        assignment: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    ):
        """``mesh``: an optional ``jax.sharding.Mesh`` over a ``clients``
        axis — rounds then run under ``shard_map`` with per-client state and
        data sharded across its devices and FedAvg as a psum over ICI
        (:mod:`fedtpu.parallel`). ``None`` keeps the single-program path
        (one chip, or tests).

        ``assignment``: an externally-built ``(idx, mask)`` client→example
        map (``[num_clients, shard_len]``, the :mod:`fedtpu.data.partition`
        convention) used instead of partitioning internally — the hook the
        massive-cohort simulation layer (:mod:`fedtpu.sim`) uses to hand the
        engine a cohort's rows gathered from a much larger population."""
        self.cfg = cfg
        self.mesh = mesh
        # Host-side telemetry (fedtpu.obs), built FIRST so that set-up runs
        # under its spans too: spans wrap what the HOST does (fed.setup.*
        # here and in the first dispatch, fed.plan / fed.enqueue in a
        # round; device compute is async) and reach a --profile-rounds
        # capture in every mode but "off", on the device operations' own
        # clock; counters track rounds completed. Swappable
        # post-construction — the jitted programs never close over it
        # (bench.py --telemetry-microbench retimes one engine under all
        # three modes).
        self.telemetry = Telemetry(cfg.fed.telemetry, role="engine")
        with self.telemetry.phase("fed.setup.build"):
            self._build(cfg, seed, compressor, data, mesh, assignment)

    def _build(self, cfg, seed, compressor, data, mesh, assignment):
        tel = self.telemetry
        # Config validation FIRST — a bad flag must not cost a model build,
        # a dataset load, jit construction, or even backend initialisation
        # (enable_compile_cache below) before raising.
        if cfg.fed.participation_sampling not in ("uniform", "loss"):
            raise ValueError(
                f"unknown participation_sampling "
                f"{cfg.fed.participation_sampling!r}; have uniform | loss"
            )
        if cfg.data.device_layout not in ("presharded", "gather"):
            raise ValueError(
                f"unknown device_layout {cfg.data.device_layout!r}; "
                "have presharded | gather"
            )
        if cfg.opt.momentum_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown momentum_dtype {cfg.opt.momentum_dtype!r}; "
                "have float32 | bfloat16"
            )
        if cfg.fed.delta_layout not in ("per_leaf", "flat"):
            raise ValueError(
                f"unknown delta_layout {cfg.fed.delta_layout!r}; "
                "have per_leaf | flat"
            )
        if not 0.0 <= cfg.fed.sim.malicious_fraction < 1.0:
            raise ValueError(
                f"sim.malicious_fraction must be in [0, 1), got "
                f"{cfg.fed.sim.malicious_fraction}"
            )
        if cfg.dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown dtype {cfg.dtype!r}; have float32 | bfloat16"
            )
        shape, n_classes = dataset_info(cfg.data.dataset)
        # Token data: num_classes is the model's vocabulary (the rows it
        # holds), and a caller's corpus draws its ids from it.
        self._tokens = is_token_dataset(cfg.data.dataset)
        if cfg.num_classes != n_classes and not self._tokens:
            raise ValueError(
                f"cfg.num_classes={cfg.num_classes} but dataset "
                f"'{cfg.data.dataset}' has {n_classes} classes — set "
                f"RoundConfig(num_classes={n_classes})"
            )
        # Persistent XLA compile cache, placed before anything compiles
        # (jax decides once, at its first compile, whether the cache is in
        # use). AFTER the cheap validation above: it initialises the JAX
        # backend, which an invalid config must never pay for.
        from fedtpu.utils.platform import enable_compile_cache

        enable_compile_cache()
        if cfg.fed.compression != "none" and compressor is None:
            from fedtpu.ops.compression import make_compressor

            compressor = make_compressor(cfg.fed)
        # local_epochs folds into the per-round step count: one epoch is
        # steps_per_round passes over the shard (make_client_batches wraps
        # short shards), matching the reference's epochs-per-StartTrain knob.
        self._steps = cfg.steps_per_round * max(1, cfg.fed.local_epochs)
        self.model = model_zoo.create(
            cfg.model, num_classes=cfg.num_classes, remat=cfg.remat,
            **dict(cfg.model_args),
        )

        if data is None:
            images, labels = load(
                cfg.data.dataset,
                "train",
                seed=cfg.data.seed,
                num=cfg.data.num_examples,
            )
            # Captured immediately after OUR load so an unrelated later load
            # of the same dataset name can't relabel this run.
            self._data_source = data_source(cfg.data.dataset, "train")
        else:
            images, labels = data
            self._data_source = "caller"
        self.images, self.labels = images, labels

        n = cfg.fed.num_clients
        with tel.phase("fed.setup.build.partition"):
            idx, mask = self._assign(images, labels, assignment)
            self.client_idx, self.client_mask = idx, mask
            self.weights = jnp.asarray(partition.shard_sizes(mask))

        # Seeded adversarial participants (fedtpu.sim.adversary; the
        # SimConfig.malicious_fraction axis). On the resident engine the
        # seat IS the client, so the attacker mask is static; SimFederation
        # re-derives the per-seat mask from each round's cohort ids.
        # label_flip is a DATA attack: the attackers' example labels are
        # poisoned here on the host and the jitted program is unchanged.
        self._attack_plan = None
        self._attack_seats = None
        if cfg.fed.sim.malicious_fraction > 0:
            from fedtpu.sim import adversary

            plan = adversary.parse_attack(cfg.fed.sim.attack)
            self._attack_plan = plan
            if mesh is not None:
                raise NotImplementedError(
                    "sim.malicious_fraction does not compose with a mesh "
                    "yet (the attack mask is not threaded through "
                    "shard_map); run the adversarial scenario single-chip"
                )
            if cfg.fed.sim.population <= 0:
                amask = adversary.attacker_mask(
                    n, cfg.fed.sim.malicious_fraction,
                    cfg.data.seed + cfg.fed.sim.seed + plan.seed,
                )
                self.attacker_clients = amask
                if plan.kind == "label_flip":
                    # Static data poisoning: p/rounds windows do not apply
                    # (the shard is poisoned for the whole run).
                    labels = adversary.flip_labels(
                        labels, idx, mask, amask, plan.label_offset,
                        cfg.num_classes,
                    )
                    self.labels = labels
                else:
                    self._attack_seats = amask.astype(np.float32)

        sample = jnp.zeros(
            (1,) + tuple(images.shape[1:]),
            jnp.int32 if self._tokens else jnp.float32,
        )
        with tel.phase("fed.setup.build.init_state", compiles=True):
            state = init_state(
                self.model, cfg, jax.random.PRNGKey(seed), sample, compressor
            )
        self.state: FederatedState = state  # placed by the setter
        shuffle = cfg.data.partition != "round_robin"
        img_shape = tuple(images.shape[1:])
        layout = cfg.data.device_layout
        if self._tokens:
            # Rows are sequences of ids with a target a position; the
            # presharded rows are float pixels with one label a row.
            layout = "gather"
        if layout == "presharded":
            # Footprint guard: presharded costs clients * 2L floats of
            # labels-side rows where L is the padded MAX shard length, so a
            # skewed partition (low-alpha dirichlet) can inflate far beyond
            # the 2x-dataset cost of the balanced case. Fall back to the
            # gather layout (correct for every shape, just slower on TPU)
            # rather than OOM.
            footprint = 2 * n * idx.shape[1]
            if footprint > 4 * len(images):
                import warnings

                warnings.warn(
                    f"device_layout='presharded' would store "
                    f"{footprint / len(images):.1f}x the dataset (skewed "
                    f"partition: max shard {idx.shape[1]} of {len(images)} "
                    f"examples x {n} clients); falling back to 'gather'",
                    stacklevel=2,
                )
                layout = "gather"
        self._layout = layout
        with tel.phase("fed.setup.build.programs"):
            if mesh is None:
                from fedtpu.data.device import make_data_round_step

                self._round_step = jax.jit(
                    make_round_step(self.model, cfg, compressor), donate_argnums=(0,)
                )
                self._data_step = jax.jit(
                    make_data_round_step(
                        self.model, cfg, self._steps, compressor, shuffle=shuffle,
                        image_shape=img_shape, layout=layout,
                    ),
                    donate_argnums=(0,),
                )
            else:
                from fedtpu.data.device import make_sharded_data_round_step
                from fedtpu.parallel.sharded import make_sharded_round_step

                self._round_step = make_sharded_round_step(
                    self.model, cfg, mesh, compressor
                )
                self._data_step = make_sharded_data_round_step(
                    self.model, cfg, self._steps, mesh, compressor, shuffle=shuffle,
                    image_shape=img_shape, layout=layout,
                )
                # self.state was already mesh-placed by the property setter.
                self.weights = self._placed(self.weights, sharded=True)
        # Device-resident data (uploaded lazily on the first device-path
        # step, so explicit-batch callers never pay the HBM footprint):
        # dataset + assignment matrix go to HBM once; each round gathers its
        # batches inside the jitted step.
        self._device_data = None
        self._data_key = jax.random.PRNGKey(cfg.data.seed)
        self._evaluate = make_eval_fn(self.model.apply, cfg)
        self.alive = np.ones((n,), bool)
        self._compressor = compressor
        self._shuffle = shuffle
        self._img_shape = img_shape
        self._multi_steps = {}  # num_rounds -> compiled scan program
        # Compiled programs called at least once ("round", "data", a fused
        # block's num_rounds): the first call of each runs under
        # fed.setup.first_dispatch.
        self._called = set()
        # Live status feed (fedtpu.obs.http: /statusz via --obs-port):
        # round/phase updates are one locked dict merge each — cheap enough
        # to run unconditionally (bench.py --obs-plane-microbench).
        self.status = StatusBoard(
            role="engine", phase="init", round=0,
            num_clients=cfg.fed.num_clients,
        )
        # Continuous MFU/roofline accounting (fedtpu.obs.profile): OPT-IN
        # via enable_mfu_accounting() — building the cost model traces and
        # AOT-compiles the round program once (seconds), which library
        # users constructing many engines must not pay implicitly. The
        # per-round observe is a few gauge sets (bench.py --mfu-microbench
        # gates it ≤1% of a round).
        self.profiler = None
        # Optional process-wide CompileWatcher, attached by the owning CLI
        # (jax.monitoring listeners are global, so the process owns it, not
        # the engine) — surfaced on /statusz when present.
        self.compile_watcher = None

    def _assign(self, images, labels, assignment):
        """The client→example ``(idx, mask)``: the caller's, or the
        configured partition of the loaded examples."""
        cfg, n = self.cfg, self.cfg.fed.num_clients
        if assignment is not None:
            idx, mask = np.asarray(assignment[0]), np.asarray(assignment[1])
            if idx.shape[0] != n or idx.shape != mask.shape:
                raise ValueError(
                    f"assignment must be [num_clients={n}, shard_len] "
                    f"idx/mask pairs, got {idx.shape} vs {mask.shape}"
                )
            return idx, mask
        if cfg.data.partition == "round_robin":
            return partition.round_robin(len(images), n, cfg.data.batch_size)
        if cfg.data.partition == "iid":
            return partition.iid(len(images), n, seed=cfg.data.seed)
        if cfg.data.partition == "dirichlet":
            return partition.dirichlet(
                labels, n, alpha=cfg.data.dirichlet_alpha, seed=cfg.data.seed
            )
        raise ValueError(f"unknown partition {cfg.data.partition}")

    def enable_mfu_accounting(self, xla_check: bool = True):
        """Arm per-round MFU/roofline gauges + round-record stamping.

        Builds the per-round cost model now (analytic jaxpr FLOP walk,
        cross-checked against XLA ``cost_analysis`` when ``xla_check``) —
        a one-time trace/compile cost, so this is explicit rather than a
        construction default. Returns the :class:`RoundProfiler`."""
        from fedtpu.obs.profile import RoundProfiler, engine_cost_model

        if self.profiler is None:
            if self.mesh is not None:
                n_dev = len(self.mesh.devices.flatten())
                kind = self.mesh.devices.flatten()[0].device_kind
            else:
                n_dev = 1
                kind = jax.devices()[0].device_kind
            self.profiler = RoundProfiler(
                self.telemetry, n_devices=n_dev, device_kind=kind,
            )
            self.profiler.set_cost_model(
                engine_cost_model(self, xla_check=xla_check)
            )
        return self.profiler

    def status_snapshot(self) -> dict:
        """``/statusz`` feed: live round/phase plus the alive mask (and the
        perf/compile observability blocks when armed)."""
        snap = self.status.snapshot()
        snap["alive"] = self.alive.tolist()
        if self.telemetry.enabled:
            snap["setup"] = setup_snapshot()
        if self.telemetry.tracer is not None:
            snap["trace_id"] = self.telemetry.tracer.trace_id
        if self.profiler is not None:
            snap["perf"] = self.profiler.snapshot()
        if self.compile_watcher is not None:
            snap["compile"] = self.compile_watcher.snapshot()
        return snap

    def _placed(self, x, sharded: bool):
        """Place an array for the active topology: sharded along the clients
        axis (or replicated) on the mesh, or a plain device_put without one."""
        if self.mesh is None:
            return jax.device_put(jnp.asarray(x))
        from fedtpu.parallel.sharded import _put
        from jax.sharding import PartitionSpec as P

        return _put(x, self.mesh, P(self.cfg.mesh_axis) if sharded else P())

    def _store_dtype(self):
        """HBM storage dtype for the device-resident images: the COMPUTE
        dtype. Every consumer (the client local step) casts inputs to the
        compute dtype as its first act, so storing bf16 under a bf16 config
        is bit-identical end-to-end while halving the dataset's HBM
        footprint and every per-round slice/gather's bandwidth."""
        import ml_dtypes

        dt = jnp.dtype(self.cfg.dtype)
        return np.dtype(ml_dtypes.bfloat16) if dt == jnp.bfloat16 else np.float32

    def _ensure_device_data(self):
        if self._device_data is None:
            with self.telemetry.phase("fed.setup.first_dispatch.device_data"):
                self._device_data = self._upload_device_data()
        return self._device_data

    def _upload_device_data(self):
        """The dataset and the assignment matrix, made on the host
        (``.host``) and put on the device or the mesh (``.h2d``: the puts
        as the host sees them; the transfers themselves are asynchronous)."""
        tel = self.telemetry
        store = self._store_dtype()
        with tel.phase("fed.setup.first_dispatch.device_data.host"):
            if self._layout == "presharded":
                # Per-client contiguous rows ([n, 2L, F], see
                # fedtpu.data.device.preshard_arrays) — sharded by CLIENT on
                # a mesh, so each device stores only its own clients' data.
                from fedtpu.data.device import preshard_arrays

                xs, ys = preshard_arrays(
                    self.images, self.labels, self.client_idx,
                    self.client_mask,
                )
                xs = xs.astype(store)
            else:
                # Gather layout: dataset replicated (every device gathers
                # its own clients' batches locally); assignment matrix
                # sharded by client. Images live FLAT ([N, H*W*C]): NHWC
                # tensors pad ~4x under TPU tiled layouts, flat rows tile
                # exactly — the per-batch reshape after the gather is free.
                if self._tokens:
                    xs = np.asarray(self.images, np.int32)  # ids, not pixels
                else:
                    xs = np.asarray(self.images, np.float32).reshape(
                        len(self.images), -1
                    ).astype(store)
                ys = np.asarray(self.labels, np.int32)
        by_client = self._layout == "presharded"
        replicas = 1 if by_client or self.mesh is None else self.mesh.size
        tel.setup_gauge(
            "fedtpu_setup_device_data_bytes",
            "bytes of dataset and assignment the engine put on the devices "
            "(a replicated array counts once a device)",
        ).inc(
            (xs.nbytes + ys.nbytes) * replicas
            + self.client_idx.nbytes + self.client_mask.nbytes
        )
        with tel.phase("fed.setup.first_dispatch.device_data.h2d"):
            return (
                self._placed(xs, sharded=by_client),
                self._placed(ys, sharded=by_client),
                self._placed(self.client_idx, sharded=True),
                self._placed(self.client_mask, sharded=True),
            )

    # ---------------------------------------------------------------- data
    def set_assignment(
        self,
        idx: np.ndarray,
        mask: np.ndarray,
        weights: Optional[np.ndarray] = None,
    ) -> None:
        """Swap the client→example assignment in place (same shapes).

        The sim layer's per-round cohort re-gather: the jitted data-round
        program takes ``idx``/``mask`` as *inputs* of static shape, so
        replacing their VALUES (a cohort-sized H2D of int32 rows) swaps
        which population clients the fixed device slots represent without
        recompiling. Gather layout only — the presharded layout bakes the
        assignment into per-client data rows at upload, which would cost an
        O(cohort·shard·features) re-preshard per round.
        """
        if self._layout != "gather":
            raise ValueError(
                "set_assignment requires device_layout='gather' (presharded "
                "bakes the assignment into the uploaded data rows)"
            )
        idx = np.asarray(idx, np.int32)
        mask = np.asarray(mask, bool)
        if idx.shape != self.client_idx.shape or mask.shape != idx.shape:
            raise ValueError(
                f"assignment shape {idx.shape} must match the engine's "
                f"{self.client_idx.shape} (static program shapes)"
            )
        self.client_idx, self.client_mask = idx, mask
        w = partition.shard_sizes(mask) if weights is None else weights
        self.weights = self._placed(np.asarray(w, np.float32),
                                    sharded=self.mesh is not None)
        if self._device_data is not None:
            d_images, d_labels, _, _ = self._device_data
            self._device_data = (
                d_images,
                d_labels,
                self._placed(idx, sharded=True),
                self._placed(mask, sharded=True),
            )

    def _alive_for_round(self, round_idx: int) -> np.ndarray:
        """This round's participation mask: heartbeat-dead clients plus
        optional subsampling of the live ones (the reference always uses
        every live client). With ``participation_sampling='loss'`` the
        subset is drawn with probability proportional to each client's last
        observed training loss (importance sampling — worst-served clients
        get picked more often); uniform until a loss has been observed, and
        a fused block reuses the losses known before the block started."""
        alive = self.alive.copy()
        frac = self.cfg.fed.participation_fraction
        if frac < 1.0:
            rng = np.random.default_rng(self.cfg.data.seed * 7919 + round_idx)
            live = np.flatnonzero(alive)
            k = max(1, int(round(frac * len(live))))
            p = None
            if self.cfg.fed.participation_sampling == "loss":
                # Observations live in FederatedState (updated per round on
                # device, NaN until first observed, checkpointed); fetched
                # only here, when a sampling decision actually needs them.
                # Multi-controller: the loss vector is SHARDED by client
                # across processes, so every controller allgathers the full
                # vector first — identical inputs + the round-seeded
                # deterministic draw below then yield the SAME mask on every
                # host (the desync hazard that previously made this
                # single-controller only). Tested by a real two-process run
                # (tests/test_multihost.py).
                loss_vec = self._state.last_client_loss
                if not getattr(loss_vec, "is_fully_addressable", True):
                    # Mesh spanning processes: allgather yields the global
                    # [N] vector on every host. Gate on addressability, NOT
                    # process_count: a host-local vector under an initialized
                    # cluster (mesh=None — independent federations per host)
                    # is already complete, and tiled concatenation would
                    # silently hand every host process 0's copy.
                    from jax.experimental import multihost_utils

                    loss_vec = multihost_utils.process_allgather(
                        loss_vec, tiled=True
                    )
                # Shared sparse-observation rule (fedtpu.sim.sampling):
                # never-observed clients draw at the optimistic fill (max
                # observed loss) so they are explored, not starved; None
                # (nothing observed yet) falls back to uniform. The sim
                # layer's population-scale cohort sampler routes through
                # the SAME function, so both surfaces weigh sparse
                # last-seen losses identically.
                from fedtpu.sim.sampling import loss_weights

                p = loss_weights(np.asarray(loss_vec)[live])
            keep = rng.choice(live, size=k, replace=False, p=p)
            alive = np.zeros_like(alive)
            alive[keep] = True
        return alive

    def round_batch(self, round_idx: int) -> RoundBatch:
        """Materialise this round's batch tensors on the HOST.

        Kept for tests and for callers that inject custom batches; the hot
        path (:meth:`step` with ``batch=None``) gathers on device instead and
        never calls this.
        """
        cfg = self.cfg
        x, y, step_mask = partition.make_client_batches(
            self.images,
            self.labels,
            self.client_idx,
            self.client_mask,
            cfg.data.batch_size,
            self._steps,
            seed=cfg.data.seed + round_idx,
            shuffle=cfg.data.partition != "round_robin",
        )
        return RoundBatch(
            x=jnp.asarray(x),
            y=jnp.asarray(y),
            step_mask=jnp.asarray(step_mask),
            weights=self.weights,
            alive=jnp.asarray(self._alive_for_round(round_idx)),
            attack_seats=(
                jnp.asarray(self._attack_seats)
                if self._attack_seats is not None else ()
            ),
        )

    @property
    def data_source(self) -> str:
        """'disk' | 'synthetic' | 'caller' — where this instance's training
        data came from (captured at construction)."""
        return self._data_source

    # --------------------------------------------------------------- rounds
    @property
    def state(self) -> FederatedState:
        return self._state

    @state.setter
    def state(self, s: FederatedState) -> None:
        # External assignment (e.g. checkpoint resume) invalidates the
        # host-side round counter; it re-syncs from the device on next use.
        # On a mesh, host/numpy trees (a restored checkpoint) are placed with
        # the engine's shardings so resume Just Works; trees that already
        # hold non-addressable global arrays (multi-controller stepping
        # output) are left untouched.
        # Without a mesh, host leaves (seeded or restored weights as NumPy
        # arrays) are put on the device here, where a span says so, and not
        # inside the next dispatch's argument handling.
        with self.telemetry.phase("fed.setup.place_state"):
            leaves = jax.tree_util.tree_leaves(s)
            if self.mesh is None:
                moved = [l for l in leaves if not isinstance(l, jax.Array)]
                if moved:
                    s = jax.device_put(s)
                self._count_placed(moved, 1)
            elif not any(
                isinstance(l, jax.Array) and not l.is_fully_addressable
                for l in leaves
            ):
                from fedtpu.parallel.sharded import shard_state

                s = shard_state(s, self.mesh, self.cfg.mesh_axis)
                placed = jax.tree_util.tree_leaves(s)
                moved = [
                    after for before, after in zip(leaves, placed)
                    if not (isinstance(before, jax.Array)
                            and before.sharding == after.sharding)
                ]
                self._count_placed(moved, self.mesh.size)
        self._state = s
        self._round_host = None

    def _count_placed(self, moved, n_devices: int) -> None:
        """Count the state leaves a placement moved, and their bytes times
        the devices they went to (a replicated leaf goes to every one)."""
        if not moved or not self.telemetry.enabled:
            return
        tel = self.telemetry
        tel.setup_gauge(
            "fedtpu_setup_state_leaves",
            "state leaves the engine placed on the devices during set-up",
        ).inc(len(moved))
        tel.setup_gauge(
            "fedtpu_setup_state_bytes",
            "bytes of those leaves times the devices each went to",
        ).inc(sum(
            getattr(l, "nbytes", 0) * (
                n_devices if n_devices > 1
                and l.sharding.is_fully_replicated else 1)
            for l in moved
        ))

    def _round_number(self) -> int:
        """Host-tracked current round. Avoids a blocking device readback of
        ``state.round_idx`` every round (which would serialise dispatch
        against the previous round's compute)."""
        if self._round_host is None:
            self._round_host = int(self._state.round_idx)
        return self._round_host

    def step(self, batch: Optional[RoundBatch] = None) -> RoundMetrics:
        tel = self.telemetry
        r = self._round_number()
        self.status.update(round=r, phase="round")
        t0 = time.perf_counter()
        with tel.span("fed.round", step_num=r):
            program = "data" if batch is None else "round"
            if program in self._called:
                metrics = self._step_impl(batch)
            else:
                metrics = self._first_dispatch(
                    program, self._step_impl, batch)
        self._observe(t0, metrics)
        self.status.update(round=r + 1, phase="idle")
        tel.counter(
            "fedtpu_rounds_completed_total",
            "simulated FedAvg rounds dispatched by this engine",
        ).inc()
        return metrics

    def _first_dispatch(self, program, dispatch, *args) -> RoundMetrics:
        """The first call of a compiled program (``program`` names it in
        ``_called``): the same dispatch under ``fed.setup.first_dispatch``,
        which also hears the trace, lowering, compile or cache load that
        the call sets off, and holds the dataset's upload if this is the
        first call to need it."""
        self._called.add(program)
        with self.telemetry.phase("fed.setup.first_dispatch", compiles=True):
            return dispatch(*args)

    def _observe(self, t0: float, metrics: RoundMetrics, rounds: int = 1):
        """Feed the armed profiler the wall of a dispatch that has FINISHED.
        Dispatch is asynchronous: a wall that ends at the enqueue is
        microseconds, and the first CLI run on a v5e reported an MFU of
        6.27 from it. The accounting is opt-in, and its callers (the CLI
        loops, ``run()``) fetch the metrics right after anyway."""
        if self.profiler is not None:
            jax.block_until_ready(metrics)
            self.profiler.observe_round(
                time.perf_counter() - t0, rounds=rounds
            )

    def _step_impl(self, batch: Optional[RoundBatch] = None) -> RoundMetrics:
        tel = self.telemetry
        r = self._round_number()
        if batch is not None:
            if self.mesh is not None:
                from fedtpu.parallel.sharded import shard_batch

                with tel.span("fed.plan"):
                    batch = shard_batch(batch, self.mesh, self.cfg.mesh_axis)
            with tel.span("fed.enqueue"):
                self._state, metrics = self._round_step(self._state, batch)
            self._round_host = r + 1
            return metrics
        with tel.span("fed.plan"):
            d_images, d_labels, d_idx, d_mask = self._ensure_device_data()
            extra = (
                (jnp.asarray(self._attack_seats),)
                if self._attack_seats is not None else ()
            )
            alive = self._placed(self._alive_for_round(r), sharded=True)
        with tel.span("fed.enqueue"):
            self._state, metrics = self._data_step(
                self._state,
                d_images,
                d_labels,
                d_idx,
                d_mask,
                self.weights,
                alive,
                self._data_key,
                *extra,
            )
        self._round_host = r + 1
        return metrics

    def _multi_step(self, num_rounds: int):
        """Build (and cache) the ``num_rounds``-round fused scan program."""
        if num_rounds not in self._multi_steps:
            if self.mesh is None:
                from fedtpu.data.device import make_multi_round_step

                self._multi_steps[num_rounds] = jax.jit(
                    make_multi_round_step(
                        self.model, self.cfg, self._steps, num_rounds,
                        self._compressor, shuffle=self._shuffle,
                        image_shape=self._img_shape, layout=self._layout,
                    ),
                    donate_argnums=(0,),
                )
            else:
                from fedtpu.data.device import make_sharded_multi_round_step

                self._multi_steps[num_rounds] = make_sharded_multi_round_step(
                    self.model, self.cfg, self._steps, num_rounds, self.mesh,
                    self._compressor, shuffle=self._shuffle,
                    image_shape=self._img_shape, layout=self._layout,
                )
        return self._multi_steps[num_rounds]

    def run_on_device(self, num_rounds: int) -> RoundMetrics:
        """Run ``num_rounds`` rounds as ONE fused XLA program (``lax.scan``).

        Numerically identical to ``num_rounds`` calls of :meth:`step` (the
        per-round shuffle key folds ``round_idx``, and per-round alive masks
        — heartbeat state + participation sampling — are precomputed on the
        host and scanned over), but with zero host involvement between
        rounds: no dispatch, no sync, no data movement. This is the
        framework's answer to the reference's per-round host round-trip
        (``src/server.py:120-153``) taken to its limit.
        Returns metrics stacked ``[num_rounds, ...]``.
        """
        if num_rounds < 1:
            raise ValueError(f"num_rounds must be >= 1, got {num_rounds}")
        tel = self.telemetry
        r = self._round_number()
        self.status.update(round=r, phase="fused_rounds",
                           fused_block=num_rounds)
        t0 = time.perf_counter()
        with tel.span("fed.fused_rounds", round=r, num_rounds=num_rounds):
            if num_rounds in self._called:
                metrics = self._fused_impl(r, num_rounds)
            else:
                metrics = self._first_dispatch(
                    num_rounds, self._fused_impl, r, num_rounds)
        self._observe(t0, metrics, rounds=num_rounds)
        self._round_host = r + num_rounds
        self.status.update(round=r + num_rounds, phase="idle")
        tel.counter(
            "fedtpu_rounds_completed_total",
            "simulated FedAvg rounds dispatched by this engine",
        ).inc(num_rounds)
        return metrics

    def _fused_impl(self, r: int, num_rounds: int) -> RoundMetrics:
        tel = self.telemetry
        with tel.span("fed.plan"):
            alive = np.stack(
                [self._alive_for_round(r + i) for i in range(num_rounds)]
            )
            d_images, d_labels, d_idx, d_mask = self._ensure_device_data()
            if self.mesh is None:
                alive_dev = jnp.asarray(alive)
            else:
                from fedtpu.parallel.sharded import _put
                from jax.sharding import PartitionSpec as P

                alive_dev = _put(
                    alive, self.mesh, P(None, self.cfg.mesh_axis)
                )
            extra = (
                (jnp.asarray(self._attack_seats),)
                if self._attack_seats is not None else ()
            )
            multi_step = self._multi_step(num_rounds)
        with tel.span("fed.enqueue"):
            self._state, metrics = multi_step(
                self._state,
                d_images,
                d_labels,
                d_idx,
                d_mask,
                self.weights,
                alive_dev,
                self._data_key,
                *extra,
            )
        return metrics

    def run(
        self,
        num_rounds: Optional[int] = None,
        logger: Optional[MetricsLogger] = None,
        eval_every: int = 0,
        eval_data: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    ) -> RoundMetrics:
        if num_rounds is None:
            num_rounds = self.cfg.fed.num_rounds
        from fedtpu.config import screening_enabled

        metrics = None
        self.eval_history = []
        screen_on = screening_enabled(self.cfg.fed.screen)
        for r in range(num_rounds):
            t0 = time.time()
            ridx = self._round_number()
            metrics = self.step()
            rec = {
                "loss": metrics.loss,
                "acc": metrics.accuracy,
                "active": metrics.num_active,
                # Worst live client this round — a diverging/poisoned client
                # shows up here rounds before it drags the mean.
                "worst_client_loss": float(
                    jnp.max(metrics.per_client_loss)
                ),
                "round_s": time.time() - t0,
                "dataset": self.cfg.data.dataset,
                # 'synthetic' when the loader fell back — accuracy curves from
                # such runs must never be read as real-data results. Captured
                # at construction from THIS instance's load (or 'caller' for
                # injected data), immune to later unrelated loads.
                "data_source": self._data_source,
            }
            self.telemetry.histogram(
                "fedtpu_round_wall_seconds",
                "per-round host wall time (dispatch + sync)",
            ).observe(rec["round_s"])
            if self.profiler is not None:
                # step() already observed this round into the gauges; the
                # record stamps the SAME last-round figures (MFU absent on
                # a backend with no peaks, i.e. the CPU).
                rec.update(self.profiler.record_fields())
            if screen_on:
                # The run() loop already syncs per round (worst_client_loss
                # above), so reading the verdict mask costs nothing extra.
                n_screened = int(np.sum(np.asarray(metrics.screened)))
                rec["screened"] = n_screened
                if n_screened:
                    self.telemetry.counter(
                        "fedtpu_screening_rejected_total",
                        "client rows rejected by the fused screening "
                        "stage, by surface",
                        labels={"surface": "engine"},
                    ).inc(n_screened)
            if self._attack_plan is not None:
                from fedtpu.sim import adversary

                if self._attack_seats is not None:
                    fired = adversary.fires_this_round(
                        self._attack_plan, self._attack_seats, ridx
                    )
                    n_fired = int(fired.sum())
                else:  # label_flip: statically poisoned shards train every round
                    n_fired = int(
                        getattr(self, "attacker_clients",
                                np.zeros(0, bool)).sum()
                    )
                rec["attackers_fired"] = n_fired
                if n_fired:
                    self.telemetry.counter(
                        "fedtpu_attack_injected_total",
                        "model/data-level attacks executed by seeded "
                        "adversarial clients, by kind",
                        labels={"kind": self._attack_plan.kind},
                    ).inc(n_fired)
            if eval_every and (r + 1) % eval_every == 0 and eval_data is not None:
                te_loss, te_acc = self.evaluate(*eval_data)
                rec["test_loss"], rec["test_acc"] = te_loss, te_acc
                self.eval_history.append((r, te_loss, te_acc))
            if logger is not None:
                logger.log(r, **rec)
        return metrics

    # ----------------------------------------------------------------- eval
    def evaluate(self, images: np.ndarray, labels: np.ndarray):
        """Evaluate the current global model (parity: ``src/main.py:167-191``)."""
        from fedtpu.core.client import batch_eval_arrays

        xs, ys = batch_eval_arrays(images, labels, self.cfg.data.eval_batch_size)
        loss, acc = self._evaluate(self.state.params, self.state.batch_stats, xs, ys)
        return float(loss), float(acc)

    # ------------------------------------------------------- fault injection
    def set_alive(self, client: int, alive: bool) -> None:
        """Mark a simulated client dead/alive (the reference flips
        ``clients[addr]`` on RpcError / heartbeat success,
        ``src/server.py:59-62,95-99``)."""
        self.alive[client] = alive
