"""Performance observatory: continuous MFU/roofline accounting + compile
observability + profiler capture windows.

The headline number rides on ~1.31% MFU (artifacts/MFU_PROFILE_r04*.json),
but until this module that figure was a one-off hand-run artifact. Here the
accounting becomes *continuous*:

- :func:`analytic_flops` — an analytic per-architecture FLOP model that
  walks the jaxpr counting matmul/conv MACs, cross-checked against XLA's
  ``jax.jit(...).lower(...).compile().cost_analysis()`` (the two agree to a
  few percent on every zoo model; the ratio is stamped on the cost model so
  drift between them is visible, not silent).
- :class:`RoundProfiler` — per-round ``fedtpu_step_time_seconds``,
  ``fedtpu_achieved_flops_per_sec`` and ``fedtpu_mfu_ratio`` gauges through
  the existing registry, plus a ``snapshot()`` dict for ``/statusz`` and
  round records. Per-round cost is a handful of gauge sets (microseconds;
  gated ≤1% of a round by ``bench.py --mfu-microbench``).
- :class:`CompileWatcher` — counts and times XLA backend compilations via
  ``jax.monitoring`` listeners, with a steady-state recompile detector
  that warns + flight-records (silent steady-state recompiles are the
  classic JAX perf killer: one drifting shape and every "fast" round pays
  a multi-second compile).
- :func:`capture_window` / :class:`CaptureWindow` — programmatic
  ``jax.profiler`` windows (the CLIs' ``--profile-rounds N:M``) that also
  write a ``profile_meta.json`` sidecar carrying the wall-clock start, so
  ``tools/trace_merge.py`` can align device ops onto the host-span
  timeline.

Shared scalar conventions: FLOPs/bytes are PER ROUND from the SINGLE-round
program. XLA's cost analysis counts a ``lax.scan`` body ONCE regardless of
trip count, so on a round of ``steps`` local steps it reads ``steps`` times
too low (smallcnn, 6 steps: 6.6 x, PERF.md). ``analytic_flops`` multiplies
every scan body by its length, and is what the MFU gauge is priced with;
the ``analytic_vs_xla`` stamp then reads about ``steps`` wherever XLA
counted once.
"""

from __future__ import annotations

import json
import logging
import math
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

log = logging.getLogger("fedtpu.obs.profile")

# ------------------------------------------------------------- peak tables
# Public per-chip peak figures by PJRT device_kind substring (matched on
# the lowercase space/hyphen-stripped form): (bf16 FLOPs/s, HBM bytes/s).
# The ONLY peak table — bench.py, chip_smoke.py and the tools resolve
# through device_peaks(). v5e row: Google Cloud documentation, "TPU v5e".
PEAK_TABLE: Tuple[Tuple[Tuple[str, ...], float, float], ...] = (
    (("v6e", "v6lite", "trillium"), 918e12, 1640e9),
    (("v5p",), 459e12, 2765e9),
    (("v5e", "v5lite"), 197e12, 819e9),
    (("v4",), 275e12, 1228e9),
    (("v3",), 123e12, 900e9),
    (("v2",), 45e12, 700e9),
)

# Stand-in peaks for a backend that has none (a CPU dev box exercising the
# MFU gauges in tests). Never consulted on a TPU backend.
PEAK_FLOPS_ENV = "FEDTPU_PEAK_FLOPS"
PEAK_HBM_ENV = "FEDTPU_PEAK_HBM_BYTES"


def device_peaks(device_kind: str) -> Tuple[Optional[float], Optional[float]]:
    """``(peak_flops_per_s, peak_hbm_bytes_per_s)`` for a PJRT device kind.

    A kind in :data:`PEAK_TABLE` gets the table's row, always. An unknown
    kind on a TPU backend RAISES — utilisation against a guessed peak, or
    MFU silently dropped, is how a run on the wrong device goes unnoticed;
    add the chip to the table. Off TPU an unknown kind (``"cpu"``) yields
    the ``FEDTPU_PEAK_*`` stand-ins, else ``(None, None)``."""
    kind = (device_kind or "").lower().replace(" ", "").replace("-", "")
    for aliases, f, b in PEAK_TABLE:
        if any(a in kind for a in aliases):
            return f, b
    import jax

    if jax.default_backend() == "tpu":
        raise ValueError(
            f"device kind {device_kind!r} is not in "
            "fedtpu.obs.profile.PEAK_TABLE; add its published peaks there"
        )
    env_f = os.environ.get(PEAK_FLOPS_ENV)
    env_b = os.environ.get(PEAK_HBM_ENV)
    return (
        float(env_f) if env_f else None,
        float(env_b) if env_b else None,
    )


# -------------------------------------------------------- analytic FLOPs
def _subjaxprs(params: dict):
    """Yield every jaxpr nested in an eqn's params (pjit bodies, scan/while
    bodies, cond branches, custom_* calls)."""
    for val in params.values():
        objs = val if isinstance(val, (list, tuple)) else (val,)
        for obj in objs:
            if hasattr(obj, "jaxpr"):  # ClosedJaxpr
                yield obj.jaxpr
            elif hasattr(obj, "eqns"):  # raw Jaxpr
                yield obj


def _dot_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
    k = math.prod(lhs[i] for i in lc)
    b = math.prod(lhs[i] for i in lb)
    m = math.prod(
        d for i, d in enumerate(lhs) if i not in lc and i not in lb
    )
    n = math.prod(
        d for i, d in enumerate(rhs) if i not in rc and i not in rb
    )
    return 2.0 * b * m * n * k


def _conv_flops(eqn) -> float:
    dnums = eqn.params["dimension_numbers"]
    rhs = eqn.invars[1].aval.shape
    out = eqn.outvars[0].aval.shape
    groups = eqn.params.get("feature_group_count", 1) or 1
    # rhs_spec = (out_chan, in_chan_per_group, *spatial)
    in_per_group = rhs[dnums.rhs_spec[1]]
    k_spatial = math.prod(rhs[i] for i in dnums.rhs_spec[2:])
    del groups  # in_chan axis of rhs is already per-group
    return 2.0 * math.prod(out) * in_per_group * k_spatial


def _count_jaxpr(jaxpr) -> float:
    flops = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            flops += _dot_flops(eqn)
        elif name == "conv_general_dilated":
            flops += _conv_flops(eqn)
        elif name == "cond":
            # One branch executes; count the worst case.
            branches = eqn.params.get("branches", ())
            flops += max(
                (_count_jaxpr(b.jaxpr) for b in branches), default=0.0
            )
        else:
            # A scan runs its body `length` times; a while's trip count is
            # not in the jaxpr and counts once. Everything else recursed
            # structurally.
            trips = eqn.params["length"] if name == "scan" else 1
            for sub in _subjaxprs(eqn.params):
                flops += trips * _count_jaxpr(sub)
    return flops


def analytic_flops(fn: Callable, *args, **kwargs) -> float:
    """Analytic FLOP count of ``fn(*args)``: 2 FLOPs per matmul/conv MAC,
    read off the traced jaxpr's shapes. Elementwise/reduction ops are
    excluded (MXU work dominates every zoo model by orders of magnitude);
    a ``lax.scan`` body counts ``length`` times (XLA's ``cost_analysis``
    counts it once, see module docstring), a ``while`` body once."""
    import jax

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return _count_jaxpr(closed.jaxpr)


# Pure shape/metadata primitives: XLA lowers these to layout bookkeeping or
# folds them into neighbouring fusions — they move no HBM bytes of their own
# (counting a scalar broadcast to [clients, ...] as traffic would swamp the
# model with phantom bytes).
_LAYOUT_PRIMS = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "transpose",
    "copy", "stop_gradient",
})

# Elementwise primitives XLA reliably folds into loop fusions: a chain of
# these runs as ONE pass over the data, so intermediates between them never
# touch HBM. The byte model groups maximal connected runs (see
# :func:`_bytes_jaxpr`) and charges only tensors crossing group boundaries.
_ELEMENTWISE_PRIMS = frozenset({
    "add", "sub", "mul", "div", "neg", "abs", "max", "min", "pow",
    "integer_pow", "square", "sqrt", "rsqrt", "exp", "exp2", "log", "log1p",
    "expm1", "tanh", "sin", "cos", "logistic", "erf", "erf_inv", "erfc",
    "sign", "floor", "ceil", "round", "clamp", "rem", "nextafter",
    "select_n", "convert_element_type", "reduce_precision", "eq", "ne",
    "lt", "le", "gt", "ge", "and", "or", "not", "xor", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "is_finite", "add_any",
    "atan2",
})

# Reductions fuse with their PRODUCERS (XLA input fusion: the reduce is the
# fusion root, reading its operand from registers), but their outputs are
# materialization points — consumers start a fresh pass over the data.
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_and", "reduce_or",
    "reduce_prod", "argmax", "argmin",
})

_FUSIBLE_PRIMS = _LAYOUT_PRIMS | _ELEMENTWISE_PRIMS | _REDUCE_PRIMS


def _aval_bytes(var) -> float:
    if hasattr(var, "val"):  # Literal: a compile-time constant, not traffic
        return 0.0
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0.0
    return float(math.prod(shape)) * dtype.itemsize


def _bytes_jaxpr(jaxpr) -> float:
    """Fusion-aware byte walk of one jaxpr level.

    Greedy producer->consumer fusion grouping over
    :data:`_FUSIBLE_PRIMS`: a maximal connected run of elementwise /
    layout / reduction eqns is ONE pass over the data, charging only the
    tensors that cross its boundary (read once by each consuming group,
    written once by the producer) — intermediates inside a group are
    register traffic, not HBM. Reduction outputs always materialize
    (consumers re-read). Non-fusible ops (conv, dot, gather, rng, ...)
    are singleton groups, i.e. charged per-eqn input+output exactly as
    before. Layout eqns alias their output to their operand, so a
    pure-layout group charges nothing and a broadcast feeding another
    group charges its (small) operand, not the phantom broadcast bytes.
    A scan body counts ``length`` times (as :func:`_count_jaxpr`), a while
    body once; cond takes the worst branch.
    """
    eqns = jaxpr.eqns
    total = 0.0
    opaque = set()
    for i, eqn in enumerate(eqns):
        if eqn.primitive.name == "cond":
            branches = eqn.params.get("branches", ())
            total += max(
                (_bytes_jaxpr(b.jaxpr) for b in branches), default=0.0
            )
            opaque.add(i)
            continue
        subs = list(_subjaxprs(eqn.params))
        if subs:
            # The container eqn's own full-array operands are NOT added on
            # top: the body's boundary tensors carry the traffic.
            trips = (
                eqn.params["length"] if eqn.primitive.name == "scan" else 1
            )
            for sub in subs:
                total += trips * _bytes_jaxpr(sub)
            opaque.add(i)

    producer: Dict[Any, int] = {}
    alias: Dict[Any, Any] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.outvars:
            producer[v] = i
        if (
            i not in opaque
            and eqn.primitive.name in _LAYOUT_PRIMS
            and eqn.invars
        ):
            alias[eqn.outvars[0]] = eqn.invars[0]

    def resolve(v):
        while not hasattr(v, "val") and v in alias:
            v = alias[v]
        return v  # a Literal endpoint charges 0 via _aval_bytes

    def fusible(i: int) -> bool:
        return i not in opaque and eqns[i].primitive.name in _FUSIBLE_PRIMS

    parent = list(range(len(eqns)))

    def find(i: int) -> int:
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    for i, eqn in enumerate(eqns):
        if not fusible(i):
            continue
        for v in eqn.invars:
            if hasattr(v, "val"):  # Literal
                continue
            p = producer.get(v)
            if (
                p is not None
                and fusible(p)
                and eqns[p].primitive.name not in _REDUCE_PRIMS
            ):
                parent[find(i)] = find(p)

    def gid(i: int):
        return ("f", find(i)) if fusible(i) else ("op", i)

    consumers: Dict[Any, list] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not hasattr(v, "val"):
                consumers.setdefault(v, []).append(i)

    out_set = set(v for v in jaxpr.outvars if not hasattr(v, "val"))
    reads: Dict[Any, set] = {}
    writes: Dict[Any, set] = {}
    has_real: Dict[Any, bool] = {}
    for i, eqn in enumerate(eqns):
        if i in opaque:
            continue
        g = gid(i)
        if eqn.primitive.name not in _LAYOUT_PRIMS:
            has_real[g] = True
        for v in eqn.invars:
            if hasattr(v, "val"):
                continue
            p = producer.get(v)
            if p is None or gid(p) != g:
                r = resolve(v)
                if not hasattr(r, "val"):
                    reads.setdefault(g, set()).add(r)
        for v in eqn.outvars:
            cons = consumers.get(v, [])
            ext = (
                v in out_set
                or not cons
                or eqn.primitive.name in _REDUCE_PRIMS
                or any(gid(c) != g for c in cons)
            )
            if ext:
                w = resolve(v)
                if not hasattr(w, "val"):
                    writes.setdefault(g, set()).add(w)
    for g in set(reads) | set(writes):
        if not has_real.get(g):
            continue  # pure-layout group: bookkeeping, no traffic
        total += sum(_aval_bytes(v) for v in reads.get(g, ()))
        total += sum(_aval_bytes(v) for v in writes.get(g, ()))
    return total


def analytic_bytes(fn: Callable, *args, **kwargs) -> float:
    """Analytic HBM-traffic model of ``fn(*args)``: fusion-group boundary
    bytes at the JAXPR avals' stated dtypes, scan bodies times their
    length, shape/layout primitives free (see :func:`_bytes_jaxpr`).

    This is deliberately BACKEND-INDEPENDENT — read off the traced jaxpr,
    never the lowered HLO — because it exists to predict the TPU HBM
    effect of dtype/layout levers from a host without the chip: a CPU
    backend's ``cost_analysis`` bytes describe bf16 *emulation* (f32
    upconverts inserted by the CPU lowering), which inverts the very
    signal being measured. Fusion-awareness matters for the same reason:
    an unfused per-eqn count charges the f32 intermediates of e.g. a
    BatchNorm statistics chain at 5x activation size, even though XLA
    folds the whole chain into one pass over the (compute-dtype) input —
    biasing the count AGAINST exactly the dtype lever being measured.
    Greedy elementwise grouping is still a model, not a compiler:
    absolute numbers are approximate; mode-over-mode RATIOS (f32 vs
    bf16) are the supported use.
    On-chip, prefer the XLA figure (:func:`xla_cost`), which is measured
    from the optimised HLO."""
    import jax

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return _bytes_jaxpr(closed.jaxpr)


def xla_cost(compiled) -> Dict[str, float]:
    """``{"flops": ..., "bytes": ...}`` from a compiled executable's
    ``cost_analysis()``."""
    analysis = compiled.cost_analysis()
    return {
        "flops": float(analysis.get("flops", 0.0)),
        "bytes": float(analysis.get("bytes accessed", 0.0)),
    }


def roofline(
    flops: Optional[float],
    bytes_accessed: Optional[float],
    peak_flops: Optional[float],
    peak_bw: Optional[float],
    achieved_flops_per_s: Optional[float] = None,
) -> Dict[str, Any]:
    """Classic roofline classification for one program execution. Returns
    ``arith_intensity_flops_per_byte``, ``ridge_point_flops_per_byte``,
    ``roofline_bound`` ("compute" | "bandwidth") and, when an achieved rate
    is given, ``roofline_utilization`` = achieved / ceiling-at-intensity.
    Keys are present-but-None when an input is missing — schema-stable for
    the ``--mfu-profile`` artifact."""
    out: Dict[str, Any] = {
        "arith_intensity_flops_per_byte": None,
        "ridge_point_flops_per_byte": None,
        "roofline_bound": None,
        "roofline_utilization": None,
    }
    if flops and bytes_accessed:
        out["arith_intensity_flops_per_byte"] = round(
            flops / bytes_accessed, 3
        )
    if peak_flops and peak_bw:
        out["ridge_point_flops_per_byte"] = round(peak_flops / peak_bw, 3)
    ai = out["arith_intensity_flops_per_byte"]
    ridge = out["ridge_point_flops_per_byte"]
    if ai is not None and ridge is not None:
        out["roofline_bound"] = "compute" if ai >= ridge else "bandwidth"
        if achieved_flops_per_s:
            ceiling = (
                peak_flops if ai >= ridge else peak_bw * ai
            )
            if ceiling:
                out["roofline_utilization"] = round(
                    achieved_flops_per_s / ceiling, 6
                )
    return out


# ------------------------------------------------------------- cost model
class CostModel:
    """Per-round FLOP/byte figures for one round program, carrying both the
    analytic count and the XLA cost-analysis one plus their ratio.
    ``flops`` prefers the analytic walk: it multiplies the local steps'
    scan in, which XLA's count leaves out (module docstring), so a gauge
    priced with XLA's reads low by the number of local steps. XLA's is the
    cross-check, and the fallback where the walk fails."""

    def __init__(
        self,
        xla_flops: Optional[float] = None,
        xla_bytes: Optional[float] = None,
        analytic: Optional[float] = None,
        analytic_bytes: Optional[float] = None,
    ):
        self.xla_flops = xla_flops or None
        self.xla_bytes = xla_bytes or None
        self.analytic = analytic or None
        self.analytic_bytes = analytic_bytes or None
        self.flops = self.analytic or self.xla_flops
        self.source = (
            "analytic" if self.analytic else
            ("xla" if self.xla_flops else None)
        )
        self.agreement = (
            round(self.analytic / self.xla_flops, 4)
            if self.analytic and self.xla_flops else None
        )

    def as_dict(self) -> Dict[str, Any]:
        return {
            "flops_per_round": self.flops,
            "bytes_per_round": self.xla_bytes,
            "analytic_flops_per_round": self.analytic,
            "analytic_bytes_per_round": self.analytic_bytes,
            "flops_source": self.source,
            "analytic_vs_xla": self.agreement,
        }


def engine_cost_model(fed, xla_check: bool = True) -> CostModel:
    """Build the per-round :class:`CostModel` for a
    :class:`fedtpu.core.engine.Federation`'s device-data round program:
    analytic jaxpr walk + (with ``xla_check``, best-effort) AOT compile for
    ``cost_analysis``. One-time cost at first use (the AOT compile hits
    the persistent XLA compile cache the engine already enables)."""
    import jax.numpy as jnp

    d_images, d_labels, d_idx, d_mask = fed._ensure_device_data()
    n = fed.cfg.fed.num_clients
    alive = fed._placed(
        jnp.ones((n,), bool), sharded=fed.mesh is not None
    )
    extra = ()
    if fed._attack_seats is not None:
        extra = (jnp.asarray(fed._attack_seats),)
    args = (
        fed.state, d_images, d_labels, d_idx, d_mask, fed.weights, alive,
        fed._data_key, *extra,
    )
    analytic = ab = None
    try:
        import jax

        closed = jax.make_jaxpr(fed._data_step)(*args)
        analytic = _count_jaxpr(closed.jaxpr)
        ab = _bytes_jaxpr(closed.jaxpr)
    except Exception as e:  # pragma: no cover - backend quirks
        log.debug("analytic FLOP/byte model failed: %s", e)
    xf = xb = None
    if xla_check:
        try:
            compiled = fed._data_step.lower(*args).compile()
            cost = xla_cost(compiled)
            xf, xb = cost["flops"], cost["bytes"]
        except Exception as e:  # pragma: no cover - backend quirks
            log.debug("XLA cost analysis unavailable: %s", e)
    return CostModel(
        xla_flops=xf, xla_bytes=xb, analytic=analytic, analytic_bytes=ab
    )


# ---------------------------------------------------------- round profiler
class RoundProfiler:
    """Continuous per-round MFU/step-time accounting through one Telemetry.

    ``observe_round(wall_s, rounds=n)`` after each dispatch sets three
    gauges and returns the derived dict for round-record stamping. All
    per-round work is arithmetic + gauge sets (no device sync, no
    compile); the cost model is attached once via :meth:`set_cost_model`.
    """

    def __init__(
        self,
        telemetry,
        n_devices: int = 1,
        device_kind: str = "",
    ):
        self.telemetry = telemetry
        self.n_devices = max(1, int(n_devices))
        self.device_kind = device_kind
        self.peak_flops, self.peak_bw = device_peaks(device_kind)
        self.cost: Optional[CostModel] = None
        self._last: Dict[str, Any] = {}
        self._rounds = 0

    def set_cost_model(self, cost: CostModel) -> None:
        self.cost = cost

    def observe_round(self, wall_s: float, rounds: int = 1) -> Dict[str, Any]:
        """Account one dispatch of ``rounds`` fused rounds taking ``wall_s``
        seconds; returns ``{step_time_s, achieved_flops_per_s, mfu}``
        (items None when underivable) after updating the gauges."""
        tel = self.telemetry
        step_s = wall_s / max(1, rounds)
        self._rounds += rounds
        out: Dict[str, Any] = {
            "step_time_s": step_s,
            "achieved_flops_per_s": None,
            "mfu": None,
        }
        tel.gauge(
            "fedtpu_step_time_seconds",
            "wall time of the last round dispatch, per round",
        ).set(step_s)
        flops = self.cost.flops if self.cost else None
        if flops and wall_s > 0:
            achieved = flops * rounds / wall_s
            out["achieved_flops_per_s"] = achieved
            tel.gauge(
                "fedtpu_achieved_flops_per_sec",
                "model FLOPs retired per second over the last dispatch "
                "(all devices)",
            ).set(achieved)
            if self.peak_flops:
                mfu = achieved / (self.n_devices * self.peak_flops)
                out["mfu"] = mfu
                tel.gauge(
                    "fedtpu_mfu_ratio",
                    "model FLOPs utilization of the last dispatch vs "
                    "per-chip peak (fedtpu.obs.profile.PEAK_TABLE)",
                ).set(mfu)
        self._last = out
        return out

    def record_fields(self) -> Dict[str, Any]:
        """Rounded stamps for a v1 round record from the last observation
        (empty before any round / when underivable) — the round loops merge
        this into each record they emit."""
        out: Dict[str, Any] = {}
        last = self._last
        if last.get("achieved_flops_per_s"):
            out["achieved_flops_per_s"] = round(
                last["achieved_flops_per_s"], 1
            )
        if last.get("mfu") is not None:
            out["mfu"] = round(last["mfu"], 6)
        return out

    def snapshot(self) -> Dict[str, Any]:
        """The ``/statusz`` perf block: last-round derived figures + the
        static cost model and peaks."""
        snap: Dict[str, Any] = {
            "device_kind": self.device_kind,
            "n_devices": self.n_devices,
            "peak_flops_per_s": self.peak_flops,
            "rounds_observed": self._rounds,
        }
        if self.cost is not None:
            snap.update(self.cost.as_dict())
        snap.update(self._last)
        if self.cost is not None and self._last.get("achieved_flops_per_s"):
            snap.update(roofline(
                self.cost.flops, self.cost.xla_bytes,
                self.peak_flops, self.peak_bw,
                self._last["achieved_flops_per_s"] / self.n_devices,
            ))
        return snap


# ------------------------------------------------------- latency summaries
def latency_summary(
    pairs: Sequence[Tuple[str, float]], top_k: int = 3
) -> Dict[str, Any]:
    """p50/p95/p99 + top-k slowest over ``(client, seconds)`` pairs — the
    straggler-attribution block on server round records and ``/statusz``.
    Empty input yields ``{}`` (rounds with no completed RPCs)."""
    if not pairs:
        return {}
    lats = sorted(v for _, v in pairs)

    def pct(p: float) -> float:
        # Nearest-rank percentile: exact at small n, no interpolation.
        i = min(len(lats) - 1, max(0, math.ceil(p / 100.0 * len(lats)) - 1))
        return round(lats[i], 6)

    slowest = sorted(pairs, key=lambda cv: cv[1], reverse=True)[:top_k]
    return {
        "n": len(pairs),
        "p50_s": pct(50),
        "p95_s": pct(95),
        "p99_s": pct(99),
        "max_s": round(lats[-1], 6),
        "slowest": [[c, round(v, 6)] for c, v in slowest],
    }


# ------------------------------------------------ what jax says of a compile
# jax 0.9.0's names, the one table both listeners below read. The four
# durations arrive through ``jax.monitoring``'s duration listeners with the
# program's ``fun_name`` (the cache's retrieval alone comes without); the two
# events through its event listeners. ``backend_compile_duration`` wraps
# ``compile_or_get_cached``, so on a cache hit it CONTAINS the retrieval.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    BACKEND_COMPILE_EVENT: "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class SetupCompileListener:
    """What jax says about compiling while ONE set-up phase of an engine is
    open (``Telemetry.phase(..., compiles=True)``): each duration of
    :data:`COMPILE_DURATION_EVENTS` is added to
    ``fedtpu_setup_seconds{phase="<phase>.trace" | ".lower" | ".compile" |
    ".cache_load"}``, each hit or miss of the persistent cache to
    ``fedtpu_setup_cache_hits`` / ``fedtpu_setup_cache_misses`` (jax
    records a miss when it WRITES the compiled program to the cache, so
    neither fires without one).

    A duration is charged NET of the durations that ended inside it: an
    inner jit's trace inside the outer one's, and the cache's retrieval
    inside ``backend_compile_duration`` (a program either compiled or
    loaded; the hit or miss that fired in between says which). The four
    gauges of a phase are therefore disjoint pieces of its wall.

    Registered on the phase's entry and unregistered on its exit, so the
    listener lives only while the engine's own set-up compiles: a caller's
    jits, before or after, are never counted."""

    def __init__(self, registry, phase: str, charge):
        """``charge(phase, seconds)`` adds to the phase's seconds (the
        owner's: ``fedtpu_setup_seconds`` is defined where phases are)."""
        self.registry = registry
        self.phase = phase
        self._charge = charge
        self._ended: List[Tuple[float, float]] = []  # (end, gross duration)
        self._lock = threading.Lock()

    def _cache_gauges(self):
        help = ("programs of the newest engine's set-up that jax's "
                "persistent compile cache served (hits) or that compiled "
                "and were written to it (misses)")
        return (self.registry.gauge("fedtpu_setup_cache_hits", help),
                self.registry.gauge("fedtpu_setup_cache_misses", help))

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        kind = COMPILE_DURATION_EVENTS.get(event)
        if kind is None:
            return
        end = time.perf_counter()  # the listener runs as the interval closes
        with self._lock:
            inside = 0.0
            while self._ended and self._ended[-1][0] >= end - duration:
                inside += self._ended.pop()[1]
            self._ended.append((end, duration))
        self._charge(f"{self.phase}.{kind}", max(0.0, duration - inside))

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self._cache_gauges()[0].inc()
        elif event == CACHE_MISS_EVENT:
            self._cache_gauges()[1].inc()

    def __enter__(self) -> "SetupCompileListener":
        from jax import monitoring

        # A phase that can compile states both counts: 0 is a reading.
        for gauge in self._cache_gauges():
            gauge.inc(0)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)


# --------------------------------------------------------- compile watcher
RECOMPILES_KEPT = 8  # the last steady-state recompiles snapshot() lists


class CompileWatcher:
    """Count + time XLA compilations via ``jax.monitoring`` duration events
    (:data:`BACKEND_COMPILE_EVENT` fires once per backend compile, with the
    program's ``fun_name``). After :meth:`mark_steady` — the owner's signal
    that every program it intends to run has warmed up — any further
    compile is a *steady-state recompile*: it warns, flight-records, and
    bumps ``fedtpu_xla_recompiles_steady_total{fun_name}``, each naming the
    program, because a recompile inside the round loop silently turns a
    ~ms round into a multi-second one.

    ``install()``/``uninstall()`` manage the process-global listener; one
    active watcher per process (the registration API has no scoping)."""

    _active: Optional["CompileWatcher"] = None

    def __init__(self, telemetry=None, flight=None):
        self.telemetry = telemetry
        self.flight = flight
        self.compiles = 0
        self.compile_seconds = 0.0
        self.recompiles_after_steady = 0
        self._recompiled: List[Dict[str, Any]] = []
        self._steady = False
        self._installed = False
        self._lock = threading.Lock()

    def _listener(self, event: str, duration: float,
                  fun_name: str = "", **kwargs) -> None:
        if not self._installed or event != BACKEND_COMPILE_EVENT:
            return
        fun_name = str(fun_name) or "unknown"
        with self._lock:
            self.compiles += 1
            self.compile_seconds += duration
            steady = self._steady
            if steady:
                self.recompiles_after_steady += 1
                self._recompiled.append(
                    {"fun_name": fun_name, "seconds": round(duration, 4)})
                del self._recompiled[:-RECOMPILES_KEPT]
        tel = self.telemetry
        if tel is not None:
            tel.counter(
                "fedtpu_xla_compiles_total",
                "XLA backend compilations observed by this process",
            ).inc()
            tel.histogram(
                "fedtpu_xla_compile_seconds",
                "XLA backend compile wall time per executable",
            ).observe(duration)
        if steady:
            log.warning(
                "steady-state XLA recompile of %s (%.2fs): a program shape "
                "or constant drifted after warmup — the classic silent "
                "round slowdown (compiles so far: %d)",
                fun_name, duration, self.compiles,
            )
            if tel is not None:
                tel.counter(
                    "fedtpu_xla_recompiles_steady_total",
                    "XLA compilations after the owner declared steady "
                    "state (each one is a latent perf bug), by the "
                    "program that recompiled",
                    labels={"fun_name": fun_name},
                ).inc()
            if self.flight is not None:
                self.flight.record(
                    "xla_recompile",
                    fun_name=fun_name,
                    duration_s=round(duration, 4),
                    compiles_total=self.compiles,
                )

    def install(self) -> "CompileWatcher":
        if self._installed:
            return self
        if CompileWatcher._active is not None:
            raise RuntimeError(
                "another CompileWatcher is already installed in this "
                "process (jax.monitoring listeners are global)"
            )
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._listener)
        self._installed = True
        CompileWatcher._active = self
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        self._installed = False
        if CompileWatcher._active is self:
            CompileWatcher._active = None
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._listener)

    def mark_steady(self) -> None:
        with self._lock:
            self._steady = True

    @property
    def steady(self) -> bool:
        return self._steady

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "compiles": self.compiles,
                "compile_seconds": round(self.compile_seconds, 4),
                "steady": self._steady,
                "recompiles_after_steady": self.recompiles_after_steady,
                "recompiled": list(self._recompiled),
            }


# -------------------------------------------------------- capture windows
PROFILE_META = "profile_meta.json"


def parse_round_window(spec: str) -> Tuple[int, int]:
    """Parse ``--profile-rounds N:M`` into a half-open ``[N, M)`` round
    window (``"3:5"`` captures rounds 3 and 4). A bare ``N`` means one
    round ``[N, N+1)``."""
    try:
        if ":" in spec:
            a, b = spec.split(":", 1)
            lo, hi = int(a), int(b)
        else:
            lo = int(spec)
            hi = lo + 1
    except ValueError:
        raise ValueError(
            f"--profile-rounds wants N:M (half-open round window), "
            f"got {spec!r}"
        )
    if lo < 0 or hi <= lo:
        raise ValueError(
            f"--profile-rounds window must satisfy 0 <= N < M, got {spec!r}"
        )
    return lo, hi


def write_profile_meta(
    trace_dir: str, role: str = "", trace_id: Optional[str] = None,
    extra: Optional[dict] = None,
) -> str:
    """Drop the ``profile_meta.json`` sidecar into a profiler output dir:
    ``wall_start`` (wall clock at capture start — device-trace timestamps
    are relative to it) + role/trace_id for lane naming and federation
    stitching. This is what lets ``tools/trace_merge.py`` put device ops on
    the same wall-clock timeline as host spans."""
    meta = {
        "wall_start": time.time(),
        "role": role,
        "trace_id": trace_id,
        "format": "jax.profiler",
    }
    if extra:
        meta.update(extra)
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, PROFILE_META)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(meta, fh)
    os.replace(tmp, path)
    return path


class CaptureWindow:
    """Round-windowed ``jax.profiler`` capture for a round loop.

    The loop calls :meth:`maybe_start` with the first round of the block it
    is about to dispatch and :meth:`maybe_stop` with the next round index
    after it completes; the window opens before the first block that
    overlaps ``[lo, hi)`` and closes after the block that reaches ``hi``.
    Fused blocks are captured whole (the profiler cannot cut inside one
    dispatch). ``stop()`` is idempotent and must be called on loop exit so
    a window that spans the tail still flushes."""

    def __init__(
        self, spec: str, trace_dir: str,
        role: str = "", trace_id: Optional[str] = None,
    ):
        self.lo, self.hi = parse_round_window(spec)
        self.trace_dir = trace_dir
        self.role = role
        self.trace_id = trace_id
        self._ctx = None

    @property
    def active(self) -> bool:
        return self._ctx is not None

    def stamp(self, trace_id: Optional[str]) -> None:
        """The federation's trace id, where it is known only after the
        window opened (a round-0 window of the run CLI opens before the
        engine, and its tracer, exist): an open window's sidecar is
        rewritten with it, its ``wall_start`` kept."""
        self.trace_id = trace_id
        if self._ctx is None or not trace_id:
            return
        with open(os.path.join(self.trace_dir, PROFILE_META)) as fh:
            meta = json.load(fh)
        write_profile_meta(
            self.trace_dir, role=self.role, trace_id=trace_id,
            extra={k: meta[k] for k in ("wall_start", "round_window")},
        )

    def maybe_start(self, first_round: int, last_round: int = None) -> None:
        """Open the window if block ``[first_round, last_round]`` overlaps
        it (``last_round`` defaults to ``first_round``)."""
        if self._ctx is not None:
            return
        last = first_round if last_round is None else last_round
        if first_round >= self.hi or last < self.lo:
            return
        import jax

        write_profile_meta(
            self.trace_dir, role=self.role, trace_id=self.trace_id,
            extra={"round_window": [self.lo, self.hi]},
        )
        self._ctx = jax.profiler.trace(self.trace_dir)
        self._ctx.__enter__()
        log.info(
            "profiler capture window open: rounds [%d, %d) -> %s",
            self.lo, self.hi, self.trace_dir,
        )

    def maybe_stop(self, next_round: int) -> None:
        if self._ctx is not None and next_round >= self.hi:
            self.stop()

    def stop(self) -> None:
        if self._ctx is None:
            return
        ctx, self._ctx = self._ctx, None
        try:
            ctx.__exit__(None, None, None)
        except Exception as e:  # pragma: no cover - profiler teardown
            log.warning("profiler capture stop failed: %s", e)
        else:
            log.info("profiler capture window closed: %s", self.trace_dir)
