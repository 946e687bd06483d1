"""The comparison that decides ``correct``.

The program's first rounds, driven in set-up through the very object and call
the window then uses, are set against the plain reference's rounds from the
same seeded weights on the same rows. Every number is printed beside its
limit; the limits live in ``limits/<cell>.json`` with the readings they were
set from.
"""

from __future__ import annotations

import jax
import numpy as np


def follow_reference(cell, seed, inputs, devices, quant=None, bits=None):
    """The plain reference's first rounds from the seeded weights on the
    cell's rows (``inputs`` as ``seeded_inputs`` gives them):
    ``({"losses", "first", "last"}, codec)``. ``quant`` and ``bits`` are the
    lower-precision controls' (``reference/lowprec.py``)."""
    from benchmark import seeded
    from benchmark.reference import fedavg, layers, rotq

    cfg, traffic = cell.config, cell.traffic
    examples, targets, shards, initial = inputs

    def feed(c):
        rows = seeded.client_rows(shards[0][c], traffic["steps"], cfg["batch_size"])
        return examples[rows], targets[rows]

    codec = None
    if traffic.get("codec"):
        codec = rotq.RotQ(initial["params"], bits or traffic["codec"]["bits"], seed)
    ref = fedavg.Reference(
        cell.reference.make_forward(cfg), cell.task.loss, initial["params"],
        initial["stats"], cfg["optimizer"], feed, shards[1].sum(axis=1),
        devices, codec, quant or layers.ident,
        cfg.get("reference_block_rows"), getattr(cell.task, "loss_parts", None))
    out = {"losses": []}
    for r in range(traffic["check_rounds"]):
        loss, _, _, extra = ref.round()
        out["losses"].append(loss)
        if r == 0:
            out["first"] = {"params": ref.params, "stats": ref.stats, **extra}
    out["last"] = {"params": ref.params}
    return out, codec


def seeded_inputs(cell, seed):
    """What a run draws from the seed for a cell: ``(examples, targets,
    shards, initial)``, the first two the task's (host arrays, first axis the
    examples), ``initial = {"params", "stats"}`` as host trees."""
    from benchmark import seeded

    examples, targets = cell.task.make_data(seed, cell.config)
    shards = seeded.make_shards(seed, len(examples), cell.traffic["clients"],
                                cell.traffic.get("shards", "iid"))
    params, stats = seeded.make_weights(seed, *cell.reference.spec(cell.config))
    return examples, targets, shards, {
        "params": jax.tree.map(np.asarray, params), "stats": stats}


def first_rounds(fed, n, one_round, after_first=lambda: None):
    """Drive the program's first ``n`` rounds through ``one_round() -> loss``
    and keep what the check reads: ``{"losses", "first", "last"}``."""
    from benchmark import sut

    program = {"losses": []}
    for r in range(n):
        program["losses"].append(one_round())
        if r == 0:
            after_first()
            program["first"] = sut.snapshot(fed)
    program["last"] = program["first"] if n == 1 else sut.snapshot(fed)
    return program


def against_reference(cell, seed, inputs, devices, program):
    """The numbers of ``program`` against the plain reference's rounds, and
    the reference's readings (the controls are compared with them too)."""
    initial = inputs[3]
    reference, codec = follow_reference(cell, seed, inputs, devices)
    if codec is not None and "mean_residual_row" in program["first"]:
        program["first"]["mean_residual"] = codec.unpack(
            program["first"].pop("mean_residual_row"), initial["params"])
    return numbers(initial, program, reference), reference


def _f64(leaf):
    return np.asarray(leaf, np.float64)


class _Lazy:
    """A float64 leaf of a difference or a sum of trees, worked out when it
    is read and kept by no one: the check holds one leaf in float64 at a
    time, not whole trees."""

    def __init__(self, op, x, y):
        self.op, self.x, self.y = op, x, y

    def __array__(self, dtype=None, copy=None):
        return self.op(_f64(self.x), _f64(self.y))


def sub(a, b):
    return jax.tree.map(lambda x, y: _Lazy(np.subtract, x, y), a, b)


def add(a, b):
    return jax.tree.map(lambda x, y: _Lazy(np.add, x, y), a, b)


def _leaf_norms(tree):
    return np.array([float(np.linalg.norm(_f64(l))) for l in jax.tree.leaves(tree)])


def worst_leaf_gap(program, reference):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some leaves' changes are all but zero)."""
    p, r = _leaf_norms(program), _leaf_norms(reference)
    if len(r) == 0:
        return 0.0
    floor = np.median(r)
    denom = np.maximum(r, floor)
    if not np.all(denom > 0):
        return float("inf") if np.any(p != r) else 0.0
    return float(np.max(np.abs(p - r) / denom))


def rel_diff(program, reference):
    """Norm of the difference over the reference's norm, whole tree: what
    rounding in a lower precision moves most, and steadily."""
    d, n = [], []
    for p, r in zip(jax.tree.leaves(program), jax.tree.leaves(reference)):
        r = _f64(r)
        d.append(float(np.sum((_f64(p) - r) ** 2)))
        n.append(float(np.sum(r ** 2)))
    d, n = sum(d), sum(n)
    return float(np.sqrt(d / n)) if n > 0 else float("inf")


def numbers(initial, program, reference):
    """The numbers compared. ``program`` and ``reference`` each hold
    ``losses`` (one a round), ``first`` (state after round 1) and ``last``
    (params after the last checked round); codec cells add the residual
    readings of round 1."""
    out = {}
    pl, rl = np.array(program["losses"]), np.array(reference["losses"])
    if not (np.all(np.isfinite(pl)) and len(pl) == len(rl)):
        out["loss_gap"] = float("inf")
    else:
        out["loss_gap"] = float(np.max(np.abs(pl - rl) / np.abs(rl)))
    p_upd = sub(program["first"]["params"], initial["params"])
    r_upd = sub(reference["first"]["params"], initial["params"])
    if "mean_residual" in program["first"]:
        # mean(change) = what the server applied + what the clients kept.
        p_upd = add(p_upd, program["first"]["mean_residual"])
        r_upd = add(r_upd, reference["first"]["mean_residual"])
        pn = np.asarray(program["first"]["residual_norms"], np.float64)
        rn = np.asarray(reference["first"]["residual_norms"], np.float64)
        out["codec_residual_gap"] = float(np.max(np.abs(pn - rn) / rn))
    out["update1_gap"] = worst_leaf_gap(p_upd, r_upd)
    out["update1_diff"] = rel_diff(p_upd, r_upd)
    if jax.tree.leaves(initial["stats"]):
        out["stats1_gap"] = worst_leaf_gap(
            sub(program["first"]["stats"], initial["stats"]),
            sub(reference["first"]["stats"], initial["stats"]))
    out["change_gap"] = worst_leaf_gap(
        sub(program["last"]["params"], initial["params"]),
        sub(reference["last"]["params"], initial["params"]))
    return out


def verdict(nums, limits, out=print):
    """Print each number beside its limit; correct iff every one is within."""
    ok = True
    for name, value in nums.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits file")
        limit = limits[name]["limit"]
        if limit is None:  # read and printed, not held in this cell (see why)
            out(f"check {name} = {value:.6g}  not held")
            continue
        good = bool(np.isfinite(value) and value <= limit)
        ok = ok and good
        out(f"check {name} = {value:.6g}  limit {limit:g}  "
            f"{'ok' if good else 'OVER'}")
    return ok
