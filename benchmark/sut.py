"""The system under test: a ``fedtpu`` ``Federation`` built from a cell's
configuration and traffic files. The only file of the benchmark that imports
the program; what it takes from it is the engine, its state, and nothing else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def round_config(config, traffic, task):
    """The program's own configuration object for a cell's files: what every
    cell states (model, optimizer, batch, clients, codec), then the task's
    fields (``program_fields``: the kind of data), then the optional
    ``"program": {"round"|"opt"|"data"|"fed": {...}}`` of the configuration
    file and of the traffic file, by dataclass field name. A field the
    program gains later is stated in a file, not here."""
    import dataclasses

    from fedtpu.config import DataConfig, FedConfig, OptimizerConfig, RoundConfig

    opt, codec = config["optimizer"], traffic.get("codec") or {}
    fields = {
        "round": dict(
            model=config["model"], steps_per_round=traffic["steps"],
            dtype=config["activation_dtype"], remat=config["remat"]),
        "opt": dict(
            learning_rate=opt["learning_rate"], momentum=opt["momentum"],
            weight_decay=opt["weight_decay"], schedule="constant"),
        # The benchmark hands in its own IID shards; "round_robin" is the
        # program's name for iterating a shard unshuffled from its head.
        "data": dict(batch_size=config["batch_size"], partition="round_robin"),
        "fed": dict(
            num_clients=traffic["clients"], weighted=True,
            delta_layout=traffic["delta_layout"],
            compression=codec.get("name", "none"),
            rotq_bits=codec.get("bits", 4),
            error_feedback=codec.get("error_feedback", True)),
    }
    classes = {"round": RoundConfig, "opt": OptimizerConfig, "data": DataConfig,
               "fed": FedConfig}
    overlays = (("the task", task.program_fields(config)),
                ("the configuration file", config.get("program", {})),
                ("the traffic file", traffic.get("program", {})))
    for where, overlay in overlays:
        for group, values in overlay.items():
            if group not in classes:
                raise ValueError(f"{where} states program fields under {group!r}; "
                                 f"the groups are {sorted(classes)}")
            known = {f.name for f in dataclasses.fields(classes[group])} - set(classes)
            for name in values:
                if name not in known:
                    raise ValueError(
                        f"{where} states {group}.{name}, and the program's "
                        f"{classes[group].__name__} has no field {name!r}")
            fields[group].update(values)
    return RoundConfig(
        opt=OptimizerConfig(**fields["opt"]), data=DataConfig(**fields["data"]),
        fed=FedConfig(**fields["fed"]), **fields["round"])


def build(cell, inputs):
    """The engine for a cell on what ``check.seeded_inputs`` drew for it."""
    from fedtpu.core import Federation

    examples, targets, shards, initial = inputs
    cfg = round_config(cell.config, cell.traffic, cell.task)
    mesh = None
    if cell.traffic["mesh"]:
        from fedtpu.parallel import client_mesh

        mesh = client_mesh(cell.chips, cfg.mesh_axis)
    fed = Federation(cfg, seed=0, data=(examples, targets), mesh=mesh,
                     assignment=shards)
    state = fed.state
    fed.state = state._replace(
        params=_like(state.params, initial["params"]),
        batch_stats=_like(state.batch_stats, initial["stats"]),
    )
    return fed


def _names(path):
    return tuple(getattr(k, "key", getattr(k, "name", None)) for k in path)


def _like(theirs, ours):
    """Our seeded leaves in the program's tree, matched by name and shape."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(theirs)
    out = []
    for path, leaf in leaves:
        node = ours
        for name in _names(path):
            if not isinstance(node, dict) or name not in node:
                raise KeyError(f"the program has a leaf {_names(path)} that the "
                               "reference's parameter list lacks")
            node = node[name]
        if tuple(np.shape(node)) != tuple(leaf.shape):
            raise ValueError(f"{_names(path)}: program {leaf.shape}, "
                             f"reference {np.shape(node)}")
        out.append(np.asarray(node, np.float32))
    n_ours = len(jax.tree.leaves(ours))
    if n_ours != len(out):
        raise ValueError(f"the reference lists {n_ours} leaves, the program "
                         f"has {len(out)}")
    return jax.tree_util.tree_unflatten(treedef, out)


def named(tree):
    """A program tree as nested plain dicts of host arrays."""
    root = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        node, names = root, _names(path)
        for name in names[:-1]:
            node = node.setdefault(name, {})
        node[names[-1]] = np.asarray(leaf)
    return root


@jax.jit
def _residual_readings(residual, weights):
    share = weights / jnp.sum(weights)
    return (jnp.linalg.norm(residual, axis=1),
            jnp.einsum("c,cp->p", share, residual, precision="highest"))


def snapshot(fed):
    """What the check reads of the program's state after a round: the global
    model and, where a codec keeps residuals, each client's residual norm and
    the weighted mean residual row."""
    state = fed.state
    snap = {"params": named(state.params), "stats": named(state.batch_stats)}
    if not isinstance(state.comp_state, tuple):
        norms, row = _residual_readings(state.comp_state, fed.weights)
        snap["residual_norms"] = np.asarray(norms)
        snap["mean_residual_row"] = np.asarray(row)
    return snap


def step(fed):
    """One timed dispatch: a federated round as the caller sees it."""
    return fed.step()


def release():
    """Free everything the process holds on the devices (the caller has
    dropped its engine): the reference then has the chip to itself."""
    import gc

    gc.collect()
    for a in jax.live_arrays():
        a.delete()
