#!/usr/bin/env python
"""Whether two checkouts lower the same round programs: what a PR means by
"the other cells run the parent's programs, byte for byte".

    git archive <parent> | tar -x -C /tmp/parent
    python tools/lowered_programs.py /tmp/parent [.]

Every cell of every language model's tiny twin that BOTH checkouts have
(``tests/benchmark/*_tiny_manifest.json``) is built as the harness builds it
(``benchmark/sut.py::round_config``), its round program lowered on the CPU
from shapes alone, and the two ``.as_text()`` compared: one line a cell,
``equal`` or ``DIFFERS``, exit code 1 if any differs. On the CPU every core
takes its plain body, so this says nothing of a kernel's own body: PERF.md
§7 question 34. A checkout is lowered in a process of its own (``--write``),
with nothing of the other on its path.
"""
import argparse
import glob
import json
import os
import subprocess
import sys
import tempfile


def twins(root):
    """{manifest's file name: path} of the language models' tiny twins."""
    return {os.path.basename(p): p for p in sorted(glob.glob(
        os.path.join(root, "tests", "benchmark", "*_tiny_manifest.json")))}


def write(root, out, manifests):
    """``out/<cell>.txt``: the lowered round program of each cell of
    ``manifests`` (file names under ``root``'s ``tests/benchmark``)."""
    os.chdir(root)
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp

    from benchmark import run as bench, sut
    from fedtpu import models
    from fedtpu.core.round import init_state
    from fedtpu.data.device import make_data_round_step

    for manifest in (twins(root)[name] for name in manifests):
        with open(manifest) as fh:
            names = [w["name"] for w in json.load(fh)["workloads"]]
        for name in names:
            cell = bench.Cell(manifest, name)
            cfg = sut.round_config(cell.config, cell.traffic, cell.task)
            t, clients = cell.config["seq_len"], cell.traffic["clients"]
            rows = cell.config["rows_per_client"]
            model = models.create(cfg.model, num_classes=cfg.num_classes,
                                  remat=cfg.remat, **dict(cfg.model_args))
            state = jax.eval_shape(
                lambda key: init_state(model, cfg, key, jnp.zeros((1, t), jnp.int32)),
                jax.random.PRNGKey(0))
            step = jax.jit(make_data_round_step(
                model, cfg, cfg.steps_per_round, shuffle=False, image_shape=(t,),
                layout="gather"), donate_argnums=(0,))
            tokens = jax.ShapeDtypeStruct((clients * rows, t), jnp.int32)
            shapes = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
                (state, tokens, tokens, jnp.zeros((clients, rows), jnp.int32),
                 jnp.ones((clients, rows), bool), jnp.ones((clients,), jnp.float32),
                 jnp.ones((clients,), bool), jax.random.PRNGKey(0)))
            with open(os.path.join(out, name + ".txt"), "w") as fh:
                fh.write(step.lower(*shapes).as_text())


def compare(parent, change, out=print):
    """Lowers both checkouts' shared twins and compares cell by cell; the
    names of the cells that differ."""
    shared = sorted(set(twins(parent)) & set(twins(change)))
    differ = []
    with tempfile.TemporaryDirectory() as tmp:
        for side, root in (("parent", parent), ("change", change)):
            os.mkdir(os.path.join(tmp, side))
            subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--write",
                 os.path.join(tmp, side), root, *shared],
                check=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
        for name in sorted(os.listdir(os.path.join(tmp, "parent"))):
            texts = []
            for side in ("parent", "change"):
                with open(os.path.join(tmp, side, name)) as fh:
                    texts.append(fh.read())
            same = texts[0] == texts[1]
            out(f"{name[:-4]} {len(texts[0])} bytes "
                f"{'equal' if same else 'DIFFERS'}")
            if not same:
                differ.append(name[:-4])
    return differ


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("parent", help="a checkout's root")
    parser.add_argument("change", nargs="*", default=["."],
                        help="the other checkout's root (default: .)")
    parser.add_argument("--write", metavar="OUT", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.write:  # one checkout's side: OUT ROOT manifests...
        write(os.path.abspath(args.parent), os.path.abspath(args.write), args.change)
        return 0
    return 1 if compare(os.path.abspath(args.parent),
                        os.path.abspath(args.change[0])) else 0


if __name__ == "__main__":
    sys.exit(main())
