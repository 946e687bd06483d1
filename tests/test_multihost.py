"""Real two-process ``jax.distributed`` smoke.

Spawns two subprocesses running ``examples/multihost_cpu.py`` — each pins a
4-virtual-device CPU platform, joins the cluster through
``fedtpu.parallel.multihost.initialize`` (the true multi-controller init
path, not a mock), builds one global 8-device mesh, and executes a full
sharded federated round whose FedAvg psum crosses the process boundary.
CPU stand-in for the reference's manual multi-machine launch
(``README.md:6-17``).
"""

import os
import socket
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_REPO, "examples", "multihost_cpu.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _launch(port: int, extra=()):
    env = dict(os.environ)
    # The child pins its own platform/device count; scrub ours so the
    # conftest's 8-device flag doesn't leak in.
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, _SCRIPT, "--process-id", str(i), "--port", str(port)]
            + list(extra),
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            try:
                # 480 s: the --all spawn runs jax import + gloo bring-up +
                # THREE legs, and this 1-core host runs ~2x slower when a
                # heavy job shares it. A timeout feeds the rc!=0 retry path
                # instead of escaping as a raw TimeoutExpired.
                out, err = p.communicate(timeout=480)
                outs.append((p.returncode, out, err))
            except subprocess.TimeoutExpired:
                p.kill()
                out, err = p.communicate()
                outs.append((124, out or "", (err or "") + "\n[timeout 480s]"))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs


def _run_and_check(markers, agree_keys, extra=()):
    """Launch both controllers, assert success + every ``markers`` entry
    (a list) in each output, and assert both agree on every ``agree_keys``
    (a list) tagged value (same psum result / same sampling masks). The
    free-port probe is inherently racy (the socket closes before the
    coordinator binds it), so a failed attempt retries once on a new
    port."""
    for attempt in range(2):
        outs = _launch(_free_port(), extra=extra)
        if all(rc == 0 for rc, _, _ in outs) or attempt == 1:
            break
    for rc, out, err in outs:
        assert rc == 0, f"child failed (rc={rc}):\n{out}\n{err}"
        for marker in markers:
            assert marker in out, out
    for key in agree_keys:
        agreed = {line.split(key)[1] for rc, out, _ in outs
                  for line in out.splitlines() if key in line}
        assert len(agreed) == 1, (key, agreed)
    return outs


def test_two_process_all_legs():
    """ONE two-process jax.distributed spawn covering the three legs (each
    spawn costs ~20 s of jax import + gloo bring-up per process on this
    1-core host, so they share one cluster):

    1. Raw sharded round: mesh spanning both processes, cross-process psum
       FedAvg; both controllers agree on the aggregate loss.
    2. The high-level Federation engine: sharded per-client state,
       on-device gather, converging loss, then the fused multi-round scan
       (run_on_device) — controllers agree on every round's aggregate and
       the fused stack ("losses=" covers both lists).
    3. Loss-proportional participation sampling (round-5: previously
       rejected as single-controller-only): each process allgathers the
       sharded per-client loss vector, so the round-seeded draw yields the
       SAME mask on both hosts ("masks=" lists four consecutive rounds).
    """
    outs = _run_and_check(
        ["multihost ok", "multihost engine ok", "multihost loss-sampling ok"],
        ["loss=", "losses=", "masks="],
        extra=["--all"],
    )
    for _, out, _ in outs:
        assert "8 global devices" in out, out
