"""Platform/env plumbing shared by every process entry point: the virtual
device-count flag, the persistent compile cache rule, and the one-line
device report a process prints before it builds anything.
"""

from __future__ import annotations

import logging
import os
import re
from typing import MutableMapping, Optional

# <checkout>/.jax_cache, resolved from this file's own location so two
# processes started from different working directories agree on it (the
# path is part of jax's cache key: a directory that moves never hits).
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> Optional[str]:
    """The one rule for jax's persistent compilation cache; returns the
    directory in use, or None when caching stays off.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    this touches nothing — the cache is placed from outside. Where it is
    not, an accelerator backend caches under ``<checkout>/.jax_cache``
    (git-ignored) and the CPU backend does not cache at all: caching pays
    on accelerators (round programs take tens of seconds to compile), and
    XLA:CPU reloads of cached executables warn about host machine-feature
    mismatches.

    jax decides ONCE per process, at its first compile, whether the cache
    is in use — so every entry that compiles (the CLIs, the engines, the
    gRPC trainer and server, bench.py, chip_smoke.py) calls this before
    building anything. Initialises the backend."""
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE


def log_devices() -> None:
    """One INFO line — backend, device kind, device count, compile cache —
    so a run that is not on the chip says so before its first round."""
    import jax

    devices = jax.devices()
    logging.getLogger("fedtpu").info(
        "jax backend=%s device_kind=%s devices=%d compile_cache=%s",
        jax.default_backend(), devices[0].device_kind, len(devices),
        jax.config.jax_compilation_cache_dir,
    )


def force_host_device_count(
    n: int, env: Optional[MutableMapping[str, str]] = None
) -> None:
    """Set ``--xla_force_host_platform_device_count=n`` in ``env`` (default:
    ``os.environ``), replacing any existing occurrence. Must run before jax
    initialises its backends to have any effect."""
    if env is None:
        env = os.environ
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+",
        "",
        env.get("XLA_FLAGS", ""),
    )
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={int(n)}"
    ).strip()
