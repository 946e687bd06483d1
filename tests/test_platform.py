"""Process entry and device selection: the compile-cache rule and the chip
smoke's refusal to run anywhere but on a TPU.

The smoke itself can only pass on the chip (``python chip_smoke.py`` through
the chip tool); what the CPU suite pins is that it FAILS here, fast, and
that the cache lands where the rule says.
"""

import os
import subprocess
import sys
import time

import jax
import pytest

from fedtpu.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cache_config():
    """Restore jax's cache-dir config whatever the test did to it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_placed_from_outside_is_untouched(monkeypatch, cache_config):
    """JAX_COMPILATION_CACHE_DIR set -> fedtpu leaves the config alone,
    even on an accelerator backend."""
    jax.config.update("jax_compilation_cache_dir", "/placed/by/the/env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/the/env")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert platform.enable_compile_cache() == "/placed/by/the/env"
    assert jax.config.jax_compilation_cache_dir == "/placed/by/the/env"


def test_cache_dir_default_is_checkout_local(monkeypatch, cache_config):
    """Unset -> <checkout>/.jax_cache on an accelerator, nothing on CPU."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    assert platform.enable_compile_cache() is None  # the suite runs on CPU
    assert jax.config.jax_compilation_cache_dir is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    want = os.path.join(REPO, ".jax_cache")
    assert platform.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_cache_dir_is_the_same_from_any_working_directory(tmp_path):
    """The path is part of jax's cache key: two fresh processes started in
    different directories must resolve the identical one."""
    probe = (
        "import sys; sys.path.insert(0, sys.argv[1]); "
        "from fedtpu.utils.platform import DEFAULT_COMPILE_CACHE as d; print(d)"
    )
    seen = {
        subprocess.run(
            [sys.executable, "-c", probe, REPO],
            cwd=cwd, capture_output=True, text=True, check=True, timeout=60,
        ).stdout.strip()
        for cwd in (str(tmp_path), REPO)
    }
    assert seen == {os.path.join(REPO, ".jax_cache")}


def test_chip_smoke_refuses_the_cpu_quickly():
    """``JAX_PLATFORMS=cpu python chip_smoke.py``: non-zero exit within
    seconds, the reason on stderr, no verdict on stdout, no model built."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 20
    assert "not 'tpu'" in proc.stderr
    assert proc.stdout == ""
