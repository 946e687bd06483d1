"""Test configuration: force an 8-device virtual CPU platform.

This is the standard JAX trick for testing pjit/shard_map/psum multi-device
code without TPU hardware (SURVEY.md §4): must run before jax initialises.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# JAX_PLATFORMS=cpu alone is honoured by this installation; the config
# update also wins over an environment that pins another platform, so the
# suite can never take the chip.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


# tests/benchmark/test_joyai_cell.py::test_the_cell_is_the_issues asserts, in
# one of its eight lines, that PR 34's cell is the LAST entry of
# BENCHMARK.json's workloads. PR 36 adds a cell, and each way to keep that
# line true is closed to a PR that is not a `benchmark` PR: the PR contract
# has new entries "at the end of their lists: one put first or in the middle
# reads as a change to what was there" (so REVIEW.md's "insert it ahead of
# the JoyAI cell" was left out, as the contract says to), and it has the PR
# "edit and delete no file the benchmark already has" (`tests/benchmark` is
# one of BENCHMARK.json's `paths`). So that one line fails until a
# `benchmark` PR rewrites it as "is in the manifest" (ROADMAP Reach B0,
# PERF.md section 7, question 22), and that PR deletes this hook: the mark is
# strict and takes an AssertionError only, so the test passing again, or
# raising anything else, fails the suite. An expected failure cannot tell
# WHICH line failed, so its other seven lines (the cell's traffic and size,
# the one four-chip cell, its four metrics, no tail) are asserted, word for
# word, by
# tests/benchmark/test_qwen3_next_cell.py::test_the_earlier_language_cell_is_as_it_was.
#
# PR 48 meets the same with PR 44's cell: test_laguna_cell.py's test of the
# same name asserts, in four of its lines, that Laguna's entries are the
# manifest's last (`workloads[-1]`, `configs[-1]`, `per_layer[-8:]`) and that
# the traffic file `fl4_seq8k` has two cells; PR 48 appends a configuration,
# a cell of that traffic file and seven metrics. Its other lines are asserted,
# word for word, by
# tests/benchmark/test_nemotron_cell.py::test_the_earlier_cell_of_this_traffic_is_as_it_was.
#
# PR 51 meets it a third time with PR 48's cell: test_nemotron_cell.py's test
# of the same name asserts, in ONE of its lines, that the traffic file
# `fl4_seq8k` has three cells of which Nemotron's is the last; PR 51 appends a
# fourth. Its other lines are asserted, word for word, by
# tests/benchmark/test_granite_cell.py::test_the_earlier_cell_of_this_traffic_is_as_it_was.
_ASSERTS_IT_IS_LAST = ("test_joyai_cell.py::test_the_cell_is_the_issues",
                       "test_laguna_cell.py::test_the_cell_is_the_issues",
                       "test_nemotron_cell.py::test_the_cell_is_the_issues")


# tests/test_tpu_compile.py holds the suite's longest single test (a whole
# round program compiled for a described chip, about 150 s) and, by the
# on-chip-measurement guide, has to stay ONE file. Under `--dist loadfile` a
# file goes to the next free worker in collection order, and this one sorts
# near the end: run last it would be the run's tail, run first it is hidden
# behind everything else. Every worker collects the same order.
_RUNS_FIRST = "tests/test_tpu_compile.py"


def pytest_collection_modifyitems(items):
    items.sort(key=lambda item: not item.nodeid.startswith(_RUNS_FIRST))
    for item in items:
        if item.nodeid.endswith(_ASSERTS_IT_IS_LAST):
            item.add_marker(pytest.mark.xfail(
                reason="asserts its cell is the manifest's last; a later PR appended one",
                raises=AssertionError, strict=True))
