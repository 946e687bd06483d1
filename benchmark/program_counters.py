"""What the program counted about itself: the gauges of its process-global
metrics registry (``fedtpu.obs.registry.get_global_registry``), which outlive
the engine that set them (a run frees its engine before the result line is
made). The one file of the per-layer readers that imports the program's
registry; a program that has no such registry, or that recorded nothing
under a name, reads as ``None`` and the metric is left out of the line.
"""

from __future__ import annotations

SETUP_SECONDS = "fedtpu_setup_seconds"  # {phase}: a span fed.setup.<phase>


def rows(name):
    """``[(labels, value)]`` of every series the program holds under
    ``name``; empty where it holds none."""
    try:
        from fedtpu.obs.registry import get_global_registry
    except ImportError:
        return []
    return [(row["labels"], float(row["value"]))
            for row in get_global_registry().snapshot().get(name, [])
            if "value" in row]


def value(name, **labels):
    """The series of ``name`` with exactly these labels, or ``None``."""
    for have, v in rows(name):
        if have == labels:
            return v
    return None


def setup_seconds(phase):
    """Host wall of the set-up phase ``fed.setup.<phase>``, summed over its
    occurrences in the newest engine's set-up, or ``None``."""
    return value(SETUP_SECONDS, phase=phase)


def setup_seconds_ending(*suffixes):
    """The sum over every phase whose name ends in one of ``suffixes`` (what
    jax reported inside the phases that can compile), or ``None``."""
    found = [v for labels, v in rows(SETUP_SECONDS)
             if labels.get("phase", "").endswith(suffixes)]
    return sum(found) if found else None
