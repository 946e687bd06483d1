#!/usr/bin/env python
"""Span-, scope- and metric-name drift check: everything emitted must be
documented.

Scans ``fedtpu/`` for literal span names passed to ``*.span("name", ...)``
or ``*.phase("name", ...)`` (a set-up phase is a span plus a gauge),
literal ``jax.named_scope("name")`` scopes of the round program, literal
``TraceAnnotation("name")`` / ``StepTraceAnnotation("name")`` annotations
and literal metric names passed to
``.counter/.gauge/.histogram/.setup_gauge(...)``, and
verifies each appears as inline code (`` `name` ``) in
``docs/OBSERVABILITY.md``. Catches the silent failure mode where a new
subsystem adds spans or ``fedtpu_*`` metrics (or renames one) and the
operator-facing model drifts out of date — dashboards, alerts and trace
queries then filter on names that no longer exist.

The trace vocabulary has one rule on top: every scope and annotation, and
every span that is not one of the coordinator's
(``gap_analyze.SERVER_SPANS``, which the scan holds equal to what is
emitted), starts with ``fed.``, so that a reduction finds it under whatever
transformation wraps it; and none is named like the benchmark harness's
own spans (``dispatch``, ``sync``, ``record.read``).

Tier-1 runnable: ``tests/test_obs_propagation.py`` calls :func:`check`;
standalone: ``python tools/span_check.py`` (exit 1 + a list on drift).
Stdlib only.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Set

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Literal first argument of a .span( or .phase( call. Variables/f-strings
# never match — fedtpu's span names are deliberately all literal
# (greppability is the point of a fixed span vocabulary).
_SPAN_CALL = re.compile(
    r"""\.(?:span|phase)\(\s*(['"])([A-Za-z0-9_.:-]+)\1"""
)
# Literal argument of a named_scope( call or decorator: a layer of the round
# program, written into the compiled module's op_name metadata.
_SCOPE_CALL = re.compile(r"""named_scope\(\s*(['"])([A-Za-z0-9_.:-]+)\1""")
# Literal first argument of a direct profiler annotation.
_ANNOTATION_CALL = re.compile(
    r"""\b(?:Step)?TraceAnnotation\(\s*(['"])([A-Za-z0-9_.:-]+)\1"""
)
TRACE_PREFIX = "fed."
HARNESS_SPANS = ("dispatch", "sync", "record.read")  # benchmark/run.py's
# Literal first argument of a .counter(/.gauge(/.histogram(/.setup_gauge(
# call on the telemetry facade or registry. Only the framework namespace
# is policed: ad-hoc test instruments don't start with fedtpu_.
_METRIC_CALL = re.compile(
    r"""\.(?:counter|gauge|histogram|setup_gauge)\(\s*"""
    r"""(['"])(fedtpu_[A-Za-z0-9_]+)\1"""
)
_INLINE_CODE = re.compile(r"`([^`]+)`")


def _emitted(pattern, package_dir: str = None) -> Dict[str, List[str]]:
    """{name: [relative file paths emitting it]} for one call pattern over
    fedtpu/."""
    package_dir = package_dir or os.path.join(REPO, "fedtpu")
    found: Dict[str, List[str]] = {}
    for dirpath, _dirnames, filenames in os.walk(package_dir):
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path, encoding="utf-8") as fh:
                text = fh.read()
            for m in pattern.finditer(text):
                rel = os.path.relpath(path, REPO)
                found.setdefault(m.group(2), []).append(rel)
    return found


def emitted_span_names(package_dir: str = None) -> Dict[str, List[str]]:
    return _emitted(_SPAN_CALL, package_dir)


def emitted_scope_names(package_dir: str = None) -> Dict[str, List[str]]:
    return _emitted(_SCOPE_CALL, package_dir)


def emitted_annotation_names(package_dir: str = None) -> Dict[str, List[str]]:
    return _emitted(_ANNOTATION_CALL, package_dir)


def emitted_metric_names(package_dir: str = None) -> Dict[str, List[str]]:
    return _emitted(_METRIC_CALL, package_dir)


def documented_names(doc_path: str = None) -> Set[str]:
    """Every inline-code token in OBSERVABILITY.md (the span table uses
    `` `name` `` markup; matching the whole doc keeps the check insensitive
    to table layout)."""
    doc_path = doc_path or os.path.join(REPO, "docs", "OBSERVABILITY.md")
    with open(doc_path, encoding="utf-8") as fh:
        text = fh.read()
    # Drop fenced code blocks first: their ``` markers desynchronize naive
    # single-backtick pairing over the rest of the document.
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    names: Set[str] = set()
    for m in _INLINE_CODE.finditer(text):
        # A cell like `round` / `fused_rounds` documents both tokens.
        for tok in re.split(r"[\s/|,]+", m.group(1)):
            if tok:
                tok = tok.strip()
                names.add(tok)
                # `fedtpu_foo{label="x"}` documents the base metric name.
                names.add(tok.split("{")[0])
    return names


def check(package_dir: str = None, doc_path: str = None) -> List[str]:
    """Problem strings (empty = pass)."""
    emitted = emitted_span_names(package_dir)
    documented = documented_names(doc_path)
    problems = []
    if not emitted and package_dir is None:
        problems.append("scanner found NO span calls in fedtpu/ — the "
                        "regex or layout drifted; fix tools/span_check.py")
    for name in sorted(emitted):
        if name not in documented:
            problems.append(
                f"span {name!r} (emitted in {', '.join(emitted[name])}) has "
                "no entry in docs/OBSERVABILITY.md"
            )
    problems.extend(check_trace_vocabulary(package_dir, doc_path))
    problems.extend(check_metrics(package_dir, doc_path))
    if package_dir is None:
        problems.extend(check_chaos_kinds())
    return problems


def check_trace_vocabulary(
    package_dir: str = None, doc_path: str = None
) -> List[str]:
    """Problems with the names a profiler capture is reduced by (empty =
    pass): see the module docstring's rule."""
    import gap_analyze

    spans = emitted_span_names(package_dir)
    scopes = emitted_scope_names(package_dir)
    annotations = emitted_annotation_names(package_dir)
    documented = documented_names(doc_path)
    problems = []
    if not scopes and package_dir is None:
        problems.append("scanner found NO named_scope calls in fedtpu/ — "
                        "the regex or layout drifted; fix tools/span_check.py")
    for kind, found in (("scope", scopes), ("annotation", annotations)):
        for name in sorted(found):
            where = ", ".join(found[name])
            if not name.startswith(TRACE_PREFIX):
                problems.append(
                    f"{kind} {name!r} ({where}) does not start with "
                    f"{TRACE_PREFIX!r}"
                )
            if name not in documented:
                problems.append(
                    f"{kind} {name!r} ({where}) has no entry in "
                    "docs/OBSERVABILITY.md"
                )
    for name in sorted(spans):
        if not name.startswith(TRACE_PREFIX) and (
            name not in gap_analyze.SERVER_SPANS
        ):
            problems.append(
                f"span {name!r} ({', '.join(spans[name])}) neither starts "
                f"with {TRACE_PREFIX!r} nor is in gap_analyze.SERVER_SPANS: "
                "a capture's reduction would not keep it"
            )
    if package_dir is None:
        for name in sorted(gap_analyze.SERVER_SPANS - set(spans)):
            problems.append(
                f"gap_analyze.SERVER_SPANS lists {name!r}, which nothing in "
                "fedtpu/ emits any more"
            )
    for name in HARNESS_SPANS:
        for kind, found in (("span", spans), ("scope", scopes),
                            ("annotation", annotations)):
            if name in found:
                problems.append(
                    f"{kind} {name!r} ({', '.join(found[name])}) is the "
                    "benchmark harness's own span name"
                )
    return problems


def check_metrics(package_dir: str = None, doc_path: str = None) -> List[str]:
    """Metric-name drift problems (empty = pass)."""
    emitted = emitted_metric_names(package_dir)
    documented = documented_names(doc_path)
    problems = []
    # Scanner-drift guard only for the real tree: a synthetic package_dir
    # may legitimately emit spans but no metrics.
    if not emitted and package_dir is None:
        problems.append("scanner found NO fedtpu_* metric calls in fedtpu/ "
                        "— the regex or layout drifted; fix "
                        "tools/span_check.py")
    for name in sorted(emitted):
        if name not in documented:
            problems.append(
                f"metric {name!r} (emitted in {', '.join(emitted[name])}) "
                "has no entry in docs/OBSERVABILITY.md"
            )
    return problems


def check_chaos_kinds(doc_path: str = None) -> List[str]:
    """Chaos fault-kind drift problems (empty = pass): every kind name in
    ``fedtpu.ft.chaos.KINDS`` must appear as inline code in
    docs/FAULT_TOLERANCE.md's DSL grammar — a new fault class
    (``NET_KINDS`` and whatever follows) cannot ship undocumented.
    chaos.py is loaded standalone (importlib, stdlib-only module) so this
    check never drags jax into a docs-lint environment."""
    import importlib.util

    doc_path = doc_path or os.path.join(REPO, "docs", "FAULT_TOLERANCE.md")
    chaos_path = os.path.join(REPO, "fedtpu", "ft", "chaos.py")
    spec = importlib.util.spec_from_file_location("_span_check_chaos",
                                                  chaos_path)
    chaos = importlib.util.module_from_spec(spec)
    # Registered for the exec: dataclass processing resolves the module's
    # (string) annotations through sys.modules.
    sys.modules[spec.name] = chaos
    try:
        spec.loader.exec_module(chaos)
        kinds = tuple(chaos.KINDS)
    finally:
        sys.modules.pop(spec.name, None)
    documented = documented_names(doc_path)
    problems = []
    if not kinds:
        problems.append("fedtpu.ft.chaos.KINDS is empty — the kind registry "
                        "or loader drifted; fix tools/span_check.py")
    for kind in sorted(kinds):
        if kind not in documented:
            problems.append(
                f"chaos fault kind {kind!r} (fedtpu/ft/chaos.py KINDS) has "
                "no entry in docs/FAULT_TOLERANCE.md"
            )
    return problems


def main(argv=None) -> int:
    problems = check()
    if problems:
        for problem in problems:
            print(f"SPAN DRIFT: {problem}", file=sys.stderr)
        return 1
    spans, scopes = emitted_span_names(), emitted_scope_names()
    m = len(emitted_metric_names())
    print(f"ok: {len(spans)} span names + {len(scopes)} scope names + {m} "
          "metric names emitted + chaos kinds, all documented")
    print("spans: " + " ".join(sorted(spans)))
    print("scopes: " + " ".join(sorted(scopes)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
