"""Shared helpers: JSON-line salvage + versioned round-record parsing.

The capture wrappers that run a measurement in a child process
(``tools/run_accfull_tpu.py``, ``tools/bench_model_tpu.py``) read the
child's result as the last parseable JSON line of its stdout — progress
lines precede it.

Round records (the ``--metrics`` JSONL the CLIs write through
``fedtpu.obs.RoundRecordWriter``) are schema-versioned since PR 3:
:func:`round_records` normalises a stream of them — legacy unversioned
lines get ``schema_version: 0``, lines from a NEWER schema than this
checkout understands are surfaced, not silently misread.
"""

import json

# The round-record schema this checkout's tools understand. Mirrors
# fedtpu.obs.exporters.SCHEMA_VERSION without importing fedtpu (the tools
# must run standalone); tests/test_obs_exporters.py pins the two equal.
ROUND_RECORD_SCHEMA_VERSION = 1


def last_json_line(text):
    """Last line of ``text`` that parses as a JSON object, or ``None``."""
    for line in reversed((text or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return None


def round_records(text, max_schema=ROUND_RECORD_SCHEMA_VERSION):
    """Parse round records out of a JSONL blob.

    Returns ``(records, skipped)``: every parseable JSON-object line that
    looks like a round record (has a ``step``), with missing
    ``schema_version`` normalised to 0, in file order — plus the count of
    lines skipped for being unparseable OR carrying a schema newer than
    ``max_schema`` (a newer writer's keys cannot be trusted to mean what
    this checkout thinks they mean).
    """
    records, skipped = [], 0
    for line in (text or "").strip().splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            skipped += 1
            continue
        if not isinstance(rec, dict) or "step" not in rec:
            continue
        rec.setdefault("schema_version", 0)
        if rec["schema_version"] > max_schema:
            skipped += 1
            continue
        records.append(rec)
    return records, skipped


def last_round_record(text, max_schema=ROUND_RECORD_SCHEMA_VERSION):
    """Newest understood round record in ``text``, or ``None``."""
    records, _ = round_records(text, max_schema=max_schema)
    return records[-1] if records else None
