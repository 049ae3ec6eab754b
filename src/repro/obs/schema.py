"""Documented telemetry schemas + zero-dependency validator.

**REPRO_TRACE JSON-lines** — one object per line, three kinds::

    {"kind": "span",    "name": str, "ts": float, "span_id": int,
     "parent_id": int|null, "dur_s": float, "attrs": {...}}
    {"kind": "event",   "name": str, "ts": float, "span_id": int,
     "parent_id": int|null, "attrs": {...}}
    {"kind": "metrics", "ts": float, "span_id": int, "parent_id": null,
     "attrs": {}, "metrics": {<metric>: {"type": str, "help": str,
                                         "series": [{"labels": {...},
                                                     "value": any}]}}}

CLI::

    PYTHONPATH=src python -m repro.obs.schema trace.jsonl

validates every trace line and the content a traced DNDM run with a
scheduler drain carries: an ``engine.generate`` span with
nfe/backend/jit-cache attrs, per-step ``sampler.step`` events carrying
|R_t| (``reveal``), and a ``metrics`` record with scheduler occupancy.
"""
from __future__ import annotations

import json
import sys
from typing import Iterable

_SPAN_KINDS = ("span", "event", "metrics")


class SchemaError(ValueError):
    pass


def _check(ok: bool, path: str, msg: str) -> None:
    if not ok:
        raise SchemaError(f"{path}: {msg}")


def _typed(obj: dict, path: str, key: str, types) -> object:
    _check(key in obj, path, f"missing key {key!r}")
    v = obj[key]
    _check(isinstance(v, types), path,
           f"{key!r} is {type(v).__name__}, want {types}")
    return v


def _number(obj, path, key, minimum=None):
    v = _typed(obj, path, key, (int, float))
    _check(not isinstance(v, bool), path, f"{key!r} is bool, want number")
    if minimum is not None:
        _check(v >= minimum, path, f"{key!r}={v} < {minimum}")
    return v


def validate_metrics_snapshot(snap: dict, path: str = "metrics") -> None:
    _check(isinstance(snap, dict), path, "snapshot must be an object")
    for name, inst in snap.items():
        p = f"{path}.{name}"
        _typed(inst, p, "type", str)
        _typed(inst, p, "help", str)
        series = _typed(inst, p, "series", list)
        for i, s in enumerate(series):
            sp = f"{p}.series[{i}]"
            _check(isinstance(s, dict), p, f"series[{i}] must be an object")
            _typed(s, sp, "labels", dict)
            _check("value" in s, sp, "missing 'value'")
            if inst["type"] == "histogram":
                # quantiles are first-class: every histogram series
                # carries sketch-backed p50/p95/p99 plus the serialized
                # sketch itself (repro.obs.sketch) for arbitrary q
                v = _typed(s, sp, "value", dict)
                for q in ("p50", "p95", "p99"):
                    _number(v, f"{sp}.value", q, minimum=0.0)
                sk = _typed(v, f"{sp}.value", "sketch", dict)
                _number(sk, f"{sp}.value.sketch", "alpha", minimum=0.0)
                _number(sk, f"{sp}.value.sketch", "count", minimum=0)
                _typed(sk, f"{sp}.value.sketch", "bins", dict)


def validate_trace_lines(lines: Iterable[str]) -> list[dict]:
    """Structural check of a JSON-lines trace; returns parsed records."""
    out: list[dict] = []
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        p = f"trace:{i + 1}"
        try:
            rec = json.loads(line)
        except json.JSONDecodeError as e:
            raise SchemaError(f"{p}: not JSON ({e})") from None
        _check(isinstance(rec, dict), p, "line must be an object")
        kind = _typed(rec, p, "kind", str)
        _check(kind in _SPAN_KINDS, p, f"kind={kind!r}")
        _number(rec, p, "ts", minimum=0.0)
        _number(rec, p, "span_id", minimum=1)
        _check("parent_id" in rec, p, "missing 'parent_id'")
        _check(rec["parent_id"] is None
               or isinstance(rec["parent_id"], int), p,
               "parent_id must be int or null")
        _typed(rec, p, "attrs", dict)
        if kind in ("span", "event"):
            _typed(rec, p, "name", str)
        if kind == "span":
            _number(rec, p, "dur_s", minimum=0.0)
        if kind == "metrics":
            validate_metrics_snapshot(_typed(rec, p, "metrics", dict), p)
        out.append(rec)
    return out


def validate_trace_content(records: list[dict]) -> None:
    """Acceptance-level content checks for a full DNDM benchmark trace."""
    p = "trace"
    gen = [r for r in records
           if r["kind"] == "span" and r["name"] == "engine.generate"]
    _check(len(gen) > 0, p, "no engine.generate span")
    _check(any({"nfe", "backend", "cache"} <= set(r["attrs"]) for r in gen),
           p, "no engine.generate span with nfe/backend/cache attrs")
    steps = [r for r in records
             if r["kind"] == "event" and r["name"] == "sampler.step"]
    _check(any("reveal" in r["attrs"] for r in steps),
           p, "no sampler.step event with a per-step reveal count (|R_t|)")
    mets = [r for r in records if r["kind"] == "metrics"]
    _check(len(mets) > 0, p, "no metrics record")
    final = mets[-1]["metrics"]
    for required in ("engine.jit_cache.misses", "scheduler.occupancy",
                     "decode.backend_calls"):
        _check(required in final, p,
               f"final metrics record lacks {required!r}")


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print("usage: python -m repro.obs.schema trace.jsonl",
              file=sys.stderr)
        return 2
    try:
        with open(argv[0]) as f:
            records = validate_trace_lines(f)
        validate_trace_content(records)
        spans = sum(r["kind"] == "span" for r in records)
        events = sum(r["kind"] == "event" for r in records)
        print(f"ok: {argv[0]} valid ({spans} spans, {events} events, "
              f"{len(records)} records)")
    except (OSError, SchemaError) as e:
        print(f"schema validation FAILED: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
