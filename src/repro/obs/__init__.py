"""Runtime telemetry: metrics registry + trace spans + quantile sketches
+ live export + SLOs + schema.

Disabled by default and near-free when disabled (one guard check per
instrumented call site).  Ways to turn it on:

* ``REPRO_TRACE=path.jsonl``  — enable metrics *and* export every span /
  event / metrics record as JSON lines to ``path`` (buffered; schema in
  :mod:`repro.obs.schema`);
* ``REPRO_METRICS=1``         — enable the in-process metrics registry
  only (``obs.snapshot()`` / ``obs.summary()``);
* ``REPRO_METRICS_PORT=9099`` — enable metrics *and* serve them live:
  Prometheus text at ``/metrics``, JSON at ``/snapshot``
  (:mod:`repro.obs.exporter`);
* ``REPRO_SNAPSHOT=path.json`` (``REPRO_SNAPSHOT_INTERVAL=5``) — enable
  metrics and write the JSON snapshot to a file every interval, for
  headless runs nothing can scrape;
* ``REPRO_SLO=latency<0.25@0.99,nfe<64`` — declarative per-request
  budgets scored at request completion (:mod:`repro.obs.slo`);
* ``obs.enable()``            — programmatic, e.g. from tests.

``REPRO_JAX_PROFILE=dir`` records one ``jax.profiler`` session for the
whole process into ``dir`` (written at exit): the device trace, with
every span above on the profiler's clock beside the device's operations.

Every serving-path record carries the request id minted at
``submit()``; ``obs.timeline(request_id)`` (optionally with a trace-file
path) reconstructs one request's full submit → admission → per-call →
completion history.  See the "Observability" section of ARCHITECTURE.md
for the metric-name table and which layer emits what.
"""
from __future__ import annotations

import os

from repro.obs import exporter, metrics, sketch, slo, tracing
from repro.obs.metrics import (counter, disable, enable, enabled, gauge,
                               histogram, reset, snapshot, suppressed)
from repro.obs.tracing import (event, flush_sink, set_sink, span,
                               summary, timeline, write_metrics_record)

__all__ = [
    "counter", "gauge", "histogram", "snapshot", "reset",
    "enable", "disable", "enabled", "suppressed",
    "span", "event", "summary", "set_sink", "flush_sink", "timeline",
    "write_metrics_record",
    "metrics", "tracing", "sketch", "exporter", "slo",
    "configure_from_env",
]


def configure_from_env() -> None:
    """Read REPRO_TRACE / REPRO_METRICS / exporter / SLO /
    REPRO_JAX_PROFILE env; idempotent."""
    trace = os.environ.get("REPRO_TRACE", "").strip()
    port = os.environ.get("REPRO_METRICS_PORT", "").strip()
    snap = os.environ.get("REPRO_SNAPSHOT", "").strip()
    if trace:
        enable()
        if tracing.sink_path() != trace:
            set_sink(trace)
    elif os.environ.get("REPRO_METRICS", "").strip() not in ("", "0"):
        enable()
    if port:
        enable()
        exporter.serve(int(port))
    if snap:
        enable()
        interval = float(
            os.environ.get("REPRO_SNAPSHOT_INTERVAL", "5") or 5)
        exporter.start_snapshot_writer(snap, interval)
    spec = os.environ.get("REPRO_SLO", "").strip()
    if spec and not slo.active():
        slo.configure(slo.parse(spec))
    profile = os.environ.get("REPRO_JAX_PROFILE", "").strip()
    if profile:
        tracing.start_profile(profile)


configure_from_env()
