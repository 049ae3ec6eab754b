"""Telemetry layer: metric semantics, span nesting/export schema,
per-step reveal-count correctness, the disabled-path overhead guard, and
the engine/scheduler integration (host warm-up split, amortized wall)."""
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.samplers import loop
from repro.obs import schema
from repro.models import Model, ModelConfig
from repro.serving import BatchScheduler, EngineConfig, GenerationEngine

VOCAB, SEQ, STEPS = 12, 8, 4


@pytest.fixture()
def telemetry():
    """Enable obs for one test; always restore the disabled default."""
    obs.metrics.reset()
    obs.tracing.clear()
    obs.enable()
    yield
    obs.metrics.reset()
    obs.tracing.clear()
    obs.disable()


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="obs", arch_type="dense", n_layers=1,
                      d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                      vocab_size=VOCAB, block_pattern=("attn",),
                      bidirectional=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _engine(tiny, method="dndm"):
    model, params = tiny
    return GenerationEngine(model, params, EngineConfig(
        method=method, steps=STEPS, nfe_budget=2))


# ------------------------------------------------------------------
# metrics registry
# ------------------------------------------------------------------

def test_counter_gauge_histogram_semantics(telemetry):
    c = obs.counter("t.count", "help text")
    c.inc(a="x")
    c.inc(2, a="x")
    c.inc(5, a="y")
    assert c.value(a="x") == 3
    assert c.value(a="y") == 5
    assert c.value(a="unseen") == 0

    g = obs.gauge("t.gauge")
    g.set(1.5, k="v")
    g.set(2.5, k="v")                       # overwrites
    assert g.value(k="v") == 2.5

    h = obs.histogram("t.hist")
    for v in (0.1, 0.2, 0.3):
        h.observe(v, op="f")
    s = h.value(op="f")
    assert s["count"] == 3
    assert s["min"] == pytest.approx(0.1)
    assert s["max"] == pytest.approx(0.3)
    assert s["sum"] == pytest.approx(0.6)

    snap = obs.snapshot()
    assert snap["t.count"]["type"] == "counter"
    assert snap["t.count"]["help"] == "help text"
    series = {tuple(s["labels"].items()): s["value"]
              for s in snap["t.count"]["series"]}
    assert series[(("a", "x"),)] == 3
    assert snap["t.hist"]["series"][0]["value"]["mean"] == pytest.approx(0.2)
    # same name, different type -> error
    with pytest.raises(TypeError):
        obs.gauge("t.count")


def test_reset_clears_values_not_instruments(telemetry):
    c = obs.counter("t.reset")
    c.inc(7)
    obs.metrics.reset()
    assert c.value() == 0
    assert obs.counter("t.reset") is c


# ------------------------------------------------------------------
# tracing
# ------------------------------------------------------------------

def test_span_nesting_and_export_schema(telemetry, tmp_path):
    path = tmp_path / "trace.jsonl"
    obs.set_sink(str(path))
    with obs.span("outer", method="dndm") as sp:
        obs.event("tick", i=0, t=np.int32(3))   # numpy scalar coerced
        with obs.span("inner"):
            pass
        sp.set(nfe=4)
    obs.write_metrics_record()
    obs.tracing.close_sink()

    recs = schema.validate_trace_lines(path.read_text().splitlines())
    by_name = {r.get("name"): r for r in recs}
    outer, inner, tick = by_name["outer"], by_name["inner"], by_name["tick"]
    # children point at the enclosing span; the root has no parent
    assert outer["parent_id"] is None
    assert inner["parent_id"] == outer["span_id"]
    assert tick["parent_id"] == outer["span_id"]
    assert tick["attrs"] == {"i": 0, "t": 3}
    # attrs set mid-span are exported; spans carry durations
    assert outer["attrs"] == {"method": "dndm", "nfe": 4}
    assert outer["dur_s"] >= inner["dur_s"] >= 0.0
    assert recs[-1]["kind"] == "metrics"


def test_null_span_when_disabled():
    assert not obs.enabled()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    sp = obs.span("nope", a=1)
    assert sp is obs.tracing.NULL_SPAN
    with sp as s:
        s.set(b=2)                              # no-op, no error
    obs.event("nope")
    assert obs.tracing.records() == []


# ------------------------------------------------------------------
# per-step reveal counts (|R_t|)
# ------------------------------------------------------------------

def test_reveal_series_hand_computed():
    # tau = [3, 1, 3, 2]; unique descending times = [3, 2, 1]
    tau = np.array([[3, 1, 3, 2]])
    times = np.array([3, 2, 1])
    # Algorithm 1 reveals #(tau == t) per step
    assert loop.reveal_series(tau, times, version=1).tolist() == [2, 1, 1]
    # Algorithm 3 re-updates everything already revealed (tau >= t)
    assert loop.reveal_series(tau, times, version=2).tolist() == [2, 3, 4]
    # batch mean: second row reveals all 4 tokens at t=3
    tau2 = np.array([[3, 1, 3, 2], [3, 3, 3, 3]])
    assert loop.reveal_series(tau2, times, version=1).tolist() == [3, 0.5, 0.5]


def test_dndm_generate_records_reveal_series(telemetry, tiny, key):
    eng = _engine(tiny, "dndm")
    out, _ = eng.generate(key, 2, SEQ)
    reveals = out.aux["reveal_counts"]
    # every token is revealed exactly once across the walk
    assert float(np.sum(reveals)) == pytest.approx(SEQ)
    # the series matches a hand recomputation from the returned tau set
    tau = np.asarray(jax.device_get(out.aux["tau"]))
    expect = loop.reveal_series(tau, out.aux["times"], version=1)
    np.testing.assert_allclose(reveals, expect)
    # ... and is exported per step as sampler.step events under the
    # engine.generate span
    recs = obs.tracing.records()
    gen = [r for r in recs if r["kind"] == "span"
           and r["name"] == "engine.generate"]
    steps = [r for r in recs if r["kind"] == "event"
             and r["name"] == "sampler.step"]
    assert gen and gen[0]["attrs"]["nfe"] == out.nfe
    assert gen[0]["attrs"]["cache"] == "miss"
    assert gen[0]["attrs"]["backend"] in ("pallas", "interpret", "reference")
    step_reveals = [r["attrs"]["reveal"] for r in steps]
    # the untimed jit warm-up run is obs-suppressed, so the series shows
    # up exactly once — not doubled by the cache-miss warm-up replay
    assert step_reveals == list(map(float, expect))


# ------------------------------------------------------------------
# engine: jit-cache counters + host warm-up split
# ------------------------------------------------------------------

def test_host_warmup_split(telemetry, tiny, key):
    """First host-sampler call per key warms the per-step jit caches
    untimed; wall_seconds is steady-state and the warm-up surplus is
    reported as compile_seconds (0.0 once warm)."""
    eng = _engine(tiny, "dndm")
    out, wall = eng.generate(key, 2, SEQ)
    assert out.aux["compile_seconds"] >= 0.0
    assert obs.counter("engine.jit_cache.misses").value(
        method="dndm", kind="host") == 1
    out2, wall2 = eng.generate(key, 2, SEQ)
    assert out2.aux["compile_seconds"] == 0.0
    assert obs.counter("engine.jit_cache.hits").value(
        method="dndm", kind="host") == 1
    # warm-up reruns the same PRNG key: outputs identical
    assert (np.asarray(out.tokens) == np.asarray(out2.tokens)).all()
    assert wall >= 0 and wall2 >= 0


def test_suppressed_silences_without_flipping_global(telemetry):
    """obs.suppressed(): instruments and events are silenced inside the
    context (enabled() reads False), the global on-state is untouched,
    and nesting unwinds correctly."""
    c = obs.counter("suppress.probe")
    c.inc()
    with obs.suppressed():
        assert not obs.enabled()
        c.inc()
        obs.event("suppress.nope")
        with obs.suppressed():
            c.inc()
        c.inc()                     # still inside the outer context
    assert obs.enabled()
    c.inc()
    assert c.value() == 2
    assert all(r["name"] != "suppress.nope"
               for r in obs.tracing.records())


def test_cold_warm_metric_equality(telemetry, tiny, key):
    """Regression (cold-key double counting): a jit-cache-miss host call
    runs the sampler twice (untimed warm-up + timed run) but must record
    each per-step metric exactly once — the same counts a warm call
    records.  Pre-fix, every cold call double-counted sampler.step
    events, step/reveal histograms and decode.* counters."""
    eng = _engine(tiny, "dndm")

    def emission_counts():
        h_step = obs.histogram("sampler.step_seconds").value(loop="host")
        h_rev = obs.histogram("sampler.reveal_count").value(
            sampler="dndm", version=1)
        steps = sum(1 for r in obs.tracing.records()
                    if r["kind"] == "event" and r["name"] == "sampler.step")
        return ((h_step or {"count": 0})["count"],
                (h_rev or {"count": 0})["count"], steps)

    out, _ = eng.generate(key, 2, SEQ)          # cold: warm-up + timed
    cold = emission_counts()
    obs.metrics.reset()
    obs.tracing.clear()
    out2, _ = eng.generate(key, 2, SEQ)         # warm: timed run only
    warm = emission_counts()
    assert cold == warm
    assert cold[0] == out.nfe                   # one step record per call
    assert (np.asarray(out.tokens) == np.asarray(out2.tokens)).all()


def test_scan_cache_counters(telemetry, tiny, key):
    eng = _engine(tiny, "dndm_static")
    eng.generate(key, 2, SEQ)
    eng.generate(key, 2, SEQ)
    assert obs.counter("engine.jit_cache.misses").value(
        method="dndm_static", kind="scan") == 1
    assert obs.counter("engine.jit_cache.hits").value(
        method="dndm_static", kind="scan") == 1
    assert obs.counter("engine.nfe").value(method="dndm_static") == 4


# ------------------------------------------------------------------
# scheduler: amortized wall + occupancy metrics
# ------------------------------------------------------------------

def test_scheduler_amortized_wall_and_occupancy(telemetry, tiny):
    eng = _engine(tiny, "dndm_static")
    sched = BatchScheduler(eng, max_batch=4, bucket_len=SEQ)
    rids = [sched.submit(SEQ) for _ in range(3)]
    done = sched.run()
    for rid in rids:
        r = done[rid]
        assert r.batch_size == 3
        assert r.batch_wall > 0
        assert r.wall == pytest.approx(r.batch_wall / 3)
    occ = obs.histogram("scheduler.occupancy").value(method="dndm_static")
    assert occ["count"] == 1
    assert occ["max"] == pytest.approx(3 / 4)   # 3 requests in a 4-bucket
    assert obs.counter("scheduler.padded_rows").value(
        method="dndm_static") == 1
    # the exported batch span carries the post-run attrs (wall/occupancy)
    batch_spans = [r for r in obs.tracing.records()
                   if r["kind"] == "span" and r["name"] == "scheduler.batch"]
    assert batch_spans and {"wall_s", "occupancy", "padded_rows"} <= \
        set(batch_spans[0]["attrs"])
    # nesting: the engine span is a child of the scheduler batch span
    gen = [r for r in obs.tracing.records()
           if r["kind"] == "span" and r["name"] == "engine.generate"]
    assert gen[0]["parent_id"] == batch_spans[0]["span_id"]


# ------------------------------------------------------------------
# disabled-path overhead guard
# ------------------------------------------------------------------

def test_disabled_path_overhead():
    """With telemetry off, an instrumented call site costs one guard
    check — no allocation, no records.  Budget: well under the <2%
    engine.generate regression ceiling (a host step is >=100us of real
    work; we require the full span+event+counter trio to stay under
    10us/op even on a loaded CI machine)."""
    assert not obs.enabled()
    c = obs.counter("t.overhead")
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        obs.span("x", a=1)
        obs.event("y", b=2)
        c.inc(3, d="z")
    per_op = (time.perf_counter() - t0) / n
    assert per_op < 10e-6, f"disabled telemetry costs {per_op * 1e6:.2f}us"
    assert obs.tracing.records() == []
    assert c.value(d="z") == 0
    assert obs.snapshot() == {}


# ------------------------------------------------------------------
# schema validator
# ------------------------------------------------------------------

def test_schema_rejects_malformed_trace():
    with pytest.raises(schema.SchemaError):
        schema.validate_trace_lines(['{"kind": "span", "name": "x"}'])
    with pytest.raises(schema.SchemaError):
        schema.validate_trace_lines(["not json"])
    # a valid line passes
    ok = ('{"kind": "event", "name": "e", "ts": 1.0, "span_id": 1, '
          '"parent_id": null, "attrs": {}}')
    assert len(schema.validate_trace_lines([ok])) == 1


def test_schema_rejects_malformed_bench():
    with pytest.raises(schema.SchemaError):
        schema.validate_bench({"schema": 1})
    good = {
        "schema": 2, "jax_backend": "cpu", "quick": True,
        "config": {"batch": 8, "seq": 32, "steps": 16},
        "methods": {"dndm": {
            "noise": "absorbing", "kind": "host", "wall_seconds": 0.1,
            "compile_seconds": 0.0, "nfe": 10, "tokens_per_second": 100.0,
            "us_per_nfe": 9.0,
            "metrics": {"jit_cache_hits": 1, "jit_cache_misses": 1}}},
        "telemetry": {"enabled": True, "trace": None, "metrics": {}},
    }
    schema.validate_bench(good)                  # no raise
    bad = {**good, "methods": {}}
    with pytest.raises(schema.SchemaError):
        schema.validate_bench(bad)


# ------------------------------------------------------------------
# ISSUE 10: quantile sketches behind every histogram
# ------------------------------------------------------------------
import json
import threading
import urllib.request

from repro.obs import exporter, regress, slo
from repro.obs.sketch import DDSketch, quantile_of_snapshot


def test_sketch_relative_error_and_merge_exactness():
    """Deterministic companion to the hypothesis properties: quantile
    estimates stay within alpha relative error across five decades, and
    merging per-shard sketches reproduces the global sketch exactly."""
    vals = [10.0 ** (i / 100.0) for i in range(-200, 301)]  # 1e-2..1e3
    sk = DDSketch(alpha=0.01)
    for v in vals:
        sk.add(v)
    srt = sorted(vals)
    for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        true = srt[int(q * (len(vals) - 1))]
        assert abs(sk.quantile(q) - true) <= 0.01 * true + 1e-12, q

    a, b = DDSketch(), DDSketch()
    for v in vals[::2]:
        a.add(v)
    for v in vals[1::2]:
        b.add(v)
    merged = a.copy().merge(b)
    assert merged.count == sk.count
    assert merged.bins == sk.bins
    # round-trip through the snapshot JSON form
    back = DDSketch.from_dict(json.loads(json.dumps(merged.to_dict())))
    assert back.quantile(0.95) == merged.quantile(0.95)


def test_sketch_fixed_memory_collapse_keeps_upper_quantiles():
    """max_bins is a hard bound; collapsing the low tail must not move
    p95/p99 (they live in the highest buckets)."""
    sk = DDSketch(alpha=0.01, max_bins=64)
    vals = [10.0 ** (i / 50.0) for i in range(-300, 301)]   # 1e-6..1e6
    for v in vals:
        sk.add(v)
    assert len(sk.bins) <= 64
    srt = sorted(vals)
    for q in (0.95, 0.99):
        true = srt[int(q * (len(vals) - 1))]
        assert abs(sk.quantile(q) - true) <= 0.01 * true


def test_sketch_zero_bucket_and_validation():
    sk = DDSketch()
    assert sk.quantile(0.5) == 0.0                  # empty
    sk.add(0.0, n=3)
    sk.add(-1.0)
    sk.add(5.0)
    assert sk.count == 5
    assert sk.quantile(0.0) == 0.0                  # zeros rank first
    assert abs(sk.quantile(1.0) - 5.0) <= 0.05
    with pytest.raises(ValueError):
        sk.quantile(1.5)
    with pytest.raises(ValueError):
        DDSketch(alpha=0.0)
    with pytest.raises(ValueError):
        DDSketch().merge(DDSketch(alpha=0.05))


def test_histogram_snapshot_carries_sketch_quantiles(telemetry):
    """Every histogram series snapshot now carries p50/p95/p99 plus the
    serialized sketch, and quantile_of_snapshot recomputes any quantile
    from the artifact alone (no live registry needed)."""
    h = obs.histogram("t.sketch_hist")
    vals = [0.001 * (i + 1) for i in range(500)]
    for v in vals:
        h.observe(v, op="f")
    snap = obs.snapshot()
    sv = snap["t.sketch_hist"]["series"][0]["value"]
    for q, field in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
        true = sorted(vals)[int(q * (len(vals) - 1))]
        assert abs(sv[field] - true) <= 0.01 * true + 1e-9
        assert sv[field] == quantile_of_snapshot(sv, q)
    # schema: the new fields are required, not incidental
    schema.validate_metrics_snapshot(snap)
    broken = json.loads(json.dumps(snap))
    del broken["t.sketch_hist"]["series"][0]["value"]["sketch"]
    with pytest.raises(schema.SchemaError):
        schema.validate_metrics_snapshot(broken)


def test_snapshot_is_deep_copy_and_lock_consistent(telemetry):
    """snapshot() under a concurrent writer storm never throws (the
    registry lock covers iteration) and returns an isolated deep copy."""
    c = obs.counter("t.race")
    h = obs.histogram("t.race_hist")
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            c.inc(a=str(i % 7))             # churns the series dict
            h.observe(i % 13 + 0.1, b=str(i % 5))
            i += 1

    threads = [threading.Thread(target=writer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            snap = obs.snapshot()           # must not raise mid-iteration
    finally:
        stop.set()
        for t in threads:
            t.join()
    snap = obs.snapshot()
    before = obs.counter("t.race").value(a="0")
    snap["t.race"]["series"][0]["value"] = -999     # mutate the copy
    assert obs.counter("t.race").value(a="0") == before
    assert obs.snapshot()["t.race"]["series"][0]["value"] != -999


# ------------------------------------------------------------------
# ISSUE 10: trace drop accounting + buffered sink
# ------------------------------------------------------------------

def test_dropped_records_counted_and_surfaced(telemetry, tmp_path,
                                              monkeypatch):
    """Records past the in-memory bound are counted (never silently
    swallowed), surfaced in summary(), pinned into the metrics footer —
    and the file sink still receives every one of them."""
    monkeypatch.setattr(obs.tracing, "_MAX_RECORDS", 4)
    path = tmp_path / "trace.jsonl"
    obs.set_sink(str(path))
    for i in range(10):
        obs.event("spam", i=i)
    assert obs.tracing.dropped_records() == 6
    assert obs.counter("obs.trace.dropped_records").value() == 6
    assert len(obs.tracing.records()) == 4
    assert "6 trace records dropped" in obs.summary()
    obs.tracing.close_sink(final_metrics=True)
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert sum(r.get("name") == "spam" for r in recs) == 10   # sink complete
    footer = recs[-1]
    assert footer["kind"] == "metrics"
    g = footer["metrics"]["obs.trace.dropped_records_total"]
    assert g["series"][0]["value"] == 6


def test_sink_is_buffered_not_per_record(telemetry, tmp_path):
    """Satellite: the sink coalesces writes — emitting N records costs
    O(N / _SINK_FLUSH_RECORDS) file writes, not N — and flush_sink()
    forces the tail out for live tailing."""
    path = tmp_path / "buf.jsonl"
    obs.set_sink(str(path))

    class _Spy:
        def __init__(self, f):
            self.f, self.writes = f, []

        def write(self, s):
            self.writes.append(s)
            return self.f.write(s)

        def flush(self):
            return self.f.flush()

        def close(self):
            return self.f.close()

    spy = obs.tracing._sink = _Spy(obs.tracing._sink)
    n = 600
    for i in range(n):
        obs.event("b", i=i)
    # coalesced: one write per flush threshold, not one per record
    # (+slack for a time-threshold flush on a very slow machine)
    assert len(spy.writes) <= 2 + n // obs.tracing._SINK_FLUSH_RECORDS
    obs.flush_sink()
    assert sum(s.count("\n") for s in spy.writes) == n
    obs.tracing.close_sink()
    assert len(path.read_text().splitlines()) == n


# ------------------------------------------------------------------
# ISSUE 10: per-request timelines through the serving stack
# ------------------------------------------------------------------
from repro.serving import ContinuousScheduler


def test_request_timeline_continuous(telemetry, tiny, tmp_path):
    """Acceptance: every request minted at submit() is traceable through
    one trace file — submit -> admission -> every engine.stepwise call
    it rode (batched with other requests) -> completion — and every
    stepwise span a request participated in carries its request_id.
    Covers mid-flight admission: r2 joins r1's live batch."""
    model, params = tiny
    eng = GenerationEngine(model, params, EngineConfig(
        method="dndm", steps=4, shared_tau=False))
    path = tmp_path / "serve_trace.jsonl"
    obs.set_sink(str(path))
    sched = ContinuousScheduler(eng, max_batch=2, bucket_len=SEQ, seed=5)
    r1 = sched.submit(SEQ)
    sched.pump()                             # r1 in flight alone
    r2 = sched.submit(SEQ)                   # mid-flight admission
    done = sched.run()
    obs.tracing.close_sink()

    for rid in (r1, r2):
        req = done[rid]
        assert req.request_id.startswith("req-")
        assert req.plan.request_id == req.request_id   # stamped plan
        tl = obs.timeline(req.request_id, path=str(path))
        names = [r["name"] for r in tl if r["kind"] != "metrics"]
        assert "scheduler.submit" in names
        assert "scheduler.admit" in names
        assert "scheduler.complete" in names
        order = [n for n in names if n in
                 ("scheduler.submit", "scheduler.admit",
                  "scheduler.complete")]
        assert order[0] == "scheduler.submit"
        assert order[-1] == "scheduler.complete"
        stepwise = [r for r in tl if r["name"] == "engine.stepwise"]
        assert len(stepwise) == done[rid].steps_executed
        for s in stepwise:
            assert req.request_id in s["attrs"]["request_ids"].split(",")
        # the in-memory view agrees with the file reconstruction
        assert len(obs.timeline(req.request_id)) == len(tl)

    # mid-flight: r2's admit event says it joined a live batch
    tl2 = obs.timeline(done[r2].request_id, path=str(path))
    admit = next(r for r in tl2 if r["name"] == "scheduler.admit")
    assert admit["attrs"]["midflight"] is True
    # batched calls are shared: some stepwise spans name both requests
    both = [r for r in obs.timeline(done[r1].request_id, path=str(path))
            if r["name"] == "engine.stepwise"
            and len(r["attrs"]["request_ids"].split(",")) == 2]
    assert both, "no shared batched call recorded for two live requests"


def test_request_timeline_drain_mode(telemetry, tiny, tmp_path):
    """Drain-mode requests are traceable too: the batch span carries
    request_ids, and nested engine.generate/sampler.step records are
    pulled into the timeline transitively."""
    eng = _engine(tiny, "dndm")
    path = tmp_path / "drain_trace.jsonl"
    obs.set_sink(str(path))
    sched = BatchScheduler(eng, max_batch=4, bucket_len=SEQ)
    rids = [sched.submit(SEQ) for _ in range(2)]
    done = sched.run()
    obs.tracing.close_sink()
    for rid in rids:
        tl = obs.timeline(done[rid].request_id, path=str(path))
        names = {r["name"] for r in tl if r["kind"] != "metrics"}
        assert {"scheduler.submit", "scheduler.admit", "scheduler.batch",
                "engine.generate", "scheduler.complete"} <= names
        assert "sampler.step" in names       # transitive child pickup


# ------------------------------------------------------------------
# ISSUE 10: live exporter (Prometheus text + HTTP endpoints)
# ------------------------------------------------------------------

def test_prometheus_text_round_trips(telemetry):
    """Satellite: the text exposition round-trips through the module's
    own minimal parser — counters, gauges, and histogram summaries with
    quantile labels."""
    obs.counter("t.prom.count", "a counter").inc(3, method="dndm")
    obs.gauge("t.prom.gauge").set(1.25, k="v")
    h = obs.histogram("t.prom.hist")
    for v in (0.1, 0.2, 0.4):
        h.observe(v, op="f")
    text = exporter.prometheus_text()
    assert "# TYPE t_prom_count counter" in text
    assert "# TYPE t_prom_hist summary" in text
    parsed = exporter.parse_prometheus_text(text)
    assert parsed[("t_prom_count", (("method", "dndm"),))] == 3.0
    assert parsed[("t_prom_gauge", (("k", "v"),))] == 1.25
    assert parsed[("t_prom_hist_count", (("op", "f"),))] == 3.0
    assert parsed[("t_prom_hist_sum", (("op", "f"),))] == pytest.approx(0.7)
    sv = obs.snapshot()["t.prom.hist"]["series"][0]["value"]
    for q, field in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99")):
        live = parsed[("t_prom_hist", (("op", "f"), ("quantile", q)))]
        assert live == pytest.approx(sv[field], rel=1e-5)


def test_metrics_server_serves_live_scrapes(telemetry):
    """/metrics (Prometheus text) and /snapshot (JSON) on an ephemeral
    port; values reflect the live registry; unknown paths 404."""
    obs.counter("t.live.count").inc(7, x="y")
    srv = exporter.MetricsServer(port=0)
    try:
        with urllib.request.urlopen(srv.url + "/metrics", timeout=5) as r:
            text = r.read().decode()
        parsed = exporter.parse_prometheus_text(text)
        assert parsed[("t_live_count", (("x", "y"),))] == 7.0
        with urllib.request.urlopen(srv.url + "/snapshot", timeout=5) as r:
            snap = json.loads(r.read().decode())
        assert snap["t.live.count"]["series"][0]["value"] == 7
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/nope", timeout=5)
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_snapshot_writer_atomic_file(telemetry, tmp_path):
    obs.counter("t.snapwrite").inc(2)
    path = tmp_path / "snap.json"
    w = exporter.SnapshotWriter(str(path), interval_s=3600)
    w.stop(final=True)                       # forces one atomic write
    snap = json.loads(path.read_text())
    assert snap["t.snapwrite"]["series"][0]["value"] == 2
    assert not (tmp_path / "snap.json.tmp").exists()


# ------------------------------------------------------------------
# ISSUE 10: SLO budgets + error-budget burn
# ------------------------------------------------------------------

@pytest.fixture()
def slo_budgets():
    yield
    slo.clear()


def test_slo_parse_grammar(slo_budgets):
    got = slo.parse("latency<0.25@0.95, nfe<64@1.0, dndm_c.queue<0.1")
    assert [b.name for b in got] == ["latency<0.25", "nfe<64",
                                    "dndm_c.queue<0.1"]
    assert got[0].objective == 0.95 and got[0].method == "*"
    assert got[1].objective == 1.0
    assert got[2].method == "dndm_c" and got[2].objective == 0.99
    with pytest.raises(ValueError):
        slo.parse("latency")                 # no limit
    with pytest.raises(ValueError):
        slo.parse("walltime<1.0")            # unknown metric
    with pytest.raises(ValueError):
        slo.Budget("latency", 0.1, objective=0.0)


def test_slo_breach_counting_and_burn(telemetry, slo_budgets):
    slo.configure([slo.Budget("latency", 0.1, objective=0.9),
                   slo.Budget("nfe", 8, objective=1.0, method="dndm")])
    for lat in (0.05, 0.05, 0.2):            # 1 of 3 over the limit
        slo.observe_request("dndm", latency_s=lat, queue_s=0.0, nfe=4)
    slo.observe_request("rdm", latency_s=0.05, queue_s=0.0, nfe=99)
    assert obs.counter("scheduler.slo_breaches").value(
        budget="latency<0.1", method="dndm") == 1
    # the method-scoped nfe budget ignored rdm's 99 calls
    assert obs.counter("scheduler.slo_requests").value(
        budget="dndm.nfe<8", method="rdm") == 0
    st = slo.status()
    lat = st["latency<0.1"]
    assert lat["requests"] == 4 and lat["breaches"] == 1
    # allowance = (1-0.9)*4 = 0.4 -> burn = 1/0.4 = 2.5 (budget spent)
    assert lat["burn"] == pytest.approx(2.5)
    assert st["dndm.nfe<8"]["breaches"] == 0
    assert obs.gauge("scheduler.slo_burn").value(
        budget="latency<0.1") == pytest.approx(2.5)


def test_slo_noop_without_budgets(telemetry, slo_budgets):
    assert not slo.active()
    slo.observe_request("dndm", latency_s=9e9, queue_s=9e9, nfe=9e9)
    assert obs.snapshot() == {}              # nothing recorded
    assert slo.status() == {}


def test_scheduler_reports_completed_requests_to_slo(telemetry, tiny,
                                                     slo_budgets):
    """Integration: both schedulers score completions against the active
    budgets — a sky-high latency limit records requests, a zero limit
    records breaches."""
    slo.configure(slo.parse("latency<1e9@0.99,queue<0.0@0.99"))
    eng = _engine(tiny, "dndm_static")
    sched = BatchScheduler(eng, max_batch=4, bucket_len=SEQ)
    n = 3
    for _ in range(n):
        sched.submit(SEQ)
    sched.run()
    assert obs.counter("scheduler.slo_requests").value(
        budget="latency<1e+09", method="dndm_static") == n
    assert obs.counter("scheduler.slo_breaches").value(
        budget="latency<1e+09", method="dndm_static") == 0
    assert obs.counter("scheduler.slo_breaches").value(
        budget="queue<0", method="dndm_static") == n


# ------------------------------------------------------------------
# ISSUE 10: bench-regression gate
# ------------------------------------------------------------------

def _serving_artifact(wall=10.0, rps=5.0, p95=0.4, nfe=100,
                      parity=True, fewer=True):
    mode = {"wall_seconds": wall, "throughput_rps": rps,
            "latency_p50_s": p95 / 2, "latency_p95_s": p95,
            "latency_p99_s": p95 * 1.2, "aggregate_nfe": nfe}
    return {"schema": 2, "kind": "serving",
            "modes": {"drain": dict(mode), "continuous": dict(mode)},
            "comparison": {"solo_parity": parity, "fewer_nfe": fewer}}


def test_regress_identical_and_improved_pass():
    base = _serving_artifact()
    ok, lines = regress.compare(base, _serving_artifact())
    assert ok and not any(l.startswith("REGRESSION") for l in lines)
    better = _serving_artifact(wall=5.0, rps=9.0, p95=0.2, nfe=50)
    ok, _ = regress.compare(base, better)
    assert ok                                # improvements never fail


def test_regress_catches_wall_and_parity_regressions(tmp_path):
    base = _serving_artifact()
    ok, lines = regress.compare(base, _serving_artifact(wall=20.0))
    assert not ok                            # 2x wall > 1.5x tolerance
    assert any("wall_seconds" in l for l in lines
               if l.startswith("REGRESSION"))
    # parity flip is exact-match: fails at any magnitude
    ok, lines = regress.compare(base, _serving_artifact(parity=False))
    assert not ok
    assert any("solo_parity" in l for l in lines
               if l.startswith("REGRESSION"))
    # noise inside tolerance passes
    ok, _ = regress.compare(base, _serving_artifact(wall=13.0, rps=4.0))
    assert ok
    # CLI contract: 0 ok / 1 regression / 2 unreadable
    b, n = tmp_path / "b.json", tmp_path / "n.json"
    b.write_text(json.dumps(base))
    n.write_text(json.dumps(_serving_artifact(wall=20.0)))
    assert regress.main([str(b), str(b)]) == 0
    assert regress.main([str(b), str(n)]) == 1
    assert regress.main([str(b), str(n), "--wall-tol", "2.0"]) == 0
    assert regress.main([str(b), str(tmp_path / "missing.json")]) == 2


def test_regress_bench_kind_and_mismatched_kinds():
    mk = lambda wall: {"schema": 2, "methods": {"dndm": {
        "wall_seconds": wall, "tokens_per_second": 100.0, "nfe": 10}}}
    ok, _ = regress.compare(mk(1.0), mk(1.2))
    assert ok
    ok, lines = regress.compare(mk(1.0), mk(3.0))
    assert not ok
    ok, lines = regress.compare(mk(1.0), _serving_artifact())
    assert not ok and any("kind" in l for l in lines
                          if l.startswith("REGRESSION"))
    # a method missing from NEW is a regression
    gone = {"schema": 2, "methods": {}}
    ok, lines = regress.compare(mk(1.0), gone)
    assert not ok


def test_jax_profile_off_without_env(monkeypatch):
    """Without REPRO_JAX_PROFILE no profiler session starts, and with
    telemetry off too every span is the no-op singleton."""
    monkeypatch.delenv("REPRO_JAX_PROFILE", raising=False)
    obs.configure_from_env()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert obs.span("x", a=1) is obs.tracing.NULL_SPAN


def test_jax_profile_that_cannot_start_raises(monkeypatch, tmp_path):
    """A profile asked for with REPRO_JAX_PROFILE either starts or raises
    — a missing device trace must never pass silently."""
    def refuse(log_dir, *a, **k):
        raise RuntimeError("profiler unavailable")
    monkeypatch.setenv("REPRO_JAX_PROFILE", str(tmp_path))
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        obs.configure_from_env()
    assert not jax.profiler.TraceAnnotation.is_enabled()


# ------------------------------------------------------------------
# spans on the profiler's clock
# ------------------------------------------------------------------

SERVING_SPANS = ("scheduler.submit", "engine.plan", "scheduler.pump",
                 "engine.admit", "engine.stepwise", "engine.harvest")


def _host_events(log_dir, names) -> list[tuple[str, float, float, dict]]:
    """(name, start_ns, end_ns, stats) of the host events named in
    ``names`` in the one trace under ``log_dir``, in start order."""
    import glob

    from jax.profiler import ProfileData
    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                       for e in line.events if e.name in names)
    return sorted(out, key=lambda ev: ev[1])


def test_span_on_profiler_clock(telemetry, tmp_path):
    """While a profiler session records, a span is also a profiler event
    whose stats are its scalar attributes, those set mid-span included;
    the JSON-lines record is written as before."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("outer", rows=3, method="dndm", skip=[1]) as sp:
            sp.set(padded_positions=5)
    finally:
        jax.profiler.stop_trace()
    (ev,) = _host_events(tmp_path, {"outer"})
    assert ev[3] == {"rows": 3, "method": "dndm", "padded_positions": 5}
    (rec,) = [r for r in obs.tracing.records() if r["name"] == "outer"]
    assert rec["attrs"]["padded_positions"] == 5


def test_serving_spans_in_profile(monkeypatch, tmp_path, tiny):
    """REPRO_JAX_PROFILE traces the serving path with telemetry off:
    scheduler.submit holds engine.plan, scheduler.pump holds
    engine.admit / engine.stepwise / engine.harvest, and their rows and
    padded-position stats are the ones computed here from the requests'
    plans."""
    model, params = tiny
    eng = GenerationEngine(model, params, EngineConfig(
        method="dndm", steps=STEPS, shared_tau=False))
    sched = ContinuousScheduler(eng, max_batch=2, bucket_len=SEQ, seed=3)
    lengths = {sched.submit(5): 5, sched.submit(SEQ): SEQ}
    sched.pump()                        # compile outside the profile
    sched = ContinuousScheduler(eng, max_batch=2, bucket_len=SEQ, seed=3)
    monkeypatch.setenv("REPRO_JAX_PROFILE", str(tmp_path))
    assert not obs.enabled()
    obs.configure_from_env()
    try:
        rids = [sched.submit(n) for n in lengths.values()]
        done = sched.run()
    finally:
        obs.tracing.stop_profile()
    evs = _host_events(tmp_path, set(SERVING_SPANS))

    def inside(inner, outer):
        return [[o for o in evs if o[0] == outer
                 and o[1] <= e[1] and e[2] <= o[2]]
                for e in evs if e[0] == inner]
    assert len(inside("engine.plan", "scheduler.submit")) == 2
    for inner, outer in (("engine.plan", "scheduler.submit"),
                         ("engine.admit", "scheduler.pump"),
                         ("engine.stepwise", "scheduler.pump"),
                         ("engine.harvest", "scheduler.pump")):
        assert all(len(c) == 1 for c in inside(inner, outer)), inner

    nfe = {rid: done[rid].plan.nfe for rid in rids}
    length = dict(zip(rids, lengths.values()))
    calls = max(nfe.values())
    submits = [e[3] for e in evs if e[0] == "scheduler.submit"]
    assert [(s["length"], s["planned_nfe"]) for s in submits] == [
        (length[r], nfe[r]) for r in rids]
    assert [e[3]["rows"] for e in evs if e[0] == "engine.admit"] == [2]
    steps = [e[3] for e in evs if e[0] == "engine.stepwise"]
    assert [(s["rows"], s["padded_positions"]) for s in steps] == [
        (sum(j < nfe[r] for r in rids),
         sum(SEQ - length[r] for r in rids if j < nfe[r]))
        for j in range(calls)]
    harvests = [e[3]["rows"] for e in evs if e[0] == "engine.harvest"]
    want = [sum(nfe[r] == j + 1 for r in rids) for j in range(calls)]
    assert harvests == [n for n in want if n]


def test_step_program_carries_named_scopes(tiny):
    """The denoiser's parts and the decode path are named in the step
    program's debug info, from which the compiler's op metadata (and so
    a device trace) takes them."""
    import re

    from repro.core.samplers import stepwise
    eng = _engine(tiny)
    rt = eng.runtime()
    x = np.zeros((2, SEQ), np.int32)
    lowered = stepwise._dndm_rows.lower(
        x, x + 1, np.array([1, 2], np.int32), np.zeros((2, 2), np.uint32),
        eng.call_cond(None), denoise_fn=rt.denoise_fn, noise=rt.noise,
        cfg=rt.cfg, version=1, T=rt.dist.T)
    text = lowered.as_text(debug_info=True)
    for scope in ("time_embed", "attention", "mlp", "lm_head", "decode"):
        assert re.search(rf'loc\("([^"]*/)?{scope}/', text), scope
