"""Observability layer (DESIGN.md §9): metrics registry, tracing spans,
exposition, and the zero-host-sync contract under instrumentation.

Four families:

* **histogram units** — √2-power log-bucket boundaries, quantiles,
  bucket-wise merge, saturation at the clamp rails.
* **registry** — label series, kind conflicts, partial-label totals,
  Prometheus text round-trip through ``parse_prometheus``, HTTP scrape.
* **tracing** — ring-buffer capacity/drops, nested-span containment in
  the exported Perfetto JSON, disabled-posture no-op.
* **no-sync contract** — a fully instrumented queue flush (metrics +
  tracer ON) stays a single fused dispatch under
  ``jax.transfer_guard("disallow")``, and the serve/journal plumbing
  (fsync policy counters, per-tenant summary rows, EngineStats views)
  reads back from one registry.
"""
import json
import math
import os
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.obs.metrics import (Counter, Gauge, Histogram, Registry,
                               bucket_index, bucket_upper, parse_prometheus,
                               start_http_server, use_registry)
from repro.obs.trace import Tracer
from repro.core import IndexConfig, build_index
from repro.engine.queue import (MicroBatchQueue, index_probe_fn,
                                tenant_summary)
from repro.ckpt.journal import FSYNC_POLICIES, Journal, read_segment


# ---------------------------------------------------------------- buckets
def test_bucket_boundaries_are_sqrt2_powers():
    """bucket_upper(k) = 2^(k/2); a value lands in the first bucket whose
    upper bound is >= the value."""
    for k in (-8, -1, 0, 1, 2, 9):
        assert bucket_upper(k) == pytest.approx(2.0 ** (k / 2.0))
    for v in (1e-6, 0.5, 1.0, 1.5, 2.0, 3.0, 1000.0):
        k = bucket_index(v)
        assert v <= bucket_upper(k) * (1 + 1e-12)
        assert v > bucket_upper(k - 1) * (1 - 1e-12)


def test_bucket_index_exact_powers():
    # exact powers of two sit at their own boundary, not the next bucket
    assert bucket_index(1.0) == 0
    assert bucket_index(2.0) == 2
    assert bucket_index(0.5) == -2
    assert bucket_index(math.sqrt(2.0)) == 1


def test_bucket_index_saturates_at_rails():
    """Out-of-range values clamp into the terminal buckets instead of
    growing the bucket table without bound."""
    assert bucket_index(1e30) == 128          # > 2^64: top bucket
    assert bucket_index(1e-30) == -60         # < 2^-30: bottom bucket
    assert bucket_index(0.0) == -60
    h = Histogram()
    h.observe(1e30)
    h.observe(1e-30)
    assert h.count == 2
    assert h.quantile(0.99) == pytest.approx(bucket_upper(128))


def test_histogram_quantile_and_mean():
    h = Histogram()
    for v in (1.0, 1.0, 1.0, 100.0):
        h.observe(v)
    # p50 lands in the 1.0 bucket, p99 in the 100.0 bucket
    assert h.quantile(0.5) <= 2.0
    assert h.quantile(0.99) >= 100.0
    assert h.mean == pytest.approx(103.0 / 4)
    assert h.min == 1.0 and h.max == 100.0


def test_histogram_merge_is_bucketwise_add():
    a, b = Histogram(), Histogram()
    for v in (0.25, 1.0, 4.0):
        a.observe(v)
    for v in (1.0, 64.0):
        b.observe(v)
    m = Histogram().merge(a).merge(b)         # merge folds INTO self
    assert m.count == 5
    assert m.sum == pytest.approx(70.25)
    assert m.min == 0.25 and m.max == 64.0
    ref = Histogram()
    for v in (0.25, 1.0, 4.0, 1.0, 64.0):
        ref.observe(v)
    for q in (0.1, 0.5, 0.9, 0.99):
        assert m.quantile(q) == ref.quantile(q)
    # merge does not mutate the operand
    assert a.count == 3 and b.count == 2


def test_histogram_time_contextmanager():
    h = Histogram()
    with h.time():
        pass
    assert h.count == 1 and h.sum >= 0.0


def test_counter_rejects_negative():
    c = Counter()
    c.inc(3)
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 3


# ---------------------------------------------------------------- registry
def test_registry_series_and_partial_label_totals():
    reg = Registry()
    reg.counter("ops", path="probe", tenant="a").inc(2)
    reg.counter("ops", path="probe", tenant="b").inc(3)
    reg.counter("ops", path="decode", tenant="a").inc(5)
    assert reg.total("ops") == 10
    assert reg.total("ops", path="probe") == 5
    assert reg.total("ops", path="probe", tenant="b") == 3
    assert reg.total("missing") == 0
    assert {tuple(sorted(lab.items())) for lab, _ in reg.series("ops")} == {
        (("path", "probe"), ("tenant", "a")),
        (("path", "probe"), ("tenant", "b")),
        (("path", "decode"), ("tenant", "a"))}


def test_registry_kind_conflict_raises():
    reg = Registry()
    reg.counter("x", path="a")
    with pytest.raises(ValueError):
        reg.histogram("x", path="a")
    # same (name, labels) returns the same instance
    assert reg.counter("x", path="a") is reg.counter("x", path="a")


def test_registry_merged_histogram_across_labels():
    reg = Registry()
    reg.histogram("lat", path="probe", tenant="a").observe(1.0)
    reg.histogram("lat", path="probe", tenant="b").observe(4.0)
    reg.histogram("lat", path="decode", tenant="a").observe(64.0)
    m = reg.merged_histogram("lat", path="probe")
    assert m.count == 2 and m.sum == pytest.approx(5.0)
    assert reg.merged_histogram("lat").count == 3
    assert reg.merged_histogram("nope").count == 0


def test_prometheus_text_round_trips_through_parser():
    reg = Registry()
    reg.counter("queue_submits", path="probe", tenant='we"ird\\t').inc(7)
    reg.gauge("queue_flush_at", path="probe").set(64)
    h = reg.histogram("dispatch_staging_seconds", path="search")
    h.observe(0.001)
    h.observe(0.002)
    text = reg.prometheus_text()
    parsed = parse_prometheus(text)
    names = {n for n, _ in parsed}
    # counters gain _total at exposition only; histograms explode into
    # _bucket/_sum/_count with a +Inf rail
    assert "repro_queue_submits_total" in names
    assert "repro_queue_flush_at" in names
    assert "repro_dispatch_staging_seconds_bucket" in names
    by_name = {}
    for (n, lab), v in parsed.items():
        by_name.setdefault(n, {})[lab] = v
    assert sum(by_name["repro_queue_submits_total"].values()) == 7
    buckets = by_name["repro_dispatch_staging_seconds_bucket"]
    assert any('le="+Inf"' in lab and v == 2 for lab, v in buckets.items())
    assert sum(by_name["repro_dispatch_staging_seconds_count"].values()) == 2
    # cumulative le buckets are monotone non-decreasing
    rails = sorted(
        ((float("inf") if 'le="+Inf"' in lab else
          float(lab.split('le="')[1].split('"')[0])), v)
        for lab, v in buckets.items())
    assert all(rails[i][1] <= rails[i + 1][1] for i in range(len(rails) - 1))


def test_registry_snapshot_shape():
    reg = Registry()
    reg.counter("ops", path="probe").inc(4)
    reg.histogram("lat", path="probe").observe(2.0)
    snap = reg.snapshot()
    assert set(snap) == {"ops", "lat"}
    assert snap["ops"] == [{"labels": {"path": "probe"}, "value": 4}]
    hist = snap["lat"][0]
    assert hist["count"] == 1 and "p99" in hist and "buckets" in hist
    assert hist["labels"] == {"path": "probe"}
    json.dumps(snap)                          # BENCH_*.json embeddable


def test_http_scrape_serves_registry():
    reg = Registry()
    reg.counter("ops", path="probe").inc(1)
    srv, port = start_http_server(0, registry=reg)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    finally:
        srv.shutdown()
    assert parse_prometheus(body)[
        ("repro_ops_total", '{path="probe"}')] == 1.0


def test_null_registry_posture():
    """metrics=False hands out a shared no-op metric for every series and
    empty reads — the off posture allocates nothing per call site."""
    null = obs.NULL_REGISTRY
    c = null.counter("ops", path="probe")
    c.inc()
    assert c is null.histogram("lat", path="x")
    assert null.total("ops") == 0.0
    assert null.merged_histogram("lat").count == 0
    assert list(null.series("ops")) == []
    assert null.snapshot() == {}


# ----------------------------------------------------------------- tracing
def test_span_nesting_in_export():
    tr = Tracer()
    tr.enable()
    with tr.span("outer", kind="test"):
        with tr.span("inner"):
            pass
        with tr.span("inner2"):
            pass
    doc = tr.export()
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert set(evs) == {"outer", "inner", "inner2"}
    outer, inner = evs["outer"], evs["inner"]
    assert all(e["ph"] == "X" for e in evs.values())
    assert outer["args"] == {"kind": "test"}
    # nesting = same tid + timestamp containment (how Perfetto stacks them)
    for e in (evs["inner"], evs["inner2"]):
        assert e["tid"] == outer["tid"]
        assert e["ts"] >= outer["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert inner["ts"] + inner["dur"] <= evs["inner2"]["ts"] + 1e-6


@pytest.mark.parametrize("enabled", [True, False])
def test_span_set_adds_args(enabled):
    """Labels known only inside a span (the flush that served a request)
    join its ``args``; the event keeps its bare name. Disabled, ``set``
    is a no-op on the shared null span."""
    tr = Tracer()
    if enabled:
        tr.enable()
    with tr.span("queue.result", req=7) as sp:
        sp.set(flush=3)
    if not enabled:
        assert tr.events() == []
        return
    (ev,) = tr.events()
    assert ev["name"] == "queue.result"
    assert ev["args"] == {"req": 7, "flush": 3}


def test_trace_export_writes_loadable_json(tmp_path):
    tr = Tracer()
    tr.enable()
    with tr.span("a", n=3):
        pass
    path = str(tmp_path / "trace.json")
    tr.export(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["traceEvents"][0]["name"] == "a"
    assert doc["traceEvents"][0]["args"]["n"] == 3
    assert doc["otherData"]["dropped_events"] == 0


def test_trace_ring_drops_oldest():
    tr = Tracer(capacity=4)
    tr.enable()
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    evs = tr.events()
    assert len(evs) == 4
    assert [e["name"] for e in evs] == ["s6", "s7", "s8", "s9"]
    assert tr.dropped == 6
    assert tr.export()["otherData"]["dropped_events"] == 6


def test_disabled_tracer_records_nothing():
    tr = Tracer()
    with tr.span("never"):
        pass
    assert tr.events() == [] and tr.dropped == 0


# ----------------------------------------------- instrumented no-sync flush
def _store(n=16384):
    rng = np.random.default_rng(3)
    keys = np.unique(rng.integers(0, 2**31 - 2, int(n * 1.1)
                                  ).astype(np.int32))[:n]
    vals = np.arange(keys.size, dtype=np.int32)
    idx = build_index(keys, vals, IndexConfig(kind="tiered", mutable=True))
    return keys, vals, idx


def test_instrumented_flush_is_single_dispatch_no_transfers():
    """DESIGN.md §9.3: with metrics AND tracing fully on, a flush of
    device-resident submissions still adds no host<->device transfer —
    instrumentation only reads host clocks at the dispatch boundary."""
    keys, vals, idx = _store()
    reqs = [jnp.asarray(keys[i * 8:(i + 1) * 8]) for i in range(4)]
    warm = MicroBatchQueue(index_probe_fn(idx), capacity=32, min_flush=32,
                           timer=False)
    for r in reqs:
        warm.submit(r)
    warm.flush()                                  # compile the fused shape
    tr = Tracer()
    tr.enable()
    with use_registry(Registry()) as reg:
        q = MicroBatchQueue(index_probe_fn(idx), capacity=32, min_flush=32,
                            timer=False, path="probe")
        import repro.obs.trace as trace_mod
        old, trace_mod.TRACER = trace_mod.TRACER, tr
        try:
            with jax.transfer_guard("disallow"):
                futs = [q.submit(r) for r in reqs]
                q.flush()
            # the result stages (wait, slice) read host clocks only: no
            # device value comes back. (Slicing uploads its start index as
            # a scalar, host to device, with or without instrumentation.)
            with jax.transfer_guard_device_to_host("disallow"), \
                    jax.transfer_guard_device_to_device("disallow"):
                outs = [f.result() for f in futs]
        finally:
            trace_mod.TRACER = old
        assert q.stats.flushes == 1
        for i, out in enumerate(outs):
            np.testing.assert_array_equal(np.asarray(out.values),
                                          vals[i * 8:(i + 1) * 8])
        # the boundary timer recorded exactly the one dispatch
        h = reg.merged_histogram("dispatch_staging_seconds", path="probe")
        assert h.count == 1
        assert reg.total("queue_submits", path="probe") == 4
        assert reg.total("queue_flushes", path="probe") == 1
        for name in ("queue_result_wait_seconds", "queue_slice_seconds"):
            assert reg.merged_histogram(name, path="probe").count == 4
    names = [e["name"] for e in tr.events()]
    for name in ("queue.dispatch", "queue.flush", "queue.result",
                 "queue.wait", "queue.slice"):
        assert name in names, name


def test_queue_registry_series_and_tenant_summary():
    keys, _, idx = _store()
    with use_registry(Registry()) as reg:
        q = MicroBatchQueue(index_probe_fn(idx), capacity=32, min_flush=32,
                            timer=False, path="probe")
        q.submit(keys[:8], tenant="a")
        q.submit(keys[8:16], tenant="b")
        q.flush()
        q.drain_feedback()
        rows = {(r.path, r.tenant): r for r in tenant_summary(reg)}
        assert set(rows) == {("probe", "a"), ("probe", "b")}
        ra = rows[("probe", "a")]
        assert ra.submits == 1 and ra.queries == 8 and ra.admitted == 8
        assert ra.drops == 0 and ra.wait_mean_us >= 0.0
        assert reg.merged_histogram("queue_batch_size",
                                    path="probe").count == 1
        assert reg.merged_histogram("queue_flush_occupancy",
                                    path="probe").count == 1


def test_engine_stats_views_read_registry():
    from repro.serve.engine import EngineStats
    reg = Registry()
    reg.counter("queue_flushes", path="probe", reason="capacity").inc(3)
    reg.counter("queue_flushes", path="decode", reason="demand").inc(2)
    reg.histogram("queue_flush_occupancy", path="probe").observe(0.5)
    reg.counter("queue_submits", path="probe", tenant="t0").inc(4)
    reg.counter("queue_queries", path="probe", tenant="t0").inc(32)
    s = EngineStats(registry=reg)
    assert s.probe_batches == 3 and s.decode_flushes == 2
    assert s.probe_occupancy == pytest.approx(0.5, rel=0.5)  # bucket upper
    assert ("probe", "t0") in s.tenants
    assert s.tenants[("probe", "t0")].queries == 32


# ------------------------------------------------------------ fsync policy
def test_journal_fsync_policy_counts(tmp_path):
    with use_registry(Registry()) as reg:
        syncs = {}
        for policy in FSYNC_POLICIES:
            path = str(tmp_path / f"wal-{policy}.journal")
            jr = Journal(path, np.dtype(np.int32), fsync=policy)
            for k in range(5):
                jr.append(k, k * 10)
                jr.flush()                      # 5 acknowledged batches
            jr.close()
            syncs[policy] = jr.syncs
            recs = read_segment(path)[1]
            assert len(recs) == 5               # durability independent
        assert syncs["never"] == 0
        assert syncs["rotate"] == 1             # once, at close
        assert syncs["always"] == 5             # every flushed batch
        assert reg.total("journal_syncs", policy="always") == 5
        assert reg.total("journal_syncs", policy="rotate") == 1
        assert reg.total("journal_syncs", policy="never") == 0
        assert reg.total("journal_appends") == 15


def test_journal_rejects_unknown_policy(tmp_path):
    with pytest.raises(ValueError):
        Journal(str(tmp_path / "x.journal"), np.dtype(np.int32),
                fsync="sometimes")
    with pytest.raises(ValueError):
        IndexConfig(kind="tiered", journal_fsync="sometimes")


def test_index_config_fsync_reaches_store_journal(tmp_path):
    rng = np.random.default_rng(9)
    keys = np.unique(rng.integers(0, 2**31 - 2, 600).astype(np.int32))[:512]
    cfg = IndexConfig(kind="tiered", mutable=True, journal_fsync="always",
                      ckpt_dir=str(tmp_path))
    idx = build_index(keys, np.arange(keys.size, dtype=np.int32), cfg)
    idx.insert(np.array([7, 11], np.int32), np.array([1, 2], np.int32))
    assert idx._journal is not None
    assert idx._journal.fsync == "always"
    assert idx._journal.syncs >= 1
