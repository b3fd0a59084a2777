"""Stage timers of the served read path (DESIGN.md §9.1, §9.2).

A read through ``MicroBatchQueue`` passes four host stages, each timed by
a histogram under the queue's ``path`` and, with tracing on, by a span:

* ``queue_lock_wait_seconds{op}`` — taking the queue's lock in ``submit``
  (``op=submit``), in the deadline timer (``op=deadline``) and in a flush
  (``op=<reason>``, ``demand`` from ``result()``);
* ``queue_deadline_late_seconds`` — how far past the effective window a
  deadline flush ran;
* ``queue_result_wait_seconds`` / ``queue.wait`` — ``result()`` waiting for
  the caller's batch, demand flushes included;
* ``queue_slice_seconds`` / ``queue.slice`` — staging the caller's rows
  out of the shared result, once per future.

Spans carry the request's ``req`` id and the ``flush`` that served it, so
a trace export links one request across threads; ``Index.lookup`` splits
into ``lookup.put``, the search and ``lookup.gather`` under
``index.lookup``. The span names reach the JAX profiler bare.
"""
import glob
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.obs.trace as trace_mod
from repro.core import IndexConfig, build_index
from repro.engine.queue import MicroBatchQueue, index_probe_fn
from repro.obs.metrics import Registry, use_registry
from repro.obs.trace import Tracer


def _stub(q):
    """A ``search_fn`` with no index: doubles the batch on the device."""
    return jnp.asarray(q) * 2, None


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture
def tracer():
    tr = Tracer()
    tr.enable()
    old, trace_mod.TRACER = trace_mod.TRACER, tr
    try:
        yield tr
    finally:
        trace_mod.TRACER = old


def _count(reg, name, **labels):
    return reg.merged_histogram(name, **labels).count


def _contains(outer, inner):
    return (inner["tid"] == outer["tid"] and inner["ts"] >= outer["ts"]
            and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1e-6)


# ------------------------------------------------------------ histograms
def test_result_observes_wait_each_call_and_slice_once():
    with use_registry(Registry()) as reg:
        q = MicroBatchQueue(_stub, capacity=8, timer=False)
        fut = q.submit(np.arange(3, dtype=np.int32))
        out = fut.result()
        np.testing.assert_array_equal(np.asarray(out), [0, 2, 4])
        assert _count(reg, "queue_result_wait_seconds", path="probe") == 1
        assert _count(reg, "queue_slice_seconds", path="probe") == 1
        assert fut.result() is out
        assert _count(reg, "queue_result_wait_seconds", path="probe") == 2
        assert _count(reg, "queue_slice_seconds", path="probe") == 1


def test_lock_wait_observed_per_op():
    clock = Clock()
    with use_registry(Registry()) as reg:
        q = MicroBatchQueue(_stub, capacity=8, deadline_s=0.002,
                            timer=False, now_fn=clock)
        q.submit(np.arange(2, dtype=np.int32))
        q.flush()                                   # op=manual
        fut = q.submit(np.arange(2, dtype=np.int32))
        fut.result()                                # op=demand
        q.submit(np.arange(2, dtype=np.int32))
        clock.t = 1.0
        assert q.poll() == 2                        # op=deadline
        for op, n in (("submit", 3), ("manual", 1), ("demand", 1),
                      ("deadline", 1)):
            assert _count(reg, "queue_lock_wait_seconds", path="probe",
                          op=op) == n, op


def test_lock_wait_times_a_held_lock():
    """A submit that finds the lock held (as behind a flush staging its
    dispatch) observes the time it waited."""
    with use_registry(Registry()) as reg:
        q = MicroBatchQueue(_stub, capacity=8, timer=False)
        held = threading.Event()

        def hold():
            with q._lock:
                held.set()
                time.sleep(0.05)

        t = threading.Thread(target=hold)
        t.start()
        held.wait()
        q.submit(np.arange(2, dtype=np.int32))
        t.join()
        h = reg.merged_histogram("queue_lock_wait_seconds", path="probe",
                                 op="submit")
        assert h.count == 1 and h.sum >= 0.03


@pytest.mark.parametrize("trigger", ["poll", "timer"])
def test_deadline_flush_observes_lateness(trigger):
    """Virtual time: the oldest submit is 7.5 ms old at a 2 ms window, so
    the deadline flush ran 5.5 ms late."""
    clock = Clock()
    with use_registry(Registry()) as reg:
        q = MicroBatchQueue(_stub, capacity=8, deadline_s=0.002,
                            timer=(trigger == "timer"), now_fn=clock)
        fut = q.submit(np.arange(2, dtype=np.int32))
        clock.t = 0.0075
        if trigger == "poll":
            assert q.poll() == 2
        assert fut.wait(5.0)
        h = reg.merged_histogram("queue_deadline_late_seconds", path="probe")
        assert h.count == 1
        assert h.sum == pytest.approx(0.0055)
        assert q.stats.deadline_flushes == 1
        q.close()


def test_stage_histograms_follow_the_active_registry():
    """A queue resolves its stage series once per registry: after a swap
    its observations land in the new registry, not the old one."""
    q = MicroBatchQueue(_stub, capacity=8, timer=False)
    with use_registry(Registry()) as first:
        q.submit(np.arange(2, dtype=np.int32)).result()
    with use_registry(Registry()) as second:
        q.submit(np.arange(2, dtype=np.int32)).result()
        q.submit(np.arange(2, dtype=np.int32)).result()
    for reg, n in ((first, 1), (second, 2)):
        for name in ("queue_result_wait_seconds", "queue_slice_seconds",
                     "dispatch_staging_seconds"):
            assert _count(reg, name, path="probe") == n, name
        assert _count(reg, "queue_lock_wait_seconds", path="probe",
                      op="submit") == n


def test_dispatch_staging_replaces_engine_op_timer():
    with use_registry(Registry()) as reg:
        q = MicroBatchQueue(_stub, capacity=8, timer=False)
        q.submit(np.arange(2, dtype=np.int32))
        q.flush()
        assert _count(reg, "dispatch_staging_seconds", path="probe") == 1
        assert reg.value("engine_op_seconds", path="probe") is None
        assert not list(reg.series("engine_ops"))


# ----------------------------------------------------------------- spans
def test_wait_and_slice_nest_in_result(tracer):
    q = MicroBatchQueue(_stub, capacity=8, timer=False)
    fut = q.submit(np.arange(3, dtype=np.int32))
    fut.result()
    fut.result()
    evs = tracer.events()
    results = [e for e in evs if e["name"] == "queue.result"]
    waits = [e for e in evs if e["name"] == "queue.wait"]
    slices = [e for e in evs if e["name"] == "queue.slice"]
    assert len(results) == 2 and len(waits) == 2 and len(slices) == 1
    assert _contains(results[0], waits[0])
    assert _contains(results[0], slices[0])
    assert waits[0]["ts"] + waits[0]["dur"] <= slices[0]["ts"] + 1e-6
    assert _contains(results[1], waits[1])
    # the demand flush ran inside the wait
    flush = next(e for e in evs if e["name"] == "queue.flush")
    assert _contains(waits[0], flush)


def test_req_and_flush_link_submit_flush_result(tracer):
    """Submits on the caller's thread, the flush on another, each result
    on a third: the export links them by ``req`` and ``flush``."""
    q = MicroBatchQueue(_stub, capacity=64, timer=False)
    futs = [q.submit(np.arange(i + 1, dtype=np.int32)) for i in range(3)]
    flushed, done = threading.Event(), threading.Event()

    def flusher():                    # alive until the client is done, so
        q.flush()                     # the three thread ids are distinct
        flushed.set()
        done.wait(10.0)

    def client():
        flushed.wait(10.0)
        for f in futs:
            f.result()

    threads = [threading.Thread(target=flusher),
               threading.Thread(target=client)]
    for t in threads:
        t.start()
    threads[1].join()
    done.set()
    threads[0].join()
    late = q.submit(np.arange(2, dtype=np.int32))
    late.result()
    evs = tracer.export()["traceEvents"]

    def by(name):
        return [e for e in evs if e["name"] == name]

    reqs = [f.req for f in futs] + [late.req]
    assert len(set(reqs)) == 4
    assert [e["args"]["req"] for e in by("queue.submit")] == reqs
    assert [e["args"]["flush"] for e in by("queue.flush")] == [1, 2]
    assert [e["args"]["flush"] for e in by("queue.dispatch")] == [1, 2]
    served = {e["args"]["req"]: e["args"]["flush"]
              for e in by("queue.result")}
    assert served == {reqs[0]: 1, reqs[1]: 1, reqs[2]: 1, reqs[3]: 2}
    assert [f.flush for f in futs] == [1, 1, 1] and late.flush == 2
    tids = {by(n)[0]["tid"] for n in ("queue.submit", "queue.flush",
                                      "queue.result")}
    assert len(tids) == 3


def test_index_lookup_spans(tracer):
    keys = np.arange(0, 4096, 2, dtype=np.int32)
    idx = build_index(keys, keys, IndexConfig(kind="binary"))
    res = idx.lookup(np.asarray([4, 5], np.int32))
    np.testing.assert_array_equal(np.asarray(res.found), [True, False])
    evs = tracer.events()
    outer = next(e for e in evs if e["name"] == "index.lookup")
    put = next(e for e in evs if e["name"] == "lookup.put")
    gather = next(e for e in evs if e["name"] == "lookup.gather")
    assert outer["args"] == {"kind": "binary"}
    assert _contains(outer, put) and _contains(outer, gather)
    assert put["ts"] + put["dur"] <= gather["ts"] + 1e-6


def test_stage_spans_reach_the_profiler_bare(tracer, tmp_path):
    """The spans enter ``jax.profiler.TraceAnnotation`` under their bare
    dotted names, so the benchmark's trace reduction (which keeps host
    events matching ``bench.tracing.SPAN_NAME``) sees every stage."""
    from jax.profiler import ProfileData
    from bench.tracing import SPAN_NAME

    keys = np.arange(0, 4096, 2, dtype=np.int32)
    idx = build_index(keys, keys, IndexConfig(kind="binary"))
    q = MicroBatchQueue(index_probe_fn(idx), capacity=8, timer=False)
    jax.device_get(q.submit(keys[:2]).result())            # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.device_get(q.submit(keys[2:4]).result())
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    names = {e.name for ln in host.lines for e in ln.events}
    want = {"queue.submit", "queue.result", "queue.wait", "queue.slice",
            "queue.flush", "queue.dispatch", "index.lookup", "lookup.put",
            "lookup.gather"}
    assert want <= names, want - names
    assert all(SPAN_NAME.match(n) for n in want)
