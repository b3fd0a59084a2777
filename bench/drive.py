"""The load the window offers, through the served path, timed on the host.

Each request is sent as its operation says (``bench/deployment.py``).
One sent through the queue is one ``submit`` to the program's
``MicroBatchQueue`` over the deployment's probe function (YCSB reads: one
key), then ``result()`` and ``jax.device_get`` on one client thread, in
send order, as a client does. One called on a client thread (YCSB
updates: the store's ``insert`` with the request's one key) runs on a
thread of its operation's own, in send order, so a call that blocks
delays no other request's send.

The loop is open: one sender sends each request at its due time and never
waits for a reply; latency runs from the due time to the answer on the
host (or the write's acknowledgement), so a stall also counts against the
requests queued behind it. How late the sender ran is recorded per
request.

With ``spans`` on, the harness's own spans (``bench.*``) are entered as
``jax.profiler.TraceAnnotation`` around each call into a layer, so the
trace reduction can attribute device time and idle gaps to them.
"""
from __future__ import annotations

import contextlib
import queue as _queue
import threading
import time

import jax
import numpy as np

from bench.deployment import QUEUE

NULL = contextlib.nullcontext()
RESULT_WAIT_S = 60.0       # how long past the close an answer is awaited


class Spans:
    def __init__(self):
        self.on = False

    def __call__(self, name: str):
        return jax.profiler.TraceAnnotation(name) if self.on else NULL


class Probe:
    """The queue's ``search_fn``: the program's probe function, with each
    dispatched batch recorded on the host (the roofline count reads it)."""

    def __init__(self, probe_fn, spans: Spans):
        self._fn = probe_fn
        self._spans = spans
        self.record = False
        self.batches = []             # (perf_counter, batch) while recording

    def __call__(self, batch):
        if self.record:
            self.batches.append((time.perf_counter(), batch))
        with self._spans("bench.probe"):
            return self._fn(batch)


def make_queue(probe: Probe, index_config):
    """The queue as the serving store builds it from ``IndexConfig``'s
    ``queue_*`` fields (``serve/kv_cache.PrefixPageStore.probe_queue``)."""
    from repro.engine.queue import MicroBatchQueue
    c = index_config
    return MicroBatchQueue(
        probe, capacity=c.queue_capacity, deadline_s=c.queue_deadline_s,
        min_flush=c.queue_min_flush, adapt=c.queue_adapt,
        max_share=c.queue_max_share,
        adaptive_deadline=c.queue_adaptive_deadline,
        deadline_floor_s=c.queue_deadline_floor_s,
        max_backlog=c.queue_max_backlog, path="probe")


def warm_reads(probe: Probe, keys: np.ndarray, capacity: int) -> int:
    """Compile every batch shape a window can dispatch: the queue pads each
    flush to a power of two up to its capacity. A queue of its own, which
    never flushes by itself, sends each size once; a few answers of each
    are sliced and pulled as a client does. Returns the shapes warmed."""
    from repro.engine.queue import MicroBatchQueue
    q = MicroBatchQueue(probe, capacity=capacity, min_flush=capacity,
                        adapt=False, timer=False, path="warmup")
    sizes = [1 << i for i in range(capacity.bit_length())
             if 1 << i <= capacity]
    at = 0
    for s in sizes:
        futs = [q.submit(keys[(at + j) % keys.size: (at + j) % keys.size
                              + 1]) for j in range(s)]
        at += s
        q.flush()
        for f in futs[:2] + futs[-1:]:
            jax.device_get(f.result())
    q.close()
    return len(sizes)


class Log:
    """Per-request records of one window, indexed by request number."""

    def __init__(self, n: int):
        self.t_due = np.full(n, np.nan)
        self.t_send = np.full(n, np.nan)
        self.t_done = np.full(n, np.nan)
        self.answer = [None] * n      # what the operation returned
        self.sent = np.zeros(n, bool)
        self.errors = []


class Clients:
    """Client threads: one that waits for the queue's answers, one for
    each operation called on a client thread that the window sends."""

    def __init__(self, log: Log, keys, requests, dep, operations: dict,
                 spans: Spans):
        self.log = log
        self.keys = keys
        self.args = requests.args
        self.dep = dep
        self.spans = spans
        self.reads = _queue.SimpleQueue()
        self._lock = threading.Lock()
        self.inflight = 0             # requests handed over, not finished
        self.rows = answer = None
        self.route = []               # per operation: its client queue,
        calls = []                    # or None: sent through the queue
        for name in requests.ops:
            op = operations[name]
            if op.via == QUEUE:
                self.rows, answer = op.rows, op.answer
                self.route.append(None)
                continue
            q = _queue.SimpleQueue()
            self.route.append(q)
            if requests.of(name).any():
                calls.append(threading.Thread(
                    target=self._caller, args=(name, op, q), daemon=True))
        self.threads = [threading.Thread(target=self._reader,
                                         args=(answer,), daemon=True)]
        self.threads += calls
        self._queues = [self.reads] + [q for q in self.route if q is not None]
        for t in self.threads:
            t.start()

    def _reader(self, answer):
        log = self.log
        while True:
            item = self.reads.get()
            if item is None:
                return
            i, fut = item
            try:
                with self.spans("bench.result"):
                    res = jax.device_get(fut.result(timeout=RESULT_WAIT_S))
                log.answer[i] = answer(res)
                log.t_done[i] = time.perf_counter()
            except Exception as e:            # noqa: BLE001 — a failed read
                log.errors.append(f"read {i}: {e!r}")
            self.done_one()

    def hand_over(self, q, item):
        with self._lock:
            self.inflight += 1
        q.put(item)

    def done_one(self):
        with self._lock:
            self.inflight -= 1

    def _caller(self, name: str, op, q):
        log = self.log
        span = f"bench.{name}"
        while True:
            i = q.get()
            if i is None:
                return
            log.t_send[i] = time.perf_counter()
            try:
                with self.spans(span):
                    log.answer[i] = op.call(self.dep, self.keys, self.args, i)
                log.t_done[i] = time.perf_counter()
            except Exception as e:            # noqa: BLE001 — a failed call
                log.errors.append(f"{name} {i}: {e!r}")
            self.done_one()

    def close(self, wait_s: float = RESULT_WAIT_S):
        """Wait for every request handed over to finish (answers are
        awaited up to ``wait_s`` past the close), then stop the threads.
        Returns whether all finished."""
        end = time.perf_counter() + wait_s
        while self.inflight and time.perf_counter() < end:
            time.sleep(0.001)
        for q in self._queues:
            q.put(None)
        for t in self.threads:
            t.join(max(0.0, end - time.perf_counter()))
        return not any(t.is_alive() for t in self.threads)


def send_queued(queue, clients: Clients, log: Log, i: int, spans: Spans):
    log.t_send[i] = time.perf_counter()
    log.sent[i] = True
    with spans("bench.send"):
        fut = queue.submit(clients.rows(clients.keys, clients.args, i))
    clients.hand_over(clients.reads, (i, fut))


def open_loop(queue, clients: Clients, op, due, t0: float,
              spans: Spans) -> None:
    """Send request i, of operation ``op[i]``, at ``t0 + due[i]``, never
    waiting for replies."""
    log = clients.log
    route = [clients.route[k] for k in op.tolist()]
    n = due.size
    log.t_due[:] = t0 + due
    i = 0
    while i < n:
        now = time.perf_counter()
        t = log.t_due[i]
        if t > now:
            time.sleep(t - now)
            continue
        while i < n and log.t_due[i] <= now:
            q = route[i]
            if q is None:
                send_queued(queue, clients, log, i, spans)
            else:
                log.sent[i] = True
                clients.hand_over(q, i)
            i += 1

