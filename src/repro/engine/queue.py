"""Cross-request micro-batch scheduler (DESIGN.md §7) with a multi-tenant
admission tier (§7.1).

The batch-oriented structures in this repo only pay off when batches are
deep: the sort-and-bucket schedule's occupancy (DESIGN.md §2.1) collapses
at low per-request concurrency — a single request's handful of point
lookups launches a near-empty grid step. This module is the scale lever in
front of the tiered engine: an **aggregation queue** that accumulates point
lookups across serving requests and feeds them to the zero-host-sync fused
dispatch as one deep batch — the batch-aggregation move of BS-tree
(arXiv 2505.01180) and the FPGA level-wise batch paper (arXiv 2604.21117),
applied across requests instead of within one.

Mechanics:

* ``submit(queries, tenant=...)`` enqueues one caller's point lookups on
  its tenant's lane and returns a :class:`QueueFuture`; callers never see
  each other — each future resolves to exactly its own results, in its own
  submitted order (the fused pipeline un-permutes internally, so slicing
  the concatenated result by arrival offsets restores per-caller request
  order). Submissions may be arbitrary pytrees whose leaves share a
  leading batch axis (the decode path submits ``(cdf, u)`` pairs —
  ``kernels.cdf_search.cdf_probe_fn``).
* A flush — ONE fused dispatch — triggers on **capacity** (pending queries
  reach the adaptive ``flush_at`` threshold, or the hard ``capacity``), on
  **deadline** (the oldest pending submit has waited the *effective*
  window; a daemon timer guards callers that never block), or on **demand**
  (a caller blocks on ``result()``). What a flush admits is decided by the
  weighted-fair admission policy (``engine/admission.py``): whole submits,
  round-robin across tenant lanes, any tenant hard-capped at
  ``max_share * capacity`` queries per flush — a hog's backlog defers to
  later flushes instead of starving everyone else out of the dispatch.
* **Adaptive deadline**: an EWMA arrival-rate estimate scales the flush
  window by the depth the traffic can actually deliver
  (``admission.effective_deadline``) — light traffic stops paying the full
  window for a batch that cannot deepen.
* **Occupancy feedback**: the executed plan's step count rides back out of
  the fused dispatch (``engine/store.py``) as a lazily-resolved thunk.
  Thunks resolve (one device-scalar read each) at the start of the *next*
  flush — when the prior dispatch has retired, or sits ahead of ours on
  the device stream anyway — never in ``submit``, so enqueueing a request
  cannot stall on device execution. Low executed occupancy means buckets
  were shallow — the queue raises ``flush_at`` (wait for deeper batches);
  occupancy at or above target halves it back toward ``min_flush``. The
  occupancy is also attributed to the flush's tenants by lane share
  (``schedule.occupancy_shares``) for the per-tenant stats.

The queue holds *queries*, not result copies: results stay device-resident
pytree slices, and a flush adds no host↔device sync beyond what the
wrapped ``search_fn`` itself does (transfer-guard tested).
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .admission import (AdmissionPolicy, QueueOverflow, RateEstimator,
                        TenantStats, effective_deadline)
from .schedule import _next_pow2, occupancy_shares
from ..obs import get_registry, span

DEFAULT_TENANT = "default"


@dataclass
class QueueStats:
    """Counters + executed-plan occupancy aggregate (mean over flushes that
    reported feedback). ``flush_at`` mirrors the current adaptive
    threshold so callers can watch the steering; ``tenants`` carries the
    per-tenant ledger (admission.TenantStats)."""
    submits: int = 0
    queries: int = 0
    flushes: int = 0
    capacity_flushes: int = 0
    deadline_flushes: int = 0
    demand_flushes: int = 0
    manual_flushes: int = 0
    capped_flushes: int = 0       # flushes that left admissible work behind
    drops: int = 0                # submits rejected by a backlog limit
    max_batch: int = 0
    occ_sum: float = 0.0
    occ_n: int = 0
    flush_at: int = 0
    tenants: Dict[Any, TenantStats] = field(default_factory=dict)

    @property
    def mean_occupancy(self) -> float:
        return self.occ_sum / self.occ_n if self.occ_n else 0.0

    @property
    def mean_batch(self) -> float:
        return self.queries / self.flushes if self.flushes else 0.0

    def tenant(self, key) -> TenantStats:
        ts = self.tenants.get(key)
        if ts is None:
            ts = self.tenants[key] = TenantStats()
        return ts


class QueueFuture:
    """Result handle for one ``submit``. ``result()`` flushes the queue on
    demand if the batch has not gone out yet (so a lone synchronous caller
    pays one dispatch, not one deadline); under admission caps the demand
    loop keeps flushing until *this* caller's submit is admitted.

    Resolution stores the *shared* flush result plus this caller's slice
    bounds; the per-caller slice is taken lazily on first ``result()`` —
    slicing a device array stages a device op, and doing it at consumption
    time keeps the flush itself free of anything but the fused dispatch
    (the transfer-guard contract).

    ``req`` is the queue's id for this submit and ``flush`` the sequence
    number of the flush that served it (None until then): the ``queue.*``
    spans carry both, so one request's submit, flush and result link up in
    a trace export across the threads that ran them."""

    def __init__(self, queue: "MicroBatchQueue", req: int):
        self._queue = queue
        self.req = req
        self.flush: Optional[int] = None
        self._event = threading.Event()
        self._raw: Any = None
        self._bounds: Optional[tuple] = None
        self._value: Any = None
        self._sliced = False
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until resolved WITHOUT demand-flushing — the passive twin
        of ``result()`` for callers (and tests) that want the queue's own
        triggers (deadline timer, other callers) to do the flushing."""
        return self._event.wait(timeout)

    def _resolve(self, shared_result: Any, lo: int, hi: int, flush: int):
        self._raw = shared_result
        self._bounds = (lo, hi)
        self.flush = flush
        self._event.set()

    def _reject(self, err: BaseException):
        self._error = err
        self._event.set()

    def result(self, timeout: Optional[float] = None) -> Any:
        """Two stages, each a span and a histogram: ``queue.wait`` until
        the batch is resolved (demand flushes included), then, on the
        first call only, ``queue.slice`` staging this caller's rows."""
        queue = self._queue
        path = queue.path
        with span("queue.result", path=path, req=self.req) as sp:
            with span("queue.wait", path=path, req=self.req):
                t0 = time.perf_counter()
                try:
                    self._wait(timeout)
                finally:
                    queue._metric("histogram",
                                  "queue_result_wait_seconds").observe(
                        time.perf_counter() - t0)
            sp.set(flush=self.flush)
            if self._error is not None:
                raise self._error
            if not self._sliced:
                with span("queue.slice", path=path, req=self.req):
                    t0 = time.perf_counter()
                    lo, hi = self._bounds
                    self._value = jax.tree.map(lambda leaf: leaf[lo:hi],
                                               self._raw)
                    queue._metric("histogram",
                                  "queue_slice_seconds").observe(
                        time.perf_counter() - t0)
                self._raw = None              # drop the shared batch ref
                self._sliced = True
            return self._value

    def _wait(self, timeout: Optional[float]):
        while not self._event.is_set():
            # demand-flush until OUR submit is admitted: a capped flush can
            # serve other tenants first, so one flush is not always enough
            if self._queue.flush(reason="demand") == 0 and \
                    not self._event.is_set():
                break                         # nothing pending anywhere
        if not self._event.wait(timeout):
            raise TimeoutError("micro-batch result not ready")


class _TimedLock:
    """``with queue._locked(op):`` — a class, not a generator, as it is
    taken on every submit and flush."""
    __slots__ = ("_queue", "_op")

    def __init__(self, queue: "MicroBatchQueue", op: str):
        self._queue = queue
        self._op = op

    def __enter__(self):
        q = self._queue
        t0 = time.perf_counter()
        q._lock.acquire()
        try:
            q._metric("histogram", "queue_lock_wait_seconds",
                      op=self._op).observe(time.perf_counter() - t0)
        except BaseException:
            q._lock.release()
            raise

    def __exit__(self, *exc):
        self._queue._lock.release()
        return False


def _leading_dim(queries) -> int:
    leaves = jax.tree.leaves(queries)
    if not leaves:
        return 0
    n = int(leaves[0].shape[0])
    for leaf in leaves[1:]:
        if int(leaf.shape[0]) != n:
            raise ValueError("submission leaves must share a leading axis")
    return n


class MicroBatchQueue:
    """Deadline/capacity micro-batcher over a batched ``search_fn``, with
    per-tenant weighted-fair admission.

    ``search_fn(queries) -> (result, occupancy_thunk)`` — one fused
    dispatch over the whole batch; ``result`` is any pytree whose leaves
    have the batch as their leading axis (ranks, a LookupResult, ...);
    ``occupancy_thunk`` is a zero-arg callable yielding the executed plan's
    lane occupancy (or None when the engine has no feedback to give).
    ``MutableIndex.lookup`` + ``pop_plan_feedback`` is the canonical
    pairing — see :func:`index_probe_fn`; the decode-step twin is
    ``kernels.cdf_search.cdf_probe_fn``.

    ``flush_at`` (the adaptive capacity trigger) starts at ``min_flush``
    and is steered within [min_flush, capacity] by occupancy feedback;
    ``capacity`` is both the hard trigger and the flush budget the
    admission policy packs against. A single submit larger than capacity
    is legal — it flushes as one deep batch (admission never splits a
    caller). ``max_share`` caps any tenant's slice of one flush;
    ``set_weight`` steers the round-robin interleave. ``max_backlog`` (>0)
    rejects a tenant's submits once its pending backlog exceeds that many
    queries (``admission.QueueOverflow`` — the drop path; default
    unlimited). ``adaptive_deadline`` scales the flush window by the EWMA
    arrival rate (``deadline_floor_s`` bounds it below).
    ``now_fn``/``timer`` exist for deterministic tests and the
    virtual-clock benchmark (``benchmarks/bench_queue.py``).

    Flushed batches are padded to the next power of two (``pad_pow2``) with
    zero-queries whose lanes no caller slice ever reads: flush sizes are
    data-dependent, and without the ladder every distinct size would
    re-trace the fused dispatch — the same O(log Q) shape-family argument
    as the schedule's grid ladder (DESIGN.md §2.1).
    """

    def __init__(self, search_fn: Callable, *, capacity: int = 4096,
                 deadline_s: float = 0.002, min_flush: int = 64,
                 adapt: bool = True, occupancy_target: float = 0.5,
                 pad_pow2: bool = True, max_share: float = 1.0,
                 quantum: int = 32, max_backlog: int = 0,
                 adaptive_deadline: bool = False,
                 deadline_floor_s: float = 1e-4, rate_alpha: float = 0.3,
                 record_flushes: bool = False,
                 now_fn: Callable[[], float] = time.monotonic,
                 timer: bool = True, path: str = "probe"):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        if deadline_s < 0:
            raise ValueError(f"deadline must be >= 0, got {deadline_s}")
        if max_backlog < 0:
            raise ValueError(f"max_backlog must be >= 0, got {max_backlog}")
        self._search_fn = search_fn
        self.path = str(path)       # registry/span label: "probe", "decode"
        self.capacity = int(capacity)
        self.pad_pow2 = bool(pad_pow2)
        self.deadline_s = float(deadline_s)
        self.deadline_floor_s = min(float(deadline_floor_s), self.deadline_s)
        self.adaptive_deadline = bool(adaptive_deadline)
        self.min_flush = max(1, min(int(min_flush), self.capacity))
        self.adapt = bool(adapt)
        self.occupancy_target = float(occupancy_target)
        self.flush_at = self.min_flush
        self.max_backlog = int(max_backlog)
        self.admission = AdmissionPolicy(self.capacity, max_share=max_share,
                                         quantum=quantum)
        self._rate = RateEstimator(alpha=rate_alpha)
        self._now = now_fn
        self._use_timer = bool(timer)
        self._lock = threading.RLock()
        self._req_ids = itertools.count()
        self._flush_seq = 0
        self._metrics_reg = None      # registry self._metrics resolve in
        self._metrics: Dict[tuple, Any] = {}
        # per-tenant FIFO lanes of (queries, q_n, future, t_enqueued)
        self._lanes: Dict[Any, deque] = {}
        self._pending_queries = 0
        self._oldest_t: Optional[float] = None
        self._timer: Optional[threading.Timer] = None
        self._closed = False
        # unresolved (occ_thunk, real, dispatched, tenant_counts)
        self._feedback: list = []
        # per-flush admission ledger (reason/counts/total) for the fairness
        # property suite and the bench cap gate; None unless requested
        self.flush_log: Optional[list] = [] if record_flushes else None
        # pytree spec of the last non-empty submission, for the all-empty
        # flush: (treedef, [(trailing_shape, dtype), ...])
        self._spec = (jax.tree.structure(0), [((), np.dtype(np.int32))])
        self.stats = QueueStats(flush_at=self.flush_at)

    # ------------------------------------------------------------- tenants
    def set_tenant_weight(self, tenant, weight: float):
        """Live round-robin weight reconfiguration (default 1.0): under
        contention a weight-w tenant earns admission credit w times as
        fast. Taken under the queue lock — flushes hold the same lock, so
        the rescaled deficit can never be observed mid-``plan()``."""
        with self._lock:
            self.admission.set_weight(tenant, weight)

    # legacy spelling
    set_weight = set_tenant_weight

    def set_max_share(self, max_share: float):
        """Live per-flush share-cap reconfiguration: carried deficits are
        re-clamped under the queue lock, so a tightened cap binds from
        the very next flush."""
        with self._lock:
            self.admission.set_max_share(max_share)

    def effective_deadline(self) -> float:
        """The flush window currently in force: ``deadline_s`` scaled by
        the EWMA arrival rate when ``adaptive_deadline`` is on."""
        if not self.adaptive_deadline:
            return self.deadline_s
        need = min(self.flush_at, self.capacity) - self._pending_queries
        return effective_deadline(self.deadline_s, self.deadline_floor_s,
                                  self._rate.rate, need)

    # ------------------------------------------------------------- enqueue
    def submit(self, queries, tenant=DEFAULT_TENANT) -> QueueFuture:
        """Enqueue one caller's point lookups on ``tenant``'s lane; returns
        a future for exactly those results in the caller's order. May flush
        inline (capacity trigger). Never blocks on the device: feedback
        resolution happens at the next flush (whose dispatch waits on the
        device anyway), not here."""
        if not isinstance(queries, jax.Array) and not isinstance(
                queries, (tuple, list, dict)):
            queries = np.asarray(queries)
        q_n = _leading_dim(queries)
        fut = QueueFuture(self, next(self._req_ids))
        with span("queue.submit", path=self.path, tenant=tenant, n=q_n,
                  req=fut.req), self._locked("submit"):
            if self._closed:
                raise RuntimeError("submit on a closed MicroBatchQueue")
            ts = self.stats.tenant(tenant)
            lane = self._lanes.get(tenant)
            if lane is None:
                lane = self._lanes[tenant] = deque()
            if self.max_backlog and q_n and \
                    self._lane_queries(lane) + q_n > self.max_backlog:
                ts.drops += 1
                self.stats.drops += 1
                self._metric("counter", "queue_drops",
                             tenant=str(tenant)).inc()
                fut._reject(QueueOverflow(
                    f"tenant {tenant!r} backlog over {self.max_backlog} "
                    f"queries"))
                return fut
            now = self._now()
            if q_n:
                leaves = jax.tree.leaves(queries)
                self._spec = (jax.tree.structure(queries),
                              [(tuple(leaf.shape[1:]), np.dtype(leaf.dtype))
                               for leaf in leaves])
                self._rate.observe(now, q_n)
            lane.append((queries, q_n, fut, now))
            self._pending_queries += q_n
            if self._oldest_t is None:
                self._oldest_t = now
            self.stats.submits += 1
            self.stats.queries += q_n
            ts.submits += 1
            ts.queries += q_n
            self._metric("counter", "queue_submits",
                         tenant=str(tenant)).inc()
            self._metric("counter", "queue_queries",
                         tenant=str(tenant)).inc(q_n)
            if self._pending_queries >= min(self.flush_at, self.capacity):
                # admission packs at most `capacity` per flush; keep going
                # until the backlog is back under the trigger
                while self._pending_queries >= min(self.flush_at,
                                                   self.capacity):
                    if self._flush_locked("capacity") == 0:
                        break
            elif self._use_timer and self._timer is None:
                self._arm_timer(self.effective_deadline())
        return fut

    @staticmethod
    def _lane_queries(lane) -> int:
        return sum(n for _, n, _, _ in lane)

    def _metric(self, kind: str, name: str, **labels):
        """This queue's series of a registry metric (``kind`` is counter,
        gauge or histogram; label values are strings), resolved once per
        active registry: a registry lookup sorts and formats the labels,
        and a request touches a dozen series."""
        reg = get_registry()
        if self._metrics_reg is not reg:
            self._metrics, self._metrics_reg = {}, reg
        key = (name, *labels.values())
        m = self._metrics.get(key)
        if m is None:
            m = self._metrics[key] = getattr(reg, kind)(
                name, path=self.path, **labels)
        return m

    def _locked(self, op: str) -> "_TimedLock":
        """The queue lock, observing how long taking it waited
        (``queue_lock_wait_seconds{op}``): a flush stages its dispatch
        under the lock, so submits and other flushes queue behind it."""
        return _TimedLock(self, op)

    # -------------------------------------------------------------- flush
    def flush(self, reason: str = "manual") -> int:
        """Dispatch one admitted batch as ONE fused dispatch; returns the
        number of queries dispatched (0 when nothing was pending). Under
        admission caps a flush may leave work behind — it re-arms the
        deadline timer for the leftovers."""
        with self._locked(reason):
            return self._flush_locked(reason)

    def drain(self) -> int:
        """Flush until nothing is pending (close/shutdown helper);
        returns total queries dispatched."""
        total = 0
        with self._lock:
            while self._pending_queries or any(self._lanes.values()):
                n = self._flush_locked("manual")
                total += n
                if n == 0 and not any(self._lanes.values()):
                    break
        return total

    def _flush_locked(self, reason: str) -> int:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if not any(self._lanes.values()):
            return 0
        self._flush_seq += 1
        seq = self._flush_seq
        with span("queue.flush", path=self.path, reason=reason, flush=seq):
            return self._flush_admitted(reason, seq)

    def _flush_admitted(self, reason: str, seq: int) -> int:
        # resolve the previous flush's occupancy feedback now: its dispatch
        # has retired (or is about to, ahead of ours on the device stream),
        # so this never stalls an enqueueing caller the way draining in
        # submit() would
        self.drain_feedback()
        admit = self.admission.plan(
            {t: [n for _, n, _, _ in lane]
             for t, lane in self._lanes.items() if lane})
        now = self._now()
        batch = []                          # (queries, q_n, fut, tenant)
        for t in admit.service:
            queries, q_n, fut, t_enq = self._lanes[t].popleft()
            batch.append((queries, q_n, fut, t))
            ts = self.stats.tenant(t)
            ts.admitted += q_n
            wait = max(now - t_enq, 0.0)
            ts.wait_s += wait
            ts.wait_max_s = max(ts.wait_max_s, wait)
            self._metric("counter", "queue_admitted",
                         tenant=str(t)).inc(q_n)
            self._metric("histogram", "queue_wait_seconds",
                         tenant=str(t)).observe(wait)
        if not batch:
            return 0
        total = admit.total
        self._pending_queries -= total
        leftovers = False
        for t, lane in self._lanes.items():
            if lane:
                leftovers = True
                self.stats.tenant(t).deferred += len(lane)
                self._metric("counter", "queue_deferred",
                             tenant=str(t)).inc(len(lane))
        self._oldest_t = min(
            (lane[0][3] for lane in self._lanes.values() if lane),
            default=None)
        self.stats.flushes += 1
        if leftovers:
            self.stats.capped_flushes += 1
        self.stats.max_batch = max(self.stats.max_batch, total)
        for t, n in admit.counts.items():
            if n or t in {b[3] for b in batch}:
                self.stats.tenant(t).flushes += 1
                self._metric("counter", "queue_tenant_flushes",
                             tenant=str(t)).inc()
        if self.flush_log is not None:
            subs: Dict[Any, int] = {}
            for t in admit.service:
                subs[t] = subs.get(t, 0) + 1
            self.flush_log.append({"reason": reason,
                                   "counts": dict(admit.counts),
                                   "submits": subs, "total": total})
        counter = f"{reason}_flushes"
        if not hasattr(self.stats, counter):   # free-text reason: file under
            counter = "manual_flushes"         # manual instead of raising
        setattr(self.stats, counter, getattr(self.stats, counter) + 1)
        self._metric("counter", "queue_flushes", reason=reason).inc()
        self._metric("histogram", "queue_batch_size").observe(total)
        self._metric("gauge", "queue_flush_at").set(self.flush_at)
        try:
            parts = [q for q, n, _, _ in batch if n]
            pad = (_next_pow2(total) - total) if (self.pad_pow2 and total) \
                else 0
            q = self._concat(parts, pad)
            # dispatch-boundary timer: measures the host-side *staging*
            # cost of the one fused dispatch (search_fn returns without
            # waiting on the device), so observing it adds no sync
            with span("queue.dispatch", path=self.path, n=total, pad=pad,
                      flush=seq):
                t0 = time.perf_counter()
                result, occ_thunk = self._search_fn(q)
                self._metric("histogram",
                             "dispatch_staging_seconds").observe(
                    time.perf_counter() - t0)
            if occ_thunk is not None:
                # the engine saw the padded batch; scale its occupancy back
                # to real queries so pad lanes never flatter the steering
                self._feedback.append((occ_thunk, total, total + pad,
                                       dict(admit.counts)))
            lo = 0
            for _, n, fut, _ in batch:
                hi = lo + n
                fut._resolve(result, lo, hi, seq)
                lo = hi
        except BaseException as e:            # noqa: BLE001 — futures must not hang
            for _, _, fut, _ in batch:
                fut._reject(e)
            raise
        finally:
            if leftovers and self._use_timer and not self._closed \
                    and self._timer is None:
                age = self._now() - (self._oldest_t or self._now())
                self._arm_timer(self.effective_deadline() - age)
        return total

    def _concat(self, parts: list, pad: int):
        """Concatenate submissions (pytrees sharing a structure) leaf-wise
        along the batch axis, appending ``pad`` zero rows; an all-empty
        flush materializes zero-length leaves from the recorded spec."""
        if not parts:
            treedef, specs = self._spec
            return jax.tree.unflatten(
                treedef, [np.zeros((0,) + shape, dt)
                          for shape, dt in specs])

        def cat(*leaves):
            arrs = list(leaves)
            on_device = any(isinstance(a, jax.Array) for a in arrs)
            if pad:                           # device-side pad: no transfer
                zeros = jnp.zeros if on_device else np.zeros
                arrs.append(zeros((pad,) + tuple(arrs[0].shape[1:]),
                                  arrs[0].dtype))
            if len(arrs) == 1:
                return arrs[0]
            if on_device:
                return jnp.concatenate([jnp.asarray(a) for a in arrs])
            return np.concatenate(arrs)

        return jax.tree.map(cat, *parts)

    # ----------------------------------------------------------- deadline
    def _arm_timer(self, delay: Optional[float] = None):
        timer_box = []
        timer = threading.Timer(max(delay if delay is not None
                                    else self.deadline_s, 1e-4),
                                lambda: self._on_deadline(timer_box[0]))
        timer_box.append(timer)
        timer.daemon = True
        self._timer = timer
        timer.start()

    def _on_deadline(self, me: threading.Timer):
        with self._locked("deadline"):
            if self._closed or self._timer is not me:
                return                        # closed, or cancelled and
            self._timer = None                # superseded: a newer timer
            if not any(self._lanes.values()):  # owns the batch
                return
            window = self.effective_deadline()
            age = self._now() - (self._oldest_t or 0.0)
            if age + 1e-6 >= window:
                self._deadline_flush(age, window)
            else:                             # raced a fresh batch: re-arm
                self._arm_timer(window - age)

    def _deadline_flush(self, age: float, window: float) -> int:
        """Flush on the deadline, observing how far past the effective
        window the oldest submit got (``queue_deadline_late_seconds``)."""
        self._metric("histogram", "queue_deadline_late_seconds").observe(
            max(age - window, 0.0))
        return self._flush_locked("deadline")

    def poll(self) -> int:
        """Timer-free deadline check (virtual-clock benchmarks / manual
        drivers): flush iff the oldest pending submit has aged past the
        effective window."""
        with self._locked("deadline"):
            if any(self._lanes.values()):
                window = self.effective_deadline()
                age = self._now() - self._oldest_t
                if age >= window:
                    return self._deadline_flush(age, window)
        return 0

    # ----------------------------------------------------------- feedback
    def drain_feedback(self):
        """Resolve executed-plan occupancy thunks (one device-scalar read
        each — called at the next flush, from stats readers, or explicitly;
        never from submit, which must not block on the device) and steer
        ``flush_at``: shallow buckets -> wait deeper; target met -> decay
        back toward min_flush. Occupancy is scaled to *real* queries so the
        pow2 pad lanes never flatter the signal, and attributed to the
        flush's tenants by lane share for the per-tenant ledger."""
        with self._lock:
            pending, self._feedback = self._feedback, []
        for thunk, real, dispatched, counts in pending:
            occ = float(thunk()) * (real / dispatched if dispatched else 0.0)
            self.stats.occ_sum += occ
            self.stats.occ_n += 1
            self._metric("histogram", "queue_flush_occupancy").observe(occ)
            for t, share in occupancy_shares(counts, occ).items():
                ts = self.stats.tenant(t)
                ts.occ_sum += share
                ts.occ_n += 1
                self._metric("histogram", "queue_occupancy",
                             tenant=str(t)).observe(share)
            if not self.adapt:
                continue
            if occ < self.occupancy_target:
                self.flush_at = min(self.flush_at * 2, self.capacity)
            else:
                self.flush_at = max(self.flush_at // 2, self.min_flush)
        self.stats.flush_at = self.flush_at

    # -------------------------------------------------------------- admin
    @property
    def closed(self) -> bool:
        return self._closed

    def close(self):
        """Drain leftovers and cancel the deadline timer. Idempotent, and
        safe against a timer firing concurrently: the close flag is set
        under the lock before the final drain, so a racing timer callback
        (which re-checks the flag and its own identity under the same
        lock) can never flush into a shut-down queue; submits after close
        raise instead of landing on a dead lane."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            while any(self._lanes.values()):
                if self._flush_locked("manual") == 0:
                    break                     # defensive: cannot starve
        self.drain_feedback()


@dataclass
class TenantRow:
    """One (path, tenant) line of the serving dashboard, rendered from the
    metrics registry — the single source the per-tenant printout and
    ``EngineStats.tenants`` both read (no more hand-merged ledger dicts)."""
    path: str
    tenant: str
    submits: int = 0
    queries: int = 0
    flushes: int = 0
    admitted: int = 0
    deferred: int = 0
    drops: int = 0
    wait_mean_us: float = 0.0
    wait_max_us: float = 0.0
    occupancy: float = 0.0


def tenant_summary(registry=None) -> list:
    """Render every (path, tenant) series in the registry as
    :class:`TenantRow` views, sorted by (path, tenant). This is the
    de-duplicated stats helper: wait moments come from the
    ``queue_wait_seconds`` histogram, occupancy from ``queue_occupancy``,
    counts from the queue counter families."""
    reg = registry if registry is not None else get_registry()
    keys = set()
    for name in ("queue_submits", "queue_queries", "queue_drops"):
        for labels, _ in reg.series(name):
            if "path" in labels and "tenant" in labels:
                keys.add((labels["path"], labels["tenant"]))
    rows = []
    for path, tenant in sorted(keys):
        def count(name):
            m = reg.value(name, path=path, tenant=tenant)
            return int(m.value) if m is not None else 0

        wait = reg.value("queue_wait_seconds", path=path, tenant=tenant)
        occ = reg.value("queue_occupancy", path=path, tenant=tenant)
        rows.append(TenantRow(
            path=path, tenant=tenant,
            submits=count("queue_submits"),
            queries=count("queue_queries"),
            flushes=count("queue_tenant_flushes"),
            admitted=count("queue_admitted"),
            deferred=count("queue_deferred"),
            drops=count("queue_drops"),
            wait_mean_us=wait.mean * 1e6 if wait is not None else 0.0,
            wait_max_us=(wait.max * 1e6
                         if wait is not None and wait.count else 0.0),
            occupancy=occ.mean if occ is not None else 0.0))
    return rows


def index_probe_fn(index) -> Callable:
    """Adapt an index into the queue's ``search_fn`` contract: one fused
    ``lookup`` dispatch returning (LookupResult, occupancy_thunk). Works
    with ``engine.store.MutableIndex`` (full feedback via
    ``pop_plan_feedback``) and any ``core.api.Index`` (no feedback)."""
    pop = getattr(index, "pop_plan_feedback", None)

    def probe(q):
        res = index.lookup(q)
        return res, (pop() if pop is not None else None)

    return probe
