"""What a run hands the per-layer metric readers (``bench/metrics/``).

Each reader is a file ``bench/metrics/<metric name>.py`` with one function
``read(run: RunRecord) -> float | None``. It returns None where the run
holds nothing for it to read, and the harness then leaves the metric out;
it never returns 0 for a share it could not measure.

A reader file may also define its own test case, ``cases() -> [(record,
want), ...]``: run records and the value the reader must read from each,
``None`` where the record holds nothing to read
(``bench/tests/test_bench_readers.py`` runs them). A reader of a
histogram's mean takes its cases from ``hist_mean_cases``.

Registry growth over a traced run's window leaves the traced part out:
there the program's ``obs`` spans and the profiler are on, and slow the
stages its timers measure.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

PROBE_SPAN = "bench.probe"    # the harness's span around each lookup call


@dataclass
class RunRecord:
    window_s: float             # the measured window, host clock
    registry: tuple             # obs registry snapshots (before, after)
    queue: dict = field(default_factory=dict)  # window QueueStats counts
    reduction: Optional[Any] = None  # tracing.Reduction of the traced part
    peaks: dict = field(default_factory=dict)  # chip's row of peaks.py
    # least bytes of the traced dispatches, by operation (LEAST_BYTES of
    # the deployment kind, bench/deployment.py)
    traced_bytes: dict = field(default_factory=dict)
    # registry snapshots at the start and the end of the traced part, and
    # the seconds between them; () and 0 where nothing was traced
    traced_registry: tuple = ()
    traced_s: float = 0.0

    def _growth(self, name: str, key: str, labels: dict) -> float:
        """Growth over the window, less that over the traced part."""
        def total(snap):
            return sum(row[key] for row in snap.get(name, [])
                       if all(row["labels"].get(k) == v
                              for k, v in labels.items()))

        def grown(pair):
            return total(pair[1]) - total(pair[0]) if pair else 0.0
        return grown(self.registry) - grown(self.traced_registry)

    def hist_sum(self, name: str, **labels) -> float:
        """Growth over the untraced window of a registry histogram's
        sum."""
        return self._growth(name, "sum", labels)

    def hist_mean_ms(self, name: str, **labels) -> Optional[float]:
        """Mean of a registry histogram's observations (seconds) over the
        untraced window, in ms: growth of its sum over growth of its
        count; None where the count did not grow."""
        n = self._growth(name, "count", labels)
        return self._growth(name, "sum", labels) / n * 1e3 if n > 0 else None

    def dispatches(self) -> int:
        """Lookup calls inside the traced part (spans in the trace)."""
        if self.reduction is None:
            return 0
        return self.reduction.span_counts.get(PROBE_SPAN, 0)

    def device_s(self, span: str) -> Optional[float]:
        """Device time of every program launched inside a harness span
        (``bench.probe``, or a client operation's ``bench.<name>``)."""
        if self.reduction is None:
            return None
        return self.reduction.device_s_by_category.get(span)

    def lookup_device_s(self) -> Optional[float]:
        """Device time of every program a lookup call launched."""
        return self.device_s(PROBE_SPAN)

    def per_dispatch_ms(self, seconds: Optional[float]) -> Optional[float]:
        n = self.dispatches()
        if seconds is None or not n:
            return None
        return seconds / n * 1e3

    def page_kernel_ms(self) -> Optional[float]:
        """Self time of the Pallas kernels of the lookup programs other
        than the top descent (the page kernel, under its ``lax.switch``
        branches), per lookup call."""
        r = self.reduction
        if r is None:
            return None
        s = sum(v for k, v in r.kernel_s.items()
                if not k.endswith(":top_descent"))
        return self.per_dispatch_ms(s) if s else None

    def queue_batch(self) -> Optional[float]:
        """Queries a flush over the window (QueueStats)."""
        f = self.queue.get("flushes", 0)
        return self.queue["queries"] / f if f else None

    def share_of_window(self, name: str, **labels) -> float:
        """Percent of the untraced window spent in a timed host
        operation."""
        return (100.0 * self.hist_sum(name, **labels)
                / (self.window_s - self.traced_s))

    def roofline(self, op: str, device_s: Optional[float]) -> Optional[float]:
        """Percent: least time of an operation's traced bytes at the HBM
        peak, over ``device_s``, the device time of its programs."""
        least_bytes = self.traced_bytes.get(op)
        if not device_s or not least_bytes:
            return None
        least = least_bytes / self.peaks["hbm_bytes_per_s"]
        return 100.0 * least / device_s

    def lookup_roofline(self) -> Optional[float]:
        """Percent: least time of the traced lookups' bytes at the HBM
        peak, over the device time of the programs they launched."""
        return self.roofline("read", self.lookup_device_s())

    def device_idle(self) -> Optional[float]:
        """Percent of the traced window with no program on the device."""
        r = self.reduction
        if r is None or not r.window_s:
            return None
        return 100.0 * (1.0 - r.busy_s / r.window_s)


def hist_mean_cases(hist: str, **labels) -> list:
    """The test cases of a reader of ``hist_mean_ms(hist, **labels)``: the
    series grew by 400 observations summing to 0.3 s (0.75 ms each); by
    the same plus 100 summing to 0.5 s inside a traced part, which the
    mean leaves out; not at all; or only inside the traced part. Series
    with one label changed grow all the while."""
    others = [dict(labels, **{k: "other"}) for k in labels]

    def snap(step, count, total):
        return {hist: [{"labels": labels, "count": count, "sum": total}]
                + [{"labels": o, "count": 5 + 7 * step,
                    "sum": 3.0 + 0.9 * step} for o in others]}

    def record(*snaps):
        before, *traced, after = [snap(i, *c) for i, c in enumerate(snaps)]
        return RunRecord(window_s=51.0, registry=(before, after),
                         traced_registry=tuple(traced),
                         traced_s=4.0 if traced else 0.0)
    return [(record((10, 0.02), (410, 0.32)), 0.75),
            (record((10, 0.02), (210, 0.17), (310, 0.67), (510, 0.82)), 0.75),
            (record((10, 0.02), (10, 0.02)), None),
            (record((10, 0.02), (10, 0.02), (50, 0.1), (50, 0.1)), None)]
