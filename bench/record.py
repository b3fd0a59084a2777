"""What a run hands the per-layer metric readers (``bench/metrics/``).

Each reader is a file ``bench/metrics/<metric name>.py`` with one function
``read(run: RunRecord) -> float | None``. It returns None where the run
holds nothing for it to read, and the harness then leaves the metric out;
it never returns 0 for a share it could not measure.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

PROBE_SPAN = "bench.probe"    # the harness's span around each lookup call


@dataclass
class RunRecord:
    window_s: float             # the measured window, host clock
    registry: tuple             # obs registry snapshots (before, after)
    queue: dict                 # the window queue's QueueStats counts
    reduction: Optional[Any]    # tracing.Reduction of the traced part
    peaks: dict                 # the chip's row of bench/peaks.py
    traced_bytes: int = 0       # least bytes of the traced dispatches

    def hist_sum(self, name: str, **labels) -> float:
        """Growth over the window of a registry histogram's sum."""
        def total(snap):
            return sum(row["sum"] for row in snap.get(name, [])
                       if all(row["labels"].get(k) == v
                              for k, v in labels.items()))
        before, after = self.registry
        return total(after) - total(before)

    def dispatches(self) -> int:
        """Lookup calls inside the traced part (spans in the trace)."""
        if self.reduction is None:
            return 0
        return self.reduction.span_counts.get(PROBE_SPAN, 0)

    def lookup_device_s(self) -> Optional[float]:
        """Device time of every program a lookup call launched."""
        if self.reduction is None:
            return None
        return self.reduction.device_s_by_category.get(PROBE_SPAN)

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
        """Percent of the window spent in a timed host operation."""
        return 100.0 * self.hist_sum(name, **labels) / self.window_s

    def lookup_roofline(self) -> Optional[float]:
        """Percent: least time of the traced lookups' bytes at the HBM
        peak, over the device time of the programs they launched."""
        dev = self.lookup_device_s()
        if not dev or not self.traced_bytes:
            return None
        least = self.traced_bytes / self.peaks["hbm_bytes_per_s"]
        return 100.0 * least / dev

    def device_idle(self) -> Optional[float]:
        """Percent of the traced window with no program on the device."""
        r = self.reduction
        if r is None or not r.window_s:
            return None
        return 100.0 * (1.0 - r.busy_s / r.window_s)
