"""Every per-layer reader, on a hand-made run record: the arithmetic, and
None (never 0) where there is nothing to read."""
from pathlib import Path

import pytest

from bench import run
from bench.record import RunRecord
from bench.tracing import Reduction

ROOT = Path(__file__).resolve().parents[2]
NAMES = sorted(p.name[:-3] for p in (ROOT / "bench" / "metrics").glob("*.py"))
PEAKS = {"hbm_bytes_per_s": 819e9}


def snap(fold, journal):
    return {"engine_op_seconds": [
        {"labels": {"path": "fold"}, "sum": fold},
        {"labels": {"path": "journal"}, "sum": journal},
        {"labels": {"path": "lookup"}, "sum": 99.0}]}


def record(reduction):
    return RunRecord(window_s=20.0, registry=(snap(1.0, 0.5), snap(3.0, 1.5)),
                     queue={"queries": 600, "flushes": 200},
                     reduction=reduction, peaks=PEAKS,
                     traced_bytes=81_900_000 if reduction else 0)


RED = Reduction(window_s=4.0, busy_s=0.01,
                device_s_by_category={"bench.probe": 0.002,
                                      "bench.result": 0.008},
                kernel_s={"pipeline:top_descent": 0.0005,
                          "pipeline:page_kernel": 0.0003,
                          "pipeline:branch_2_fun": 0.0001},
                span_counts={"bench.probe": 100})

WANT = {"queue_batch": 3.0, "fold_share": 10.0, "journal_share": 5.0,
        "lookup_device_ms": 0.02, "page_kernel_ms": 0.004,
        "lookup_roofline": 5.0, "device_idle": 99.75}


@pytest.mark.parametrize("name", NAMES)
def test_reader(name):
    reader = run.metric_reader(name)
    want = WANT[name.split(".")[0]]
    assert reader.read(record(RED)) == pytest.approx(want)
    if name.split(".")[0] in ("lookup_device_ms", "page_kernel_ms",
                              "lookup_roofline", "device_idle"):
        assert reader.read(record(None)) is None
