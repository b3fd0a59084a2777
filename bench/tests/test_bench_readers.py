"""Every per-layer reader: the arithmetic, and None (never 0) where there
is nothing to read. A reader file that defines ``cases()`` is run on its
own cases (``bench/record.py``); the others on the hand-made run record
below and their ``WANT`` row."""
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
                     traced_bytes={"read": 81_900_000} if reduction else {})


RED = Reduction(window_s=4.0, busy_s=0.01,
                device_s_by_category={"bench.probe": 0.002,
                                      "bench.result": 0.008},
                kernel_s={"pipeline:top_descent": 0.0005,
                          "pipeline:page_kernel": 0.0003,
                          "pipeline:branch_2_fun": 0.0001},
                span_counts={"bench.probe": 100})

# by the part of a reader's name before the first dot
WANT = {"queue_batch": 3.0, "fold_share": 10.0, "journal_share": 5.0,
        "lookup_device_ms": 0.02, "page_kernel_ms": 0.004,
        "lookup_roofline": 5.0, "device_idle": 99.75}
TRACE_ONLY = ("lookup_device_ms", "page_kernel_ms", "lookup_roofline",
              "device_idle")


def reader_cases(name: str, root: Path = ROOT) -> tuple:
    """(reader, [(run record, value it must read), ...])."""
    reader = run.metric_reader(name, root)
    path = root / "bench" / "metrics" / f"{name}.py"
    if hasattr(reader, "cases"):
        cases = reader.cases()
        assert cases, f"{path}: cases() returned none"
        return reader, cases
    stem = name.split(".")[0]
    if stem not in WANT:
        pytest.fail(f"{path} defines no cases() and has no WANT row in "
                    f"{Path(__file__).name}")
    cases = [(record(RED), WANT[stem])]
    if stem in TRACE_ONLY:
        cases.append((record(None), None))
    return reader, cases


def check_reader(name: str, root: Path = ROOT) -> None:
    reader, cases = reader_cases(name, root)
    for i, (rec, want) in enumerate(cases):
        got = reader.read(rec)
        if want is None:
            assert got is None, (name, i, got)
        else:
            assert got == pytest.approx(want), (name, i, got)


@pytest.mark.parametrize("name", NAMES)
def test_reader(name):
    check_reader(name)


def test_a_reader_with_neither_case_nor_row_fails(tmp_path):
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    path = tmp_path / "bench" / "metrics" / "new_stage_ms.read.py"
    path.write_text("def read(run):\n    return 1.0\n")
    with pytest.raises(pytest.fail.Exception, match="new_stage_ms.read.py"):
        check_reader("new_stage_ms.read", tmp_path)
