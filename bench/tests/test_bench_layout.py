"""The harness finds everything by name from BENCHMARK.json and files,
and BENCHMARK.json keeps to the benchmark's contract."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import deployment, run, tracing
from bench.record import RunRecord
from bench.tests.small import run_small
from bench.tests.test_bench_readers import check_reader

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_names_units_and_bounds():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + \
        [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "bound" not in m


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    spec = run.load_cell(cell)
    assert spec["cell"]["chips"] in (1, 4)
    kind = spec["config"]["kind"]
    assert hasattr(run.deploy_kind(kind), "build")
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert m["moves"] in e2e, (cell, m["name"])
        assert callable(run.metric_reader(m["name"]).read)


def test_configs_files_and_reduced():
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]


def test_a_new_cell_is_files_and_an_entry(tmp_path):
    """A new configuration, mix and metric, added as files plus entries
    in BENCHMARK.json, are found without editing any existing file."""
    before = copy_bench(tmp_path)
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "ycsb_frozen_2e27.json").read_text())
    cfg.update(name="ycsb_frozen_2e25", recordcount=2**25,
               kind="frozen_copy")
    (b / "configs" / "ycsb_frozen_2e25.json").write_text(json.dumps(cfg))
    (b / "deploy" / "frozen_copy.py").write_text(
        (b / "deploy" / "tiered_frozen.py").read_text())
    mix = json.loads((b / "traffic" / "ycsb_c.zipf.json").read_text())
    mix.update(name="ycsb_c.uniform", keys={"distribution": "uniform"})
    (b / "traffic" / "ycsb_c.uniform.json").write_text(json.dumps(mix))
    (b / "metrics" / "queue_batch.uniform.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "ycsb_frozen_2e25", "source": "x",
                            "file": "bench/configs/ycsb_frozen_2e25.json",
                            "reduced": ["recordcount"], "why": "x"})
    spec["workloads"].append({"name": "ycsb_c.uniform",
                              "config": "ycsb_frozen_2e25",
                              "traffic": "ycsb_c.uniform", "chips": 1,
                              "why": "x"})
    spec["end_to_end"][0]["workloads"].append("ycsb_c.uniform")
    spec["per_layer"].append({"name": "queue_batch.uniform",
                              "unit": "queries/flush", "better": "higher",
                              "source": "program_counter", "layer": "queue",
                              "moves": "read_p99_ms",
                              "workloads": ["ycsb_c.uniform"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    got = run.load_cell("ycsb_c.uniform", root=tmp_path)
    assert got["config"]["recordcount"] == 2**25
    assert got["traffic"]["keys"] == {"distribution": "uniform"}
    assert [m["name"] for m in got["per_layer"]] == ["queue_batch.uniform"]
    assert hasattr(run.deploy_kind("frozen_copy", root=tmp_path), "build")
    assert run.metric_reader("queue_batch.uniform",
                             root=tmp_path).read(None) == 42.0
    for p, data in before.items():
        assert p.read_bytes() == data, p


def copy_bench(root: Path) -> dict:
    """BENCHMARK.json and bench/ copied under ``root``; returns the bytes
    of every file copied under bench/ (BENCHMARK.json takes entries)."""
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return {p: p.read_bytes() for p in (root / "bench").rglob("*")
            if p.is_file()}


# A deployment kind with an operation the default does not serve: scans
# over the frozen tiered index, beside the default's point reads.
SCAN_KIND = '''"""Point reads and scans over the frozen tiered index. A scan
starts at the key of a record drawn from the mix's key distribution and
reads the next 1-100 records in key order (uniform), asking
Index.scan_range for their count and sum on a client thread of its own."""
from dataclasses import dataclass
from typing import Any

import jax
import numpy as np

from bench import roofline
from bench.deployment import CLIENT, POINT_OPERATIONS, Deployment, Operation
from bench.reference import Reference

OFF = 0


@dataclass
class ScanDeployment(Deployment):
    index: Any = None
    sorted_keys: Any = None      # the table's keys in order, for hi


def last_key(sorted_keys, lo, length):
    r = np.searchsorted(sorted_keys, lo) + length - 1
    return sorted_keys[np.minimum(r, sorted_keys.size - 1)]


def scan(dep, keys, args, i):
    lo = keys[i:i + 1]
    hi = last_key(dep.sorted_keys, lo, args["scan_len"][i:i + 1])
    res = dep.index.scan_range(lo, hi, aggs=("count", "sum"))
    count, total = jax.device_get((res.count, res.vsum))
    return int(count[0]) + OFF, int(total[0])


def build(config, table, workdir):
    from repro.core import IndexConfig, build_index
    from repro.engine.queue import index_probe_fn
    cfg = IndexConfig(**config["index_config"])
    index = build_index(table.keys, table.values, cfg)
    dep = ScanDeployment(index_config=cfg, probe_fn=index_probe_fn(index),
                         compare_rank=True, index=index,
                         sorted_keys=np.sort(table.keys))
    scan(dep, table.keys, {"scan_len": np.ones(1, np.int64)}, 0)  # warm
    return dep


OPERATIONS = {"read": POINT_OPERATIONS["read"],
              "scan": Operation(read=True, via=CLIENT, call=scan,
                                draw=lambda rng, n, n_records: {
                                    "scan_len": rng.integers(1, 101, n)})}


class ScanReference(Reference):
    def interval(self, lo, length):
        a = np.searchsorted(self.keys, lo)
        return a, np.minimum(a + length, self.keys.size)

    def check(self, outcome):
        checks = super().check(outcome)
        i = outcome.sent("scan")
        a, b = self.interval(outcome.keys[i],
                             outcome.requests.args["scan_len"][i])
        csum = np.concatenate([[0], np.cumsum(self.vals, dtype=np.int64)])
        want = zip((b - a).tolist(), (csum[b] - csum[a]).tolist())
        ok = outcome.answered(i)
        wrong = sum(1 for j, w, done in zip(i.tolist(), want, ok)
                    if done and tuple(outcome.log.answer[j]) != w)
        checks["wrong_scans"] = {"value": wrong, "limit": 0}
        checks["missing_scans"] = {"value": int((~ok).sum()), "limit": 0}
        return checks


def make_reference(config, table):
    return ScanReference(table.keys, table.values)


def scan_bytes(ref, batch):
    """The key and value tiles the scanned interval spans, 8 bytes in
    (lo, hi) and 8 out (count, sum)."""
    a, b = ref.interval(batch["key"], batch["scan_len"])
    tiles = (b - 1) // roofline.TILE_KEYS - a // roofline.TILE_KEYS + 1
    return int(2 * tiles.sum() * roofline.TILE_BYTES + 16 * a.size)


LEAST_BYTES = {"read": lambda ref, q: roofline.batch_bytes(ref.keys, q),
               "scan": scan_bytes}
'''

SCAN_READER = '''"""Percent of the HBM roofline the scans reach."""
from bench.record import RunRecord
from bench.tracing import Reduction


def read(run):
    return run.roofline("scan", run.device_s("bench.scan"))


def cases():
    red = Reduction(window_s=4.0, busy_s=0.01,
                    device_s_by_category={"bench.scan": 0.001,
                                          "bench.probe": 0.005})
    rec = RunRecord(window_s=20.0, registry=({}, {}), reduction=red,
                    peaks={"hbm_bytes_per_s": 819e9},
                    traced_bytes={"scan": 81_900_000, "read": 1})
    return [(rec, 10.0), (RunRecord(window_s=20.0, registry=({}, {})), None)]
'''


def keep_records(monkeypatch) -> list:
    """The run records a run hands its readers."""
    kept = []

    def record(**kw):
        kept.append(RunRecord(**kw))
        return kept[-1]
    monkeypatch.setattr(run, "RunRecord", record)
    return kept


def test_a_new_deployment_kind_is_files_and_entries(tmp_path, monkeypatch):
    """A deployment with an operation other than point reads and writes,
    its own reference, least bytes and a reader with its own case, added
    as new files and entries only, runs correct on the CPU; traced, its
    reader divides the traced scans' least bytes by their device time;
    with each scan's answer off by one it is not correct."""
    before = copy_bench(tmp_path)
    b = tmp_path / "bench"
    kind = b / "deploy" / "tiered_scan.py"
    kind.write_text(SCAN_KIND)
    cfg = json.loads((b / "configs" / "ycsb_frozen_2e27.json").read_text())
    cfg.update(name="ycsb_scan_2e27", kind="tiered_scan")
    (b / "configs" / "ycsb_scan_2e27.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "ycsb_c.zipf.json").read_text())
    mix.update(name="read_scan.zipf", mix={"read": 0.5, "scan": 0.5})
    (b / "traffic" / "read_scan.zipf.json").write_text(json.dumps(mix))
    (b / "metrics" / "scan_roofline.scan.py").write_text(SCAN_READER)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "ycsb_scan_2e27", "source": "x",
                            "file": "bench/configs/ycsb_scan_2e27.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "read_scan.zipf",
                              "config": "ycsb_scan_2e27",
                              "traffic": "read_scan.zipf", "chips": 1,
                              "why": "x"})
    spec["end_to_end"][0]["workloads"].append("read_scan.zipf")
    spec["per_layer"].append({"name": "scan_roofline.scan", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "kernels", "moves": "read_p50_ms",
                              "workloads": ["read_scan.zipf"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    check_reader("scan_roofline.scan", tmp_path)
    res, text = run_small("read_scan.zipf", root=tmp_path)
    assert res["correct"], text
    assert res["attempted"] == 150 and res["failed"] == 0
    assert res["checks"]["wrong_scans"]["value"] == 0
    assert res["checks"]["missing_scans"]["value"] == 0

    # a CPU trace has no TPU plane: the reduction stands in for the chip's
    kept = keep_records(monkeypatch)
    red = tracing.Reduction(window_s=0.3, busy_s=0.002,
                            device_s_by_category={"bench.scan": 0.001,
                                                  "bench.probe": 0.001})
    monkeypatch.setattr(tracing, "reduce", lambda path: red)
    res, text = run_small("read_scan.zipf", root=tmp_path, trace=1)
    assert res["correct"], text
    rec, = kept
    assert rec.traced_bytes["scan"] > 0 and rec.traced_bytes["read"] > 0
    assert res["metrics"]["scan_roofline.scan"]["value"] == pytest.approx(
        100.0 * rec.traced_bytes["scan"] / 819e9 / 0.001)
    monkeypatch.undo()

    kind.write_text(SCAN_KIND.replace("OFF = 0", "OFF = 1  # off by one"))
    res, text = run_small("read_scan.zipf", root=tmp_path)
    assert not res["correct"], text
    assert res["checks"]["wrong_scans"]["value"] > 0
    assert res["checks"]["wrong_reads"]["value"] == 0
    for p, data in before.items():
        assert p.read_bytes() == data, p


STAGE_SERIES = {
    "result_wait_ms.read": ("queue_result_wait_seconds", {"path": "probe"}),
    "result_slice_ms.read": ("queue_slice_seconds", {"path": "probe"}),
    "submit_lock_wait_ms.read": ("queue_lock_wait_seconds",
                                 {"path": "probe", "op": "submit"}),
    "deadline_late_ms.read": ("queue_deadline_late_seconds",
                              {"path": "probe"}),
    "probe_staging_ms.read": ("dispatch_staging_seconds", {"path": "probe"}),
}


def test_a_traced_run_reads_the_stage_means_outside_the_traced_part(
        monkeypatch):
    """A traced CPU run of the cell reports the five stage means, taken
    over the window less its traced part."""
    kept = keep_records(monkeypatch)
    res, text = run_small("ycsb_c.zipf", seconds=3.0, trace=1)
    assert res["correct"], text
    rec, = kept
    assert rec.traced_s > 0
    traced = RunRecord(window_s=rec.traced_s, registry=rec.traced_registry)
    whole = RunRecord(window_s=rec.window_s, registry=rec.registry)
    for name, (hist, labels) in STAGE_SERIES.items():
        got = res["metrics"][name]["value"]
        assert got == pytest.approx(rec.hist_mean_ms(hist, **labels))
        assert got > 0, name
    # reads were sliced inside the traced part too, and left out
    inside = traced.hist_sum("queue_slice_seconds", path="probe")
    assert 0 < inside < whole.hist_sum("queue_slice_seconds", path="probe")


def test_a_kind_that_defines_only_build_serves_point_operations():
    kind = deployment.kind_of(run.deploy_kind("tiered_frozen"))
    assert kind.operations is deployment.POINT_OPERATIONS
    assert kind.make_table is deployment.point_table
    assert kind.make_reference is deployment.point_reference
    assert kind.least_bytes is deployment.POINT_LEAST_BYTES


def test_unknown_cell_and_missing_reader_are_errors(tmp_path):
    with pytest.raises(SystemExit):
        run.load_cell("no_such_cell")
    with pytest.raises(SystemExit):
        run.metric_reader("no_such_metric", root=tmp_path)
