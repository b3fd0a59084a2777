"""The harness finds everything by name from BENCHMARK.json and files,
and BENCHMARK.json keeps to the benchmark's contract."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import run

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
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
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


def test_unknown_cell_and_missing_reader_are_errors(tmp_path):
    with pytest.raises(SystemExit):
        run.load_cell("no_such_cell")
    with pytest.raises(SystemExit):
        run.metric_reader("no_such_metric", root=tmp_path)
