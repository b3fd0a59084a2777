"""Without a TPU the run exits non-zero and prints no result, and so it
does in a directory that holds only BENCHMARK.json and bench/."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "ycsb_c.zipf", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def run_in(where: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS],
                          cwd=where, env=env, capture_output=True,
                          text=True, timeout=300)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


@pytest.mark.parametrize("where", ["checkout", "bench_only"])
def test_exits_nonzero_without_a_chip(tmp_path, where):
    root = ROOT
    if where == "bench_only":
        root = tmp_path
        shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
        shutil.copytree(ROOT / "bench", root / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run_in(root)
    assert p.returncode != 0
    assert no_result(p.stdout), p.stdout
