"""Drive a whole run of a cell on the CPU at a size a test can hold,
skipping only the harness's look for a chip: 2^12 records, a queue of
capacity 16 (five batch shapes), a few seconds of traffic at 100 ops/s.

The cell is found by name, from its entry in ``BENCHMARK.json`` at
``root``, or, for a cell that has no entry yet, from a file of the same
form, ``bench/tests/cells/<name>.json`` (the mutable mix has no cell, and
its write path is tested all the same). Its configuration and traffic are
read from their files, so a new cell's CPU run needs no edit here."""
import argparse
import contextlib
import gc
import io
import json
import tempfile
from pathlib import Path

from bench import peaks, run

RECORDS = 2**12
CAPACITY = 16


def small_spec(cell: str, root: Path = run.ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if cell in {w["name"] for w in bench["workloads"]}:
        spec = run.load_cell(cell, root)
    else:
        entry = json.loads(
            (root / "bench" / "tests" / "cells" / f"{cell}.json").read_text())

        def data(kind, name):
            return json.loads(
                (root / "bench" / kind / f"{name}.json").read_text())
        spec = {"root": root, "cell": entry,
                "config": data("configs", entry["config"]),
                "traffic": data("traffic", entry["traffic"])}
    cfg = spec["config"]
    spec["config"] = dict(cfg, recordcount=RECORDS, index_config=dict(
        cfg["index_config"], queue_capacity=CAPACITY))
    spec["traffic"] = dict(spec["traffic"], arrival=dict(
        spec["traffic"]["arrival"], rate_per_s=100))
    spec.setdefault("end_to_end", [])
    spec.setdefault("per_layer", [])
    return spec


def run_small(cell: str, seed: int = 2**34 + 3, seconds: float = 1.5,
              control=None, root: Path = run.ROOT, trace: int = 0) -> tuple:
    """(result of the last line, everything the run printed). With
    ``trace`` 1 the result holds the cell's per-layer metrics that its
    readers find in a CPU trace (none of the device's)."""
    import jax
    spec = small_spec(cell, root)
    if run.COMPILES is None:
        run.COMPILES = run.Compiles()
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace, sweep="", control=control)
    out = io.StringIO()
    try:
        with tempfile.TemporaryDirectory() as d, \
                contextlib.redirect_stdout(out):
            rc = run._run(args, spec, jax.devices()[0], 1,
                          peaks.PEAKS["TPU v5 lite"], d)
    finally:
        gc.unfreeze()              # set-up froze this test process's heap
    assert rc == 0
    text = out.getvalue()
    return json.loads(text.strip().splitlines()[-1]), text
