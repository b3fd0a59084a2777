"""Drive a whole run of a traffic mix over its configuration on the CPU
at a size a test can hold, skipping only the harness's look for a chip:
2^12 records, a queue of capacity 16 (five batch shapes), a few seconds of
traffic at 100 ops/s. The pair is read from their files, as a cell of
``BENCHMARK.json`` names them: the mutable mix has no cell yet (PERF.md),
and its write path is tested all the same."""
import argparse
import contextlib
import gc
import io
import json
import tempfile

from bench import peaks, run

RECORDS = 2**12
CAPACITY = 16
CONFIG = {"ycsb_c.zipf": "ycsb_frozen_2e27",
          "ycsb_a.zipf": "ycsb_mutable_2e27"}


def small_spec(traffic: str) -> dict:
    config = CONFIG[traffic]
    cfg = json.loads(
        (run.ROOT / "bench" / "configs" / f"{config}.json").read_text())
    spec = {"cell": {"name": traffic, "config": config, "traffic": traffic,
                     "chips": 1},
            "traffic": json.loads((run.ROOT / "bench" / "traffic" /
                                   f"{traffic}.json").read_text()),
            "end_to_end": [], "per_layer": []}
    spec["config"] = dict(cfg, recordcount=RECORDS, index_config=dict(
        cfg["index_config"], queue_capacity=CAPACITY))
    spec["traffic"] = dict(spec["traffic"], arrival=dict(
        spec["traffic"]["arrival"], rate_per_s=100))
    return spec


def run_small(cell: str, seed: int = 2**34 + 3, seconds: float = 1.5,
              control=None) -> tuple:
    """(result of the last line, everything the run printed)."""
    import jax
    spec = small_spec(cell)
    if run.COMPILES is None:
        run.COMPILES = run.Compiles()
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0, sweep="", control=control)
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
