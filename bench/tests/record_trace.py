"""Record the small chip trace that the trace-reduction tests read.

    python3 bench/tests/record_trace.py --out <directory>

Runs on a TPU only. Builds a frozen and a mutable tiered index over 2^20
keys, drives a few hundred single-key reads (and, on the mutable store, a
few writes) through ``MicroBatchQueue`` with the program's ``obs`` spans
and the harness's own span names on, under the JAX profiler with the
Python tracer off. Writes ``frozen.xplane.pb`` and ``mutable.xplane.pb``
to ``--out`` and prints the planes, lines and most frequent event names
of each, which is what the reduction in ``bench/tracing.py`` keys on.
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402


def _drive(index, keys, n_reads: int, writes: int, rng) -> None:
    from repro.engine.queue import MicroBatchQueue, index_probe_fn
    probe = index_probe_fn(index)

    def traced_probe(batch):
        with jax.profiler.TraceAnnotation("bench.probe"):
            return probe(batch)

    q = MicroBatchQueue(traced_probe, capacity=4096,
                        deadline_s=0.002, min_flush=64,
                        adaptive_deadline=True)
    pick = rng.choice(keys, n_reads)
    futs = []
    for i, k in enumerate(pick):
        with jax.profiler.TraceAnnotation("bench.send"):
            futs.append(q.submit(np.asarray([k], np.int32)))
        if writes and i % (n_reads // writes) == 0:
            with jax.profiler.TraceAnnotation("bench.write"):
                index.insert(np.asarray([k], np.int32),
                             np.asarray([i + 1], np.int32))
        if i % 64 == 63:
            for f in futs:
                with jax.profiler.TraceAnnotation("bench.result"):
                    jax.device_get(f.result())
            futs = []
    for f in futs:
        with jax.profiler.TraceAnnotation("bench.result"):
            jax.device_get(f.result())
    q.close()


def _summary(path: str) -> None:
    from jax.profiler import ProfileData
    d = ProfileData.from_file(path)
    for pl in d.planes:
        lines = list(pl.lines)
        print(f"PLANE {pl.name!r} lines={len(lines)}")
        for ln in lines:
            evs = list(ln.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  LINE {ln.name!r} events={len(evs)} "
                  f"top={names.most_common(8)}")
            for e in evs[:2]:
                print(f"    EV {e.name!r} start={e.start_ns} "
                      f"dur={e.duration_ns} stats={dict(e.stats)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    from repro.core import IndexConfig, build_index
    from repro import obs
    os.makedirs(args.out, exist_ok=True)
    rng = np.random.default_rng(7)
    n = 2**20
    keys = np.sort(rng.choice(2**30, n, replace=False)).astype(np.int32)
    vals = np.arange(n, dtype=np.int32)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    for name, cfg, writes in (
            ("frozen", IndexConfig(kind="tiered"), 0),
            ("mutable", IndexConfig(kind="tiered", mutable=True,
                                    maintenance="thread"), 8)):
        index = build_index(keys, vals, cfg)
        _drive(index, keys, 256, writes, rng)            # warm every shape
        tmp = tempfile.mkdtemp()
        obs.TRACER.enable()
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.traced"):
            _drive(index, keys, 256, writes, rng)
        jax.profiler.stop_trace()
        obs.TRACER.disable()
        src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        dst = os.path.join(args.out, f"{name}.xplane.pb")
        shutil.copy(src, dst)
        shutil.rmtree(tmp)
        print(f"== {name}: {dst} bytes={os.path.getsize(dst)}")
        _summary(dst)
        if hasattr(index, "close"):
            index.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
