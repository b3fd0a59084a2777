"""Benchmark of the tiered index service on the chip, one cell a run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/``)
and a traffic mix (``bench/traffic/<name>.json``); a per-layer metric is
read by ``bench/metrics/<name>.py``. All are found by name, so a cell, a
configuration, a mix or a metric is added as files and an entry, not as an
edit.

The configuration's ``kind`` names its deployment kind,
``bench/deploy/<kind>.py``, which owns everything about the deployment
(the contract is in ``bench/deployment.py``): ``build`` (required), and,
each with a default, ``make_table`` (the data from ``--seed``; default the
YCSB usertable), ``OPERATIONS`` (what a mix may ask, how each operation
is sent and how its arguments are drawn; default YCSB point reads through
the queue and updates on a writer thread), ``make_reference`` (the plain
reference and the checks that decide ``correct``; default
``reference.Reference``) and ``LEAST_BYTES`` (each operation's least
bytes a dispatch, for the roofline readers; default ``roofline``'s point
lookups). So a new deployment is new files as well. A reader file may
carry its own test case (``bench/record.py``).

One run is one process: it makes the table from ``--seed``, builds the
deployment, warms every shape the window will use, offers the traffic
for ``--seconds`` through the served path, then compares every answer
with the plain reference. Read latency (``read_*``) is over every request
of a read operation. It needs a TPU: with
none, or with the kernels in the Pallas interpreter, it exits 1 and prints
no result. Earlier lines report set-up, the generator's lateness, compiles
in the window and ``peak_bytes_in_use``; the numbers compared and their
limits are the last lines on standard error; the last line on standard
output is the JSON result. ``--trace 1`` traces part of the window with the
JAX profiler and reports the per-layer metrics instead of the end-to-end
ones; the readers of the program's registry leave the traced part out.

Calibration options, not used by the benchmark's runs: ``--sweep r1,r2``
offers each open-loop rate in turn after one set-up and prints what the
system sustained; ``--control f32|stale`` also compares the control's
answers to the same requests and prints its numbers.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import (deployment, drive, peaks, workload,  # noqa: E402
                   ycsb)
from bench.record import RunRecord  # noqa: E402

NO_READBACK = {"key": np.zeros(0, np.int32), "found": np.zeros(0, bool),
               "value": np.zeros(0, np.int32)}
TRACE_SHARE = (0.3, 0.2)     # traced part of the window: start, length
TRACE_MAX_S = 4.0


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------- the spec
def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry, configuration, traffic and metrics, by name."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    return {"root": root, "cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def load_file(path: Path, name: str):
    """Import one file of the benchmark by its path."""
    if not path.is_file():
        raise SystemExit(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def deploy_kind(kind: str, root: Path = ROOT):
    return load_file(root / "bench" / "deploy" / f"{kind}.py",
                     f"bench_deploy_{kind}")


def metric_reader(name: str, root: Path = ROOT):
    return load_file(root / "bench" / "metrics" / f"{name}.py",
                     "bench_metric_" + name.replace(".", "_"))


# --------------------------------------------------------------- device
def require_chip(chips: int):
    """The first device, if it is a TPU with enough chips and the kernels
    compile; else exit 1 with no result."""
    import jax
    from repro.kernels import ops
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX's first device is {devs[0].platform!r})",
              file=sys.stderr)
        raise SystemExit(1)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, JAX has {len(devs)}",
              file=sys.stderr)
        raise SystemExit(1)
    if ops.use_interpret():
        print("bench: kernels would run in the Pallas interpreter",
              file=sys.stderr)
        raise SystemExit(1)
    return devs[0], len(devs)


CACHE_DIR = ROOT / ".jax_cache"   # fixed: the path is part of the key


def enable_compile_cache() -> str:
    """JAX's persistent cache in a fixed directory inside the checkout,
    whatever the environment sets, so that only a cell's first run in a
    checkout compiles and two checkouts share nothing. No size limit: with
    one, JAX keeps an access-time file beside each entry and a single entry
    without its file (a run killed while writing, or entries copied in
    without them) makes every later write fail."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(CACHE_DIR)


class Compiles:
    """Backend compiles and persistent-cache hits, from JAX's events."""

    def __init__(self):
        import jax
        self.n = self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class GcPauses:
    """Pauses of Python's cyclic collector while a window runs."""

    def __init__(self):
        self.by_gen = {0: [], 1: [], 2: []}
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.by_gen[info["generation"]].append(
                time.perf_counter() - self._t)

    def stop(self):
        gc.callbacks.remove(self._cb)

    def __str__(self):
        return " ".join(f"gen{g}={len(v)}/max_ms={max(v, default=0) * 1e3:.3f}"
                        for g, v in self.by_gen.items())


# -------------------------------------------------------------- one run
class Cell:
    """Set-up of one cell: table, deployment, warm shapes."""

    def __init__(self, spec: dict, seed: int, workdir: str):
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.spans = drive.Spans()
        self.kind = deployment.kind_of(
            deploy_kind(self.config["kind"], spec["root"]))
        t = time.perf_counter()
        self.table = self.kind.make_table(self.config, seed)
        self.data_s = time.perf_counter() - t
        t = time.perf_counter()
        self.dep = self.kind.build(self.config, self.table, workdir)
        self.build_s = time.perf_counter() - t
        self.probe = drive.Probe(self.dep.probe_fn, self.spans)
        self.writes = {"key": [], "value": [], "t_start": [], "t_ack": []}
        t = time.perf_counter()
        rng = np.random.default_rng([seed, 0xA11])
        keys = self.table.keys[ycsb.uniform(rng, 8192, self.table.n)]
        self.shapes = drive.warm_reads(self.probe, keys,
                                       self.dep.index_config.queue_capacity)
        for k in self.dep.warm_folds:
            rec = rng.choice(self.table.n, k, replace=False)
            for key, val in zip(self.table.keys[rec],
                                ycsb.update_values(rng, k)):
                self._write(np.asarray([key]), np.asarray([val]))
            self.dep.fold()
        # what set-up leaves behind is long-lived: keep it out of the
        # collector's later passes, as a server does after start-up
        gc.collect()
        gc.freeze()
        self.warm_s = time.perf_counter() - t

    def _write(self, k, v):
        t0 = time.perf_counter()
        self.dep.write(k, v)
        self.writes["key"].append(int(k[0]))
        self.writes["value"].append(int(v[0]))
        self.writes["t_start"].append(t0)
        self.writes["t_ack"].append(time.perf_counter())

    def window(self, reqs: workload.Requests, seconds: float,
               trace_dir=None) -> dict:
        """Offer ``reqs`` for ``seconds``; returns the window's records."""
        from repro import obs
        keys = self.table.keys[reqs.record]
        lg = drive.Log(reqs.n)
        queue = drive.make_queue(self.probe, self.dep.index_config)
        clients = drive.Clients(lg, keys, reqs, self.dep,
                                self.kind.operations, self.spans)
        reg = obs.get_registry()
        before = reg.snapshot()
        tracer = _Tracer(self, seconds, trace_dir) if trace_dir else None
        compiles0 = COMPILES.n
        pauses = GcPauses()
        t0 = time.perf_counter()
        self.probe.batches = []
        self.probe.record = True
        if tracer:
            tracer.start(t0)
        drive.open_loop(queue, clients, reqs.op, reqs.due, t0, self.spans)
        if tracer:
            tracer.join()
        drained = clients.close()
        t_gave_up = time.perf_counter()
        pauses.stop()
        t_end = t0 + seconds
        self.probe.record = False
        qstats = queue.stats
        q = {"queries": qstats.queries, "flushes": qstats.flushes,
             "max_batch": qstats.max_batch}
        queue.close()
        after = reg.snapshot()
        compiles = COMPILES.n - compiles0
        is_read = reqs.is_read
        sent = lg.sent
        # a writer applies its writes in send order: that is the apply order
        w = np.nonzero(~is_read & sent)[0]
        w = w[np.argsort(lg.t_send[w], kind="stable")]
        wkey = np.zeros(w.size, np.int64)
        wval = np.zeros(w.size, np.int64)
        for k, name in enumerate(reqs.ops):
            m = reqs.op[w] == k
            if m.any():
                wkey[m], wval[m] = self.kind.operations[name].written(
                    keys, reqs.args, w[m])
        self.writes["key"] += wkey.tolist()
        self.writes["value"] += wval.tolist()
        self.writes["t_start"] += lg.t_send[w].tolist()
        self.writes["t_ack"] += lg.t_done[w].tolist()
        return {"t0": t0, "t_end": t_end, "log": lg,
                "t_gave_up": t_gave_up, "requests": reqs, "keys": keys,
                "is_read": is_read, "sent": sent,
                "drained": drained, "compiles": compiles, "queue": q,
                "gc": pauses,
                "registry": (before, after), "tracer": tracer,
                "batches": self.probe.batches}

    def writes_arrays(self) -> dict:
        return {"key": np.asarray(self.writes["key"], np.int32),
                "value": np.asarray(self.writes["value"], np.int32),
                "t_start": np.asarray(self.writes["t_start"], float),
                "t_ack": np.asarray(self.writes["t_ack"], float)}

    def read_back(self) -> dict:
        """Every written key, read through the served path after the
        window (whole batches of up to the queue's capacity a submit)."""
        keys = np.unique(np.asarray(self.writes["key"], np.int32))
        queue = drive.make_queue(self.probe, self.dep.index_config)
        cap = self.dep.index_config.queue_capacity
        found, value = [], []
        import jax
        for i in range(0, keys.size, cap):
            res = jax.device_get(queue.submit(keys[i:i + cap]).result())
            found.append(np.asarray(res.found))
            value.append(np.asarray(res.values))
        queue.close()
        if not keys.size:
            return {"key": keys, "found": np.zeros(0, bool),
                    "value": np.zeros(0, np.int32)}
        return {"key": keys, "found": np.concatenate(found),
                "value": np.concatenate(value)}


class _Tracer(threading.Thread):
    """Traces part of the window from a thread of its own, so the sender
    keeps its schedule; the program's ``obs`` spans are on meanwhile."""

    def __init__(self, cell: Cell, seconds: float, out_dir: str):
        super().__init__(daemon=True)
        self.cell = cell
        self.out_dir = out_dir
        self.delay = seconds * TRACE_SHARE[0]
        self.length = min(seconds * TRACE_SHARE[1], TRACE_MAX_S)
        self.t0 = 0.0
        self.span = (0.0, 0.0)
        self.path = None
        self.registry = ()       # snapshots as tracing starts and ends
        self.registry_s = 0.0

    def start(self, t0: float):
        self.t0 = t0
        super().start()

    def run(self):
        import jax
        from repro import obs
        time.sleep(max(0.0, self.t0 + self.delay - time.perf_counter()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        reg = obs.get_registry()
        first = reg.snapshot()
        t = time.perf_counter()
        obs.TRACER.enable()
        self.cell.spans.on = True
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.traced"):
            a = time.perf_counter()
            time.sleep(self.length)
            b = time.perf_counter()
        jax.profiler.stop_trace()
        self.cell.spans.on = False
        obs.TRACER.disable()
        self.registry_s = time.perf_counter() - t
        self.registry = (first, reg.snapshot())
        self.span = (a, b)
        found = glob.glob(f"{self.out_dir}/**/*.xplane.pb", recursive=True)
        self.path = found[0] if found else None


# ---------------------------------------------------------- end to end
def quantile(x: np.ndarray, q: float) -> float:
    """The q-quantile, the sample at or above it (no interpolation)."""
    if not x.size:
        return float("nan")
    return float(np.quantile(x, q, method="higher"))


def end_to_end(cell: Cell, win: dict, seconds: float) -> dict:
    lg = win["log"]
    is_read, sent = win["is_read"], win["sent"]
    # a request that never finished counts as waiting until the harness
    # stopped waiting for it, a minute past the close: over any limit
    done = np.where(np.isfinite(lg.t_done), lg.t_done, win["t_gave_up"])
    lat = (done - lg.t_due) * 1e3
    out = {}
    r = is_read & sent
    w = ~is_read & sent
    if r.any():
        out["read_p99_ms"] = quantile(lat[r], 0.99)
        out["read_p50_ms"] = quantile(lat[r], 0.50)
    if w.any():
        out["write_p99_ms"] = quantile(lat[w], 0.99)
        out["write_p50_ms"] = quantile(lat[w], 0.50)
    in_window = np.isfinite(lg.t_done) & (lg.t_done <= win["t_end"]) & sent
    out["ops_per_s"] = float(in_window.sum()) / seconds
    out["setup_s"] = cell.setup_s
    return out


def is_correct(checks: dict) -> bool:
    """Every number the kind's reference compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def failed(win: dict) -> int:
    """Requests sent in the window that never finished: reads with no
    answer, writes with no acknowledgement."""
    return int((win["sent"] & ~np.isfinite(win["log"].t_done)).sum())


def lateness(win: dict) -> str:
    lg = win["log"]
    s = win["sent"] & win["is_read"]
    late = lg.t_send[s] - lg.t_due[s]
    if not late.size:
        return "lateness: no reads sent"
    # stalls: sends over 50 ms late, by when in the window they were due
    due = lg.t_due[s] - win["t0"]
    stalls = np.nonzero(late > 0.05)[0]
    starts = stalls[np.diff(np.concatenate([[-2], stalls])) > 1]
    at = [round(float(due[i]), 2) for i in starts[:12]]
    return (f"lateness_ms: p50={np.quantile(late, .5) * 1e3:.4f} "
            f"p99={np.quantile(late, .99) * 1e3:.4f} "
            f"max={late.max() * 1e3:.4f} (send time minus due time, reads); "
            f"stalls_over_50ms_at_s={at}")


# ------------------------------------------------------------ per layer
def traced_bytes(cell: Cell, win: dict, ref) -> dict:
    """Least bytes of the dispatches inside the traced part, by
    operation: the queue's batches, and each call of a client operation
    (``bench/deployment.py`` LEAST_BYTES)."""
    a, b = win["tracer"].span
    reqs, keys, lg = win["requests"], win["keys"], win["log"]
    out = {}
    for name, least in cell.kind.least_bytes.items():
        if cell.kind.operations[name].via == deployment.QUEUE:
            batches = [q for t, q in win["batches"] if a <= t <= b]
        else:
            idx = np.nonzero(reqs.of(name) & win["sent"] & (lg.t_send >= a)
                             & (lg.t_send <= b))[0]
            batches = [dict({k: v[i:i + 1] for k, v in reqs.args.items()},
                            key=keys[i:i + 1]) for i in idx]
        out[name] = sum(least(ref, x) for x in batches)
    return out


def per_layer(spec: dict, cell: Cell, win: dict, ref, chip) -> tuple:
    from bench import tracing
    tr = win["tracer"]
    red = tracing.reduce(tr.path) if tr.path else None
    rec = RunRecord(window_s=win["t_end"] - win["t0"],
                    registry=win["registry"], queue=win["queue"],
                    reduction=red, peaks=chip,
                    traced_bytes=traced_bytes(cell, win, ref),
                    traced_registry=tr.registry, traced_s=tr.registry_s)
    out = {}
    for m in spec["per_layer"]:
        v = metric_reader(m["name"], spec["root"]).read(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out, red


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="",
                    help="calibration: comma-separated open-loop rates")
    ap.add_argument("--control",
                    help="calibration: also compare this control of the "
                    "kind's reference (default reference: f32, stale)")
    args = ap.parse_args(argv)

    spec = load_cell(args.workload)
    dev, count = require_chip(spec["cell"]["chips"])
    import jax
    chip = peaks.peaks_for(dev.device_kind)
    cache = enable_compile_cache()
    global COMPILES
    COMPILES = Compiles()
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={count} jax={jax.__version__} compile_cache={cache}")
    workdir = tempfile.mkdtemp(prefix="bench-")
    try:
        return _run(args, spec, dev, count, chip, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _run(args, spec, dev, count, chip, workdir) -> int:
    cell = Cell(spec, args.seed, workdir)
    log(f"setup: data_s={cell.data_s:.3f} build_s={cell.build_s:.3f} "
        f"warm_s={cell.warm_s:.3f} shapes={cell.shapes} "
        f"records={cell.table.n} compiles={COMPILES.n} "
        f"compile_s={COMPILES.seconds:.3f} cache_hits={COMPILES.hits} "
        f"cache_misses={COMPILES.misses}")
    if args.sweep:
        return sweep(cell, spec, args)
    reqs = workload.generate(spec["traffic"], cell.table.n, args.seed,
                             args.seconds, cell.kind.operations)
    cell.setup_s = time.perf_counter() - T_PROCESS
    trace_dir = os.path.join(workdir, "trace") if args.trace else None
    win = cell.window(reqs, args.seconds, trace_dir)
    log(lateness(win))
    log(f"window: seconds={args.seconds} sent={int(win['sent'].sum())} "
        f"reads={int((win['sent'] & win['is_read']).sum())} "
        f"compiles_in_window={win['compiles']} "
        f"queue_queries={win['queue']['queries']} "
        f"queue_flushes={win['queue']['flushes']} "
        f"queue_max_batch={win['queue']['max_batch']} "
        f"drained={win['drained']} errors={len(win['log'].errors)} "
        f"gc: {win['gc']}")
    for e in win["log"].errors[:5]:
        log(f"error: {e}")
    readback = cell.read_back() if cell.writes["key"] else NO_READBACK
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"peak_bytes_in_use={peak}")
    e2e = end_to_end(cell, win, args.seconds)
    outcome = deployment.Outcome(
        requests=win["requests"], keys=win["keys"], log=win["log"],
        writes=cell.writes_arrays(), readback=readback,
        compare_rank=cell.dep.compare_rank)
    table = cell.table
    cell.dep.close()
    cell.dep = cell.probe = None
    gc.collect()
    t = time.perf_counter()
    ref = cell.kind.make_reference(cell.config, table)
    checks = ref.check(outcome)
    log(f"reference: seconds={time.perf_counter() - t:.3f} "
        f"reads={int((win['sent'] & win['is_read']).sum())} "
        f"writes={outcome.writes['key'].size} "
        f"read_back={readback['key'].size}")
    if args.control:
        if not hasattr(ref, "check_control"):
            raise SystemExit(f"the reference of kind {cell.config['kind']!r} "
                             "has no controls")
        cchecks = ref.check_control(args.control, outcome)
        log("control " + args.control + ": " + " ".join(
            f"{k}={v['value']}" for k, v in cchecks.items()) +
            f" correct={is_correct(cchecks)}")
    correct = is_correct(checks)
    result = {"correct": correct,
              "attempted": int(win["sent"].sum()),
              "failed": failed(win),
              "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": count, "memory_peak_bytes": peak}}
    log("end_to_end: " + " ".join(f"{k}={v!r}" for k, v in e2e.items()))
    if args.trace:
        metrics, red = per_layer(spec, cell, win, ref, chip)
        result["metrics"] = metrics
        if red is not None:
            result["device"]["busy_s"] = red.busy_s
            result["device"]["window_s"] = red.window_s
            result["breakdown"] = red.breakdown
            log(f"trace: window_s={red.window_s} busy_s={red.busy_s} "
                f"device_s_by_span={red.device_s_by_category} "
                f"kernels_s={red.kernel_s}")
    else:
        for m in spec["end_to_end"]:
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def sweep(cell: Cell, spec: dict, args) -> int:
    """Calibration: each rate in turn, one window each, same set-up."""
    ref = cell.kind.make_reference(cell.config, cell.table)
    traffic = dict(spec["traffic"])
    for i, rate in enumerate(float(r) for r in args.sweep.split(",")):
        traffic["arrival"] = {"process": "poisson", "rate_per_s": rate}
        reqs = workload.generate(traffic, cell.table.n, args.seed + i,
                                 args.seconds, cell.kind.operations)
        cell.setup_s = 0.0
        win = cell.window(reqs, args.seconds)
        e2e = end_to_end(cell, win, args.seconds)
        lg = win["log"]
        ok = np.isfinite(lg.t_done) & win["sent"]
        checks = ref.check(deployment.Outcome(
            requests=reqs, keys=win["keys"], log=lg,
            writes=cell.writes_arrays(), readback=NO_READBACK,
            compare_rank=cell.dep.compare_rank))
        checks.pop("lost_writes", None)
        last = np.nanmax(lg.t_done[ok]) - win["t0"] if ok.any() else 0.0
        log(f"sweep rate={rate} offered={reqs.n / args.seconds:.1f} "
            f"completed_per_s={e2e['ops_per_s']:.1f} "
            f"last_answer_s={last:.3f} "
            + " ".join(f"{k}={v:.4f}" for k, v in e2e.items()
                       if k.endswith("_ms"))
            + f" flushes={win['queue']['flushes']} "
            f"queries={win['queue']['queries']} "
            f"compiles_in_window={win['compiles']} "
            f"correct={is_correct(checks)} | {lateness(win)}")
    return 0


COMPILES = None

if __name__ == "__main__":
    sys.exit(main())
