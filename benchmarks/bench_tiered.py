"""Tiered-engine benchmark (ours, DESIGN.md §4): batch size x tree size x
index kind, plus the sort-and-bucket schedule statistics that determine the
HBM tier's DMA efficiency.

The tiered engine is swept over both schedule placements
(``--plan {host,device,both}``): the host plan syncs once per batch (top
descent -> numpy bucket plan -> kernel), the device plan runs the whole
search as one jitted dispatch with zero host syncs (DESIGN.md §2.1). Each
tiered row records its ``host_syncs_per_batch`` and the executed grid /
occupancy so trend jobs can diff the two placements.

Emits the usual CSV lines *and* writes ``BENCH_tiered.json`` with per-kind
throughput so downstream tooling (experiments/render_tables.py, CI trend
jobs) can diff runs. ``--smoke`` runs the small tiered-only sweep and
asserts the device plan is no slower than the host plan on the 8192-query
batch (interpret mode, trend-only — the CI gate).

Workload: half the batch are Zipf-distributed hits (thesis §5.2.1 — skewed
re-reference is what serving traffic looks like and what makes buckets
deep), half uniform misses.

Run: ``PYTHONPATH=src python -m benchmarks.bench_tiered [--full] [--out F]``
"""
from __future__ import annotations

import argparse
import json

import numpy as np
import jax

from repro import obs
from repro.core import IndexConfig, build_index
from repro.kernels import ops as kernel_ops
from repro.launch.compile_cache import enable_compile_cache
from repro.engine import schedule, tiered
from ._timing import emit, time_fn, zipf_queries

KINDS = {
    "binary": lambda: IndexConfig(kind="binary"),
    "css": lambda: IndexConfig(kind="css", node_width=128),
    "kary": lambda: IndexConfig(kind="kary", node_width=127),
    "fast": lambda: IndexConfig(kind="fast", node_width=127, page_depth=2),
    "nitrogen": lambda: IndexConfig(kind="nitrogen", levels=3,
                                    compiled_node_width=3),
}


def _queries(keys: np.ndarray, batch: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    hits = zipf_queries(keys, batch // 2, seed=seed)
    misses = rng.integers(0, 2**31 - 2, batch - batch // 2).astype(np.int32)
    return np.concatenate([hits, misses])


def _schedule_stats(impl, qs: np.ndarray, plan_mode: str) -> dict:
    """Executed-grid statistics for one (index, batch, plan) cell. The host
    plan is computed out-of-band here for both modes: the device plan's
    rung selection lands on the same power-of-two grid, so `grid` and
    `occupancy` describe what actually executed in either mode."""
    pids = np.asarray(impl.page_of(qs))
    hp = schedule.bucket_plan(pids, impl.tile)
    stats = {
        "grid": hp.grid, "steps_used": hp.steps_used,
        "occupancy": round(hp.occupancy, 3),
        "num_pages": impl.num_pages,
        "leaf_width": impl.leaf_width,
        "top_kind": impl.top_kind,
    }
    if plan_mode == "device":
        # static plan-array cap; surplus over `grid` is masked, not executed
        stats["grid_cap"] = schedule.ladder_grid(qs.size, impl.tile,
                                                 impl.num_pages)
    return stats


def run(sizes=(2**14, 2**17), batches=(1024, 8192),
        plans=("host", "device"), kinds=KINDS, out="BENCH_tiered.json",
        assert_trend=False):
    rng = np.random.default_rng(7)
    results = []
    trend_cells = {}
    for n in sizes:
        keys = np.unique(rng.integers(0, 2**31 - 2, int(n * 1.1)
                                      ).astype(np.int32))[:n]
        oracle_sorted = np.sort(keys)
        for batch in batches:
            qs = _queries(keys, batch, seed=n % 1000 + batch)
            want = np.searchsorted(oracle_sorted, qs, side="left")
            for kind, mk in kinds.items():
                idx = build_index(keys, config=mk())
                fn = jax.jit(idx.search)
                got = np.asarray(fn(qs))
                assert np.array_equal(got, want), f"{kind} n={n} b={batch}"
                us = time_fn(fn, qs)
                results.append(
                    {"kind": kind, "n": int(n), "batch": int(batch),
                     "us_per_batch": round(us, 2),
                     "queries_per_s": round(batch / (us * 1e-6), 0),
                     "tree_bytes": idx.tree_bytes})
                emit(f"tiered/{kind}/n{n}/b{batch}", us,
                     f"qps={results[-1]['queries_per_s']:.0f}")
            # tiered: one build, both schedule placements
            idx = build_index(keys, config=IndexConfig(kind="tiered"))
            for mode in plans:
                fn = (lambda q, m=mode: tiered.search(idx.impl, q, plan=m))
                got = np.asarray(fn(qs))
                assert np.array_equal(got, want), \
                    f"tiered/{mode} n={n} b={batch}"
                us = time_fn(fn, qs)
                rec = {"kind": "tiered", "plan": mode, "n": int(n),
                       "batch": int(batch), "us_per_batch": round(us, 2),
                       "queries_per_s": round(batch / (us * 1e-6), 0),
                       "tree_bytes": idx.tree_bytes,
                       "host_syncs_per_batch": 1 if mode == "host" else 0,
                       "schedule": _schedule_stats(idx.impl, qs, mode)}
                results.append(rec)
                trend_cells[(n, batch, mode)] = us
                emit(f"tiered/tiered[{mode}]/n{n}/b{batch}", us,
                     f"qps={rec['queries_per_s']:.0f};"
                     f"syncs={rec['host_syncs_per_batch']};"
                     f"occ={rec['schedule']['occupancy']}")
    payload = {"backend": jax.default_backend(),
               "interpret_kernels": kernel_ops.use_interpret(),
               "results": results,
               "obs": obs.snapshot()}
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {out} ({len(results)} rows)")
    if assert_trend:
        _assert_device_trend(sizes, trend_cells)
    return payload


def run_obs_smoke(out="BENCH_obs_smoke.json", gate: float = 0.03):
    """The instrumentation overhead gate (DESIGN.md §9.4): time the fused
    tiered dispatch on a deep batch with observability OFF (null registry,
    tracer disabled) vs fully ON (process registry + span recording) and
    assert the median dispatch-staging latency regressed <= ``gate``.
    Also asserts the ON leg actually recorded: search histogram samples in
    the registry and spans in the tracer ring."""
    rng = np.random.default_rng(7)
    n, batch = 2**14, 8192
    keys = np.unique(rng.integers(0, 2**31 - 2, int(n * 1.1)
                                  ).astype(np.int32))[:n]
    qs = _queries(keys, batch, seed=n % 1000 + batch)
    idx = build_index(keys, config=IndexConfig(kind="tiered"))
    fn = lambda q: tiered.search(idx.impl, q)  # noqa: E731

    obs.configure(metrics=False, trace=False)
    off_us = time_fn(fn, qs)
    obs.REGISTRY.reset()
    obs.TRACER.clear()
    obs.configure(metrics=True, trace=True)
    on_us = time_fn(fn, qs)
    obs.configure(metrics=True, trace=False)

    h = obs.REGISTRY.value("dispatch_staging_seconds", path="search")
    assert h is not None and h.count > 0, \
        "instrumented run recorded no search histogram samples"
    assert obs.TRACER.events(), "instrumented run recorded no spans"
    overhead = on_us / off_us - 1.0
    verdict = "ok" if overhead <= gate else "REGRESSION"
    print(f"# obs-smoke n={n} b={batch}: off={off_us:.0f}us "
          f"on={on_us:.0f}us overhead={overhead * 100:+.2f}% "
          f"(gate {gate * 100:.0f}%, {verdict})")
    payload = {"backend": jax.default_backend(),
               "interpret_kernels": kernel_ops.use_interpret(),
               "off_us": round(off_us, 2), "on_us": round(on_us, 2),
               "overhead": round(overhead, 4), "gate": gate,
               "search_samples": h.count,
               "span_events": len(obs.TRACER.events()),
               "obs": obs.snapshot()}
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {out}")
    assert overhead <= gate, (
        f"observability overhead {overhead * 100:.2f}% over the "
        f"{gate * 100:.0f}% gate: {on_us:.0f}us vs {off_us:.0f}us")
    return payload


def run_specialize_smoke(out="BENCH_tune.json", gate_tol: float = 0.10,
                         n: int = 12000, q_n: int = 1536, reps: int = 16,
                         profile_dir=None):
    """The specialization gate (DESIGN.md §10): over a small tile ×
    leaf_width sweep, the specialized fused lookup (index baked into the
    jitted program) must be no slower than the data-as-jit-args posture
    on EVERY cell (``gate_tol`` noise floor — interpret-mode kernels
    dominate on CPU, so this is a trend gate like the device>=host one)
    and strictly faster on at least one. Both legs are measured through
    ``obs`` registries — the exact mean sidecar of
    ``dispatch_staging_seconds{path="lookup"}`` — never a parallel timer,
    and the reps alternate postures so clock drift cancels.

    Then the micro autotune sweep runs, persists its platform profile,
    and ``tune.verify_profile`` reloads it via ``IndexConfig.from_tuned``
    to check the recorded p50 reproduces within 10% / one √2 bucket.
    ``BENCH_tune.json`` records the cells, the sweep trials and the
    verify verdict.

    The lookup timer wraps dispatch STAGING only (no device sync —
    DESIGN.md §9), so single reps are spiky (~1ms async-queue outliers
    over a ~80us median) and the histogram's √2 buckets quantize too
    coarsely for a 10% floor. Each rep therefore reads its own fresh
    registry — one observation, so the exact ``mean`` sidecar IS that
    rep's staging time — and the cell statistic is the MEDIAN across
    reps: outlier-immune and not bucket-quantized, still measured
    through the same histograms serving measures with."""
    import gc
    from repro.obs import NULL_REGISTRY, Registry, use_registry
    from repro.tune import autotune, verify_profile
    from repro.tune.autotune import _workload

    keys, q, _, _ = _workload(n, q_n, seed=0)
    cells = []
    for tile in (128, 256):
        for lw in (None, 512):
            mk = lambda s: build_index(keys, None, IndexConfig(
                kind="tiered", mutable=True, specialize=s, tile=tile,
                leaf_width=lw))
            with use_registry(NULL_REGISTRY):   # build + compile warmup
                spec, args = mk(True), mk(False)
                assert spec._spec_fused is not None
                assert args._spec_fused is None
                for s in (spec, args):
                    s.lookup(q).rank.block_until_ready()
            t_spec, t_args = [], []
            gc.collect()                        # keep GC pauses out
            for _ in range(reps):               # alternate: drift cancels
                for ts, st in ((t_args, args), (t_spec, spec)):
                    r = Registry()
                    with use_registry(r):
                        st.lookup(q).rank.block_until_ready()
                    ts.append(r.merged_histogram(
                        "dispatch_staging_seconds", path="lookup").mean)
            spec.close()
            args.close()
            med_s = float(np.median(t_spec))
            med_a = float(np.median(t_args))
            cell = {"tile": tile, "leaf_width": lw,
                    "spec_med_us": round(med_s * 1e6, 2),
                    "args_med_us": round(med_a * 1e6, 2),
                    "spec_reps_us": [round(t * 1e6, 1) for t in t_spec],
                    "args_reps_us": [round(t * 1e6, 1) for t in t_args],
                    "ratio": round(med_s / med_a, 4),
                    "ok": med_s <= med_a * (1.0 + gate_tol)}
            cells.append(cell)
            print(f"# spec-smoke tile={tile} lw={lw}: "
                  f"median spec/args={cell['spec_med_us']:.0f}/"
                  f"{cell['args_med_us']:.0f}us "
                  f"ratio={cell['ratio']:.3f} "
                  f"({'ok' if cell['ok'] else 'REGRESSION'})")

    print("# spec-smoke: running micro autotune sweep")
    prof, path = autotune(smoke=True, n=n, q_n=q_n, reps=max(4, reps // 2),
                          profile_dir=profile_dir)
    verify = verify_profile(prof, profile_dir=profile_dir, n=n, q_n=q_n,
                            reps=max(4, reps // 2))
    print(f"# spec-smoke autotune: tile={prof.knobs['tile']} "
          f"lw={prof.knobs['leaf_width']} -> {path}")
    print(f"# spec-smoke verify: fresh_p50={verify['fresh_p50']:.2e} "
          f"recorded_p50={verify['recorded_p50']:.2e} "
          f"({'ok' if verify['ok'] else 'REGRESSION'})")

    payload = {"backend": jax.default_backend(),
               "interpret_kernels": kernel_ops.use_interpret(),
               "gate_tol": gate_tol, "n": n, "q_n": q_n, "reps": reps,
               "cells": cells,
               "autotune": {"knobs": prof.knobs,
                            "objective": prof.objective,
                            "trials": prof.trials,
                            "profile_path": path},
               "verify": verify,
               "obs": obs.snapshot()}
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {out} ({len(cells)} cells, {len(prof.trials)} trials)")
    bad = [c for c in cells if not c["ok"]]
    assert not bad, (
        f"specialized lookup slower than data-as-jit-args beyond the "
        f"{gate_tol * 100:.0f}% floor on {len(bad)} cell(s): {bad}")
    assert any(c["ratio"] < 1.0 for c in cells), (
        "specialized lookup not strictly faster on any swept cell: "
        f"{[c['ratio'] for c in cells]}")
    assert verify["ok"], (
        f"tuned profile failed to reproduce its recorded lookup p50: "
        f"{verify}")
    return payload


def _assert_device_trend(sizes, cells):
    """CI smoke gate: on the deep-bucket batch (8192) the device plan must
    not be slower than the host plan. Interpret mode on CPU, so this is a
    trend check (5% noise floor), not a perf claim."""
    for n in sizes:
        host, dev = cells[(n, 8192, "host")], cells[(n, 8192, "device")]
        verdict = "ok" if dev <= host * 1.05 else "REGRESSION"
        print(f"# trend n={n} b=8192: host={host:.0f}us device={dev:.0f}us "
              f"({verdict})")
        assert dev <= host * 1.05, (
            f"device plan slower than host plan at n={n}, batch=8192: "
            f"{dev:.0f}us vs {host:.0f}us")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="add the 1M-key tree (slow under interpret mode)")
    ap.add_argument("--plan", choices=("host", "device", "both"),
                    default="both", help="tiered schedule placement(s)")
    ap.add_argument("--smoke", action="store_true",
                    help="small tiered-only sweep + device>=host trend "
                         "assert on the 8192 batch (the CI gate)")
    ap.add_argument("--obs-smoke", action="store_true",
                    help="instrumentation-overhead gate: fused dispatch "
                         "with observability on vs off, <= 3% (the CI "
                         "obs-smoke gate, DESIGN.md §9.4)")
    ap.add_argument("--specialize-smoke", action="store_true",
                    help="specialization gate: specialized fused lookup "
                         "no slower than data-as-jit-args on every swept "
                         "cell, + micro autotune persist/verify (the CI "
                         "autotune-smoke gate, DESIGN.md §10)")
    ap.add_argument("--gate-tol", type=float, default=0.10,
                    help="per-cell noise floor for --specialize-smoke")
    ap.add_argument("--profile-dir", default=None,
                    help="--specialize-smoke: where the tuned profile "
                         "persists (default src/repro/configs/)")
    ap.add_argument("--out", default="BENCH_tiered.json")
    args = ap.parse_args()
    plans = ("host", "device") if args.plan == "both" else (args.plan,)
    if args.obs_smoke:
        run_obs_smoke(out=args.out)
        return
    if args.specialize_smoke:
        out = args.out if args.out != "BENCH_tiered.json" \
            else "BENCH_tune.json"
        run_specialize_smoke(out=out, gate_tol=args.gate_tol,
                             profile_dir=args.profile_dir)
        return
    if args.smoke:
        run(sizes=(2**14,), batches=(1024, 8192), plans=("host", "device"),
            kinds={}, out=args.out, assert_trend=True)
        return
    sizes = (2**14, 2**17, 2**20) if args.full else (2**14, 2**17)
    run(sizes=sizes, plans=plans, out=args.out)


if __name__ == "__main__":
    enable_compile_cache()
    main()
