"""Tiered batch-search engine (DESIGN.md §4): one index, three memory tiers.

Composition per batch:

  1. **Top tier** — map each query to its leaf-page id. The top is itself an
     index over the page-last-keys array (`seps[p]` = last slot of page p),
     because ``page_of(q) == |{p : seps[p] < q}|`` — the page id is exactly
     the searchsorted rank among page boundaries, so the top tier is a
     recursive instance of the same search problem at 1/leaf_width the size.
     Small tops compile to a NitroGen constant network (XLA literal pool —
     the "instruction cache" tier); larger tops run the k-ary VMEM kernel.
  2. **Schedule** — sort-and-bucket the batch by page id (engine/schedule.py,
     DESIGN.md §2.1). With ``plan="device"`` (default) the plan is computed
     by the jnp twin *inside* the same jit as the kernels; ``plan="host"``
     keeps the numpy plan (stats/debug) at the cost of one host sync.
  3. **Bottom tier** — ``page_search_bucketed`` streams exactly one leaf
     page HBM->VMEM per grid step via scalar-prefetched DMA.
  4. **Un-permute** — scatter ranks back to request order (valid-masked,
     out-of-bounds drop).

With the device plan the whole composition is **one jitted dispatch**: top
descent -> device plan (static worst-case grid, DESIGN.md §2.1) -> on-device
rung selection -> page kernel -> un-permute, with the query buffer donated.
Tier sizing is automatic: ``plan_tiers`` grows the leaf width until the top
tier fits the VMEM budget check from ``kernels/ops.py``.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp

from ..core import kary, nitrogen
from ..core.util import (as_sorted_numpy, ceil_to as _ceil_to, next_pow,
                         pad_to, sentinel_for)
from ..obs import get_registry, span as _span
from ..kernels import ops
from ..kernels import kary_search as _kary
from ..kernels import page_search as _page
from .schedule import (BucketPlan, bucket_plan, device_plan, ladder_for,
                       ladder_grid, run_scheduled)

# Tops at or below this page count compile to a NitroGen constant network;
# larger tops use the k-ary VMEM kernel (trace cost of the constant network
# grows with the page count; see DESIGN.md §3 for the crossover reasoning).
NITROGEN_TOP_MAX_PAGES = 256

PLAN_MODES = ("device", "host")


def plan_tiers(n: int, *, tile: int = 128,
               vmem_budget: int = ops.VMEM_BUDGET_BYTES):
    """Automatic tier sizing: the smallest tile-aligned leaf width whose
    page-boundary top tier passes the kary-kernel VMEM budget (half the
    budget is reserved for query tiles and the streamed page)."""
    budget = vmem_budget // 2
    max_pages = tile
    while ops.kary_vmem_bytes(max_pages * 2) <= budget:
        max_pages *= 2
    leaf_width = max(tile, _ceil_to(-(-n // max_pages), tile))
    num_pages = -(-n // leaf_width)
    top_kind = "nitrogen" if num_pages <= NITROGEN_TOP_MAX_PAGES else "kary"
    return leaf_width, num_pages, top_kind


@dataclass(frozen=True)
class TieredIndex:
    # no sorted-array copy here: the padded leaf pages ARE the bottom tier
    # storage (api.Index keeps keys_sorted for found/values semantics)
    pages: jnp.ndarray           # [num_pages, lw_pad // 128, 128] sentinel-
    #                              padded leaves (page_search.page_tiles)
    seps: jnp.ndarray            # [num_pages] last slot of each page
    n: int
    leaf_width: int
    lw_pad: int
    num_pages: int
    tile: int                    # queries per grid step (bucket width)
    top_kind: str                # 'nitrogen' | 'kary' | 'trivial'
    top: Any                     # the inner index over `seps` (None if trivial)
    page_of: Callable            # jit-cached: q[batch] -> leaf-page id
    page_of_raw: Callable        # traceable descent, for fusing (scan.py)
    search_raw: Callable         # traceable (q, pages) -> ranks, for fusing
    search_fused: Callable       # jitted search_raw, zero host syncs
    donate: bool = True          # search_fused donates its query buffer
    plan: str = "device"         # default schedule placement
    specialize: bool = False     # leaf pages baked into the executable
    search_spec: Any = None      # jitted pipeline closing over the pages
    #                              (None unless built with specialize=True)
    search_spec_raw: Any = None  # traceable q -> ranks behind search_spec

    @property
    def tree_bytes(self) -> int:
        # the leaf pages replace the sorted array; the resident top tier is
        # the seps structure (compiled tops live in the executable: 0 bytes)
        if self.top_kind == "kary":
            return int(self.top.tree.size * self.top.tree.dtype.itemsize)
        return 0


def _make_page_of_raw(top_kind: str, top, num_pages: int, *, lane: int,
                      tile_rows: int) -> Callable:
    """Top-tier descent as a plain traceable fn: query batch -> page id.
    (Jitted standalone for the host plan; inlined into the fused pipeline
    for the device plan.)"""
    if top_kind == "trivial":
        return lambda q: jnp.zeros(q.shape, jnp.int32)
    if top_kind == "nitrogen":
        def page_of(q):
            return jnp.minimum(nitrogen.search(top, q), num_pages - 1)
        return page_of
    # kary: pre-split the tree into per-level VMEM operands once at build
    levels = ops.kary_levels(top, lane)
    fanout = top.fanout
    tq = tile_rows * lane

    def page_of(q):
        n_q = q.shape[0]
        pad = _ceil_to(max(n_q, 1), tq) - n_q
        qp = jnp.concatenate([q, jnp.zeros((pad,), q.dtype)]) if pad else q
        ranks = _kary.kary_search_tiled(qp.reshape(-1, lane), levels,
                                        fanout=fanout, tile_rows=tile_rows)
        return jnp.minimum(ranks.reshape(-1)[:n_q], num_pages - 1)

    return page_of


def _make_pipeline(page_of_raw: Callable, *, num_pages: int, stride: int,
                   tile: int, clip: int,
                   plan_method: str | None = None,
                   with_stats: bool = False,
                   const_pages: Any = None) -> Callable:
    """The single-dispatch pipeline (DESIGN.md §4) as a plain traceable fn:
    top descent -> device plan at the static worst-case grid -> rung-selected
    page kernel -> un-permute. By default `pages` is passed (not closed
    over) so the leaf storage is not baked into the executable — the
    data-as-jit-args posture that lets the mutable store swap rows without
    retracing.

    ``const_pages`` flips that contract (DESIGN.md §10, the NitroGen
    specialization mode): pass the device leaf array and the returned
    pipeline takes only ``(q,)``, with the leaf storage, the compiled top
    (already closed over via ``page_of_raw``), and the layout constants
    (tile, stride, page count, and the rung ladder — computed here once
    per batch shape via ``schedule.ladder_for`` instead of re-derived
    inside the scheduler) all baked into the executable as compile-time
    constants.

    ``stride`` is the per-page rank base fed to the page kernel: the dense
    engine uses ``leaf_width`` (ranks are global searchsorted positions);
    the mutable store (engine/store.py) uses ``lw_pad`` so the returned
    value is a flat *slot address* into the gapped [num_pages, lw_pad]
    storage. Results are clipped to ``clip``.

    ``plan_method`` picks the device-plan construction (None = static
    per-(Q, num_pages) selection, DESIGN.md §2.1 — deep batches over few
    pages get the O(Q+P) histogram plan, everything else the packed sort;
    the thresholds are the autotuner's ``schedule.set_plan_thresholds``
    knob). ``with_stats=True`` additionally returns the plan's traced step
    count, the executed-occupancy feedback the micro-batch queue consumes
    — still one dispatch, no extra sync."""

    def pipeline(q, pages):
        # named_scope markers are trace-time only (zero runtime cost):
        # they attribute device-profile time to the pipeline's stages
        q_n = q.shape[0]
        with jax.named_scope("tiered/top_descent"):
            pids = page_of_raw(q)
        with jax.named_scope("tiered/device_plan"):
            g_cap, rungs = ladder_for(q_n, tile, num_pages)
            plan = device_plan(pids, tile, g_cap, num_pages,
                               method=plan_method)

        def body(qb, step_pages, g):
            return _page.page_search_bucketed(qb, step_pages, pages,
                                              stride=stride)

        with jax.named_scope("tiered/page_kernel"):
            out = run_scheduled(plan, q, q_n, tile, g_cap, body,
                                rungs=rungs)
        out = jnp.minimum(out, clip)
        return (out, plan.steps_used) if with_stats else out

    if const_pages is None:
        return pipeline

    def pipeline_spec(q):
        return pipeline(q, const_pages)

    return pipeline_spec


def build_top(seps: np.ndarray, *, top: str = "auto",
              vmem_budget: int = ops.VMEM_BUDGET_BYTES):
    """Top-tier index over the page-last-keys array: returns
    (top_kind, top_idx). Shared by the dense build below and the mutable
    store's merge path (engine/store.py), which re-derives the top only
    when the page count changes."""
    if top not in ("auto", "nitrogen", "kary"):
        raise ValueError(f"unknown top tier {top!r}; "
                         "want 'auto', 'nitrogen' or 'kary'")
    num_pages = int(seps.size)
    top_kind = top
    if top == "auto":
        top_kind = "nitrogen" if num_pages <= NITROGEN_TOP_MAX_PAGES \
            else "kary"
    if num_pages == 1:
        top_kind = "trivial"
    if top_kind == "nitrogen":
        levels = max(1, next_pow(4, num_pages) - 1)
        top_idx = nitrogen.build(seps, levels=levels, node_width=3,
                                 bottom="vector")
    elif top_kind == "kary":
        top_idx = kary.build(seps, node_width=127)
        vmem = ops.kary_vmem_bytes(num_pages, node_width=127)
        if vmem > vmem_budget:
            raise ValueError(
                f"top tier over {num_pages} pages needs ~{vmem/2**20:.1f} MiB "
                "VMEM; increase leaf_width or lower vmem_budget pressure")
    else:                                   # trivial: single-page index
        top_idx = None
    return top_kind, top_idx


def build(keys, *, leaf_width: int | None = None, tile: int = 128,
          top: str = "auto", plan: str = "device",
          vmem_budget: int = ops.VMEM_BUDGET_BYTES,
          specialize: bool = False) -> TieredIndex:
    if plan not in PLAN_MODES:
        raise ValueError(f"unknown plan mode {plan!r}; "
                         f"want one of {PLAN_MODES}")
    srt = as_sorted_numpy(keys)
    n = int(srt.size)
    auto_lw, _, _ = plan_tiers(n, tile=tile, vmem_budget=vmem_budget)
    lw = int(leaf_width) if leaf_width else auto_lw
    num_pages = -(-n // lw)
    lw_pad = _ceil_to(lw, 128)
    sent = sentinel_for(srt.dtype)
    pages = np.full((num_pages, lw_pad), sent, srt.dtype)
    pages[:, :lw] = pad_to(srt, num_pages * lw).reshape(num_pages, lw)
    seps = pages[:, lw - 1].copy()          # ascending; sentinel on partial tail

    top_kind, top_idx = build_top(seps, top=top, vmem_budget=vmem_budget)
    page_of_raw = _make_page_of_raw(top_kind, top_idx, num_pages, lane=128,
                                    tile_rows=8)
    pipeline = _make_pipeline(page_of_raw, num_pages=num_pages, stride=lw,
                              tile=int(tile), clip=n)
    donate = srt.dtype == np.int32
    pages_dev = _page.page_tiles(pages)
    search_spec = spec_pipe = None
    if specialize:
        # specialization mode (DESIGN.md §10): the SAME traceable pipeline,
        # re-staged with the device leaf array closed over — the jitted
        # variant takes only the query batch, so the index data rides the
        # executable (NitroGen's compile-the-index-into-code, jax-style).
        # The frozen index never mutates, so the constant can never go
        # stale; the mutable store's re-specialization discipline lives in
        # engine/store.py.
        spec_pipe = _make_pipeline(
            page_of_raw, num_pages=num_pages, stride=lw, tile=int(tile),
            clip=n, const_pages=pages_dev)
        search_spec = functools.partial(
            jax.jit, donate_argnums=(0,) if donate else ())(spec_pipe)
    return TieredIndex(
        pages=pages_dev,
        seps=jnp.asarray(seps), n=n, leaf_width=lw, lw_pad=lw_pad,
        num_pages=num_pages, tile=int(tile), top_kind=top_kind, top=top_idx,
        page_of=jax.jit(page_of_raw), page_of_raw=page_of_raw,
        search_raw=pipeline,
        search_fused=functools.partial(
            jax.jit, donate_argnums=(0,) if donate else ())(pipeline),
        donate=donate, plan=plan, specialize=specialize,
        search_spec=search_spec, search_spec_raw=spec_pipe)


@functools.partial(jax.jit, static_argnames=("leaf_width", "n"))
def _finish(q, pages, gather, valid, step_pages, *, leaf_width: int, n: int):
    """Gather sorted tiles -> bucketed page kernel -> un-permute to request
    order. Static grid comes from `gather`'s (ladder-padded) shape."""
    tile = gather.shape[0] // step_pages.shape[0]
    q_n = q.shape[0]
    q_src = q if q_n else jnp.zeros((1,), q.dtype)   # Q == 0: all lanes masked
    qb = jnp.take(q_src, gather, axis=0,
                  mode="clip").reshape(step_pages.shape[0], tile)
    ranks = _page.page_search_bucketed(qb, step_pages, pages,
                                       stride=leaf_width)
    flat = ranks.reshape(-1)
    # padded lanes scatter out of bounds and are dropped
    out = jnp.zeros((q_n,), jnp.int32).at[
        jnp.where(valid, gather, q_n)].set(flat, mode="drop")
    return jnp.minimum(out, n)


def search_with_plan(index: TieredIndex, queries) -> tuple:
    """Host-scheduled tiered search; also returns the BucketPlan (stats).
    This is the ``plan="host"`` path: one host sync between the top descent
    and the page kernel, in exchange for an inspectable plan."""
    q = jnp.asarray(queries)
    pids = np.asarray(index.page_of(q))
    plan = bucket_plan(pids, index.tile)
    ranks = _finish(q, index.pages, jnp.asarray(plan.gather),
                    jnp.asarray(plan.valid), jnp.asarray(plan.step_pages),
                    leaf_width=index.leaf_width, n=index.n)
    return ranks, plan


def search(index: TieredIndex, queries, *, plan: str | None = None
           ) -> jnp.ndarray:
    """Tiered search. ``plan`` overrides the index default: "device" runs
    the whole pipeline as one jitted dispatch (no host syncs); "host"
    computes the bucket plan in numpy (stats/debug)."""
    mode = plan or index.plan
    if mode not in PLAN_MODES:
        raise ValueError(f"unknown plan mode {mode!r}; "
                         f"want one of {PLAN_MODES}")
    if mode == "host":
        ranks, _ = search_with_plan(index, queries)
        return ranks
    owned = not isinstance(queries, jax.Array)
    q = jnp.asarray(queries)
    if not owned and index.donate:
        # the fused pipeline donates its query buffer; never eat the caller's
        # (no copy needed when the pipeline was built without donation)
        q = jnp.copy(q)
    # dispatch-boundary timer (the obs-smoke overhead gate's subject):
    # search_fused returns once the dispatch is staged — no sync added.
    # A specialized index (search_spec) dispatches on the query alone:
    # the leaf pages live inside the executable, not the argument list.
    with _span("tiered.search", n=int(q.shape[0])):
        t0 = time.perf_counter()
        if index.search_spec is not None:
            out = index.search_spec(q)
        else:
            out = index.search_fused(q, index.pages)
        reg = get_registry()
        reg.histogram("dispatch_staging_seconds",
                      path="search").observe(
            time.perf_counter() - t0)
    return out


def searcher(index: TieredIndex) -> Callable:
    """The engine's serving entry point: a closure over the index whose
    fused pipeline (device plan) or device stages (host plan) are jit-cached
    per batch shape."""
    def run(queries):
        return search(index, queries)
    return run


# ---------------------------------------------------------------- ranges
def _make_span_of(page_of_raw: Callable, key_dtype) -> Callable:
    """Doubled-endpoint descent (DESIGN.md §8): ``(lo, hi) -> (page_lo,
    page_hi)``, the inclusive boundary pages of each query's page span.
    Both endpoint batches descend the compiled top in ONE 2Q pass. The
    upper endpoint descends as its *successor* (``hi+1`` for ints,
    ``nextafter`` for floats — searchsorted-right routing): separators
    duplicate across pages when a key run crosses a boundary, and routing
    ``hi`` itself would close the span one page early, dropping the run's
    tail copies of ``hi``."""
    is_float = np.issubdtype(np.dtype(key_dtype), np.floating)

    def span_of(lo, hi):
        q_n = lo.shape[0]
        hi_next = jnp.nextafter(hi, jnp.inf) if is_float else hi + 1
        pids = page_of_raw(jnp.concatenate([lo, hi_next]))
        plo = pids[:q_n].astype(jnp.int32)
        # hi >= lo implies page_hi >= page_lo (descent is monotone); the
        # max only disciplines inverted (empty) ranges
        phi = jnp.maximum(pids[q_n:].astype(jnp.int32), plo)
        return plo, phi

    return span_of


def search_range_raw(index: TieredIndex) -> Callable:
    """Traceable ``(lo, hi, pages) -> (r_lo, r_hi_excl, count)`` over the
    range-scan subsystem (engine/scan.py, DESIGN.md §8) — the doubled
    descent, boundary-page kernel and interior count prefix in one
    composable fn (the scanner's aux arrays ride along as captured
    constants; the leaf storage stays an argument)."""
    from .scan import scanner_for
    return scanner_for(index).range_raw


def search_range(index: TieredIndex, lo, hi):
    """Batched range ranks as ONE fused dispatch: for each ``lo[i] <=
    hi[i]`` the half-open rank interval [r_lo, r_hi_excl) of keys in
    ``[lo, hi]`` plus the count — exact for duplicate keys at either
    endpoint (both endpoints descend with searchsorted-left/-right
    routing); ``lo > hi`` normalizes to the empty interval at r_lo."""
    from .scan import scanner_for
    return scanner_for(index).search_range(lo, hi)
