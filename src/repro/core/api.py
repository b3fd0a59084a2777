"""Public facade over the index-search core.

    idx = build_index(keys, values, IndexConfig(kind="nitrogen", levels=3))
    hit = idx.lookup(queries)        # -> LookupResult(rank, found, values)

This is the interface the serving stack uses (prefix-page index, sampler) and
the interface the paper-figure benchmarks drive.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import sorted_array, css_tree, kary, fast_tree, nitrogen
from ..obs import get_registry, span

KINDS = ("binary", "css", "kary", "fast", "nitrogen", "tiered")


@dataclass(frozen=True)
class IndexConfig:
    kind: str = "css"
    node_width: int = 128        # css/kary/fast: keys per node
    leaf_width: Optional[int] = None
    linear_cutoff: int = 1       # binary: switch-to-linear threshold
    page_depth: int = 2          # fast: directory levels per page
    levels: int = 3              # nitrogen: compiled levels
    compiled_node_width: int = 3  # nitrogen: separators per compiled node
    bottom: str = "binary"       # nitrogen: base approach under the code
    intra: str = "vector"        # css: intra-node search style
    top: str = "auto"            # tiered: top tier ('auto'|'nitrogen'|'kary')
    tile: int = 128              # tiered: queries per bucket / grid step
    plan: str = "device"         # tiered: schedule placement ('device'|'host')
    # compile the index INTO the program (DESIGN.md §10): the built top
    # tier, separators, page addresses and layout constants close over the
    # jitted pipeline as compile-time constants instead of riding as jit
    # args. Mutable stores re-specialize only at the derive boundary and
    # fall back to data-as-jit-args between derives.
    specialize: bool = False
    mutable: bool = False        # delta-merge write path (engine/store.py)
    delta_capacity: int = 1024   # mutable: delta buffer size (rounded to pow2)
    # mutable-store maintenance + durability (DESIGN.md §6.3–§6.5)
    maintenance: str = "deferred"  # 'deferred'|'inline'|'thread' fold policy
    maintenance_interval_s: float = 0.05  # thread mode: fold timer delay
    ckpt_dir: Optional[str] = None  # journal + snapshot dir (None = off)
    ckpt_keep: int = 3           # snapshots retained by Index.save rotation
    journal_fsync: str = "rotate"  # WAL sync: 'never'|'rotate'|'always'
    # micro-batch queue knobs (engine/queue.py, DESIGN.md §7) — consumed by
    # queue clients such as serve.kv_cache.PrefixPageStore.probe_queue
    queue_capacity: int = 4096   # hard flush trigger (pending queries)
    queue_deadline_s: float = 0.002  # max time a submit may wait in-queue
    queue_min_flush: int = 64    # floor of the adaptive flush threshold
    queue_adapt: bool = True     # occupancy feedback steers the threshold
    # multi-tenant admission knobs (engine/admission.py, DESIGN.md §7.1)
    queue_max_share: float = 1.0  # hard cap on one tenant's share of a flush
    queue_adaptive_deadline: bool = True  # EWMA rate scales the flush window
    queue_deadline_floor_s: float = 1e-4  # lower bound of the scaled window
    queue_max_backlog: int = 0   # per-tenant pending-query limit (0 = off)

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown index kind {self.kind!r}; want one of {KINDS}")
        if self.plan not in ("device", "host"):
            raise ValueError(
                f"unknown plan mode {self.plan!r}; want 'device' or 'host'")
        if self.specialize and self.kind == "tiered" and self.plan == "host":
            raise ValueError(
                "specialize=True requires the device plan for kind='tiered' "
                "(the host BucketPlan reads per-batch stats that cannot be "
                "baked into the executable); use plan='device'")
        if self.mutable and self.delta_capacity <= 0:
            raise ValueError(
                f"delta_capacity must be positive, got {self.delta_capacity}")
        if self.maintenance not in ("deferred", "inline", "thread"):
            raise ValueError(
                f"unknown maintenance mode {self.maintenance!r}; want "
                "'deferred', 'inline' or 'thread'")
        if self.maintenance_interval_s < 0:
            raise ValueError(
                f"maintenance_interval_s must be >= 0, got "
                f"{self.maintenance_interval_s}")
        if self.ckpt_keep <= 0:
            raise ValueError(
                f"ckpt_keep must be positive, got {self.ckpt_keep}")
        if self.journal_fsync not in ("never", "rotate", "always"):
            raise ValueError(
                f"unknown journal_fsync policy {self.journal_fsync!r}; "
                "want 'never', 'rotate' or 'always'")
        if self.queue_capacity <= 0:
            raise ValueError(
                f"queue_capacity must be positive, got {self.queue_capacity}")
        if self.queue_deadline_s < 0:
            raise ValueError(
                f"queue_deadline_s must be >= 0, got {self.queue_deadline_s}")
        if not (0.0 < self.queue_max_share <= 1.0):
            raise ValueError(
                f"queue_max_share must be in (0, 1], got "
                f"{self.queue_max_share}")
        if self.queue_deadline_floor_s < 0:
            raise ValueError(
                f"queue_deadline_floor_s must be >= 0, got "
                f"{self.queue_deadline_floor_s}")
        if self.queue_max_backlog < 0:
            raise ValueError(
                f"queue_max_backlog must be >= 0, got "
                f"{self.queue_max_backlog}")

    @classmethod
    def from_tuned(cls, platform: Optional[str] = None, *,
                   profile_dir: Optional[str] = None,
                   **overrides) -> "IndexConfig":
        """Config from a persisted autotuner profile (``repro.tune``):
        ``tuned_<platform>.json`` under ``src/repro/configs/`` (or
        ``profile_dir``) supplies tile / leaf_width / queue knobs /
        specialize; ``platform=None`` resolves to the current jax backend.
        Module-global plan thresholds the profile carries
        (``histogram_max_pages``) are applied to ``engine.schedule`` as a
        side effect — they are machine-wide, not per-config. Keyword
        ``overrides`` win over the profile's knobs."""
        from ..tune.profile import load_profile
        prof = load_profile(platform, profile_dir=profile_dir)
        kw = prof.config_kwargs()
        kw.update(overrides)
        cfg = cls(**kw)
        prof.apply_thresholds()
        return cfg


@dataclass(frozen=True)
class LookupResult:
    rank: jnp.ndarray            # searchsorted-left rank, [Q]
    found: jnp.ndarray           # bool [Q]
    values: Optional[jnp.ndarray]  # payload for hits (arbitrary for misses)


# a pytree, so results flow through jit boundaries and the micro-batch
# queue's per-caller slicing (engine/queue.py) without special-casing
jax.tree_util.register_pytree_node(
    LookupResult,
    lambda r: ((r.rank, r.found, r.values), None),
    lambda _, leaves: LookupResult(*leaves))


@dataclass(frozen=True)
class Index:
    config: IndexConfig
    impl: Any
    keys_sorted: jnp.ndarray
    values_sorted: Optional[jnp.ndarray]
    n: int

    def search(self, queries) -> jnp.ndarray:
        q = jnp.asarray(queries)
        mod = _module_for(self.config.kind)
        if self.config.specialize and self.config.kind != "tiered":
            # specialization for the flat/tree kinds: one jitted closure
            # with the searcher's arrays captured as compile-time constants
            # (the tiered kind carries its own specialized pipeline on the
            # impl — engine/tiered.py). Frozen index, so never stale.
            fn = getattr(self, "_spec_search", None)
            if fn is None:
                impl = self.impl
                fn = jax.jit(lambda qq: mod.search(impl, qq))
                object.__setattr__(self, "_spec_search", fn)
            return fn(q)
        return mod.search(self.impl, q)

    def search_range(self, lo, hi) -> tuple:
        """Range query (thesis §1.1: 'simple to extend'): for each pair,
        the half-open rank interval [r_lo, r_hi_excl) of keys with
        lo <= key <= hi, plus the match count. Exact under duplicate keys
        at either endpoint; ``lo > hi`` normalizes to the empty interval
        at r_lo. ``kind='tiered'`` routes through the range-scan subsystem
        (engine/scan.py, DESIGN.md §8): both endpoints descend the
        compiled top in ONE fused dispatch."""
        lo = jnp.asarray(lo)
        hi = jnp.asarray(hi)
        if self.config.kind == "tiered":
            from ..engine import scan as _scan
            # the rank-only scanner: count-mode never streams values, so
            # don't pay the value-page build for a rank query
            return _scan.scanner_for(self.impl).search_range(lo, hi)
        r_lo = self.search(lo)
        if jnp.issubdtype(hi.dtype, jnp.integer):
            # searchsorted-right(hi) == searchsorted-left(hi + 1); hi < the
            # sentinel by the key-domain contract, so hi+1 never overflows
            r_hi_excl = self.search(hi + 1)
        else:
            # searchsorted-right(hi) == searchsorted-left(nextafter(hi)) —
            # the float twin of hi+1: duplicate float keys equal to hi all
            # count, exactly
            r_hi_excl = self.search(jnp.nextafter(hi, jnp.inf))
        r_hi_excl = jnp.where(lo > hi, r_lo, r_hi_excl)
        return r_lo, r_hi_excl, jnp.maximum(r_hi_excl - r_lo, 0)

    def scan_range(self, lo, hi, *, aggs=None,
                   materialize: Optional[int] = None):
        """Batched range scan with aggregation pushdown (DESIGN.md §8):
        per query the match count, rank interval, and — when the index
        carries int32/float32 values — their sum / min / max, computed
        without materializing matches. ``aggs`` (e.g. ``("count", "sum")``)
        caps the pushdown depth: the tiered kernel then streams and
        computes strictly less. ``materialize=K`` additionally compacts
        the first K matching ranks (and values) per query with an overflow
        flag. ``kind='tiered'`` runs the fused span-scan dispatch
        (boundary-page kernel + interior page aggregates); other kinds
        fall back to rank intervals + O(1) prefix/sparse-table lookups.
        Returns ``engine.scan.ScanResult``."""
        from ..engine import scan as _scan
        if self.config.kind == "tiered":
            return _scan.scanner_for(self.impl, self.values_sorted) \
                .scan_range(lo, hi, aggs=aggs, materialize=materialize)
        mode = _scan.mode_for_aggs(aggs)     # validates the names, caps
        r_lo, r_hi_excl, cnt = self.search_range(lo, hi)
        r_lo = r_lo.astype(jnp.int32)
        r_hi_excl = r_hi_excl.astype(jnp.int32)
        cnt = cnt.astype(jnp.int32)
        vsum = vmin = vmax = None
        if mode != "count" and self.values_sorted is not None:
            fa = getattr(self, "_flat_aggregator", None)
            if fa is None:
                fa = _scan.FlatAggregator(np.asarray(self.values_sorted))
                object.__setattr__(self, "_flat_aggregator", fa)
            if fa.ok:
                vsum, vmin, vmax = fa(r_lo, r_hi_excl)
                if mode == "sum":
                    vmin = vmax = None
        if materialize is None:
            return _scan.ScanResult(count=cnt, r_lo=r_lo,
                                    r_hi_excl=r_hi_excl, vsum=vsum,
                                    vmin=vmin, vmax=vmax)
        ranks, vals, over = _scan.materialize_interval(
            r_lo, cnt, self.values_sorted, K=int(materialize))
        return _scan.ScanResult(count=cnt, r_lo=r_lo, r_hi_excl=r_hi_excl,
                                vsum=vsum, vmin=vmin, vmax=vmax,
                                ranks=ranks, values=vals, overflow=over)

    def _flat_agg(self):
        fa = getattr(self, "_flat_aggregator", None)
        if fa is None:
            from ..engine import scan as _scan
            fa = _scan.FlatAggregator(np.asarray(self.values_sorted))
            object.__setattr__(self, "_flat_aggregator", fa)
        return fa

    def scan_groups(self, lo, hi, num_groups, *, aggs=None,
                    top_k: Optional[int] = None,
                    candidates: Optional[int] = None):
        """Grouped range analytics (DESIGN.md §8.3): each ``(lo, hi)``
        range splits into ``num_groups`` equal-width key buckets with
        per-bucket count / sum / min / max pushdown (``aggs`` caps the
        depth) and optional per-bucket ``top_k`` values (``candidates``
        bounds the materialized window per bucket). ``kind='tiered'``
        answers in ONE fused dispatch — count/sum ride a (G+1)-edge
        prefix pipeline that never scans interior pages; other kinds
        fall back to G+1 searches + O(1) rank-interval aggregates.
        Returns ``engine.groupby.GroupScanResult``."""
        from ..engine import scan as _scan
        from ..engine import groupby as _gb
        if self.config.kind == "tiered":
            return _scan.scanner_for(self.impl, self.values_sorted) \
                .scan_groups(lo, hi, num_groups, aggs=aggs, top_k=top_k,
                             candidates=candidates)
        mode = _scan.mode_for_aggs(aggs)
        kd = np.dtype(self.keys_sorted.dtype)
        lo = jnp.asarray(lo, kd)
        hi = jnp.asarray(hi, kd)
        G = int(num_groups)
        if not 1 <= G <= _gb.MAX_GROUPS:
            raise ValueError(f"num_groups must be in [1, {_gb.MAX_GROUPS}]"
                             f", got {num_groups}")
        K = C = None
        if top_k is not None:
            K = int(top_k)
            if K < 1:
                raise ValueError(f"top_k must be positive, got {top_k}")
            if self.values_sorted is None:
                raise ValueError("top_k needs an index built with values")
            C = max(int(candidates) if candidates is not None
                    else max(2 * K, 32), K)
        # the bucket edges are searchsorted-left probes by construction
        # (bucket g = [e_g, e_{g+1})), so G+1 point searches give every
        # r_edge; counts and aggregates are adjacent-edge differences
        edges = _gb.group_edges(lo, hi, G, kd)
        r_edge = self.search(edges.reshape(-1)).astype(jnp.int32) \
            .reshape(-1, G + 1)
        cnt = jnp.diff(r_edge, axis=1)
        vsum = vmin = vmax = None
        if mode != "count" and self.values_sorted is not None:
            fa = self._flat_agg()
            if fa.ok:
                vs, mn, mx = fa(r_edge[:, :-1].reshape(-1),
                                r_edge[:, 1:].reshape(-1))
                vsum = vs.reshape(-1, G)
                if mode == "full":
                    vmin = mn.reshape(-1, G)
                    vmax = mx.reshape(-1, G)
        res = _gb.GroupScanResult(count=cnt, edges=edges, r_edge=r_edge,
                                  vsum=vsum, vmin=vmin, vmax=vmax)
        if K is None:
            return res
        ranks, vals, over = _scan.materialize_interval(
            r_edge[:, :-1].reshape(-1), cnt.reshape(-1),
            self.values_sorted, K=C)
        topv, topr = _gb.masked_topk(vals, ranks, cnt.reshape(-1), K)
        return dataclasses.replace(
            res, topk_values=topv.reshape(-1, G, K),
            topk_ranks=topr.reshape(-1, G, K),
            overflow=over.reshape(-1, G))

    def scan_multi(self, ranges, *, op: str = "union", aggs=None):
        """Composite multi-range predicates: ``ranges`` is [Q, R, 2]
        inclusive (lo, hi) pairs per query, combined as a union (IN-list
        of ranges) or intersection (conjunctive predicate). The
        coverage-count decomposition canonicalizes each predicate into
        at most R disjoint ranges; ``kind='tiered'`` aggregates them in
        ONE fused dispatch, other kinds fall back to the rank-interval
        machinery. Returns ``engine.scan.ScanResult`` whose
        r_lo/r_hi_excl are the rank hull of the matching set."""
        from ..engine import scan as _scan
        from ..engine import groupby as _gb
        if self.config.kind == "tiered":
            return _scan.scanner_for(self.impl, self.values_sorted) \
                .scan_multi(ranges, op=op, aggs=aggs)
        if op not in _gb.MULTI_OPS:
            raise ValueError(f"unknown multi-range op {op!r}; "
                             f"want one of {_gb.MULTI_OPS}")
        kd = np.dtype(self.keys_sorted.dtype)
        r = jnp.asarray(ranges, kd)
        if r.ndim != 3 or r.shape[-1] != 2:
            raise ValueError(f"ranges must be [Q, R, 2], got {r.shape}")
        R = int(r.shape[1])
        if R < 1:
            raise ValueError("ranges needs at least one range per query")
        mode = _scan.mode_for_aggs(aggs)
        slo, shi = _gb.coverage_ranges(r[..., 0], r[..., 1], op=op,
                                       key_dtype=kd)
        r_lo, r_hi, cnt = self.search_range(slo.reshape(-1),
                                            shi.reshape(-1))
        r_lo = r_lo.astype(jnp.int32)
        r_hi = r_hi.astype(jnp.int32)
        cnt = cnt.astype(jnp.int32)
        vs = mn = mx = None
        mode_eff = "count"
        if mode != "count" and self.values_sorted is not None:
            fa = self._flat_agg()
            if fa.ok:
                vs, mn, mx = fa(r_lo, r_hi)
                mode_eff = mode
        count, vsum, vmin, vmax, hlo, hhi = _gb._multi_reduce(
            R, mode_eff, cnt, vs, mn, mx, r_lo, r_hi)
        return _scan.ScanResult(count=count, r_lo=hlo, r_hi_excl=hhi,
                                vsum=vsum, vmin=vmin, vmax=vmax)

    @functools.cached_property
    def _fused(self):
        """The frozen tiered lookup as one program: ``(raw, data, owned,
        shared)``, where ``raw(q, *data) -> (rank, found, values)`` is the
        traceable composition of the device-plan pipeline with the clamp
        and the found/value gathers, ``owned`` its jit that donates the
        query buffer (int32 keys, as ``search_fused``) and ``shared`` its
        jit that never does. The leaf pages and sorted columns ride in
        ``data`` as jit arguments, so the table never enters an executable
        (a specialized index bakes only its pages, as ``search_spec``
        does). None for the other kinds and for ``plan="host"``, which
        keep the eager lookup."""
        impl = self.impl
        if self.config.kind != "tiered" or impl.plan != "device":
            return None
        spec = impl.search_spec_raw
        if spec is not None:
            fused_lookup = _lookup_program(lambda q, _: spec(q))
            data = (None, self.keys_sorted, self.values_sorted)
        else:
            fused_lookup = _lookup_program(impl.search_raw)
            data = (impl.pages, self.keys_sorted, self.values_sorted)
        shared = jax.jit(fused_lookup)
        owned = jax.jit(fused_lookup, donate_argnums=(0,)) if impl.donate \
            else shared
        return fused_lookup, data, owned, shared

    def lookup(self, queries) -> LookupResult:
        """Ranks, found flags and values, under span ``index.lookup``.
        The device-plan tiered kind answers in ONE jitted program (span
        ``lookup.fused``): a host batch is uploaded by the call itself and
        its buffer donated; a caller's device array is never donated; a
        tracer (the lookup inside a caller's jit) gets the raw composition.
        Every other kind, and ``plan="host"``, runs eagerly: ``lookup.put``
        (the batch to the device), the search, ``lookup.gather`` (found and
        values from the sorted columns)."""
        fused = self._fused
        kind = self.config.kind
        with span("index.lookup", kind=kind):
            if fused is not None:
                raw, data, owned, shared = fused
                if isinstance(queries, jax.core.Tracer):
                    rank, found, vals = raw(queries, *data)
                else:
                    if isinstance(queries, jax.Array):
                        fn, q = shared, queries
                    else:
                        fn, q = owned, np.asarray(queries)
                    with span("lookup.fused"):
                        t0 = time.perf_counter()
                        rank, found, vals = fn(q, *data)
                        get_registry().histogram(
                            "dispatch_staging_seconds", path="lookup") \
                            .observe(time.perf_counter() - t0)
                path = "fused"
            else:
                with span("lookup.put"):
                    q = jnp.asarray(queries)
                rank = self.search(q)
                with span("lookup.gather"):
                    found, vals = _found_values(q, rank, self.keys_sorted,
                                                self.values_sorted)
                path = "eager"
            get_registry().counter("index_lookup_dispatches", kind=kind,
                                   path=path).inc()
            return LookupResult(rank=rank, found=found, values=vals)

    def delete(self, keys):
        """Frozen indexes have no write path — deletes need the mutable
        store (``IndexConfig(mutable=True)`` routes ``build_index`` to
        ``MutableIndex``, which supports tombstone deletes)."""
        raise TypeError(
            "this index is immutable; build with "
            "IndexConfig(mutable=True) for insert/delete support")

    def save(self, ckpt_dir=None):
        """Snapshot/restore is the mutable store's durability contract
        (``MutableIndex.save``); frozen indexes are rebuilt from their
        source arrays."""
        raise TypeError(
            "this index is immutable; build with "
            "IndexConfig(mutable=True) for save/restore support")

    @property
    def tree_bytes(self) -> int:
        return int(getattr(self.impl, "tree_bytes", 0))


def _found_values(q, rank, keys_sorted, values_sorted):
    """Found flags and values of a rank batch from the sorted columns: the
    clamp and the two gathers that end every frozen lookup, eager or
    fused."""
    n = keys_sorted.shape[0]
    safe = jnp.minimum(rank, n - 1)
    found = (rank < n) & (jnp.take(keys_sorted, safe, axis=0) == q)
    vals = None
    if values_sorted is not None:
        vals = jnp.take(values_sorted, safe, axis=0)
    return found, vals


def _lookup_program(search):
    """The traceable frozen lookup over a rank search ``search(q, pages)``:
    ``(q, pages, keys_sorted, values_sorted) -> (rank, found, values)``."""
    def fused_lookup(q, pages, keys_sorted, values_sorted):
        rank = search(q, pages)
        return (rank,) + _found_values(q, rank, keys_sorted, values_sorted)
    return fused_lookup


_MODULES = {
    "binary": sorted_array,
    "css": css_tree,
    "kary": kary,
    "fast": fast_tree,
    "nitrogen": nitrogen,
}


def _module_for(kind: str):
    """Searcher module per kind; the tiered engine is imported lazily to
    keep core -> engine -> core from becoming an import cycle."""
    if kind == "tiered":
        from ..engine import tiered
        return tiered
    return _MODULES[kind]


def restore_index(ckpt_dir: str, config: IndexConfig = IndexConfig(
        kind="tiered", mutable=True)):
    """Warm-restart a mutable index from its checkpoint directory: the
    newest verifying snapshot (corrupt latest degrades to the previous
    step with a warning) plus a replay of the journaled writes after it —
    servable without an O(n) rebuild (DESIGN.md §6.5)."""
    if not config.mutable:
        raise ValueError("restore_index requires IndexConfig(mutable=True)")
    from ..engine.store import MutableIndex
    return MutableIndex.restore(ckpt_dir, config)


def build_index(keys, values=None, config: IndexConfig = IndexConfig()) -> Index:
    if config.mutable:
        # the delta-merge write path (DESIGN.md §6): returns a MutableIndex
        # (lookup + insert; under a tiered base, lookup stays one dispatch).
        # Unlike the frozen kinds it accepts an empty initial key set.
        from ..engine.store import MutableIndex
        return MutableIndex(config, keys, values)
    keys = np.asarray(keys)
    order = np.argsort(keys, kind="stable")
    srt = keys[order]
    vals = None
    if values is not None:
        values = np.asarray(values)
        if values.shape[0] != keys.shape[0]:
            raise ValueError("values must align with keys")
        vals = jnp.asarray(values[order])

    c = config
    if c.kind == "binary":
        impl = sorted_array.build(srt, linear_cutoff=c.linear_cutoff)
    elif c.kind == "css":
        impl = css_tree.build(srt, node_width=c.node_width,
                              leaf_width=c.leaf_width, intra=c.intra)
    elif c.kind == "kary":
        impl = kary.build(srt, node_width=c.node_width)
    elif c.kind == "fast":
        impl = fast_tree.build(srt, node_width=c.node_width,
                               leaf_width=c.leaf_width, page_depth=c.page_depth)
    elif c.kind == "nitrogen":
        impl = nitrogen.build(srt, levels=c.levels,
                              node_width=c.compiled_node_width, bottom=c.bottom,
                              css_node_width=c.node_width)
    elif c.kind == "tiered":
        from ..engine import tiered
        impl = tiered.build(srt, leaf_width=c.leaf_width, tile=c.tile,
                            top=c.top, plan=c.plan, specialize=c.specialize)
    else:  # pragma: no cover
        raise AssertionError
    return Index(config=c, impl=impl, keys_sorted=jnp.asarray(srt),
                 values_sorted=vals, n=int(srt.size))
