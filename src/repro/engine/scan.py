"""Batched range-scan subsystem (DESIGN.md §8): Q ``(lo, hi)`` range
queries against a tiered index become ONE fused dispatch with aggregation
pushdown.

Per batch:

1. **Doubled-endpoint descent** — ``[lo; hi']`` (2Q queries, ``hi'`` the
   successor of hi: ``hi+1`` / ``nextafter`` so duplicate runs of hi that
   cross a page boundary keep the span closed) descends the compiled top
   once (``tiered._make_span_of``), yielding each query's inclusive page
   span ``[page_lo, page_hi]``.
2. **Span expansion** — a naive per-(query, page) expansion has a
   data-dependent size (unjittable static shapes, O(Q * num_pages) worst
   case); instead every span contributes exactly its two *boundary* scan
   items, endpoint-masked (single-page spans carry both bounds on the
   lower item, the upper item is inert), and **interior pages are never
   scanned**: their contribution is read from per-page aggregate arrays —
   prefix sums for count/sum, power-of-two sparse tables for min/max —
   O(1) per query. That is what keeps the whole dispatch on the static
   grid ladder.
3. **Scheduling** — the 2Q boundary items are bucketed by page through the
   existing device-plan machinery (``schedule.span_scan_plan`` — packed
   sort or histogram construction, selected statically per
   (2Q, num_pages), reused unchanged: a span is just a pair of page
   buckets).
4. **Pushdown kernel** — ``kernels/page_scan.py`` executes one page row
   per grid step, computing endpoint-masked count / sum / min / max per
   lane plus the below-lo count that anchors ranks. Matches are never
   materialized in HBM: aggregate queries allocate O(Q), not O(matches).

Over the mutable store (engine/store.py) the same dispatch is
**delta-aware**: a branch-free in-range scan of the delta buffer joins the
base span scan, and a dup-aware shadowed-key correction (shadow bits
tracked at insert, base values synced so base ∪ delta is a duplicate
multiset) keeps upserted keys counted once — count/sum subtract the
shadowed terms, min/max are duplicate-insensitive, and the same dup count
yields exact merged searchsorted ranks (the ROADMAP "delta-aware ranks"
follow-on).

``materialize=K`` compacts the matching locators (global ranks for dense
stores, flat slot addresses for the gapped mutable store) and their values
into a caller-provided capacity ``K`` per query, with an overflow flag.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..core.util import sentinel_for
from ..kernels import page_scan as _pscan
from ..kernels.page_scan import agg_identities
from ..kernels.page_search import page_tiles
from ..obs import get_registry, span as _span
from .schedule import ladder_grid, run_scheduled_multi, span_scan_plan

VALUE_DTYPES = (np.dtype(np.int32), np.dtype(np.float32))


# ----------------------------------------------------------------- results
@dataclass(frozen=True)
class ScanResult:
    """Batched range-scan result; [Q]-shaped unless noted.

    count      int32 matches per query (delta-aware under the mutable store)
    r_lo       searchsorted-left rank of lo among the live keys — merged
               and shadow-corrected under the mutable store
    r_hi_excl  r_lo + count (== searchsorted-right(hi); lo > hi normalizes
               to the empty interval at r_lo)
    vsum/vmin/vmax  pushed-down aggregates over int32/float32 values (None
               when the index has no such values); an empty range reports
               0 / dtype-max / dtype-min; int32 sums wrap (two's
               complement, the numpy ``dtype=int32`` semantics — bit-equal
               to the oracle), float32 sums are reduction-order-dependent
               (per-page partials + prefix differences: last-ulp drift vs
               a sequential sum); count/min/max are always bit-exact
    ranks      [Q, K] materialize mode: match locators in ascending key
               order — global ranks for rank-addressed kinds, flat slot
               addresses for the gapped mutable store (delta-resident
               matches address the delta region at base_slots + slot);
               -1 past count. Materialize composes with ``aggs`` in the
               same dispatch (locator-only: pass ``aggs=("count",)``)
    values     [Q, K] the matching values (0 past count); None when the
               index has no values
    overflow   bool [Q] — count exceeded the materialize capacity K
    """
    count: jnp.ndarray
    r_lo: jnp.ndarray
    r_hi_excl: jnp.ndarray
    vsum: Optional[jnp.ndarray] = None
    vmin: Optional[jnp.ndarray] = None
    vmax: Optional[jnp.ndarray] = None
    ranks: Optional[jnp.ndarray] = None
    values: Optional[jnp.ndarray] = None
    overflow: Optional[jnp.ndarray] = None


def mode_for_aggs(aggs, has_values: bool = True) -> str:
    """Map a requested aggregate set to the kernel's static pushdown mode
    ("count" | "sum" | "full"). ``aggs=None`` means the deepest mode the
    index supports. Names are validated regardless of ``has_values`` — a
    typo must fail identically on valued and value-less indexes."""
    if aggs is not None:
        want = set(aggs)
        unknown = want - {"count", "sum", "min", "max"}
        if unknown:
            raise ValueError(f"unknown aggregates {sorted(unknown)}; "
                             "want a subset of count/sum/min/max")
    if not has_values:
        return "count"
    if aggs is None:
        return "full"
    if want & {"min", "max"}:
        return "full"
    return "sum" if "sum" in want else "count"


# ------------------------------------------------------- domain constants
def _domain_consts(key_dtype):
    """(lo_min, hi_cap, inert_lo, inert_hi) for ``key_dtype``:

    * ``lo_min`` / ``hi_cap`` — the widest in-domain bound pair: admits
      every user key (which the key-domain contract keeps strictly below
      the sentinel) but never a sentinel gap slot;
    * ``inert_lo`` / ``inert_hi`` — an impossible pair (lo maximal, hi
      minimal): the mask ``lo <= k <= hi`` is empty for every slot
      including the sentinel, which is how a lane is switched off.
    """
    kd = np.dtype(key_dtype)
    if np.issubdtype(kd, np.floating):
        return (kd.type(-np.inf), np.finfo(kd).max,
                kd.type(np.inf), kd.type(-np.inf))
    info = np.iinfo(kd)
    return (kd.type(info.min), kd.type(info.max - 1),
            kd.type(info.max), kd.type(info.min))


# ------------------------------------------------- per-page aggregate aux
class ScanAux(NamedTuple):
    """Device-resident interior-page aggregates (a pytree, passed as a jit
    argument so data updates never retrace).

    cum_cnt: [P+1] int32 exclusive prefix of per-page live counts — also
             the live-ordinal directory materialize uses to turn ordinals
             into gapped slot addresses;
    cum_sum: [P+1] value-dtype exclusive prefix of per-page value sums
             (int32 wraps);
    st_min/st_max: [L, P] power-of-two sparse tables over per-page value
             min/max — range-reducible in O(1) per query (min/max are not
             prefix-invertible, so prefixes cannot serve them).
    """
    cum_cnt: jnp.ndarray
    cum_sum: jnp.ndarray
    st_min: jnp.ndarray
    st_max: jnp.ndarray


def sparse_table(per_page: np.ndarray, op, identity) -> np.ndarray:
    """[L, P] table: st[k, p] reduces pages [p, min(p + 2^k, P)).
    Range reduce over [a, b), b > a: k = floor(log2(b-a)),
    op(st[k, a], st[k, b - 2^k])."""
    P = int(per_page.size)
    L = max(P.bit_length(), 1)
    st = np.full((L, P), identity, per_page.dtype)
    if P:
        st[0] = per_page
    for k in range(1, L):
        h = 1 << (k - 1)
        st[k, :P - h] = op(st[k - 1, :P - h], st[k - 1, h:])
        st[k, P - h:] = st[k - 1, P - h:]
    return st


def page_aggregates(vals: np.ndarray, cnt: np.ndarray, mask_value=None):
    """Host-side per-page (sum, min, max) over the live prefix of each
    value row ([P, lw_pad] + [P] live counts), vectorized. ``mask_value``
    (the mutable store's tombstone sentinel) excludes matching values —
    mirroring the kernel's static mask, so interior-page aggregates and
    boundary-page kernel lanes agree."""
    W = vals.shape[1]
    vd = vals.dtype
    id_min, id_max = agg_identities(vd)
    live = np.arange(W)[None, :] < np.asarray(cnt)[:, None]
    if mask_value is not None:
        live = live & (vals != vd.type(mask_value))
    psum = np.where(live, vals, 0).sum(axis=1, dtype=vd)
    pmin = np.where(live, vals, id_min).min(axis=1)
    pmax = np.where(live, vals, id_max).max(axis=1)
    return psum, pmin, pmax


def build_page_aux(cnt: np.ndarray, vals: Optional[np.ndarray],
                   val_dtype=np.int32, mask_value=None) -> ScanAux:
    """Device ScanAux from host truth: per-page live counts plus (optional)
    [P, lw_pad] value rows. With no values the sum/min/max members are
    identity-filled (their outputs are ignored). ``mask_value`` excludes
    tombstone-synced values from the value aggregates — ``cum_cnt`` stays
    PHYSICAL (the shadow algebra subtracts deleted keys from counts)."""
    cnt = np.asarray(cnt, np.int64)
    P = cnt.size
    vd = np.dtype(val_dtype)
    cum_cnt = np.zeros(P + 1, np.int32)
    cum_cnt[1:] = np.cumsum(cnt)
    id_min, id_max = agg_identities(vd)
    if vals is not None:
        psum, pmin, pmax = page_aggregates(np.asarray(vals, vd), cnt,
                                           mask_value)
    else:
        psum = np.zeros(P, vd)
        pmin = np.full(P, id_min, vd)
        pmax = np.full(P, id_max, vd)
    cum_sum = np.zeros(P + 1, vd)
    cum_sum[1:] = np.cumsum(psum, dtype=vd)
    return ScanAux(cum_cnt=jnp.asarray(cum_cnt),
                   cum_sum=jnp.asarray(cum_sum),
                   st_min=jnp.asarray(sparse_table(pmin, np.minimum, id_min)),
                   st_max=jnp.asarray(sparse_table(pmax, np.maximum, id_max)))


def _floor_log2(x: jnp.ndarray) -> jnp.ndarray:
    """Exact traceable floor(log2(x)) for int32 x >= 1. The float log2
    candidate can be off by one in either direction (2^k - 1 rounds up to
    k past the 24-bit mantissa; XLA computes log2 as a log ratio, which
    can round exact powers *down*), so it is corrected against integer
    shifts both ways. The up-shift is clamped to 30: x < 2^31 means the
    true floor never exceeds 30, and 1 << 31 would wrap negative."""
    k = jnp.floor(jnp.log2(x.astype(jnp.float32))).astype(jnp.int32)
    k = jnp.where(jnp.left_shift(jnp.int32(1), k) > x, k - 1, k)
    kp = jnp.minimum(k + 1, 30)
    return jnp.where(jnp.left_shift(jnp.int32(1), kp) <= x, kp, k)


def _table_range(st: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                 combine, identity):
    """Traceable sparse-table reduce over pages [a, b); identity where the
    range is empty. ``a``/``b`` are [Q] int32 with 0 <= a, b <= P."""
    P = st.shape[1]
    ln = b - a
    has = ln > 0
    k = _floor_log2(jnp.maximum(ln, 1))
    half = jnp.left_shift(jnp.int32(1), k)
    a1 = jnp.clip(a, 0, P - 1)
    a2 = jnp.clip(b - half, 0, P - 1)
    return jnp.where(has, combine(st[k, a1], st[k, a2]), identity)


# ------------------------------------------------------------ the pipeline
class SpanScan(NamedTuple):
    """Raw per-query quantities of one fused span scan (base side only):
    ``count`` (and, per the pipeline's static mode, ``vsum``/``vmin``/
    ``vmax`` — None otherwise) over the whole [lo, hi] span (boundary
    kernel lanes + interior aggregates), ``plo`` the lower boundary page,
    ``lt_lo`` the in-page live-key count below lo (the rank anchor: keys
    below lo in earlier pages are exactly ``cum_cnt[plo]``)."""
    count: jnp.ndarray
    vsum: Optional[jnp.ndarray]
    vmin: Optional[jnp.ndarray]
    vmax: Optional[jnp.ndarray]
    plo: jnp.ndarray
    lt_lo: jnp.ndarray


def make_span_pipeline(span_of: Callable, *, num_pages: int, tile: int,
                       key_dtype, val_dtype,
                       mode: str = "full", mask_value=None) -> Callable:
    """The fused span-scan as a plain traceable fn
    ``pipeline(lo, hi, kpages, vpages, aux) -> SpanScan``.

    ``span_of`` is the doubled-endpoint descent from
    ``tiered._make_span_of``. Pages and aux are passed (not closed over)
    so leaf storage and aggregate updates never retrace. The static
    ``mode`` ("count" | "sum" | "full") selects the pushdown depth — it is
    threaded into the kernel, which streams and computes strictly less in
    the narrower modes (count mode never touches the value pages).
    ``lo > hi`` queries run with inert masks: count 0, identities for the
    value aggregates, ``lt_lo`` still anchored at lo (empty-interval
    normalization falls out).
    """
    lo_min, hi_cap, inert_lo, inert_hi = _domain_consts(key_dtype)
    id_min, id_max = agg_identities(val_dtype)

    def pipeline(lo, hi, kpages, vpages, aux: ScanAux) -> SpanScan:
        q_n = lo.shape[0]
        empty = lo > hi
        # named_scope markers: trace-time device-profile attribution only
        with jax.named_scope("scan/span_of"):
            plo, phi = span_of(lo, hi)
        single = plo == phi
        # item i scans the lower boundary page: lob stays `lo` even for
        # empty ranges (its below-lo lane output anchors r_lo); the upper
        # bound closes at hi when the span is one page, else admits the
        # whole page (every key there is < hi by the separator routing).
        hib_a = jnp.where(empty, inert_hi, jnp.where(single, hi, hi_cap))
        # item Q+i scans the upper boundary page (every key there is >= lo
        # when the span has two or more pages); inert otherwise.
        lob_b = jnp.where(empty | single, inert_lo, lo_min)
        hib_b = jnp.where(empty | single, inert_hi, hi)
        item_lo = jnp.concatenate([lo, lob_b])
        item_hi = jnp.concatenate([hib_a, hib_b])
        with jax.named_scope("scan/span_plan"):
            g_cap = ladder_grid(2 * q_n, tile, num_pages)
            _, plan = span_scan_plan(plo, phi, tile, g_cap, num_pages)

        def body(qbs, step_pages, g):
            return _pscan.page_scan_bucketed(qbs[0], qbs[1], step_pages,
                                             kpages, vpages, mode=mode,
                                             mask_value=mask_value)

        with jax.named_scope("scan/page_kernel"):
            outs = run_scheduled_multi(
                plan, (item_lo, item_hi), 2 * q_n, tile, g_cap, body)
        lt, le = outs[0], outs[1]
        # in-range count per item, derived once per dispatch (not per grid
        # step); the clamp zeroes inert bound pairs
        cnt = jnp.maximum(le - lt, 0)
        cnt = cnt[:q_n] + cnt[q_n:]
        # interior pages (plo, phi) — aggregated, never scanned; for an
        # empty range phi == plo, so the interval is empty by construction
        with jax.named_scope("scan/interior"):
            a = plo + 1
            b = phi
            has = b > a
        icnt = jnp.where(has, aux.cum_cnt[b] - aux.cum_cnt[a], 0)
        vsum = vmin = vmax = None
        if mode != "count":
            vs = outs[2][:q_n] + outs[2][q_n:]
            isum = jnp.where(has, aux.cum_sum[b] - aux.cum_sum[a],
                             jnp.zeros((), aux.cum_sum.dtype))
            vsum = vs + isum
        if mode == "full":
            mn = jnp.minimum(outs[3][:q_n], outs[3][q_n:])
            mx = jnp.maximum(outs[4][:q_n], outs[4][q_n:])
            vmin = jnp.minimum(mn, _table_range(aux.st_min, a, b,
                                                jnp.minimum, id_min))
            vmax = jnp.maximum(mx, _table_range(aux.st_max, a, b,
                                                jnp.maximum, id_max))
        return SpanScan(count=(cnt + icnt).astype(jnp.int32),
                        vsum=vsum, vmin=vmin, vmax=vmax,
                        plo=plo.astype(jnp.int32),
                        lt_lo=lt[:q_n])

    return pipeline


# --------------------------------------------- immutable tiered front-end
class TieredScanner:
    """Fused batched range scans over an immutable TieredIndex.

    One instance owns the value pages, the interior aggregate arrays and
    the jitted dispatches (cached per batch shape / materialize capacity).
    Built lazily and cached on the index by :func:`scanner_for`; pass
    ``values`` (the api facade's sorted payload) to enable value-aggregate
    pushdown (int32/float32) and materialize-mode value gathers (any
    numeric dtype).
    """

    def __init__(self, index, values=None):
        from . import tiered as _tiered
        self.index = index
        P, lw, lwp = index.num_pages, index.leaf_width, index.lw_pad
        n = index.n
        kd = np.dtype(index.pages.dtype)
        self.key_dtype = kd
        cnt = np.full(P, lw, np.int64)
        cnt[-1] = n - (P - 1) * lw
        self.values_dev = None
        self.has_values = False
        vp_host = None
        vd = kd
        if values is not None:
            v = np.asarray(values)
            if v.dtype in VALUE_DTYPES:
                self.has_values = True
                vd = v.dtype
                flat = np.zeros(P * lw, vd)
                flat[:n] = v
                vp_host = np.zeros((P, lwp), vd)
                vp_host[:, :lw] = flat.reshape(P, lw)
            else:
                # non-pushdown dtypes keep a flat device copy purely for
                # materialize gathers; pushdown dtypes gather straight
                # from the value pages (one device copy, not two)
                self.values_dev = jnp.asarray(v)
        self.vpages = page_tiles(vp_host) if vp_host is not None else None
        self.aux = build_page_aux(cnt, vp_host, vd)
        self._span_of = _tiered._make_span_of(index.page_of_raw, kd)
        self._val_dtype = vd
        self._n, self._lw = n, lw
        # specialization (DESIGN.md §10): on a specialize=True index the
        # jitted dispatches close over the key/value pages AND the ScanAux
        # prefixes/sparse tables as compile-time constants — the scan twin
        # of the point pipeline's const_pages. A frozen index never
        # mutates, so the constants cannot go stale (the mutable store's
        # scan keeps aux as jit args precisely because ITS aux changes
        # per mutation, engine/store.py).
        self._spec = bool(getattr(index, "specialize", False))
        self._pipes = {}              # mode -> traceable pipeline
        self._aggs = {}               # mode -> jitted aggregate dispatch
        self._mats = {}               # K -> jitted materialize dispatch
        self._gfns = {}               # grouped/composite jitted dispatches
        self._gmk = None              # lazily-built groupby makers
        self._eprefixes = {}          # with_sum -> edge-prefix pipeline

    def _pipe(self, mode: str) -> Callable:
        pipe = self._pipes.get(mode)
        if pipe is None:
            idx = self.index
            pipe = self._pipes[mode] = make_span_pipeline(
                self._span_of, num_pages=idx.num_pages, tile=idx.tile,
                key_dtype=self.key_dtype,
                val_dtype=self._val_dtype, mode=mode)
        return pipe

    def _rank_raw(self, mode, lo, hi, kpages, vpages, aux):
        s = self._pipe(mode)(lo, hi, kpages, vpages, aux)
        r_lo = jnp.minimum(s.plo * self._lw + s.lt_lo, self._n)
        return s, r_lo, r_lo + s.count

    def agg_fn(self, mode: str) -> Callable:
        """The jitted aggregate dispatch for a static pushdown mode:
        (lo, hi, kpages, vpages, aux) -> (count, vsum, vmin, vmax, r_lo,
        r_hi_excl) with None members above the mode's depth. On a
        specialized index the signature is just ``(lo, hi)`` — pages and
        aux are baked into the executable."""
        fn = self._aggs.get(mode)
        if fn is None:
            if self._spec:
                kp, aux = self.index.pages, self.aux
                vp = self.vpages if mode != "count" else None

                def agg(lo, hi):
                    s, r_lo, r_hi = self._rank_raw(mode, lo, hi, kp, vp,
                                                   aux)
                    return s.count, s.vsum, s.vmin, s.vmax, r_lo, r_hi
            else:
                def agg(lo, hi, kpages, vpages, aux):
                    s, r_lo, r_hi = self._rank_raw(mode, lo, hi, kpages,
                                                   vpages, aux)
                    return s.count, s.vsum, s.vmin, s.vmax, r_lo, r_hi
            fn = self._aggs[mode] = jax.jit(agg)
        return fn

    def range_raw(self, lo, hi, pages):
        """Traceable (lo, hi, pages) -> (r_lo, r_hi_excl, count) for fusing
        into larger jits — count-mode, no value operands; the aux arrays
        ride along as captured constants (small: O(P) — the leaf storage
        itself stays an argument)."""
        s, r_lo, r_hi = self._rank_raw("count", lo, hi, pages, None,
                                       self.aux)
        return r_lo, r_hi, s.count

    def _coerce(self, lo, hi):
        lo = jnp.asarray(lo, self.key_dtype)
        hi = jnp.asarray(hi, self.key_dtype)
        return lo, hi

    def _mode_for(self, aggs) -> str:
        return mode_for_aggs(aggs, self.has_values)

    def scan_range(self, lo, hi, *, aggs=None,
                   materialize: Optional[int] = None) -> ScanResult:
        lo, hi = self._coerce(lo, hi)
        kp = self.index.pages
        mode = self._mode_for(aggs)
        vp = self.vpages if mode != "count" else None
        if materialize is None:
            with _span("scan.dispatch", mode=mode):
                t0 = time.perf_counter()
                if self._spec:
                    cnt, vs, mn, mx, r_lo, r_hi = self.agg_fn(mode)(lo, hi)
                else:
                    cnt, vs, mn, mx, r_lo, r_hi = self.agg_fn(mode)(
                        lo, hi, kp, vp, self.aux)
                reg = get_registry()
                reg.histogram("dispatch_staging_seconds",
                              path="scan").observe(
                    time.perf_counter() - t0)
            return ScanResult(count=cnt, r_lo=r_lo, r_hi_excl=r_hi,
                              vsum=vs, vmin=mn, vmax=mx)
        # materialize composes with the requested aggregates in the SAME
        # dispatch (aggs=None on a valued index means full depth; pass
        # aggs=("count",) for the lean locator-only compaction). Value
        # pages ride along for the output gather even in count mode — the
        # kernel still never streams them.
        K = int(materialize)
        key = (K, mode)
        vp_mat = self.vpages if self.has_values else None
        lw, lwp = self._lw, self.index.lw_pad
        fn = self._mats.get(key)
        if fn is None:
            def _mat_body(lo, hi, kpages, vpages, aux, flat_vals):
                s, r_lo, r_hi = self._rank_raw(
                    mode, lo, hi, kpages,
                    vpages if mode != "count" else None, aux)
                ranks, vals, over = _materialize_interval(
                    r_lo, s.count, flat_vals, K=K)
                if vpages is not None:
                    # dense rank -> padded slot address into the value
                    # pages (the only device copy of the values)
                    rr = jnp.clip(ranks, 0, None)
                    addr = (rr // lw) * lwp + rr % lw
                    g = jnp.take(vpages.reshape(-1), addr, mode="clip")
                    vals = jnp.where(ranks >= 0, g, 0)
                return (s.count, s.vsum, s.vmin, s.vmax, r_lo, r_hi,
                        ranks, vals, over)
            if self._spec:
                ckp, cvp, caux, cfv = kp, vp_mat, self.aux, self.values_dev

                def mat(lo, hi):
                    return _mat_body(lo, hi, ckp, cvp, caux, cfv)
            else:
                mat = _mat_body
            fn = self._mats[key] = jax.jit(mat)
        with _span("scan.dispatch", mode=mode, materialize=K):
            t0 = time.perf_counter()
            if self._spec:
                cnt, vs, mn, mx, r_lo, r_hi, ranks, vals, over = fn(lo, hi)
            else:
                cnt, vs, mn, mx, r_lo, r_hi, ranks, vals, over = fn(
                    lo, hi, kp, vp_mat, self.aux, self.values_dev)
            reg = get_registry()
            reg.histogram("dispatch_staging_seconds",
                          path="scan").observe(
                time.perf_counter() - t0)
        return ScanResult(count=cnt, r_lo=r_lo, r_hi_excl=r_hi,
                          vsum=vs, vmin=mn, vmax=mx,
                          ranks=ranks, values=vals, overflow=over)

    def search_range(self, lo, hi):
        """(r_lo, r_hi_excl, count) — the api facade's range contract as
        one fused count-mode dispatch (exact rightmost bound,
        empty-normalized; the value pages are never streamed)."""
        r = self.scan_range(lo, hi, aggs=("count",))
        return r.r_lo, r.r_hi_excl, r.count

    # ------------------------------------ grouped / composite (DESIGN §8.3)
    def _group_makers(self):
        """The grouped/composite maker family over this scanner's fused
        aggregate pipeline. The immutable operand convention is
        ``rest = (kpages, vpages, aux, flat_vals)`` — the trailing flat
        values (non-pushdown dtypes' materialize source) are not a tier
        quintuple, so the prefix path's tier loop skips them."""
        gm = self._gmk
        if gm is None:
            from . import groupby as _gb
            idx = self.index
            lw, lwp = self._lw, idx.lw_pad

            def agg_factory(mode):
                def agg(lo, hi, kpages, vpages, aux, flat_vals):
                    s, r_lo, r_hi = self._rank_raw(
                        mode, lo, hi, kpages,
                        vpages if mode != "count" else None, aux)
                    return s.count, s.vsum, s.vmin, s.vmax, r_lo, r_hi
                return agg

            def mat_factory(C, mode):
                def mat(lo, hi, kpages, vpages, aux, flat_vals):
                    s, r_lo, r_hi = self._rank_raw(
                        mode, lo, hi, kpages,
                        vpages if mode != "count" else None, aux)
                    ranks, vals, over = _materialize_interval(
                        r_lo, s.count, flat_vals, K=C)
                    if vals is None:
                        rr = jnp.clip(ranks, 0, None)
                        addr = (rr // lw) * lwp + rr % lw
                        g = jnp.take(vpages.reshape(-1), addr, mode="clip")
                        vals = jnp.where(ranks >= 0, g, 0)
                    return (s.count, s.vsum, s.vmin, s.vmax, r_lo, r_hi,
                            ranks, vals, over)
                return mat

            def prefix_path(with_sum):
                p = self._eprefixes.get(with_sum)
                if p is None:
                    p = self._eprefixes[with_sum] = _gb.make_edge_prefix(
                        idx.page_of_raw, num_pages=idx.num_pages,
                        tile=idx.tile, with_sum=with_sum)
                return p

            gm = self._gmk = _gb.make_group_makers(
                agg_factory, mat_factory, self.key_dtype,
                prefix_path=prefix_path)
        return gm

    def _group_dispatch(self, key, build, lo_args, path, **labels):
        """Shared jit-cache + obs boundary for the grouped/composite
        dispatches: build (and wrap for specialization) on miss, run as
        ONE fused dispatch, record the op at the boundary."""
        fn = self._gfns.get(key)
        if fn is None:
            body = build()
            if self._spec:
                ckp, cvp = self.index.pages, self.vpages
                caux, cfv = self.aux, self.values_dev

                def wrapped(*qs):
                    return body(*qs, ckp, cvp, caux, cfv)
            else:
                wrapped = body
            fn = self._gfns[key] = jax.jit(wrapped)
        with _span("scan.dispatch", **labels):
            t0 = time.perf_counter()
            if self._spec:
                out = fn(*lo_args)
            else:
                out = fn(*lo_args, self.index.pages, self.vpages,
                         self.aux, self.values_dev)
            reg = get_registry()
            reg.histogram("dispatch_staging_seconds",
                          path=path).observe(
                time.perf_counter() - t0)
        return out

    def scan_groups(self, lo, hi, num_groups: int, *, aggs=None,
                    top_k: Optional[int] = None,
                    candidates: Optional[int] = None):
        """Equal-width GROUP BY bucket(key) aggregates over [lo, hi]:
        G buckets per query, count/sum via the (G+1)-edge prefix pipeline,
        min/max via the per-bucket span expansion, optional per-bucket
        top-K by value (``top_k``; ``candidates`` bounds the materialized
        window, default max(2K, 32)) — ONE fused dispatch either way.
        Returns :class:`groupby.GroupScanResult`."""
        from . import groupby as _gb
        lo, hi = self._coerce(lo, hi)
        G = int(num_groups)
        if not 1 <= G <= _gb.MAX_GROUPS:
            raise ValueError(f"num_groups must be in [1, {_gb.MAX_GROUPS}]"
                             f", got {num_groups}")
        mode = self._mode_for(aggs)
        K = C = None
        if top_k is not None:
            K = int(top_k)
            if K < 1:
                raise ValueError(f"top_k must be positive, got {top_k}")
            if not self.has_values and self.values_dev is None:
                raise ValueError("top_k needs an index built with values")
            C = max(int(candidates) if candidates is not None
                    else max(2 * K, 32), K)

        def build():
            mk_gagg, mk_gtopk, _ = self._group_makers()
            return (mk_gagg(G, mode) if K is None
                    else mk_gtopk(G, mode, K, C))

        out = self._group_dispatch(("g", G, mode, K, C), build, (lo, hi),
                                   "scan_groups", mode=mode, groups=G)
        edges, r_edge, count, vsum, vmin, vmax = out[:6]
        res = _gb.GroupScanResult(count=count, edges=edges, r_edge=r_edge,
                                  vsum=vsum, vmin=vmin, vmax=vmax)
        if K is not None:
            topv, topr, over = out[6:9]
            res = _gb.GroupScanResult(
                count=count, edges=edges, r_edge=r_edge, vsum=vsum,
                vmin=vmin, vmax=vmax, topk_values=topv, topk_ranks=topr,
                overflow=over)
        return res

    def scan_multi(self, ranges, *, op: str = "union", aggs=None):
        """Composite R-range predicates: ``ranges`` is [Q, R, 2] inclusive
        (lo, hi) pairs per query, combined as a union (IN-list) or
        intersection (conjunctive predicate) via the coverage-count
        decomposition, aggregated in ONE fused dispatch. Returns a
        :class:`ScanResult` whose r_lo/r_hi_excl are the rank hull of the
        matching set ((0, 0) when empty)."""
        from . import groupby as _gb
        if op not in _gb.MULTI_OPS:
            raise ValueError(f"unknown multi-range op {op!r}; "
                             f"want one of {_gb.MULTI_OPS}")
        r = jnp.asarray(ranges, self.key_dtype)
        if r.ndim != 3 or r.shape[-1] != 2:
            raise ValueError(f"ranges must be [Q, R, 2], got {r.shape}")
        R = int(r.shape[1])
        if R < 1:
            raise ValueError("ranges needs at least one range per query")
        mode = self._mode_for(aggs)

        def build():
            _, _, mk_magg = self._group_makers()
            magg = mk_magg(R, op, mode)

            def body(rr, *rest):
                return magg(rr[..., 0], rr[..., 1], *rest)
            return body

        out = self._group_dispatch(("m", R, op, mode), build, (r,),
                                   "scan_multi", mode=mode, op=op)
        count, vsum, vmin, vmax, r_lo, r_hi = out
        return ScanResult(count=count, r_lo=r_lo, r_hi_excl=r_hi,
                          vsum=vsum, vmin=vmin, vmax=vmax)


def scanner_for(index, values=None) -> TieredScanner:
    """The (lazily built) scanner of a TieredIndex, cached on the index —
    one slot for the rank-only form, one for the valued form. A rank-only
    request is served by an existing valued scanner (its count mode never
    streams the value pages), so mixed search_range/scan_range callers
    compile one count pipeline, not two."""
    if values is None:
        sc = getattr(index, "_scanner_values", None)
        if sc is not None:
            return sc
    attr = "_scanner_ranks" if values is None else "_scanner_values"
    sc = getattr(index, attr, None)
    if sc is None:
        sc = TieredScanner(index, values)
        object.__setattr__(index, attr, sc)
    return sc


# ------------------------------------------------ materialize (dense rank)
def _materialize_interval(r_lo, count, flat_vals, *, K: int):
    ranks = r_lo[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
    valid = jnp.arange(K, dtype=jnp.int32)[None, :] < count[:, None]
    vals = None
    if flat_vals is not None:
        n = flat_vals.shape[0]
        g = jnp.take(flat_vals, jnp.clip(ranks, 0, n - 1), axis=0)
        vals = jnp.where(valid, g, 0)
    return jnp.where(valid, ranks, -1), vals, count > K


materialize_interval = jax.jit(_materialize_interval,
                               static_argnames=("K",))


# ----------------------------------------------- flat fallback aggregates
class FlatAggregator:
    """Rank-interval aggregates over a flat sorted value array: prefix sums
    for sum, power-of-two sparse tables for min/max — O(1) per query after
    an O(n log n)-memory build. The fallback behind
    ``core.api.Index.scan_range`` for the non-tiered kinds (their searchers
    have no page structure to push into), and the cross-check oracle the
    property tests use."""

    def __init__(self, values):
        v = np.asarray(values)
        self.ok = v.dtype in VALUE_DTYPES
        if not self.ok:
            return
        n = v.size
        vd = v.dtype
        id_min, id_max = agg_identities(vd)
        cum = np.zeros(n + 1, vd)
        cum[1:] = np.cumsum(v, dtype=vd)
        cum_d = jnp.asarray(cum)
        st_min = jnp.asarray(sparse_table(v, np.minimum, id_min))
        st_max = jnp.asarray(sparse_table(v, np.maximum, id_max))

        def agg(r_lo, r_hi, cum_d, st_min, st_max):
            vsum = cum_d[r_hi] - cum_d[r_lo]
            vmin = _table_range(st_min, r_lo, r_hi, jnp.minimum, id_min)
            vmax = _table_range(st_max, r_lo, r_hi, jnp.maximum, id_max)
            return vsum, vmin, vmax

        self._arrays = (cum_d, st_min, st_max)
        self._fn = jax.jit(agg)

    def __call__(self, r_lo, r_hi):
        return self._fn(jnp.asarray(r_lo, jnp.int32),
                        jnp.asarray(r_hi, jnp.int32), *self._arrays)


# -------------------------------------------------- mutable (paged) store
def _tier_terms(lo, hi, fk, fv, fsb, fss, ftomb):
    """Branch-free in-range scan of one flattened delta tier (sealed or
    active): the three-tier correction algebra of DESIGN.md §6.3.

    Per query, over the tier's occupied slots:

      cnt / vsum / vmin / vmax  — the tier's own LIVE (non-tomb)
                                  contribution in [lo, hi];
      sub      — count correction: one for every in-range sb entry (its
                 base twin is physically counted whether the key is live
                 or deleted — deleted base twins hold the tombstone
                 sentinel, masked from value aggregates but not from the
                 physical cum_cnt), plus one for every in-range LIVE ss
                 entry (its sealed twin is synced live and double-counts;
                 a tombstoned ss entry's twin is synced tomb and
                 contributes nothing, so no correction);
      sub_sum  — value correction: a live sb/ss entry's lower twin is
                 value-synced to this entry's value, so subtracting fv
                 removes the duplicate exactly (tomb entries subtract
                 nothing — their lower twins are value-masked);
      below / below_sub — the same pair over keys < lo (rank anchors).

    Gap slots hold the sentinel and can satisfy neither bound."""
    id_min, id_max = agg_identities(np.int32)
    inr = (fk[None, :] >= lo[:, None]) & (fk[None, :] <= hi[:, None])
    blw = fk[None, :] < lo[:, None]
    live = ~ftomb[None, :]
    corr = fsb[None, :] | (fss[None, :] & live)      # sb and ss never co-set
    vcorr = (fsb[None, :] | fss[None, :]) & live
    return dict(
        cnt=jnp.sum(inr & live, -1).astype(jnp.int32),
        sub=jnp.sum(inr & corr, -1).astype(jnp.int32),
        vsum=jnp.sum(jnp.where(inr & live, fv, 0), -1),
        sub_sum=jnp.sum(jnp.where(inr & vcorr, fv, 0), -1),
        vmin=jnp.min(jnp.where(inr & live, fv, id_min), -1),
        vmax=jnp.max(jnp.where(inr & live, fv, id_max), -1),
        below=jnp.sum(blw & live, -1).astype(jnp.int32),
        below_sub=jnp.sum(blw & corr, -1).astype(jnp.int32),
    )


def _sorted_tier_window(fk, fv, ftomb, lo, hi, offset: int):
    """The in-range run of one key-sorted delta tier, per query, over the
    tier's full ``capacity`` columns (tombstoned and superseded entries
    interleave with live ones, so no shorter window is safe): (mask —
    in-range AND live, keys, slot addresses [offset + original flat slot],
    values, all sorted keys — for the callers' supersession membership
    tests). Tier keys are unique and the gaps sort last (sentinel), so
    the matches of any [lo, hi] are one contiguous run of the sorted
    view."""
    cap = fk.shape[0]
    order = jnp.argsort(fk).astype(jnp.int32)        # sentinels last
    sk = jnp.take(fk, order)
    sv = jnp.take(fv, order)
    stb = jnp.take(ftomb, order)
    dstart = jnp.sum(sk[None, :] < lo[:, None], -1).astype(jnp.int32)
    didx = dstart[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    didxc = jnp.clip(didx, 0, cap - 1)
    dkey = jnp.take(sk, didxc)
    dok = (didx < cap) & (dkey >= lo[:, None]) & (dkey <= hi[:, None]) \
        & ~jnp.take(stb, didxc)
    daddr = offset + jnp.take(order, didxc)
    dval = jnp.take(sv, didxc)
    return dok, dkey, daddr, dval, sk


def _member(sorted_keys, query_keys):
    """[Q, W] bool: each query key occupies a slot of the sorted tier
    view (sentinels sort last and never match user keys)."""
    cap = sorted_keys.shape[0]
    pos = jnp.clip(jnp.searchsorted(sorted_keys,
                                    query_keys).astype(jnp.int32),
                   0, cap - 1)
    return jnp.take(sorted_keys, pos) == query_keys


def make_paged_scan_fns(span_of: Callable, *, num_pages: int, lw_pad: int,
                        tile: int, key_dtype, mask_value=None):
    """Traceable fused scan over a gapped paged base + BOTH delta tiers
    (sealed + active) with the three-tier shadow/tombstone correction
    (DESIGN.md §6.3/§8.2). Returns ``(make_agg, make_mat)``:

    * ``make_agg(mode)`` — ``agg(lo, hi, kpages, vpages, aux, sk, sv,
      s_sb, s_ss, s_tb, ak, av, a_sb, a_ss, a_tb) -> (count, vsum, vmin,
      vmax, r_lo, r_hi_excl)`` at the static pushdown depth ``mode``
      (fields beyond it are None; count mode never streams the value
      pages): exact merged aggregates and delta-aware searchsorted ranks
      — base terms from the span pipeline (physical counts, tombstone
      values masked by the kernel's static ``mask_value``), each tier's
      live terms added and its sb/ss corrections subtracted
      (:func:`_tier_terms`); min/max need no correction at all — the
      write path value-syncs every lower twin, making the three tiers a
      duplicate multiset over live keys.
    * ``make_mat(K, mode)`` — materialize at pushdown depth ``mode`` (the
      aggregates ride the same dispatch): the first K merged live
      matches' slot addresses (base region, then sealed at ``P*lw_pad +
      slot``, then active at ``P*lw_pad + capacity + slot``) and values
      in key order, merged on device from a base candidate window of
      K + 2·capacity physical ordinals (at most 2·capacity of them
      superseded by a tier twin) and each tier's in-range run — base
      candidates with a twin in EITHER tier are dropped (the twin is the
      newer copy or a tombstone), sealed candidates with an active twin
      likewise, tombstones everywhere.
    """
    sent = sentinel_for(key_dtype)
    base_sz = num_pages * lw_pad
    pipes = {}

    def pipe(mode):
        p = pipes.get(mode)
        if p is None:
            p = pipes[mode] = make_span_pipeline(
                span_of, num_pages=num_pages, tile=tile, key_dtype=key_dtype,
                val_dtype=np.int32, mode=mode, mask_value=mask_value)
        return p

    def core(mode, lo, hi, kpages, vpages, aux, tiers):
        s = pipe(mode)(lo, hi, kpages, vpages, aux)
        count = s.count
        vsum = s.vsum if mode != "count" else None
        vmin = s.vmin if mode == "full" else None
        vmax = s.vmax if mode == "full" else None
        below = aux.cum_cnt[s.plo] + s.lt_lo
        for (dk, dv, dsb, dss, dtb) in tiers:
            d = _tier_terms(lo, hi, dk.reshape(-1), dv.reshape(-1),
                            dsb.reshape(-1), dss.reshape(-1),
                            dtb.reshape(-1))
            count = count + d["cnt"] - d["sub"]
            below = below + d["below"] - d["below_sub"]
            if mode != "count":
                vsum = vsum + d["vsum"] - d["sub_sum"]
            if mode == "full":
                vmin = jnp.minimum(vmin, d["vmin"])
                vmax = jnp.maximum(vmax, d["vmax"])
        return s, count, vsum, vmin, vmax, below

    def make_agg(mode: str):
        def agg(lo, hi, kpages, vpages, aux,
                sk, sv, s_sb, s_ss, s_tb, ak, av, a_sb, a_ss, a_tb):
            _, count, vsum, vmin, vmax, below = core(
                mode, lo, hi, kpages, vpages, aux,
                ((sk, sv, s_sb, s_ss, s_tb), (ak, av, a_sb, a_ss, a_tb)))
            return count, vsum, vmin, vmax, below, below + count
        return agg

    def make_mat(K: int, mode: str = "count"):
        def mat(lo, hi, kpages, vpages, aux,
                sk, sv, s_sb, s_ss, s_tb, ak, av, a_sb, a_ss, a_tb):
            s, count, vsum, vmin, vmax, below = core(
                mode, lo, hi, kpages, vpages, aux,
                ((sk, sv, s_sb, s_ss, s_tb), (ak, av, a_sb, a_ss, a_tb)))
            sfk, sfv = sk.reshape(-1), sv.reshape(-1)
            afk, afv = ak.reshape(-1), av.reshape(-1)
            cap = sfk.shape[0]
            # base candidates: physical ordinals from the first in-range
            # slot; K + 2*cap suffice (each exclusion needs a tier twin)
            o_lo = aux.cum_cnt[s.plo] + s.lt_lo
            W = K + 2 * cap
            j = jnp.arange(W, dtype=jnp.int32)[None, :]
            ords = o_lo[:, None] + j
            pg = jnp.clip(
                jnp.searchsorted(aux.cum_cnt, ords,
                                 side="right").astype(jnp.int32) - 1,
                0, num_pages - 1)
            addr = jnp.clip(pg * lw_pad + (ords - aux.cum_cnt[pg]),
                            0, base_sz - 1)
            bkey = jnp.take(kpages.reshape(-1), addr, mode="clip")
            bval = jnp.take(vpages.reshape(-1), addr, mode="clip")
            # keys are globally sorted across pages, so the in-range test
            # bounds the physical window (overshoot reads larger keys or
            # sentinels); tombstone-synced slots pass it but are dropped
            # by their guaranteed tier twin below
            bok = (bkey >= lo[:, None]) & (bkey <= hi[:, None])
            # tier candidates: each tier's in-range live run
            sok, skey, saddr, sval, s_sorted = _sorted_tier_window(
                sfk, sfv, s_tb.reshape(-1), lo, hi, base_sz)
            aok, akey, aaddr, aval, a_sorted = _sorted_tier_window(
                afk, afv, a_tb.reshape(-1), lo, hi, base_sz + cap)
            # supersession: any tier twin outranks a base copy; an active
            # twin outranks a sealed copy (tomb twins delete them)
            bok = bok & ~_member(s_sorted, bkey) & ~_member(a_sorted, bkey)
            sok = sok & ~_member(a_sorted, skey)
            bkey = jnp.where(bok, bkey, sent)
            skey = jnp.where(sok, skey, sent)
            akey = jnp.where(aok, akey, sent)
            keys_all = jnp.concatenate([bkey, skey, akey], axis=1)
            addr_all = jnp.concatenate([addr, saddr, aaddr], axis=1)
            val_all = jnp.concatenate([bval, sval, aval], axis=1)
            ordx = jnp.argsort(keys_all, axis=1)[:, :K]
            rk = jnp.take_along_axis(addr_all, ordx, axis=1)
            vv = jnp.take_along_axis(val_all, ordx, axis=1)
            valid = jnp.arange(K, dtype=jnp.int32)[None, :] < count[:, None]
            return (count, vsum, vmin, vmax, below, below + count,
                    jnp.where(valid, rk, -1), jnp.where(valid, vv, 0),
                    count > K)
        return mat

    return make_agg, make_mat


def make_delta_scan_fns(key_dtype):
    """The base-less twin of :func:`make_paged_scan_fns` — a mutable store
    before its first fold. Two tiers (sealed + active), no base: sb bits
    are never set, ss corrections apply unchanged. Returns ``(make_agg,
    make_mat)`` with the same 10 tier operands (the delta scan is cheap
    jnp either way; narrower modes just return None fields, XLA prunes
    the rest). Materialize addresses: sealed at ``slot``, active at
    ``capacity + slot``."""
    sent = sentinel_for(key_dtype)

    def _terms(lo, hi, tiers):
        count = below = jnp.zeros(lo.shape[0], jnp.int32)
        vsum = jnp.zeros(lo.shape[0], jnp.int32)
        id_min, id_max = agg_identities(np.int32)
        vmin = jnp.full(lo.shape[0], id_min, jnp.int32)
        vmax = jnp.full(lo.shape[0], id_max, jnp.int32)
        for (dk, dv, dsb, dss, dtb) in tiers:
            d = _tier_terms(lo, hi, dk.reshape(-1), dv.reshape(-1),
                            dsb.reshape(-1), dss.reshape(-1),
                            dtb.reshape(-1))
            count = count + d["cnt"] - d["sub"]
            below = below + d["below"] - d["below_sub"]
            vsum = vsum + d["vsum"] - d["sub_sum"]
            vmin = jnp.minimum(vmin, d["vmin"])
            vmax = jnp.maximum(vmax, d["vmax"])
        return count, vsum, vmin, vmax, below

    def make_agg(mode: str):
        def agg(lo, hi, sk, sv, s_sb, s_ss, s_tb,
                ak, av, a_sb, a_ss, a_tb):
            count, vsum, vmin, vmax, below = _terms(
                lo, hi, ((sk, sv, s_sb, s_ss, s_tb),
                         (ak, av, a_sb, a_ss, a_tb)))
            if mode == "count":
                vsum = vmin = vmax = None
            elif mode == "sum":
                vmin = vmax = None
            return count, vsum, vmin, vmax, below, below + count
        return agg

    def make_mat(K: int, mode: str = "count"):
        def mat(lo, hi, sk, sv, s_sb, s_ss, s_tb,
                ak, av, a_sb, a_ss, a_tb):
            count, vsum, vmin, vmax, below = _terms(
                lo, hi, ((sk, sv, s_sb, s_ss, s_tb),
                         (ak, av, a_sb, a_ss, a_tb)))
            if mode == "count":
                vsum = vmin = vmax = None
            elif mode == "sum":
                vmin = vmax = None
            cap = sk.reshape(-1).shape[0]
            sok, skey, saddr, sval, _ = _sorted_tier_window(
                sk.reshape(-1), sv.reshape(-1), s_tb.reshape(-1),
                lo, hi, 0)
            aok, akey, aaddr, aval, a_sorted = _sorted_tier_window(
                ak.reshape(-1), av.reshape(-1), a_tb.reshape(-1),
                lo, hi, cap)
            sok = sok & ~_member(a_sorted, skey)
            skey = jnp.where(sok, skey, sent)
            akey = jnp.where(aok, akey, sent)
            keys_all = jnp.concatenate([skey, akey], axis=1)
            addr_all = jnp.concatenate([saddr, aaddr], axis=1)
            val_all = jnp.concatenate([sval, aval], axis=1)
            Kc = min(K, keys_all.shape[1])
            ordx = jnp.argsort(keys_all, axis=1)[:, :Kc]
            rk = jnp.take_along_axis(addr_all, ordx, axis=1)
            vv = jnp.take_along_axis(val_all, ordx, axis=1)
            if Kc < K:
                pad = ((0, 0), (0, K - Kc))
                rk = jnp.pad(rk, pad)
                vv = jnp.pad(vv, pad)
            valid = jnp.arange(K, dtype=jnp.int32)[None, :] < count[:, None]
            return (count, vsum, vmin, vmax, below, below + count,
                    jnp.where(valid, rk, -1), jnp.where(valid, vv, 0),
                    count > K)
        return mat

    return make_agg, make_mat
