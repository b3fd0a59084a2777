"""MutableIndex — the delta-merge write path over the tiered engine
(DESIGN.md §6).

The thesis' read-optimized structures batch updates and rebuild wholesale;
`PrefixPageStore` inherited that posture and paid an O(n) rebuild per insert
batch. This subsystem bounds update cost without giving up compiled-index
reads, the FB+-tree / BS-tree recipe (arXiv 2503.23397, 2505.01180):

* **writes** land in a small gapped delta buffer (`engine/delta.py`,
  CSB+-style incremental insert, power-of-two capacity);
* **reads** probe both sides in ONE fused dispatch: the tiered pipeline
  over gapped leaf pages plus the branch-free delta probe, delta hit wins
  by recency — `plan="device"`'s zero-host-sync contract is preserved;
* **merges** fold an overflowing buffer into the leaf pages *page-locally*:
  only touched pages are rewritten (host row surgery + one donated device
  row-scatter) and their `seps` entry updated; the compiled top tier keeps
  routing correctly against its build-time separators (an insert can never
  push a key above its page's separator — the page id IS the searchsorted
  rank among separators) and is re-derived only when a page overflows
  `leaf_width` and splits, i.e. when `num_pages` changes.

Leaf pages here are **gapped**: packed at ``MERGE_FILL`` so most merges
absorb locally. The page kernel is reused unchanged — gap slots hold the
sentinel, which never compares below a user key, so the kernel's in-page
popcount returns the *live-prefix slot* and the pipeline (stride =
``lw_pad``) yields a flat storage address instead of a dense rank.

Non-tiered bases (binary/css/kary/fast/nitrogen) are also accepted: they
keep wholesale rebuild *at merge time*, which still amortizes the O(n)
rebuild over ``delta_capacity`` inserts.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..core.util import ceil_to as _ceil_to, sentinel_for
from ..kernels.page_search import page_tiles
from ..obs import get_registry, span
from . import delta as _delta
from . import tiered
from .schedule import _next_pow2

# Target page fill after a pack or split: the remaining (1-fill)·leaf_width
# gap slots are what lets a merge stay page-local instead of splitting.
MERGE_FILL = 0.75

# Reserved VALUE sentinel marking a deleted key (DESIGN.md §6.4). Values
# are always int32 regardless of key dtype; user inserts of this value are
# rejected. A tombstone-synced base slot keeps its key (physical counts
# stay cheap) but holds this value, masked out of every value aggregate by
# the kernel's static mask and removed for real at the next fold/repack.
TOMBSTONE = int(np.iinfo(np.int32).min)

MAINTENANCE_MODES = ("deferred", "inline", "thread")


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_rows(keys_dev, vals_dev, idx, krows, vrows):
    """In-place (donated) rewrite of the touched leaf rows on device — the
    page-local merge's entire device-side cost: O(touched pages), not O(n).
    ``idx`` is pow2-padded with repeats (idempotent: same row, same data);
    the host ``[k, lw_pad]`` rows take the device page layout here."""
    tiles = (-1,) + keys_dev.shape[1:]
    return (keys_dev.at[idx].set(krows.reshape(tiles)),
            vals_dev.at[idx].set(vrows.reshape(tiles)))


def _dedup_last(keys: np.ndarray, values: np.ndarray):
    """Sort by key, keep the LAST duplicate (upsert semantics: later wins)."""
    order = np.argsort(keys, kind="stable")
    ks, vs = keys[order], values[order]
    if ks.size:
        keep = np.append(ks[1:] != ks[:-1], True)
        ks, vs = ks[keep], vs[keep]
    return ks, vs


class _PagedBase:
    """Gapped-leaf tiered base: host (numpy) truth + device mirrors + the
    traceable rank pipeline. All mutation goes through ``merge``."""

    def __init__(self, keys_sorted: np.ndarray, vals_sorted: np.ndarray, *,
                 leaf_width: Optional[int] = None, tile: int = 128,
                 top: str = "auto", vmem_budget: Optional[int] = None,
                 specialize: bool = False):
        from ..kernels import ops
        self.dtype = keys_sorted.dtype
        self.sentinel = sentinel_for(self.dtype)
        self.tile = int(tile)
        self.top_cfg = top
        self.vmem_budget = vmem_budget or ops.VMEM_BUDGET_BYTES
        self.specialize = bool(specialize)
        n = int(keys_sorted.size)
        auto_lw, _, _ = tiered.plan_tiers(n, tile=tile,
                                          vmem_budget=self.vmem_budget)
        self.leaf_width = int(leaf_width) if leaf_width else auto_lw
        self.lw_pad = _ceil_to(self.leaf_width, 128)
        per = max(1, int(self.leaf_width * MERGE_FILL))
        chunks = [keys_sorted[i: i + per] for i in range(0, n, per)] or \
                 [keys_sorted]
        self._alloc(len(chunks))
        for p, ck in enumerate(chunks):
            m = ck.size
            self.keys[p, :m] = ck
            self.vals[p, :m] = vals_sorted[p * per: p * per + m]
            self.cnt[p] = m
            self.seps[p] = ck[-1] if m else self.sentinel
        self.derives = 0
        self._derive()

    def _alloc(self, num_pages: int):
        self.keys = np.full((num_pages, self.lw_pad), self.sentinel,
                            self.dtype)
        self.vals = np.zeros((num_pages, self.lw_pad), np.int32)
        self.cnt = np.zeros(num_pages, np.int64)
        self.seps = np.full(num_pages, self.sentinel, self.dtype)

    @property
    def num_pages(self) -> int:
        return self.keys.shape[0]

    @property
    def n(self) -> int:
        return int(self.cnt.sum())

    def find_slot(self, key):
        """(page, pos) of a live key in the gapped leaves, or None — the
        host twin of the device probe, used by the insert path's
        shadowed-key tracking (DESIGN.md §8.2)."""
        p = min(int(np.searchsorted(self.seps, key, side="left")),
                self.num_pages - 1)
        cnt = int(self.cnt[p])
        pos = int(np.searchsorted(self.keys[p, :cnt], key, side="left"))
        if pos < cnt and self.keys[p, pos] == key:
            return p, pos
        return None

    def _derive(self):
        """(Re-)derive the top tier + pipeline from the current pages.
        Called at build and on split (num_pages change) — never on a
        page-local merge."""
        P = self.num_pages
        self.top_kind, self.top = tiered.build_top(
            self.seps, top=self.top_cfg, vmem_budget=self.vmem_budget)
        page_of_raw = tiered._make_page_of_raw(
            self.top_kind, self.top, P, lane=128, tile_rows=8)
        self.page_of_raw = page_of_raw   # the range scan fuses over it
        # stride = lw_pad: the pipeline returns flat slot addresses into the
        # gapped [P, lw_pad] storage (clip keeps the address gatherable).
        # with_stats: the fused lookup also yields the plan's step count —
        # the occupancy feedback the micro-batch queue steers on.
        self.pipeline_stats = tiered._make_pipeline(
            page_of_raw, num_pages=P, stride=self.lw_pad, tile=self.tile,
            clip=P * self.lw_pad - 1, with_stats=True)
        self.dev_keys = page_tiles(self.keys)
        self.dev_vals = page_tiles(self.vals)
        # specialized twin (DESIGN.md §10): the freshly-derived key pages
        # baked in as compile-time constants. Re-built ONLY here — at the
        # geometrically-rare derive boundary — so the specialized posture
        # never retraces on the insert hot path. Any scatter that replaces
        # dev_keys/dev_vals between derives (page-local merge, dirty-row
        # sync) invalidates the consumer (MutableIndex._spec_fused) because
        # the donated old buffers are exactly the ones this closure holds.
        self.pipeline_spec = None
        if getattr(self, "specialize", False):
            self.pipeline_spec = tiered._make_pipeline(
                page_of_raw, num_pages=P, stride=self.lw_pad,
                tile=self.tile, clip=P * self.lw_pad - 1, with_stats=True,
                const_pages=self.dev_keys)
        self.derives += 1

    # ---------------------------------------------------------------- merge
    def merge(self, dk: np.ndarray, dv: np.ndarray,
              dt: Optional[np.ndarray] = None) -> dict:
        """Fold sorted unique delta entries into the leaf pages. Page-local
        when every touched page stays within leaf_width; otherwise the
        overflowing pages split (num_pages changes, top re-derived).
        ``dt`` flags tombstone rows: a tombstone with a resident twin
        REMOVES the twin (the page may go empty — its stale separator
        keeps routing, reclaimed at the next repack); one without a twin
        is simply dropped."""
        if dt is None:
            dt = np.zeros(dk.shape, bool)
        P, lw = self.num_pages, self.leaf_width
        pids = np.minimum(np.searchsorted(self.seps, dk, side="left"), P - 1)
        merged = {}
        overflow = False
        for p in np.unique(pids):
            sel = pids == p
            ks, vs, ts = dk[sel], dv[sel], dt[sel]
            cnt = int(self.cnt[p])
            pk = self.keys[p, :cnt]
            pv = self.vals[p, :cnt].copy()
            pos = np.searchsorted(pk, ks, side="left")
            if cnt:
                isdup = (pos < cnt) & (pk[np.minimum(pos, cnt - 1)] == ks)
            else:
                isdup = np.zeros(ks.shape, bool)
            upd = isdup & ~ts
            pv[pos[upd]] = vs[upd]                   # live upsert
            keep = np.ones(cnt, bool)
            keep[pos[isdup & ts]] = False            # tombstone: remove row
            ins = ~isdup & ~ts                       # twin-less tomb: drop
            mk = np.concatenate([pk[keep], ks[ins]])
            mv = np.concatenate([pv[keep], vs[ins]])
            order = np.argsort(mk, kind="stable")
            merged[int(p)] = (mk[order], mv[order])
            overflow |= mk.size > lw
        if not overflow:
            self._write_rows(merged)
            return {"touched": len(merged), "split": False,
                    "rows_rewritten": len(merged)}
        return self._repack(merged)

    def _write_rows(self, merged: dict):
        idx = np.fromiter(sorted(merged), np.int32, len(merged))
        for p in idx:
            mk, mv = merged[int(p)]
            m = mk.size
            self.keys[p, :] = self.sentinel
            self.vals[p, :] = 0
            self.keys[p, :m] = mk
            self.vals[p, :m] = mv
            self.cnt[p] = m
            if m and mk[-1] > self.seps[p]:
                self.seps[p] = mk[-1]            # grow-only (last page)
            # separators NEVER shrink (tombstone removals can lower a
            # page's max): the compiled top routes on build-time seps, so
            # host routing must agree with it — a stale larger sep keeps
            # both consistent, the vacated span just misses correctly.
            # An empty page (everything tombstoned) likewise keeps its
            # sep; the slot is reclaimed at the next repack.
        # device: one donated row-scatter, pow2-padded so the executable
        # cache stays O(log P) per shape family
        pad = _next_pow2(idx.size)
        idx_p = np.concatenate([idx, np.full(pad - idx.size, idx[-1],
                                             np.int32)])
        self.dev_keys, self.dev_vals = _scatter_rows(
            self.dev_keys, self.dev_vals, jnp.asarray(idx_p),
            jnp.asarray(self.keys[idx_p]), jnp.asarray(self.vals[idx_p]))

    def _repack(self, merged: dict) -> dict:
        """A page overflowed leaf_width: repack ALL live entries at
        MERGE_FILL so every page regains gap headroom, and re-derive the
        top tier (num_pages changed). O(n) row moves but NO re-sort (pages
        concatenate in key order), and amortized over the
        ~(1-MERGE_FILL)·n inserts it takes to overflow again — without
        the global repack, full pages would split (and re-derive the
        compiled top) on every subsequent merge."""
        splits = sum(mk.size > self.leaf_width for mk, _ in merged.values())
        parts_k, parts_v = [], []
        for p in range(self.num_pages):
            if p in merged:
                mk, mv = merged[p]
            else:
                c = int(self.cnt[p])
                mk, mv = self.keys[p, :c], self.vals[p, :c]
            parts_k.append(mk)
            parts_v.append(mv)
        ks = np.concatenate(parts_k)
        vs = np.concatenate(parts_v)
        per = max(1, int(self.leaf_width * MERGE_FILL))
        num_pages = max(1, -(-ks.size // per))
        self._alloc(num_pages)
        for p in range(num_pages):
            ck = ks[p * per: (p + 1) * per]
            m = ck.size
            self.keys[p, :m] = ck
            self.vals[p, :m] = vs[p * per: p * per + m]
            self.cnt[p] = m
            self.seps[p] = ck[-1] if m else self.sentinel
        self._derive()
        return {"touched": len(merged), "split": True, "splits": splits,
                "rows_rewritten": num_pages, "num_pages": num_pages}

    # ------------------------------------------------------------ snapshot
    def state(self) -> dict:
        """Snapshot of the leaf storage — everything a warm restore needs
        to skip the O(n) sort/chunk build (the top tier is re-derived from
        ``seps``, never persisted)."""
        return {"keys": self.keys.copy(), "vals": self.vals.copy(),
                "cnt": self.cnt.copy(), "seps": self.seps.copy(),
                "meta": np.asarray([self.leaf_width, self.tile], np.int64)}

    @classmethod
    def from_state(cls, st: dict, *, top: str = "auto",
                   vmem_budget: Optional[int] = None,
                   specialize: bool = False) -> "_PagedBase":
        """Adopt snapshot arrays directly (no sort, no chunking) and
        re-derive the compiled top — the restore path's O(pages) build."""
        from ..kernels import ops
        self = cls.__new__(cls)
        keys = np.array(st["keys"])
        self.dtype = keys.dtype
        self.sentinel = sentinel_for(self.dtype)
        meta = np.asarray(st["meta"])
        self.leaf_width = int(meta[0])
        self.tile = int(meta[1])
        self.top_cfg = top
        self.vmem_budget = vmem_budget or ops.VMEM_BUDGET_BYTES
        self.specialize = bool(specialize)
        self.lw_pad = keys.shape[1]
        self.keys = keys
        self.vals = np.array(st["vals"], np.int32)
        self.cnt = np.array(st["cnt"], np.int64)
        self.seps = np.array(st["seps"], self.dtype)
        self.derives = 0
        self._derive()
        return self


class MutableIndex:
    """Mutable point-lookup store: delta buffer over a read-optimized base.

    Built through ``core.api.build_index(..., IndexConfig(mutable=True))``.
    ``lookup`` returns the facade's LookupResult; under a tiered base,
    ``rank`` is a flat *slot address* into the gapped leaf storage (pages
    carry gap slots, so dense searchsorted ranks do not exist here) — the
    found/values contract is unchanged. Keys are unique (inserting an
    existing key overwrites its value — recency wins).
    """

    def __init__(self, config, keys=None, values=None):
        self.config = config
        if config.kind == "tiered" and config.plan != "device":
            # the fused base+delta lookup exists only in device-plan form;
            # silently ignoring plan="host" would mask a misconfiguration
            raise ValueError(
                "the mutable store runs the device plan only; "
                "plan='host' (BucketPlan stats) requires mutable=False")
        keys = np.asarray([] if keys is None else keys)
        if keys.size and values is None:
            values = np.arange(keys.size, dtype=np.int32)
        self._key_dtype = keys.dtype if keys.size else np.dtype(np.int32)
        self.delta = _delta.DeltaBuffer(config.delta_capacity,
                                        dtype=self._key_dtype)
        # the frozen twin: a full active buffer swaps here and is folded
        # into the base off the hot path (maintain); same capacity so the
        # swap is O(1) and the fused lookup sees one compiled shape
        self.sealed = _delta.DeltaBuffer(self.delta.capacity,
                                         dtype=self._key_dtype)
        self._mode = getattr(config, "maintenance", "deferred")
        if self._mode not in MAINTENANCE_MODES:
            raise ValueError(f"unknown maintenance mode {self._mode!r}; "
                             f"want one of {MAINTENANCE_MODES}")
        self._interval = getattr(config, "maintenance_interval_s", 0.05)
        self._lock = threading.RLock()
        self._timer = None
        self._closed = False
        self.base: Any = None
        self.stats = {"inserts": 0, "upserts": 0, "deletes": 0, "merges": 0,
                      "splits": 0, "pages_touched": 0, "rows_rewritten": 0,
                      "top_derives": 0, "base_rebuilds": 0, "shadowed": 0,
                      "seals": 0, "maintains": 0, "journal_replayed": 0}
        self._last_plan = None        # (q_n, steps, tile, P) of last lookup
        self._rev = 0                 # mutation revision (scan-state cache)
        self._dirty_rows = set()      # pages with host-synced shadow values
        self._scan_jit = None         # jitted scan fns per base structure
        self._scan_aux = None         # (rev, ScanAux) device aggregates
        if keys.size:
            ks, vs = _dedup_last(keys, np.asarray(values, np.int32))
            if np.any(vs == TOMBSTONE):
                raise ValueError("value equals the tombstone sentinel "
                                 f"({TOMBSTONE}); out of value domain")
            self._build_base(ks, vs)
        self._fused = self._make_lookup()
        # durability (DESIGN.md §6.5): with a checkpoint dir configured,
        # every write is journaled ahead of application; save() snapshots
        # and rotates the journal segment
        self._ckpt_dir = getattr(config, "ckpt_dir", None)
        self._ckpt_keep = getattr(config, "ckpt_keep", 3)
        self._journal = None
        if self._ckpt_dir:
            self._open_journal(self._ckpt_dir)

    # ---------------------------------------------------------------- build
    def _build_base(self, ks: np.ndarray, vs: np.ndarray):
        c = self.config
        if c.kind == "tiered":
            self.base = _PagedBase(
                ks, vs, leaf_width=c.leaf_width, tile=c.tile, top=c.top,
                specialize=bool(getattr(c, "specialize", False)))
            self.stats["top_derives"] = self.base.derives
        else:
            from ..core.api import build_index
            self.base = build_index(
                ks, vs, dataclasses.replace(c, mutable=False))
            self._flat = (ks, vs)
            self.stats["base_rebuilds"] += 1

    def _make_lookup(self):
        """Fused three-tier lookup: (rank, found, values, plan_steps) in
        ONE dispatch over base + sealed + active delta. Recency resolves
        newest-first — an active hit decides found = hit & ~tomb before
        the sealed tier is consulted, sealed before the base — and a
        tombstone anywhere reads as not-found. ``plan_steps`` is the
        executed device plan's traced step count under a paged base (the
        queue's occupancy feedback signal) and None otherwise.

        Also (re-)arms ``self._spec_fused``: the specialized twin of the
        paged-base lookup with the leaf pages closed over as compile-time
        constants. Armed only here — and _make_lookup is called exactly at
        the derive boundaries (build, split/repack, base rebuild, restore)
        — so inserts between derives never retrace it; any scatter that
        replaces the captured device buffers sets it back to None and the
        store falls back to the data-as-jit-args posture."""
        probe_full = _delta.probe_full
        self._spec_fused = None

        def overlay(q, bfound, bval, tiers):
            # tiers newest-first: [(dk, dv, dtb, dsp), ...]
            found, val = bfound, bval
            for dk, dv, dtb, dsp in reversed(tiers):   # oldest applied last
                hit, tomb, tval = probe_full(q, dk, dv, dtb, dsp)
                found = jnp.where(hit, ~tomb, found)
                val = jnp.where(hit, tval, val)
            return found, val

        if self.base is None:
            def fused(q, ak, av, atb, asp, sk, sv, stb, ssp):
                found, val = overlay(
                    q, jnp.zeros(q.shape, bool), jnp.zeros(q.shape,
                                                           jnp.int32),
                    [(ak, av, atb, asp), (sk, sv, stb, ssp)])
                return jnp.zeros(q.shape, jnp.int32), found, val, None
            return jax.jit(fused)
        if isinstance(self.base, _PagedBase):
            pipeline = self.base.pipeline_stats
            def fused(q, pages, vpages, ak, av, atb, asp, sk, sv, stb, ssp):
                addr, steps = pipeline(q, pages)
                bval = jnp.take(vpages.reshape(-1), addr, axis=0,
                                mode="clip")
                # a tombstone-synced base slot is a deleted key: its tier
                # twin answers first anyway, the value guard is the
                # restore-path belt-and-braces
                bfound = (jnp.take(pages.reshape(-1), addr, axis=0,
                                   mode="clip") == q) & (bval != TOMBSTONE)
                found, val = overlay(q, bfound, bval,
                                     [(ak, av, atb, asp),
                                      (sk, sv, stb, ssp)])
                return addr, found, val, steps
            spec_pipe = getattr(self.base, "pipeline_spec", None)
            if spec_pipe is not None:
                pages_c = self.base.dev_keys
                vpages_c = self.base.dev_vals

                def fused_spec(q, ak, av, atb, asp, sk, sv, stb, ssp):
                    addr, steps = spec_pipe(q)
                    bval = jnp.take(vpages_c.reshape(-1), addr, axis=0,
                                    mode="clip")
                    bfound = (jnp.take(pages_c.reshape(-1), addr, axis=0,
                                       mode="clip") == q) & \
                        (bval != TOMBSTONE)
                    found, val = overlay(q, bfound, bval,
                                         [(ak, av, atb, asp),
                                          (sk, sv, stb, ssp)])
                    return addr, found, val, steps
                self._spec_fused = jax.jit(fused_spec)
            return jax.jit(fused)
        base = self.base                       # core Index: traceable facade
        def fused(q, ak, av, atb, asp, sk, sv, stb, ssp):
            res = base.lookup(q)
            found, val = overlay(q, res.found, res.values,
                                 [(ak, av, atb, asp), (sk, sv, stb, ssp)])
            return res.rank, found, val, None
        return jax.jit(fused)

    # ---------------------------------------------------------------- write
    def insert(self, keys, values):
        """Upsert a batch. O(w) per key on the hot path: a full active
        buffer SWAPS with the empty sealed twin (O(1)) instead of merging
        inline — the fold into the leaf pages runs off the hot path
        (:meth:`maintain`, explicit / inline / timer-thread per the
        ``maintenance`` config knob). Writes sync every lower twin of the
        key to the newest state (sealed value+tomb, base value), which is
        what keeps the scan algebra's corrections exact and min/max
        duplicate-insensitive (DESIGN.md §6.3)."""
        keys = np.atleast_1d(np.asarray(keys, self._key_dtype))
        values = np.atleast_1d(np.asarray(values, np.int32))
        if keys.shape != values.shape:
            raise ValueError("keys/values must align")
        if np.any(values == TOMBSTONE):
            raise ValueError("value equals the tombstone sentinel "
                             f"({TOMBSTONE}); out of value domain")
        self._write(keys, values, delete=False)

    def delete(self, keys):
        """Delete a batch by key — a tombstone sentinel through the same
        delta path as insert (idempotent; deleting an absent key is a
        no-op tombstone). Lookups read the key as not-found immediately;
        scans mask it; the fold physically removes the base row and the
        repack reclaims the slot."""
        keys = np.atleast_1d(np.asarray(keys, self._key_dtype))
        self._write(keys, np.full(keys.shape, TOMBSTONE, np.int32),
                    delete=True)

    def _write(self, keys, values, *, delete: bool):
        with self._lock:
            jr = self._journal
            if jr is not None:
                # write-ahead for the WHOLE batch, then apply: replay is an
                # idempotent upsert, so batch-level WAL ordering is
                # equivalent to per-key interleaving — and it puts the
                # journal cost in one measured place
                with span("journal.append", n=int(keys.size)):
                    t0 = time.perf_counter()
                    for k, v in zip(keys, values):
                        jr.append(k, 0 if delete else int(v), delete=delete)
                    jr.flush()
                    reg = get_registry()
                    reg.histogram("engine_op_seconds", path="journal") \
                        .observe(time.perf_counter() - t0)
            for k, v in zip(keys, values):
                if self.delta.full:
                    self._seal()
                # ---- lower-twin sync + bit derivation (DESIGN.md §6.3):
                # sb = no sealed twin AND a base twin exists (this entry
                # carries the base copy's correction); ss = a sealed twin
                # exists (the sealed entry keeps carrying any sb)
                sslot = self.sealed.find(k)
                ss = sslot is not None
                if ss:
                    self.sealed.sync(sslot, int(v), delete)
                sb = False
                base = self.base
                if isinstance(base, _PagedBase):
                    slot = base.find_slot(k)
                    if slot is not None:
                        sb = not ss
                        p, pos = slot
                        nv = TOMBSTONE if delete else v
                        if base.vals[p, pos] != nv:
                            base.vals[p, pos] = nv
                            self._dirty_rows.add(int(p))
                elif base is not None:           # wholesale: membership only
                    bk = self._flat[0]
                    pos = int(np.searchsorted(bk, k, side="left"))
                    sb = (not ss) and pos < bk.size and bk[pos] == k
                if self.delta.insert(k, v, shadows=sb, shadows_sealed=ss,
                                     tomb=delete):
                    self.stats["deletes" if delete else "inserts"] += 1
                    if sb:
                        self.stats["shadowed"] += 1
                else:
                    self.stats["upserts"] += 1
            self._rev += 1

    def _seal(self):
        """Swap the full active buffer with the (empty) sealed twin — the
        O(1) hot-path hand-off. Backpressure: if the previous sealed
        buffer has not been folded yet, fold it now (the only path where
        a writer still pays a merge — sustained pressure with maintenance
        disabled or lagging)."""
        with span("store.seal"):
            if self.sealed.count:
                self.maintain()
            self.delta, self.sealed = self.sealed, self.delta
            self.stats["seals"] += 1
            self._rev += 1
            if self._mode == "inline":
                self.maintain()
            elif self._mode == "thread":
                self._arm_timer()

    def maintain(self) -> bool:
        """Fold the sealed buffer into the base — the off-hot-path
        maintenance step. Returns True when a fold ran. After the fold
        the active buffer's ss bits are promoted (live ss -> sb: the twin
        is now a physical base copy) or cleared (tombstoned ss: the twin
        was removed with the fold)."""
        with self._lock:
            if self.sealed.count == 0:
                return False
            dk, dv, dt = self.sealed.drain()
            self.stats["maintains"] += 1
            self.stats["merges"] += 1
            self._rev += 1
            with span("store.fold", n=int(dk.size)):
                t0 = time.perf_counter()
                self._fold(dk, dv, dt)
                reg = get_registry()
                reg.histogram("engine_op_seconds", path="fold").observe(
                    time.perf_counter() - t0)
            self.delta.promote_ss()
            return True

    def _fold(self, dk, dv, dt):
        live = ~dt
        if self.base is None:
            if live.any():
                self._build_base(dk[live], dv[live])
                self._dirty_rows.clear()
                self._fused = self._make_lookup()
            return
        if isinstance(self.base, _PagedBase):
            info = self.base.merge(dk, dv, dt)
            self.stats["pages_touched"] += info["touched"]
            self.stats["rows_rewritten"] += info["rows_rewritten"]
            self.stats["top_derives"] = self.base.derives
            if info["split"]:
                # repack renumbered the pages; stale dirty-row ids die here
                self._dirty_rows.clear()
                self.stats["splits"] += info["splits"]
                self._fused = self._make_lookup()
            else:
                # page-local merge: pipeline unchanged, keep the compiled
                # fused lookup (rows flow in as arguments) — but the row
                # scatter donated the device buffers the specialized twin
                # captured as constants, so it is dead until the next
                # derive re-arms it
                self._spec_fused = None
            return
        # wholesale (non-tiered base): rebuild with upserts + removals
        bk, bv = self._flat
        pos = np.searchsorted(bk, dk, side="left")
        if bk.size:
            isdup = (pos < bk.size) & \
                (bk[np.minimum(pos, bk.size - 1)] == dk)
        else:
            isdup = np.zeros(dk.shape, bool)
        bv = bv.copy()
        upd = isdup & live
        bv[pos[upd]] = dv[upd]
        keep = np.ones(bk.size, bool)
        keep[pos[isdup & dt]] = False
        ins = ~isdup & live
        mk = np.concatenate([bk[keep], dk[ins]])
        mv = np.concatenate([bv[keep], dv[ins]])
        if mk.size:
            order = np.argsort(mk, kind="stable")
            self._build_base(mk[order], mv[order])
        else:
            self.base = None                     # everything deleted
        self._fused = self._make_lookup()

    def flush(self):
        """Force-fold everything (sealed, then active) into the base —
        tests/benchmarks and the pre-snapshot quiesce."""
        with self._lock:
            if self.delta.count:
                self._seal()                     # folds old sealed first
            self.maintain()

    # ------------------------------------------------------- worker thread
    def _arm_timer(self):
        """Arm the one-shot maintenance timer (``maintenance="thread"``),
        mirroring engine/queue.py's timer discipline: identity-checked
        under the lock, idempotent, dead after close()."""
        with self._lock:
            if self._closed or self._timer is not None:
                return
            t = threading.Timer(self._interval, self._tick)
            t.daemon = True
            self._timer = t
            t.start()

    def _tick(self):
        with self._lock:
            self._timer = None
            if self._closed:
                return
            self.maintain()

    def close(self):
        """Cancel the maintenance timer and close the journal (idempotent;
        the store stays readable)."""
        with self._lock:
            self._closed = True
            t, self._timer = self._timer, None
            jr, self._journal = self._journal, None
        if t is not None:
            t.cancel()
        if jr is not None:
            jr.close()

    # ---------------------------------------------------------------- read
    def lookup(self, queries):
        """Single-dispatch lookup over base + delta (delta wins). Returns
        core.api.LookupResult. Under a paged base, the executed plan's step
        count (a device scalar — no sync here) is retained for
        :meth:`pop_plan_feedback`."""
        from ..core.api import LookupResult
        q = jnp.asarray(queries)
        with self._lock, span("store.lookup", n=int(q.shape[0])):
            ak, av, asp = self.delta.device_state()
            _, _, atb = self.delta.device_bits()
            sk, sv, ssp = self.sealed.device_state()
            _, _, stb = self.sealed.device_bits()
            tiers = (ak, av, atb, asp, sk, sv, stb, ssp)
            # dispatch-boundary timer: the jitted call returns as soon as
            # the dispatch is staged (async), so observing it adds no sync
            t0 = time.perf_counter()
            if isinstance(self.base, _PagedBase):
                spec = getattr(self, "_spec_fused", None)
                if spec is not None:
                    rank, found, vals, steps = spec(q, *tiers)
                else:
                    rank, found, vals, steps = self._fused(
                        q, self.base.dev_keys, self.base.dev_vals, *tiers)
                self._last_plan = (int(q.shape[0]), steps, self.base.tile,
                                   self.base.num_pages)
            else:
                rank, found, vals, _ = self._fused(q, *tiers)
                self._last_plan = None
            reg = get_registry()
            reg.histogram("dispatch_staging_seconds",
                          path="lookup").observe(
                time.perf_counter() - t0)
        return LookupResult(rank=rank, found=found, values=vals)

    def pop_plan_feedback(self):
        """Executed-plan occupancy of the most recent lookup, as a lazy
        thunk (or None when the base is not paged / nothing ran). Resolving
        the thunk reads one device scalar — callers (the micro-batch queue)
        defer that outside the dispatch path, keeping lookups sync-free."""
        fb, self._last_plan = getattr(self, "_last_plan", None), None
        if fb is None:
            return None
        q_n, steps, tile, num_pages = fb
        from .schedule import executed_occupancy
        return lambda: executed_occupancy(q_n, int(steps), tile, num_pages)

    # ---------------------------------------------------------------- scan
    def _ensure_scan(self):
        """(jitted scan fns, device ScanAux) for the fused range scan,
        rebuilt lazily: the fns when the base structure changed (a derive),
        the aux arrays + dirty value rows when any mutation happened.
        Returns None for non-paged bases (host fallback)."""
        base = self.base
        if base is not None and not isinstance(base, _PagedBase):
            return None
        from . import scan as _scan
        key = -1 if base is None else base.derives
        if self._scan_jit is None or self._scan_jit["key"] != key:
            from . import groupby as _gb
            if base is None:
                make_agg, make_mat = _scan.make_delta_scan_fns(
                    self._key_dtype)
                gmk = _gb.make_group_makers(make_agg, make_mat,
                                            self._key_dtype)
            else:
                span_of = tiered._make_span_of(base.page_of_raw, base.dtype)
                make_agg, make_mat = _scan.make_paged_scan_fns(
                    span_of, num_pages=base.num_pages, lw_pad=base.lw_pad,
                    tile=base.tile, key_dtype=base.dtype,
                    mask_value=TOMBSTONE)
                prefixes = {}

                def prefix_path(with_sum, base=base, prefixes=prefixes):
                    p = prefixes.get(with_sum)
                    if p is None:
                        p = prefixes[with_sum] = _gb.make_edge_prefix(
                            base.page_of_raw, num_pages=base.num_pages,
                            tile=base.tile, with_sum=with_sum,
                            mask_value=TOMBSTONE)
                    return p

                gmk = _gb.make_group_makers(make_agg, make_mat, base.dtype,
                                            prefix_path=prefix_path)
            self._scan_jit = {"key": key, "make_agg": make_agg,
                              "aggs": {}, "make_mat": make_mat, "mats": {},
                              "gmk": gmk, "gfns": {}}
        if self._scan_aux is None or self._scan_aux[0] != self._rev:
            aux = None
            if base is not None:
                if self._dirty_rows:
                    # push host-synced shadowed values to the device rows
                    # (one pow2-padded donated scatter, like the merge path)
                    idx = np.fromiter(sorted(self._dirty_rows), np.int32,
                                      len(self._dirty_rows))
                    pad = _next_pow2(idx.size)
                    idx_p = np.concatenate(
                        [idx, np.full(pad - idx.size, idx[-1], np.int32)])
                    base.dev_keys, base.dev_vals = _scatter_rows(
                        base.dev_keys, base.dev_vals, jnp.asarray(idx_p),
                        jnp.asarray(base.keys[idx_p]),
                        jnp.asarray(base.vals[idx_p]))
                    # the donated scatter just deleted the buffers the
                    # specialized lookup closed over — args posture until
                    # the next derive
                    self._spec_fused = None
                    self._dirty_rows.clear()
                aux = _scan.build_page_aux(base.cnt, base.vals, np.int32,
                                           mask_value=TOMBSTONE)
            self._scan_aux = (self._rev, aux)
        return self._scan_jit, self._scan_aux[1]

    def _tier_scan_ops(self, buf):
        """One tier's five scan operands (keys, vals, sb, ss, tomb) as
        cached device mirrors."""
        k, v, _ = buf.device_state()
        sb, ss, tb = buf.device_bits()
        return k, v, sb, ss, tb

    def scan_range(self, lo, hi, *, aggs=None, materialize=None):
        """Batched delta-aware range scan (DESIGN.md §8.2): count / sum /
        min / max over live values in [lo, hi] plus exact merged
        searchsorted ranks, ONE fused dispatch under a paged base (span
        pipeline + branch-free delta scan + shadowed-key correction).
        ``aggs`` caps the pushdown depth like the immutable facade (count
        mode never streams the value pages). ``materialize=K``
        additionally compacts the first K matches' slot addresses (base
        region, then delta region at ``P*lw_pad + slot``) and values in
        key order, with an overflow flag. Returns
        ``engine.scan.ScanResult``. Non-tiered bases take a host path."""
        from . import scan as _scan
        mode = _scan.mode_for_aggs(aggs)
        lo = jnp.asarray(lo, self._key_dtype)
        hi = jnp.asarray(hi, self._key_dtype)
        with self._lock:
            st = self._ensure_scan()
            if st is None:
                return self._scan_host(np.asarray(lo), np.asarray(hi),
                                       mode, materialize)
            jits, aux = st
            tiers = (*self._tier_scan_ops(self.sealed),
                     *self._tier_scan_ops(self.delta))
            base = self.base
        if base is None:
            args = (lo, hi, *tiers)
        else:
            args = (lo, hi, base.dev_keys, base.dev_vals, aux, *tiers)
        if materialize is None:
            fn = jits["aggs"].get(mode)
            if fn is None:
                fn = jits["aggs"][mode] = jax.jit(jits["make_agg"](mode))
            with span("store.scan", mode=mode):
                t0 = time.perf_counter()
                count, vsum, vmin, vmax, r_lo, r_hi = fn(*args)
                reg = get_registry()
                reg.histogram("dispatch_staging_seconds",
                              path="scan").observe(
                    time.perf_counter() - t0)
            return _scan.ScanResult(count=count, r_lo=r_lo, r_hi_excl=r_hi,
                                    vsum=vsum, vmin=vmin, vmax=vmax)
        K = int(materialize)
        key = (K, mode)
        fn = jits["mats"].get(key)
        if fn is None:
            fn = jits["mats"][key] = jax.jit(jits["make_mat"](K, mode))
        with span("store.scan", mode=mode, materialize=K):
            t0 = time.perf_counter()
            count, vsum, vmin, vmax, r_lo, r_hi, ranks, vals, over = \
                fn(*args)
            reg = get_registry()
            reg.histogram("dispatch_staging_seconds",
                          path="scan").observe(
                time.perf_counter() - t0)
        return _scan.ScanResult(count=count, r_lo=r_lo, r_hi_excl=r_hi,
                                vsum=vsum, vmin=vmin, vmax=vmax,
                                ranks=ranks, values=vals, overflow=over)

    def search_range(self, lo, hi):
        """Exact merged range ranks over base + delta — the delta-aware
        searchsorted the ROADMAP asked for: for each ``lo[i] <= hi[i]``
        the half-open interval [r_lo, r_hi_excl) among the *live* merged
        keys (shadow dup-count subtracted), plus the match count; lo > hi
        normalizes to the empty interval at r_lo. Count-mode dispatch —
        the value pages are never streamed."""
        r = self.scan_range(lo, hi, aggs=("count",))
        return r.r_lo, r.r_hi_excl, r.count

    def _group_args(self):
        """Snapshot the fused-dispatch operands under the lock: (scan
        state or None, aux, tier operands, base). Shared by the grouped
        and composite dispatch paths."""
        with self._lock:
            st = self._ensure_scan()
            if st is None:
                return None, None, None, self.base
            jits, aux = st
            tiers = (*self._tier_scan_ops(self.sealed),
                     *self._tier_scan_ops(self.delta))
            return jits, aux, tiers, self.base

    def scan_groups(self, lo, hi, num_groups, *, aggs=None, top_k=None,
                    candidates=None):
        """Delta-aware GROUP BY bucket(key) over [lo, hi] (DESIGN.md
        §8.3): G equal-width buckets per query; count/sum ride the
        (G+1)-edge prefix pipeline with per-tier shadow corrections,
        min/max the per-bucket span expansion, optional per-bucket
        ``top_k`` by value over a ``candidates``-bounded merged window —
        ONE fused dispatch under a paged base. Returns
        ``engine.groupby.GroupScanResult`` (topk_ranks are flat slot
        addresses, like materialize). Non-tiered bases take a host
        path."""
        from . import scan as _scan
        from . import groupby as _gb
        mode = _scan.mode_for_aggs(aggs)
        lo = jnp.asarray(lo, self._key_dtype)
        hi = jnp.asarray(hi, self._key_dtype)
        G = int(num_groups)
        if not 1 <= G <= _gb.MAX_GROUPS:
            raise ValueError(f"num_groups must be in [1, {_gb.MAX_GROUPS}]"
                             f", got {num_groups}")
        K = C = None
        if top_k is not None:
            K = int(top_k)
            if K < 1:
                raise ValueError(f"top_k must be positive, got {top_k}")
            C = max(int(candidates) if candidates is not None
                    else max(2 * K, 32), K)
        jits, aux, tiers, base = self._group_args()
        if jits is None:
            return self._scan_groups_host(np.asarray(lo), np.asarray(hi),
                                          G, mode, K, C)
        if base is None:
            args = (lo, hi, *tiers)
        else:
            args = (lo, hi, base.dev_keys, base.dev_vals, aux, *tiers)
        key = ("g", G, mode, K, C)
        fn = jits["gfns"].get(key)
        if fn is None:
            mk_gagg, mk_gtopk, _ = jits["gmk"]
            fn = jits["gfns"][key] = jax.jit(
                mk_gagg(G, mode) if K is None else mk_gtopk(G, mode, K, C))
        with span("store.scan", mode=mode, groups=G):
            t0 = time.perf_counter()
            out = fn(*args)
            reg = get_registry()
            reg.histogram("dispatch_staging_seconds",
                          path="scan_groups").observe(
                time.perf_counter() - t0)
        edges, r_edge, count, vsum, vmin, vmax = out[:6]
        if K is None:
            return _gb.GroupScanResult(count=count, edges=edges,
                                       r_edge=r_edge, vsum=vsum,
                                       vmin=vmin, vmax=vmax)
        topv, topr, over = out[6:9]
        return _gb.GroupScanResult(count=count, edges=edges,
                                   r_edge=r_edge, vsum=vsum, vmin=vmin,
                                   vmax=vmax, topk_values=topv,
                                   topk_ranks=topr, overflow=over)

    def scan_multi(self, ranges, *, op="union", aggs=None):
        """Delta-aware composite R-range predicates ([Q, R, 2] inclusive
        pairs, union = IN-list / intersect = conjunction) via the
        coverage-count decomposition, aggregated in ONE fused dispatch
        under a paged base. Returns ``engine.scan.ScanResult`` whose
        r_lo/r_hi_excl are the merged-rank hull of the matching set
        ((0, 0) when empty). Non-tiered bases take a host path."""
        from . import scan as _scan
        from . import groupby as _gb
        if op not in _gb.MULTI_OPS:
            raise ValueError(f"unknown multi-range op {op!r}; "
                             f"want one of {_gb.MULTI_OPS}")
        r = jnp.asarray(ranges, self._key_dtype)
        if r.ndim != 3 or r.shape[-1] != 2:
            raise ValueError(f"ranges must be [Q, R, 2], got {r.shape}")
        R = int(r.shape[1])
        if R < 1:
            raise ValueError("ranges needs at least one range per query")
        mode = _scan.mode_for_aggs(aggs)
        jits, aux, tiers, base = self._group_args()
        if jits is None:
            return self._scan_multi_host(np.asarray(r), op, mode)
        if base is None:
            args = (r, *tiers)
        else:
            args = (r, base.dev_keys, base.dev_vals, aux, *tiers)
        key = ("m", R, op, mode)
        fn = jits["gfns"].get(key)
        if fn is None:
            _, _, mk_magg = jits["gmk"]
            magg = mk_magg(R, op, mode)

            def body(rr, *rest):
                return magg(rr[..., 0], rr[..., 1], *rest)
            fn = jits["gfns"][key] = jax.jit(body)
        with span("store.scan", mode=mode, op=op):
            t0 = time.perf_counter()
            count, vsum, vmin, vmax, r_lo, r_hi = fn(*args)
            reg = get_registry()
            reg.histogram("dispatch_staging_seconds",
                          path="scan_multi").observe(
                time.perf_counter() - t0)
        return _scan.ScanResult(count=count, r_lo=r_lo, r_hi_excl=r_hi,
                                vsum=vsum, vmin=vmin, vmax=vmax)

    def _merged_host(self):
        """Numpy snapshot of the LIVE sorted (keys, values) view: base +
        delta tiers overlaid newest-last (active wins over sealed wins
        over base; a tombstone anywhere above the base deletes the key).
        The compatibility substrate for every host-path scan family."""
        if self.base is not None:
            bk, bv = self._flat
        else:
            bk = np.empty(0, self._key_dtype)
            bv = np.empty(0, np.int32)
        ov = {}
        for buf in (self.sealed, self.delta):
            k, v, _, _, tb = buf.entries()
            for i in range(k.size):
                ov[k[i].item()] = (int(v[i]), bool(tb[i]))
        if ov:
            okeys = np.asarray(sorted(ov), self._key_dtype)
            keep = ~np.isin(bk, okeys)
            lk = [k for k in sorted(ov) if not ov[k][1]]
            mk = np.concatenate([bk[keep],
                                 np.asarray(lk, self._key_dtype)])
            mv = np.concatenate([bv[keep],
                                 np.asarray([ov[k][0] for k in lk],
                                            np.int32)])
            order = np.argsort(mk, kind="stable")
            mk, mv = mk[order], mv[order]
        else:
            mk, mv = bk, bv
        return mk, mv

    def _scan_host(self, lo, hi, mode, materialize):
        """Host-path scan for non-tiered mutable bases (the fused span
        machinery is the paged store's contract): merge the base + delta
        snapshots in numpy. O(n + Q·matches) — a compatibility path, not a
        fast path."""
        from . import scan as _scan
        from ..kernels.page_scan import agg_identities
        mk, mv = self._merged_host()
        r_lo = np.searchsorted(mk, lo, side="left").astype(np.int32)
        r_hi = np.searchsorted(mk, hi, side="right").astype(np.int32)
        r_hi = np.where(lo > hi, r_lo, r_hi).astype(np.int32)
        cnt = r_hi - r_lo
        id_min, id_max = agg_identities(np.int32)
        vsum = np.zeros(lo.shape[0], np.int32)
        vmin = np.full(lo.shape[0], id_min, np.int32)
        vmax = np.full(lo.shape[0], id_max, np.int32)
        for i in range(lo.shape[0]):
            if cnt[i]:
                seg = mv[r_lo[i]: r_hi[i]]
                vsum[i] = seg.sum(dtype=np.int32)
                vmin[i] = seg.min()
                vmax[i] = seg.max()
        res = dict(count=jnp.asarray(cnt), r_lo=jnp.asarray(r_lo),
                   r_hi_excl=jnp.asarray(r_hi))
        if materialize is None:
            return _scan.ScanResult(
                **res,
                vsum=jnp.asarray(vsum) if mode != "count" else None,
                vmin=jnp.asarray(vmin) if mode == "full" else None,
                vmax=jnp.asarray(vmax) if mode == "full" else None)
        K = int(materialize)
        ranks, vals, over = _scan.materialize_interval(
            jnp.asarray(r_lo), jnp.asarray(cnt), jnp.asarray(mv), K=K)
        return _scan.ScanResult(
            **res,
            vsum=jnp.asarray(vsum) if mode != "count" else None,
            vmin=jnp.asarray(vmin) if mode == "full" else None,
            vmax=jnp.asarray(vmax) if mode == "full" else None,
            ranks=ranks, values=vals, overflow=over)

    def _scan_groups_host(self, lo, hi, G, mode, K, C):
        """Host-path grouped scan for non-tiered bases: searchsorted over
        the host-computed bucket edges on the merged snapshot."""
        from . import groupby as _gb
        from ..kernels.page_scan import agg_identities
        mk, mv = self._merged_host()
        edges = _gb.group_edges_host(lo, hi, G)          # [Q, G+1]
        r_edge = np.searchsorted(mk, edges.reshape(-1),
                                 side="left").astype(np.int32)
        r_edge = r_edge.reshape(-1, G + 1)
        cnt = np.diff(r_edge, axis=1).astype(np.int32)
        Q = lo.shape[0]
        id_min, id_max = agg_identities(np.int32)
        vsum = np.zeros((Q, G), np.int32)
        vmin = np.full((Q, G), id_min, np.int32)
        vmax = np.full((Q, G), id_max, np.int32)
        if K is not None:
            topv = np.zeros((Q, G, K), np.int32)
            topr = np.full((Q, G, K), -1, np.int32)
            over = np.zeros((Q, G), bool)
        for q in range(Q):
            for g in range(G):
                if not cnt[q, g]:
                    continue
                s, e = int(r_edge[q, g]), int(r_edge[q, g + 1])
                seg = mv[s:e]
                vsum[q, g] = seg.sum(dtype=np.int32)
                vmin[q, g] = seg.min()
                vmax[q, g] = seg.max()
                if K is not None:
                    # device semantics: top-K over the first C candidate
                    # slots only, overflow flags truncation
                    cand = seg[:C]
                    k = min(K, cand.size)
                    o = np.argsort(-cand.astype(np.int64),
                                   kind="stable")[:k]
                    topv[q, g, :k] = cand[o]
                    topr[q, g, :k] = (s + o).astype(np.int32)
                    over[q, g] = cnt[q, g] > C
        res = _gb.GroupScanResult(
            count=jnp.asarray(cnt),
            edges=jnp.asarray(edges.astype(self._key_dtype)),
            r_edge=jnp.asarray(r_edge),
            vsum=jnp.asarray(vsum) if mode != "count" else None,
            vmin=jnp.asarray(vmin) if mode == "full" else None,
            vmax=jnp.asarray(vmax) if mode == "full" else None)
        if K is None:
            return res
        return dataclasses.replace(res, topk_values=jnp.asarray(topv),
                                   topk_ranks=jnp.asarray(topr),
                                   overflow=jnp.asarray(over))

    def _scan_multi_host(self, r, op, mode):
        """Host-path composite-range scan for non-tiered bases: per-query
        membership masks over the merged snapshot (union = any subrange,
        intersect = all)."""
        from . import scan as _scan
        from ..kernels.page_scan import agg_identities
        mk, mv = self._merged_host()
        Q = r.shape[0]
        id_min, id_max = agg_identities(np.int32)
        cnt = np.zeros(Q, np.int32)
        vsum = np.zeros(Q, np.int32)
        vmin = np.full(Q, id_min, np.int32)
        vmax = np.full(Q, id_max, np.int32)
        r_lo = np.zeros(Q, np.int32)
        r_hi = np.zeros(Q, np.int32)
        for q in range(Q):
            inr = (mk[None, :] >= r[q, :, 0][:, None]) & \
                  (mk[None, :] <= r[q, :, 1][:, None])    # [R, n]
            m = inr.any(axis=0) if op == "union" else inr.all(axis=0)
            idx = np.nonzero(m)[0]
            cnt[q] = idx.size
            if idx.size:
                seg = mv[m]
                vsum[q] = seg.sum(dtype=np.int32)
                vmin[q] = seg.min()
                vmax[q] = seg.max()
                r_lo[q] = idx[0]
                r_hi[q] = idx[-1] + 1
        return _scan.ScanResult(
            count=jnp.asarray(cnt), r_lo=jnp.asarray(r_lo),
            r_hi_excl=jnp.asarray(r_hi),
            vsum=jnp.asarray(vsum) if mode != "count" else None,
            vmin=jnp.asarray(vmin) if mode == "full" else None,
            vmax=jnp.asarray(vmax) if mode == "full" else None)

    @property
    def n(self) -> int:
        """Exact live key count — the full-range instance of the scan
        algebra: physical base count, plus each tier's live entries, minus
        its corrections (every sb entry has exactly one physical base
        copy — live duplicate or tombstone-synced slot — and every live
        ss entry a synced sealed duplicate)."""
        base_n = self.base.n if self.base is not None else 0
        if self.base is not None and not isinstance(self.base, _PagedBase):
            base_n = int(self._flat[0].size)
        for buf in (self.sealed, self.delta):
            _, _, sb, ss, tb = buf.entries()
            live = ~tb
            base_n += int(live.sum()) - int(sb.sum()) \
                - int((ss & live).sum())
        return base_n

    # ----------------------------------------------------------- durability
    def _open_journal(self, ckpt_dir: str):
        """Open (or continue) the journal segment for the current latest
        snapshot step, truncating any torn tail and resuming the sequence
        counter after the last valid record."""
        from ..ckpt import checkpoint as _ckpt
        from ..ckpt import journal as _jr
        os.makedirs(ckpt_dir, exist_ok=True)
        step = _ckpt.latest_step(ckpt_dir) or 0
        path = _jr.segment_path(ckpt_dir, step)
        seq = 0
        if os.path.exists(path):
            _jr.truncate_torn(path)
            _, recs = _jr.read_segment(path)
            if recs:
                seq = recs[-1][0] + 1
        self._journal = _jr.Journal(path, self._key_dtype, next_seq=seq,
                                    fsync=self._fsync_policy())

    def _fsync_policy(self) -> str:
        return getattr(self.config, "journal_fsync", None) or "rotate"

    def save(self, ckpt_dir: Optional[str] = None) -> str:
        """Snapshot the full index state (leaf pages, both delta tiers,
        counters) through the manifest-verified checkpoint writer, then
        rotate the journal to a fresh segment keyed by the new step. A
        crash between journal writes and the next save loses nothing: the
        previous snapshot + its segment replay reconstruct this exact
        state (DESIGN.md §6.5)."""
        from ..ckpt import checkpoint as _ckpt
        with self._lock, span("store.snapshot_save"):
            d = ckpt_dir or self._ckpt_dir
            if d is None:
                raise ValueError("no checkpoint directory: pass ckpt_dir "
                                 "or set IndexConfig.ckpt_dir")
            t0 = time.perf_counter()
            step = (_ckpt.latest_step(d) or 0) + 1
            tree = {"active": self.delta.state(),
                    "sealed": self.sealed.state()}
            if isinstance(self.base, _PagedBase):
                tree["base"] = self.base.state()
            elif self.base is not None:
                bk, bv = self._flat
                tree["flat"] = {"keys": bk.copy(), "vals": bv.copy()}
            path = _ckpt.save(d, step, tree, keep=self._ckpt_keep)
            self._rotate_journal(d, step)
            reg = get_registry()
            reg.histogram("engine_op_seconds",
                          path="snapshot_save").observe(
                              time.perf_counter() - t0)
            return path

    def _rotate_journal(self, ckpt_dir: str, step: int):
        from ..ckpt import checkpoint as _ckpt
        from ..ckpt import journal as _jr
        with span("journal.rotate", step=step):
            old, seq = self._journal, 0
            if old is not None:
                seq = old.seq
                old.close()
                # the rotated segment is immutable from here on: collapse
                # each key's overwrite chain to its last writer before the
                # segment settles into the replay set
                _jr.compact_segment(old.path)
            self._journal = _jr.Journal(_jr.segment_path(ckpt_dir, step),
                                        self._key_dtype, next_seq=seq,
                                        fsync=self._fsync_policy())
            get_registry().counter("journal_rotations").inc()
        self._ckpt_dir = self._ckpt_dir or ckpt_dir
        # GC segments no retained snapshot can replay from
        retained = _ckpt.all_steps(ckpt_dir)
        floor = min(retained) if retained else 0
        for s, p in _jr.scan_dir(ckpt_dir):
            if s < floor and s != step:
                try:
                    os.remove(p)
                except OSError:
                    pass

    @classmethod
    def restore(cls, ckpt_dir: str, config) -> "MutableIndex":
        """Bring a store back servable from the newest VERIFYING snapshot
        (a corrupt/torn latest degrades to the previous step) plus a
        journal replay of every write after it — O(pages) array adoption
        + one top derive + at most the un-snapshotted writes, never an
        O(n) rebuild. Journaling resumes on the restored store."""
        from ..ckpt import checkpoint as _ckpt
        from ..ckpt import journal as _jr
        cfg = dataclasses.replace(config, ckpt_dir=None) \
            if getattr(config, "ckpt_dir", None) else config
        with span("store.snapshot_restore"):
            return cls._restore(cfg, config, ckpt_dir)

    @classmethod
    def _restore(cls, cfg, config, ckpt_dir: str) -> "MutableIndex":
        from ..ckpt import checkpoint as _ckpt
        from ..ckpt import journal as _jr
        t_start = time.perf_counter()
        self = cls(cfg)
        try:
            raw, step = _ckpt.restore(ckpt_dir, None)
        except FileNotFoundError:
            raw, step = None, 0                  # journal-only recovery
        if raw is not None:
            def sub(prefix):
                return {k[len(prefix) + 1:]: v for k, v in raw.items()
                        if k.startswith(prefix + "/")}
            self.delta = _delta.DeltaBuffer.from_state(sub("active"))
            self.sealed = _delta.DeltaBuffer.from_state(sub("sealed"))
            self._key_dtype = self.delta.dtype
            if "base/keys" in raw:
                self.base = _PagedBase.from_state(
                    sub("base"), top=getattr(config, "top", "auto"),
                    specialize=bool(getattr(config, "specialize", False)))
                self.stats["top_derives"] = self.base.derives
            elif "flat/keys" in raw:
                self._build_base(np.asarray(raw["flat/keys"]),
                                 np.asarray(raw["flat/vals"], np.int32))
            self._fused = self._make_lookup()
            self._rev += 1
        applied, last_seq = self._replay(ckpt_dir, step)
        self.stats["journal_replayed"] = applied
        segs = [s for s, _ in _jr.scan_dir(ckpt_dir) if s >= step]
        seg = max(segs) if segs else step
        path = _jr.segment_path(ckpt_dir, seg)
        if os.path.exists(path):
            _jr.truncate_torn(path)
        self._ckpt_dir = ckpt_dir
        self._journal = _jr.Journal(path, self._key_dtype,
                                    next_seq=last_seq + 1,
                                    fsync=self._fsync_policy())
        reg = get_registry()
        reg.histogram("engine_op_seconds", path="snapshot_restore") \
            .observe(time.perf_counter() - t_start)
        return self

    def _replay(self, ckpt_dir: str, from_step: int):
        """Apply journaled writes from every segment at/after the restored
        step, in step order, stopping at the first torn/corrupt record or
        sequence regression (everything before it is intact by CRC)."""
        from ..ckpt import journal as _jr
        applied, last = 0, -1
        run_op, run_k, run_v = None, [], []

        def flush_run():
            if not run_k:
                return
            ks = np.asarray(run_k, self._key_dtype)
            if run_op == _jr.OP_DELETE:
                self.delete(ks)
            else:
                self.insert(ks, np.asarray(run_v, np.int32))

        for s, p in _jr.scan_dir(ckpt_dir):
            if s < from_step:
                continue
            _, recs = _jr.read_segment(p)
            for seq, op, k, v in recs:
                if seq <= last:
                    flush_run()                 # replay order broken: stop
                    return applied, last
                last = seq
                # batch consecutive same-op records into one write call —
                # _write applies keys sequentially, so this is equivalent
                # to per-record application, minus the per-call overhead
                if op != run_op:
                    flush_run()
                    run_op, run_k, run_v = op, [], []
                run_k.append(k)
                run_v.append(v)
                applied += 1
        flush_run()
        return applied, last

    @property
    def tree_bytes(self) -> int:
        if isinstance(self.base, _PagedBase) and self.base.top_kind == "kary":
            return int(self.base.top.tree.size *
                       self.base.top.tree.dtype.itemsize)
        return 0
