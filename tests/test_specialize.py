"""Specialization acceptance (DESIGN.md §10): ``IndexConfig(specialize=
True)`` bakes the built index into the jitted program. Contracts under
test:

* bit-identity oracle — the specialized posture answers every query
  identically to the data-as-jit-args posture, across kinds × dtypes ×
  plan constructions × mutable, through writes that cross both the
  page-local-merge (spec invalidated) and split/derive (spec re-armed)
  boundaries;
* retrace guard — mutable-store inserts BETWEEN derives trigger zero jit
  traces in both postures (the data-as-jit-args contract the delta-merge
  write path has relied on since it landed);
* single dispatch — the specialized path still answers device-resident
  queries under ``jax.transfer_guard("disallow")`` with one observed
  dispatch.
"""
import contextlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import IndexConfig, build_index
from repro.engine import schedule
from repro.obs import Registry, use_registry

KINDS = ("binary", "css", "kary", "fast", "nitrogen", "tiered")


def _data(dtype, n=4000, seed=0):
    rng = np.random.default_rng(seed)
    if np.dtype(dtype).kind == "f":
        keys = np.unique(rng.normal(size=n).astype(dtype))
        qs = np.concatenate([keys[::7], rng.normal(
            size=n // 4).astype(dtype)])
    else:
        keys = np.sort(rng.choice(1 << 20, n, replace=False)).astype(dtype)
        qs = np.concatenate([keys[::7], (keys[::11] + 1).astype(dtype)])
    vals = np.arange(keys.size, dtype=np.int32)
    return keys, vals, qs


def _assert_lookups_equal(a, b, q):
    ra, rb = a.lookup(q), b.lookup(q)
    np.testing.assert_array_equal(np.asarray(ra.rank), np.asarray(rb.rank))
    np.testing.assert_array_equal(np.asarray(ra.found),
                                  np.asarray(rb.found))
    np.testing.assert_array_equal(np.asarray(ra.values),
                                  np.asarray(rb.values))


@contextlib.contextmanager
def _count_traces():
    """Count jaxpr traces via jax's monitoring events — the ground truth
    for 'did this call retrace', independent of which jit cache the entry
    landed in."""
    from jax._src import monitoring
    events = []

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            events.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        yield events
    finally:
        monitoring.unregister_event_duration_listener(listener)


# ------------------------------------------------------- bit-identity oracle
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_specialized_matches_args_posture(kind, dtype):
    keys, vals, qs = _data(dtype)
    base = build_index(keys, vals, IndexConfig(kind=kind))
    spec = build_index(keys, vals, IndexConfig(kind=kind, specialize=True))
    np.testing.assert_array_equal(np.asarray(base.search(qs)),
                                  np.asarray(spec.search(qs)))
    _assert_lookups_equal(base, spec, qs)
    if kind == "tiered":
        assert spec.impl.search_spec is not None
        lo = keys[::131]
        hi = lo + (np.float32(0.5) if np.dtype(dtype).kind == "f"
                   else np.int32(5000))
        sa = base.scan_range(lo, hi, materialize=4)
        sb = spec.scan_range(lo, hi, materialize=4)
        for f in ("count", "r_lo", "r_hi_excl", "vsum", "vmin", "vmax",
                  "ranks", "values", "overflow"):
            np.testing.assert_array_equal(np.asarray(getattr(sa, f)),
                                          np.asarray(getattr(sb, f)))


@pytest.mark.parametrize("thresholds", [
    {"max_pages": 1},                      # force the sort plan
    {"max_pages": 1 << 16, "min_queries": 1, "min_depth": 1},  # histogram
])
def test_specialized_matches_across_plan_constructions(thresholds):
    """Both plan methods (sort schedule vs histogram buckets) produce the
    same answers specialized — the rung ladder collapse changes staging,
    never results."""
    keys, vals, qs = _data(np.int32)
    with schedule.plan_thresholds(**thresholds):
        base = build_index(keys, vals, IndexConfig(kind="tiered"))
        spec = build_index(keys, vals,
                           IndexConfig(kind="tiered", specialize=True))
        np.testing.assert_array_equal(np.asarray(base.search(qs)),
                                      np.asarray(spec.search(qs)))


def test_specialize_rejects_host_plan():
    with pytest.raises(ValueError, match="device plan"):
        IndexConfig(kind="tiered", plan="host", specialize=True)


def test_mutable_specialized_tracks_args_posture_through_writes():
    """The mutable oracle: identical answers through (a) delta-only
    writes, (b) a fold that merges page-locally (spec invalidated — args
    fallback), (c) a fold that splits/repacks (derive re-arms spec),
    (d) deletes and re-inserts."""
    keys, vals, qs = _data(np.int32, n=3000)
    mk = lambda s: build_index(keys, vals, IndexConfig(
        kind="tiered", mutable=True, specialize=s, delta_capacity=64,
        leaf_width=128))
    spec, args = mk(True), mk(False)
    assert spec._spec_fused is not None
    assert args._spec_fused is None
    probe = np.concatenate([qs, np.arange(64, dtype=np.int32) * 5 + 1])
    rng = np.random.default_rng(3)

    _assert_lookups_equal(spec, args, probe)
    for step in range(4):
        newk = rng.choice(1 << 20, 48, replace=False).astype(np.int32)
        for idx in (spec, args):
            idx.insert(newk, newk % 1000)
            idx.delete(newk[:8])
        _assert_lookups_equal(spec, args, probe)
    for idx in (spec, args):
        idx.flush()                         # force folds (merge or split)
    _assert_lookups_equal(spec, args, probe)
    # heavy insert wave: many seal/fold cycles; whether the LAST fold was
    # page-local (spec disarmed) or a split (spec re-armed), answers match
    wave = rng.choice(1 << 21, 2048, replace=False).astype(np.int32)
    for idx in (spec, args):
        idx.insert(wave, wave % 1000)
        idx.flush()
    assert spec.base.derives > 1
    _assert_lookups_equal(spec, args, probe)
    # a single fold guaranteed to split (delta swallows the whole wave in
    # one seal): the derive must RE-ARM the specialized twin
    mk2 = lambda s: build_index(keys, vals, IndexConfig(
        kind="tiered", mutable=True, specialize=s, delta_capacity=4096,
        leaf_width=128))
    spec2, args2 = mk2(True), mk2(False)
    for idx in (spec2, args2):
        idx.insert(wave, wave % 1000)
        idx.flush()
    assert spec2.base.derives > 1
    assert spec2._spec_fused is not None    # re-armed at the derive
    _assert_lookups_equal(spec2, args2, probe)
    spec2.close()
    args2.close()
    # scans agree too (mutable scan stays data-as-args by design)
    for lohi in ((np.asarray([0], np.int32), np.asarray([1 << 21] ,
                                                        np.int32)),):
        sa, sb = spec.scan_range(*lohi), args.scan_range(*lohi)
        np.testing.assert_array_equal(np.asarray(sa.count),
                                      np.asarray(sb.count))
        np.testing.assert_array_equal(np.asarray(sa.vsum),
                                      np.asarray(sb.vsum))
    spec.close()
    args.close()


def test_snapshot_restore_rearms_specialization(tmp_path):
    """from_state is a derive boundary: a restored specialize=True store
    comes back with the spec twin armed and bit-identical answers."""
    from repro.core import restore_index
    keys, vals, qs = _data(np.int32, n=1500)
    cfg = IndexConfig(kind="tiered", mutable=True, specialize=True,
                      delta_capacity=64, ckpt_dir=str(tmp_path / "ck"))
    idx = build_index(keys, vals, cfg)
    idx.insert(np.asarray([7, 9], np.int32), np.asarray([70, 90], np.int32))
    idx.save()
    want = idx.lookup(qs)
    idx.close()
    got = restore_index(str(tmp_path / "ck"), cfg)
    assert got._spec_fused is not None
    res = got.lookup(qs)
    np.testing.assert_array_equal(np.asarray(want.found),
                                  np.asarray(res.found))
    np.testing.assert_array_equal(np.asarray(want.values),
                                  np.asarray(res.values))
    got.close()


# ------------------------------------------------------------ retrace guard
@pytest.mark.parametrize("specialize", [False, True])
def test_inserts_between_derives_never_retrace(specialize):
    """The contract the delta-merge write path is built on, now pinned by
    jax's own trace-event stream: after warmup, insert→lookup cycles that
    stay between derives (no seal, no fold) compile NOTHING, in both
    specialize postures."""
    keys, vals, _ = _data(np.int32, n=2000)
    idx = build_index(keys, vals, IndexConfig(
        kind="tiered", mutable=True, specialize=specialize,
        delta_capacity=1024))
    q = jnp.asarray(keys[:256])
    batch = np.arange(16, dtype=np.int32)
    # warmup: compile the lookup shape + the delta mirrors for this batch
    idx.insert(batch * 2 + 1, batch)
    idx.lookup(q).rank.block_until_ready()
    derives0 = idx.base.derives
    with _count_traces() as traces:
        for r in range(1, 6):
            idx.insert(batch * 2 + 1, batch + r)     # upserts: no growth
            idx.lookup(q).rank.block_until_ready()
    assert idx.base.derives == derives0              # between derives
    assert traces == []
    idx.close()


# ----------------------------------------------------------- single dispatch
def test_specialized_path_single_dispatch_no_transfers():
    """Device-resident queries through the specialized fused lookup under
    transfer_guard('disallow'): one dispatch observed per call, zero
    host<->device transfers forced by the probe."""
    keys, vals, _ = _data(np.int32, n=2000)
    idx = build_index(keys, vals, IndexConfig(
        kind="tiered", mutable=True, specialize=True))
    q = jnp.asarray(keys[:128])
    idx.lookup(q).rank.block_until_ready()           # compile
    assert idx._spec_fused is not None
    with use_registry(Registry()) as reg:
        with jax.transfer_guard("disallow"):
            res = idx.lookup(q)
        h = reg.merged_histogram("dispatch_staging_seconds", path="lookup")
        assert h.count == 1
    np.testing.assert_array_equal(np.asarray(res.found),
                                  np.ones(128, bool))
    idx.close()

    frozen = build_index(keys, vals,
                         IndexConfig(kind="tiered", specialize=True))
    fq = jnp.asarray(keys[:128])
    frozen.search(fq).block_until_ready()            # compile
    with jax.transfer_guard("disallow"):
        out = frozen.search(fq)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(frozen.lookup(fq).rank))
