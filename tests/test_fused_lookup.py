"""The frozen tiered ``Index.lookup`` as one jitted program (DESIGN.md §4).

Contracts under test:

* equivalence — the fused program answers bit-identically (rank, found
  flag, value, dtypes) to the eager composition it replaces: the search,
  then the clamp and the two gathers from the sorted columns; across key
  dtypes (int32 donates the query buffer, float32 does not), both
  ``specialize`` postures and the batch shapes the queue sends;
* ownership — a caller's device query buffer is never donated;
* reach — only the device-plan tiered kind takes the fused path; every
  other kind and ``plan="host"`` keep the eager spans, and a lookup traced
  inside a caller's jit (the mutable store over a non-paged base) still
  composes;
* engagement — through the micro-batch queue, one fused dispatch per
  flush (``index_lookup_dispatches{path="fused"}``,
  ``dispatch_staging_seconds{path="lookup"}``), no forced transfer for
  device-resident queries, and the ``lookup.fused`` span inside
  ``index.lookup``.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.obs.trace as trace_mod
from repro.core import IndexConfig, build_index
from repro.core.util import sentinel_for
from repro.engine.queue import MicroBatchQueue, index_probe_fn
from repro.obs.metrics import Registry, use_registry
from repro.obs.trace import Tracer


def _keys(dtype, n=3000, seed=0):
    """Sorted unique keys whose smallest is above zero and whose largest
    sits one step below the top of the key domain, the value just below
    the sentinel."""
    rng = np.random.default_rng(seed)
    if np.dtype(dtype).kind == "f":
        body = np.unique(rng.uniform(1.0, 1e6, n).astype(dtype))
    else:
        body = np.unique(rng.integers(1, 1 << 24, n)).astype(dtype)
    return np.concatenate([body, [_below(_top(dtype))]]).astype(dtype)


def _top(dtype):
    """The largest key the domain admits, just below the sentinel."""
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return np.finfo(dt).max
    return dt.type(sentinel_for(dt) - 1)


def _below(x):
    return np.nextafter(x, x.dtype.type(0)) if x.dtype.kind == "f" \
        else x.dtype.type(x - 1)


def _queries(keys, kind):
    """``one``: a single present key. ``padded``: present keys, absent
    keys, one below the first key and three at the sentinel edge (the
    largest key; the value just below it and the top of the domain, both
    absent, the latter ranked past every key), padded to a power of two
    with zero rows as the queue pads a flush. ``empty``: Q = 0."""
    dt = keys.dtype
    if kind == "empty":
        return np.zeros((0,), dt)
    if kind == "one":
        return keys[5:6].copy()
    if dt.kind == "f":
        absent = np.nextafter(keys[1:40:3], keys[2:41:3]).astype(dt)
    else:
        absent = (keys[1:40:3] + 1).astype(dt)
    edge = np.asarray([keys[-1], _below(keys[-1]), _top(dt)], dt)
    real = np.concatenate([keys[::97], absent, [_below(keys[0])], edge])
    pad = (1 << int(np.ceil(np.log2(real.size)))) - real.size
    return np.concatenate([real, np.zeros((pad,), dt)])


@functools.lru_cache(maxsize=None)
def _index(dtype, specialize):
    keys = _keys(np.dtype(dtype))
    vals = np.arange(keys.size, dtype=np.int32)[::-1].copy()
    return build_index(keys, vals, IndexConfig(kind="tiered",
                                               specialize=specialize))


def _eager(idx, q):
    """The lookup before fusion: search, clamp, two eager gathers."""
    qd = jnp.asarray(q)
    rank = idx.search(qd)
    safe = jnp.minimum(rank, idx.n - 1)
    found = (rank < idx.n) & (jnp.take(idx.keys_sorted, safe, axis=0) == qd)
    return rank, found, jnp.take(idx.values_sorted, safe, axis=0)


def _dispatches(reg, kind, path):
    return reg.total("index_lookup_dispatches", kind=kind, path=path)


def _assert_same(got, want):
    for g, w in zip((got.rank, got.found, got.values), want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("batch", ["empty", "one", "padded"])
@pytest.mark.parametrize("specialize", [False, True])
@pytest.mark.parametrize("dtype", ["int32", "float32"])
def test_fused_lookup_matches_eager_composition(dtype, specialize, batch):
    idx = _index(dtype, specialize)
    assert idx.impl.donate is (dtype == "int32")
    q = _queries(np.asarray(idx.keys_sorted), batch)
    with use_registry(Registry()) as reg:
        got = idx.lookup(q.copy())
        assert _dispatches(reg, "tiered", "fused") == 1
    _assert_same(got, _eager(idx, q))
    keys = np.asarray(idx.keys_sorted)
    rank = np.searchsorted(keys, q, side="left")
    np.testing.assert_array_equal(np.asarray(got.rank), rank)
    hit = (rank < keys.size) & (keys[np.minimum(rank, keys.size - 1)] == q)
    np.testing.assert_array_equal(np.asarray(got.found), hit)
    if batch == "padded":
        assert hit.any() and not hit.all() and rank.max() == keys.size


@pytest.mark.parametrize("specialize", [False, True])
def test_caller_device_buffer_is_never_donated(specialize):
    idx = _index("int32", specialize)
    assert idx.impl.donate
    host = _queries(np.asarray(idx.keys_sorted), "padded")
    q = jnp.asarray(host)
    first = idx.lookup(q)
    second = idx.lookup(q)                   # q must still be live
    assert not q.is_deleted()
    np.testing.assert_array_equal(np.asarray(q), host)
    _assert_same(second, (first.rank, first.found, first.values))


def test_lookup_traced_in_a_callers_jit_composes():
    idx = _index("int32", False)
    q = _queries(np.asarray(idx.keys_sorted), "padded")
    with use_registry(Registry()) as reg:
        got = jax.jit(idx.lookup)(jnp.asarray(q))
        assert reg.value("dispatch_staging_seconds", path="lookup") is None
    _assert_same(got, _eager(idx, q))


@pytest.mark.parametrize("config", [
    IndexConfig(kind="binary"),
    IndexConfig(kind="css"),
    IndexConfig(kind="tiered", plan="host"),
], ids=["binary", "css", "tiered-host"])
def test_other_kinds_and_host_plan_stay_eager(config):
    keys = np.arange(0, 8192, 2, dtype=np.int32)
    idx = build_index(keys, keys * 3, config)
    assert idx._fused is None
    tr = Tracer()
    tr.enable()
    old, trace_mod.TRACER = trace_mod.TRACER, tr
    try:
        with use_registry(Registry()) as reg:
            res = idx.lookup(np.asarray([4, 5, 8190], np.int32))
            assert _dispatches(reg, config.kind, "eager") == 1
            assert _dispatches(reg, config.kind, "fused") == 0
    finally:
        trace_mod.TRACER = old
    names = {e["name"] for e in tr.events()}
    assert {"lookup.put", "lookup.gather"} <= names
    assert "lookup.fused" not in names
    np.testing.assert_array_equal(np.asarray(res.found), [True, False, True])
    np.testing.assert_array_equal(np.asarray(res.values)[[0, 2]],
                                  [12, 24570])


def test_mutable_store_over_binary_base_traces_base_lookup():
    """``MutableIndex`` over a non-paged base calls ``base.lookup`` inside
    its own jit: the frozen lookup must stay traceable."""
    keys = np.arange(0, 4000, 4, dtype=np.int32)
    idx = build_index(keys, keys + 1, IndexConfig(
        kind="binary", mutable=True, delta_capacity=64))
    idx.insert(np.asarray([1, 2, 3], np.int32), np.asarray([10, 20, 30],
                                                           np.int32))
    idx.delete(np.asarray([8], np.int32))
    q = np.asarray([0, 1, 2, 3, 4, 8, 12, 3996, 3997], np.int32)
    for _ in range(2):                      # delta-resident, then folded
        res = idx.lookup(q)
        np.testing.assert_array_equal(
            np.asarray(res.found),
            [True, True, True, True, True, False, True, True, False])
        got = np.asarray(res.values)[np.asarray(res.found)]
        np.testing.assert_array_equal(got, [1, 10, 20, 30, 5, 13, 3997])
        idx.flush()
    idx.close()


def test_queue_flushes_take_one_fused_dispatch_each():
    idx = _index("int32", False)
    keys = np.asarray(idx.keys_sorted)
    with use_registry(Registry()) as reg:
        q = MicroBatchQueue(index_probe_fn(idx), capacity=64, timer=False)
        n = 4
        futs = []
        for i in range(n):
            futs += [q.submit(keys[i * 3 + j: i * 3 + j + 1])
                     for j in range(3)]
            q.flush()
        q.close()
        assert _dispatches(reg, "tiered", "fused") == n
        assert _dispatches(reg, "tiered", "eager") == 0
        assert reg.merged_histogram("dispatch_staging_seconds",
                                    path="lookup").count == n
        assert reg.merged_histogram("dispatch_staging_seconds",
                                    path="probe").count == n
    assert q.stats.flushes == n
    for i, f in enumerate(futs):
        res = jax.device_get(f.result())
        assert bool(res.found[0]) and int(res.rank[0]) == i


def test_device_queries_force_no_transfer():
    idx = _index("int32", False)
    q = jnp.asarray(np.asarray(idx.keys_sorted)[:128])
    jax.block_until_ready(idx.lookup(q))                # compile
    with use_registry(Registry()) as reg:
        with jax.transfer_guard("disallow"):
            res = idx.lookup(q)
        assert reg.merged_histogram("dispatch_staging_seconds",
                                    path="lookup").count == 1
    np.testing.assert_array_equal(np.asarray(res.found), np.ones(128, bool))
    np.testing.assert_array_equal(np.asarray(res.rank), np.arange(128))


def test_fused_span_nests_in_index_lookup():
    from bench.tracing import SPAN_NAME

    idx = _index("int32", False)
    tr = Tracer()
    tr.enable()
    old, trace_mod.TRACER = trace_mod.TRACER, tr
    try:
        idx.lookup(np.asarray(idx.keys_sorted)[:4])
    finally:
        trace_mod.TRACER = old
    evs = tr.events()
    outer = next(e for e in evs if e["name"] == "index.lookup")
    fused = next(e for e in evs if e["name"] == "lookup.fused")
    assert outer["args"] == {"kind": "tiered"}
    assert fused["tid"] == outer["tid"] and fused["ts"] >= outer["ts"]
    assert fused["ts"] + fused["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    names = {e["name"] for e in evs}
    assert not names & {"lookup.put", "lookup.gather", "tiered.search"}
    assert SPAN_NAME.match("lookup.fused")
