"""Chip-compile guards: every main-path Pallas kernel, compiled through
Mosaic (``interpret=False``) for a described TPU v5e — no chip attached —
at the widths a 2^27-key tiered table uses (leaf width 16384, 8192 pages,
a depth-2 k-ary top over the page separators) and, for the page kernels
and the fused jits, at the 2^28-key leaf width (32768) too. A kernel the
TPU compiler refuses (block shapes off the (8, 128) tiling, unsupported
vector shape casts, too much VMEM) fails here instead of on the chip."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import kary
from repro.engine import scan, tiered
from repro.kernels import cdf_search, kary_search, ops, page_scan, page_search

N_KEYS = 2**27
LEAF_WIDTH = 16384                   # plan_tiers' pick for 2^27 keys
NUM_PAGES = N_KEYS // LEAF_WIDTH     # 8192
# plan_tiers' leaf widths for 2^27 and 2^28 keys: both give 8192 pages
LEAF_WIDTHS = [tiered.plan_tiers(n)[0] for n in (N_KEYS, 2 * N_KEYS)]
TILE = 128
GRID = 64                            # bucketed grid steps per dispatch
N_QUERIES = 8192                     # one fused dispatch's batch


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off: a compile for a described chip is written to the cache but cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _page_args(one_chip, n_lanes, with_values, leaf_width=LEAF_WIDTH):
    lanes = [_spec((GRID, TILE), jnp.int32, one_chip)] * n_lanes
    pids = _spec((GRID,), jnp.int32, one_chip)
    tiles = (NUM_PAGES, leaf_width // 128, 128)
    pages = [_spec(tiles, jnp.int32, one_chip)] * (2 if with_values else 1)
    return lanes, pids, pages


@pytest.mark.parametrize("leaf_width", LEAF_WIDTHS)
def test_page_search_compiles(one_chip, leaf_width):
    (q,), pids, (pages,) = _page_args(one_chip, 1, False, leaf_width)
    _assert_mosaic(lambda q, p, pg: page_search.page_search_bucketed(
        q, p, pg, stride=leaf_width, interpret=False), q, pids, pages)


@pytest.mark.parametrize("leaf_width", LEAF_WIDTHS)
@pytest.mark.parametrize("mode", page_scan.MODES)
def test_page_scan_compiles(one_chip, mode, leaf_width):
    (lo, hi), pids, (kp, vp) = _page_args(one_chip, 2, True, leaf_width)
    if mode == "count":
        _assert_mosaic(lambda lo, hi, p, kp: page_scan.page_scan_bucketed(
            lo, hi, p, kp, mode=mode, interpret=False), lo, hi, pids, kp)
    else:
        _assert_mosaic(lambda lo, hi, p, kp, vp: page_scan.page_scan_bucketed(
            lo, hi, p, kp, vp, mode=mode, mask_value=np.iinfo(np.int32).min,
            interpret=False), lo, hi, pids, kp, vp)


@pytest.mark.parametrize("leaf_width", LEAF_WIDTHS)
@pytest.mark.parametrize("with_sum", [False, True])
def test_page_prefix_compiles(one_chip, with_sum, leaf_width):
    (e,), pids, (kp, vp) = _page_args(one_chip, 1, True, leaf_width)
    if with_sum:
        _assert_mosaic(lambda e, p, kp, vp: page_scan.page_prefix_bucketed(
            e, p, kp, vp, interpret=False), e, pids, kp, vp)
    else:
        _assert_mosaic(lambda e, p, kp: page_scan.page_prefix_bucketed(
            e, p, kp, interpret=False), e, pids, kp)


def test_kary_top_compiles(one_chip):
    seps = np.arange(NUM_PAGES, dtype=np.int32) * LEAF_WIDTH
    top = kary.build(seps, node_width=127)
    levels = [_spec(l.shape, l.dtype, one_chip)
              for l in ops.kary_levels(top, 128)]
    q = _spec((64, 128), jnp.int32, one_chip)
    _assert_mosaic(lambda q, *lv: kary_search.kary_search_tiled(
        q, list(lv), fanout=top.fanout, interpret=False), q, *levels)


def test_cdf_search_compiles(one_chip):
    cdf = _spec((8, 32768), jnp.float32, one_chip)
    u = _spec((8,), jnp.float32, one_chip)
    _assert_mosaic(lambda c, u: cdf_search.cdf_search(c, u, interpret=False),
                   cdf, u)


def _fused_top(leaf_width):
    """The k-ary top over the separators of a table of NUM_PAGES pages
    at ``leaf_width`` (2^27 or 2^28 keys), as the traceable page-id
    descent the fused jits inline."""
    num_pages = NUM_PAGES
    seps = np.arange(1, num_pages + 1, dtype=np.int32) * leaf_width - 1
    top_kind, top = tiered.build_top(seps)
    assert top_kind == "kary"
    return num_pages, tiered._make_page_of_raw(top_kind, top, num_pages,
                                               lane=128, tile_rows=8)


def _assert_fused(monkeypatch, fn, args, pages_bytes):
    """Compile a whole engine jit for the chip: the kernels inside resolve
    their mode from the backend, so the backend reads as a TPU here. The
    fused dispatch must hold Mosaic kernels and never stage a copy of the
    leaf storage in its temporaries."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pages_bytes // 16, mem


@pytest.mark.parametrize("leaf_width", LEAF_WIDTHS)
def test_fused_search_compiles(one_chip, monkeypatch, leaf_width):
    num_pages, page_of = _fused_top(leaf_width)
    pipe = tiered._make_pipeline(page_of, num_pages=num_pages,
                                 stride=leaf_width, tile=TILE,
                                 clip=num_pages * leaf_width)
    pages = _spec((num_pages, leaf_width // 128, 128), jnp.int32, one_chip)
    q = _spec((N_QUERIES,), jnp.int32, one_chip)
    _assert_fused(monkeypatch, pipe, (q, pages), num_pages * leaf_width * 4)


@pytest.mark.parametrize("leaf_width", LEAF_WIDTHS)
def test_fused_lookup_compiles(one_chip, monkeypatch, leaf_width):
    """The frozen ``Index.lookup`` program: the search pipeline, the clamp
    and the found/value gathers from the sorted columns, all arguments."""
    from repro.core.api import _lookup_program
    num_pages, page_of = _fused_top(leaf_width)
    n = num_pages * leaf_width
    lookup = _lookup_program(tiered._make_pipeline(
        page_of, num_pages=num_pages, stride=leaf_width, tile=TILE, clip=n))
    pages = _spec((num_pages, leaf_width // 128, 128), jnp.int32, one_chip)
    col = _spec((n,), jnp.int32, one_chip)
    q = _spec((N_QUERIES,), jnp.int32, one_chip)
    _assert_fused(monkeypatch, lookup, (q, pages, col, col), n * 4)


@pytest.mark.parametrize("leaf_width", LEAF_WIDTHS)
def test_fused_scan_compiles(one_chip, monkeypatch, leaf_width):
    num_pages, page_of = _fused_top(leaf_width)
    span_of = tiered._make_span_of(page_of, np.int32)
    pipe = scan.make_span_pipeline(span_of, num_pages=num_pages, tile=TILE,
                                   key_dtype=np.int32, val_dtype=np.int32,
                                   mode="full")
    cnt = np.full(num_pages, leaf_width, np.int64)
    aux = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                       scan.build_page_aux(cnt, None, np.int32))
    pages = _spec((num_pages, leaf_width // 128, 128), jnp.int32, one_chip)
    lo = hi = _spec((N_QUERIES,), jnp.int32, one_chip)
    _assert_fused(monkeypatch, pipe, (lo, hi, pages, pages, aux),
                  2 * num_pages * leaf_width * 4)


@pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True),
                                          ("gpu", True)])
def test_interpret_resolves_from_backend(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.use_interpret() is want
    assert ops.use_interpret(not want) is (not want)
