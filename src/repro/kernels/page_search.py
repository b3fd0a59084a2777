"""Pallas TPU kernel: FAST leaf-page search with scalar-prefetched DMA.

This is the HBM tier of the hierarchical blocking (thesis §3.4): the
directory descent (small, VMEM/"code"-resident) has already produced a leaf
page id per query; queries are then *bucketed by page* (the sorted-batch
traversal from DESIGN.md §2.1) and each grid step DMAs exactly one leaf page
HBM->VMEM via a ``PrefetchScalarGridSpec`` index map — the TPU translation
of the paper's page blocking: one contiguous memory fetch serves a whole
tile of queries, and the scalar core issues the next page's DMA while the
VPU compares the current one (automatic double buffering).

Device page layout: leaf storage is ``[P, lw_pad // 128, 128]`` (see
:func:`page_tiles`), so one page is a whole number of (8, 128) VMEM tiles
and its block's last two dims equal the array's — the TPU block rule —
and a page DMA is one contiguous copy. Query and output tiles ride as
``[G, 1, TQ]`` for the same reason.

Within a page the search is a vector popcount (the paper's SIMD tier),
swept one 128-key lane row at a time (:func:`sweep_rows`): queries sit on
sublanes (one row per query, broadcast across lanes), keys on lanes, and a
``[TQ, 128]`` accumulator collects the per-lane counts, so VMEM use is
independent of the leaf width.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ops as _ops

LANES = 128
_ROW_BLOCK = 8                        # sublanes per loaded page tile


def page_tiles(pages) -> jnp.ndarray:
    """Host ``[P, lw_pad]`` leaf rows -> the device page layout
    ``[P, lw_pad // 128, 128]``. Row-major order is unchanged, so a flat
    slot address ``p * lw_pad + s`` means the same slot in both."""
    pages = np.asarray(pages)
    p, lw_pad = pages.shape
    return jnp.asarray(pages.reshape(p, lw_pad // LANES, LANES))


def query_rows(x: jnp.ndarray) -> jnp.ndarray:
    """``[1, TQ]`` lane row -> ``[TQ, 128]`` with row j = ``x[0, j]`` on
    every lane (sublane broadcast + one transpose)."""
    return jnp.transpose(jnp.broadcast_to(x, (LANES, x.shape[1])))


def lane_reduce(acc: jnp.ndarray, op=jnp.sum) -> jnp.ndarray:
    """``[TQ, 128]`` per-lane partials -> ``[1, TQ]`` per-query totals."""
    return op(jnp.transpose(acc), axis=0, keepdims=True)


def sweep_rows(refs, carry, body):
    """Fold ``body(carry, *rows)`` over the 128-key lane rows of the page
    blocks in ``refs`` (each ``[R, 128]``), ``rows`` being the aligned
    ``[1, 128]`` row of every ref. Rows load in (8, 128) tiles under a
    ``fori_loop``; a ragged tail (R % 8) is one static load."""
    n_rows = refs[0].shape[0]

    def tile_body(start, size, c):
        tiles = [r[pl.ds(start, size), :] for r in refs]
        for t in range(size):
            c = body(c, *[x[t:t + 1, :] for x in tiles])
        return c

    n_full = n_rows // _ROW_BLOCK
    if n_full:
        carry = jax.lax.fori_loop(
            0, n_full, lambda i, c: tile_body(
                pl.multiple_of(i * _ROW_BLOCK, _ROW_BLOCK), _ROW_BLOCK, c),
            carry)
    tail = n_rows % _ROW_BLOCK
    if tail:
        carry = tile_body(n_rows - tail, tail, carry)
    return carry


def _kernel(page_ids_ref, q_ref, pages_ref, o_ref, *, stride: int):
    g = pl.program_id(0)
    qm = query_rows(q_ref[...])                      # [TQ, 128]
    acc = sweep_rows((pages_ref,), jnp.zeros(qm.shape, jnp.int32),
                     lambda a, k: a + (k < qm).astype(jnp.int32))
    local = lane_reduce(acc)                         # [1, TQ]
    base = page_ids_ref[g] * stride
    o_ref[...] = (base + jnp.minimum(local, stride)).astype(jnp.int32)


def bucketed_call(kern, lanes_in, page_ids, page_arrays, out_dtypes,
                  interpret, name=None):
    """One scalar-prefetched pallas_call over G page buckets: ``lanes_in``
    are [G, TQ] per-lane operands, ``page_arrays`` the [P, R, 128] page
    storages streamed at ``page_ids[g]``; returns one [G, TQ] array per
    entry of ``out_dtypes``. ``name`` names the kernel's op in a device
    trace whatever ``lax.switch`` rung runs it (unnamed, a rung's op shows
    as ``branch_<k>_fun``)."""
    G, TQ = lanes_in[0].shape
    lane_spec = pl.BlockSpec((None, 1, TQ), lambda g, pids: (g, 0, 0))
    _, rows, lanes = page_arrays[0].shape
    page_spec = pl.BlockSpec((None, rows, lanes),
                             lambda g, pids: (pids[g], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G,),
        in_specs=[lane_spec] * len(lanes_in) + [page_spec] * len(page_arrays),
        out_specs=tuple(lane_spec for _ in out_dtypes),
    )
    outs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=tuple(jax.ShapeDtypeStruct((G, 1, TQ), d)
                        for d in out_dtypes),
        interpret=_ops.use_interpret(interpret),
        name=name,
    )(page_ids, *(x.reshape(G, 1, TQ) for x in lanes_in), *page_arrays)
    return tuple(o.reshape(G, TQ) for o in outs)


def page_search_bucketed(queries_bucketed: jnp.ndarray, page_ids: jnp.ndarray,
                         pages: jnp.ndarray, *, stride: int,
                         interpret: bool | None = None) -> jnp.ndarray:
    """queries_bucketed: [G, TQ] — step g's queries all live in page
    page_ids[g]; pages: [num_pages, lw_pad // 128, 128] leaf storage
    (sentinel padded, :func:`page_tiles`). Returns ``page_ids[g] * stride +
    in-page count`` per lane, [G, TQ].

    ``stride`` is the per-page base the in-page count is offset by — the
    dense engine passes ``leaf_width`` (results are global searchsorted
    ranks); the mutable store passes the padded row width ``lw_pad``
    (results are flat *slot addresses* into its gapped leaf storage), which
    is why this kwarg is not named ``leaf_width``. ``interpret=None``
    resolves from the backend (:func:`ops.use_interpret`).
    """
    kern = functools.partial(_kernel, stride=stride)
    (out,) = bucketed_call(kern, (queries_bucketed,), page_ids, (pages,),
                           (jnp.int32,), interpret, name="page_kernel")
    return out


# The host-side bucketing plan lives in engine/schedule.py (bucket_plan, plus
# its in-jit twin device_plan); this module is kernel-only.
