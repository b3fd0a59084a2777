"""The bytes a batch of point lookups has to move, counted from the data.

A lookup of q in a sorted int32 key column has to read at least the tile
of the column that holds q's answer position (its searchsorted-left rank),
and a hit the same tile of the value column. A tile is 4 KiB, one
(8, 128) int32 vreg tile. Queries whose answers share a tile share its
read. Add the batch's input (one int32 key a query) and output (an int32
rank, a bool found flag and an int32 value a query). This is a lower bound
for any layout of the index, so the share of the roofline it gives cannot
pass 100% whatever a later kernel reads; it does not depend on the
kernel's grid, steps or page width.
"""
from __future__ import annotations

import numpy as np

TILE_BYTES = 4096
TILE_KEYS = TILE_BYTES // 4
IN_BYTES = 4                  # int32 key
OUT_BYTES = 4 + 1 + 4         # int32 rank, bool found, int32 value


def batch_bytes(sorted_keys: np.ndarray, queries: np.ndarray) -> int:
    """Least HBM bytes one dispatched batch of ``queries`` must move."""
    q = np.asarray(queries)
    pos = np.searchsorted(sorted_keys, q, side="left")
    n = sorted_keys.size
    hit = pos < n
    hit[hit] = sorted_keys[pos[hit]] == q[hit]
    key_tiles = np.unique(np.minimum(pos, n - 1) // TILE_KEYS).size
    val_tiles = np.unique(pos[hit] // TILE_KEYS).size
    return (key_tiles + val_tiles) * TILE_BYTES + q.size * (IN_BYTES
                                                            + OUT_BYTES)
