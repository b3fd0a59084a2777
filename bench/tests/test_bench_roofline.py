"""The roofline byte count against a brute-force count."""
import numpy as np
import pytest

from bench import roofline


def brute(sorted_keys, queries):
    """Walk every query: the tile of its answer position in the key
    column, and for a hit the same tile of the value column."""
    key_tiles, val_tiles = set(), set()
    n = sorted_keys.size
    for q in queries.tolist():
        pos = int(np.searchsorted(sorted_keys, q))
        key_tiles.add(min(pos, n - 1) // 1024)
        if pos < n and sorted_keys[pos] == q:
            val_tiles.add(pos // 1024)
    return (len(key_tiles) + len(val_tiles)) * 4096 + queries.size * 13


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batch_bytes_matches_brute_force(seed):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(2**31 - 1, 50_000, replace=False)) \
        .astype(np.int32)
    hits = rng.choice(keys, 300)
    misses = rng.integers(0, 2**31 - 1, 200, dtype=np.int64) \
        .astype(np.int32)
    edges = np.array([0, keys[0], keys[-1], 2**31 - 2], np.int32)
    q = rng.permutation(np.concatenate([hits, misses, edges]))
    assert roofline.batch_bytes(keys, q) == brute(keys, q)


def test_batch_bytes_shares_tiles():
    keys = np.arange(0, 8192, dtype=np.int32) * 2
    same_tile = np.full(64, 100, np.int32)
    assert roofline.batch_bytes(keys, same_tile) == 2 * 4096 + 64 * 13
    spread = keys[::1024][:8]
    assert roofline.batch_bytes(keys, spread) == 16 * 4096 + 8 * 13
