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


def test_traced_bytes_count_the_dispatches_inside_the_traced_part():
    """``run.traced_bytes`` on a hand-made window: the queue's batches
    dispatched inside the tracer's span, counted as the point reads were
    before kinds brought their own counts, and the sent requests of a
    client operation inside it, each with its own arguments."""
    from types import SimpleNamespace

    from bench import deployment, run
    from bench.drive import Log
    from bench.reference import Reference
    from bench.workload import Requests

    rng = np.random.default_rng(7)
    keys = rng.permutation(np.arange(1, 2**15, dtype=np.int32) * 5)
    ref = Reference(keys, keys)
    batches = [(t, rng.choice(keys, 1 + t % 5).astype(np.int32))
               for t in range(0, 30, 2)]
    touch = deployment.Operation(read=True, via=deployment.CLIENT,
                                 call=lambda *a: None)
    kind = SimpleNamespace(
        operations=dict(deployment.POINT_OPERATIONS, touch=touch),
        least_bytes=dict(deployment.POINT_LEAST_BYTES,
                         touch=lambda r, b: 1000 * b["key"].size
                         + int(b["n"].sum())))
    n = 12
    op = np.array([0, 2] * (n // 2))              # read, touch, read, ...
    reqs = Requests(ops=("read", "update", "touch"), op=op,
                    is_read=op != 1, record=np.arange(n), due=np.zeros(n),
                    args={"wval": np.zeros(n, np.int32),
                          "n": np.arange(n) * 10})
    lg = Log(n)
    lg.t_send[:] = np.arange(n) * 2.0             # 0, 2, ..., 22
    lg.sent[:] = True
    lg.sent[7] = False                            # a touch never sent
    win = {"tracer": SimpleNamespace(span=(10.0, 20.0)), "batches": batches,
           "requests": reqs, "keys": keys[:n], "log": lg, "sent": lg.sent}

    got = run.traced_bytes(SimpleNamespace(kind=kind), win, ref)

    traced = [q for t, q in batches if 10 <= t <= 20]
    assert len(traced) == 6
    assert got["read"] == sum(roofline.batch_bytes(ref.keys, q)
                              for q in traced)
    # touches 5, 7 and 9 fall inside, at 10, 14 and 18 s; 7 was not sent
    assert got["touch"] == 2 * 1000 + 50 + 90
    assert set(got) == {"read", "touch"}
