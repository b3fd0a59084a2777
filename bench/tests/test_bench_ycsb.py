"""The YCSB generators against their definitions (site.ycsb.generator)."""
import math

import numpy as np
import pytest

from bench import workload, ycsb

M64 = (1 << 64) - 1


def fnv_scalar(val: int) -> int:
    """Utils.fnvhash64, written as the Java does it, on Python ints."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * 1099511628211) & M64
    if h >= 1 << 63:                  # Java long, then Math.abs
        h -= 1 << 64
    return abs(h)


def zipf_scalar(u: float, items: int, theta: float, zetan: float) -> int:
    """ZipfianGenerator.nextLong(items) for one uniform draw u."""
    alpha = 1.0 / (1.0 - theta)
    zeta2theta = 1.0 + 0.5 ** theta
    eta = (1 - (2.0 / items) ** (1 - theta)) / (1 - zeta2theta / zetan)
    uz = u * zetan
    if uz < 1.0:
        return 0
    if uz < 1.0 + 0.5 ** theta:
        return 1
    return int(items * (eta * u - eta + 1) ** alpha)


def test_fnvhash64_matches_java_arithmetic():
    vals = [0, 1, 2, 255, 256, 123456789, 10**10, 2**40 + 7]
    got = ycsb.fnvhash64(np.asarray(vals, np.uint64))
    assert got.tolist() == [fnv_scalar(v) for v in vals]


def test_zipfian_matches_next_long():
    rng = np.random.default_rng(1)
    u = np.concatenate([rng.random(2000), [0.0, 0.01, 0.05, 0.999999]])
    got = ycsb.zipfian(u)
    want = [zipf_scalar(float(x), ycsb.ZIPF_ITEM_COUNT + 1,
                        ycsb.ZIPF_CONSTANT, ycsb.ZIPF_ZETAN) for x in u]
    assert got.tolist() == want


def test_zipfian_head_probabilities():
    """Item 0 is drawn with probability 1/zeta(n), item 1 with
    0.5^theta/zeta(n)."""
    u = np.random.default_rng(2).random(400_000)
    z = ycsb.zipfian(u)
    p0 = 1 / ycsb.ZIPF_ZETAN
    p1 = 0.5 ** ycsb.ZIPF_CONSTANT / ycsb.ZIPF_ZETAN
    for item, p in ((0, p0), (1, p1)):
        sd = math.sqrt(p * (1 - p) / u.size)
        assert abs((z == item).mean() - p) < 5 * sd


def test_scrambled_zipfian_is_hashed_zipfian_mod_keyspace():
    n = 1000
    rng = np.random.default_rng(3)
    got = ycsb.scrambled_zipfian(rng, 5000, n)
    assert got.min() >= 0 and got.max() < n
    # same draws by hand: hash the Zipfian item, modulo recordcount + 1,
    # redraw the one record past the end
    rng = np.random.default_rng(3)
    u = rng.random(5000)
    want = np.array([fnv_scalar(zipf_scalar(
        float(x), ycsb.ZIPF_ITEM_COUNT + 1, ycsb.ZIPF_CONSTANT,
        ycsb.ZIPF_ZETAN)) % (n + 1) for x in u])
    keep = want < n
    assert np.array_equal(got[keep], want[keep])
    # the hot records scatter: the most popular ones are not neighbours
    vals, cnt = np.unique(got, return_counts=True)
    hot = np.sort(vals[np.argsort(-cnt)[:8]])
    assert np.diff(hot).min() > 1


def test_record_keys_distinct_below_sentinel_and_seeded():
    a = ycsb.record_keys(1 << 16, seed=2**40 + 1)
    b = ycsb.record_keys(1 << 16, seed=2**40 + 1)
    c = ycsb.record_keys(1 << 16, seed=2**40 + 2)
    assert a.dtype == np.int32 and np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.unique(a).size == a.size
    assert a.min() >= 0 and a.max() < ycsb.INT32_MAX
    # spread over the domain, loaded in hashed (unsorted) order
    assert a.max() > 2**30 and a.min() < 2**26
    assert not np.all(np.diff(a) > 0)


def test_update_values_never_tombstone():
    v = ycsb.update_values(np.random.default_rng(4), 100_000)
    assert v.dtype == np.int32 and not np.any(v == ycsb.INT32_MIN)


MIX = {"mix": {"read": 0.95, "update": 0.05},
       "keys": {"distribution": "scrambled_zipfian"},
       "arrival": {"process": "poisson", "rate_per_s": 1000}}


def test_mix_is_exact_and_the_seed_only_reorders():
    a = workload.generate(MIX, 4096, seed=2**35 + 11, seconds=4)
    b = workload.generate(MIX, 4096, seed=2**35 + 12, seconds=4)
    assert a.n == b.n == 4000
    assert a.is_read.sum() == b.is_read.sum() == 3800
    assert not np.array_equal(a.is_read, b.is_read)
    assert np.all(np.diff(a.due) > 0) and 0 < a.due[0] and a.due[-1] < 4
    c = workload.generate(MIX, 4096, seed=2**35 + 11, seconds=4)
    assert np.array_equal(a.record, c.record) and np.array_equal(a.due,
                                                                 c.due)


def test_poisson_arrivals():
    spec = dict(MIX, arrival={"process": "poisson", "rate_per_s": 2000})
    r = workload.generate(spec, 4096, seed=9, seconds=10)
    assert r.n == 20000
    assert np.all(np.diff(r.due) >= 0) and r.due[-1] < 10
    closed = dict(MIX, arrival={"process": "closed", "rate_per_s": 10})
    with pytest.raises(ValueError):
        workload.generate(closed, 4096, seed=9, seconds=10)
