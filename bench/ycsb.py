"""YCSB core-workload generation from a seed: table, request keys, mixes.

Follows YCSB's CoreWorkload (Cooper et al., SoCC 2010; the Java sources of
``site.ycsb.generator``) wherever it defines something:

* records are numbered 0..n-1 and loaded in hashed order
  (``insertorder=hashed``). YCSB's key is ``"user" + fnvhash64(i)``; here
  it is a seeded bijection of the record number onto the 31-bit key
  domain below the index's sentinel, so keys are distinct int32 values
  spread over the domain and handed to the build in hashed order.
* the value of a record is its 32-bit locator, the record number: the
  index maps a key to where the record lives, not to a 1 KB body.
* request keys come from ``ScrambledZipfianGenerator``: a Zipfian draw
  with constant 0.99 over 10^10 items (YCSB's precomputed zeta), hashed by
  FNV-64 and taken modulo the key space, so hot records scatter over the
  table instead of sitting in neighbouring pages.
* ``CoreWorkload`` sizes that key space as recordcount plus the expected
  inserts and redraws a record that does not exist yet; with no inserts
  that is recordcount + 1, and the one record past the end is redrawn.
* the operation of each request is drawn from the mix's proportions.

Everything is vectorized NumPy: the table of 2^27 records is made in a few
seconds, so data generation stays a small part of set-up.
"""
from __future__ import annotations

import numpy as np

INT32_MAX = int(np.iinfo(np.int32).max)   # the index's key sentinel
INT32_MIN = int(np.iinfo(np.int32).min)   # the store's tombstone value
MASK31 = np.uint32(2**31 - 1)

# ScrambledZipfianGenerator's constants (YCSB site.ycsb.generator)
ZIPF_ITEM_COUNT = 10_000_000_000
ZIPF_CONSTANT = 0.99
ZIPF_ZETAN = 26.46902820178302
FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def _bijection31(x: np.ndarray, seed: int) -> np.ndarray:
    """A seeded permutation of [0, 2^31): offset, then xor-shift and odd
    multiply rounds, each a bijection modulo 2^31. Runs in uint32, whose
    wrapping product taken modulo 2^31 is the product modulo 2^31."""
    rng = np.random.default_rng([seed, 0x5EED])
    a0, a1 = (np.uint32(v) for v in rng.integers(0, 2**31, 2))
    m1, m2 = (np.uint32(v | 1) for v in rng.integers(0, 2**31, 2))
    x = x.astype(np.uint32)
    t = np.empty_like(x)
    x += a0
    x &= MASK31
    for shift, mult in ((16, m1), (13, m2)):
        np.right_shift(x, np.uint32(shift), out=t)
        x ^= t
        x *= mult
        x &= MASK31
    np.right_shift(x, np.uint32(16), out=t)
    x ^= t
    x += a1
    x &= MASK31
    return x


def record_keys(n: int, seed: int) -> np.ndarray:
    """int32 key of each record 0..n-1, in record (= hashed) order: n
    distinct values in [0, 2^31 - 1), the sentinel excluded."""
    keys = _bijection31(np.arange(n + 1, dtype=np.uint32), seed)
    keys = keys[keys != np.uint32(INT32_MAX)][:n]
    return keys.view(np.int32)


def record_values(n: int) -> np.ndarray:
    """int32 locator of each record: its record number."""
    return np.arange(n, dtype=np.int32)


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1 over the 8 low-to-high octets, then
    ``Math.abs`` of the signed result. Returns int64."""
    v = v.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            v >>= np.uint64(8)
            h *= FNV_PRIME_64
    return np.abs(h.view(np.int64))


def zipfian(u: np.ndarray, items: int = ZIPF_ITEM_COUNT + 1,
            theta: float = ZIPF_CONSTANT,
            zetan: float = ZIPF_ZETAN) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextLong`` for uniform draws ``u`` in [0, 1):
    item numbers in [0, items), item 0 the most popular. ``items`` is
    YCSB's ``max - min + 1`` for ``ZipfianGenerator(0, ITEM_COUNT)``."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    base = np.floor(items * np.power(eta * u - eta + 1.0, alpha))
    out = np.where(uz < 1.0 + 0.5 ** theta, 1, base.astype(np.int64))
    return np.where(uz < 1.0, 0, out).astype(np.int64)


def scrambled_zipfian(rng: np.random.Generator, count: int,
                      n_records: int) -> np.ndarray:
    """Record numbers drawn by YCSB's ScrambledZipfianGenerator over
    [0, n_records], the one past the end redrawn (CoreWorkload's
    ``nextKeynum``)."""
    out = np.empty(count, np.int64)
    todo = np.arange(count)
    while todo.size:
        r = fnvhash64(zipfian(rng.random(todo.size))) % (n_records + 1)
        ok = r < n_records
        out[todo[ok]] = r[ok]
        todo = todo[~ok]
    return out


def uniform(rng: np.random.Generator, count: int,
            n_records: int) -> np.ndarray:
    return rng.integers(0, n_records, count, dtype=np.int64)


KEY_DISTRIBUTIONS = {"scrambled_zipfian": scrambled_zipfian,
                     "uniform": uniform}


def update_values(rng: np.random.Generator, count: int) -> np.ndarray:
    """Fresh int32 values for updates, never the store's tombstone."""
    return rng.integers(INT32_MIN + 1, INT32_MAX, count, dtype=np.int32,
                        endpoint=True)


class Table:
    """The YCSB usertable as the index holds it: ``keys[i]``/``values[i]``
    of record i, in the hashed order they are loaded."""

    def __init__(self, n: int, seed: int):
        self.n = int(n)
        self.keys = record_keys(self.n, seed)
        self.values = record_values(self.n)
