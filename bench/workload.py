"""The one generator every traffic mix goes through.

A mix is a JSON file under ``bench/traffic/`` (see the files there):

* ``mix``: the share of each operation, ``{"read": 0.95, "update": 0.05}``.
* ``keys``: ``{"distribution": "scrambled_zipfian" | "uniform"}``, over the
  record numbers of the configuration's table.
* ``arrival``: ``{"process": "poisson", "rate_per_s": r}``, an open loop.

Every seed gets the same number of requests and of each operation, in
another order and with other arrival times, so the seed changes which
keys are asked and when, not how much work there is.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from bench import ycsb

OPS = ("read", "update")


@dataclass
class Requests:
    is_read: np.ndarray        # bool per request
    record: np.ndarray         # record number per request
    wval: np.ndarray           # value an update writes
    due: np.ndarray            # seconds after the window opens

    @property
    def n(self) -> int:
        return self.is_read.size


def count(spec: dict, seconds: float) -> int:
    return max(1, int(round(spec["arrival"]["rate_per_s"] * seconds)))


def arrivals(rng: np.random.Generator, arr: dict, n: int,
             seconds: float) -> np.ndarray:
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    gaps = rng.exponential(1.0, n + 1)
    return np.cumsum(gaps)[:n] * (seconds / gaps.sum())


def generate(spec: dict, n_records: int, seed: int,
             seconds: float) -> Requests:
    rng = np.random.default_rng([seed, 0xC0FFEE])
    n = count(spec, seconds)
    mix = spec["mix"]
    unknown = set(mix) - set(OPS)
    if unknown:
        raise ValueError(f"unknown operations {sorted(unknown)}; "
                         f"this generator knows {OPS}")
    n_read = int(round(mix.get("read", 0.0) * n))
    is_read = np.zeros(n, bool)
    is_read[:n_read] = True
    rng.shuffle(is_read)
    dist = ycsb.KEY_DISTRIBUTIONS[spec["keys"]["distribution"]]
    record = dist(rng, n, n_records)
    wval = ycsb.update_values(rng, n)
    due = arrivals(rng, spec["arrival"], n, seconds)
    return Requests(is_read=is_read, record=record, wval=wval, due=due)
