"""The one generator every traffic mix goes through.

A mix is a JSON file under ``bench/traffic/`` (see the files there):

* ``mix``: the share of each operation, ``{"read": 0.95, "update": 0.05}``,
  summing to 1. The operations are those the configuration's deployment
  kind serves (``bench/deployment.py``; by default YCSB point reads and
  updates).
* ``keys``: ``{"distribution": "scrambled_zipfian" | "uniform"}``, over the
  record numbers of the configuration's table.
* ``arrival``: ``{"process": "poisson", "rate_per_s": r}``, an open loop.

Every seed gets the same number of requests and of each operation, in
another order and with other arrival times, so the seed changes which
keys are asked and when, not how much work there is. The draws come in a
fixed order from one stream: the operations, the records, each
operation's arguments in the kind's order, the arrivals.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Optional

import numpy as np

from bench import deployment, ycsb


@dataclass
class Requests:
    ops: tuple                 # the kind's operations, by name
    op: np.ndarray             # operation per request (index into ops)
    is_read: np.ndarray        # bool per request: a read operation
    record: np.ndarray         # record number per request
    args: dict                 # each operation's arguments, per request
    due: np.ndarray            # seconds after the window opens

    @property
    def n(self) -> int:
        return self.is_read.size

    def of(self, name: str) -> np.ndarray:
        """Bool per request: it is operation ``name``."""
        return self.op == self.ops.index(name) if name in self.ops else \
            np.zeros(self.n, bool)


def count(spec: dict, seconds: float) -> int:
    return max(1, int(round(spec["arrival"]["rate_per_s"] * seconds)))


def arrivals(rng: np.random.Generator, arr: dict, n: int,
             seconds: float) -> np.ndarray:
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    gaps = rng.exponential(1.0, n + 1)
    return np.cumsum(gaps)[:n] * (seconds / gaps.sum())


def assign(rng: np.random.Generator, mix: dict, ops: tuple,
           n: int) -> np.ndarray:
    """Operation per request: each operation's share of ``n`` (rounded
    on the running total, in the kind's order), shuffled."""
    unknown = set(mix) - set(ops)
    if unknown:
        raise ValueError(f"unknown operations {sorted(unknown)}; "
                         f"this deployment serves {list(ops)}")
    served = [k for k in ops if k in mix]
    if not served or abs(sum(mix.values()) - 1.0) > 1e-9:
        raise ValueError(f"the mix's shares must sum to 1: {mix}")
    op = np.empty(n, np.int8)
    lo = 0
    for k, total in zip(served, itertools.accumulate(mix[k] for k in served)):
        hi = n if k == served[-1] else int(round(total * n))
        op[lo:hi] = ops.index(k)
        lo = hi
    rng.shuffle(op)
    return op


def generate(spec: dict, n_records: int, seed: int, seconds: float,
             operations: Optional[dict] = None) -> Requests:
    """The window's requests; ``operations`` are the kind's (default the
    YCSB point reads and updates)."""
    operations = operations or deployment.POINT_OPERATIONS
    ops = tuple(operations)
    rng = np.random.default_rng([seed, 0xC0FFEE])
    n = count(spec, seconds)
    op = assign(rng, spec["mix"], ops, n)
    dist = ycsb.KEY_DISTRIBUTIONS[spec["keys"]["distribution"]]
    record = dist(rng, n, n_records)
    args = {}
    for k in ops:
        args.update(operations[k].draw(rng, n, n_records))
    due = arrivals(rng, spec["arrival"], n, seconds)
    is_read = np.asarray([operations[k].read for k in ops])[op]
    return Requests(ops=ops, op=op, is_read=is_read, record=record,
                    args=args, due=due)
