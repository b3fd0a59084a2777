"""The plain reference and the comparison that decides ``correct``.

The reference is NumPy over the same table, imports nothing of the
program and takes nothing it made: the sorted key column, searchsorted
ranks, and the acknowledged writes applied in the order they were made.
Its lookup is ``chip_smoke.lookup_oracle``, copied here so that a later
program change cannot move it.

What a read may return (the configuration's guarantees): answers are
exact, and a read sent after a write was acknowledged returns that
write's value or a newer one. A read that overlaps writes to its key may
return the value before them or any of theirs that had started before
the read's answer came back. Every read due in the window is compared.

This is the default reference of a deployment kind
(``bench/deployment.py``): ``Reference.check`` answers the point reads
and updates of ``POINT_OPERATIONS``.

The controls put the reference in the program's place, one guarantee
broken: ``f32`` searches keys cast to float32 (the nearest precision below
the int32 keys the configuration states; keys above 2^24 collide), and
``stale`` answers from the table as loaded, ignoring every write.
"""
from __future__ import annotations

import bisect
from collections import defaultdict

import numpy as np

CONTROLS = ("f32", "stale")


def lookup_oracle(keys, vals, q):
    rank = np.searchsorted(keys, q, side="left")
    safe = np.minimum(rank, keys.size - 1)
    found = (rank < keys.size) & (keys[safe] == q)
    return rank.astype(np.int32), found, vals[safe]


class Reference:
    """The table in key order, built from the records as loaded."""

    def __init__(self, keys: np.ndarray, values: np.ndarray):
        # one sort of (key, record) pairs packed in int64: keys are
        # distinct, so the order is the key order
        packed = (keys.astype(np.int64) << 32) | np.arange(keys.size)
        packed.sort()
        self.keys = (packed >> 32).astype(np.int32)
        self.vals = values[packed & 0xFFFFFFFF]

    def lookup(self, q: np.ndarray):
        return lookup_oracle(self.keys, self.vals, q)

    def control(self, kind: str, q: np.ndarray):
        """(rank, found, value) the control answers for ``q``."""
        if kind == "f32":
            kf = self.keys.astype(np.float32)
            qf = np.asarray(q).astype(np.float32)
            rank = np.searchsorted(kf, qf, side="left")
            safe = np.minimum(rank, kf.size - 1)
            found = (rank < kf.size) & (kf[safe] == qf)
            return rank.astype(np.int32), found, self.vals[safe]
        if kind == "stale":
            return self.lookup(q)
        raise ValueError(f"unknown control {kind!r}; want one of {CONTROLS}")

    def check(self, outcome) -> dict:
        """Each number compared, with its limit, for a window's point
        reads and writes (``deployment.Outcome``)."""
        return compare(self, point_reads(outcome), outcome.writes,
                       outcome.readback, outcome.compare_rank)

    def check_control(self, kind: str, outcome) -> dict:
        """The numbers ``check`` compares, for the control's answers to
        the same requests."""
        creads, cback = control_answers(self, kind, point_reads(outcome),
                                        outcome.readback)
        return compare(self, creads, outcome.writes, cback,
                       outcome.compare_rank)


def point_reads(outcome, op: str = "read") -> dict:
    """The sent requests of a point-read operation, with the answers kept
    (found, value, rank) and their times."""
    i = outcome.sent(op)
    answered = outcome.answered(i)
    got = [outcome.log.answer[j] if ok else (False, 0, 0)
           for j, ok in zip(i.tolist(), answered.tolist())]
    found, value, rank = (np.asarray(c) for c in zip(*got)) if got else \
        ((),) * 3
    t_done = outcome.log.t_done[i]
    return {"key": outcome.keys[i], "found": np.asarray(found, bool),
            "value": np.asarray(value, np.int32),
            "rank": np.asarray(rank, np.int32),
            "t_send": outcome.log.t_send[i],
            "t_done": np.where(answered, t_done, np.inf),
            "answered": answered}


def _write_log(writes: dict) -> dict:
    """{key: (t_start list, t_ack list, value list)} in the order the
    writes were applied (one writer applies them in turn)."""
    log = defaultdict(lambda: ([], [], []))
    for k, v, t0, t1 in zip(writes["key"], writes["value"],
                            writes["t_start"], writes["t_ack"]):
        s, a, vals = log[int(k)]
        s.append(float(t0))
        a.append(float(t1))
        vals.append(int(v))
    return log


def wrong_reads(ref: Reference, reads: dict, writes: dict,
                compare_rank: bool) -> np.ndarray:
    """Bool per answered read: its answer breaks the guarantees."""
    key = reads["key"]
    rank, found, val = ref.lookup(key)
    bad = (reads["found"] != found) | (found & (reads["value"] != val))
    if compare_rank:
        bad |= reads["rank"] != rank
    if writes["key"].size:
        log = _write_log(writes)
        hot = np.isin(key, writes["key"])
        for i in np.nonzero(hot)[0]:
            starts, acks, vals = log[int(key[i])]
            t_send, t_done = reads["t_send"][i], reads["t_done"][i]
            j0 = bisect.bisect_left(acks, t_send) - 1
            ok = {vals[j0] if j0 >= 0 else int(val[i])}
            for j in range(j0 + 1, len(vals)):
                if starts[j] < t_done:
                    ok.add(vals[j])
            bad[i] = (not reads["found"][i]) or \
                int(reads["value"][i]) not in ok
    return bad & reads["answered"]


def lost_writes(writes: dict, readback: dict) -> int:
    """Written keys whose value read back after the window is not the
    last acknowledged write's."""
    if not writes["key"].size:
        return 0
    last = {}
    for k, v, t1 in zip(writes["key"], writes["value"], writes["t_ack"]):
        if np.isfinite(t1):
            last[int(k)] = int(v)
    got = dict(zip(readback["key"].tolist(),
                   zip(readback["found"].tolist(),
                       readback["value"].tolist())))
    return sum(1 for k, v in last.items()
               if got.get(k, (False, None)) != (True, v))


def control_answers(ref: Reference, kind: str, reads: dict,
                    readback: dict) -> tuple:
    """The run's reads and read-back as the control would answer them."""
    def swap(rec):
        rank, found, val = ref.control(kind, rec["key"])
        out = dict(rec)
        out.update(rank=rank, found=found, value=val)
        return out
    return swap(reads), swap(readback)


def compare(ref: Reference, reads: dict, writes: dict, readback: dict,
            compare_rank: bool) -> dict:
    """Each number compared, with its limit. All are exact comparisons
    (limit 0): a read's answer, an answer that never came, a write that
    failed, a write not read back."""
    checks = {
        "wrong_reads": int(wrong_reads(ref, reads, writes,
                                       compare_rank).sum()),
        "missing_reads": int((~reads["answered"]).sum()),
    }
    if writes["key"].size:
        checks["failed_writes"] = int((~np.isfinite(writes["t_ack"])).sum())
        checks["lost_writes"] = lost_writes(writes, readback)
    return {k: {"value": v, "limit": 0} for k, v in checks.items()}
