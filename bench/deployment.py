"""The contract between the harness and a deployment kind.

A deployment kind is a module ``bench/deploy/<kind>.py``, named by a
configuration's ``kind``. It owns everything about its deployment. The
harness (``bench/run.py``) looks up each hook below by name and takes the
default where the module defines none, so a kind that defines only
``build`` serves YCSB point reads and updates as the first kinds do, and a
new deployment is new files and entries, not an edit.

``build(config, table, workdir) -> Deployment`` (required)
    The system under test over ``table``, ready to serve: a kind whose
    operations launch programs of their own warms them here.

``make_table(config, seed)`` (default ``ycsb.Table(recordcount, seed)``)
    The data, from the configuration and ``--seed``: an object with ``n``
    records, their int32 ``keys[n]`` (a request asks for record ``r`` by
    ``keys[r]``) and ``values[n]``.

``OPERATIONS = {name: Operation}`` (default ``POINT_OPERATIONS``)
    Every operation the kind serves, in a fixed order; a traffic file's
    ``mix`` names them. What each is, how it is sent and how its
    arguments are drawn: ``Operation``.

``make_reference(config, table)`` (default ``reference.Reference``)
    The plain reference, built from the table alone: it imports nothing
    of the program and takes nothing the program made. Its
    ``check(outcome) -> checks`` answers every operation the kind serves
    and returns each number compared with its limit, ``{name: {"value":
    v, "limit": l}}``, under the guarantees the configuration states. It
    may have ``check_control(control, outcome) -> checks``, the
    calibration controls of ``run.py --control``.

``LEAST_BYTES = {operation: fn(ref, batch) -> int}`` (default: ``read``
counted by ``roofline.batch_bytes``)
    The least HBM bytes one dispatch of an operation must move, counted
    from the data by the reference ``ref``. ``batch`` is the batch the
    queue dispatched, for the operation sent through it, or the request's
    arguments ``{"key": keys[i:i+1], name: args[name][i:i+1], ...}`` for
    one called on a client thread. The sums over the traced part reach
    the readers by operation (``RunRecord.traced_bytes``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np

from bench import reference, roofline, ycsb

QUEUE = "queue"       # submitted to the cell's MicroBatchQueue
CLIENT = "client"     # called on a client thread of the operation's own


@dataclass
class Deployment:
    index_config: Any                 # the program's IndexConfig
    probe_fn: Callable                # the queue's search_fn
    compare_rank: bool                # reads answer searchsorted ranks
    write: Optional[Callable] = None  # write(keys[1], values[1]) -> None
    warm_folds: tuple = ()            # distinct keys per warm-up fold
    fold: Optional[Callable] = None   # fold every pending write, wait
    close: Callable = lambda: None


def _no_args(rng, n: int, n_records: int) -> dict:
    return {}


@dataclass(frozen=True)
class Operation:
    """One operation a kind serves. Every request asks for a record drawn
    from the mix's key distribution; ``keys[i]`` is its key and
    ``args[name][i]`` its other arguments.

    * ``read``: a read's answer is compared and its latency counts in
      ``read_*``; a write is acknowledged, and read back after the window.
    * ``via``: ``QUEUE``, submitted to the cell's queue (``rows``, the rows
      submitted; ``answer``, the request's sliced result on the host ->
      the answer kept), or ``CLIENT``, called on a client thread of its
      own (``call(deployment, keys, args, i)`` -> the answer kept). A kind
      sends at most one operation through the queue: it dispatches one
      function.
    * ``draw(rng, n, n_records) -> {name: array}``: the operation's
      arguments for every request of the window, mix or not. The
      generator calls each operation's in the kind's order, so a kind
      that adds an operation after the others leaves their draws alone.
    * ``written(keys, args, idx) -> (keys, values)``: what writes ``idx``
      write.

    The harness's span around a client call is ``bench.<name>``.
    """
    read: bool
    via: str
    draw: Callable = _no_args
    rows: Optional[Callable] = None
    answer: Optional[Callable] = None
    call: Optional[Callable] = None
    written: Optional[Callable] = None


def _point_rows(keys, args, i):
    return keys[i:i + 1]


def _point_answer(res) -> tuple:
    return bool(res.found[0]), int(res.values[0]), int(res.rank[0])


def _update_draw(rng, n: int, n_records: int) -> dict:
    return {"wval": ycsb.update_values(rng, n)}


def _update_call(dep: Deployment, keys, args, i) -> None:
    dep.write(keys[i:i + 1], args["wval"][i:i + 1])


def _update_written(keys, args, idx) -> tuple:
    return keys[idx], args["wval"][idx]


POINT_OPERATIONS = {
    # one key a submit; the answer is (found, value, rank)
    "read": Operation(read=True, via=QUEUE, rows=_point_rows,
                      answer=_point_answer),
    # one key a call to the store's insert, on the writer thread
    "update": Operation(read=False, via=CLIENT, draw=_update_draw,
                        call=_update_call, written=_update_written),
}


def _point_bytes(ref, batch) -> int:
    return roofline.batch_bytes(ref.keys, np.asarray(batch))


POINT_LEAST_BYTES = {"read": _point_bytes}


def point_table(config: dict, seed: int):
    return ycsb.Table(config["recordcount"], seed)


def point_reference(config: dict, table):
    return reference.Reference(table.keys, table.values)


@dataclass
class Outcome:
    """What a window did, as the reference checks it."""
    requests: Any            # workload.Requests
    keys: np.ndarray         # the key of each request's record
    log: Any                 # drive.Log: times, sent flags, answers
    writes: dict             # acknowledged writes, set-up's too, in order
    readback: dict           # every written key, read after the window
    compare_rank: bool       # the deployment's reads answer ranks

    def sent(self, op: str) -> np.ndarray:
        """Request numbers of operation ``op`` that were sent."""
        return np.nonzero(self.requests.of(op) & self.log.sent)[0]

    def answered(self, idx: np.ndarray) -> np.ndarray:
        return np.isfinite(self.log.t_done[idx])


@dataclass(frozen=True)
class Kind:
    """A kind module's hooks, each resolved to the module's or the
    default."""
    build: Callable
    make_table: Callable
    operations: dict
    make_reference: Callable
    least_bytes: dict


def kind_of(module) -> Kind:
    """The hooks of a kind module, defaults where it defines none."""
    kind = Kind(build=module.build,
                make_table=getattr(module, "make_table", point_table),
                operations=getattr(module, "OPERATIONS", POINT_OPERATIONS),
                make_reference=getattr(module, "make_reference",
                                       point_reference),
                least_bytes=getattr(module, "LEAST_BYTES",
                                    POINT_LEAST_BYTES))
    where = getattr(module, "__file__", repr(module))
    for name, op in kind.operations.items():
        ok = (op.via == QUEUE and op.rows and op.answer) or \
            (op.via == CLIENT and op.call)
        if not ok or (not op.read and not op.written):
            raise ValueError(f"{where}: operation {name!r} is incomplete")
    if sum(op.via == QUEUE for op in kind.operations.values()) > 1:
        raise ValueError(f"{where}: more than one operation via the queue")
    unknown = set(kind.least_bytes) - set(kind.operations)
    if unknown:
        raise ValueError(f"{where}: least bytes of operations it does not "
                         f"serve: {sorted(unknown)}")
    return kind
