"""A run with the timed path broken underneath comes out not correct,
once for each fault a cell can have; the controls fail too. Runs the
whole harness on the CPU at a small size (``small.py``)."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, run
from bench.tests.small import run_small


@pytest.fixture
def broken(monkeypatch):
    """Plant ``fault(deployment) -> deployment`` under every build."""
    def plant(fault):
        real = run.deploy_kind

        def deploy_kind(kind, root=run.ROOT):
            mod = real(kind, root)

            class Broken:
                @staticmethod
                def build(config, table, workdir):
                    return fault(mod.build(config, table, workdir))
            return Broken
        monkeypatch.setattr(run, "deploy_kind", deploy_kind)
    return plant


def wrap_probe(dep, edit):
    probe = dep.probe_fn

    def broken_probe(q):
        res, occ = probe(q)
        return edit(q, res), occ
    return dataclasses.replace(dep, probe_fn=broken_probe)


def altered(q, res):
    """One answer of every batch altered where it is produced."""
    return dataclasses.replace(res, values=res.values.at[0].add(1))


def half_left_out(q, res):
    """The second half of every batch left out of the answer."""
    n = res.found.shape[0]
    keep = jnp.arange(n) < (n + 1) // 2
    return dataclasses.replace(res, found=res.found & keep,
                               values=jnp.where(keep, res.values, 0),
                               rank=jnp.where(keep, res.rank, 0))


@pytest.mark.parametrize("cell", ["ycsb_c.zipf", "ycsb_a.zipf"])
def test_sound_run_is_correct(cell):
    res, text = run_small(cell)
    assert res["correct"], text
    assert res["attempted"] == 150 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["checks"].values())


@pytest.mark.parametrize("cell,edit", [
    ("ycsb_c.zipf", altered), ("ycsb_c.zipf", half_left_out),
    ("ycsb_a.zipf", altered)])
def test_broken_answers_are_not_correct(broken, cell, edit):
    broken(lambda dep: wrap_probe(dep, edit))
    res, text = run_small(cell)
    assert not res["correct"], text
    assert res["checks"]["wrong_reads"]["value"] > 0


def test_write_that_leaves_the_state_unchanged_is_not_correct(broken):
    broken(lambda dep: dataclasses.replace(dep, write=lambda k, v: None))
    res, text = run_small("ycsb_a.zipf")
    assert not res["correct"], text
    assert res["checks"]["lost_writes"]["value"] > 0


def test_stale_control_is_not_correct():
    res, text = run_small("ycsb_a.zipf", control="stale")
    assert res["correct"], text
    line = [ln for ln in text.splitlines() if ln.startswith("control")][0]
    assert "correct=False" in line and "lost_writes=0 " not in line


def test_f32_control_is_not_correct_on_dense_keys():
    """Keys as dense as a 2^27-record table's (a gap of 16 in a domain
    of 2^31): float32 keys collide, the control's answers are wrong."""
    rng = np.random.default_rng(5)
    keys = (2**30 + 16 * rng.permutation(4096)).astype(np.int32)
    vals = np.arange(4096, dtype=np.int32)
    ref = reference.Reference(keys, vals)
    q = rng.choice(keys, 1000)
    reads = {"key": q, "answered": np.ones(q.size, bool),
             "t_send": np.zeros(q.size), "t_done": np.ones(q.size)}
    none = {"key": np.zeros(0, np.int32), "value": np.zeros(0, np.int32),
            "t_start": np.zeros(0), "t_ack": np.zeros(0)}
    rank, found, val = ref.lookup(q)
    exact = dict(reads, rank=rank, found=found, value=val)
    assert not reference.wrong_reads(ref, exact, none, True).any()
    rank, found, val = ref.control("f32", q)
    ctl = dict(reads, rank=rank, found=found, value=val)
    assert reference.wrong_reads(ref, ctl, none, True).sum() > 500
