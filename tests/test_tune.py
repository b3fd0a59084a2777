"""Autotuner acceptance (DESIGN.md §10): trials read their objective
from the metrics registry (never a parallel timing harness), the winning
profile persists as a platform config, and ``IndexConfig.from_tuned``
round-trips it — including the module-global plan thresholds."""
import json
import os

import numpy as np
import pytest

from repro.core import IndexConfig, build_index
from repro.engine import schedule
from repro.tune import (TunedProfile, autotune, load_profile, platform_key,
                        profile_path, run_trial, save_profile,
                        verify_profile)


@pytest.fixture(autouse=True)
def _restore_thresholds():
    prev = schedule.set_plan_thresholds()
    yield
    schedule.set_plan_thresholds(**prev)


def _profile(**knobs):
    base = {"tile": 256, "leaf_width": 512, "histogram_max_pages": 16,
            "queue_min_flush": 128, "queue_deadline_s": 0.001,
            "specialize": True}
    base.update(knobs)
    return TunedProfile(platform="testplat", backend="cpu",
                        device_kind="fake", knobs=base,
                        objective={"lookup": {"p50": 1e-4, "p99": 2e-4,
                                              "mean": 1.2e-4, "count": 8}})


def test_profile_round_trip(tmp_path):
    prof = _profile()
    path = save_profile(prof, str(tmp_path))
    assert path == profile_path("testplat", str(tmp_path))
    with open(path) as f:
        assert json.load(f)["version"] == prof.version
    got = load_profile("testplat", str(tmp_path))
    assert got.knobs == prof.knobs
    assert got.objective == prof.objective


def test_from_tuned_maps_knobs_and_thresholds(tmp_path):
    save_profile(_profile(), str(tmp_path))
    cfg = IndexConfig.from_tuned("testplat", profile_dir=str(tmp_path))
    assert cfg.kind == "tiered"
    assert cfg.tile == 256 and cfg.leaf_width == 512
    assert cfg.specialize is True
    assert cfg.queue_min_flush == 128
    assert cfg.queue_deadline_s == pytest.approx(0.001)
    # histogram_max_pages is a module-global plan threshold, applied as a
    # side effect, not a config field
    assert schedule.HISTOGRAM_MAX_PAGES == 16
    # overrides win over the profile
    cfg2 = IndexConfig.from_tuned("testplat", profile_dir=str(tmp_path),
                                  tile=128, mutable=True)
    assert cfg2.tile == 128 and cfg2.mutable is True


def test_from_tuned_missing_profile_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="autotune"):
        IndexConfig.from_tuned("absent", profile_dir=str(tmp_path))


def test_newer_profile_version_rejected(tmp_path):
    prof = _profile()
    prof.version = 99
    path = save_profile(prof, str(tmp_path))
    assert os.path.exists(path)
    with pytest.raises(ValueError, match="newer"):
        load_profile("testplat", str(tmp_path))


def test_platform_key_sanitizes():
    assert platform_key("NVIDIA A100-SXM!") == "nvidia_a100_sxm"
    assert platform_key() in ("cpu", "gpu", "tpu")


def test_run_trial_objective_comes_from_registry():
    t = run_trial({"tile": 128, "leaf_width": None,
                   "histogram_max_pages": 32, "queue_min_flush": 32,
                   "queue_deadline_s": 1e-3}, n=2000, q_n=256, reps=2)
    obj = t["objective"]
    for path in ("lookup", "scan", "flush"):
        assert obj[path]["count"] > 0, path
        assert obj[path]["p50"] > 0.0
        assert obj[path]["p99"] >= obj[path]["p50"]
        assert obj[path]["mean"] > 0.0
    assert t["score"][0] > 0.0
    # the trial ran under its own registry: the process registry did not
    # absorb the trial's lookups
    assert "dispatch_staging_seconds" in t["registry"]


def test_autotune_smoke_persists_and_verifies(tmp_path):
    prof, path = autotune(smoke=True, n=2000, q_n=256, reps=2,
                          platform="smoketest",
                          profile_dir=str(tmp_path))
    assert os.path.exists(path)
    assert prof.knobs["specialize"] is True
    assert len(prof.trials) == 3            # 2-point stage A + 1-point B
    # the persisted profile loads through the public entry point and
    # builds a working index
    cfg = IndexConfig.from_tuned("smoketest", profile_dir=str(tmp_path),
                                 mutable=True)
    keys = np.sort(np.random.RandomState(0).choice(
        1 << 16, 500, replace=False)).astype(np.int32)
    idx = build_index(keys, None, cfg)
    res = idx.lookup(keys[:32])
    assert bool(np.asarray(res.found).all())
    idx.close()
    v = verify_profile(prof, profile_dir=str(tmp_path), n=2000, q_n=256,
                       reps=2)
    assert set(v) >= {"ok", "fresh_p50", "recorded_p50"}
    assert v["fresh_p50"] > 0.0
