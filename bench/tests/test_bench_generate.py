"""The generator draws the YCSB point mixes exactly as it did before the
deployment kinds declared their operations: same stream, same order, same
due times. Digests of (is_read, record, update values, due) at the cells'
size (2^27 records, 51 s), taken from the generator as it was then."""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench import workload

ROOT = Path(__file__).resolve().parents[2]
DIGESTS = {
    ("ycsb_c.zipf", 7):
        "26ec979d6290f1c827ec92eed0dada5b89beba9d32b056f3856e435f6209d661",
    ("ycsb_c.zipf", 3000001401):
        "956171e8b0d3599f98934f02db23c891c1f879a808af511bca8bb9dc8669aa38",
    ("ycsb_c.zipf", 2**33 + 17):
        "c49702fc5d57f24369b7bc7a796dab49f7218b522a77c4066f25fff5f4a7334c",
    ("ycsb_a.zipf", 7):
        "9bb0abe31e304a4137c405fef2a2f447d08640eedeaffc6d3bd7a9889c8f6e87",
    ("ycsb_a.zipf", 3000001401):
        "56ba1c8b0fe8a949ed80fb89e3ad5c448b1c90920c599b4fc8d69991f84a23a6",
    ("ycsb_a.zipf", 2**33 + 17):
        "593bb84dec8c5f4023a21b2fdc9241f1fd9c2a601182a95ced1ce4cd16aaa45f",
}


@pytest.mark.parametrize("mix,seed", sorted(DIGESTS))
def test_point_mixes_draw_as_before(mix, seed):
    spec = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json")
                      .read_text())
    r = workload.generate(spec, 2**27, seed, 51)
    arrays = (r.is_read, r.record, r.args["wval"], r.due)
    assert [a.dtype for a in arrays] == [np.dtype(bool), np.dtype(np.int64),
                                         np.dtype(np.int32),
                                         np.dtype(np.float64)]
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    assert h.hexdigest() == DIGESTS[(mix, seed)]


def test_mix_shares_must_sum_to_one():
    spec = {"mix": {"read": 0.9}, "keys": {"distribution": "uniform"},
            "arrival": {"process": "poisson", "rate_per_s": 10}}
    with pytest.raises(ValueError, match="sum to 1"):
        workload.generate(spec, 4096, 1, 1)
