"""A read-only primary-key index, bulk-loaded: ``build_index`` with the
configuration's ``IndexConfig`` (kind "tiered", not mutable). Reads
answer with the searchsorted rank too, and the rank is compared."""
from __future__ import annotations

import jax

from bench.deployment import Deployment


def build(config: dict, table, workdir: str) -> Deployment:
    from repro.core import IndexConfig, build_index
    from repro.engine.queue import index_probe_fn
    cfg = IndexConfig(**config["index_config"])
    index = build_index(table.keys, table.values, cfg)
    jax.block_until_ready((index.impl.pages, index.keys_sorted,
                           index.values_sorted))
    return Deployment(index_config=cfg, probe_fn=index_probe_fn(index),
                      compare_rank=True)
