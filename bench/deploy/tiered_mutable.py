"""A durable, updatable primary-key index: ``build_index`` with
``IndexConfig(mutable=True, ...)`` as the configuration states, journaling
to a fresh directory of this run. Reads answer found flag and value (the
rank field is a slot address here and is not compared); updates go
through ``MutableIndex.insert``, one key a call.

Set-up warms the write path's device programs: each fold writes the pages
it touched back with one scatter padded to a power of two, so updates of
1024 and of 400 distinct keys, each folded at once, compile the 1024-
and 512-row scatters a window's folds of a full 1024-entry delta use."""
from __future__ import annotations

import os

import jax

from bench.deployment import Deployment

WARM_FOLDS = (1024, 400)     # distinct keys written, then folded


def build(config: dict, table, workdir: str) -> Deployment:
    from repro.core import IndexConfig, build_index
    from repro.engine.queue import index_probe_fn
    cfg = IndexConfig(**config["index_config"],
                      ckpt_dir=os.path.join(workdir, "journal"))
    index = build_index(table.keys, table.values, cfg)
    jax.block_until_ready((index.base.dev_keys, index.base.dev_vals))

    def fold():
        index.flush()
        jax.block_until_ready((index.base.dev_keys, index.base.dev_vals))

    return Deployment(index_config=cfg, probe_fn=index_probe_fn(index),
                      compare_rank=False, write=index.insert,
                      warm_folds=WARM_FOLDS, fold=fold, close=index.close)
