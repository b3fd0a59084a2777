"""What a deployment kind's ``build`` (``bench/deploy/<kind>.py``) hands
the harness: the system under test, as the served path uses it."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional


@dataclass
class Deployment:
    index_config: Any                 # the program's IndexConfig
    probe_fn: Callable                # the queue's search_fn
    compare_rank: bool                # reads answer searchsorted ranks
    write: Optional[Callable] = None  # write(keys[1], values[1]) -> None
    warm_folds: tuple = ()            # distinct keys per warm-up fold
    fold: Optional[Callable] = None   # fold every pending write, wait
    close: Callable = lambda: None
