"""The one generator every traffic mix goes through.

A traffic file under ``bench/traffic/`` holds the churn as data:
``fail_every`` (a stage fails every that many wall steps; 0 for none) and
``rotation`` (which stage fails, in turn).  The token batches come from
``--seed`` alone; the failures never do, so every seed sees the same
failures at the same steps and the same window sizes around them.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Sequence

import numpy as np


class PeriodicFailures:
    """Failure schedule for the trainer: ``at(step)`` lists the stages that
    fail at the boundary before wall step ``step``.  Failure ``n`` (from 1)
    strikes at wall step ``n * fail_every`` and takes stage
    ``rotation[(n - 1) % len(rotation)]``."""

    def __init__(self, fail_every: int, rotation: Sequence[int] = ()):
        if fail_every < 0 or (fail_every and not rotation):
            raise ValueError(f"fail_every={fail_every} needs a rotation")
        self.fail_every = int(fail_every)
        self.rotation = [int(s) for s in rotation]

    def at(self, step: int) -> List[int]:
        if not self.fail_every or step <= 0 or step % self.fail_every:
            return []
        n = step // self.fail_every
        return [self.rotation[(n - 1) % len(self.rotation)]]


def schedule(traffic: Dict) -> PeriodicFailures:
    return PeriodicFailures(traffic.get("fail_every", 0),
                            traffic.get("rotation", ()))


def token_batches(seed: int, batch: int, seq: int, vocab: int,
                  ) -> Iterator[Dict[str, np.ndarray]]:
    """Endless (batch, seq) next-token batches of token ids uniform over
    the vocabulary, drawn from ``seed``: every row of every step differs."""
    rng = np.random.default_rng(seed)
    while True:
        raw = rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)
        yield {"tokens": raw[:, :-1], "labels": raw[:, 1:]}
