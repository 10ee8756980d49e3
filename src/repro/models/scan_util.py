"""Layer-scan wrapper.

``jax.lax.scan`` keeps the compiled HLO O(1) in depth (what you want for
training/serving), but XLA's ``cost_analysis`` counts a ``while``-loop body
ONCE — which would understate FLOPs / bytes / collective traffic by a factor
of num_layers in the roofline analysis.  The dry-run therefore sets
``REPRO_UNROLL_SCAN=1`` to unroll layer scans into straight-line HLO so every
layer's compute and every per-layer collective is visible to the analysis.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp


def unrolling() -> bool:
    return os.environ.get("REPRO_UNROLL_SCAN", "0") == "1"


@jax.named_scope("layer_scan")
def scan(f: Callable, init: Any, xs: Any) -> Tuple[Any, Any]:
    """Drop-in for ``jax.lax.scan(f, init, xs)`` honouring the unroll flag."""
    if not unrolling():
        return jax.lax.scan(f, init, xs)
    leaves = jax.tree.leaves(xs)
    assert leaves, "unrolled scan needs xs"
    length = leaves[0].shape[0]
    carry = init
    ys = []
    for i in range(length):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = f(carry, x_i)
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    return carry, stacked
