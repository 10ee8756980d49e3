"""Layer-scan wrapper.

``jax.lax.scan`` keeps the compiled HLO O(1) in depth. Every family's layer
loop goes through ``scan`` so the device trace names it ``layer_scan``.
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax


@jax.named_scope("layer_scan")
def scan(f: Callable, init: Any, xs: Any) -> Tuple[Any, Any]:
    """``jax.lax.scan(f, init, xs)`` under the ``layer_scan`` scope."""
    return jax.lax.scan(f, init, xs)
