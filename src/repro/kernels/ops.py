"""Jit'd dispatch wrappers around the Pallas kernels.

The kernels are compiled for the TPU and interpreted everywhere else: the
choice follows the platform JAX runs on (:func:`interpret_default`), read
at call time, so no environment variable or caller has to know it.  The
compiled path is fully trainable: ``flash_attention`` carries a
recompute-based custom VJP (see ``kernels/flash_attention.py``), so
reverse-mode autodiff never needs the interpreter.

The wrappers also adapt the model-layer layouts ((B, S, H, D)) to the
kernel layouts ((B, H, S, D)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.ssd_scan import ssd_scan as _ssd
from repro.kernels.stage_merge import stage_merge as _merge


def interpret_default() -> bool:
    """Whether the kernels run in Pallas interpret mode: everywhere except
    on a TPU, where they are compiled."""
    return jax.default_backend() != "tpu"


def stage_merge(x: jnp.ndarray, y: jnp.ndarray, ca, cb) -> jnp.ndarray:
    return _merge(x, y, ca, cb, interpret=interpret_default())


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    blk_q: int = 128, blk_k: int = 128) -> jnp.ndarray:
    """Model layout (B, S, H, D) in/out."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, causal=causal, window=window, blk_q=blk_q,
                 blk_k=blk_k, interpret=interpret_default())
    return jnp.swapaxes(out, 1, 2)


def ssd_scan(x: jnp.ndarray, a: jnp.ndarray, bmat: jnp.ndarray,
             cmat: jnp.ndarray, *, chunk: int = 64) -> jnp.ndarray:
    """Model layout: x (B,T,H,P), a (B,T,H), bmat/cmat (B,T,G,N)."""
    xt = jnp.swapaxes(x, 1, 2)                # (B,H,T,P)
    at = jnp.swapaxes(a, 1, 2)                # (B,H,T)
    bt = jnp.swapaxes(bmat, 1, 2)             # (B,G,T,N)
    ct = jnp.swapaxes(cmat, 1, 2)
    out = _ssd(xt, at, bt, ct, chunk=chunk, interpret=interpret_default())
    return jnp.swapaxes(out, 1, 2)
