"""CheckFree stage-merge kernel.

Computes ``out = ca * x + cb * y`` over arbitrarily-shaped stage parameter
buffers — Alg. 1 line 3 with the normalization folded into (ca, cb).  On TPU
this is HBM-bandwidth-bound (2 reads + 1 write per element); the kernel
streams (8, 1024)-element tiles through VMEM so the whole stage (hundreds of
MB) never needs to be resident.  The scalar weights ride along as a (1, 2)
SMEM-style operand block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# rows x lanes per VMEM tile: 8 sublanes x 1024 lanes = 32 KiB fp32
TILE_ROWS = 8
TILE_COLS = 1024


def _merge_kernel(w_ref, x_ref, y_ref, o_ref):
    ca = w_ref[0, 0]
    cb = w_ref[0, 1]
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    o_ref[...] = (ca * x + cb * y).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def stage_merge_flat(x: jnp.ndarray, y: jnp.ndarray, ca: jnp.ndarray,
                     cb: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """x, y: 2D (rows, TILE_COLS) with rows % TILE_ROWS == 0."""
    rows, cols = x.shape
    assert cols == TILE_COLS and rows % TILE_ROWS == 0, x.shape
    w = jnp.stack([ca, cb]).astype(jnp.float32).reshape(1, 2)
    grid = (rows // TILE_ROWS,)
    return pl.pallas_call(
        _merge_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2), lambda i: (0, 0)),          # weights
            pl.BlockSpec((TILE_ROWS, TILE_COLS), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, TILE_COLS), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_ROWS, TILE_COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        interpret=interpret,
    )(w, x, y)


def stage_merge(x: jnp.ndarray, y: jnp.ndarray, ca, cb, *,
                interpret: bool) -> jnp.ndarray:
    """Arbitrary-shape wrapper: flatten -> pad -> tile -> kernel -> unpad."""
    shape, dtype = x.shape, x.dtype
    n = x.size
    tile = TILE_ROWS * TILE_COLS
    pad = (-n) % tile
    xf = jnp.pad(x.reshape(-1), (0, pad)).reshape(-1, TILE_COLS)
    yf = jnp.pad(y.reshape(-1), (0, pad)).reshape(-1, TILE_COLS)
    out = stage_merge_flat(xf, yf, jnp.asarray(ca, jnp.float32),
                           jnp.asarray(cb, jnp.float32), interpret=interpret)
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)
