"""Mamba2 SSD chunked-scan Pallas kernel.

Grid (B, H, num_chunks) with the chunk dimension sequential ("arbitrary"):
a per-(batch, head) SSM state tile (P, N) lives in VMEM scratch and is
carried across chunk steps.  Each step computes the intra-chunk quadratic
term on the MXU, adds the inter-chunk contribution from the carried state,
and updates the state — the TPU-native shape of the SSD recurrence (compare
``repro.models.ssm.ssd_chunked``, the pure-jnp oracle).

Layouts: x (B, H, T, P) dt-weighted; a (B, H, T) log-decay; b/c (B, G, T, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, o_ref, state_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)                  # (Q, P)
    a = a_ref[0, 0].astype(jnp.float32)                  # (Q, 1)
    bm = b_ref[0, 0].astype(jnp.float32)                 # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)                 # (Q, N)
    q, p = x.shape
    n = bm.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = row >= col                                     # j <= i

    # inclusive cumulative log-decay cs as exact-precision matmuls (the TPU
    # lowering has no in-kernel cumsum, nor a broadcast of a (1, 1) value):
    # cs_i[i, j] = cs_i, cs_j[i, j] = cs_j, total[.., ..] = cs_{Q-1}
    hi = jax.lax.Precision.HIGHEST
    a_cols = jnp.broadcast_to(a, (q, q))                 # [k, j] = a_k
    cs_i = jnp.dot(tri.astype(jnp.float32), a_cols, precision=hi)
    cs_j = jnp.dot(jnp.ones((q, q), jnp.float32),
                   jnp.where(row <= col, a_cols, 0.0), precision=hi)
    total = jnp.dot(jnp.ones((p, q), jnp.float32),
                    jnp.broadcast_to(a, (q, n)), precision=hi)  # (P, N)
    cs = cs_i[:, :1]                                     # (Q, 1)
    cs_last = cs_j[:, q - 1:]                            # (Q, 1): cs_{Q-1}

    # intra-chunk: att[i,j] = (C_i . B_j) * exp(cs_i - cs_j), j <= i
    att = cm @ bm.T
    att = jnp.where(tri, att * jnp.exp(cs_i - cs_j), 0.0)
    y = att @ x                                          # (Q, P)

    # inter-chunk: contribution of the carried state
    state = state_ref[...]                               # (P, N)
    y = y + jnp.exp(cs) * (cm @ state.T)

    # state update: S <- S * exp(cs_Q) + sum_j exp(cs_Q - cs_j) x_j B_j^T
    w = jnp.exp(cs_last - cs)                            # (Q, 1)
    state_ref[...] = state * jnp.exp(total) + (x * w).T @ bm
    o_ref[0, 0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, a: jnp.ndarray, bmat: jnp.ndarray,
             cmat: jnp.ndarray, *, chunk: int = 64,
             interpret: bool) -> jnp.ndarray:
    """x: (B, H, T, P); a: (B, H, T); bmat/cmat: (B, G, T, N); H % G == 0."""
    b, h, t, p = x.shape
    g, n = bmat.shape[1], bmat.shape[3]
    assert h % g == 0 and t % chunk == 0, (h, g, t, chunk)
    r = h // g
    grid = (b, h, t // chunk)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            # the decays ride as a (chunk, 1) column: a (1, 1, chunk) block
            # of a (B, H, T) array is not a legal TPU tile
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, hi // r, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, hi // r, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, t, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, a[..., None], bmat, cmat)
