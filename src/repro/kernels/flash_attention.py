"""Flash attention (block-tiled online-softmax) Pallas kernel, with a
recompute-based custom VJP so the *compiled* path is trainable.

TPU-native tiling: the query tile (blk_q, D) and one K/V tile (blk_k, D) are
resident in VMEM; the kernel walks K/V tiles with dynamic loop bounds so a
causal / sliding-window query block only touches the tiles inside its
horizon (this is where the sub-quadratic ``long_500k`` support comes from).
GQA is folded into the BlockSpec index map (q head -> kv head = h // group).

Autodiff: ``pl.pallas_call`` has no reverse-mode rule when compiled, so the
public :func:`flash_attention` carries a :func:`jax.custom_vjp`.  The
forward kernel additionally emits the per-row logsumexp (``lse``); the
backward recomputes the (blk_q, blk_k) probability tiles from (q, k, lse)
instead of materializing the S x S matrix — two kernels, one tiled over
query blocks (dq) and one over key/value blocks (dk/dv, accumulating the
whole GQA group of query heads for its kv head).  This is the standard
FlashAttention-2 backward decomposition:

    P_ij  = exp(q_i . k_j * scale - lse_i)
    dV_j  = sum_i P_ij dO_i
    dS_ij = P_ij (dO_i . V_j - D_i),   D_i = dO_i . O_i
    dQ_i  = scale * sum_j dS_ij K_j
    dK_j  = scale * sum_i dS_ij Q_i

Layout: q (B, Hq, S, D); k/v (B, Hkv, S, D); output (B, Hq, S, D).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _tile(ref, h: int, j, blk: int) -> jnp.ndarray:
    """Rows ``[j * blk, (j + 1) * blk)`` of head ``h`` of a (1, H, S, X)
    block, as float32.  The slice is taken on the ref (a dynamic window of
    the VMEM block), which the TPU lowering supports; slicing a loaded
    value with a dynamic start it does not."""
    start = pl.multiple_of(j * blk, blk)
    return ref[0, h, pl.ds(start, blk), :].astype(jnp.float32)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, blk_k: int,
                  causal: bool, window: int, scale: float, seq_len: int):
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (blk_q, D)
    blk_q, d = q.shape
    q_pos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, 1), 0)

    nkb = seq_len // blk_k
    if causal:
        # last K tile that any query in this block can see
        hi = jnp.minimum(((iq + 1) * blk_q + blk_k - 1) // blk_k, nkb)
    else:
        hi = nkb
    if window > 0:
        lo = jnp.maximum((iq * blk_q - window + 1) // blk_k, 0)
    else:
        lo = 0

    def body(j, carry):
        m, l, acc = carry
        kj = _tile(k_ref, 0, j, blk_k)                   # (blk_k, D)
        vj = _tile(v_ref, 0, j, blk_k)
        s = q @ kj.T                                     # (blk_q, blk_k)
        k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)
        mask = jnp.ones_like(s, dtype=bool)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window > 0:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ vj
        return m_new, l_new, acc_new

    m0 = jnp.full((blk_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((blk_q,), jnp.float32)
    acc0 = jnp.zeros((blk_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / (l[:, None] + 1e-30)).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l + 1e-30))[:, None]


def _fwd_call(q, k, v, causal, window, blk_q, blk_k, interpret):
    """pallas_call of the forward kernel -> (out, lse)."""
    b, hq, s, d = q.shape
    g = hq // k.shape[1]
    scale = 1.0 / math.sqrt(d)
    grid = (b, hq, s // blk_q)
    kernel = functools.partial(_flash_kernel, blk_k=blk_k, causal=causal,
                               window=window, scale=scale, seq_len=s)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi // g, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, blk_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b, hq, s, 1), jnp.float32)],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   blk_k: int, causal: bool, window: int, scale: float,
                   seq_len: int):
    """dQ for one query block: walk the K/V tiles inside its horizon."""
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (blk_q, D)
    do = do_ref[0, 0].astype(jnp.float32)                # (blk_q, D)
    lse = lse_ref[0, 0]                                  # (blk_q, 1)
    delta = delta_ref[0, 0]                              # (blk_q, 1)
    blk_q, d = q.shape
    q_pos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, 1), 0)

    nkb = seq_len // blk_k
    if causal:
        hi = jnp.minimum(((iq + 1) * blk_q + blk_k - 1) // blk_k, nkb)
    else:
        hi = nkb
    if window > 0:
        lo = jnp.maximum((iq * blk_q - window + 1) // blk_k, 0)
    else:
        lo = 0

    def body(j, acc):
        kj = _tile(k_ref, 0, j, blk_k)                   # (blk_k, D)
        vj = _tile(v_ref, 0, j, blk_k)
        s = q @ kj.T                                     # (blk_q, blk_k)
        k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)
        mask = jnp.ones_like(s, dtype=bool)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window > 0:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                             # masked -> 0
        dp = do @ vj.T                                   # (blk_q, blk_k)
        ds = p * (dp - delta)
        return acc + ds @ kj

    acc0 = jnp.zeros((blk_q, d), jnp.float32)
    acc = jax.lax.fori_loop(lo, hi, body, acc0)
    dq_ref[0, 0] = (acc * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, blk_q: int, causal: bool, window: int,
                    scale: float, seq_len: int, group: int):
    """dK/dV for one K/V block of one *kv* head: walk the query tiles of
    every q head in the GQA group that can see this block."""
    ik = pl.program_id(2)
    kb = k_ref[0, 0].astype(jnp.float32)                 # (blk_k, D)
    vb = v_ref[0, 0].astype(jnp.float32)
    blk_k, d = kb.shape
    k_pos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)

    nqb = seq_len // blk_q
    if causal:
        # queries strictly before this block's first key see none of it
        lo = (ik * blk_k) // blk_q
    else:
        lo = 0
    if window > 0:
        # q_pos < k_pos + window bounds the last contributing query tile
        hi = jnp.minimum(((ik + 1) * blk_k + window - 2) // blk_q + 1, nqb)
    else:
        hi = nqb

    dk = jnp.zeros((blk_k, d), jnp.float32)
    dv = jnp.zeros((blk_k, d), jnp.float32)
    for h in range(group):                               # static GQA group
        def body(i, carry, h=h):
            dk_acc, dv_acc = carry
            qi = _tile(q_ref, h, i, blk_q) * scale       # (blk_q, D)
            doi = _tile(do_ref, h, i, blk_q)
            lsei = _tile(lse_ref, h, i, blk_q)           # (blk_q, 1)
            deltai = _tile(delta_ref, h, i, blk_q)
            q_pos = i * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, 1), 0)
            s = qi @ kb.T                                # (blk_q, blk_k)
            mask = jnp.ones_like(s, dtype=bool)
            if causal:
                mask = mask & (k_pos <= q_pos)
            if window > 0:
                mask = mask & (k_pos > q_pos - window)
            s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lsei)                        # masked -> 0
            dv_acc = dv_acc + p.T @ doi
            dp = doi @ vb.T
            ds = p * (dp - deltai)
            dk_acc = dk_acc + ds.T @ qi                  # qi carries `scale`
            return dk_acc, dv_acc

        dk, dv = jax.lax.fori_loop(lo, hi, body, (dk, dv))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd_call(q, k, v, o, lse, do, causal, window, blk_q, blk_k, interpret):
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                       # (B, Hq, S, 1)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, blk_k=blk_k, causal=causal,
                          window=window, scale=scale, seq_len=s),
        grid=(b, hq, s // blk_q),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi // g, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi // g, 0, 0)),
            pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, blk_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, blk_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # grid over *kv* heads: each program owns one K/V block and sums the
    # contributions of its whole query-head group (block size g on axis 1)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, blk_q=blk_q, causal=causal,
                          window=window, scale=scale, seq_len=s, group=g),
        grid=(b, hkv, s // blk_k),
        in_specs=[
            pl.BlockSpec((1, g, s, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, blk_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, g, s, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, g, s, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, g, s, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, hkv, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b, hkv, s, d), v.dtype)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-VJP wiring
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, blk_q, blk_k, interpret):
    out, _ = _fwd_call(q, k, v, causal, window, blk_q, blk_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, window, blk_q, blk_k, interpret):
    out, lse = _fwd_call(q, k, v, causal, window, blk_q, blk_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, blk_q, blk_k, interpret, res, g):
    q, k, v, out, lse = res
    return _bwd_call(q, k, v, out, lse, g, causal, window, blk_q, blk_k,
                     interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "blk_q",
                                             "blk_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0, blk_q: int = 128,
                    blk_k: int = 128, interpret: bool) -> jnp.ndarray:
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D) with Hq % Hkv == 0."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, s)
    assert s % blk_q == 0 and s % blk_k == 0, (s, blk_q, blk_k)
    return _flash(q, k, v, causal, window, blk_q, blk_k, interpret)
