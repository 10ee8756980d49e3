"""Per-kernel correctness sweeps: Pallas (interpret=True) vs pure-jnp oracle.

Every kernel is swept over shapes and dtypes and asserted allclose against
``repro.kernels.ref`` (the definitional semantics).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as FA
from repro.kernels import ref as R
from repro.kernels import ssd_scan as SSD
from repro.kernels import stage_merge as SM

# the oracles check the kernels' semantics in interpret mode (the compiled
# TPU lowering is checked by tests/test_tpu_compile.py)
flash_attention = functools.partial(FA.flash_attention, interpret=True)
ssd_scan = functools.partial(SSD.ssd_scan, interpret=True)
stage_merge = functools.partial(SM.stage_merge, interpret=True)


def test_interpret_mode_follows_the_platform():
    """Compiled on a TPU, interpreted everywhere else; never from an
    environment variable."""
    from repro.kernels.ops import interpret_default
    assert interpret_default() is (jax.default_backend() != "tpu")

TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


def rand(key, shape, dtype, scale=1.0):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# stage_merge
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(5,), (8, 1024), (3, 65, 33), (8193,),
                                   (2, 4, 8, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stage_merge_sweep(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = rand(k1, shape, dtype)
    y = rand(k2, shape, dtype)
    got = stage_merge(x, y, 0.25, 0.75)
    want = R.stage_merge_ref(x, y, 0.25, 0.75)
    assert got.shape == shape and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("ca,cb", [(1.0, 0.0), (0.0, 1.0), (0.5, 0.5),
                                   (0.9999, 0.0001)])
def test_stage_merge_weight_extremes(ca, cb):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = rand(k1, (4, 130), jnp.float32)
    y = rand(k2, (4, 130), jnp.float32)
    got = stage_merge(x, y, ca, cb)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ca * x + cb * y), atol=1e-6)


def test_stage_merge_convexity():
    """A convex combination is bounded by the elementwise min/max."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    x = rand(k1, (64, 64), jnp.float32)
    y = rand(k2, (64, 64), jnp.float32)
    got = np.asarray(stage_merge(x, y, 0.3, 0.7))
    lo = np.minimum(np.asarray(x), np.asarray(y)) - 1e-6
    hi = np.maximum(np.asarray(x), np.asarray(y)) + 1e-6
    assert (got >= lo).all() and (got <= hi).all()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,blk", [(64, 32), (128, 64), (256, 128)])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (4, 1)])
def test_flash_attention_causal_gqa(s, blk, hq, hkv):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    d = 32
    q = rand(ks[0], (1, hq, s, d), jnp.float32)
    k = rand(ks[1], (1, hkv, s, d), jnp.float32)
    v = rand(ks[2], (1, hkv, s, d), jnp.float32)
    got = flash_attention(q, k, v, causal=True, blk_q=blk, blk_k=blk)
    want = R.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **TOL[jnp.float32])


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    s, h, d = 128, 2, 32
    q = rand(ks[0], (2, h, s, d), jnp.float32)
    k = rand(ks[1], (2, h, s, d), jnp.float32)
    v = rand(ks[2], (2, h, s, d), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window,
                          blk_q=32, blk_k=32)
    want = R.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **TOL[jnp.float32])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    s, d = 64, 64
    q = rand(ks[0], (1, 2, s, d), dtype)
    k = rand(ks[1], (1, 2, s, d), dtype)
    v = rand(ks[2], (1, 2, s, d), dtype)
    got = flash_attention(q, k, v, causal=True, blk_q=32, blk_k=32)
    want = R.flash_attention_ref(q, k, v, causal=True)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_flash_attention_non_causal():
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    s, d = 64, 32
    q = rand(ks[0], (1, 2, s, d), jnp.float32)
    k = rand(ks[1], (1, 2, s, d), jnp.float32)
    v = rand(ks[2], (1, 2, s, d), jnp.float32)
    got = flash_attention(q, k, v, causal=False, blk_q=32, blk_k=32)
    want = R.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **TOL[jnp.float32])


# ---------------------------------------------------------------------------
# flash attention custom VJP (recompute-based backward kernels)
# ---------------------------------------------------------------------------

def _grad_pair(q, k, v, w, *, causal, window, blk):
    """(custom-VJP grads, oracle grads) of sum(attn * w) wrt (q, k, v)."""
    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, window=window,
                                       blk_q=blk, blk_k=blk) * w)

    def fr(q, k, v):
        return jnp.sum(R.flash_attention_ref(q, k, v, causal=causal,
                                             window=window) * w)

    return (jax.grad(f, argnums=(0, 1, 2))(q, k, v),
            jax.grad(fr, argnums=(0, 1, 2))(q, k, v))


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (4, 1)])
def test_flash_attention_vjp_causal_gqa(hq, hkv):
    ks = jax.random.split(jax.random.PRNGKey(10), 4)
    s, d = 64, 32
    q = rand(ks[0], (2, hq, s, d), jnp.float32)
    k = rand(ks[1], (2, hkv, s, d), jnp.float32)
    v = rand(ks[2], (2, hkv, s, d), jnp.float32)
    w = rand(ks[3], (2, hq, s, d), jnp.float32)
    got, want = _grad_pair(q, k, v, w, causal=True, window=0, blk=32)
    for g1, g2, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name} hq={hq} hkv={hkv}")


@pytest.mark.parametrize("window", [16, 48, 100])
def test_flash_attention_vjp_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    s, h, d = 128, 2, 32
    q = rand(ks[0], (1, h, s, d), jnp.float32)
    k = rand(ks[1], (1, h, s, d), jnp.float32)
    v = rand(ks[2], (1, h, s, d), jnp.float32)
    w = rand(ks[3], (1, h, s, d), jnp.float32)
    got, want = _grad_pair(q, k, v, w, causal=True, window=window, blk=32)
    for g1, g2, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name} window={window}")


def test_flash_attention_vjp_non_causal():
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    s, d = 64, 32
    q = rand(ks[0], (1, 2, s, d), jnp.float32)
    k = rand(ks[1], (1, 2, s, d), jnp.float32)
    v = rand(ks[2], (1, 2, s, d), jnp.float32)
    w = rand(ks[3], (1, 2, s, d), jnp.float32)
    got, want = _grad_pair(q, k, v, w, causal=False, window=0, blk=32)
    for g1, g2, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=2e-4, rtol=2e-4, err_msg=f"d{name}")


def test_flash_attention_vjp_dtype_preserved():
    """Gradients come back in the input dtype (bf16 in, bf16 grads out)."""
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    s, d = 64, 32
    q = rand(ks[0], (1, 2, s, d), jnp.bfloat16)
    k = rand(ks[1], (1, 2, s, d), jnp.bfloat16)
    v = rand(ks[2], (1, 2, s, d), jnp.bfloat16)

    def f(q, k, v):
        out = flash_attention(q, k, v, causal=True, blk_q=32, blk_k=32)
        return jnp.sum(out.astype(jnp.float32))

    gq, gk, gv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    assert gq.dtype == gk.dtype == gv.dtype == jnp.bfloat16
    assert all(np.isfinite(np.asarray(g, np.float32)).all()
               for g in (gq, gk, gv))


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,chunk", [(64, 16), (64, 64), (128, 32)])
@pytest.mark.parametrize("h,g", [(2, 1), (4, 2)])
def test_ssd_scan_sweep(t, chunk, h, g):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    b, p, n = 2, 16, 8
    x = rand(ks[0], (b, h, t, p), jnp.float32, 0.5)
    a = -jnp.abs(rand(ks[1], (b, h, t), jnp.float32)) * 0.1
    bm = rand(ks[2], (b, g, t, n), jnp.float32, 0.4)
    cm = rand(ks[3], (b, g, t, n), jnp.float32, 0.4)
    got = ssd_scan(x, a, bm, cm, chunk=chunk)
    want = R.ssd_scan_ref(x, a, bm, cm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_ssd_scan_state_carry_matters():
    """Zeroing the carried state across chunks must change the output —
    guards against a kernel that silently re-inits the VMEM scratch."""
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    b, h, t, p, g, n = 1, 1, 64, 8, 1, 4
    x = rand(ks[0], (b, h, t, p), jnp.float32, 0.5)
    a = -jnp.abs(rand(ks[1], (b, h, t), jnp.float32)) * 0.05
    bm = rand(ks[2], (b, g, t, n), jnp.float32, 0.4)
    cm = rand(ks[3], (b, g, t, n), jnp.float32, 0.4)
    full = ssd_scan(x, a, bm, cm, chunk=16)
    # per-chunk independent scans == dropping the inter-chunk term
    parts = [ssd_scan(x[:, :, i:i + 16], a[:, :, i:i + 16],
                      bm[:, :, i:i + 16], cm[:, :, i:i + 16], chunk=16)
             for i in range(0, t, 16)]
    chopped = jnp.concatenate(parts, axis=2)
    assert float(jnp.abs(full - chopped).max()) > 1e-3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_dtypes(dtype):
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    b, h, t, p, g, n = 1, 2, 64, 8, 1, 4
    x = rand(ks[0], (b, h, t, p), dtype, 0.5)
    a = (-jnp.abs(rand(ks[1], (b, h, t), jnp.float32)) * 0.1).astype(dtype)
    bm = rand(ks[2], (b, g, t, n), dtype, 0.4)
    cm = rand(ks[3], (b, g, t, n), dtype, 0.4)
    got = ssd_scan(x, a, bm, cm, chunk=32)
    want = R.ssd_scan_ref(x, a, bm, cm)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])
