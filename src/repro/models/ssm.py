"""Mamba2 / SSD (state-space duality) family.

Implements the chunked SSD algorithm (Dao & Gu, 2024) in pure JAX:
intra-chunk quadratic ("attention-like") term + inter-chunk state recurrence
via ``lax.scan``.  Decode runs the exact recurrent update against a
(state, conv-tail) cache.  The per-chunk scan body is the compute hot-spot
mirrored by the ``kernels/ssd_scan`` Pallas kernel.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models.scan_util import scan as layer_scan

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# depthwise causal conv1d
# ---------------------------------------------------------------------------

def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """x: (B, L, C); w: (K, C) depthwise taps; b: (C,)."""
    k = w.shape[0]
    ln = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + ln, :] * w[i][None, None, :] for i in range(k))
    return y + b[None, None, :]


def conv1d_decode(x_new: jnp.ndarray, state: jnp.ndarray, w: jnp.ndarray,
                  b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x_new: (B, C); state: (B, K-1, C) last K-1 inputs (oldest first)."""
    k = w.shape[0]
    y = x_new * w[k - 1][None, :]
    for i in range(k - 1):
        y = y + state[:, i, :] * w[i][None, :]
    new_state = jnp.concatenate([state[:, 1:, :], x_new[:, None, :]], axis=1)
    return y + b[None, :], new_state


# ---------------------------------------------------------------------------
# chunked SSD
# ---------------------------------------------------------------------------

@jax.named_scope("ssd_scan")
def ssd_chunked(xb: jnp.ndarray, a: jnp.ndarray, bmat: jnp.ndarray,
                cmat: jnp.ndarray, chunk: int,
                init_state: Optional[jnp.ndarray] = None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked state-space-duality scan.

    xb:   (B, T, H, P)  dt-weighted inputs
    a:    (B, T, H)     per-token log decay (dt * A, A < 0)
    bmat: (B, T, G, N)  input projections (grouped)
    cmat: (B, T, G, N)  output projections (grouped)
    Returns (y (B, T, H, P), final_state (B, H, P, N)).
    """
    b, t, h, p = xb.shape
    g, n = bmat.shape[2], bmat.shape[3]
    r = h // g
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk

    xc = xb.reshape(b, nc, chunk, h, p)
    ac = a.reshape(b, nc, chunk, h).astype(jnp.float32)
    bc = bmat.reshape(b, nc, chunk, g, n)
    cc = cmat.reshape(b, nc, chunk, g, n)

    cs = jnp.cumsum(ac, axis=2)                              # (b,nc,q,h) incl.
    # ---- intra-chunk quadratic term -------------------------------------
    cb = jnp.einsum("bcqgn,bckgn->bcgqk", cc.astype(jnp.float32),
                    bc.astype(jnp.float32))                  # (b,nc,g,q,k)
    cbh = jnp.repeat(cb, r, axis=2)                          # heads (b,nc,h,q,k)
    csh = jnp.moveaxis(cs, 3, 2)                             # (b,nc,h,q)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    # segment sums masked before the exp: above the diagonal they are
    # positive and reach (chunk-1)*dt*|A|, whose exp overflows float32, and
    # a mask after the exp would multiply that inf by a zero cotangent in
    # the backward; exp(-inf) is exactly 0, with a zero derivative
    seg = jnp.where(mask[None, None, None],
                    csh[..., :, None] - csh[..., None, :], -jnp.inf)
    att = cbh * jnp.exp(seg)                                 # (b,nc,h,q,k)
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", att,
                         xc.astype(jnp.float32))

    # ---- per-chunk states -------------------------------------------------
    w_end = jnp.exp(cs[:, :, -1:, :] - cs)                   # (b,nc,q,h)
    bh = jnp.repeat(bc, r, axis=3)                           # (b,nc,q,h*? )
    # bc is (b,nc,q,g,n) -> heads axis 3
    s_chunk = jnp.einsum("bcqhn,bcqhp,bcqh->bchpn",
                         bh.astype(jnp.float32),
                         xc.astype(jnp.float32), w_end)      # (b,nc,h,p,n)
    d_tot = jnp.exp(cs[:, :, -1, :])                         # (b,nc,h)

    # ---- inter-chunk recurrence -------------------------------------------
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)
    else:
        init_state = init_state.astype(jnp.float32)

    def step(s_prev, inp):
        s_c, d_c = inp
        s_new = s_prev * d_c[:, :, None, None] + s_c
        return s_new, s_prev

    s_chunk_t = jnp.moveaxis(s_chunk, 1, 0)                  # (nc,b,h,p,n)
    d_tot_t = jnp.moveaxis(d_tot, 1, 0)                      # (nc,b,h)
    # NOTE: this scan runs over SEQUENCE CHUNKS, not layers — keep it a real
    # lax.scan even when layer scans are unrolled for the dry-run analysis.
    final_state, prev_states = jax.lax.scan(step, init_state,
                                            (s_chunk_t, d_tot_t))
    prev_states = jnp.moveaxis(prev_states, 0, 1)            # (b,nc,h,p,n)

    # ---- inter-chunk output -------------------------------------------------
    ch = jnp.repeat(cc, r, axis=3)                           # (b,nc,q,h,n)
    y_inter = jnp.einsum("bcqhn,bchpn->bcqhp", ch.astype(jnp.float32),
                         prev_states)
    y_inter = y_inter * jnp.exp(cs)[..., None]
    y = (y_intra + y_inter).reshape(b, t, h, p)
    return y.astype(xb.dtype), final_state


def ssd_recurrent_step(state: jnp.ndarray, x: jnp.ndarray, dt: jnp.ndarray,
                       a_log: jnp.ndarray, bmat: jnp.ndarray,
                       cmat: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact single-token recurrence (decode).

    state: (B, H, P, N); x: (B, H, P); dt: (B, H); bmat/cmat: (B, G, N).
    Returns (y (B, H, P), new_state).
    """
    b, h, p, n = state.shape
    g = bmat.shape[1]
    r = h // g
    amt = -jnp.exp(a_log.astype(jnp.float32))                # (H,)
    da = jnp.exp(dt.astype(jnp.float32) * amt[None])         # (B, H)
    bh = jnp.repeat(bmat, r, axis=1).astype(jnp.float32)     # (B, H, N)
    ch = jnp.repeat(cmat, r, axis=1).astype(jnp.float32)
    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    new_state = state * da[..., None, None] + \
        xdt[..., :, None] * bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", new_state, ch)
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# mamba2 block
# ---------------------------------------------------------------------------

def block_dims(cfg: ModelConfig) -> Tuple[int, int, int, int, int]:
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nheads = d_in // s.head_dim
    conv_ch = d_in + 2 * s.ngroups * s.state_dim
    proj_out = 2 * d_in + 2 * s.ngroups * s.state_dim + nheads
    return d_in, nheads, conv_ch, proj_out, s.state_dim


def init_mamba_block(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    s = cfg.ssm
    d = cfg.d_model
    d_in, nheads, conv_ch, proj_out, _ = block_dims(cfg)
    ks = jax.random.split(key, 5)
    # dt bias init so softplus(dt_bias) spans [1e-3, 1e-1] (mamba default)
    u = jax.random.uniform(ks[3], (nheads,))
    dt0 = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))                # inv softplus
    return {
        "norm": L.init_rmsnorm(d, dtype),
        "w_in": L.dense_init(ks[0], (d, proj_out), dtype),
        "conv_w": (0.1 * jax.random.normal(ks[1], (s.conv_width, conv_ch))
                   ).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nheads)).astype(jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "d_skip": jnp.ones((nheads,), jnp.float32),
        "gate_norm": L.init_rmsnorm(d_in, dtype),
        "w_out": L.dense_init(ks[2], (d_in, d), dtype),
    }


def _split_proj(cfg: ModelConfig, zxbcdt: jnp.ndarray):
    s = cfg.ssm
    d_in, nheads, _, _, n = block_dims(cfg)
    gn = s.ngroups * n
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + d_in + 2 * gn]
    dt = zxbcdt[..., d_in + d_in + 2 * gn:]
    return z, xbc, dt


def _split_xbc(cfg: ModelConfig, xbc: jnp.ndarray):
    s = cfg.ssm
    d_in, _, _, _, n = block_dims(cfg)
    gn = s.ngroups * n
    x = xbc[..., :d_in]
    bmat = xbc[..., d_in:d_in + gn]
    cmat = xbc[..., d_in + gn:]
    return x, bmat, cmat


def mamba_block(bp: Params, x: jnp.ndarray, cfg: ModelConfig,
                init_state: Optional[jnp.ndarray] = None,
                return_state: bool = False):
    """Full-sequence mamba2 block: x (B, T, d) -> (B, T, d)."""
    s = cfg.ssm
    b, t, d = x.shape
    d_in, nheads, conv_ch, _, n = block_dims(cfg)
    with jax.named_scope("ssm_in_proj"):
        h = L.rmsnorm(bp["norm"], x, cfg.rmsnorm_eps)
        zxbcdt = h @ bp["w_in"]
        z, xbc_raw, dt_raw = _split_proj(cfg, zxbcdt)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                             + bp["dt_bias"])                 # (b,t,H)
        a = dt * -jnp.exp(bp["a_log"])[None, None, :]
    with jax.named_scope("ssm_conv"):
        xbc = jax.nn.silu(causal_conv1d(xbc_raw, bp["conv_w"],
                                        bp["conv_b"]))
        xs, bmat, cmat = _split_xbc(cfg, xbc)
        xs = xs.reshape(b, t, nheads, s.head_dim)
        bmat = bmat.reshape(b, t, s.ngroups, n)
        cmat = cmat.reshape(b, t, s.ngroups, n)
    xb = xs * dt[..., None].astype(xs.dtype)
    chunk = min(s.chunk_size, t)
    while t % chunk != 0:
        chunk -= 1
    y, final_state = ssd_chunked(xb, a, bmat, cmat, chunk, init_state)
    with jax.named_scope("ssm_out"):
        y = y + xs * bp["d_skip"][None, None, :, None].astype(xs.dtype)
        y = y.reshape(b, t, d_in)
        y = L.rmsnorm(bp["gate_norm"], y * jax.nn.silu(z), cfg.rmsnorm_eps)
        out = y @ bp["w_out"]
    if return_state:
        # conv tail: last (K-1) pre-activation conv inputs
        k = s.conv_width
        tail = xbc_raw[:, -(k - 1):, :]
        pad = (k - 1) - tail.shape[1]
        if pad > 0:
            tail = jnp.pad(tail, ((0, 0), (pad, 0), (0, 0)))
        return out, (final_state, tail)
    return out


def mamba_block_decode(bp: Params, x: jnp.ndarray, cfg: ModelConfig,
                       ssm_state: jnp.ndarray, conv_state: jnp.ndarray):
    """One-token decode: x (B, 1, d); returns (out, new_ssm, new_conv)."""
    s = cfg.ssm
    b = x.shape[0]
    d_in, nheads, conv_ch, _, n = block_dims(cfg)
    h = L.rmsnorm(bp["norm"], x[:, 0, :], cfg.rmsnorm_eps)
    zxbcdt = h @ bp["w_in"]
    z, xbc_raw, dt_raw = _split_proj(cfg, zxbcdt)
    xbc, new_conv = conv1d_decode(xbc_raw, conv_state, bp["conv_w"],
                                  bp["conv_b"])
    xbc = jax.nn.silu(xbc)
    xs, bmat, cmat = _split_xbc(cfg, xbc)
    xs = xs.reshape(b, nheads, s.head_dim)
    bmat = bmat.reshape(b, s.ngroups, n)
    cmat = cmat.reshape(b, s.ngroups, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + bp["dt_bias"])  # (b,H)
    y, new_state = ssd_recurrent_step(ssm_state, xs, dt, bp["a_log"],
                                      bmat, cmat)
    y = y + xs * bp["d_skip"][None, :, None].astype(xs.dtype)
    y = y.reshape(b, d_in)
    y = L.rmsnorm(bp["gate_norm"], y * jax.nn.silu(z), cfg.rmsnorm_eps)
    return (y @ bp["w_out"])[:, None, :], new_state, new_conv


# ---------------------------------------------------------------------------
# full model (mamba2-1.3b style: pure SSM tower)
# ---------------------------------------------------------------------------

def init(key: jax.Array, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    keys = jax.random.split(k_blocks, cfg.num_layers)
    params: Params = {
        "embed": L.init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "blocks": jax.vmap(lambda k: init_mamba_block(k, cfg, dtype))(keys),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.init_unembed(k_head, cfg.d_model, cfg.vocab_size,
                                        dtype)
    return params


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            *, remat: bool = False, return_aux: bool = False):
    params = L.cast_tree(params, cfg.dtype)
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))

    def body(carry, bp):
        from repro.launch.perf import constrain_activations
        return constrain_activations(carry + mamba_block(bp, carry, cfg)), \
            None

    if remat:
        from repro.launch.perf import remat_policy
        body = jax.checkpoint(body, policy=remat_policy())
    x, _ = layer_scan(body, x, params["blocks"])
    x = L.rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], x)
    else:
        logits = L.unembed_w(params["head"], x)
    if return_aux:
        return logits, jnp.zeros((), jnp.float32)
    return logits


def init_cache(cfg: ModelConfig, batch: int, capacity: int = 0,
               dtype=None) -> Params:
    del capacity  # SSM state is O(1) in sequence length
    s = cfg.ssm
    d_in, nheads, conv_ch, _, n = block_dims(cfg)
    lcount = cfg.num_layers
    return {
        "ssm": jnp.zeros((lcount, batch, nheads, s.head_dim, n), jnp.float32),
        "conv": jnp.zeros((lcount, batch, s.conv_width - 1, conv_ch),
                          jnp.dtype(cfg.dtype)),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            capacity: int = 0) -> Tuple[jnp.ndarray, Params]:
    del capacity
    params = L.cast_tree(params, cfg.dtype)
    b, t = tokens.shape
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))

    def body(carry, bp):
        out, (state, tail) = mamba_block(bp, carry, cfg, return_state=True)
        return carry + out, (state, tail)

    x, (states, tails) = layer_scan(body, x, params["blocks"])
    x = L.rmsnorm(params["final_norm"], x[:, -1:, :], cfg.rmsnorm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], x)
    else:
        logits = L.unembed_w(params["head"], x)
    cache = {"ssm": states, "conv": tails,
             "pos": jnp.full((b,), t, jnp.int32)}
    return logits, cache


def decode_step(params: Params, cfg: ModelConfig, cache: Params,
                tokens: jnp.ndarray, **_) -> Tuple[jnp.ndarray, Params]:
    params = L.cast_tree(params, cfg.dtype)
    x = L.embed(params["embed"], tokens[:, None]).astype(jnp.dtype(cfg.dtype))

    def body(carry, xs):
        bp, st, cv = xs
        out, nst, ncv = mamba_block_decode(bp, carry, cfg, st, cv)
        return carry + out, (nst, ncv)

    x, (nst, ncv) = layer_scan(body, x, (params["blocks"], cache["ssm"],
                                           cache["conv"]))
    x = L.rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], x)
    else:
        logits = L.unembed_w(params["head"], x)
    return logits, {"ssm": nst, "conv": ncv, "pos": cache["pos"] + 1}
