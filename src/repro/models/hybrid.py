"""Hybrid SSM+attention family (zamba2-style).

A Mamba2 backbone with a single *shared* attention+MLP block applied every
``cfg.attn_every`` SSM layers (zamba2's shared transformer blocks).  The
shared block has one parameter set reused at every application — which is
exactly why its failure is handled by CheckFree+'s replication path rather
than neighbour averaging.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models.scan_util import scan as layer_scan
from repro.models import ssm as S
from repro.models import transformer as T

Params = Dict[str, Any]


def _nseg(cfg: ModelConfig) -> Tuple[int, int]:
    per = cfg.attn_every
    assert per > 0 and cfg.num_layers % per == 0, (cfg.num_layers, per)
    return cfg.num_layers // per, per


def init(key: jax.Array, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_m, k_a, k_head = jax.random.split(key, 4)
    keys = jax.random.split(k_m, cfg.num_layers)
    params: Params = {
        "embed": L.init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "mamba": jax.vmap(lambda k: S.init_mamba_block(k, cfg, dtype))(keys),
        "shared_attn": T.init_block(k_a, cfg, dtype),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.init_unembed(k_head, cfg.d_model, cfg.vocab_size,
                                        dtype)
    return params


def _attn_apply(bp: Params, x: jnp.ndarray, positions: jnp.ndarray,
                cfg: ModelConfig, mask: jnp.ndarray) -> jnp.ndarray:
    h = L.apply_norm(bp["attn_norm"], x, cfg)
    x = x + L.attention(bp["attn"], h, positions, cfg, mask=mask)
    h = L.apply_norm(bp["mlp_norm"], x, cfg)
    return x + L.apply_mlp(bp["mlp"], h, cfg)


def _reshape_seg(tree: Params, nseg: int, per: int) -> Params:
    return jax.tree.map(lambda a: a.reshape(nseg, per, *a.shape[1:]), tree)


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            *, return_aux: bool = False):
    params = L.cast_tree(params, cfg.dtype)
    b, t = tokens.shape
    nseg, per = _nseg(cfg)
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    window = cfg.sliding_window
    mask = L.swa_mask(t, t, window) if window > 0 else L.causal_mask(t, t)
    mseg = _reshape_seg(params["mamba"], nseg, per)

    def seg_body(carry, seg_params):
        def inner(c, bp):
            return c + S.mamba_block(bp, c, cfg), None
        x2, _ = layer_scan(inner, carry, seg_params)
        x2 = _attn_apply(params["shared_attn"], x2, positions, cfg, mask)
        return x2, None

    x, _ = layer_scan(seg_body, x, mseg)
    x = L.rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    logits = (L.unembed(params["embed"], x) if cfg.tie_embeddings
              else L.unembed_w(params["head"], x))
    if return_aux:
        return logits, jnp.zeros((), jnp.float32)
    return logits


def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    nseg, per = _nseg(cfg)
    s = cfg.ssm
    d_in, nheads, conv_ch, _, n = S.block_dims(cfg)
    hd = cfg.resolved_head_dim
    return {
        "ssm": jnp.zeros((cfg.num_layers, batch, nheads, s.head_dim, n),
                         jnp.float32),
        "conv": jnp.zeros((cfg.num_layers, batch, s.conv_width - 1, conv_ch),
                          dtype),
        "k": jnp.zeros((nseg, batch, capacity, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((nseg, batch, capacity, cfg.num_kv_heads, hd), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            capacity: int) -> Tuple[jnp.ndarray, Params]:
    params = L.cast_tree(params, cfg.dtype)
    b, t = tokens.shape
    nseg, per = _nseg(cfg)
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    window = cfg.sliding_window
    mask = L.swa_mask(t, t, window) if window > 0 else L.causal_mask(t, t)
    mseg = _reshape_seg(params["mamba"], nseg, per)

    def seg_body(carry, seg_params):
        def inner(c, bp):
            out, (st, tail) = S.mamba_block(bp, c, cfg, return_state=True)
            return c + out, (st, tail)
        x2, (sts, tails) = layer_scan(inner, carry, seg_params)
        h = L.apply_norm(params["shared_attn"]["attn_norm"], x2, cfg)
        attn_out, (k, v) = L.attention(params["shared_attn"]["attn"], h,
                                       positions, cfg, mask=mask,
                                       return_kv=True)
        x2 = x2 + attn_out
        h = L.apply_norm(params["shared_attn"]["mlp_norm"], x2, cfg)
        x2 = x2 + L.apply_mlp(params["shared_attn"]["mlp"], h, cfg)
        return x2, (sts, tails, k, v)

    x, (sts, tails, ks, vs) = layer_scan(seg_body, x, mseg)
    # sts: (nseg, per, b, ...) -> (L, b, ...)
    sts = jax.tree.map(lambda a: a.reshape(cfg.num_layers, *a.shape[2:]), sts)
    tails = tails.reshape(cfg.num_layers, *tails.shape[2:])
    # place KV into capacity cache (ring if SWA window == capacity)
    if window > 0 and capacity == window and t > window:
        start = t - window
        ks = jax.lax.dynamic_slice_in_dim(ks, start, window, axis=2)
        vs = jax.lax.dynamic_slice_in_dim(vs, start, window, axis=2)
        roll = start % window
        ks = jnp.roll(ks, roll, axis=2)
        vs = jnp.roll(vs, roll, axis=2)
    else:
        pad = capacity - t
        assert pad >= 0
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    x = L.rmsnorm(params["final_norm"], x[:, -1:, :], cfg.rmsnorm_eps)
    logits = (L.unembed(params["embed"], x) if cfg.tie_embeddings
              else L.unembed_w(params["head"], x))
    cache = {"ssm": sts, "conv": tails, "k": ks, "v": vs,
             "pos": jnp.full((b,), t, jnp.int32)}
    return logits, cache


def decode_step(params: Params, cfg: ModelConfig, cache: Params,
                tokens: jnp.ndarray, *, window: int = 0,
                ) -> Tuple[jnp.ndarray, Params]:
    params = L.cast_tree(params, cfg.dtype)
    b = tokens.shape[0]
    nseg, per = _nseg(cfg)
    pos = cache["pos"]
    x = L.embed(params["embed"], tokens[:, None]).astype(jnp.dtype(cfg.dtype))
    mseg = _reshape_seg(params["mamba"], nseg, per)
    sseg = jax.tree.map(lambda a: a.reshape(nseg, per, *a.shape[1:]),
                        cache["ssm"])
    cseg = cache["conv"].reshape(nseg, per, *cache["conv"].shape[1:])

    def seg_body(carry, xs):
        seg_params, st_seg, cv_seg, ck, cv = xs

        def inner(c, inner_xs):
            bp, st, cvs = inner_xs
            out, nst, ncv = S.mamba_block_decode(bp, c, cfg, st, cvs)
            return c + out, (nst, ncv)

        x2, (nst, ncv) = layer_scan(inner, carry, (seg_params, st_seg,
                                                     cv_seg))
        h = L.apply_norm(params["shared_attn"]["attn_norm"], x2, cfg)
        out, nk, nv = L.attention_decode(params["shared_attn"]["attn"], h,
                                         pos, ck, cv, cfg, window=window)
        x2 = x2 + out
        h = L.apply_norm(params["shared_attn"]["mlp_norm"], x2, cfg)
        x2 = x2 + L.apply_mlp(params["shared_attn"]["mlp"], h, cfg)
        return x2, (nst, ncv, nk, nv)

    x, (nst, ncv, nk, nv) = layer_scan(
        seg_body, x, (mseg, sseg, cseg, cache["k"], cache["v"]))
    nst = jax.tree.map(lambda a: a.reshape(cfg.num_layers, *a.shape[2:]), nst)
    ncv = ncv.reshape(cfg.num_layers, *ncv.shape[2:])
    x = L.rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    logits = (L.unembed(params["embed"], x) if cfg.tie_embeddings
              else L.unembed_w(params["head"], x))
    return logits, {"ssm": nst, "conv": ncv, "k": nk, "v": nv,
                    "pos": pos + 1}
