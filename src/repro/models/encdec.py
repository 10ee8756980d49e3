"""Encoder-decoder family (whisper-large-v3 backbone).

The audio frontend (mel-spectrogram + conv feature extractor) is a STUB per
the assignment: inputs are precomputed frame embeddings (B, F, d) where
F = cfg.encoder_seq_len (1500 for whisper).  We implement the transformer
backbone: bidirectional encoder + causal decoder with cross-attention.
Whisper idioms: layernorm, plain (non-gated) GELU MLP, learned absolute
positions, tied deembedding.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models.scan_util import scan as layer_scan

Params = Dict[str, Any]


def init_enc_block(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": L.init_norm_cfg(cfg.d_model, dtype, cfg),
        "attn": L.init_attention(k1, cfg, dtype),
        "mlp_norm": L.init_norm_cfg(cfg.d_model, dtype, cfg),
        "mlp": L.init_mlp_cfg(k2, cfg.d_model, cfg.d_ff, dtype, cfg),
    }


def init_dec_block(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "self_norm": L.init_norm_cfg(cfg.d_model, dtype, cfg),
        "self_attn": L.init_attention(k1, cfg, dtype),
        "cross_norm": L.init_norm_cfg(cfg.d_model, dtype, cfg),
        "cross_attn": L.init_attention(k2, cfg, dtype),
        "mlp_norm": L.init_norm_cfg(cfg.d_model, dtype, cfg),
        "mlp": L.init_mlp_cfg(k3, cfg.d_model, cfg.d_ff, dtype, cfg),
    }


def init(key: jax.Array, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    enc_keys = jax.random.split(ks[0], cfg.num_encoder_layers)
    dec_keys = jax.random.split(ks[1], cfg.num_layers)
    return {
        "enc_pos": {"table": L.embed_init(ks[2], (cfg.encoder_seq_len,
                                                  cfg.d_model), dtype)},
        "enc_blocks": jax.vmap(lambda k: init_enc_block(k, cfg, dtype)
                               )(enc_keys),
        "enc_final_norm": L.init_norm_cfg(cfg.d_model, dtype, cfg),
        "embed": L.init_embedding(ks[3], cfg.vocab_size, cfg.d_model, dtype),
        "dec_pos": {"table": L.embed_init(ks[4], (cfg.max_seq_len,
                                                  cfg.d_model), dtype)},
        "dec_blocks": jax.vmap(lambda k: init_dec_block(k, cfg, dtype)
                               )(dec_keys),
        "final_norm": L.init_norm_cfg(cfg.d_model, dtype, cfg),
    }


def encode(params: Params, cfg: ModelConfig, frames: jnp.ndarray,
           ) -> jnp.ndarray:
    """frames: (B, F, d) stubbed conv-frontend output -> encoder states."""
    b, f, _ = frames.shape
    x = frames.astype(jnp.dtype(cfg.dtype)) + \
        params["enc_pos"]["table"][None, :f, :].astype(jnp.dtype(cfg.dtype))
    positions = jnp.broadcast_to(jnp.arange(f)[None], (b, f))
    mask = jnp.ones((f, f), bool)

    def step(carry, bp):
        h = L.apply_norm(bp["attn_norm"], carry, cfg)
        x2 = carry + L.attention(bp["attn"], h, positions, cfg, mask=mask,
                                 use_rope=False)
        h = L.apply_norm(bp["mlp_norm"], x2, cfg)
        x2 = x2 + L.apply_mlp(bp["mlp"], h, cfg)
        return x2, None

    x, _ = layer_scan(step, x, params["enc_blocks"])
    return L.apply_norm(params["enc_final_norm"], x, cfg)


def _dec_block(bp: Params, x: jnp.ndarray, positions: jnp.ndarray,
               cfg: ModelConfig, self_mask: jnp.ndarray,
               enc_out: jnp.ndarray, return_kv: bool = False):
    h = L.apply_norm(bp["self_norm"], x, cfg)
    if return_kv:
        so, (sk, sv) = L.attention(bp["self_attn"], h, positions, cfg,
                                   mask=self_mask, use_rope=False,
                                   return_kv=True)
    else:
        so = L.attention(bp["self_attn"], h, positions, cfg, mask=self_mask,
                         use_rope=False)
    x = x + so
    h = L.apply_norm(bp["cross_norm"], x, cfg)
    if return_kv:
        co, (ck, cv) = L.attention(bp["cross_attn"], h, positions, cfg,
                                   mask=None, kv=(enc_out, enc_out),
                                   use_rope=False, return_kv=True)
    else:
        co = L.attention(bp["cross_attn"], h, positions, cfg, mask=None,
                         kv=(enc_out, enc_out), use_rope=False)
    x = x + co
    h = L.apply_norm(bp["mlp_norm"], x, cfg)
    x = x + L.apply_mlp(bp["mlp"], h, cfg)
    if return_kv:
        return x, (sk, sv, ck, cv)
    return x


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            frames: jnp.ndarray, *, return_aux: bool = False):
    """tokens: (B, S) decoder inputs; frames: (B, F, d) stub embeddings."""
    params = L.cast_tree(params, cfg.dtype)
    enc_out = encode(params, cfg, frames)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    x = x + jnp.take(params["dec_pos"]["table"], positions, axis=0
                     ).astype(x.dtype)
    self_mask = L.causal_mask(s, s)

    def step(carry, bp):
        return _dec_block(bp, carry, positions, cfg, self_mask, enc_out), None

    x, _ = layer_scan(step, x, params["dec_blocks"])
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], x)  # tied
    if return_aux:
        return logits, jnp.zeros((), jnp.float32)
    return logits


def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    lc = cfg.num_layers
    f = cfg.encoder_seq_len
    return {
        "k": jnp.zeros((lc, batch, capacity, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((lc, batch, capacity, cfg.num_kv_heads, hd), dtype),
        "ck": jnp.zeros((lc, batch, f, cfg.num_kv_heads, hd), dtype),
        "cv": jnp.zeros((lc, batch, f, cfg.num_kv_heads, hd), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            frames: jnp.ndarray, capacity: int) -> Tuple[jnp.ndarray, Params]:
    params = L.cast_tree(params, cfg.dtype)
    enc_out = encode(params, cfg, frames)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    x = x + jnp.take(params["dec_pos"]["table"], positions, axis=0
                     ).astype(x.dtype)
    self_mask = L.causal_mask(s, s)

    def step(carry, bp):
        return _dec_block(bp, carry, positions, cfg, self_mask, enc_out,
                          return_kv=True)

    x, (sk, sv, ck, cv) = layer_scan(step, x, params["dec_blocks"])
    pad = capacity - s
    assert pad >= 0
    sk = jnp.pad(sk, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    sv = jnp.pad(sv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    x = L.apply_norm(params["final_norm"], x[:, -1:, :], cfg)
    logits = L.unembed(params["embed"], x)
    cache = {"k": sk, "v": sv, "ck": ck, "cv": cv,
             "pos": jnp.full((b,), s, jnp.int32)}
    return logits, cache


def decode_step(params: Params, cfg: ModelConfig, cache: Params,
                tokens: jnp.ndarray, **_) -> Tuple[jnp.ndarray, Params]:
    params = L.cast_tree(params, cfg.dtype)
    b = tokens.shape[0]
    pos = cache["pos"]
    x = L.embed(params["embed"], tokens[:, None]).astype(jnp.dtype(cfg.dtype))
    x = x + jnp.take(params["dec_pos"]["table"], pos[:, None], axis=0
                     ).astype(x.dtype)
    hd = cfg.resolved_head_dim
    f = cfg.encoder_seq_len

    def step(carry, xs):
        bp, ck_, cv_, xk, xv = xs
        h = L.apply_norm(bp["self_norm"], carry, cfg)
        # self-attn against the growing cache (no rope in whisper)
        q = (h @ bp["self_attn"]["wq"]).reshape(b, 1, cfg.num_heads, hd)
        k = (h @ bp["self_attn"]["wk"]).reshape(b, 1, cfg.num_kv_heads, hd)
        v = (h @ bp["self_attn"]["wv"]).reshape(b, 1, cfg.num_kv_heads, hd)
        cap = ck_.shape[1]
        oh = jax.nn.one_hot(pos, cap, dtype=k.dtype)
        nk = ck_ * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * k
        nv = cv_ * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * v
        valid = (jnp.arange(cap)[None, :] <= pos[:, None])[:, None, :]
        so = L._sdpa(q, nk, nv, valid, 1.0 / (hd ** 0.5))
        x2 = carry + so.reshape(b, 1, -1) @ bp["self_attn"]["wo"]
        # cross-attn against precomputed encoder K/V
        h = L.apply_norm(bp["cross_norm"], x2, cfg)
        cq = (h @ bp["cross_attn"]["wq"]).reshape(b, 1, cfg.num_heads, hd)
        co = L._sdpa(cq, xk, xv, jnp.ones((b, 1, f), bool), 1.0 / (hd ** 0.5))
        x2 = x2 + co.reshape(b, 1, -1) @ bp["cross_attn"]["wo"]
        h = L.apply_norm(bp["mlp_norm"], x2, cfg)
        x2 = x2 + L.apply_mlp(bp["mlp"], h, cfg)
        return x2, (nk, nv)

    x, (nk, nv) = layer_scan(step, x, (params["dec_blocks"], cache["k"],
                                         cache["v"], cache["ck"],
                                         cache["cv"]))
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], x)
    return logits, {"k": nk, "v": nv, "ck": cache["ck"], "cv": cache["cv"],
                    "pos": pos + 1}
