"""Dense decoder-only transformer family (llama / qwen3 / gemma / danube /
deepseek-coder and the paper's LLaMa sizes).

Blocks are stacked on axis 0 and executed with ``jax.lax.scan``; per-layer
sliding-window flags ride along as scan inputs.  Three entry points:

* :func:`forward`      — full-sequence training/eval forward (causal).
* :func:`prefill`      — full-sequence forward that also emits the KV cache.
* :func:`decode_step`  — one-token decode against a (possibly ring) KV cache.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models.scan_util import scan as layer_scan
from repro.models import moe as MOE

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_block(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    if cfg.arch_type == "moe":
        mlp_params = MOE.init_moe_layer(k2, cfg, dtype)
    else:
        mlp_params = L.init_mlp_cfg(k2, cfg.d_model, cfg.d_ff, dtype, cfg)
    return {
        "attn_norm": L.init_norm_cfg(cfg.d_model, dtype, cfg),
        "attn": L.init_attention(k1, cfg, dtype),
        "mlp_norm": L.init_norm_cfg(cfg.d_model, dtype, cfg),
        "mlp": mlp_params,
    }


def _mlp_or_moe(bp: Params, h: jnp.ndarray, cfg: ModelConfig):
    """Returns (out, aux). Dense archs have aux = 0."""
    if cfg.arch_type == "moe":
        return MOE.moe_mlp(bp["mlp"], h, cfg)
    return L.apply_mlp(bp["mlp"], h, cfg), jnp.zeros((), jnp.float32)


def init_stacked_blocks(key: jax.Array, cfg: ModelConfig, n: int, dtype) -> Params:
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_block(k, cfg, dtype))(keys)


def init(key: jax.Array, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_blocks, k_head, k_pos = jax.random.split(key, 4)
    params: Params = {
        "embed": L.init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "blocks": init_stacked_blocks(k_blocks, cfg, cfg.num_layers, dtype),
        "final_norm": L.init_norm_cfg(cfg.d_model, dtype, cfg),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.init_unembed(k_head, cfg.d_model, cfg.vocab_size, dtype)
    if not cfg.use_rope:
        params["pos_embed"] = {
            "table": L.embed_init(k_pos, (cfg.max_seq_len, cfg.d_model), dtype)}
    return params


def swa_flags(cfg: ModelConfig) -> jnp.ndarray:
    """(L,) bool — which layers use sliding-window attention."""
    idx = jnp.arange(cfg.num_layers)
    if cfg.sliding_window > 0:
        return (idx % max(cfg.swa_every, 1)) == 0
    return jnp.zeros((cfg.num_layers,), bool)


# ---------------------------------------------------------------------------
# forward (train / eval)
# ---------------------------------------------------------------------------

def _block_apply(cfg: ModelConfig):
    def f(x, bp, full_mask, swa_m, flag, positions):
        mask = jnp.where(flag, swa_m, full_mask) if cfg.sliding_window > 0 \
            else full_mask
        h = L.apply_norm(bp["attn_norm"], x, cfg)
        x = x + L.attention(bp["attn"], h, positions, cfg, mask=mask)
        h = L.apply_norm(bp["mlp_norm"], x, cfg)
        out, aux = _mlp_or_moe(bp, h, cfg)
        x = x + out
        return x, aux
    return f


def embed_tokens(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                 positions: jnp.ndarray) -> jnp.ndarray:
    x = L.embed(params["embed"], tokens, scale=cfg.embed_scale)
    x = x.astype(jnp.dtype(cfg.dtype))
    if not cfg.use_rope:
        x = x + jnp.take(params["pos_embed"]["table"], positions, axis=0
                         ).astype(x.dtype)
    return x


def logits_from_hidden(params: Params, cfg: ModelConfig,
                       x: jnp.ndarray) -> jnp.ndarray:
    x = L.apply_norm(params["final_norm"], x, cfg)
    if cfg.tie_embeddings:
        return L.unembed(params["embed"], x, cfg.logit_softcap)
    return L.unembed_w(params["head"], x, cfg.logit_softcap)


def run_blocks(params: Params, cfg: ModelConfig, x: jnp.ndarray,
               positions: jnp.ndarray, *,
               offset: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scan the stacked decoder blocks over a full sequence (causal)."""
    s = x.shape[1]
    full_mask = L.causal_mask(s, s, offset)
    swa_m = L.swa_mask(s, s, cfg.sliding_window, offset) \
        if cfg.sliding_window > 0 else full_mask
    block = _block_apply(cfg)

    def step(carry, xs):
        bp, flag = xs
        return block(carry, bp, full_mask, swa_m, flag, positions)

    x, auxs = layer_scan(step, x, (params["blocks"], swa_flags(cfg)))
    return x, jnp.sum(auxs)


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            *, inputs_embeds: Optional[jnp.ndarray] = None,
            return_aux: bool = False):
    """tokens: (B, S) -> logits (B, S, V).

    ``inputs_embeds``: optional (B, P, d) prefix embeddings (VLM stub) that are
    prepended to the token embeddings.
    """
    params = L.cast_tree(params, cfg.dtype)
    b, s = tokens.shape
    if inputs_embeds is not None:
        p = inputs_embeds.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s + p)[None], (b, s + p))
        x = embed_tokens(params, cfg, tokens, positions[:, p:])
        x = jnp.concatenate([inputs_embeds.astype(x.dtype), x], axis=1)
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x = embed_tokens(params, cfg, tokens, positions)
    x, aux = run_blocks(params, cfg, x, positions)
    logits = logits_from_hidden(params, cfg, x)
    if return_aux:
        return logits, aux
    return logits


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, capacity, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((batch,), jnp.int32)}


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            capacity: int, *, inputs_embeds: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, Params]:
    """Full causal forward over the prompt; returns last-token logits + cache.

    ``inputs_embeds``: optional (B, P, d) prefix (VLM patch embeddings); the
    cache then covers P + S positions.
    """
    params = L.cast_tree(params, cfg.dtype)
    b, s = tokens.shape
    if inputs_embeds is not None:
        pfx = inputs_embeds.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s + pfx)[None], (b, s + pfx))
        x = embed_tokens(params, cfg, tokens, positions[:, pfx:])
        x = jnp.concatenate([inputs_embeds.astype(x.dtype), x], axis=1)
        s = s + pfx
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x = embed_tokens(params, cfg, tokens, positions)
    full_mask = L.causal_mask(s, s)
    swa_m = L.swa_mask(s, s, cfg.sliding_window) if cfg.sliding_window > 0 \
        else full_mask

    def step(carry, xs):
        bp, flag = xs
        mask = jnp.where(flag, swa_m, full_mask) if cfg.sliding_window > 0 \
            else full_mask
        h = L.apply_norm(bp["attn_norm"], carry, cfg)
        attn_out, (k, v) = L.attention(bp["attn"], h, positions, cfg,
                                       mask=mask, return_kv=True)
        x2 = carry + attn_out
        h = L.apply_norm(bp["mlp_norm"], x2, cfg)
        out, _aux = _mlp_or_moe(bp, h, cfg)
        x2 = x2 + out
        return x2, (k, v)

    x, (ks, vs) = layer_scan(step, x, (params["blocks"], swa_flags(cfg)))
    # place the prompt K/V into a fixed-capacity cache
    window = cfg.sliding_window
    if window > 0 and capacity == window and s > window:
        # ring cache: keep only the last ``window`` positions, rotated so that
        # absolute position p sits at slot p % window
        start = s - window
        ks = jax.lax.dynamic_slice_in_dim(ks, start, window, axis=2)
        vs = jax.lax.dynamic_slice_in_dim(vs, start, window, axis=2)
        roll = start % window  # abs pos p lands at slot p % window
        ks = jnp.roll(ks, roll, axis=2)
        vs = jnp.roll(vs, roll, axis=2)
        cache_k, cache_v = ks, vs
    else:
        pad = capacity - s
        assert pad >= 0, (capacity, s)
        cache_k = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        cache_v = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {"k": cache_k, "v": cache_v,
             "pos": jnp.full((b,), s, jnp.int32)}
    logits = logits_from_hidden(params, cfg, x[:, -1:, :])
    return logits, cache


def decode_step(params: Params, cfg: ModelConfig, cache: Params,
                tokens: jnp.ndarray, *, window: int = 0,
                ) -> Tuple[jnp.ndarray, Params]:
    """tokens: (B,) next input token; returns (logits (B,1,V), new cache).

    ``window``: 0 = full-cache attention; >0 = ring-buffer SWA with the cache
    capacity equal to the window (the SWA serving variant / native SWA archs).
    """
    params = L.cast_tree(params, cfg.dtype)
    b = tokens.shape[0]
    pos = cache["pos"]                        # (B,) absolute position to write
    x = embed_tokens(params, cfg, tokens[:, None], pos[:, None])

    def step(carry, xs):
        bp, ck, cv = xs
        h = L.apply_norm(bp["attn_norm"], carry, cfg)
        out, nk, nv = L.attention_decode(bp["attn"], h, pos, ck, cv, cfg,
                                         window=window)
        x2 = carry + out
        h = L.apply_norm(bp["mlp_norm"], x2, cfg)
        mo, _aux = _mlp_or_moe(bp, h, cfg)
        x2 = x2 + mo
        return x2, (nk, nv)

    x, (nk, nv) = layer_scan(step, x, (params["blocks"], cache["k"],
                                         cache["v"]))
    logits = logits_from_hidden(params, cfg, x)
    return logits, {"k": nk, "v": nv, "pos": pos + 1}
