"""Core neural-net layers shared by every architecture family.

Pure-JAX: parameters are nested dicts of ``jnp.ndarray``; each layer is an
``init_*`` function (returns the param pytree) and an ``apply``-style pure
function.  Transformer blocks are stacked on axis 0 and driven by
``jax.lax.scan`` so the compiled HLO stays O(1) in depth.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

@jax.named_scope("param_cast")
def cast_tree(tree: Any, dtype) -> Any:
    """Cast every floating leaf to ``dtype`` (compute-dtype entry cast)."""
    dt = jnp.dtype(dtype)

    def cast(a):
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(dt)
        return a

    return jax.tree.map(cast, tree)


def dense_init(key: jax.Array, shape: Tuple[int, ...], dtype, scale: float = 1.0):
    """Truncated-normal fan-in initializer (LLaMa-style)."""
    fan_in = shape[0]
    std = scale / math.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -3.0, 3.0, shape)).astype(dtype)


def embed_init(key: jax.Array, shape: Tuple[int, ...], dtype):
    return (0.02 * jax.random.truncated_normal(key, -3.0, 3.0, shape)).astype(dtype)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    out = x * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) int32."""
    freqs = rope_freqs(x.shape[-1], theta)                       # (D/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs    # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA / MQA, optional qk-norm, optional sliding window)
# ---------------------------------------------------------------------------

def init_attention(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], (d, nq * hd), dtype),
        "wk": dense_init(ks[1], (d, nkv * hd), dtype),
        "wv": dense_init(ks[2], (d, nkv * hd), dtype),
        "wo": dense_init(ks[3], (nq * hd, d), dtype),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def _sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
          mask: Optional[jnp.ndarray], scale: float) -> jnp.ndarray:
    """q: (B,S,nq,D) k,v: (B,T,nkv,D); GQA via head grouping. fp32 softmax."""
    b, s, nq, d = q.shape
    t, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    qg = q.reshape(b, s, nkv, g, d)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if mask is not None:
        # mask: (B, S, T) or (S, T) boolean, True = attend
        m = mask if mask.ndim == 3 else mask[None]
        logits = jnp.where(m[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, nq, d).astype(q.dtype)


def causal_mask(s: int, t: int, offset: int = 0) -> jnp.ndarray:
    """(s, t) boolean mask; query i (at absolute pos offset+i) sees keys <= it."""
    qpos = jnp.arange(s)[:, None] + offset
    kpos = jnp.arange(t)[None, :]
    return kpos <= qpos


def swa_mask(s: int, t: int, window: int, offset: int = 0) -> jnp.ndarray:
    qpos = jnp.arange(s)[:, None] + offset
    kpos = jnp.arange(t)[None, :]
    return (kpos <= qpos) & (kpos > qpos - window)


@jax.named_scope("attention")
def attention(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
              cfg: ModelConfig, *, mask: Optional[jnp.ndarray],
              kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              use_rope: bool = True, return_kv: bool = False):
    """Full-sequence attention (training / prefill).

    ``kv``: externally provided key/value sequence (cross-attention) — when
    given, wk/wv are applied to it and no rope is applied to k.
    ``return_kv``: also return the (k, v) tensors (prefill cache building).
    """
    hd = cfg.resolved_head_dim
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, cfg.num_heads, hd)
    src = x if kv is None else kv[0]
    t = src.shape[1]
    k = (src @ p["wk"]).reshape(b, t, cfg.num_kv_heads, hd)
    v = (src @ p["wv"]).reshape(b, t, cfg.num_kv_heads, hd)
    if cfg.use_qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.rmsnorm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.rmsnorm_eps)
    if use_rope and kv is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = _sdpa(q, k, v, mask, 1.0 / math.sqrt(hd))
    out = out.reshape(b, s, -1) @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def attention_decode(p: Params, x: jnp.ndarray, pos: jnp.ndarray,
                     cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                     cfg: ModelConfig, *, window: int = 0,
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-token decode against a KV cache.

    x: (B, 1, d); pos: (B,) absolute position of the new token.
    cache_k/v: (B, C, nkv, hd) where C = cache capacity (ring buffer if
    ``window`` > 0, in which case C == window).
    Returns (out, new_cache_k, new_cache_v).
    """
    hd = cfg.resolved_head_dim
    b, _, _ = x.shape
    cap = cache_k.shape[1]
    q = (x @ p["wq"]).reshape(b, 1, cfg.num_heads, hd)
    k = (x @ p["wk"]).reshape(b, 1, cfg.num_kv_heads, hd)
    v = (x @ p["wv"]).reshape(b, 1, cfg.num_kv_heads, hd)
    if cfg.use_qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.rmsnorm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.rmsnorm_eps)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)

    slot = (pos % cap) if window > 0 else pos       # (B,)
    oh = jax.nn.one_hot(slot, cap, dtype=k.dtype)   # (B, C)
    cache_k = cache_k * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * k
    cache_v = cache_v * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * v

    kpos = jnp.arange(cap)[None, :]                 # slot index
    if window > 0:
        # ring buffer: valid slots hold absolute positions in (pos-window, pos]
        abs_base = (pos[:, None] // cap) * cap
        abs_pos = jnp.where(kpos <= (pos[:, None] % cap), abs_base + kpos,
                            abs_base - cap + kpos)
        valid = (abs_pos >= 0) & (abs_pos > pos[:, None] - window) & \
                (abs_pos <= pos[:, None])
    else:
        valid = kpos <= pos[:, None]
    mask = valid[:, None, :]                         # (B, 1, C)
    out = _sdpa(q, cache_k, cache_v, mask, 1.0 / math.sqrt(hd))
    return out.reshape(b, 1, -1) @ p["wo"], cache_k, cache_v


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def init_mlp(key: jax.Array, d: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, d_ff), dtype),
        "w_up": dense_init(ks[1], (d, d_ff), dtype),
        "w_down": dense_init(ks[2], (d_ff, d), dtype),
    }


def _act(name: str, x: jnp.ndarray) -> jnp.ndarray:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if name == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if name == "relu":
        return jax.nn.relu(x)
    raise ValueError(name)


def mlp(p: Params, x: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    return (_act(act, x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def init_mlp_plain(key: jax.Array, d: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "w_up": dense_init(ks[0], (d, d_ff), dtype),
        "w_down": dense_init(ks[1], (d_ff, d), dtype),
    }


def mlp_plain(p: Params, x: jnp.ndarray, act: str = "gelu") -> jnp.ndarray:
    return _act(act, x @ p["w_up"]) @ p["w_down"]


def apply_mlp(p: Params, x: jnp.ndarray, cfg) -> jnp.ndarray:
    if cfg.gated_mlp:
        return mlp(p, x, cfg.act)
    return mlp_plain(p, x, cfg.act)


def init_mlp_cfg(key: jax.Array, d: int, d_ff: int, dtype, cfg) -> Params:
    if cfg.gated_mlp:
        return init_mlp(key, d, d_ff, dtype)
    return init_mlp_plain(key, d, d_ff, dtype)


def apply_norm(p: Params, x: jnp.ndarray, cfg) -> jnp.ndarray:
    if cfg.norm == "layernorm":
        return layernorm(p, x, cfg.rmsnorm_eps)
    return rmsnorm(p, x, cfg.rmsnorm_eps)


def init_norm_cfg(d: int, dtype, cfg) -> Params:
    if cfg.norm == "layernorm":
        return init_layernorm(d, dtype)
    return init_rmsnorm(d, dtype)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def init_embedding(key: jax.Array, vocab: int, d: int, dtype) -> Params:
    return {"table": embed_init(key, (vocab, d), dtype)}


@jax.named_scope("embed")
def embed(p: Params, tokens: jnp.ndarray, scale: bool = False) -> jnp.ndarray:
    x = jnp.take(p["table"], tokens, axis=0)
    if scale:  # gemma-style sqrt(d) embedding scale
        x = x * jnp.asarray(math.sqrt(x.shape[-1]), x.dtype)
    return x


@jax.named_scope("logits_loss")
def unembed(p: Params, x: jnp.ndarray, softcap: float = 0.0) -> jnp.ndarray:
    logits = x @ p["table"].T
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


def init_unembed(key: jax.Array, d: int, vocab: int, dtype) -> Params:
    return {"w": dense_init(key, (d, vocab), dtype)}


@jax.named_scope("logits_loss")
def unembed_w(p: Params, x: jnp.ndarray, softcap: float = 0.0) -> jnp.ndarray:
    logits = x @ p["w"]
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _token_nll(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Per-token NLL with a memory-lean VJP.

    The naive autodiff of logsumexp saves an fp32 (B, S, V) softmax — at
    vocab 256k x 4k seq that alone is GBs per device.  The custom VJP keeps
    logits in their compute dtype and recomputes the (fused) softmax in the
    backward pass, so no fp32 (B, S, V) buffer is ever materialized.
    """
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold.astype(jnp.float32)


def _token_nll_fwd(logits, labels):
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold.astype(jnp.float32), (logits, labels, logz)


def _token_nll_bwd(res, g):
    logits, labels, logz = res
    # softmax recomputed and immediately consumed — fuses to compute dtype
    p = jnp.exp(logits.astype(jnp.float32) - logz[..., None]
                ).astype(logits.dtype)
    onehot = (labels[..., None] ==
              jnp.arange(logits.shape[-1], dtype=labels.dtype)
              ).astype(logits.dtype)
    return ((p - onehot) * g[..., None].astype(logits.dtype), None)


_token_nll.defvjp(_token_nll_fwd, _token_nll_bwd)


@jax.named_scope("logits_loss")
def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token cross-entropy (fp32 accumulation). labels: int32 (B, S)."""
    nll = _token_nll(logits, labels)
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
