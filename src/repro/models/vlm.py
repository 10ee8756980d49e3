"""VLM family (internvl2-76b backbone).

Per the assignment, the vision tower (InternViT) is a STUB: inputs are
precomputed patch embeddings of shape (B, num_patches, d_patch).  We implement
the language backbone (llama-family) plus the MLP projector that maps patch
embeddings into the LLM residual stream.  The projector is treated like the
(de)embedding layers in CheckFree+ — replicated, not averaged.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T

Params = Dict[str, Any]

D_PATCH = 1024  # stubbed InternViT output dim (post pixel-shuffle)


def init(key: jax.Array, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    k_llm, k_proj = jax.random.split(key)
    params = T.init(k_llm, cfg)
    k1, k2 = jax.random.split(k_proj)
    params["projector"] = {
        "w1": L.dense_init(k1, (D_PATCH, cfg.d_model), dtype),
        "w2": L.dense_init(k2, (cfg.d_model, cfg.d_model), dtype),
    }
    return params


def project(params: Params, patches: jnp.ndarray, cfg: ModelConfig,
            ) -> jnp.ndarray:
    """patches: (B, P, d_patch) -> (B, P, d_model)."""
    p = L.cast_tree(params["projector"], cfg.dtype)
    h = jax.nn.gelu(patches.astype(jnp.dtype(cfg.dtype)) @ p["w1"])
    return h @ p["w2"]


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            patches: jnp.ndarray, *, return_aux: bool = False):
    """tokens: (B, S_text); patches: (B, P, d_patch).  Logits cover the full
    (P + S_text) sequence; the caller masks the image positions in the loss."""
    embeds = project(params, patches, cfg)
    return T.forward(params, cfg, tokens, inputs_embeds=embeds,
                     return_aux=return_aux)


def init_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=None):
    return T.init_cache(cfg, batch, capacity, dtype)


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            patches: jnp.ndarray, capacity: int) -> Tuple[jnp.ndarray, Params]:
    embeds = project(params, patches, cfg)
    return T.prefill(params, cfg, tokens, capacity, inputs_embeds=embeds)


def decode_step(params: Params, cfg: ModelConfig, cache: Params,
                tokens: jnp.ndarray, *, window: int = 0):
    return T.decode_step(params, cfg, cache, tokens, window=window)
