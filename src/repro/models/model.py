"""Unified model API over all architecture families.

``build_model(cfg)`` returns a :class:`Model` exposing
init / apply / loss / init_cache / prefill / decode_step with a common batch
dict convention:

    {"tokens": (B, S) int32, "labels": (B, S) int32,
     "loss_mask": (B, S) float32 (optional),
     "patches": (B, P, D_PATCH) (vlm only),
     "frames": (B, F, d_model) (encdec only)}
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.models import ssm as S
from repro.models import hybrid as H
from repro.models import encdec as ED
from repro.models import vlm as V

Params = Dict[str, Any]
Batch = Dict[str, jnp.ndarray]


class Model:
    """Family-dispatching facade (pure functions inside; no state)."""

    def __init__(self, cfg: ModelConfig):
        cfg.validate()
        self.cfg = cfg

    # ---- init --------------------------------------------------------
    def init(self, key: jax.Array) -> Params:
        c = self.cfg
        if c.arch_type in ("dense", "moe"):
            return T.init(key, c)
        if c.arch_type == "ssm":
            return S.init(key, c)
        if c.arch_type == "hybrid":
            return H.init(key, c)
        if c.arch_type == "encdec":
            return ED.init(key, c)
        if c.arch_type == "vlm":
            return V.init(key, c)
        raise ValueError(c.arch_type)

    # ---- forward -----------------------------------------------------
    def apply(self, params: Params,
              batch: Batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Full-sequence forward -> (logits, aux_loss)."""
        c = self.cfg
        toks = batch["tokens"]
        if c.arch_type in ("dense", "moe"):
            return T.forward(params, c, toks, return_aux=True)
        if c.arch_type == "ssm":
            return S.forward(params, c, toks, return_aux=True)
        if c.arch_type == "hybrid":
            return H.forward(params, c, toks, return_aux=True)
        if c.arch_type == "encdec":
            return ED.forward(params, c, toks, batch["frames"],
                              return_aux=True)
        if c.arch_type == "vlm":
            return V.forward(params, c, toks, batch["patches"],
                             return_aux=True)
        raise ValueError(c.arch_type)

    # ---- loss --------------------------------------------------------
    def loss(self, params: Params,
             batch: Batch) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        c = self.cfg
        logits, aux = self.apply(params, batch)
        if c.arch_type == "vlm":
            logits = logits[:, batch["patches"].shape[1]:, :]
        mask = batch.get("loss_mask")
        ce = L.cross_entropy(logits, batch["labels"], mask)
        total = ce + c.moe.router_aux_coef * aux
        return total, {"ce": ce, "aux": aux}

    # ---- serving -----------------------------------------------------
    def init_cache(self, batch: int, capacity: int) -> Params:
        c = self.cfg
        if c.arch_type in ("dense", "moe"):
            return T.init_cache(c, batch, capacity)
        if c.arch_type == "ssm":
            return S.init_cache(c, batch, capacity)
        if c.arch_type == "hybrid":
            return H.init_cache(c, batch, capacity)
        if c.arch_type == "encdec":
            return ED.init_cache(c, batch, capacity)
        if c.arch_type == "vlm":
            return V.init_cache(c, batch, capacity)
        raise ValueError(c.arch_type)

    def prefill(self, params: Params, batch: Batch, capacity: int,
                ) -> Tuple[jnp.ndarray, Params]:
        c = self.cfg
        toks = batch["tokens"]
        if c.arch_type in ("dense", "moe"):
            return T.prefill(params, c, toks, capacity)
        if c.arch_type == "ssm":
            return S.prefill(params, c, toks, capacity)
        if c.arch_type == "hybrid":
            return H.prefill(params, c, toks, capacity)
        if c.arch_type == "encdec":
            return ED.prefill(params, c, toks, batch["frames"], capacity)
        if c.arch_type == "vlm":
            return V.prefill(params, c, toks, batch["patches"], capacity)
        raise ValueError(c.arch_type)

    def decode_step(self, params: Params, cache: Params, tokens: jnp.ndarray,
                    *, window: int = 0) -> Tuple[jnp.ndarray, Params]:
        c = self.cfg
        if c.arch_type in ("dense", "moe"):
            return T.decode_step(params, c, cache, tokens, window=window)
        if c.arch_type == "ssm":
            return S.decode_step(params, c, cache, tokens)
        if c.arch_type == "hybrid":
            return H.decode_step(params, c, cache, tokens, window=window)
        if c.arch_type == "encdec":
            return ED.decode_step(params, c, cache, tokens)
        if c.arch_type == "vlm":
            return V.decode_step(params, c, cache, tokens, window=window)
        raise ValueError(c.arch_type)

    def param_count_actual(self, params: Params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
