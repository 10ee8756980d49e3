"""Mixture-of-Experts MLP layer (token-choice top-k router).

GShard/Switch-style capacity-based dispatch: tokens are grouped, each group
dispatches at most ``capacity`` tokens per expert via one-hot dispatch/combine
einsums.  This is fully static-shaped (TPU/XLA friendly) and shards cleanly:
the group dim follows the batch ("data") axis and the expert dim can be
sharded over the "model" axis (expert parallelism) when divisible.

Covers granite-moe (40 routed, top-8) and deepseek-moe (64 routed top-6 +
2 shared, fine-grained d_ff).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L

Params = Dict[str, Any]

CAPACITY_FACTOR = 1.25


def init_moe_layer(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    d, ffe, E = cfg.d_model, m.d_ff_expert, m.num_experts
    ks = jax.random.split(key, 5)
    std = 1.0 / math.sqrt(d)
    p: Params = {
        "router": L.dense_init(ks[0], (d, E), jnp.float32),  # router in fp32
        "w_gate": (std * jax.random.truncated_normal(ks[1], -3, 3, (E, d, ffe))
                   ).astype(dtype),
        "w_up": (std * jax.random.truncated_normal(ks[2], -3, 3, (E, d, ffe))
                 ).astype(dtype),
        "w_down": ((1.0 / math.sqrt(ffe)) *
                   jax.random.truncated_normal(ks[3], -3, 3, (E, ffe, d))
                   ).astype(dtype),
    }
    if m.num_shared_experts > 0:
        p["shared"] = L.init_mlp(ks[4], d, m.num_shared_experts * ffe, dtype)
    return p


def _group_size(total_tokens: int, seq: int) -> int:
    """Pick a group size that divides the per-example token count.

    The one-hot dispatch/combine einsums cost O(T_g * C * d) per token with
    C ~ T_g * k / E — QUADRATIC in the group size T_g — so the candidates
    start at a cap of 4096 tokens (GShard uses a few hundred).
    """
    for cand in (4096, 2048, 1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if cand <= seq and seq % cand == 0:
            return cand
    return 1


def topk_dispatch(gates: jnp.ndarray, k: int, capacity: int,
                  dtype) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """gates: (G, T, E) fp32 router probabilities.

    Returns (dispatch (G,T,E,C) in ``dtype``, combine (G,T,E,C) fp32-ish,
    aux load-balance loss scalar).
    """
    g, t, e = gates.shape
    topv, topi = jax.lax.top_k(gates, k)                   # (G, T, k)
    topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-9)

    dispatch = jnp.zeros((g, t, e, capacity), dtype)
    combine = jnp.zeros((g, t, e, capacity), dtype)
    offsets = jnp.zeros((g, e), jnp.int32)                 # used slots per expert
    for j in range(k):
        m = jax.nn.one_hot(topi[..., j], e, dtype=jnp.int32)      # (G,T,E)
        pos = (jnp.cumsum(m, axis=1) - m) + offsets[:, None, :]   # exclusive
        keep = (pos < capacity) & (m > 0)
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity,
                                dtype=dtype)               # OOB rows -> all-zero
        dj = pos_oh * keep[..., None].astype(dtype)
        dispatch = dispatch + dj
        combine = combine + dj * topv[..., j][..., None, None].astype(dtype)
        offsets = offsets + jnp.sum(m, axis=1)

    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(gates, axis=(0, 1))                       # mean router prob
    top1 = jax.nn.one_hot(topi[..., 0], e, dtype=jnp.float32)
    ce = jnp.mean(top1, axis=(0, 1))                        # top-1 dispatch frac
    aux = e * jnp.sum(me * ce)
    return dispatch, combine, aux


def moe_mlp(p: Params, x: jnp.ndarray, cfg: ModelConfig,
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out (B, S, d), aux loss scalar)."""
    m = cfg.moe
    b, s, d = x.shape
    tg = _group_size(b * s, s)
    gdim = (b * s) // tg
    xg = x.reshape(gdim, tg, d)

    with jax.named_scope("moe_dispatch"):
        logits = (xg.astype(jnp.float32) @ p["router"].astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)             # (G, T, E)
        capacity = max(1, int(math.ceil(tg * m.top_k / m.num_experts
                                        * m.capacity_factor)))
        dispatch, combine, aux = topk_dispatch(gates, m.top_k, capacity,
                                               x.dtype)
        ein = jnp.einsum("gtd,gtec->gecd", xg, dispatch)    # (G, E, C, d)
    with jax.named_scope("moe_experts"):
        h = L._act(cfg.act, jnp.einsum("gecd,edf->gecf", ein, p["w_gate"]))
        h = h * jnp.einsum("gecd,edf->gecf", ein, p["w_up"])
        eout = jnp.einsum("gecf,efd->gecd", h, p["w_down"])  # (G, E, C, d)
    with jax.named_scope("moe_dispatch"):
        out = jnp.einsum("gecd,gtec->gtd", eout, combine)
    out = out.reshape(b, s, d)
    if m.num_shared_experts > 0:
        out = out + L.mlp(p["shared"], x, cfg.act)
    return out, aux
