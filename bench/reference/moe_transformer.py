"""Plain reference of a decoder-only transformer whose feed-forward is a
token-choice mixture of experts (Granite 3.0 MoE): RMSNorm, grouped-query
attention with rotary positions, a softmax router over the experts, top-k
selection with a per-expert capacity, SwiGLU experts, a tied output
embedding, and the router's load-balancing loss.

Float32 throughout, one layer at a time in a Python loop, every expert run
on every token and masked afterwards: the shortest honest statement of the
mathematics, not a fast one.  It follows the published description; where
the configuration file states a rule the description leaves open (the
capacity of an expert, the group its queue spans, the order in which the
k choices claim slots), this file implements that rule.

Departures from the published model, kept so that the reference computes
what the configuration describes: Granite's embedding, attention,
residual and logit multipliers are not applied (the configuration file
lists them under ``not_modelled``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from bench.reference.common import dot, rmsnorm, token_nll, truncated

Params = Dict[str, Any]


def init(key: jax.Array, m: Dict[str, Any]) -> Params:
    """Float32 weights drawn from ``key``: truncated normals of standard
    deviation 1/sqrt(fan-in) for the projections, router and experts
    (the experts' down projection 1/sqrt(expert width)), 0.02 for the
    embedding, ones for the norm scales.  The key is split as
    (embedding, layers, head, positions), each layer's key as
    (attention, experts) and those as the weights in the order listed."""
    d, hd = m["d_model"], m["head_dim"]
    nq, nkv = m["num_heads"], m["num_kv_heads"]
    e, f = m["moe"]["num_experts"], m["moe"]["d_ff_expert"]
    k_emb, k_layers, _k_head, _k_pos = jax.random.split(key, 4)

    def layer(k):
        k_attn, k_moe = jax.random.split(k)
        ka = jax.random.split(k_attn, 4)
        km = jax.random.split(k_moe, 5)
        return {
            "attn_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "attn": {"wq": truncated(ka[0], (d, nq * hd), 1 / math.sqrt(d)),
                     "wk": truncated(ka[1], (d, nkv * hd), 1 / math.sqrt(d)),
                     "wv": truncated(ka[2], (d, nkv * hd), 1 / math.sqrt(d)),
                     "wo": truncated(ka[3], (nq * hd, d),
                                     1 / math.sqrt(nq * hd))},
            "mlp_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "mlp": {"router": truncated(km[0], (d, e), 1 / math.sqrt(d)),
                    "w_gate": truncated(km[1], (e, d, f), 1 / math.sqrt(d)),
                    "w_up": truncated(km[2], (e, d, f), 1 / math.sqrt(d)),
                    "w_down": truncated(km[3], (e, f, d), 1 / math.sqrt(f))},
        }

    return {
        "embed": {"table": truncated(k_emb, (m["vocab_size"], d), 0.02)},
        "blocks": jax.vmap(layer)(jax.random.split(k_layers, m["num_layers"])),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
    }


def _rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary positions 0..S-1 on x (B, S, H, D): the two halves of each
    head are rotated as a pair."""
    s, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p: Params, x: jnp.ndarray, m: Dict[str, Any],
              precision: str) -> jnp.ndarray:
    b, s, _ = x.shape
    hd, nq, nkv = m["head_dim"], m["num_heads"], m["num_kv_heads"]
    q = dot("bsd,dh->bsh", x, p["wq"], precision).reshape(b, s, nq, hd)
    k = dot("bsd,dh->bsh", x, p["wk"], precision).reshape(b, s, nkv, hd)
    v = dot("bsd,dh->bsh", x, p["wv"], precision).reshape(b, s, nkv, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    # query head h reads key/value head h // (nq // nkv)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    scores = dot("bshd,bthd->bhst", q, k, precision) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = dot("bhst,bthd->bshd", jax.nn.softmax(scores, axis=-1), v,
              precision)
    return dot("bsh,hd->bsd", out.reshape(b, s, nq * hd), p["wo"], precision)


def experts(p: Params, x: jnp.ndarray, m: Dict[str, Any],
            precision: str):
    """Token-choice top-k mixture over (B, S, d); returns (out, aux).

    Each group of ``moe.group_tokens`` consecutive tokens of a row keeps
    its own queue per expert of ``capacity = ceil(group * k / E *
    capacity_factor)`` slots.  The k choices claim slots in rank order:
    every token's first choice before any token's second, and within a
    rank in token order; a choice that finds its expert's queue full is
    dropped.  A kept choice adds its renormalised router probability times
    the expert's output.  The auxiliary loss is E * sum_e (mean router
    probability of e) * (share of tokens whose first choice is e)."""
    b, s, d = x.shape
    cfg = m["moe"]
    e, k = cfg["num_experts"], cfg["top_k"]
    t = cfg["group_tokens"]
    xg = x.reshape(b * s // t, t, d)
    gates = jax.nn.softmax(dot("gtd,de->gte", xg, p["router"], precision), -1)
    top_p, top_i = jax.lax.top_k(gates, k)
    top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-9)
    capacity = math.ceil(t * k / e * cfg["capacity_factor"])

    weight = jnp.zeros(gates.shape, jnp.float32)      # (G, T, E)
    used = jnp.zeros((xg.shape[0], e), jnp.int32)     # slots taken per expert
    for j in range(k):
        chose = jax.nn.one_hot(top_i[..., j], e, dtype=jnp.int32)
        slot = used[:, None, :] + jnp.cumsum(chose, axis=1) - chose
        kept = (chose > 0) & (slot < capacity)
        weight = weight + jnp.where(kept, top_p[..., j:j + 1], 0.0)
        used = used + jnp.sum(chose, axis=1)

    gate = dot("gtd,edf->gtef", xg, p["w_gate"], precision)
    up = dot("gtd,edf->gtef", xg, p["w_up"], precision)
    y = dot("gtef,efd->gted", jax.nn.silu(gate) * up, p["w_down"], precision)
    out = jnp.einsum("gte,gted->gtd", weight, y,
                     precision=jax.lax.Precision.HIGHEST)
    first = jax.nn.one_hot(top_i[..., 0], e, dtype=jnp.float32)
    aux = e * jnp.sum(jnp.mean(gates, axis=(0, 1)) * jnp.mean(first, (0, 1)))
    return out.reshape(b, s, d), aux


def loss(params: Params, batch: Dict[str, jnp.ndarray], order: List[int],
         m: Dict[str, Any], precision: str) -> jnp.ndarray:
    """Cross-entropy of the next token plus ``router_aux_coef`` times the
    summed auxiliary losses, the layers applied in ``order``."""
    eps = m["rmsnorm_eps"]
    x = params["embed"]["table"][batch["tokens"]]
    blocks = params["blocks"]

    @jax.checkpoint
    def layer(x, lp):
        x = x + attention(lp["attn"], rmsnorm(x, lp["attn_norm"]["scale"],
                                              eps), m, precision)
        y, aux = experts(lp["mlp"], rmsnorm(x, lp["mlp_norm"]["scale"], eps),
                         m, precision)
        return x + y, aux

    aux_total = 0.0
    for i in order:
        x, aux = layer(x, jax.tree.map(lambda a: a[i], blocks))
        aux_total = aux_total + aux
    x = rmsnorm(x, params["final_norm"]["scale"], eps)
    logits = dot("bsd,vd->bsv", x, params["embed"]["table"], precision)
    return token_nll(logits, batch["labels"]) \
        + m["moe"]["router_aux_coef"] * aux_total


# Operations a training step requires, per token, from the shapes: forward
# and backward (three times the forward), no recomputation, causal
# attention and the causal half of the state-space quadratic form.  Matrix
# products count two operations per multiply-add; norms, activations and
# the optimizer are left out, as is customary for MFU.

def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward plus backward operations per token of a training step."""
    d, hd = m["d_model"], m["head_dim"]
    nq, nkv = m["num_heads"], m["num_kv_heads"]
    moe = m["moe"]
    attn = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
    router = d * moe["num_experts"]
    active_experts = moe["top_k"] * 3 * d * moe["d_ff_expert"]
    matmul_params = m["num_layers"] * (attn + router + active_experts) \
        + m["vocab_size"] * d                     # tied output projection
    # per token and layer, QK^T and PV over the (on average seq/2) keys
    # before it: 2 * 2 * hd * seq/2 per head forward
    attention = m["num_layers"] * nq * 2 * hd * seq
    return 3 * (2 * matmul_params + attention)
