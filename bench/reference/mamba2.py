"""Plain reference of an attention-free Mamba-2 language model (Dao & Gu,
"Transformers are SSMs", arXiv:2405.21060): each layer is RMSNorm, one
input projection to (z, x, B, C, dt), a depthwise causal convolution with
SiLU over (x, B, C), the selective state-space recurrence with a scalar
decay per head, the skip term D, a gated RMSNorm and the output
projection, added to the residual.  A tied output embedding closes it.

The state-space part is computed as the paper's state-space duality
states it: within a chunk of ``chunk`` tokens the output is a masked
quadratic form, and chunks are joined through the state each one leaves.
Float32 throughout, one layer at a time.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from bench.reference.common import dot, rmsnorm, token_nll, truncated

Params = Dict[str, Any]


def _dims(m: Dict[str, Any]):
    s = m["ssm"]
    d_in = s["expand"] * m["d_model"]
    heads = d_in // s["head_dim"]
    gn = s["ngroups"] * s["state_dim"]
    return d_in, heads, gn, d_in + 2 * gn, 2 * d_in + 2 * gn + heads


def init(key: jax.Array, m: Dict[str, Any]) -> Params:
    """Float32 weights drawn from ``key`` (split as embedding, layers,
    head; each layer's key in five): projections truncated normal with
    standard deviation 1/sqrt(fan-in), convolution taps normal times 0.1,
    dt bias the inverse softplus of a step size log-uniform in
    [1e-3, 1e-1], A = -(1..16) spread over the heads, D = 1, norm scales
    1, the embedding truncated normal times 0.02."""
    d = m["d_model"]
    d_in, heads, _gn, conv_ch, proj = _dims(m)
    k_emb, k_layers, _k_head = jax.random.split(key, 3)

    def layer(k):
        ks = jax.random.split(k, 5)
        u = jax.random.uniform(ks[3], (heads,))
        dt0 = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return {
            "norm": {"scale": jnp.ones((d,), jnp.float32)},
            "w_in": truncated(ks[0], (d, proj), 1 / math.sqrt(d)),
            "conv_w": 0.1 * jax.random.normal(
                ks[1], (m["ssm"]["conv_width"], conv_ch)),
            "conv_b": jnp.zeros((conv_ch,), jnp.float32),
            "a_log": jnp.log(jnp.linspace(1.0, 16.0, heads)),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "d_skip": jnp.ones((heads,), jnp.float32),
            "gate_norm": {"scale": jnp.ones((d_in,), jnp.float32)},
            "w_out": truncated(ks[2], (d_in, d), 1 / math.sqrt(d_in)),
        }

    return {
        "embed": {"table": truncated(k_emb, (m["vocab_size"], d), 0.02)},
        "blocks": jax.vmap(layer)(jax.random.split(k_layers, m["num_layers"])),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
    }


def ssd(x: jnp.ndarray, a: jnp.ndarray, bm: jnp.ndarray, cm: jnp.ndarray,
        chunk: int, precision: str) -> jnp.ndarray:
    """y_t = sum_{s<=t} (C_t . B_s) exp(a_{s+1} + ... + a_t) x_s.

    x (B, T, H, P), a (B, T, H) log decays, bm/cm (B, T, G, N) with heads
    split evenly over the G groups."""
    b, t, h, p = x.shape
    g = bm.shape[2]
    bm = jnp.repeat(bm, h // g, axis=2)
    cm = jnp.repeat(cm, h // g, axis=2)
    nc = t // chunk
    xc, ac = x.reshape(b, nc, chunk, h, p), a.reshape(b, nc, chunk, h)
    bc = bm.reshape(b, nc, chunk, h, -1)
    cc = cm.reshape(b, nc, chunk, h, -1)
    cum = jnp.cumsum(ac, axis=2)                               # (b,c,q,h)
    # within a chunk: decay from token j to token i (j <= i)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]        # (b,c,i,j,h)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    scores = dot("bcihn,bcjhn->bcijh", cc, bc, precision) * decay
    y = dot("bcijh,bcjhp->bcihp", scores, xc, precision)
    # the state each chunk leaves, then carried across chunks
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)                  # (b,c,q,h)
    left = dot("bcqhn,bcqhp->bchpn", bc * to_end[..., None], xc, precision)
    whole = jnp.exp(cum[:, :, -1, :])                          # (b,c,h)

    def carry(state, inputs):
        left_c, whole_c = inputs
        return state * whole_c[:, :, None, None] + left_c, state

    _, before = jax.lax.scan(
        carry, jnp.zeros((b, h, p, bc.shape[-1]), jnp.float32),
        (jnp.moveaxis(left, 1, 0), jnp.moveaxis(whole, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                        # (b,c,h,p,n)
    y = y + dot("bcqhn,bchpn->bcqhp", cc * jnp.exp(cum)[..., None], before,
                precision)
    return y.reshape(b, t, h, p)


def layer(lp: Params, x: jnp.ndarray, m: Dict[str, Any],
          precision: str) -> jnp.ndarray:
    s = m["ssm"]
    eps = m["rmsnorm_eps"]
    b, t, _ = x.shape
    d_in, heads, gn, conv_ch, _proj = _dims(m)
    zxbcdt = dot("btd,dk->btk", rmsnorm(x, lp["norm"]["scale"], eps),
                 lp["w_in"], precision)
    z, xbc, dt = (zxbcdt[..., :d_in], zxbcdt[..., d_in:d_in + conv_ch],
                  zxbcdt[..., d_in + conv_ch:])
    width = s["conv_width"]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + t] * lp["conv_w"][i] for i in range(width))
    xbc = jax.nn.silu(conv + lp["conv_b"])
    xs = xbc[..., :d_in].reshape(b, t, heads, s["head_dim"])
    bm = xbc[..., d_in:d_in + gn].reshape(b, t, s["ngroups"], -1)
    cm = xbc[..., d_in + gn:].reshape(b, t, s["ngroups"], -1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                   # (b,t,h)
    a = dt * -jnp.exp(lp["a_log"])
    y = ssd(xs * dt[..., None], a, bm, cm, min(s["chunk_size"], t), precision)
    y = (y + xs * lp["d_skip"][:, None]).reshape(b, t, d_in)
    y = rmsnorm(y * jax.nn.silu(z), lp["gate_norm"]["scale"], eps)
    return x + dot("btk,kd->btd", y, lp["w_out"], precision)


def loss(params: Params, batch: Dict[str, jnp.ndarray], order: List[int],
         m: Dict[str, Any], precision: str) -> jnp.ndarray:
    """Cross-entropy of the next token, the layers applied in ``order``."""
    x = params["embed"]["table"][batch["tokens"]]
    step = jax.checkpoint(lambda x, lp: layer(lp, x, m, precision))
    for i in order:
        x = step(x, jax.tree.map(lambda a: a[i], params["blocks"]))
    x = rmsnorm(x, params["final_norm"]["scale"], m["rmsnorm_eps"])
    logits = dot("btd,vd->btv", x, params["embed"]["table"], precision)
    return token_nll(logits, batch["labels"])


# Operations a training step requires, per token, from the shapes: forward
# and backward (three times the forward), no recomputation, causal
# attention and the causal half of the state-space quadratic form.  Matrix
# products count two operations per multiply-add; norms, activations and
# the optimizer are left out, as is customary for MFU.

def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward plus backward operations per token of a training step."""
    d, s = m["d_model"], m["ssm"]
    d_in = s["expand"] * d
    heads = d_in // s["head_dim"]
    gn = s["ngroups"] * s["state_dim"]
    conv_ch = d_in + 2 * gn
    proj = 2 * d_in + 2 * gn + heads
    matmul_params = m["num_layers"] * (d * proj + d_in * d) \
        + m["vocab_size"] * d
    q, p, n = min(s["chunk_size"], seq), s["head_dim"], s["state_dim"]
    # per token and layer, forward: C.B over the causal half of its chunk
    # (per group), the weighted sum of x over it (per head), the chunk
    # state it adds to and the state it reads (per head), the conv taps
    ssd = (s["ngroups"] * q * n + heads * q * p + 2 * 2 * heads * p * n
           + 2 * s["conv_width"] * conv_ch)
    return 3 * (2 * matmul_params + m["num_layers"] * ssd)
