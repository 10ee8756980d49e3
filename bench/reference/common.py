"""What every plain reference shares: the numerics switch, the optimizer,
the learning-rate schedule, CheckFree+'s swapped stage order and the
CheckFree recovery rules.

Written from the published descriptions (Adam, Kingma & Ba 2015; the
CheckFree paper's Algorithm 1 and its CheckFree+ swap schedule), not from
the program: nothing here imports ``repro``.  Every matrix product goes
through :func:`dot`, so one switch turns the whole reference into its
lower-precision control.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: precisions a reference can compute its matrix products in: "float32"
#: is the reference, "float8" its control (operands and results scaled per
#: tensor to float8_e4m3fn, the step below the configuration's bfloat16
#: compute), "bfloat16" the control of the float32 recovery arithmetic
PRECISIONS = ("float32", "bfloat16", "float8")
_FP8_MAX = 448.0


def quantize(x: jnp.ndarray, precision: str) -> jnp.ndarray:
    """``x`` rounded to ``precision`` and returned as float32."""
    x = x.astype(jnp.float32)
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
        scale = jax.lax.stop_gradient(scale)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def dot(eq: str, a: jnp.ndarray, b: jnp.ndarray, precision: str) -> jnp.ndarray:
    """``einsum(eq, a, b)`` accumulated in float32 at full precision, its
    operands and its result rounded to ``precision`` -- as a program whose
    compute type is ``precision`` keeps both.  The rounding is a
    straight-through cast, so gradients flow as through the exact
    product."""
    def q(x):
        return x + jax.lax.stop_gradient(quantize(x, precision) - x)
    return q(jnp.einsum(eq, q(a), q(b), precision=HIGHEST,
                        preferred_element_type=jnp.float32))


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def truncated(key: jax.Array, shape: Sequence[int], std: float) -> jnp.ndarray:
    """Normal draws cut at three standard deviations, scaled to ``std``."""
    return std * jax.random.truncated_normal(key, -3.0, 3.0, tuple(shape))


def token_nll(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean negative log-likelihood of ``labels`` under ``logits``."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# CheckFree+ swap schedule and the training loss over both halves
# ---------------------------------------------------------------------------

def swapped_layer_order(num_layers: int, num_stages: int) -> List[int]:
    """CheckFree+ runs half of each batch with the first two and the last
    two stages exchanged: S2, S1, S3, ..., SK, SK-1 (with four or more
    stages)."""
    per = num_layers // num_stages
    stages = list(range(num_stages))
    if num_stages >= 4:
        stages[0], stages[1] = stages[1], stages[0]
        stages[-1], stages[-2] = stages[-2], stages[-1]
    return [s * per + i for s in stages for i in range(per)]


def swap_loss(model_loss, params, batch: Dict[str, jnp.ndarray],
              num_layers: int, num_stages: int):
    """The mean of the in-order loss on the first half of the rows and the
    swapped-order loss on the second half."""
    half = batch["tokens"].shape[0] // 2
    first = {k: v[:half] for k, v in batch.items()}
    second = {k: v[half:] for k, v in batch.items()}
    order = list(range(num_layers))
    swapped = swapped_layer_order(num_layers, num_stages)
    return 0.5 * (model_loss(params, first, order)
                  + model_loss(params, second, swapped))


# ---------------------------------------------------------------------------
# Adam with global-norm clipping and warm-up + cosine learning rate
# ---------------------------------------------------------------------------

def learning_rate(opt: Dict[str, Any], step: int) -> float:
    """Linear warm-up over ``warmup_steps``, then cosine decay to
    ``min_lr_ratio`` of the peak at ``total_steps``; ``step`` counts from 1."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = (step - opt["warmup_steps"]) / max(opt["total_steps"]
                                          - opt["warmup_steps"], 1)
    t = min(max(t, 0.0), 1.0)
    r = opt["min_lr_ratio"]
    return opt["lr"] * warm * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * t)))


def adam_step(opt: Dict[str, Any], params, grads, m, v, step: jnp.ndarray):
    """One Adam update on float32 trees; gradients clipped to a global
    norm of ``grad_clip`` first.  ``step`` (counting from 1) and the
    learning rate (:func:`learning_rate`) arrive as traced scalars:
    ``step = (t, lr)``."""
    t, lr = step
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (norm + 1e-9))
    b1, b2 = opt["betas"]
    m = jax.tree.map(lambda mi, g: b1 * mi + (1 - b1) * g * scale, m, grads)
    v = jax.tree.map(lambda vi, g: b2 * vi + (1 - b2) * (g * scale) ** 2,
                     v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, mi, vi: p - lr * (mi / c1) / (jnp.sqrt(vi / c2)
                                                 + opt["eps"]),
        params, m, v)
    return params, m, v


# ---------------------------------------------------------------------------
# CheckFree recovery of a lost stage (Algorithm 1, and CheckFree+'s edges)
# ---------------------------------------------------------------------------

def recovered_stage(stages: List[Any], failed: int, omegas: Sequence[float],
                    precision: str = "float32") -> Any:
    """The weights a lost stage gets back.  A middle stage takes the
    average of its two neighbours weighted by their squared gradient
    norms; the first stage copies the second and the last copies the one
    before it (their swap-trained twins).  ``stages`` holds each stage's
    layers as a pytree."""
    k = len(stages)
    if failed == 0:
        return stages[1]
    if failed == k - 1:
        return stages[k - 2]
    wa, wb = omegas[failed - 1], omegas[failed + 1]
    return jax.tree.map(
        lambda a, b: quantize((wa * quantize(a, precision)
                               + wb * quantize(b, precision)) / (wa + wb),
                              precision),
        stages[failed - 1], stages[failed + 1])


def tree_paths(tree) -> List[Tuple[str, Any]]:
    """``[(path, leaf)]`` with paths like ``blocks/attn/wq``."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append(("/".join(str(getattr(p, "key", p)) for p in path), leaf))
    return out
