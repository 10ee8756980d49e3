"""Adam optimizer + LR schedules in pure JAX (the paper uses Adam,
betas=(0.9, 0.999), no weight decay).

The optimizer state is a pytree mirroring the params (m, v) plus a step
counter; everything composes with pjit/shard_map since it is just tree maps.
The CheckFree recovery manager resets the (m, v) slices of a recovered stage
to zero — exposed via :func:`reset_state_subtree`.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import OptimizerConfig

Params = Any


class OptState(NamedTuple):
    m: Params
    v: Params
    step: jnp.ndarray  # scalar int32


def init_adam(params: Params) -> OptState:
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    return OptState(m=zeros,
                    v=jax.tree.map(jnp.copy, zeros),
                    step=jnp.zeros((), jnp.int32))


def global_norm(tree: Params) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(grads: Params, max_norm: float,
                        ) -> Tuple[Params, jnp.ndarray]:
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (gn + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), grads), gn


def lr_schedule(cfg: OptimizerConfig, step: jnp.ndarray) -> jnp.ndarray:
    """Warmup + {cosine, linear, constant} decay."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    t = jnp.clip((step - cfg.warmup_steps) /
                 jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    if cfg.schedule == "cosine":
        decay = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * \
            0.5 * (1 + jnp.cos(jnp.pi * t))
    elif cfg.schedule == "linear":
        decay = 1.0 - (1 - cfg.min_lr_ratio) * t
    else:  # constant
        decay = jnp.ones(())
    return cfg.lr * warm * decay


@jax.named_scope("adam")
def adam_update(cfg: OptimizerConfig, params: Params, grads: Params,
                state: OptState, lr_scale: jnp.ndarray | float = 1.0,
                *, grad_norm: Optional[jnp.ndarray] = None,
                ) -> Tuple[Params, OptState, Dict[str, jnp.ndarray]]:
    """One Adam step.  ``lr_scale`` carries CheckFree's 1.1x recovery boost.

    ``grad_norm`` overrides the locally computed global grad norm — the
    SPMD pipeline backend passes the psum-assembled *mesh-global* norm so
    each device clips its shard by the same factor the host backend would
    use on the gathered tree.
    """
    if grad_norm is None:
        gn = global_norm(grads)
    else:
        gn = grad_norm
    if cfg.grad_clip > 0:
        scale = jnp.minimum(1.0, cfg.grad_clip / (gn + 1e-9))
        grads = jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                        ).astype(g.dtype), grads)
    step = state.step + 1
    b1, b2 = cfg.betas
    lr = lr_schedule(cfg, step) * lr_scale

    new_m = jax.tree.map(
        lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
        state.m, grads)
    new_v = jax.tree.map(
        lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
        state.v, grads)
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, m, v):
        mh = m / bc1
        vh = v / bc2
        delta = lr * mh / (jnp.sqrt(vh) + cfg.eps)
        if cfg.weight_decay > 0:
            delta = delta + lr * cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - delta).astype(p.dtype)

    new_params = jax.tree.map(upd, params, new_m, new_v)
    return new_params, OptState(new_m, new_v, step), {"grad_norm": gn,
                                                      "lr": lr}


def reset_state_subtree(state: OptState, mask_fn) -> OptState:
    """Zero the Adam moments wherever ``mask_fn(path, leaf)`` says so.

    Used by CheckFree after a stage recovery: the merged weights get fresh
    moments (the failed stage's optimizer state died with the node).
    """
    def zero_where(tree):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.where(mask_fn(path, leaf),
                                         jnp.zeros_like(leaf), leaf), tree)

    return OptState(zero_where(state.m), zero_where(state.v), state.step)
