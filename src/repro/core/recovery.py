"""CheckFree recovery (paper Algorithm 1) + the ablation reinit strategies.

The failed stage ``i`` is replaced by

    W_i <- (omega_{i-1} W_{i-1} + omega_{i+1} W_{i+1}) / (omega_{i-1}+omega_{i+1})

with ``omega_j = ||grad W_j||^2`` (CheckFree), or by uniform averaging /
copying / random reinit (the Fig. 2 ablation).  Edge stages use the
CheckFree+ twin-copy path (the swap schedule trains S2 to mimic S1 and
S_{K-1} to mimic S_K).

All functions are pure pytree -> pytree; the elementwise merge dispatches to
the ``stage_merge`` Pallas kernel when ``use_kernel=True`` (TPU hot path —
the merge is HBM-bandwidth-bound over the whole stage).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.stages import StagePartition

Params = Dict[str, Any]


def _merge_trees(a: Params, b: Params, wa: jnp.ndarray, wb: jnp.ndarray,
                 use_kernel: bool = False) -> Params:
    """(wa*a + wb*b) / (wa+wb), elementwise over the stage pytree."""
    denom = wa + wb + 1e-30
    ca = wa / denom
    cb = wb / denom
    if use_kernel:
        from repro.kernels import ops as K
        return jax.tree.map(lambda x, y: K.stage_merge(x, y, ca, cb), a, b)
    return jax.tree.map(
        lambda x, y: (ca * x.astype(jnp.float32) +
                      cb * y.astype(jnp.float32)).astype(x.dtype), a, b)


def _align_layers(stage: Params, n: int, side: str) -> Params:
    """Fit a neighbour's stage slice to ``n`` layers for the merge.

    Variable layouts (elastic re-layout, docs/elastic.md) can give the two
    neighbours different layer counts than the failed stage.  The merge
    pairs each lost layer with the neighbour layer *nearest* the shared
    stage boundary — the last ``n`` layers of the previous stage, the first
    ``n`` of the next — repeating the boundary layer when the neighbour is
    smaller.  Uniform layouts pass through untouched (bit-identical).
    """
    def pick(x):
        m = x.shape[0]
        if m == n:
            return x
        if side == "prev":
            idx = jnp.clip(jnp.arange(m - n, m), 0, m - 1)
        else:
            idx = jnp.clip(jnp.arange(n), 0, m - 1)
        return x[idx]
    return jax.tree.map(pick, stage)


def recover_stage(params: Params, part: StagePartition, failed: int,
                  omegas: jnp.ndarray, *, strategy: str = "grad_norm",
                  key: Optional[jax.Array] = None,
                  use_kernel: bool = False) -> Params:
    """Reinitialize stage ``failed`` (0-based within the tower).

    strategy:
      grad_norm  — Alg. 1 weighted average (CheckFree)
      uniform    — plain average of the two neighbours
      copy_prev  — copy the previous stage (layer-stacking baseline)
      random     — random reinit (worst baseline in Fig. 2)
      twin_copy  — CheckFree+ edge-stage path: copy the swap-twin
    """
    k = part.num_stages
    first, last = failed == 0, failed == k - 1

    if strategy == "random":
        assert key is not None
        stage = part.get_stage(params, failed)
        leaves, treedef = jax.tree_util.tree_flatten(stage)
        keys = jax.random.split(key, len(leaves))
        new = [0.02 * jax.random.normal(kk, x.shape, jnp.float32
                                        ).astype(x.dtype)
               for kk, x in zip(keys, leaves)]
        return part.set_stage(params, failed,
                              jax.tree_util.tree_unflatten(treedef, new))

    if strategy == "twin_copy" or ((first or last) and
                                   strategy in ("grad_norm", "uniform")):
        # CheckFree+ edge recovery: S1 <- S2 (swap-trained twin), SK <- SK-1
        twin = 1 if first else (k - 2 if last else failed - 1)
        side = "next" if twin > failed else "prev"
        return part.set_stage(params, failed, _align_layers(
            part.get_stage(params, twin), part.layer_counts[failed], side))

    if strategy == "copy_prev":
        src = failed - 1 if failed > 0 else failed + 1
        side = "prev" if src < failed else "next"
        return part.set_stage(params, failed, _align_layers(
            part.get_stage(params, src), part.layer_counts[failed], side))

    # weighted / uniform average of the two neighbours (intermediate stages)
    assert 0 < failed < k - 1, "edge stages need CheckFree+ (twin_copy)"
    n = part.layer_counts[failed]
    prev_s = _align_layers(part.get_stage(params, failed - 1), n, "prev")
    next_s = _align_layers(part.get_stage(params, failed + 1), n, "next")
    if strategy == "uniform":
        wa = jnp.ones(())
        wb = jnp.ones(())
    else:  # grad_norm (Alg. 1)
        wa = omegas[failed - 1].astype(jnp.float32)
        wb = omegas[failed + 1].astype(jnp.float32)
    merged = _merge_trees(prev_s, next_s, wa, wb, use_kernel=use_kernel)
    return part.set_stage(params, failed, merged)


def recover_consecutive(params: Params, part: StagePartition,
                        failed_run: "list[int]", omegas: jnp.ndarray, *,
                        use_kernel: bool = False) -> Params:
    """BEYOND-PAPER: recover a run of CONSECUTIVE failed stages [i..j].

    The paper cannot recover consecutive failures ("no neighboring stages
    for the reinitialization") and defers to future work (§6).  We close the
    gap with distance-weighted interpolation between the surviving flanks:
    stage k in the run is initialized from the survivors p = i-1 and
    q = j+1 with weights combining Alg. 1's gradient norms and the linear
    distance across the gap:

        a_k = omega_p * (q - k),  b_k = omega_q * (k - p)
        W_k = (a_k W_p + b_k W_q) / (a_k + b_k)

    For a run of length 1 this reduces exactly to Alg. 1.  Edge-touching
    runs (i == 0 or j == K-1) fall back to copying the single survivor into
    every lost stage (the CheckFree+ twin-copy generalization).
    """
    run = sorted(failed_run)
    assert run == list(range(run[0], run[-1] + 1)), run
    i, j = run[0], run[-1]
    k_stages = part.num_stages
    p, q = i - 1, j + 1
    if p < 0 or q >= k_stages:
        src = q if p < 0 else p
        assert 0 <= src < k_stages, "entire pipeline lost"
        stage = part.get_stage(params, src)
        side = "next" if p < 0 else "prev"
        out = params
        for k in run:
            out = part.set_stage(
                out, k, _align_layers(stage, part.layer_counts[k], side))
        return out
    prev_s = part.get_stage(params, p)
    next_s = part.get_stage(params, q)
    out = params
    for k in run:
        n = part.layer_counts[k]
        a = omegas[p].astype(jnp.float32) * (q - k)
        b = omegas[q].astype(jnp.float32) * (k - p)
        merged = _merge_trees(_align_layers(prev_s, n, "prev"),
                              _align_layers(next_s, n, "next"),
                              a, b, use_kernel=use_kernel)
        out = part.set_stage(out, k, merged)
    return out


def zero_stages(tree: Params, part: StagePartition,
                stages: "list[int]") -> Params:
    """``tree`` with every leaf of the lost ``stages`` set to zero: the
    failed node's Adam moments are gone with it."""
    for stage in stages:
        zeros = jax.tree.map(jnp.zeros_like, part.get_stage(tree, stage))
        tree = part.set_stage(tree, stage, zeros)
    return tree


def recovery_error(params_before: Params, params_after: Params,
                   part: StagePartition, failed: int) -> jnp.ndarray:
    """||omega1 f_{k+1} + omega2 f_{k-1} - f_k||^2 — the per-failure error term
    from the paper's convergence bound (§4.4), measured directly."""
    a = part.get_stage(params_before, failed)
    b = part.get_stage(params_after, failed)
    sq = [jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))
          for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    return jnp.sum(jnp.stack(sq))
