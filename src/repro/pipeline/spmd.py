"""Paper-faithful pipeline parallelism as shard_map + lax.ppermute — the
SPMD **training backend** behind ``Trainer(backend="spmd")``.

This is the TPU-native translation of the paper's setting (DESIGN.md §3):
the mesh's ``"stage"`` axis *is* the pipeline; each device holds a
contiguous slice of the stacked block tower (axis 0 sharded over "stage"),
microbatch activations rotate stage-to-stage with ``lax.ppermute`` in a
GPipe schedule, and the backward pass reverses the permutes automatically
(ppermute is differentiable) — no NCCL emulation anywhere.

Three layers of machinery live here:

* :func:`pipeline_loss` — the forward pipeline loss (parity oracle for the
  subprocess check; kept API-stable).
* :func:`make_spmd_fused_train_step` — the full training step: one
  ``shard_map`` wrapping a fused loop window of
  grad -> psum -> Adam steps.  Per-device autodiff differentiates the
  *pre-psum* local loss (the global loss is the sum of per-device partial
  losses, so local grads of the tower slice are exact and only the
  replicated (de)embedding grads need one ``psum``); per-stage omegas are
  a single in-mesh ``psum`` of the local tower-grad square norm; Adam
  state stays stage-sharded alongside the tower for the whole window.
* :func:`checkfree_recover_spmd` / :func:`make_in_mesh_recover` — recovery
  as collectives.  Middle stages: the failed stage's two neighbours
  ``ppermute`` their weight slices one hop each and the receiving device
  applies the Alg. 1 weighted merge locally (2 x |stage| bytes over one
  ICI hop each — the paper's "new node receives W_{i-1}, W_{i+1}").
  Edge stages (CheckFree+): the swap-trained twin's slice hops one stage
  and the replicated (de)embeddings need no transfer at all — replication
  *is* the restore.

Scope: dense/MoE decoder-only towers with homogeneous blocks (the paper's
LLaMa configs).  The embedding/head (paper's S0) are replicated — exactly
the CheckFree+ replication path for (de)embeddings.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, OptimizerConfig
from repro.core.stages import StagePartition
from repro.core.swap import stage_permutations
from repro.models import layers as L
from repro.models import transformer as T
from repro.optim.adam import OptState, adam_update

Params = Dict[str, Any]


def stage_index(axis: str = "stage") -> jnp.ndarray:
    return jax.lax.axis_index(axis)


def param_pipeline_specs(params: Params, num_stages: int) -> Params:
    """PartitionSpecs: block tower sharded over 'stage' on axis 0, rest
    replicated (the S0 replication path)."""
    def spec(path, leaf):
        top = path[0].key if hasattr(path[0], "key") else str(path[0])
        if top == "blocks":
            return P("stage")
        return P()
    return jax.tree_util.tree_map_with_path(spec, params)


def opt_pipeline_specs(pspecs: Params) -> OptState:
    """Adam moments mirror the param sharding; the step counter is
    replicated."""
    return OptState(m=pspecs, v=pspecs, step=P())


def pipeline_state_shardings(mesh: Mesh, state: Tuple[Params, OptState],
                             ) -> Tuple[Params, OptState]:
    """``NamedSharding``s of ``(params, opt_state)`` as the fused step keeps
    them on ``mesh``: the specs of :func:`param_pipeline_specs` and
    :func:`opt_pipeline_specs` (``state`` may hold shapes only)."""
    params, _ = state
    pspecs = param_pipeline_specs(params, mesh.shape["stage"])
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        (pspecs, opt_pipeline_specs(pspecs)),
                        is_leaf=lambda x: isinstance(x, P))


def _apply_local_blocks(cfg: ModelConfig, blocks_local: Params,
                        x: jnp.ndarray, positions: jnp.ndarray,
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Run this device's slice of the tower over one microbatch.

    Returns ``(hidden, aux)`` where ``aux`` is the summed router auxiliary
    loss of the local blocks (zero for dense archs).
    """
    s = x.shape[1]
    full_mask = L.causal_mask(s, s)
    block = T._block_apply(cfg)

    def step(carry, bp):
        out, aux = block(carry, bp, full_mask, full_mask,
                         jnp.zeros((), bool), positions)
        return out, aux

    x, auxs = jax.lax.scan(step, x, blocks_local)
    return x, jnp.sum(auxs)


def _tick_perm(t: int, num_stages: int, num_microbatches: int,
               ) -> List[Tuple[int, int]]:
    """The live stage->stage sends at GPipe tick ``t``.

    Stage ``s`` holds microbatch ``t - s`` at tick ``t``; the send to
    ``s + 1`` is live iff that microbatch exists (``0 <= t - s <= M - 1``).
    Narrowing the permute to live lanes keeps the fill/drain bubbles from
    rotating dead activations across the mesh; devices outside the
    permutation receive zeros, which is exactly what their (dead) lanes
    should carry.
    """
    lo = max(0, t - num_microbatches + 1)
    hi = min(t, num_stages - 2)
    return [(i, i + 1) for i in range(lo, hi + 1)]


def _pipeline_forward(cfg: ModelConfig, cparams: Params, blocks: Params,
                      tokens: jnp.ndarray, labels: jnp.ndarray,
                      num_stages: int, num_microbatches: int,
                      loss_mask: Optional[jnp.ndarray] = None,
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One GPipe schedule over the 'stage' axis, per-device view.

    Returns the **pre-psum per-device partial** ``(ce, aux)``: the cross
    entropy lives on the last stage only and the router aux loss on every
    stage's live lanes, so ``psum(ce)`` / ``psum(aux)`` are the batch
    means.  ``psum(ce)`` equals the host backend's global (mask-weighted)
    mean exactly; ``psum(aux)`` is the mean of per-microbatch aux losses —
    MoE routing and capacity dropping are per-microbatch under GPipe, so
    for MoE towers with M > 1 this is the standard pipeline objective
    rather than the full-batch ``model.loss`` aux (equal for dense towers
    at any M, and for MoE at M = 1).
    Differentiating this partial (NOT the psum'd total) gives exact local
    tower grads — the global loss is the sum of per-device partials, and
    under shard_map the transpose of ``psum`` is ``psum``, which would
    overcount a post-psum loss by the axis size.

    ``blocks`` is passed separately from ``cparams`` so the CheckFree+
    swap variant can feed a ppermute-hopped tower while the replicated
    (de)embeddings stay in place.

    Drain ticks (``t >= M``) inject nothing: stage 0's bubble is idle
    zeros instead of a redundant re-embed of the last microbatch, and the
    narrowed per-tick permutes stop rotating dead activations.
    """
    K, M = num_stages, num_microbatches
    my = jax.lax.axis_index("stage")
    b, s = tokens.shape
    assert b % M == 0, (b, M)
    mb = b // M
    toks = tokens.reshape(M, mb, s)
    labs = labels.reshape(M, mb, s)
    masks = (loss_mask.reshape(M, mb, s)
             if loss_mask is not None else None)
    # per-microbatch CE means are combined into the host backend's GLOBAL
    # mean: equal 1/M weights unmasked, valid-token-count weights masked
    # (mean-of-means would diverge when mask density varies per microbatch)
    if masks is None:
        ce_w = jnp.full((M,), 1.0 / M, jnp.float32)
    else:
        counts = jnp.sum(masks.reshape(M, -1).astype(jnp.float32), axis=1)
        ce_w = counts / jnp.maximum(jnp.sum(counts), 1e-9)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (mb, s))
    dt = jnp.dtype(cfg.dtype)

    h_recv = jnp.zeros((mb, s, cfg.d_model), dt)
    ce_acc = jnp.zeros((), jnp.float32)
    aux_acc = jnp.zeros((), jnp.float32)
    for t in range(M + K - 1):
        if t < M:
            # stage 0 injects microbatch t; others take the activation
            # received from the previous stage
            inject = T.embed_tokens(cparams, cfg, toks[t], positions)
            h_in = jnp.where(my == 0, inject, h_recv)
        else:
            h_in = h_recv           # drain: the bubble is idle, not redundant
        h_out, aux = _apply_local_blocks(cfg, blocks, h_in, positions)
        # this stage's lane is live iff it holds a real microbatch now
        live = (t - my >= 0) & (t - my <= M - 1)
        aux_acc = aux_acc + jnp.where(live, aux, 0.0)
        # the last stage finishes microbatch t-(K-1) at tick t
        if t >= K - 1:
            m = t - (K - 1)
            logits = T.logits_from_hidden(cparams, cfg, h_out)
            ce = L.cross_entropy(logits, labs[m],
                                 masks[m] if masks is not None else None)
            ce_acc = ce_acc + jnp.where(my == K - 1, ce * ce_w[m], 0.0)
        if t < M + K - 2:
            h_recv = jax.lax.ppermute(h_out, "stage", _tick_perm(t, K, M))
    return ce_acc, aux_acc / M


def _swap_block_perm(num_stages: int) -> List[Tuple[int, int]]:
    """ppermute pairs realizing CheckFree+'s swapped stage order: device d
    must apply the blocks of stage ``swapped[d]``, so the stage-s slice
    hops from device s to every d with ``swapped[d] == s`` (identity hops
    omitted — those devices keep their own slice)."""
    _, swapped = stage_permutations(num_stages)
    return [(src, dst) for dst, src in enumerate(swapped) if src != dst]


def _swapped_blocks(blocks: Params, pairs: List[Tuple[int, int]]) -> Params:
    """The swap-schedule tower: neighbour slices hop ONE stage via ppermute
    (no host-side layer gather).  Gradients flow back through the reversed
    permute to each slice's original holder."""
    if not pairs:
        return blocks
    my = jax.lax.axis_index("stage")
    moved = functools.reduce(jnp.logical_or,
                             [my == dst for _, dst in pairs])
    hopped = jax.tree.map(
        lambda w: jax.lax.ppermute(w, "stage", pairs), blocks)
    return jax.tree.map(lambda own, hop: jnp.where(moved, hop, own),
                        blocks, hopped)


def pipeline_loss(cfg: ModelConfig, mesh: Mesh, num_stages: int,
                  num_microbatches: int):
    """Build a jitted pipeline-parallel loss fn over the 'stage' mesh axis.

    Returns ``loss_fn(params, tokens, labels) -> scalar`` where tokens/labels
    are (B, S) with B divisible by ``num_microbatches``.  The schedule is
    GPipe: M + K - 1 pipeline ticks, activations hop stages via ppermute.
    The scalar is the full training objective (CE plus the router aux loss
    for MoE towers).  It matches ``model.loss``'s total for dense towers
    (any M) and MoE at M = 1; for MoE with M > 1 the aux term is the mean
    of per-microbatch aux losses — routing/capacity are per-microbatch
    under GPipe (see :func:`_pipeline_forward`).
    """
    assert cfg.arch_type in ("dense", "moe"), cfg.arch_type
    assert cfg.sliding_window == 0, "pipeline path: full attention only"
    K, M = num_stages, num_microbatches

    def per_device(params, tokens, labels):
        cparams = L.cast_tree(params, cfg.dtype)
        ce, aux = _pipeline_forward(cfg, cparams, cparams["blocks"],
                                    tokens, labels, K, M)
        total = ce + cfg.moe.router_aux_coef * aux
        # every stage ends with the global loss (for grads + logging)
        return jax.lax.psum(total, "stage")

    @functools.partial(jax.jit)
    def loss_fn(params, tokens, labels):
        specs = param_pipeline_specs(params, K)
        f = shard_map(
            per_device, mesh=mesh,
            in_specs=(specs, P(), P()), out_specs=P())
        return f(params, tokens, labels)

    return loss_fn


# ---------------------------------------------------------------------------
# the SPMD training backend: fused grad -> psum -> Adam windows
# ---------------------------------------------------------------------------

def make_spmd_fused_train_step(model, opt_cfg: OptimizerConfig,
                               part: StagePartition, mesh: Mesh,
                               num_microbatches: int, *,
                               use_swap: bool = False,
                               lr_decay: float = 1.0):
    """Build the pipeline-parallel fused K-step train step.

    Same contract as :func:`repro.core.trainer.make_fused_train_step`:
    ``fused(params, opt_state, stacked, lr_scale)`` scans one train step
    per leading-axis slice of ``stacked`` and returns
    ``(params, opt_state, lr_scale, outs)`` with the per-step metric rings
    (``loss`` / ``ce`` / ``aux`` / ``grad_norm`` / ``lr`` / ``omegas``)
    still on device — so the Trainer's window driver runs unmodified on
    either backend.  The differences are *where* things live:

    * the block tower and both Adam moments stay sharded over the 'stage'
      axis for the whole window (specs from :func:`param_pipeline_specs`);
    * per-stage omegas are one in-mesh ``psum`` of the local tower-grad
      square norm (each device's slice IS its stage's omega);
    * the global grad-clip norm combines ``psum``'d tower norms with the
      (already replicated) embedding-grad norms, so clipping matches the
      host backend's ``global_norm`` exactly;
    * with ``use_swap`` (CheckFree+), half the batch runs the swapped
      stage order: the swapped tower is built by hopping neighbour slices
      one stage via ppermute (:func:`_swapped_blocks`).

    The static replication checker is disabled (``check_vma=False``): it
    rejects grad-inside-shard_map over looped collectives; semantics are
    unaffected (it is a static check only).
    """
    cfg = model.cfg
    assert cfg.arch_type in ("dense", "moe"), (
        f"spmd backend supports dense/moe towers, not {cfg.arch_type}")
    assert cfg.sliding_window == 0, "pipeline path: full attention only"
    assert part.tower_key == "blocks", part.tower_key
    K, M = part.num_stages, num_microbatches
    swap_pairs = _swap_block_perm(K) if use_swap else []
    # deferred: trainer imports this module lazily, never the reverse at
    # module scope
    from repro.core.trainer import _jit_donated, window_loop

    def local_loss(params, batch):
        cparams = L.cast_tree(params, cfg.dtype)
        blocks = cparams["blocks"]
        tokens, labels = batch["tokens"], batch["labels"]
        mask = batch.get("loss_mask")
        if use_swap:
            half = tokens.shape[0] // 2
            assert half % M == 0, (
                f"swap schedule: half-batch {half} not divisible into "
                f"{M} microbatches")
            ce1, aux1 = _pipeline_forward(
                cfg, cparams, blocks, tokens[:half], labels[:half], K, M,
                None if mask is None else mask[:half])
            ce2, aux2 = _pipeline_forward(
                cfg, cparams, _swapped_blocks(blocks, swap_pairs),
                tokens[half:], labels[half:], K, M,
                None if mask is None else mask[half:])
            ce = 0.5 * (ce1 + ce2)
            aux = 0.5 * (aux1 + aux2)
        else:
            ce, aux = _pipeline_forward(cfg, cparams, blocks, tokens,
                                        labels, K, M, mask)
        total = ce + cfg.moe.router_aux_coef * aux
        return total, (ce, aux)

    def per_device(params, opt_state, stacked, lr_scale, n):
        my = jax.lax.axis_index("stage")

        def body(carry, batch):
            params, opt_state, ls = carry
            (total, (ce, aux)), grads = jax.value_and_grad(
                local_loss, has_aux=True)(params, batch)
            # the (de)embedding/norm grads are partial per device (each
            # stage only saw its own lanes' use of them); one psum makes
            # them the true replicated grads.  Tower grads are exact
            # locally — the pre-psum loss partials sum to the global loss.
            grads = {
                k: (v if k == "blocks" else
                    jax.tree.map(lambda g: jax.lax.psum(g, "stage"), v))
                for k, v in grads.items()}
            # Alg. 1's omegas: this device's tower-slice grad square norm
            # IS omega_my; one psum of the one-hot assembles the vector
            local_om = jnp.zeros((), jnp.float32)
            for g in jax.tree.leaves(grads["blocks"]):
                local_om += jnp.sum(jnp.square(g.astype(jnp.float32)))
            omegas = jax.lax.psum(
                jnp.where(jnp.arange(K) == my, local_om, 0.0), "stage")
            repl_sq = jnp.zeros((), jnp.float32)
            for k, v in grads.items():
                if k != "blocks":
                    for g in jax.tree.leaves(v):
                        repl_sq += jnp.sum(jnp.square(g.astype(jnp.float32)))
            gn = jnp.sqrt(jax.lax.psum(local_om, "stage") + repl_sq)
            params, opt_state, opt_metrics = adam_update(
                opt_cfg, params, grads, opt_state, ls, grad_norm=gn)
            ls_next = 1.0 + (ls - 1.0) * lr_decay
            ring = {"ce": jax.lax.psum(ce, "stage"),
                    "aux": jax.lax.psum(aux, "stage")}
            ring.update(opt_metrics)        # grad_norm, lr (replicated)
            ring.update(loss=jax.lax.psum(total, "stage"), omegas=omegas)
            return (params, opt_state, ls_next), ring

        carry0 = (params, opt_state, jnp.asarray(lr_scale, jnp.float32))
        (params, opt_state, ls), outs = window_loop(body, carry0, stacked, n)
        return params, opt_state, ls, outs

    @_jit_donated
    def fused_step(params, opt_state, stacked, lr_scale, n):
        pspecs = param_pipeline_specs(params, K)
        f = shard_map(
            per_device, mesh=mesh,
            in_specs=(pspecs, opt_pipeline_specs(pspecs), P(), P(), P()),
            out_specs=(pspecs, opt_pipeline_specs(pspecs), P(), P()),
            check_vma=False)
        return f(params, opt_state, stacked, lr_scale, n)

    return fused_step


# ---------------------------------------------------------------------------
# recovery as collectives
# ---------------------------------------------------------------------------

# the reinit modes expressible as neighbour-hop collectives; the single
# source of truth — MergeRecovery routes exactly these in-mesh
IN_MESH_REINITS = ("grad_norm", "uniform", "copy_prev", "twin_copy")


def checkfree_recover_spmd(mesh: Mesh, num_stages: int):
    """Build the collective recovery: the failed stage's device receives
    neighbour weight slices over one ICI hop each and rebuilds in place.

    Returns ``recover(blocks, omegas, failed, strategy="grad_norm") ->
    blocks`` operating on the 'stage'-sharded tower.  ``failed`` is static
    (a recovery event compiles its own tiny program — it runs once per
    failure, paper: ~30 s budget).  Reinit modes mirror
    :func:`repro.core.recovery.recover_stage` bit-for-bit:

    * ``grad_norm`` / ``uniform`` — middle stages: Alg. 1 weighted merge
      of both neighbours' slices (two one-hop ppermutes); edge stages
      degrade to the CheckFree+ twin copy, exactly like the host path.
    * ``twin_copy`` — the swap-trained twin's slice hops one stage
      (S_first <- S_1, S_last <- S_{K-2}); the replicated (de)embeddings
      on the replacement device need no transfer — replication is the
      restore.
    * ``copy_prev`` — the layer-stacking baseline: previous stage's slice
      (next stage's for S_first).
    """
    K = num_stages

    def make(failed: int, strategy: str):
        first, last = failed == 0, failed == K - 1
        if strategy == "copy_prev":
            srcs = [failed - 1 if failed > 0 else failed + 1]
        elif strategy == "twin_copy" or first or last:
            # CheckFree+ edge path (grad_norm/uniform degrade to it too,
            # matching core/recovery.recover_stage)
            srcs = [1 if first else (K - 2 if last else failed - 1)]
        else:
            srcs = [failed - 1, failed + 1]

        def per_device(blocks, omegas):
            my = jax.lax.axis_index("stage")
            hops = [jax.tree.map(
                lambda w: jax.lax.ppermute(w, "stage", [(s, failed)]),
                blocks) for s in srcs]
            if len(srcs) == 1:
                return jax.tree.map(
                    lambda old, a: jnp.where(my == failed, a, old),
                    blocks, hops[0])
            if strategy == "uniform":
                wa = jnp.ones(())
                wb = jnp.ones(())
            else:  # grad_norm (Alg. 1)
                wa = omegas[failed - 1]
                wb = omegas[failed + 1]
            denom = wa + wb + 1e-30

            def merge(old, a, b):
                m = (wa * a.astype(jnp.float32) +
                     wb * b.astype(jnp.float32)) / denom
                return jnp.where(my == failed, m.astype(old.dtype), old)

            return jax.tree.map(merge, blocks, *hops)

        return jax.jit(shard_map(
            per_device, mesh=mesh,
            in_specs=(P("stage"), P()), out_specs=P("stage")))

    cache: Dict[Tuple[int, str], Any] = {}

    def recover(blocks: Params, omegas: jnp.ndarray, failed: int,
                strategy: str = "grad_norm") -> Params:
        assert 0 <= failed < K, (failed, K)
        if strategy not in IN_MESH_REINITS:
            raise ValueError(
                f"no in-mesh collective for reinit {strategy!r}; "
                f"supported: {IN_MESH_REINITS}")
        key = (failed, strategy)
        if key not in cache:
            cache[key] = make(failed, strategy)
        return cache[key](blocks, jnp.asarray(omegas, jnp.float32))

    return recover


def make_in_mesh_recover(mesh: Mesh, part: StagePartition):
    """Adapt :func:`checkfree_recover_spmd` to the full param pytree — the
    ``recover_in_mesh`` capability hook recovery strategies bind to.

    ``recover(params, omegas, failed, strategy) -> params``: the tower is
    rebuilt collectively; every non-tower (replicated) leaf passes through
    untouched, which *is* the CheckFree+ (de)embedding restore — the
    replacement device reads the surviving replicas.
    """
    rec = checkfree_recover_spmd(mesh, part.num_stages)
    tower_key = part.tower_key

    def recover(params: Params, omegas: jnp.ndarray, failed: int,
                strategy: str = "grad_norm") -> Params:
        out = dict(params)
        out[tower_key] = rec(params[tower_key], omegas, failed, strategy)
        return out

    return recover
