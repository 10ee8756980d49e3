"""The paper's recovery policies, ported onto :class:`RecoveryStrategy`.

Seven config-selectable built-ins:

  checkfree       — Alg. 1 gradient-norm-weighted neighbour merge; edge
                    stages degrade to copy (the paper protects them)
  checkfree_plus  — + swap schedule, so edge stages have trained twins
  elastic         — checkfree reconstruction plus live re-layout: a
                    permanent departure shrinks the pipeline to the
                    survivors instead of limping on a spare
                    (docs/elastic.md)
  checkpoint      — periodic save / rollback baseline (restarts from a fresh
                    init when a failure precedes the first save)
  redundant       — Bamboo-style redundant computation: exact weights, paid
                    for with a 1.654x iteration time (Table 2)
  none            — ignore failures (convergence lower bound)
  copy / uniform / random — the Fig. 2 ablation reinits

All recovery math lives in ``repro.core.recovery`` (pure pytree functions);
these classes bind it to the trainer lifecycle and the wall-clock model.
"""
from __future__ import annotations

from typing import ClassVar, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.recovery import (recover_consecutive, recover_stage,
                                 recovery_error)
from repro.pipeline.spmd import IN_MESH_REINITS
from repro.core.state import History, TrainState
from repro.optim.adam import OptState
from repro.recovery.base import FailureContext, RecoveryStrategy
from repro.recovery.registry import register_strategy


@register_strategy("none")
class NoRecovery(RecoveryStrategy):
    """Failures are ignored — the paper's convergence lower bound."""


@register_strategy("redundant")
class Redundant(RecoveryStrategy):
    """Bamboo: each stage's predecessor holds a redundant copy; on failure it
    promotes the copy, so weights are recovered exactly and only wall-clock
    is charged (every iteration pays the redundant-compute factor)."""

    def iteration_cost(self) -> float:
        return self.wall.iter_time_s * self.wall.redundant_factor

    def failure_cost(self) -> float:
        return self.wall.promote_time_s


@register_strategy("checkpoint")
class Checkpointing(RecoveryStrategy):
    """Periodic full-model save + rollback (the paper's baseline).

    The :class:`Checkpointer` (a single-disk-tier view of
    ``repro.statestore``) is created lazily on first use so that strategy
    construction stays side-effect-free (cost queries must not wipe
    checkpoint directories).  Wall-clock is priced through the *remote*
    tier spec — the paper's 500 Mb/s link to non-faulty storage (fn. 2) —
    which is numerically the old flat ``ckpt_bandwidth_Bps`` pricing.
    """

    def __init__(self, rcfg, wall):
        super().__init__(rcfg, wall)
        self._ckpt = None

    @property
    def checkpointer(self):
        if self._ckpt is None:
            # deferred import: repro.ckpt sits on top of repro.statestore,
            # whose strategies import this module — resolving the
            # Checkpointer at first use keeps the import graph acyclic
            from repro.ckpt.checkpoint import Checkpointer
            self._ckpt = Checkpointer(self.rcfg.checkpoint_dir,
                                      self.rcfg.checkpoint_every)
        return self._ckpt

    def on_failure(self, state: TrainState,
                   event: FailureContext) -> TrainState:
        event.hist.recovery_errors.append((event.wall_step, float("nan")))
        ckpt = self.checkpointer
        if not ckpt.has_checkpoint():
            # nothing saved yet -> restart from a fresh init at step 0
            # (lr_scale resets too: any boost belonged to the lost trajectory)
            assert self.init_fn is not None, "checkpoint strategy needs bind()"
            params, opt_state = self.init_fn()
            return TrainState(params, opt_state, lr_scale=1.0,
                              omegas=None, effective_step=0)
        step, (params, opt_state), _lost = ckpt.rollback(
            state.effective_step, (state.params, state.opt_state))
        return TrainState(params, opt_state, state.lr_scale,
                          state.omegas, effective_step=step)

    def after_step(self, state: TrainState, hist: History) -> None:
        self.checkpointer.maybe_save(state.effective_step,
                                     (state.params, state.opt_state))

    def after_step_horizon(self, step: int) -> int:
        # saves only fire at multiples of checkpoint_every; every other
        # after_step is a no-op, so the trainer may fuse up to the next
        # save boundary (the window then ends exactly on the saving step)
        every = max(self.rcfg.checkpoint_every, 1)
        return every - step % every

    def replay_horizon(self) -> int:
        # deepest rollback: the newest checkpoint plus every corrupted-
        # fallback candidate the Checkpointer retains (keep=3), plus the
        # restart-from-step-0 path before the first save (covered because
        # effective_step is then < checkpoint_every <= horizon)
        from repro.ckpt.checkpoint import Checkpointer
        return Checkpointer.DEFAULT_KEEP * max(self.rcfg.checkpoint_every, 1)

    def iteration_cost(self) -> float:
        # saves overlap training partially; amortized residual overhead,
        # priced by the remote tier's latency + bandwidth
        remote = self.wall.tier_specs()["remote"]
        return (self.wall.iter_time_s +
                0.1 * remote.write_time_s(self.wall.model_bytes)
                / self.rcfg.checkpoint_every)

    def failure_cost(self) -> float:
        remote = self.wall.tier_specs()["remote"]
        return (self.wall.restart_overhead_s
                + remote.read_time_s(self.wall.model_bytes))


def _phase(name: str, event: FailureContext):
    """Host span of one phase of a merge recovery: the eager merge's
    dispatch, the drain of its recovery error (which waits for the merge
    on the device), and the dispatch of the moment reset."""
    return telemetry.span(name, cat="recovery", wall_step=event.wall_step)


class MergeRecovery(RecoveryStrategy):
    """Shared CheckFree-family machinery: neighbour-merge reinit of the failed
    stage, zeroed optimizer moments for that stage, Alg. 1's LR boost.

    On the SPMD backend the trainer binds an in-mesh collective
    (``bind_in_mesh``); deterministic reinits then run as neighbour-hop
    ppermutes + a local merge on the stage-sharded tower instead of
    host-side slice gathers.  Stochastic reinits (``random``) and
    consecutive-run recovery keep the host path — they are rare events and
    bit-match either way."""

    recover_in_mesh = True
    reinit: ClassVar[str] = "grad_norm"

    def _omegas(self, state: TrainState) -> jnp.ndarray:
        k = self.part.num_stages
        return jnp.asarray(state.omegas if state.omegas is not None
                           else np.ones((k,), np.float32))

    def _boosted(self, lr_scale: float) -> float:
        return min(lr_scale * self.rcfg.lr_boost,
                   self.rcfg.lr_boost_cap)  # Alg. 1 line 4 (capped)

    def _zero_stage_moments(self, opt_state: OptState,
                            stages: List[int]) -> OptState:
        # the failed node's optimizer moments are gone: zero those stages
        m, v = opt_state.m, opt_state.v
        for stage in stages:
            zeros = jax.tree.map(jnp.zeros_like,
                                 self.part.get_stage(m, stage))
            m = self.part.set_stage(m, stage, zeros)
            v = self.part.set_stage(v, stage, zeros)
        return OptState(m, v, opt_state.step)

    def on_failure(self, state: TrainState,
                   event: FailureContext) -> TrainState:
        k = self.part.num_stages
        reinit = self.reinit
        if not self.handles_edge_stages and event.stage in (0, k - 1):
            # CheckFree (no '+') cannot recover edge stages — the paper
            # protects them; if an event still arrives, degrade to copy.
            reinit = "copy_prev"
        before = state.params
        with _phase("recovery_merge", event):
            if self._in_mesh_recover is not None and \
                    reinit in IN_MESH_REINITS:
                params = self._in_mesh_recover(before, self._omegas(state),
                                               event.stage, reinit)
            else:
                params = recover_stage(before, self.part, event.stage,
                                       self._omegas(state), strategy=reinit,
                                       key=event.key)
        # explicit drain: the recovery error is a host-side metric, and the
        # failure path must stay legal under the implicit-transfer guard
        with _phase("recovery_error_drain", event):
            err = float(jax.device_get(
                recovery_error(before, params, self.part, event.stage)))
        event.hist.recovery_errors.append((event.wall_step, err))
        with _phase("recovery_moment_reset", event):
            opt_state = self._zero_stage_moments(state.opt_state,
                                                 [event.stage])
        return TrainState(params, opt_state, self._boosted(state.lr_scale),
                          state.omegas, state.effective_step)

    def on_consecutive(self, state: TrainState, run: List[int],
                       event: FailureContext) -> TrainState:
        """Beyond-paper: a run of consecutive stages died together —
        distance-weighted interpolation between the surviving flanks."""
        before = state.params
        with _phase("recovery_merge", event):
            params = recover_consecutive(before, self.part, run,
                                         self._omegas(state))
        with _phase("recovery_error_drain", event):
            errs = [float(jax.device_get(
                recovery_error(before, params, self.part, stage)))
                for stage in run]
        event.hist.recovery_errors.extend(
            (event.wall_step, err) for err in errs)
        with _phase("recovery_moment_reset", event):
            opt_state = self._zero_stage_moments(state.opt_state, run)
        return TrainState(params, opt_state, self._boosted(state.lr_scale),
                          state.omegas, state.effective_step)

    def failure_cost(self) -> float:
        return self.wall.recovery_time_s


@register_strategy("checkfree")
class CheckFree(MergeRecovery):
    handles_edge_stages = False
    handles_consecutive = True


@register_strategy("checkfree_plus")
class CheckFreePlus(MergeRecovery):
    handles_edge_stages = True
    handles_consecutive = True
    uses_swap_schedule = True


@register_strategy("elastic")
class Elastic(MergeRecovery):
    """CheckFree reconstruction + elastic repartitioning (docs/elastic.md).

    Transient failures behave exactly like ``checkfree``.  When the
    simulator reports a *permanent* departure, the lost stage is first
    reconstructed by the gradient-norm-weighted neighbour merge (the
    ``stage_merge`` kernel path) in the old layout, then the trainer
    re-cuts the surviving K-1 stages into balanced contiguous ranges and
    rebuilds the fused step; on a later regrow it rebalances back to K.
    The re-layout itself is priced once through
    :meth:`repro.core.walltime.WallClockModel.relayout_time_s`.
    """

    handles_edge_stages = False
    handles_consecutive = True
    recover_by_repartition = True


@register_strategy("uniform")
class UniformMerge(MergeRecovery):
    reinit = "uniform"


@register_strategy("copy")
class CopyPrev(MergeRecovery):
    reinit = "copy_prev"


@register_strategy("random")
class RandomReinit(MergeRecovery):
    reinit = "random"
