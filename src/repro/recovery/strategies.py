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

import functools
from typing import Callable, ClassVar, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.recovery import (recover_consecutive, recover_stage,
                                 recovery_error, zero_stages)
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
    """Host span of one phase of a merge recovery: the dispatch of the
    recovery, and the drain of its recovery error (which waits for the
    whole recovery on the device)."""
    return telemetry.span(name, cat="recovery", wall_step=event.wall_step)


def _recovery_program(part, stages: Tuple[int, ...], reinit: Optional[str],
                      tower, m, v, omegas, key):
    """One failure's recovery, traced as one program: the lost ``stages``
    rebuilt (``recover_stage`` for one stage, ``recover_consecutive`` for
    a run, where ``reinit`` is None), each one's recovery error, and their
    Adam moments zeroed.  It reads and returns the tower subtrees of the
    params and both moments only; the embeddings and the step count stay
    where they are.  Nothing is donated: the caller still holds the
    pre-failure state, and the new towers are fresh buffers."""
    tk = part.tower_key
    before = {tk: tower}
    if reinit is None:
        after = recover_consecutive(before, part, list(stages), omegas)
    else:
        (stage,) = stages
        after = recover_stage(before, part, stage, omegas, strategy=reinit,
                              key=key)
    errs = jnp.stack([recovery_error(before, after, part, s)
                      for s in stages])
    m = zero_stages({tk: m}, part, stages)[tk]
    v = zero_stages({tk: v}, part, stages)[tk]
    return after[tk], m, v, errs


class MergeRecovery(RecoveryStrategy):
    """Shared CheckFree-family machinery: neighbour-merge reinit of the failed
    stage, zeroed optimizer moments for that stage, Alg. 1's LR boost.

    A failure (or a consecutive run) is one compiled program
    (:func:`_recovery_program`), built at the first failure of each
    (layout, stages, reinit) and reused after; a re-layout builds its own.

    On the SPMD backend the trainer binds an in-mesh collective
    (``bind_in_mesh``); deterministic reinits then run as neighbour-hop
    ppermutes + a local merge on the stage-sharded tower instead of
    host-side slice gathers.  Stochastic reinits (``random``) and
    consecutive-run recovery keep the program — they are rare events and
    bit-match either way."""

    recover_in_mesh = True
    reinit: ClassVar[str] = "grad_norm"

    def __init__(self, rcfg, wall):
        super().__init__(rcfg, wall)
        # (layer_counts, stages, reinit) -> the jitted recovery program
        self._programs: Dict[Tuple, Callable] = {}

    def _omegas(self, state: TrainState) -> jax.Array:
        k = self.part.num_stages
        return jax.device_put(state.omegas if state.omegas is not None
                              else np.ones((k,), np.float32))

    def _boosted(self, lr_scale: float) -> float:
        return min(lr_scale * self.rcfg.lr_boost,
                   self.rcfg.lr_boost_cap)  # Alg. 1 line 4 (capped)

    def _program(self, stages: Tuple[int, ...],
                 reinit: Optional[str]) -> Callable:
        key = (self.part.layer_counts, stages, reinit)
        if key not in self._programs:
            self._programs[key] = jax.jit(functools.partial(
                _recovery_program, self.part, stages, reinit))
        return self._programs[key]

    def _recover(self, state: TrainState, stages: Tuple[int, ...],
                 reinit: Optional[str], event: FailureContext) -> TrainState:
        tk = self.part.tower_key
        params, opt = state.params, state.opt_state
        with _phase("recovery_merge", event):
            tower, m, v, errs = self._program(stages, reinit)(
                params[tk], opt.m[tk], opt.v[tk], self._omegas(state),
                event.key if reinit == "random" else None)
        # explicit drain: the recovery error is a host-side metric, and the
        # failure path must stay legal under the implicit-transfer guard
        with _phase("recovery_error_drain", event):
            errs = jax.device_get(errs)
        event.hist.recovery_errors.extend(
            (event.wall_step, float(err)) for err in errs)
        event.path = "program"
        return TrainState({**params, tk: tower},
                          OptState({**opt.m, tk: m}, {**opt.v, tk: v},
                                   opt.step),
                          self._boosted(state.lr_scale),
                          state.omegas, state.effective_step)

    def _recover_in_mesh(self, state: TrainState, reinit: str,
                         event: FailureContext) -> TrainState:
        before, opt = state.params, state.opt_state
        stages = [event.stage]
        with _phase("recovery_merge", event):
            params = self._in_mesh_recover(before, self._omegas(state),
                                           event.stage, reinit)
            opt = OptState(zero_stages(opt.m, self.part, stages),
                           zero_stages(opt.v, self.part, stages), opt.step)
        with _phase("recovery_error_drain", event):
            err = float(jax.device_get(
                recovery_error(before, params, self.part, event.stage)))
        event.hist.recovery_errors.append((event.wall_step, err))
        event.path = "in_mesh"
        return TrainState(params, opt, self._boosted(state.lr_scale),
                          state.omegas, state.effective_step)

    def on_failure(self, state: TrainState,
                   event: FailureContext) -> TrainState:
        k = self.part.num_stages
        reinit = self.reinit
        if not self.handles_edge_stages and event.stage in (0, k - 1):
            # CheckFree (no '+') cannot recover edge stages — the paper
            # protects them; if an event still arrives, degrade to copy.
            reinit = "copy_prev"
        if self._in_mesh_recover is not None and reinit in IN_MESH_REINITS:
            return self._recover_in_mesh(state, reinit, event)
        return self._recover(state, (event.stage,), reinit, event)

    def on_consecutive(self, state: TrainState, run: List[int],
                       event: FailureContext) -> TrainState:
        """Beyond-paper: a run of consecutive stages died together —
        distance-weighted interpolation between the surviving flanks."""
        return self._recover(state, tuple(run), None, event)

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
