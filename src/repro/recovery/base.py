"""The :class:`RecoveryStrategy` interface — recovery policies as first-class
objects.

The paper's contribution is a *family* of recovery policies (CheckFree,
CheckFree+, checkpointing, redundancy, the Fig. 2 ablation reinits); follow-up
work (Chameleon, arXiv 2508.21613; TierCheck) composes and *switches* them at
runtime.  A strategy therefore owns the full policy surface the trainer used
to string-dispatch over:

lifecycle hooks (called by the trainer)
  ``on_failure(state, event)``      — one stage died at an iteration boundary
  ``on_consecutive(state, run, event)`` — a run of adjacent stages died
                                      together (only if ``handles_consecutive``)
  ``after_step(state, hist)``       — bookkeeping after every wall iteration
                                      (checkpoint saves, window statistics)
  ``on_run_end()``                  — loop exit (even on error): release
                                      background resources (async snapshot
                                      writers)
  ``observe_environment(rate)``     — cluster telemetry: the simulator's
                                      observed failure rate, fed once per
                                      wall iteration when available
  ``on_departure(state, event)``    — a stage's node is permanently gone
                                      (reconstruct values; the trainer then
                                      repartitions if the strategy's
                                      ``recover_by_repartition`` says so)
  ``on_layout_change(state, old, new)`` — the trainer re-cut the stage
                                      layout; rebind per-stage state

wall-clock model (absorbing ``WallClockModel``'s per-strategy dispatch)
  ``iteration_cost()``  — modelled seconds per wall iteration
  ``failure_cost()``    — extra modelled seconds per failure event

capability flags (drive trainer wiring — the trainer never looks at names)
  ``handles_edge_stages``  — can recover S_first/S_last losslessly; when
                             False the strategy degrades edge failures itself
  ``handles_consecutive``  — recovers a run of adjacent failed stages jointly
  ``uses_swap_schedule``   — the train step must run CheckFree+'s swapped
                             stage order on half the batch

fused hot-path contract (the trainer fuses failure-free iteration runs into
a single on-device loop window and only drains state at window
boundaries — see ``docs/perf.md``)
  ``after_step_horizon(step)`` — how many iterations may be fused before
                             ``after_step`` must observe host state again
  ``replay_horizon()``     — how far ``effective_step`` can roll back on a
                             failure (bounds the trainer's batch replay
                             cache)

Strategies are selected purely through the registry
(:func:`repro.recovery.registry.make_strategy`); writing a new policy is a
subclass + ``@register_strategy("name")`` — no trainer surgery.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, ClassVar, List, Optional, Tuple, TYPE_CHECKING

import jax

from repro import telemetry

if TYPE_CHECKING:  # pragma: no cover — typing only, no import cycles
    from repro.config import RecoveryConfig
    from repro.core.state import History, TrainState
    from repro.core.stages import StagePartition
    from repro.core.walltime import WallClockModel

# () -> (params, opt_state): a deterministic from-scratch reinitialization
InitFn = Callable[[], Tuple[Any, Any]]


@dataclass
class FailureContext:
    """Everything a strategy may consult when reacting to a failure event."""

    stage: int                 # 0-based failed stage (run[0] for runs)
    wall_step: int             # wall-iteration index of the event
    key: jax.Array             # PRNG key (random reinit ablation)
    hist: "History"            # strategies append recovery_errors here
    path: Optional[str] = None  # set by the strategy: how it recovered,
                                # "program" (one compiled recovery) or
                                # "in_mesh" (the SPMD collective)


class RecoveryStrategy:
    """Base class: a no-op policy (registered as ``none``).

    Subclasses override the hooks they need; the defaults are "do nothing,
    charge one plain iteration, recover for free".
    """

    name: ClassVar[str] = "none"           # set by @register_strategy
    handles_edge_stages: ClassVar[bool] = True
    handles_consecutive: ClassVar[bool] = False
    uses_swap_schedule: ClassVar[bool] = False
    recover_in_mesh: ClassVar[bool] = False   # repairs stages with in-mesh
                                              # collectives when a backend
                                              # offers them (SPMD pipeline)
    recover_by_repartition: ClassVar[bool] = False  # on a *permanent* node
                                              # departure the trainer may
                                              # shrink the layout to the
                                              # survivors (host backend;
                                              # see docs/elastic.md)

    def __init__(self, rcfg: "RecoveryConfig", wall: "WallClockModel"):
        self.rcfg = rcfg
        self.wall = wall
        self.part: Optional["StagePartition"] = None
        self.init_fn: Optional[InitFn] = None
        self._in_mesh_recover: Optional[Callable] = None

    # ---- trainer wiring ----------------------------------------------
    def bind(self, part: "StagePartition",
             init_fn: Optional[InitFn] = None) -> "RecoveryStrategy":
        """Attach the stage partition (and a from-scratch init for policies
        that may have to restart).  Called once by the trainer."""
        self.part = part
        self.init_fn = init_fn
        return self

    def bind_in_mesh(self, recover_fn: Callable) -> "RecoveryStrategy":
        """Attach a backend-provided in-mesh recovery collective
        ``recover(params, omegas, failed, reinit) -> params`` (see
        :func:`repro.pipeline.spmd.make_in_mesh_recover`).  Called by the
        trainer only when both the backend offers one and the strategy
        advertises ``recover_in_mesh``; strategies that never bind keep
        using the host-side pytree math unchanged — that is what makes
        every policy run unmodified on either backend."""
        self._in_mesh_recover = recover_fn
        return self

    # ---- instrumented entry points (what the trainer calls) ----------
    def _recorded(self, recover: Callable[[], "TrainState"],
                  event: FailureContext, recovered: List[int],
                  **span_args) -> "TrainState":
        """Run ``recover()`` inside a host-side ``recovery`` span (the
        parent of the strategy's own phase spans) and emit the structured
        ``recovery`` event (``repro.telemetry``), with the ``path`` the
        strategy took (None where it names none)."""
        t0 = telemetry.clock()
        with telemetry.span("recovery", cat="recovery", strategy=self.name,
                            stage=event.stage, wall_step=event.wall_step,
                            **span_args):
            state = recover()
            duration = telemetry.clock() - t0
        telemetry.emit("recovery", wall_step=event.wall_step,
                       stage=event.stage, strategy=self.name,
                       duration_s=duration, stages=recovered,
                       path=event.path)
        return state

    def handle_failure(self, state: "TrainState",
                       event: FailureContext) -> "TrainState":
        """:meth:`on_failure` wrapped in a host-side trace span and a
        structured ``recovery`` event (``repro.telemetry``).  The trainer
        routes failures through here so every policy's recovery execution
        is measured uniformly; subclasses keep overriding
        :meth:`on_failure` and never need to touch this."""
        return self._recorded(lambda: self.on_failure(state, event), event,
                              [event.stage])

    def handle_consecutive(self, state: "TrainState", run: List[int],
                           event: FailureContext) -> "TrainState":
        """:meth:`on_consecutive` with the same span + event treatment as
        :meth:`handle_failure` (one ``recovery`` event for the whole
        adjacent-stage run)."""
        return self._recorded(lambda: self.on_consecutive(state, run, event),
                              event, list(run), stages=len(run))

    def handle_departure(self, state: "TrainState",
                         event: FailureContext) -> "TrainState":
        """:meth:`on_departure` with the same span + event treatment as
        :meth:`handle_failure`.  Called instead of it when the failure is a
        permanent departure the trainer will repartition away — the
        strategy's job here is only to reconstruct the lost stage's values
        in the *old* layout; the trainer re-cuts the layout afterwards."""
        return self._recorded(lambda: self.on_departure(state, event), event,
                              [event.stage])

    # ---- lifecycle ---------------------------------------------------
    def on_failure(self, state: "TrainState",
                   event: FailureContext) -> "TrainState":
        return state

    def on_departure(self, state: "TrainState",
                     event: FailureContext) -> "TrainState":
        """A permanent departure reconstructs exactly like a failure; the
        re-layout that follows is the trainer's job (it owns the fused
        step and the partition), not the strategy's."""
        return self.on_failure(state, event)

    def accept_repartition(self, event: FailureContext,
                           moved_bytes: float) -> bool:
        """Whether to shrink the layout for this departure (``moved_bytes``
        is the planned state movement the re-layout would pay for).  Only
        consulted when ``recover_by_repartition`` is set; the ``adaptive``
        strategy prices this against staying degraded (docs/elastic.md)."""
        return True

    def on_layout_change(self, state: "TrainState", old: "StagePartition",
                         new: "StagePartition") -> "TrainState":
        """The trainer re-cut the stage layout (shrink after a departure or
        grow on regrow).  Rebind the partition and refresh any per-stage
        derived state; store-backed strategies re-shard their snapshots
        here so post-shrink restores stay correct."""
        self.part = new
        return state

    def on_consecutive(self, state: "TrainState", run: List[int],
                       event: FailureContext) -> "TrainState":
        """Default: recover each stage of the run independently."""
        from dataclasses import replace
        for stage in run:
            state = self.on_failure(state, replace(event, stage=stage))
        return state

    def after_step(self, state: "TrainState", hist: "History") -> None:
        pass

    def on_run_end(self) -> None:
        """Called once when the trainer's loop exits (even on error):
        release background resources — the statestore strategies flush and
        stop their asynchronous snapshot writer here."""

    def observe_environment(self, rate: float) -> None:
        """Environment telemetry: the cluster's observed failure rate
        (failures per wall iteration).  Called by the trainer once per wall
        iteration when the failure schedule exposes ``observed_rate`` (the
        simulator's adapter does); default is to ignore it."""

    # ---- fused hot-path contract -------------------------------------
    def after_step_horizon(self, step: int) -> Optional[int]:
        """How many consecutive iterations, starting from effective step
        ``step``, the trainer may fuse into one on-device window before
        ``after_step`` must observe host-resident state again.

        ``None`` means unbounded (``after_step`` never needs per-step host
        state); ``1`` forces the eager per-step loop.  The trainer ends
        every fused window with one ``after_step`` call on the drained
        state, so a strategy whose bookkeeping only *acts* at a cadence
        (checkpoint saves every N steps) returns the distance to its next
        acting step — the skipped intermediate calls must be no-ops.

        The default inspects whether the subclass overrides
        :meth:`after_step` at all: strategies that keep the no-op
        bookkeeping fuse freely, anything that overrides it is
        conservatively pinned to the eager loop unless it also overrides
        this method."""
        if type(self).after_step is RecoveryStrategy.after_step:
            return None
        return 1

    def replay_horizon(self) -> Optional[int]:
        """Maximum number of iterations ``effective_step`` can move
        *backwards* on a failure — i.e. how much of the deterministic batch
        stream must stay replayable.  The trainer evicts cached batches
        older than this horizon; ``None`` keeps every batch (unbounded
        rollback).  The base policy never rolls back, so the default is 0;
        strategies that restore older state (checkpoint rollback) must
        report their deepest possible rollback."""
        return 0

    # ---- wall-clock model --------------------------------------------
    def iteration_cost(self) -> float:
        return self.wall.iter_time_s

    def failure_cost(self) -> float:
        return 0.0

    def consume_restore_bytes(self) -> Optional[float]:
        """Serialized bytes that had to reach the replacement node for the
        failure event just handled, or ``None`` for the schedule's default
        stage-sized estimate.  Store-backed strategies report the actual
        shard size served; the simulator's ``failure_overhead`` hook
        reprices the state transfer with it."""
        return None

    def __repr__(self) -> str:
        return f"{type(self).__name__}(name={self.name!r})"
