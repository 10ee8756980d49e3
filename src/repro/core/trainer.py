"""Failure-aware trainer: the paper's training loop with pluggable recovery
strategies, executed through a fused multi-step hot path.

The trainer executes *wall iterations*; a :class:`~repro.recovery.base.
RecoveryStrategy` (constructed from ``RecoveryConfig`` via the registry)
reacts to failure events (same seeded schedule across strategies), mutating
the train state (CheckFree merge / checkpoint rollback / redundant promote)
and pricing wall-clock through its ``iteration_cost``/``failure_cost``.
The loop itself is strategy-agnostic: it only consults the strategy's
lifecycle hooks and capability flags, never its name.  CheckFree+'s
out-of-order microbatches are realized by computing half the batch through a
swapped stage order (the cast tower's layers reordered by static slices —
see :func:`_swap_views` and core/swap.py).

**Fused hot path.**  The failure schedule is deterministic and queryable
ahead of time (``schedule.at(step)``), so between failure events the
trainer knows it will run K uninterrupted steps.  It fuses them into a
single jitted loop over a stacked batch window: one dispatch, zero
per-step host round-trips.  Per-step metrics (loss, per-stage grad
square-norms, lr) accumulate on device in the scan's output ring and are
drained with one ``device_get`` at window boundaries — failure events,
eval points, strategy ``after_step_horizon`` limits, and run end.  The
window's trip count is a runtime operand of the loop (:func:`window_loop`),
so XLA compiles one loop body for every window size and eager (window 1)
and fused traces are bit-identical on one backend.  Params and optimizer
state are donated to the step (``donate_argnums``): Adam's moments update
in place instead of being copied every iteration, and a donated buffer is
deleted once the step is dispatched.  The next window's batches are
stacked on a background thread
(:class:`~repro.data.pipeline.WindowPrefetcher`) while the current
window runs, and the replay cache is bounded by the strategy's
``replay_horizon()``.  See ``docs/perf.md``.

The ``schedule`` may be the legacy seeded :class:`FailureSchedule` or a
simulated cluster's ``SimFailureSchedule`` (``repro.sim``): when the
schedule exposes the per-event wall-clock hooks (``iteration_factor`` /
``failure_overhead``) the loop prices iterations and recoveries with
node-dependent costs, and when it exposes ``observed_rate`` the strategy
receives the cluster's failure-rate telemetry each wall iteration.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.config import OptimizerConfig, TrainConfig
from repro.core.failures import FailureSchedule
from repro.core.stages import StagePartition, moved_layers, remap_stage_stats
from repro.core.state import History, TrainState  # noqa: F401  (re-export)
from repro.core.swap import swap_permutation
from repro.core.walltime import WallClockModel
from repro.data.pipeline import WindowPrefetcher
from repro.models import layers as L
from repro.models.layers import cross_entropy
from repro.models.model import Model
from repro.optim.adam import adam_update, init_adam
from repro.recovery import FailureContext, RecoveryStrategy, make_strategy

Params = Any


def _runs(perm: np.ndarray) -> Tuple[Tuple[int, int], ...]:
    """``perm`` as its maximal runs of consecutive indices, ``(lo, hi)``."""
    cuts = np.flatnonzero(np.diff(perm) != 1) + 1
    return tuple((int(r[0]), int(r[-1]) + 1) for r in np.split(perm, cuts))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _reorder(a: jnp.ndarray, runs, inverse) -> jnp.ndarray:
    """``a``'s rows (axis 0) as the concatenation of its ``runs``; the
    backward pass reorders the cotangent by ``inverse``, the runs of the
    inverse permutation.  Static slices both ways: never a gather, whose
    transpose is a scatter-add into zeros."""
    with jax.named_scope("tower_swap"):
        return jnp.concatenate([a[lo:hi] for lo, hi in runs], axis=0)


def _reorder_fwd(a, runs, inverse):
    return _reorder(a, runs, inverse), None


def _reorder_bwd(runs, inverse, _, g):
    return (_reorder(g, inverse, runs),)


_reorder.defvjp(_reorder_fwd, _reorder_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _cast_shared(a: jnp.ndarray, dtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``a`` cast to ``dtype`` once and handed to both half-batches; the
    backward pass converts each half's cotangent to ``a``'s dtype and sums
    them there (the float32 sum two separate casts would give, where
    sharing one cast would sum in ``dtype``)."""
    c = L.cast_tree(a, dtype)
    return c, c


def _cast_shared_fwd(a, dtype):
    # the residual only carries ``a``'s dtype to the backward pass
    return _cast_shared(a, dtype), jnp.zeros((0,), a.dtype)


def _cast_shared_bwd(dtype, like, cts):
    g1, g2 = cts
    with jax.named_scope("param_cast"):
        return (g1.astype(like.dtype) + g2.astype(like.dtype),)


_cast_shared.defvjp(_cast_shared_fwd, _cast_shared_bwd)


def _swap_views(params: Params, dtype, tower_key: str, runs, inverse,
                ) -> Tuple[Params, Params]:
    """CheckFree+'s parameter views for its two half-batches: ``params``
    cast to the compute ``dtype`` once (the models' own entry cast is then
    a no-op), and the same cast with the tower's layers in the swapped
    order (``runs`` of the permutation, ``inverse`` those of its inverse;
    :func:`_runs`), permuted in the compute dtype.  A leaf the cast leaves
    as it is goes to both halves unchanged, so its gradient accumulates
    exactly as it would without the views."""

    def views(a, in_tower):
        if jax.dtypes.issubdtype(a.dtype, jnp.floating) and a.dtype != dtype:
            first, second = _cast_shared(a, dtype)
        else:
            first = second = a
        if in_tower and len(runs) > 1:
            second = _reorder(second, runs, inverse)
        return first, second

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    pairs = [views(a, path[0].key == tower_key) for path, a in flat]
    return (treedef.unflatten([p[0] for p in pairs]),
            treedef.unflatten([p[1] for p in pairs]))


def _make_loss_fn(model: Model, part: StagePartition, use_swap: bool,
                  ) -> Callable:
    """The (possibly swap-scheduled) loss closure shared by every step."""
    tower_key = part.tower_key
    if use_swap:
        perm = swap_permutation(
            part.num_layers, part.num_stages,
            bounds=[part.stage_bounds(i) for i in range(part.num_stages)])
        runs, inverse = _runs(perm), _runs(np.argsort(perm))
        dtype = jnp.dtype(model.cfg.dtype)

    def loss_fn(params, batch):
        if not use_swap:
            loss, metrics = model.loss(params, batch)
            return loss, metrics
        half = batch["tokens"].shape[0] // 2
        first = {k: v[:half] for k, v in batch.items()}
        second = {k: v[half:] for k, v in batch.items()}
        # the model's own entry cast is a no-op on these views
        in_order, swapped = _swap_views(params, dtype, tower_key, runs,
                                        inverse)
        l1, m1 = model.loss(in_order, first)
        l2, m2 = model.loss(swapped, second)
        # telemetry covers the WHOLE batch: average both halves' metrics
        # (the in-order half alone would silently drop half the ce/aux)
        metrics = {k: 0.5 * (m1[k] + m2[k]) for k in m1}
        return 0.5 * (l1 + l2), metrics

    return loss_fn


@jax.named_scope("window_loop")
def window_loop(step: Callable, carry: Any, stacked: Any, n: jnp.ndarray,
                ) -> Tuple[Any, Any]:
    """``lax.scan(step, carry, stacked)`` with the trip count ``n`` passed
    in as a runtime operand instead of the static leading axis.

    XLA specializes a loop whose trip count it can see, and a window of 1
    then compiles to a body whose reductions round differently from the
    body of a window of K (last-bit loss differences).  With ``n`` opaque
    the loop body is one program for every window size, which is what
    makes eager and fused traces bit-identical.  ``n`` must equal the
    leading axis of ``stacked``; the per-step outputs are written into a
    ring with that leading axis."""
    length = jax.tree.leaves(stacked)[0].shape[0]
    first = jax.tree.map(lambda x: x[0], stacked)
    out_shapes = jax.eval_shape(step, carry, first)[1]
    ring0 = jax.tree.map(lambda s: jnp.zeros((length,) + s.shape, s.dtype),
                         out_shapes)

    def body(i, state):
        carry, ring = state
        batch = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, i, keepdims=False),
            stacked)
        carry, out = step(carry, batch)
        ring = jax.tree.map(
            lambda r, o: jax.lax.dynamic_update_index_in_dim(r, o, i, 0),
            ring, out)
        return carry, ring

    return jax.lax.fori_loop(0, n, body, (carry, ring0))


def _jit_donated(fn):
    """jit ``fn(params, opt_state, stacked, lr_scale, n)`` with
    params/opt_state (argnums 0, 1) donated, and dispatch it as
    ``fn(params, opt_state, stacked, lr_scale)``: the wrapper passes the
    window length ``n`` as a runtime int32 (see :func:`window_loop`).
    Donated buffers are consumed by the call; the "not usable" warning a
    backend gives for a donated buffer it cannot alias is suppressed
    *scoped to this dispatch only* — the process-global filter is left
    alone so callers' own donation misconfigurations still surface."""
    jitted = jax.jit(fn, donate_argnums=(0, 1))

    @functools.wraps(jitted)
    def dispatch(*args):
        # args = (params, opt_state, stacked, lr_scale)
        n = np.int32(jax.tree.leaves(args[2])[0].shape[0])
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            # n (the trip count) is not donated: the lint counts positions
            # from the starred args
            return jitted(*args, n)  # repro: allow[donation-after-dispatch]

    # the retrace sentinel (repro.analysis.runtime) counts compiled
    # variants through the wrapper
    dispatch._jitted = jitted
    return dispatch


def make_train_step(model: Model, opt_cfg: OptimizerConfig,
                    part: StagePartition, *, use_swap: bool = False,
                    ) -> Callable:
    """Build the jitted single train step — the fused step at window 1.

    With ``use_swap`` (CheckFree+), the batch is split in half: the first half
    runs the normal stage order, the second half the swapped order.

    NOTE: ``params`` and ``opt_state`` are **donated** — do not reuse them
    after the call (on donating backends their buffers are consumed;
    thread state linearly like the trainer does).
    """
    fused = make_fused_train_step(model, opt_cfg, part, use_swap=use_swap)

    def train_step(params, opt_state, batch, lr_scale):
        stacked = {k: jnp.asarray(v)[None] for k, v in batch.items()}
        params, opt_state, _ls, ring = fused(params, opt_state, stacked,
                                             lr_scale)
        metrics = {k: v[0] for k, v in ring.items() if k != "omegas"}
        return params, opt_state, ring["omegas"][0], metrics

    return train_step


def make_fused_train_step(model: Model, opt_cfg: OptimizerConfig,
                          part: StagePartition, *, use_swap: bool = False,
                          lr_decay: float = 1.0) -> Callable:
    """Build the fused K-step train step: a jitted loop over a stacked
    batch window.

    ``fused(params, opt_state, stacked, lr_scale)`` runs one scan step per
    leading-axis slice of ``stacked`` and returns
    ``(params, opt_state, lr_scale, outs)`` where ``outs`` holds the
    per-step metric rings — ``loss`` / ``omegas`` / ``grad_norm`` / ``lr``
    plus the model's scalar metrics (``ce``, ``aux``) — with leading axis
    K, still on device.  The CheckFree LR-boost decay
    (``lr_scale -> 1 + (lr_scale - 1) * lr_decay``) is folded into the scan
    carry so no host round-trip is needed between steps.  ``params`` and
    ``opt_state`` are donated: on backends with donation support Adam's
    moments update in place across the whole window.

    The window size is purely the leading axis of ``stacked``; the loop's
    trip count reaches XLA as a runtime operand (:func:`window_loop`), so
    K=1 runs the identical loop body, which is what makes eager (window 1)
    and fused (window K) loss traces bit-identical on the same backend.
    """
    loss_fn = _make_loss_fn(model, part, use_swap)

    @_jit_donated
    def fused_step(params, opt_state, stacked, lr_scale, n):
        def body(carry, batch):
            params, opt_state, ls = carry
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            omegas = part.stage_grad_sqnorms(grads)
            params, opt_state, opt_metrics = adam_update(
                opt_cfg, params, grads, opt_state, ls)
            ls_next = 1.0 + (ls - 1.0) * lr_decay
            ring = dict(metrics)            # scalar model metrics (ce, aux)
            ring.update(opt_metrics)        # grad_norm, lr
            ring.update(loss=loss, omegas=omegas)
            return (params, opt_state, ls_next), ring

        carry0 = (params, opt_state, jnp.asarray(lr_scale, jnp.float32))
        (params, opt_state, ls), outs = window_loop(body, carry0, stacked, n)
        return params, opt_state, ls, outs

    return fused_step


def make_eval_step(model: Model) -> Callable:
    @jax.jit
    def eval_step(params, batch):
        logits, aux = model.apply(params, batch)
        if model.cfg.arch_type == "vlm":
            logits = logits[:, batch["patches"].shape[1]:, :]
        return cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    return eval_step


def _window_buckets(cap: int) -> List[int]:
    """Descending power-of-two window sizes <= cap (always ending in 1).

    Every distinct window size is a separate XLA executable; bucketing the
    schedule-derived distances to powers of two bounds compilation to
    O(log cap) variants."""
    buckets = []
    k = 1
    while k <= cap:
        buckets.append(k)
        k *= 2
    return buckets[::-1]


class Trainer:
    """Drives (model x recovery strategy x failure schedule).

    ``backend`` selects where the fused step executes:

    * ``"host"`` (default) — the single-program loop; stages are slices of
      one resident parameter tree.
    * ``"spmd"`` — the real pipeline-parallel backend
      (:mod:`repro.pipeline.spmd`): the tower and Adam moments are sharded
      over a 1-D ``("stage",)`` mesh (one device per stage — built by
      ``launch.mesh.make_host_pipeline_mesh`` unless ``mesh`` is given),
      activations hop stages via ``ppermute`` in a GPipe schedule, and
      recovery strategies exposing the ``recover_in_mesh`` capability
      repair failed stages with neighbour-hop collectives instead of
      host-side gathers.  Everything downstream of ``fused_step`` —
      window sizing, failure handling, metrics drain — is backend-agnostic.
    """

    def __init__(self, model: Model, tcfg: TrainConfig,
                 wall: Optional[WallClockModel] = None,
                 schedule: Optional[FailureSchedule] = None, *,
                 backend: str = "host", mesh=None):
        self.model = model
        self.tcfg = tcfg
        self.rcfg = tcfg.recovery
        self.backend = backend
        self.part = StagePartition(model.cfg, self.rcfg.num_stages)
        self.strategy: RecoveryStrategy = make_strategy(self.rcfg, wall=wall)
        self.wall = self.strategy.wall
        if schedule is None and self.rcfg.scenario:
            from repro.sim import simulate  # deferred: core stays sim-free
            schedule = simulate(
                self.rcfg.scenario, steps=tcfg.steps * 10,
                seed=self.rcfg.seed, num_stages=self.rcfg.num_stages,
                protect_edges=self.rcfg.protect_edge_stages, wall=self.wall)
        self.schedule = schedule

        def fresh_init():
            params = self.model.init(jax.random.PRNGKey(tcfg.seed))
            return params, init_adam(params)

        self.strategy.bind(self.part, init_fn=fresh_init)
        if backend == "spmd":
            from repro.launch.mesh import make_host_pipeline_mesh
            from repro.pipeline.spmd import (make_in_mesh_recover,
                                             make_spmd_fused_train_step,
                                             pipeline_state_shardings)
            self.mesh = (mesh if mesh is not None
                         else make_host_pipeline_mesh(self.rcfg.num_stages))
            self._state_shardings = pipeline_state_shardings(
                self.mesh, jax.eval_shape(fresh_init))
            self.fused_step = make_spmd_fused_train_step(
                model, tcfg.optimizer, self.part, self.mesh,
                tcfg.num_microbatches,
                use_swap=self.strategy.uses_swap_schedule,
                lr_decay=self.rcfg.lr_boost_decay)
            if self.strategy.recover_in_mesh:
                self.strategy.bind_in_mesh(
                    make_in_mesh_recover(self.mesh, self.part))
        elif backend == "host":
            self.mesh = None
            self._state_shardings = None
            self.fused_step = make_fused_train_step(
                model, tcfg.optimizer, self.part,
                use_swap=self.strategy.uses_swap_schedule,
                lr_decay=self.rcfg.lr_boost_decay)
        else:
            raise ValueError(
                f"unknown backend {backend!r}; expected 'host' or 'spmd'")
        self.eval_step = make_eval_step(model)
        self._buckets = _window_buckets(max(int(tcfg.fuse_window), 1))
        self._eval_batches: Optional[List] = None
        # window sizes actually dispatched — the retrace sentinel asserts
        # one compiled variant per bucket (repro.analysis.runtime)
        self.dispatched_buckets: set = set()

        # ---- elastic repartitioning (docs/elastic.md) -------------------
        # partition stage index -> cluster slot; identity until a permanent
        # departure shrinks the layout (K slots keep their sim identity,
        # the partition re-cuts over the survivors)
        self._slots: List[int] = list(range(self.rcfg.num_stages))
        self._allow_repartition = (
            backend == "host"
            and bool(getattr(self.strategy, "recover_by_repartition", False)))
        if backend == "spmd" and \
                getattr(self.strategy, "recover_by_repartition", False):
            telemetry.log(
                f"strategy {self.strategy.name!r} advertises repartition but "
                "the spmd backend has a fixed mesh: permanent departures "
                "degrade to in-place recovery on a spare")
        # (wall_step, direction, from_k, to_k, moved_layers, cost_s)
        self.repartition_log: List[Tuple[int, str, int, int, int, float]] = []

    def _placed(self, state: TrainState) -> TrainState:
        """On the spmd backend, put params and Adam state where the fused
        step keeps them (the tower stage-sharded, the rest replicated).
        A fresh init or a restored checkpoint otherwise arrives on one
        device, and the fused step would compile a second executable for
        that placement; arrays already in place are not copied."""
        if self._state_shardings is None:
            return state
        params, opt_state = jax.device_put((state.params, state.opt_state),
                                           self._state_shardings)
        return dataclasses.replace(state, params=params, opt_state=opt_state)

    # ---- window sizing -------------------------------------------------
    def _window_size(self, wall_step: int, effective_step: int,
                     max_wall: int) -> int:
        """Largest bucketed K such that steps [wall_step, wall_step+K) are
        failure-free after the first, no interior step needs host state
        (strategy horizon / eval), and the run doesn't overshoot."""
        cap = self._buckets[0]
        cap = min(cap, self.tcfg.steps - effective_step)
        cap = min(cap, max_wall - wall_step)
        horizon = self.strategy.after_step_horizon(effective_step)
        if horizon is not None:
            cap = min(cap, horizon)
        if self._eval_batches:
            ev = self.tcfg.eval_every
            cap = min(cap, ev - effective_step % ev)
        if self.schedule is not None:
            regrown_at = (getattr(self.schedule, "regrown_at", None)
                          if self._allow_repartition else None)
            for i in range(1, cap):
                if self.schedule.at(wall_step + i):
                    cap = i
                    break
                # a regrow re-cuts the layout (rebalance back toward K0):
                # the fused window must end at that boundary too
                if regrown_at is not None and regrown_at(wall_step + i):
                    cap = i
                    break
        for k in self._buckets:
            if k <= cap:
                return k
        return 1

    # ---- elastic re-layout (docs/elastic.md) ---------------------------
    def _rebuild_fused_step(self) -> None:
        """Recompile the fused step for the current partition.  Host backend
        only: the stacked tower is one resident array, so a re-layout changes
        stage *bounds* (and the compiled program cut along them), never the
        weight values themselves."""
        self.fused_step = make_fused_train_step(
            self.model, self.tcfg.optimizer, self.part,
            use_swap=self.strategy.uses_swap_schedule,
            lr_decay=self.rcfg.lr_boost_decay)
        # fresh executables per bucket: reset the retrace-sentinel ledger so
        # the one-variant-per-bucket invariant holds per layout epoch
        self.dispatched_buckets = set()

    def _repartition(self, state: TrainState, new_slots: List[int], *,
                     wall_step: int, direction: str,
                     ) -> Tuple[TrainState, float]:
        """Re-cut the stage layout over ``new_slots`` surviving cluster
        slots: rebuild the partition (balanced layer counts), recompile the
        fused step, let the strategy re-shard its per-stage state, remap the
        omega statistics, and price the state movement through the wall-clock
        model's link bandwidth."""
        old_part, old_slots = self.part, self._slots
        new_part = StagePartition(self.model.cfg, len(new_slots))
        moved = moved_layers(old_part, old_slots, new_part, new_slots)
        nbytes = moved * self.wall.layer_bytes(old_part.num_layers)
        t0 = telemetry.clock()
        self.part = new_part
        self._slots = list(new_slots)
        self._rebuild_fused_step()
        state = self.strategy.on_layout_change(state, old_part, new_part)
        state = TrainState(
            state.params, state.opt_state, state.lr_scale,
            remap_stage_stats(old_part, new_part, state.omegas),
            state.effective_step)
        cost = self.wall.relayout_time_s(nbytes)
        telemetry.complete("repartition", t0, cat="trainer",
                           direction=direction, to_stages=new_part.num_stages)
        telemetry.emit(
            "repartition", wall_step=wall_step, direction=direction,
            from_stages=old_part.num_stages, to_stages=new_part.num_stages,
            moved_layers=int(moved), nbytes=float(nbytes), cost_s=cost)
        self.repartition_log.append(
            (wall_step, direction, old_part.num_stages, new_part.num_stages,
             int(moved), cost))
        return state, cost

    # ---- main loop ----------------------------------------------------
    def run(self, batches, eval_batches: Optional[List] = None,
            verbose: bool = False) -> Tuple[TrainState, History]:
        tcfg = self.tcfg
        strategy = self.strategy
        init_key = jax.random.PRNGKey(tcfg.seed)
        params = self.model.init(init_key)
        # the failure-event subkey stream is fold_in-derived so it is
        # decorrelated from the init draws; the init key itself must stay
        # exactly PRNGKey(seed) — fresh_init (checkpointless restarts)
        # replays the same draw
        key = jax.random.fold_in(init_key, 1)
        state = self._placed(TrainState(params, init_adam(params)))
        hist = History()
        clock = 0.0
        self._eval_batches = [
            {k: jnp.asarray(v) for k, v in eb.items()}
            for eb in eval_batches] if eval_batches else None
        self._prefetch = WindowPrefetcher(batches)

        telemetry.emit(
            "run_start", arch=self.model.cfg.name, strategy=strategy.name,
            backend=self.backend, steps=tcfg.steps,
            num_stages=self.rcfg.num_stages,
            tokens_per_step=tcfg.global_batch * tcfg.seq_len)

        wall_step = 0
        max_wall = tcfg.steps * 10  # safety bound for rollback-heavy runs
        try:
            state, hist, clock, wall_step = self._loop(
                verbose, state, hist, clock, wall_step, max_wall, key)
        finally:
            # release background resources (async snapshot writers, the
            # batch prefetcher) even when the loop raises
            self._prefetch.close()
            strategy.on_run_end()

        hist.wall_iters = wall_step
        if state.effective_step < tcfg.steps:
            # the max_wall safety bound fired: the run is NOT converged, and
            # rollback-heavy sweeps must not masquerade as such
            hist.truncated = True
            telemetry.emit(
                "truncation", wall_iters=wall_step,
                effective_step=state.effective_step, target_steps=tcfg.steps)
            warnings.warn(
                f"Trainer.run truncated at max_wall={max_wall} wall "
                f"iterations (effective_step={state.effective_step}/"
                f"{tcfg.steps}); results are incomplete", RuntimeWarning,
                stacklevel=2)
        telemetry.emit(
            "run_end", effective_steps=state.effective_step,
            wall_iters=hist.wall_iters, dispatches=hist.dispatches,
            failures=len(hist.failures), truncated=hist.truncated,
            clock_s=clock)
        return state, hist

    def _handle_failures(self, state: TrainState, hist: History,
                         clock: float, wall_step: int, key,
                         failure_overhead) -> Tuple[TrainState, float, Any]:
        """Failures arrive at iteration boundaries; consecutive-stage runs
        (beyond-paper, §6 future work) are recovered together when the
        strategy advertises the capability."""
        strategy = self.strategy
        slots = sorted(self.schedule.at(wall_step))
        departed_at = (getattr(self.schedule, "departed_at", None)
                       if self._allow_repartition else None)
        departed = (set(departed_at(wall_step))
                    if departed_at is not None else set())
        # the schedule speaks in cluster-slot identities; recovery math in
        # partition stage indices — identical until the first shrink
        slot_to_stage = {s: i for i, s in enumerate(self._slots)}

        def charge(slot: int) -> None:
            nonlocal clock
            hist.failures.append((wall_step, slot))
            cost = strategy.failure_cost()
            clock += cost
            # store-backed strategies report the actual serialized
            # bytes shipped to the replacement node; drained
            # unconditionally (the per-event queue must stay in
            # lockstep with failure_cost even when the schedule has no
            # repricing hook)
            nbytes = strategy.consume_restore_bytes()
            overhead = 0.0
            if failure_overhead is not None:
                overhead = (failure_overhead(wall_step, slot)
                            if nbytes is None else
                            failure_overhead(wall_step, slot, nbytes))
                clock += overhead
            telemetry.emit("failure", wall_step=wall_step, stage=slot,
                           cost_s=cost, overhead_s=overhead,
                           nbytes=nbytes)

        # 1) permanent departures first: reconstruct values in the old
        #    layout, then shrink the partition to the survivors — but only
        #    when the strategy accepts the priced re-layout and at least
        #    two stages would remain
        shrink_slots: List[int] = []
        transient: List[Tuple[int, int]] = []   # (slot, stage)
        for slot in slots:
            stage = slot_to_stage.get(slot)
            if stage is None:
                continue   # slot already departed at an earlier boundary
            accepted = False
            if slot in departed and len(self._slots) - len(shrink_slots) > 2:
                key, sub = jax.random.split(key)
                event = FailureContext(stage=stage, wall_step=wall_step,
                                       key=sub, hist=hist)
                cand_slots = [s for s in self._slots
                              if s != slot and s not in shrink_slots]
                cand = StagePartition(self.model.cfg, len(cand_slots))
                moved = moved_layers(self.part, self._slots, cand, cand_slots)
                nbytes = moved * self.wall.layer_bytes(self.part.num_layers)
                if strategy.accept_repartition(event, nbytes):
                    state = strategy.handle_departure(state, event)
                    shrink_slots.append(slot)
                    charge(slot)
                    accepted = True
            if not accepted:
                transient.append((slot, stage))

        # 2) transient failures (and declined departures): consecutive-stage
        #    runs (beyond-paper, §6 future work) recovered together when the
        #    strategy advertises the capability; adjacency is a *partition*
        #    property, so runs group by stage index
        runs: List[List[Tuple[int, int]]] = []
        for slot, stage in transient:
            if runs and stage == runs[-1][-1][1] + 1:
                runs[-1].append((slot, stage))
            else:
                runs.append([(slot, stage)])
        for run in runs:
            key, sub = jax.random.split(key)
            event = FailureContext(stage=run[0][1], wall_step=wall_step,
                                   key=sub, hist=hist)
            if len(run) > 1 and strategy.handles_consecutive:
                state = strategy.handle_consecutive(
                    state, [stage for _, stage in run], event)
            else:
                for _, stage in run:
                    state = strategy.handle_failure(
                        state, dataclasses.replace(event, stage=stage))
            for slot, _ in run:
                charge(slot)

        # 3) one shrink covers every accepted departure at this boundary
        if shrink_slots:
            survivors = [s for s in self._slots if s not in shrink_slots]
            state, cost = self._repartition(
                state, survivors, wall_step=wall_step, direction="shrink")
            clock += cost
        return state, clock, key

    def _loop(self, verbose, state, hist, clock, wall_step, max_wall, key):
        tcfg = self.tcfg
        strategy = self.strategy

        # per-event wall-clock hooks: a simulated cluster (repro.sim)
        # stretches iterations by its slowest node and adds node-dependent
        # recovery overheads; the legacy FailureSchedule has neither, so the
        # constant per-strategy pricing stands unchanged
        iter_factor = getattr(self.schedule, "iteration_factor", None)
        failure_overhead = getattr(self.schedule, "failure_overhead", None)
        observed_rate = getattr(self.schedule, "observed_rate", None)
        # elastic hooks (simulated clusters only): regrow events rebalance a
        # shrunk layout back toward K0, and iteration pacing follows only the
        # slots the layout actually runs on
        regrown_at = (getattr(self.schedule, "regrown_at", None)
                      if self._allow_repartition else None)
        iter_factor_active = (
            getattr(self.schedule, "iteration_factor_active", None)
            if self._allow_repartition else None)

        replay = strategy.replay_horizon()

        while state.effective_step < tcfg.steps and wall_step < max_wall:
            # 0) environment telemetry (the simulator's observed failure
            #    rate) reaches the strategy before this iteration's events
            if observed_rate is not None:
                strategy.observe_environment(observed_rate(wall_step))

            # 0b) fresh capacity at this boundary: grow the layout back
            #     (the resident tower never moved — only the cut changes)
            if regrown_at is not None and \
                    len(self._slots) < self.rcfg.num_stages:
                back = [s for s in regrown_at(wall_step)
                        if s not in self._slots]
                if back:
                    state, cost = self._repartition(
                        state, sorted(self._slots + back),
                        wall_step=wall_step, direction="grow")
                    clock += cost

            # 1) failures at this boundary
            if self.schedule is not None:
                with telemetry.span("failures", cat="trainer",
                                    wall_step=wall_step):
                    state, clock, key = self._handle_failures(
                        state, hist, clock, wall_step, key, failure_overhead)

            # 2) fused window: K steps, one dispatch, zero interior syncs.
            #    The dispatch span uses the manual clock/complete pattern —
            #    a `with` block around a donating call would make the
            #    donation-liveness lint see the donated-arg read and the
            #    re-dispatch as one statement (and it is a no-op two-call
            #    path when telemetry is disabled anyway).  The anchor ties
            #    the recorder's clock to the profiler's once per window.
            with telemetry.span("window_prepare", cat="trainer",
                                wall_step=wall_step):
                if self.schedule is not None:
                    state = self._placed(state)
                k = self._window_size(wall_step, state.effective_step,
                                      max_wall)
                stacked = self._prefetch.take(state.effective_step, k)
            t0 = telemetry.clock()
            telemetry.anchor()
            params, opt_state, lr_scale, outs = self.fused_step(
                state.params, state.opt_state,
                {kk: jnp.asarray(v) for kk, v in stacked.items()},
                state.lr_scale)
            telemetry.complete("window_dispatch", t0, cat="trainer",
                               k=k, wall_step=wall_step,
                               backend=self.backend)
            hist.dispatches += 1
            self.dispatched_buckets.add(k)

            # while the device chews on this window, line up the next one
            # (contiguous continuation — a failure at the boundary replays
            # from the cache instead)
            next_k = self._window_size(wall_step + k,
                                       state.effective_step + k, max_wall)
            if state.effective_step + k < tcfg.steps:
                self._prefetch.prime(state.effective_step + k, next_k)

            # 3) drain the window: ONE host sync for K steps of metrics
            #    (the lr-scale carry rides the same transfer as the rings)
            with telemetry.span("window_drain", cat="trainer", k=k):
                ring, lr_scale = jax.device_get((outs, lr_scale))
            lr_scale = float(lr_scale)
            losses = ring["loss"]
            state = TrainState(params, opt_state, lr_scale,
                               ring["omegas"][-1],
                               state.effective_step + k)

            # 4) host-side bookkeeping, per wall iteration, in the exact
            #    order the eager loop used (telemetry -> pricing -> hist);
            #    steps 4-5 open the boundary at wall step `wall_step + k`
            with telemetry.span("window_bookkeeping", cat="trainer",
                                wall_step=wall_step + k):
                stretch = 0.0
                for i in range(k):
                    if i > 0 and observed_rate is not None:
                        strategy.observe_environment(
                            observed_rate(wall_step + i))
                    if iter_factor_active is not None and \
                            len(self._slots) < self.rcfg.num_stages:
                        # shrunk layout: pace by the surviving slots only —
                        # departed slots no longer stall the pipeline
                        factor = iter_factor_active(wall_step + i, self._slots)
                    elif iter_factor is not None:
                        factor = iter_factor(wall_step + i)
                    else:
                        factor = 1.0
                    clock += strategy.iteration_cost() * factor
                    stretch += factor
                    hist.steps.append(state.effective_step - k + i + 1)
                    hist.wall_time.append(clock)
                    hist.loss.append(float(losses[i]))
                telemetry.emit("step_window", wall_step=wall_step, k=k,
                               effective_step=state.effective_step,
                               loss=float(losses[-1]), clock_s=clock,
                               stretch=stretch / k)

                # 5) strategy bookkeeping on the drained state (checkpoint
                #    saves, adaptive windows...); interior steps were certified
                #    skippable by after_step_horizon
                strategy.after_step(state, hist)
                if replay is not None:
                    self._prefetch.evict_below(state.effective_step - replay)

                if self._eval_batches and \
                        state.effective_step % tcfg.eval_every == 0:
                    el = float(np.mean([
                        float(self.eval_step(state.params, eb))
                        for eb in self._eval_batches]))
                    hist.eval_loss.append((state.effective_step, clock, el))
                    telemetry.emit("eval", step=state.effective_step, loss=el,
                                   clock_s=clock)
                    if verbose:
                        telemetry.log(
                            f"  step {state.effective_step:4d} "
                            f"wall {clock/3600:7.2f}h loss "
                            f"{losses[-1]:.3f} eval {el:.3f}")
            wall_step += k

        return state, hist, clock, wall_step
