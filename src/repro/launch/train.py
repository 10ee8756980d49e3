"""End-to-end training driver with CheckFree recovery.

    PYTHONPATH=src python -m repro.launch.train \
        --arch paper-llama-124m --strategy checkfree_plus \
        --steps 300 --rate 0.10 [--reduced] [--seq 512 --batch 8]
    PYTHONPATH=src python -m repro.launch.train \
        --strategy adaptive --scenario spot_diurnal --reduced   # repro.sim

``--arch`` accepts any assigned architecture id or the paper's own models
(paper-llama-{124m,500m,1.5b}).  ``--reduced`` swaps in the CPU-sized smoke
variant of the same family.  The driver wires: config -> model -> data ->
failure schedule -> Trainer (recovery strategy) and reports the History.
"""
from __future__ import annotations

import argparse
import os
from typing import Optional, Sequence

from repro import telemetry
from repro.telemetry import log
from repro.config import OptimizerConfig, RecoveryConfig, TrainConfig
from repro.configs import ARCHS, PAPER_MODELS, get_config, get_stages, reduced
from repro.core.failures import FailureSchedule
from repro.core.trainer import Trainer
from repro.core.walltime import WallClockModel
from repro.data.pipeline import batch_for, make_batches, SyntheticLM
from repro.launch.compile_cache import configure_compile_cache
from repro.models.model import build_model
from repro.recovery import available_strategies

import numpy as np


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-llama-124m",
                    choices=sorted(ARCHS) + sorted(PAPER_MODELS))
    ap.add_argument("--strategy", default="checkfree",
                    choices=available_strategies())
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--rate", type=float, default=0.10,
                    help="hourly per-stage failure probability")
    ap.add_argument("--scenario", default="",
                    help="simulated-cluster environment (repro.sim): a "
                         "registered scenario name or trace:<file>; "
                         "supersedes --rate's Bernoulli schedule")
    ap.add_argument("--depart-prob", type=float, default=None,
                    help="override the scenario's per-failure probability "
                         "that the node is permanently gone (elastic "
                         "repartitioning; see docs/elastic.md)")
    ap.add_argument("--regrow-h", type=float, default=None,
                    help="override the scenario's hours until fresh "
                         "capacity replaces a departed node (inf = never)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=0,
                    help="0 -> the config's max_seq_len (capped at 512)")
    ap.add_argument("--lr", type=float, default=0.0, help="0 -> family LR")
    ap.add_argument("--stages", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fuse-window", type=int, default=8,
                    help="max iterations fused into one on-device scan "
                         "window (1 = eager per-step loop; see docs/perf.md)")
    ap.add_argument("--backend", default="host", choices=["host", "spmd"],
                    help="'spmd' runs the pipeline-parallel shard_map "
                         "backend (one device per stage; under "
                         "JAX_PLATFORMS=cpu it forces host devices — see "
                         "docs/pipeline.md)")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized variant of the same family")
    ap.add_argument("--layers", type=int, default=0,
                    help="override the config's transformer layer count "
                         "(0 = keep); with --reduced this lifts the 2-layer "
                         "floor so a >2-stage pipeline can exercise elastic "
                         "shrink on CPU (docs/elastic.md)")
    ap.add_argument("--out", default="", help="write History JSON here")
    ap.add_argument("--telemetry-dir", default="",
                    help="record the structured telemetry event stream "
                         "(events.jsonl) into this directory; summarize "
                         "with `python -m repro.telemetry.report <dir>` "
                         "(see docs/observability.md)")
    ap.add_argument("--trace", action="store_true",
                    help="also export a Chrome trace_event JSON "
                         "(trace.json, loadable in Perfetto) into "
                         "--telemetry-dir")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    configure_compile_cache()

    rec = None
    if args.telemetry_dir:
        rec = telemetry.configure(run_dir=args.telemetry_dir)
    elif args.trace:
        ap.error("--trace needs --telemetry-dir")
    if (args.depart_prob is not None or args.regrow_h is not None) \
            and not args.scenario:
        ap.error("--depart-prob/--regrow-h need --scenario (repro.sim)")

    cfg = get_config(args.arch)
    stages = args.stages or get_stages(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
        stages = min(stages, 2)
    if args.layers > 0:
        import dataclasses
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
        stages = args.stages or stages
    stages = min(max(stages, 1), cfg.num_layers)
    if args.backend == "spmd" and cfg.num_layers % stages != 0:
        # the SPMD mesh shards the stacked tower uniformly over devices;
        # the host backend takes any layout (variable per-stage layer
        # counts — docs/elastic.md), so only spmd snaps to a divisor
        stages = max(d for d in range(1, cfg.num_layers + 1)
                     if cfg.num_layers % d == 0 and d <= stages)
    if args.backend == "spmd":
        # one device per stage: under JAX_PLATFORMS=cpu ask for virtual host
        # devices (only works before jax's first backend query); on an
        # accelerator the stages map onto its chips
        from repro.launch.mesh import force_host_devices
        force_host_devices(stages)
    seq = args.seq or min(cfg.max_seq_len, 512)
    lr = args.lr or 3e-4

    from repro.recovery import default_protect_edges
    protect = default_protect_edges(args.strategy)
    rcfg = RecoveryConfig(
        strategy=args.strategy, num_stages=stages,
        failure_rate_per_hour=args.rate, scenario=args.scenario,
        seed=args.seed, protect_edge_stages=protect)
    tcfg = TrainConfig(
        global_batch=args.batch, microbatch=args.batch, seq_len=seq,
        steps=args.steps, eval_every=max(args.steps // 10, 1),
        fuse_window=args.fuse_window, seed=args.seed,
        optimizer=OptimizerConfig(lr=lr, total_steps=args.steps),
        recovery=rcfg)

    model = build_model(cfg)
    n = cfg.param_count()
    log(f"arch={cfg.name} ({n / 1e6:.0f}M params) strategy={args.strategy} "
        f"backend={args.backend} stages={stages} steps={args.steps} "
        f"rate={args.rate:.0%}/h seq={seq} batch={args.batch}")

    wall = WallClockModel(model_bytes=4 * n * 2)
    schedule = None
    if args.scenario:
        # the Trainer builds the schedule from rcfg.scenario unless the
        # shrink knobs override the scenario's churn shape, in which case
        # the driver simulates with the overridden config itself
        overrides = {}
        if args.depart_prob is not None:
            overrides["depart_prob"] = args.depart_prob
        if args.regrow_h is not None:
            overrides["regrow_h"] = args.regrow_h
        if overrides:
            from repro.sim import simulate
            from repro.sim.scenario import get_scenario
            schedule = simulate(
                get_scenario(args.scenario, **overrides),
                steps=args.steps * 10, seed=args.seed, num_stages=stages,
                protect_edges=rcfg.protect_edge_stages, wall=wall)
    elif args.rate > 0 and args.strategy != "none":
        schedule = FailureSchedule(
            rate_per_hour=args.rate, iteration_time_s=rcfg.iteration_time_s,
            num_stages=stages, steps=args.steps * 10, seed=args.seed,
            protect_edges=rcfg.protect_edge_stages)
        log(schedule.summary())

    src = SyntheticLM(cfg.vocab_size, seed=1234)
    batches = make_batches(cfg, batch=args.batch, seq=seq, seed=args.seed,
                           source=src)
    rng = np.random.default_rng(999)
    evals = [batch_for(cfg, src.sample(rng, args.batch, seq), rng)
             for _ in range(2)]

    trainer = Trainer(model, tcfg, wall=wall, schedule=schedule,
                      backend=args.backend)
    if args.scenario and trainer.schedule is not None:
        log(trainer.schedule.summary())
    state, hist = trainer.run(batches, evals, verbose=not args.quiet)

    log(f"\ndone: {state.effective_step} effective steps over "
        f"{hist.wall_iters} wall iterations, "
        f"{len(hist.failures)} stage failures, final loss "
        f"{hist.loss[-1]:.4f}, modelled wall "
        f"{hist.wall_time[-1] / 3600:.1f}h", level=0)
    for (step, err) in hist.recovery_errors:
        log(f"  recovery @ wall-iter {step}: error term {err:.3e}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(hist.to_json())
        log(f"history -> {args.out}")
    if rec is not None:
        if args.trace:
            log(f"trace -> {rec.write_chrome_trace()}")
        rec.close()
        telemetry.set_recorder(None)
        log(f"telemetry -> {os.path.join(args.telemetry_dir, 'events.jsonl')}"
            f"  (summarize: python -m repro.telemetry.report "
            f"{args.telemetry_dir})")


if __name__ == "__main__":
    main()
