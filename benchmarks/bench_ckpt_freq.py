"""Fig. 4b — checkpointing frequency sweep vs CheckFree+ (and beyond).

Checkpointing every 10 / 50 / 100 iterations at a 10% failure rate,
compared to CheckFree+ — plus the two statestore-backed baselines the
comparison deserves: ``tiered_ckpt`` (the frequency controls its cold disk
interval; the hot memory tier snapshots every step) and ``neighbor``
(frequency-independent in-memory replication).  Paper expectation:
CheckFree+ beats even high-frequency classic checkpointing because every
failure still rolls the whole model back; the tiered store closes most of
that gap because a stage failure only restores one shard from the hot
tier.

    PYTHONPATH=src python -m benchmarks.bench_ckpt_freq
    PYTHONPATH=src python -m benchmarks.bench_ckpt_freq --smoke   # CI wiring
"""
from __future__ import annotations

import argparse

from benchmarks.common import FAST_STEPS, fmt_table, run_strategy, save_json
from repro.launch.compile_cache import configure_compile_cache

FREQS = [10, 50, 100]
FREQ_STRATEGIES = ["checkpoint", "tiered_ckpt"]   # sweep ckpt_every
FLAT_STRATEGIES = ["neighbor", "checkfree_plus"]  # frequency-independent


def run(steps: int = FAST_STEPS, rate: float = 0.10, verbose: bool = False,
        use_cache: bool = True):
    recs = {}
    for strategy in FREQ_STRATEGIES:
        for f in FREQS:
            recs[f"{strategy}_every_{f}"] = run_strategy(
                strategy=strategy, rate=rate, steps=steps, ckpt_every=f,
                use_cache=use_cache, verbose=verbose)
    for strategy in FLAT_STRATEGIES:
        recs[strategy] = run_strategy(strategy=strategy, rate=rate,
                                      steps=steps, use_cache=use_cache,
                                      verbose=verbose)
    rows = []
    for name, r in recs.items():
        best = min(e for _, _, e in r["eval_loss"])
        rows.append([name, r["n_failures"], r["wall_iters"],
                     f"{r['final_eval']:.4f}", f"{best:.4f}",
                     f"{r['wall_time'][-1] / 3600:.1f}"])
    print(f"\n== Fig. 4b — checkpoint frequency vs CheckFree+ "
          f"(rate={rate:.0%}/h, {steps} steps) ==")
    print(fmt_table(["variant", "failures", "wall_iters", "final_eval",
                     "best_eval", "wall_h"], rows))
    out = {k: {"eval_loss": r["eval_loss"], "wall_time": r["wall_time"],
               "wall_iters": r["wall_iters"]} for k, r in recs.items()}
    save_json("fig4b_ckpt_freq.json", out)
    return out


def smoke() -> None:
    """CI wiring check: both statestore strategies (and the classic
    baseline) end-to-end through the simulated cluster, with enough churn
    that the restore paths actually fire."""
    strategies = ["tiered_ckpt", "neighbor", "checkpoint"]
    out = {}
    for strategy in strategies:
        # an explicit rate of 2.0/h on the paper scenario yields ~8 events
        # in 12 steps, so every strategy pays real tier-priced recoveries
        out[strategy] = run_strategy(
            strategy=strategy, scenario="paper_10pct", rate=2.0, steps=12,
            ckpt_every=4, use_cache=False)
    for strategy, rec in out.items():
        assert rec["wall_iters"] > 0, strategy
        assert rec["n_failures"] >= 1, (
            f"{strategy}: no failures delivered — recovery path untested")
        assert rec["wall_time"][-1] > 0, strategy
    rows = [[s, r["n_failures"], r["wall_iters"],
             f"{r['avg_iter_time_s']:.1f}"] for s, r in out.items()]
    print(fmt_table(["strategy", "failures", "wall_iters", "s/iter"], rows))
    print("smoke OK: tiered_ckpt/neighbor/checkpoint recovered through "
          "the statestore under simulated churn")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI wiring check for the statestore-backed "
                         "strategies (tiny steps, forced churn, no cache)")
    ap.add_argument("--steps", type=int, default=0)
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    run(steps=args.steps or FAST_STEPS)


if __name__ == "__main__":
    configure_compile_cache()
    main()
