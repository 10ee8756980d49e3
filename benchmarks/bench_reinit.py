"""Fig. 2 — reinitialization strategies for failed stages.

Trains the bench model at a 16% hourly stage-failure rate (paper A.5) and
compares reinit strategies: random / copy / uniform average / CheckFree
gradient-norm-weighted average.  Expected ordering (paper Fig. 2):
weighted > copy > random.
"""
from __future__ import annotations

from benchmarks.common import (FAST_STEPS, fmt_table, iters_to_target,
                               run_strategy, save_json)
from repro.launch.compile_cache import configure_compile_cache

STRATEGIES = ["random", "copy", "uniform", "checkfree"]


def run(steps: int = FAST_STEPS, rate: float = 0.16, verbose: bool = False):
    recs = {s: run_strategy(strategy=s, rate=rate, steps=steps,
                            verbose=verbose) for s in STRATEGIES}
    # target reachable by every strategy: the worst strategy's best eval
    worst_best = max(min(e for _, _, e in r["eval_loss"])
                     for r in recs.values())
    target = worst_best + 0.02
    rows = []
    for s, r in recs.items():
        rows.append([s, r["n_failures"], f"{r['final_eval']:.4f}",
                     f"{min(e for _, _, e in r['eval_loss']):.4f}",
                     iters_to_target(r, target)])
    print("\n== Fig. 2 — reinit strategies "
          f"(rate={rate:.0%}/h, {steps} steps, floor="
          f"{recs['checkfree']['entropy_floor']:.3f} nats) ==")
    print(fmt_table(
        ["strategy", "failures", "final_eval", "best_eval",
         f"iters_to_{target:.3f}"], rows))
    out = {s: {"final_eval": r["final_eval"],
               "best_eval": min(e for _, _, e in r["eval_loss"]),
               "eval_loss": r["eval_loss"], "n_failures": r["n_failures"]}
           for s, r in recs.items()}
    save_json("fig2_reinit.json", out)
    return out


def main() -> None:
    run()


if __name__ == "__main__":
    configure_compile_cache()
    main()
