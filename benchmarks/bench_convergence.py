"""Fig. 3 — convergence of recovery strategies under 10% failure rate.

Trains the bench model with all four strategies (checkpointing, redundant
computation, CheckFree, CheckFree+) under the SAME failure schedule and
reports eval loss over iterations and over modelled wall-clock.  Paper
expectations: redundant comp converges fastest per-iteration (failures are
lossless) but pays 1.65x per iteration; CheckFree/+ track closely; pure
checkpointing trails because each failure rolls the model back.
"""
from __future__ import annotations

from benchmarks.common import FAST_STEPS, fmt_table, run_strategy, save_json
from repro.launch.compile_cache import configure_compile_cache

STRATEGIES = ["checkpoint", "redundant", "checkfree", "checkfree_plus"]


def run(steps: int = FAST_STEPS, rate: float = 0.10, verbose: bool = False):
    recs = {s: run_strategy(strategy=s, rate=rate, steps=steps,
                            verbose=verbose) for s in STRATEGIES}
    rows = []
    for s, r in recs.items():
        best = min(e for _, _, e in r["eval_loss"])
        wall_h = r["wall_time"][-1] / 3600.0
        rows.append([s, r["n_failures"], r["wall_iters"],
                     f"{r['final_eval']:.4f}", f"{best:.4f}",
                     f"{wall_h:.1f}"])
    print(f"\n== Fig. 3 — convergence under {rate:.0%}/h failures "
          f"({steps} effective steps) ==")
    print(fmt_table(["strategy", "failures", "wall_iters", "final_eval",
                     "best_eval", "total_wall_h"], rows))
    out = {s: {"eval_loss": r["eval_loss"], "loss": r["loss"],
               "wall_time": r["wall_time"], "n_failures": r["n_failures"],
               "wall_iters": r["wall_iters"]} for s, r in recs.items()}
    save_json("fig3_convergence.json", out)
    return out


def main() -> None:
    run()


if __name__ == "__main__":
    configure_compile_cache()
    main()
