"""Fig. 5b — the cost of CheckFree+'s out-of-order swapping with NO failures.

Compares convergence of the bench model trained with the 50/50 swap schedule
(CheckFree+) against the plain in-order model.  Paper expectation: a visible
convergence slowdown from swapping alone — the price paid for edge-stage
recoverability.
"""
from __future__ import annotations

from benchmarks.common import FAST_STEPS, fmt_table, run_strategy, save_json
from repro.launch.compile_cache import configure_compile_cache


def run(steps: int = FAST_STEPS, verbose: bool = False):
    recs = {
        "no_swap": run_strategy(strategy="none", rate=0.0, steps=steps,
                                verbose=verbose),
        "swap (checkfree+)": run_strategy(strategy="checkfree_plus",
                                          rate=0.0, steps=steps,
                                          verbose=verbose),
    }
    rows = []
    for name, r in recs.items():
        best = min(e for _, _, e in r["eval_loss"])
        rows.append([name, f"{r['final_eval']:.4f}", f"{best:.4f}"])
    print(f"\n== Fig. 5b — swap overhead, 0% failures ({steps} steps) ==")
    print(fmt_table(["variant", "final_eval", "best_eval"], rows))
    out = {k: {"eval_loss": r["eval_loss"], "loss": r["loss"]}
           for k, r in recs.items()}
    save_json("fig5b_swap_overhead.json", out)
    return out


def main() -> None:
    run()


if __name__ == "__main__":
    configure_compile_cache()
    main()
