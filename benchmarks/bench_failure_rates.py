"""Fig. 4a — CheckFree+ convergence across failure frequencies (5/10/16%).

Paper expectation: graceful degradation — validation loss only slightly
worse when the failure rate is tripled.

The failure environment is the cluster simulator's ``bernoulli`` scenario
(``repro.sim``), which is bit-identical to the legacy
``core.failures.FailureSchedule`` for the same (rate, seed) — so this
figure doubles as a live parity check of the simulator's legacy adapter.
"""
from __future__ import annotations

from benchmarks.common import FAST_STEPS, fmt_table, run_strategy, save_json
from repro.launch.compile_cache import configure_compile_cache

RATES = [0.0, 0.05, 0.10, 0.16]


def run(steps: int = FAST_STEPS, verbose: bool = False):
    recs = {r: run_strategy(strategy="checkfree_plus", rate=r,
                            scenario="bernoulli", steps=steps,
                            verbose=verbose) for r in RATES}
    rows = []
    for r, rec in recs.items():
        best = min(e for _, _, e in rec["eval_loss"])
        rows.append([f"{r:.0%}", rec["n_failures"],
                     f"{rec['final_eval']:.4f}", f"{best:.4f}"])
    print(f"\n== Fig. 4a — CheckFree+ at varying failure rates "
          f"({steps} steps) ==")
    print(fmt_table(["rate/h", "failures", "final_eval", "best_eval"], rows))
    out = {f"{r:.2f}": {"eval_loss": rec["eval_loss"],
                        "n_failures": rec["n_failures"],
                        "final_eval": rec["final_eval"]}
           for r, rec in recs.items()}
    save_json("fig4a_failure_rates.json", out)
    return out


def main() -> None:
    run()


if __name__ == "__main__":
    configure_compile_cache()
    main()
