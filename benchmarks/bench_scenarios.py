"""Scenario x strategy sweep on the simulated cluster (``repro.sim``).

Beyond the paper: prices every recovery policy against *environments*
instead of a single failure rate — the paper's Bernoulli churn with node
costs, diurnal spot preemption on heterogeneous nodes, a correlated
flash-crowd reclaim storm, Weibull wear-out, and recorded trace replay.
Wall-clock includes the simulator's node-dependent costs (stragglers and
spares stretch iterations; restart latency and state-transfer bandwidth
price each recovery).

    PYTHONPATH=src python -m benchmarks.bench_scenarios
    PYTHONPATH=src python -m benchmarks.bench_scenarios --smoke  # CI wiring
    PYTHONPATH=src python -m benchmarks.bench_scenarios \
        --scenarios spot_diurnal,trace:spot_demo.jsonl --strategies adaptive
"""
from __future__ import annotations

import argparse
import math
from typing import List, Optional

from benchmarks.common import FAST_STEPS, fmt_table, run_strategy, save_json
from repro.launch.compile_cache import configure_compile_cache

SCENARIOS = ["paper_10pct", "spot_diurnal", "flash_crowd", "wearout",
             "spot_shrink", "trace:spot_demo.jsonl"]
STRATEGIES = ["checkfree", "checkfree_plus", "checkpoint", "tiered_ckpt",
              "neighbor", "redundant", "adaptive", "elastic"]

# the CI smoke sweep: every process family (incl. a trace replay and the
# permanent-departure shrink scenario) x the paper's policy + both
# statestore-backed baselines (their recovery wall-clock is priced through
# the store's tier bandwidths) + the elastic repartitioner, tiny step
# count, no cache
SMOKE_SCENARIOS = ["bernoulli", "spot_diurnal", "flash_crowd",
                   "spot_shrink", "trace:spot_demo.jsonl"]
SMOKE_STRATEGIES = ["checkfree", "tiered_ckpt", "neighbor", "elastic"]


def run(steps: int = FAST_STEPS, scenarios: Optional[List[str]] = None,
        strategies: Optional[List[str]] = None, use_cache: bool = True,
        verbose: bool = False):
    scenarios = scenarios or SCENARIOS
    strategies = strategies or STRATEGIES
    rows, out = [], {}
    for sc_name in scenarios:
        for strategy in strategies:
            rec = run_strategy(strategy=strategy, scenario=sc_name,
                               steps=steps, use_cache=use_cache,
                               verbose=verbose)
            final = rec["final_eval"]
            rows.append([sc_name, strategy, rec["n_failures"],
                         rec["wall_iters"],
                         f"{rec['wall_time'][-1] / 3600:.1f}",
                         f"{rec['avg_iter_time_s']:.0f}",
                         "-" if math.isnan(final) else f"{final:.4f}",
                         "yes" if rec.get("truncated") else ""])
            out.setdefault(sc_name, {})[strategy] = {
                "n_failures": rec["n_failures"],
                "wall_iters": rec["wall_iters"],
                "wall_hours": rec["wall_time"][-1] / 3600,
                "iter_time_s": rec["iter_time_s"],
                "avg_iter_time_s": rec["avg_iter_time_s"],
                "final_eval": final,
                "truncated": rec.get("truncated", False),
            }
    print(f"\n== Scenario x strategy sweep ({steps} steps) ==")
    print(fmt_table(["scenario", "strategy", "failures", "wall_iters",
                     "wall_h", "s/iter", "final_eval", "trunc"], rows))
    save_json("scenarios.json", out)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI wiring check: tiny steps, one strategy, "
                         "every process family incl. trace replay")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--scenarios", default="",
                    help="comma-separated scenario names / trace:<file>")
    ap.add_argument("--strategies", default="",
                    help="comma-separated recovery strategy names")
    args = ap.parse_args()

    scenarios = [s for s in args.scenarios.split(",") if s] or None
    strategies = [s for s in args.strategies.split(",") if s] or None
    if args.smoke:
        # 12 steps reaches the demo trace's first preemption (t=0.8 h ->
        # step 9), so the replay path exercises a real recovery
        out = run(steps=args.steps or 12,
                  scenarios=scenarios or SMOKE_SCENARIOS,
                  strategies=strategies or SMOKE_STRATEGIES, use_cache=False)
        assert all(rec["wall_iters"] > 0
                   for per_sc in out.values() for rec in per_sc.values())
        # the trace replay must actually deliver a preemption, or the
        # recovery path silently loses its CI coverage
        assert all(rec["n_failures"] >= 1
                   for sc, per_sc in out.items() if sc.startswith("trace:")
                   for rec in per_sc.values()), "trace replay saw no failures"
        # the statestore strategies must price their snapshot traffic
        # through the tier specs: replication/write residuals make their
        # nominal iteration strictly dearer than checkfree's bare iteration
        for sc, per_sc in out.items():
            if "checkfree" in per_sc:
                base = per_sc["checkfree"]["iter_time_s"]
                for s in ("tiered_ckpt", "neighbor"):
                    if s in per_sc:
                        assert per_sc[s]["iter_time_s"] > base, (sc, s)
        print("smoke OK: all scenarios ran end-to-end through Trainer "
              f"({', '.join(strategies or SMOKE_STRATEGIES)})")
        return
    run(steps=args.steps or FAST_STEPS, scenarios=scenarios,
        strategies=strategies)


if __name__ == "__main__":
    configure_compile_cache()
    main()
