"""Benchmark runner — one bench per paper table/figure + kernels + roofline.

Usage:
    PYTHONPATH=src python -m benchmarks.run              # full suite
    PYTHONPATH=src python -m benchmarks.run --only fig2,kernels
    REPRO_BENCH_STEPS=120 PYTHONPATH=src python -m benchmarks.run  # faster

Results land in ``benchmarks/results/*.json`` (+ cached strategy runs that
are shared across benches).
"""
from __future__ import annotations

import argparse
import time
import traceback

from repro.launch.compile_cache import configure_compile_cache

BENCHES = {
    "kernels": ("kernel microbenches vs oracle", "benchmarks.bench_kernels"),
    "fig2": ("reinit strategies", "benchmarks.bench_reinit"),
    "fig3": ("convergence under failures", "benchmarks.bench_convergence"),
    "table2": ("iteration/train wall-clock", "benchmarks.bench_throughput"),
    "fig4a": ("failure-rate sweep", "benchmarks.bench_failure_rates"),
    "fig4b": ("checkpoint-frequency sweep", "benchmarks.bench_ckpt_freq"),
    "fig5b": ("swap overhead", "benchmarks.bench_swap_overhead"),
    "table3": ("held-out eval", "benchmarks.bench_eval"),
    "sec44": ("recovery-error bound term", "benchmarks.bench_recovery_error"),
    "scenarios": ("simulated-cluster scenario sweep",
                  "benchmarks.bench_scenarios"),
    "roofline": ("dry-run roofline report", "benchmarks.roofline"),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(BENCHES))
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(BENCHES)

    failures = []
    for name in names:
        desc, module = BENCHES[name]
        print(f"\n{'=' * 72}\n[bench:{name}] {desc}\n{'=' * 72}")
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["run"])
            mod.run()
            print(f"[bench:{name}] done in {time.time() - t0:.0f}s")
        except Exception:  # noqa: BLE001
            failures.append(name)
            print(f"[bench:{name}] FAILED:\n{traceback.format_exc()}")
    print(f"\n{'=' * 72}")
    if failures:
        print(f"FAILED benches: {failures}")
        raise SystemExit(1)
    print(f"all {len(names)} benches passed")


if __name__ == "__main__":
    configure_compile_cache()
    main()
