"""Hillclimb driver (EXPERIMENTS.md §Perf): re-run one (arch x shape)
dry-run under perf levers and diff the roofline terms against baseline.

    PYTHONPATH=src python -m benchmarks.hillclimb --arch mamba2-1.3b \
        --shape train_4k --levers REPRO_ACT_SHARD=seq \
        --levers REPRO_ACT_SHARD=feature,REPRO_PARAM_SHARD=fsdp

Each ``--levers`` value is a comma-separated env assignment set applied at
trace time.  Levers:
    REPRO_ACT_SHARD   = feature | seq   (layer-boundary activation sharding)
    REPRO_PARAM_SHARD = fsdp            (params over ('data','model') jointly)
Results append to benchmarks/results/hillclimb.json.
"""
from __future__ import annotations

# isort: off — dryrun must set XLA flags before jax initializes devices
from repro.launch import dryrun  # noqa: F401  (sets device count)
# isort: on

import argparse
import json
import os

from benchmarks.common import RESULTS_DIR
from repro.launch.compile_cache import configure_compile_cache

LEVER_KEYS = ("REPRO_ACT_SHARD", "REPRO_PARAM_SHARD", "REPRO_MOE_GROUP",
              "REPRO_REMAT")


def run_with(arch: str, shape: str, levers: dict) -> dict:
    for k in LEVER_KEYS:
        os.environ.pop(k, None)
    os.environ.update(levers)
    try:
        rec = dryrun.run_one(arch, shape, verbose=False)
    finally:
        for k in LEVER_KEYS:
            os.environ.pop(k, None)
    rec["levers"] = dict(levers)
    return rec


def fmt(rec: dict) -> str:
    if rec["status"] != "ok":
        return f"ERROR: {rec.get('error', '')[:120]}"
    r = rec["roofline"]
    mem = rec["memory"]["peak_est_B"] / 2**30
    return (f"compute {r['compute_s']:.3f}s  memory {r['memory_s']:.3f}s  "
            f"collective {r['collective_s']:.3f}s  dom={r['dominant']}  "
            f"mem/dev {mem:.1f}GiB  useful {r['useful_ratio']:.2f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--levers", action="append", default=[],
                    help="comma-separated K=V sets; repeatable")
    ap.add_argument("--skip-baseline", action="store_true")
    args = ap.parse_args()

    out_path = os.path.join(RESULTS_DIR, "hillclimb.json")
    history = []
    if os.path.exists(out_path):
        with open(out_path) as f:
            history = json.load(f)

    runs = []
    if not args.skip_baseline:
        runs.append({})
    for spec in args.levers:
        runs.append(dict(kv.split("=", 1) for kv in spec.split(",") if kv))

    for levers in runs:
        tag = ",".join(f"{k}={v}" for k, v in levers.items()) or "baseline"
        print(f"--- {args.arch} x {args.shape} [{tag}] ---", flush=True)
        rec = run_with(args.arch, args.shape, levers)
        print(fmt(rec), flush=True)
        history.append(rec)
        with open(out_path, "w") as f:
            json.dump(history, f, indent=1)


if __name__ == "__main__":
    configure_compile_cache()
    main()
