"""Hot-path throughput: eager per-step loop vs fused scan windows.

Unlike the paper-figure benches (which price wall-clock through the
analytic :class:`WallClockModel`), this one measures *real* steps/s of
``Trainer.run`` with ``time.perf_counter`` — it is the harness-overhead
benchmark that seeds the repo's perf trajectory.  For each model family it
runs the same failure-free training loop at ``fuse_window=1`` (the eager
per-step loop: one dispatch + one blocking metrics drain per step) and at
fused window sizes (one dispatch + one drain per K steps), asserts the
fused loss trace is *bit-identical* to the eager one (same backend, one
loop body for every window size — see docs/perf.md), and reports steps/s
+ speedups.

Results land in ``benchmarks/results/BENCH_hotpath.json``.  ``--smoke``
runs the paper_llama smoke config only and fails hard unless the fused
window reaches >= 2x eager throughput with an exactly matching trace (the
CI regression gate).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.common import fmt_table, save_json
from repro.launch.compile_cache import configure_compile_cache
from repro.config import (ModelConfig, OptimizerConfig, RecoveryConfig,
                          TrainConfig)
from repro.configs import get_config, reduced
from repro.core.trainer import Trainer
from repro.data.pipeline import make_batches
from repro.models.model import build_model

# the paper_llama family shape (Table 4 small), shrunk until the per-step
# math is small enough that harness overhead — Python dispatch, per-step
# host syncs — dominates the eager loop; that is exactly the regime the
# fused hot path exists for (and the regime a TPU pod is in when the host
# cannot keep up with the device)
PAPER_LLAMA_SMOKE = ModelConfig(
    name="paper-llama-smoke",
    arch_type="dense",
    num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
    d_ff=88, vocab_size=128, act="silu", max_seq_len=32,
    dtype="float32", param_dtype="float32",
    source="paper Table 4 (small family), shrunk to the overhead-dominated "
           "smoke regime")

SMOKE_SEQ, SMOKE_BATCH = 8, 1


def _family(name: str) -> Dict[str, Any]:
    """Bench configs per family: the smoke llama plus reduced real archs."""
    if name == "paper_llama":
        return dict(cfg=PAPER_LLAMA_SMOKE, seq=SMOKE_SEQ, batch=SMOKE_BATCH,
                    stages=2)
    if name == "moe":
        cfg = dataclasses.replace(reduced(get_config("granite-moe-3b-a800m")),
                                  max_seq_len=64)
        return dict(cfg=cfg, seq=32, batch=2, stages=2)
    if name == "ssm":
        cfg = dataclasses.replace(reduced(get_config("mamba2-1.3b")),
                                  max_seq_len=64)
        return dict(cfg=cfg, seq=32, batch=2, stages=2)
    raise KeyError(name)


def time_run(cfg: ModelConfig, *, window: int, steps: int, seq: int,
             batch: int, stages: int, seed: int = 0, repeats: int = 3,
             backend: str = "host") -> Dict[str, Any]:
    """Real wall-clock of a failure-free Trainer.run at ``fuse_window``.

    The first run warms the jit caches (every window bucket compiles); the
    loop is then timed ``repeats`` times and the best run is reported
    (shared CI runners jitter badly; min is the standard noise floor).
    """
    rcfg = RecoveryConfig(strategy="none", num_stages=stages)
    tcfg = TrainConfig(global_batch=batch, microbatch=batch, seq_len=seq,
                       steps=steps, eval_every=10 * steps,
                       fuse_window=window,
                       optimizer=OptimizerConfig(lr=1e-3, total_steps=steps,
                                                 warmup_steps=5),
                       recovery=rcfg)
    trainer = Trainer(build_model(cfg), tcfg, schedule=None,
                      backend=backend)

    def one_run():
        batches = make_batches(cfg, batch=batch, seq=seq, seed=seed)
        t0 = time.perf_counter()
        state, hist = trainer.run(batches)
        return time.perf_counter() - t0, state, hist

    one_run()                                   # compile
    elapsed = float("inf")
    for _ in range(max(repeats, 1)):
        t, state, hist = one_run()
        elapsed = min(elapsed, t)
    assert state.effective_step == steps
    return dict(window=window, steps=steps, elapsed_s=round(elapsed, 4),
                steps_per_s=round(steps / elapsed, 2),
                dispatches=hist.dispatches, loss=hist.loss)


def run(families: List[str], windows: List[int], steps: int,
        smoke: bool = False, backend: str = "host") -> Dict[str, Any]:
    out: Dict[str, Any] = {"steps": steps, "smoke": smoke,
                           "backend": backend, "families": {}}
    rows = []
    ok = True
    for fam in families:
        spec = _family(fam)
        recs = {w: time_run(spec["cfg"], window=w, steps=steps,
                            seq=spec["seq"], batch=spec["batch"],
                            stages=spec["stages"], backend=backend)
                for w in windows}
        eager = recs[1]
        fam_out: Dict[str, Any] = {"model": spec["cfg"].name,
                                   "seq": spec["seq"],
                                   "batch": spec["batch"], "windows": {}}
        for w, rec in recs.items():
            trace_ok = rec["loss"] == eager["loss"]
            ok &= trace_ok
            speedup = rec["steps_per_s"] / eager["steps_per_s"]
            fam_out["windows"][str(w)] = {
                "steps_per_s": rec["steps_per_s"],
                "elapsed_s": rec["elapsed_s"],
                "dispatches": rec["dispatches"],
                "speedup_vs_eager": round(speedup, 2),
                "trace_matches_eager": trace_ok,
            }
            rows.append([fam, w, rec["steps_per_s"], rec["dispatches"],
                         f"{speedup:.2f}x",
                         "exact" if trace_ok else "DIVERGED"])
        out["families"][fam] = fam_out
    print("\n== hot path: eager vs fused (real steps/s) ==")
    print(fmt_table(["family", "window", "steps/s", "dispatches",
                     "speedup", "loss trace"], rows))
    out["trace_parity"] = ok
    suffix = "" if backend == "host" else f"_{backend}"
    path = save_json(f"BENCH_hotpath{suffix}.json", out)
    print(f"wrote {path}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="paper_llama smoke config only; fail unless the "
                         "fused window reaches >= 2x eager with an exact "
                         "loss-trace match (CI gate)")
    ap.add_argument("--backend", default="host", choices=["host", "spmd"],
                    help="'spmd' times the pipeline-parallel shard_map "
                         "backend (needs one device per stage; under "
                         "JAX_PLATFORMS=cpu this script forces 2 host "
                         "devices); results land in "
                         "BENCH_hotpath_spmd.json")
    ap.add_argument("--steps", type=int, default=0)
    args = ap.parse_args()

    if args.backend == "spmd":
        # one device per stage (the bench families use 2): virtual host
        # devices under JAX_PLATFORMS=cpu (before jax's first backend
        # query), the chips otherwise
        from repro.launch.mesh import force_host_devices
        force_host_devices(2)
        import jax
        if len(jax.devices()) < 2:
            raise SystemExit(
                f"spmd bench needs >= 2 devices, found "
                f"{len(jax.devices())} {jax.devices()[0].platform} "
                "device(s); on the CPU run with JAX_PLATFORMS=cpu")

    if args.smoke:
        steps = args.steps or 128
        out = run(["paper_llama"], [1, 8, 16, 32], steps, smoke=True,
                  backend=args.backend)
        fam = out["families"]["paper_llama"]["windows"]
        best_w, best = max(((w, rec["speedup_vs_eager"])
                            for w, rec in fam.items() if w != "1"),
                           key=lambda kv: kv[1])
        if not out["trace_parity"]:
            raise SystemExit("FAIL: fused loss trace diverged from eager")
        # the 2x bar is calibrated for the host backend's overhead-
        # dominated smoke regime; the spmd per-step includes real
        # cross-device collectives, so fusion buys less there — the gate
        # still catches "fusion stopped helping" regressions
        bar = 2.0 if args.backend == "host" else 1.2
        if best < bar:
            raise SystemExit(
                f"FAIL: best fused window ({best_w}) reached only "
                f"{best:.2f}x eager (>= {bar}x required)")
        print(f"smoke OK: fused window {best_w} = {best:.2f}x eager "
              f"(>= {bar}x), traces exact")
    else:
        steps = args.steps or 96
        fams = (["paper_llama", "moe"] if args.backend == "spmd"
                else ["paper_llama", "moe", "ssm"])  # spmd: dense/moe towers
        run(fams, [1, 2, 4, 8, 16], steps, backend=args.backend)


if __name__ == "__main__":
    configure_compile_cache()
    main()
