"""Readings that set the correctness limits, where no benchmark run gives
them: the lower-precision control and the planted faults, at a cell's own
size, on the chip.

    python3 -m bench.calibrate --workload <name> --seeds 1 2 3

For each seed the float32 reference follows the cell's first window; its
float8 control (the step below the configuration's bfloat16) and the
fault of a step that leaves half of each batch out follow the same steps
in the program's place, and each is read against the reference as a run
reads the program.  The recovery rule is read at bfloat16 against float32
on the reference's state.  A step that returns its state unchanged reads
``change_gap`` 1 by construction and needs no run.  One JSON line per
seed; the benchmark's own runs give the program's readings.
"""
import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.load_workload(args.workload)
    sys.path.insert(0, harness.SRC)
    harness.require_devices(spec["chips"])
    from bench import check
    from bench.reference import common
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()

    config = spec["config"]
    k0 = min(harness.WARM_STEPS, spec["traffic"].get("fail_every") or
             config["train"]["fuse_window"])
    stages = config["train"]["num_stages"]
    for seed in args.seeds:
        t0 = time.perf_counter()
        ref = check.reference_capture(config, seed, k0)
        ref_s = time.perf_counter() - t0
        control = check.reference_capture(config, seed, k0, "float8")
        half = check.reference_capture(config, seed, k0, half_rows=True)
        merge = _merge_reading(check, common, config, seed, stages)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "k0": k0,
            "reference_s": ref_s,
            "control_float8": check.readings(control, ref),
            "control_worst_leaves": sorted(
                check.leaf_differences(control, ref).items(),
                key=lambda kv: -kv[1])[:5],
            "fault_half_batch": check.readings(half, ref),
            "recovery_gap_bfloat16": merge}), flush=True)
    return 0


def _merge_reading(check, common, config, seed, stages) -> float:
    """Worst leaf's relative error of the middle-stage merge computed at
    bfloat16 against float32, on the initial weights."""
    import jax
    import jax.numpy as jnp
    tower = check.init_params(config, seed)[check.TOWER]
    per = jax.tree.leaves(tower)[0].shape[0] // stages
    parts = [jax.tree.map(lambda a: a[s * per:(s + 1) * per], tower)
             for s in range(stages)]
    omegas = jnp.arange(1.0, stages + 1.0)
    exact = common.recovered_stage(parts, 1, omegas)
    low = common.recovered_stage(parts, 1, omegas, "bfloat16")
    return max(float(check._norm(a - b) / check._norm(b)) for a, b in
               zip(jax.tree.leaves(low), jax.tree.leaves(exact)))


if __name__ == "__main__":
    sys.exit(main())
