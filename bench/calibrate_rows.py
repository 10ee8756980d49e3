"""Readings that set the correctness limits of a cell whose batch is too
small for ``bench.calibrate``'s half-of-each-batch fault, at the cell's
own size, on the chip.

    python3 -m bench.calibrate_rows --workload <name> --seeds 1 2 3

At a batch of two rows the half batch is one row, which the swap's two
half-batches cannot split.  In its place, for each seed, two faults follow
the cell's first window in the program's place and are read against the
float32 reference as a run reads the program:

- ``row_dropped``: the step trains on the first row alone (the second
  row's loss left out), in order;
- ``half_tokens``: the step leaves out the second half of every row's
  tokens.

Beside them, as ``bench.calibrate`` reads them, the float8 control and the
recovery rule at bfloat16; and the ``recovery_gap`` of a recovery that
leaves each lost stage as it was, on the initial weights.  One JSON line
per seed.
"""
import argparse
import functools
import json
import sys
import time

#: the planted faults, by name
FAULTS = ("row_dropped", "half_tokens")


def _fault_batch(fault: str, batch):
    if fault == "row_dropped":
        return {k: v[:1] for k, v in batch.items()}
    return {k: v[:, :v.shape[1] // 2] for k, v in batch.items()}


@functools.lru_cache(maxsize=None)
def _fault_step(config_json: str, fault: str):
    """``check._reference_step`` at float32 with ``fault`` planted in its
    loss."""
    import jax
    import jax.numpy as jnp

    from bench import check
    from bench.reference import common
    config = json.loads(config_json)
    ref = check.reference_module(config)
    model, train = config["model"], config["train"]
    layers, stages = model["num_layers"], train["num_stages"]
    opt = train["optimizer"]

    def model_loss(p, b, order):
        return ref.loss(p, b, order, model, "float32")

    def loss_fn(params, batch):
        batch = _fault_batch(fault, batch)
        if fault == "row_dropped":
            return model_loss(params, batch, list(range(layers)))
        return common.swap_loss(model_loss, params, batch, layers, stages)

    @jax.jit
    def step(params, m, v, batch, t, lr):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        per = layers // stages
        omegas = jnp.stack([
            sum(jnp.sum(jnp.square(g[s * per:(s + 1) * per]))
                for g in jax.tree.leaves(grads[check.TOWER]))
            for s in range(stages)])
        params, m, v = common.adam_step(opt, params, grads, m, v, (t, lr))
        return params, m, v, loss, omegas

    return step


def fault_capture(config, seed: int, k0: int, fault: str):
    """What ``check.reference_capture`` returns, for the reference's first
    ``k0`` steps with ``fault`` planted."""
    import jax
    import jax.numpy as jnp

    from bench import check
    from bench.reference import common
    from bench.traffic import token_batches
    model, train = config["model"], config["train"]
    step = _fault_step(json.dumps(config, sort_keys=True), fault)
    params = check.init_params(config, seed)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    data = token_batches(seed, train["global_batch"], train["seq_len"],
                         model["vocab_size"])
    losses = []
    for t in range(1, k0 + 1):
        batch = {k: jnp.asarray(x) for k, x in next(data).items()}
        params, m, v, loss, omegas = step(
            params, m, v, batch, jnp.float32(t),
            jnp.float32(common.learning_rate(train["optimizer"], t)))
        losses.append(loss)
    return check.training_capture(params, m, omegas, jax.device_get(losses))


def stage_left_gaps(config, seed: int):
    """Per lost stage, the worst leaf's ``recovery_gap`` of a recovery that
    leaves the stage as it was: |stage - rule| / |rule| on the initial
    weights."""
    import jax
    import jax.numpy as jnp

    from bench import check
    from bench.reference import common
    stages = config["train"]["num_stages"]
    tower = check.init_params(config, seed)[check.TOWER]
    per = jax.tree.leaves(tower)[0].shape[0] // stages
    parts = [jax.tree.map(lambda a: a[s * per:(s + 1) * per], tower)
             for s in range(stages)]
    omegas = jnp.arange(1.0, stages + 1.0)
    gaps = {}
    for failed in range(stages):
        want = common.recovered_stage(parts, failed, omegas)
        gaps[str(failed)] = max(
            float(check._norm(a - b) / check._norm(b))
            for a, b in zip(jax.tree.leaves(parts[failed]),
                            jax.tree.leaves(want)))
    return gaps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.load_workload(args.workload)
    sys.path.insert(0, harness.SRC)
    harness.require_devices(spec["chips"])
    from bench import calibrate, check
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
        print(json.dumps({
            "workload": args.workload, "seed": seed, "k0": k0,
            "reference_s": ref_s,
            "control_float8": check.readings(control, ref),
            **{f"fault_{fault}": check.readings(
                fault_capture(config, seed, k0, fault), ref)
               for fault in FAULTS},
            "recovery_gap_bfloat16": calibrate._merge_reading(
                check, common, config, seed, stages),
            "recovery_gap_stage_left": stage_left_gaps(config, seed)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
