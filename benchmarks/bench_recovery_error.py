"""§4.4 — the convergence bound's per-failure error term, measured directly.

The paper bounds post-failure convergence by
``O(1/t) + 2E||w1 f_{k+1} + w2 f_{k-1} - f_k||^2``; the second term is the
reinit error.  We train a failure-free model, then for each reinit strategy
replace an intermediate stage, and measure (a) the parameter-space error
term, (b) the immediate loss jump, (c) the loss after a short recovery
window.  Expectation: the error ordering weighted <= uniform < copy <<
random predicts the convergence impact — the bound's driver.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (BENCH_BATCH, BENCH_MODEL, BENCH_SEQ,
                               BENCH_STAGES, FAST_STEPS, data_source,
                               fmt_table, load_params, run_strategy,
                               save_json)
from repro.launch.compile_cache import configure_compile_cache
from repro.config import OptimizerConfig
from repro.core.recovery import recover_stage, recovery_error
from repro.core.stages import StagePartition
from repro.data.pipeline import make_batches
from repro.models.model import build_model
from repro.optim import adam_update, init_adam

STRATEGIES = ["grad_norm", "uniform", "copy_prev", "random"]
FAILED_STAGE = 2          # intermediate
RECOVERY_STEPS = 30


def run(steps: int = FAST_STEPS, verbose: bool = False):
    rec = run_strategy(strategy="none", rate=0.0, steps=steps,
                       verbose=verbose)
    params = jax.tree.map(jnp.asarray, load_params(rec))
    model = build_model(BENCH_MODEL)
    part = StagePartition(BENCH_MODEL, BENCH_STAGES)
    batches = make_batches(BENCH_MODEL, batch=BENCH_BATCH, seq=BENCH_SEQ,
                           seed=5, source=data_source())
    probe = {k: jnp.asarray(v) for k, v in next(batches).items()}

    loss_fn = jax.jit(lambda p, b: model.loss(p, b)[0])
    base_loss = float(loss_fn(params, probe))

    # omega proxies: grad sqnorm per stage from one backward pass
    grads = jax.grad(lambda p: model.loss(p, probe)[0])(params)
    omegas = part.stage_grad_sqnorms(grads)

    ocfg = OptimizerConfig(lr=1e-3, total_steps=RECOVERY_STEPS,
                           warmup_steps=0, schedule="constant")

    @jax.jit
    def train_step(p, o, b):
        (l, _), g = jax.value_and_grad(model.loss, has_aux=True)(p, b)
        p, o, _ = adam_update(ocfg, p, g, o)
        return p, o, l

    results = {}
    for strat in STRATEGIES:
        key = jax.random.PRNGKey(7)
        p2 = recover_stage(params, part, FAILED_STAGE, omegas,
                           strategy=strat, key=key)
        err = float(recovery_error(params, p2, part, FAILED_STAGE))
        jump = float(loss_fn(p2, probe))
        o = init_adam(p2)
        losses = []
        for _ in range(RECOVERY_STEPS):
            b = {k: jnp.asarray(v) for k, v in next(batches).items()}
            p2, o, l = train_step(p2, o, b)
            losses.append(float(l))
        results[strat] = {"error_term": err, "loss_after_reinit": jump,
                          "loss_after_recovery": float(np.mean(losses[-5:]))}

    rows = [[s, f"{r['error_term']:.4e}", f"{r['loss_after_reinit']:.4f}",
             f"{r['loss_after_recovery']:.4f}"]
            for s, r in results.items()]
    print(f"\n== §4.4 — recovery error term (base loss {base_loss:.4f}, "
          f"stage {FAILED_STAGE}/{BENCH_STAGES}) ==")
    print(fmt_table(["strategy", "||w1 f_k+1 + w2 f_k-1 - f_k||^2",
                     "loss@reinit", f"loss@+{RECOVERY_STEPS}"], rows))
    results["base_loss"] = base_loss

    # ---- elastic re-layout (docs/elastic.md): the departure path ---------
    # A permanent departure reconstructs the lost stage in the OLD layout
    # (the elastic strategy's grad_norm merge vs the copy_prev degrade),
    # then re-cuts to K-1 balanced stages; the error term is re-measured
    # under the shrunk variable partition whose stage inherits the lost
    # layers — exercising the variable-layout slicing end to end.
    shrunk = StagePartition(BENCH_MODEL, BENCH_STAGES - 1)
    lost_lo, _ = part.stage_bounds(FAILED_STAGE)
    heir = shrunk.stage_of_layer(lost_lo)
    elastic = {}
    for strat in ("grad_norm", "copy_prev"):
        p2 = recover_stage(params, part, FAILED_STAGE, omegas,
                           strategy=strat, key=jax.random.PRNGKey(7))
        err_old = float(recovery_error(params, p2, part, FAILED_STAGE))
        err_new = float(recovery_error(params, p2, shrunk, heir))
        jump = float(loss_fn(p2, probe))
        label = "elastic" if strat == "grad_norm" else "copy_prev"
        elastic[label] = {"error_term": err_old,
                          "error_term_shrunk": err_new,
                          "loss_after_reinit": jump}
    rows = [[s, f"{r['error_term']:.4e}", f"{r['error_term_shrunk']:.4e}",
             f"{r['loss_after_reinit']:.4f}"]
            for s, r in elastic.items()]
    print(f"\n== elastic departure: reinit error before the K->K-1 re-cut "
          f"(stage {FAILED_STAGE} -> shrunk stage {heir}/"
          f"{BENCH_STAGES - 1}) ==")
    print(fmt_table(["strategy", "error (K layout)", "error (K-1 layout)",
                     "loss@reinit"], rows))
    results["elastic_relayout"] = elastic

    save_json("sec44_recovery_error.json", results)
    return results


def main() -> None:
    run()


if __name__ == "__main__":
    configure_compile_cache()
    main()
