"""Table 3 — held-out evaluation of a CheckFree-trained model vs a
failure-free-trained model (the paper's "redundant computation" arm is
convergence-equivalent to failure-free training, §5.3).

The paper evaluates perplexity on four datasets; our analog is four held-out
*domains* of the synthetic grammar: the training distribution (fresh
samples), a longer-period variant, a flatter successor distribution, and a
peakier one.  The learned transition table transfers across all four, with
different achievable floors — mirroring in-domain vs shifted-corpus eval.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from benchmarks.common import (BENCH_BATCH, BENCH_MODEL, BENCH_SEQ,
                               FAST_STEPS, data_source, fmt_table,
                               load_params, run_strategy, save_json)
from repro.launch.compile_cache import configure_compile_cache
from repro.data.pipeline import SyntheticLM, batch_for
from repro.models.model import build_model


def domain_variants():
    base = data_source()
    flat = SyntheticLM(BENCH_MODEL.vocab_size, seed=1234)
    flat.probs = np.ones_like(flat.probs) / len(flat.probs)
    peaky = SyntheticLM(BENCH_MODEL.vocab_size, seed=1234)
    p = np.arange(1, len(peaky.probs) + 1, dtype=np.float64)[::-1] ** 4.0
    peaky.probs = p / p.sum()
    longp = SyntheticLM(BENCH_MODEL.vocab_size, seed=1234, period=256)
    return {"in-domain": base, "long-period": longp,
            "flat-successors": flat, "peaky-successors": peaky}


def eval_model(params, domains, n_batches: int = 4, seed: int = 999):
    model = build_model(BENCH_MODEL)
    import jax
    from repro.models.layers import cross_entropy

    @jax.jit
    def loss_of(params, batch):
        logits, _ = model.apply(params, batch)
        return cross_entropy(logits, batch["labels"])

    out = {}
    for name, src in domains.items():
        rng = np.random.default_rng(seed)
        losses = []
        for _ in range(n_batches):
            b = batch_for(BENCH_MODEL, src.sample(rng, BENCH_BATCH,
                                                  BENCH_SEQ))
            losses.append(float(loss_of(params,
                                        {k: jnp.asarray(v)
                                         for k, v in b.items()})))
        nll = float(np.mean(losses))
        out[name] = {"nll": nll, "ppl": math.exp(nll)}
    return out


def run(steps: int = FAST_STEPS, verbose: bool = False):
    # failure-free training == redundant computation's convergence (§5.3)
    rec_ff = run_strategy(strategy="none", rate=0.0, steps=steps,
                          verbose=verbose)
    rec_cf = run_strategy(strategy="checkfree", rate=0.16, steps=steps,
                          verbose=verbose)
    domains = domain_variants()
    ev = {"failure-free (= redundant)": eval_model(load_params(rec_ff),
                                                   domains),
          "checkfree @16%/h": eval_model(load_params(rec_cf), domains)}
    rows = []
    for dom in domains:
        rows.append([dom] + [f"{ev[m][dom]['ppl']:.3f}" for m in ev])
    print(f"\n== Table 3 — held-out perplexity ({steps} steps) ==")
    print(fmt_table(["domain"] + list(ev.keys()), rows))
    save_json("table3_eval.json", ev)
    return ev


def main() -> None:
    run()


if __name__ == "__main__":
    configure_compile_cache()
    main()
