"""Shared benchmark harness.

All paper-figure benchmarks train the same *bench model* (a 19M llama-family
model, 12 layers / 4 stages, float32 on CPU) on the deterministic
:class:`SyntheticLM` stream, under the same seeded failure schedules the
trainer replays across strategies — exactly the paper's methodology
("simulating the failures of different stages across iterations, so that the
failure patterns between tests are the same", §5.1).

Wall-clock is the paper-calibrated analytic model (core/walltime.py): CPU
convergence (iterations) x per-iteration cost per strategy (Table 2's
91.3 s / 151.0 s) + per-failure costs.  Runs are cached in
``benchmarks/results/cache`` keyed by their full parameterization and the
device they ran on, so the figure benches can share runs.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Any, Dict, List, Optional

import numpy as np

from repro.config import (ModelConfig, OptimizerConfig, RecoveryConfig,
                          TrainConfig)
from repro.core.failures import FailureSchedule
from repro.core.trainer import Trainer
from repro.core.walltime import WallClockModel
from repro.data.pipeline import SyntheticLM, batch_for, make_batches
from repro.models.model import build_model
from repro.sim import get_scenario, simulate

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
CACHE_DIR = os.path.join(RESULTS_DIR, "cache")

# ---------------------------------------------------------------------------
# the bench model — paper-small-shaped, CPU-sized
# ---------------------------------------------------------------------------

BENCH_MODEL = ModelConfig(
    name="bench-llama-2m",
    arch_type="dense",
    num_layers=12, d_model=128, num_heads=4, num_kv_heads=4,
    d_ff=344, vocab_size=512, act="silu", max_seq_len=64,
    dtype="float32", param_dtype="float32",
    source="paper Table 4 (medium shape: 6 stages), scaled to this "
           "1-core CPU container",
)
BENCH_STAGES = 6          # paper medium: 6 transformer stages (2 layers each)
BENCH_SEQ = 64
BENCH_BATCH = 8
DATA_SEED = 1234

FAST_STEPS = int(os.environ.get("REPRO_BENCH_STEPS", "400"))
EVAL_EVERY = 20
EVAL_BATCHES = 2

# The paper's runs span days (1.9k-38k iterations), so a 10%/h rate yields
# dozens of failure events; our CPU budget is a few hundred iterations.  The
# failure SCHEDULE therefore uses a 300 s/iter clock (so 400 steps ~ 33 h of
# simulated churn -> a paper-like number of events), while the Table-2
# wall-clock COST model keeps the paper's measured 91.3 s/151.0 s iteration
# times.  Rates themselves are untouched (5/10/16 %/h).
SCHEDULE_ITER_TIME_S = 300.0


def env_fingerprint() -> Dict[str, Any]:
    """The environment a result was measured under — stamped into every
    results JSON so numbers from different hosts/backends are never
    compared silently (CPU-interpret vs TPU runs differ by orders of
    magnitude)."""
    import platform

    import jax

    from repro.kernels.ops import interpret_default
    devs = jax.devices()
    return dict(
        jax=jax.__version__,
        numpy=np.__version__,
        python=platform.python_version(),
        backend=jax.default_backend(),
        device_kind=devs[0].device_kind if devs else "none",
        device_count=len(devs),
        pallas_interpret=interpret_default(),
    )


def data_source() -> SyntheticLM:
    return SyntheticLM(BENCH_MODEL.vocab_size, seed=DATA_SEED)


def eval_batches(n: int = EVAL_BATCHES, seed: int = 777) -> List[Dict]:
    src = data_source()
    rng = np.random.default_rng(seed)
    return [batch_for(BENCH_MODEL, src.sample(rng, BENCH_BATCH, BENCH_SEQ))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# cached strategy runs
# ---------------------------------------------------------------------------

def _cache_key(kw: Dict[str, Any]) -> str:
    blob = json.dumps(kw, sort_keys=True).encode()
    return hashlib.sha1(blob).hexdigest()[:16]


def run_key(env: Dict[str, Any], **params: Any) -> Dict[str, Any]:
    """What a cached run is keyed on: its parameters and the device it
    runs on (``env`` is :func:`env_fingerprint`), so a CPU result is never
    served for a chip run of the same parameters, or the reverse."""
    kw = dict(params, model=BENCH_MODEL.name, stages=BENCH_STAGES, v=8,
              platform=env["backend"], device_kind=env["device_kind"],
              device_count=env["device_count"])
    scenario = params.get("scenario")
    if scenario is not None and scenario.startswith("trace:"):
        # key the cache on the trace *contents*: editing the file must miss
        from repro.sim import resolve_trace_path
        with open(resolve_trace_path(scenario[len("trace:"):]), "rb") as f:
            kw["trace_sha"] = hashlib.sha1(f.read()).hexdigest()[:12]
    return kw


def run_strategy(*, strategy: str, rate: Optional[float] = None,
                 scenario: Optional[str] = None,
                 steps: int = FAST_STEPS, seed: int = 0,
                 ckpt_every: int = 50, failure_seed: int = 42,
                 lr: float = 2e-3, use_cache: bool = True,
                 verbose: bool = False) -> Dict[str, Any]:
    """Train the bench model under ``strategy`` with failures at ``rate``/h
    (default 0.10 on the legacy schedule).

    With ``scenario`` the failure environment comes from the cluster
    simulator (``repro.sim``) instead of the legacy Bernoulli schedule:
    pass any registered scenario name or ``trace:<file>``.  The scenario's
    own rate/iteration-time stand unless ``rate`` is passed *explicitly*,
    which overrides them; under ``scenario="bernoulli"`` the simulated run
    is bit-identical to the legacy schedule for the same seed.

    Returns a JSON-able record with the History series + derived metrics.
    """
    if scenario is None and rate is None:
        rate = 0.10  # the legacy schedule's long-standing default
    env = env_fingerprint()
    kw = run_key(env, strategy=strategy, rate=rate, scenario=scenario,
                 steps=steps, seed=seed, ckpt_every=ckpt_every,
                 failure_seed=failure_seed, lr=lr)
    os.makedirs(CACHE_DIR, exist_ok=True)
    path = os.path.join(CACHE_DIR, _cache_key(kw) + ".json")
    if use_cache and os.path.exists(path):
        with open(path) as f:
            return json.load(f)

    wall = WallClockModel(model_bytes=4 * BENCH_MODEL.param_count() * 2)
    from repro.recovery import default_protect_edges, make_strategy
    protect = default_protect_edges(strategy)
    sc = None
    if scenario is not None:
        overrides: Dict[str, Any] = dict(num_stages=BENCH_STAGES,
                                         protect_edges=protect)
        if rate is not None:
            overrides.update(rate_per_hour=rate,
                             iteration_time_s=SCHEDULE_ITER_TIME_S)
        sc = get_scenario(scenario, **overrides)
    eff_rate = sc.rate_per_hour if sc is not None else (rate or 0.0)
    rcfg = RecoveryConfig(
        strategy=strategy, num_stages=BENCH_STAGES,
        checkpoint_every=ckpt_every,
        checkpoint_dir=os.path.join("/tmp/repro_bench_ckpt",
                                    _cache_key(kw)),
        store_dir=os.path.join("/tmp/repro_bench_store", _cache_key(kw)),
        failure_rate_per_hour=eff_rate, seed=failure_seed,
        protect_edge_stages=protect)
    tcfg = TrainConfig(
        global_batch=BENCH_BATCH, microbatch=BENCH_BATCH, seq_len=BENCH_SEQ,
        steps=steps, eval_every=EVAL_EVERY, seed=seed,
        optimizer=OptimizerConfig(lr=lr, total_steps=steps, warmup_steps=20),
        recovery=rcfg)
    # failure schedule over wall iterations (same seed across strategies)
    schedule = None
    if sc is not None:
        schedule = simulate(sc, steps=steps * 10, seed=failure_seed,
                            wall=wall)
    elif rate:
        schedule = FailureSchedule(
            rate_per_hour=rate, iteration_time_s=SCHEDULE_ITER_TIME_S,
            num_stages=BENCH_STAGES, steps=steps * 10, seed=failure_seed,
            protect_edges=rcfg.protect_edge_stages)
    model = build_model(BENCH_MODEL)
    trainer = Trainer(model, tcfg, wall=wall, schedule=schedule)
    batches = make_batches(BENCH_MODEL, batch=BENCH_BATCH, seq=BENCH_SEQ,
                           seed=seed, source=data_source())
    state, hist = trainer.run(batches, eval_batches(), verbose=verbose)
    # persist final params so eval benches can reuse cached runs
    import jax
    leaves = jax.tree_util.tree_flatten(state.params)[0]
    np.savez(path.replace(".json", "_params.npz"),
             **{f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)})

    rec = dict(
        params_path=path.replace(".json", "_params.npz"),
        config=kw,
        env=env,
        entropy_floor=data_source().entropy_floor,
        steps=hist.steps, wall_time=hist.wall_time, loss=hist.loss,
        eval_loss=hist.eval_loss, failures=hist.failures,
        recovery_errors=hist.recovery_errors, wall_iters=hist.wall_iters,
        truncated=hist.truncated,
        # seed-independent per-iteration cost: a fresh strategy (adaptive
        # starts in its calm/low mode, so this never depends on where a
        # particular run's sliding window happened to end)
        iter_time_s=make_strategy(rcfg, wall=wall).iteration_cost(),
        # effective rate actually paid, failures included
        avg_iter_time_s=(hist.wall_time[-1] / max(hist.wall_iters, 1)
                         if hist.wall_time else float("nan")),
        n_failures=len(hist.failures),
        final_loss=hist.loss[-1] if hist.loss else float("nan"),
        final_eval=hist.eval_loss[-1][2] if hist.eval_loss else float("nan"),
    )
    with open(path, "w") as f:
        json.dump(rec, f)
    return rec


def load_params(rec: Dict[str, Any]):
    """Rebuild the final parameter pytree saved by :func:`run_strategy`."""
    import jax
    model = build_model(BENCH_MODEL)
    template = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(template)
    data = np.load(rec["params_path"])
    return jax.tree_util.tree_unflatten(
        treedef, [data[f"leaf_{i}"] for i in range(len(leaves))])


# ---------------------------------------------------------------------------
# derived metrics
# ---------------------------------------------------------------------------

def wall_to_target(rec: Dict[str, Any], target: float) -> float:
    """Wall-clock hours until eval loss first drops below ``target``."""
    for step, wall, el in rec["eval_loss"]:
        if el <= target:
            return wall / 3600.0
    return float("inf")


def iters_to_target(rec: Dict[str, Any], target: float) -> float:
    for step, wall, el in rec["eval_loss"]:
        if el <= target:
            return step
    return float("inf")


def smooth(xs: List[float], k: int = 9) -> np.ndarray:
    a = np.asarray(xs, np.float64)
    if len(a) < k:
        return a
    ker = np.ones(k) / k
    return np.convolve(a, ker, mode="valid")


def save_json(name: str, obj: Any) -> str:
    if isinstance(obj, dict) and "env" not in obj:
        obj = dict(obj, env=env_fingerprint())
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
    return path


def fmt_table(headers: List[str], rows: List[List[Any]]) -> str:
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows)) if rows
              else len(str(h)) for i, h in enumerate(headers)]
    out = ["  ".join(str(h).ljust(w) for h, w in zip(headers, widths))]
    out.append("  ".join("-" * w for w in widths))
    for r in rows:
        out.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    return "\n".join(out)
