"""How a run decides ``correct``.

The window drives the program's own ``Trainer.run``.  While it warms up,
:class:`~bench.harness.Probe` hands this module the trainer's state at two
kinds of moment:

* after the first fused window (``k0`` steps from the seed): the loss of
  each step, each stage's squared gradient norm at step ``k0`` (the
  program's own ``omegas``), and host copies of the weights and of Adam's
  first moment (the clipped gradients as the optimizer holds them);
* around each warm-up failure: the recovered stage against the plain
  recovery rule applied to the state the failure found, whether anything
  else moved, whether the lost stage's Adam moments were zeroed, and the
  learning-rate boost -- a few floats.

Once the window has closed and the program's state is freed, the plain
reference (``bench/reference/<name>.py``, float32 at full precision)
follows the same ``k0`` steps from the same seed and data, and
:func:`readings` sets the two side by side, per leaf and per stage of the
layer tower.  Each reading has its limit in
``bench/limits/<workload>.json``; ``PERF.md`` gives the readings each limit
was set from.
"""
from __future__ import annotations

import functools
import importlib
import json
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import common
from bench.traffic import token_batches

TOWER = "blocks"
#: a leaf whose reference gradient is under this share of the median
#: leaf's is moved by Adam through round-off alone: its change is not
#: compared (its gradient still is)
ROUNDOFF_SHARE = 1e-3


def reference_module(config: Dict[str, Any]):
    return importlib.import_module(f"bench.reference.{config['reference']}")


def _slices(tree, num_stages: int) -> List[tuple]:
    """``[(name, array)]``: tower leaves cut per stage (``path@stage``),
    every other leaf whole."""
    out = []
    for path, leaf in common.tree_paths(tree):
        if path.split("/")[0] == TOWER:
            per = leaf.shape[0] // num_stages
            out += [(f"{path}@{s}", leaf[s * per:(s + 1) * per])
                    for s in range(num_stages)]
        else:
            out.append((path, leaf))
    return out


def _norm(x):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x))


def training_capture(params, m, omegas, losses) -> Dict[str, Any]:
    """What one side of the comparison keeps after ``k0`` steps: the
    losses, the per-stage squared gradient norms, and host copies of the
    weights and of Adam's first moment (a copy to the host allocates
    nothing on the device, so the probe leaves the program's memory peak
    alone)."""
    return {"loss": [float(x) for x in losses],
            "omega": [float(x) for x in np.asarray(omegas)],
            "params": jax.device_get(params),
            "moment": jax.device_get(m)}


def init_params(config: Dict[str, Any], seed: int):
    ref = reference_module(config)
    return jax.jit(lambda key: ref.init(key, config["model"]))(
        jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# the reference (or its lower-precision control) over the first k0 steps
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _reference_step(config_json: str, precision: str, half_rows: bool):
    config = json.loads(config_json)
    ref = reference_module(config)
    model, train = config["model"], config["train"]
    layers, stages = model["num_layers"], train["num_stages"]
    opt = train["optimizer"]

    def loss_fn(params, batch):
        if half_rows:
            batch = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return common.swap_loss(
            lambda p, b, order: ref.loss(p, b, order, model, precision),
            params, batch, layers, stages)

    @jax.jit
    def step(params, m, v, batch, t, lr):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        per = layers // stages
        omegas = jnp.stack([
            sum(jnp.sum(jnp.square(g[s * per:(s + 1) * per]))
                for g in jax.tree.leaves(grads[TOWER]))
            for s in range(stages)])
        params, m, v = common.adam_step(opt, params, grads, m, v, (t, lr))
        return params, m, v, loss, omegas

    return step


def reference_capture(config: Dict[str, Any], seed: int, k0: int,
                      precision: str = "float32",
                      half_rows: bool = False) -> Dict[str, Any]:
    """Follow the first ``k0`` steps from ``seed`` with the plain
    reference at ``precision`` and return what
    :func:`training_capture` returns for the program.  ``half_rows``
    plants the fault of a step that leaves half of each batch out."""
    model, train = config["model"], config["train"]
    step = _reference_step(json.dumps(config, sort_keys=True), precision,
                           half_rows)
    params0 = init_params(config, seed)
    params = jax.tree.map(jnp.copy, params0)
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
    out = training_capture(params, m, omegas, jax.device_get(losses))
    out["params0"] = jax.device_get(params0)
    return out


# ---------------------------------------------------------------------------
# what the program's recovery did at a failure
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(4, 5))
def _recovery_gaps(pre, post, pre_opt, post_opt, failed, num_stages,
                   omegas):
    per = jax.tree.leaves(pre[TOWER])[0].shape[0] // num_stages

    def stage(tree, s):
        return jax.tree.map(lambda a: a[s * per:(s + 1) * per], tree)

    want = common.recovered_stage(
        [stage(pre[TOWER], s) for s in range(num_stages)], failed, omegas)
    got = stage(post[TOWER], failed)
    rel = jnp.max(jnp.stack([_norm(g - w) / jnp.maximum(_norm(w), 1e-30)
                             for g, w in zip(jax.tree.leaves(got),
                                             jax.tree.leaves(want))]))

    def moved(a, b):
        """Largest change outside the lost stage."""
        return jnp.max(jnp.stack([
            jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))
            for (k, x), (_, y) in zip(_slices(a, num_stages),
                                      _slices(b, num_stages))
            if not k.endswith(f"@{failed}")]))

    elsewhere = jnp.max(jnp.stack([
        moved(pre, post), moved(pre_opt.m, post_opt.m),
        moved(pre_opt.v, post_opt.v),
        jnp.abs(pre_opt.step - post_opt.step).astype(jnp.float32)]))
    lost = jnp.max(jnp.stack([
        jnp.max(jnp.abs(a)) for a in
        jax.tree.leaves(stage(post_opt.m[TOWER], failed))
        + jax.tree.leaves(stage(post_opt.v[TOWER], failed))]))
    return rel, elsewhere, lost


def recovery_capture(pre, post, failed: int, num_stages: int,
                     recovery: Dict[str, Any]) -> Dict[str, float]:
    """``pre``/``post``: the trainer's state before and after the
    strategy handled a failure of stage ``failed``."""
    omegas = jnp.asarray(np.asarray(pre.omegas, np.float32))
    rel, moved, kept = jax.device_get(_recovery_gaps(
        pre.params, post.params, pre.opt_state, post.opt_state, failed,
        num_stages, omegas))
    boost = min(pre.lr_scale * recovery["lr_boost"], recovery["lr_boost_cap"])
    return {"recovery_gap": float(rel), "untouched_moved": float(moved),
            "lost_moments": float(kept),
            "lr_boost_gap": abs(float(post.lr_scale) - boost)}


# ---------------------------------------------------------------------------
# readings and the verdict
# ---------------------------------------------------------------------------

def _host_norms(tree, num_stages: int) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(a, np.float32).ravel()))
            for k, a in _slices(tree, num_stages)}


def _worst_gap(prog: Dict[str, float], ref: Dict[str, float],
               keys=None) -> float:
    """max over keys of | |prog| - |ref| | / max(|ref|, median |ref|)."""
    keys = sorted(ref) if keys is None else keys
    if sorted(prog) != sorted(ref):
        missing = sorted(set(prog) ^ set(ref))
        raise ValueError(f"the two sides hold different leaves: {missing}")
    floor = float(np.median([ref[k] for k in ref]))
    # np.max, unlike max(), carries a NaN through
    return float(np.max([abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
                         for k in keys]))


def leaf_differences(prog: Dict[str, Any], ref: Dict[str, Any]
                     ) -> Dict[str, float]:
    """Per leaf (per stage in the tower): |m_prog - m_ref| / max(|m_ref|,
    median |m_ref|), Adam's first moment -- the clipped gradients as the
    optimizer holds them -- compared element by element.  Norm gaps
    average a lower precision's rounding away; this does not."""
    stages = len(ref["omega"])
    norms = _host_norms(ref["moment"], stages)
    floor = float(np.median(list(norms.values())))
    a = dict(_slices(prog["moment"], stages))
    b = dict(_slices(ref["moment"], stages))
    if sorted(a) != sorted(b):
        raise ValueError("the two sides hold different leaves")
    return {k: float(np.linalg.norm((np.asarray(a[k], np.float32)
                                     - np.asarray(b[k], np.float32)).ravel())
                     / max(norms[k], floor, 1e-30)) for k in b}


def readings(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The training numbers: each a gap relative to the reference, taken
    at its worst leaf.  ``ref`` carries the initial weights (``params0``)
    both sides started from."""
    stages = len(ref["omega"])
    loss = float(np.max([abs(a - b) / abs(b)
                         for a, b in zip(prog["loss"], ref["loss"])]))
    moment_p = _host_norms(prog["moment"], stages)
    moment_r = _host_norms(ref["moment"], stages)
    start = dict(_slices(ref["params0"], stages))

    def change(params):
        return {k: float(np.linalg.norm((np.asarray(a, np.float32)
                                         - np.asarray(start[k], np.float32)
                                         ).ravel()))
                for k, a in _slices(params, stages)}

    floor = float(np.median(list(moment_r.values())))
    counted = [k for k, v in moment_r.items()
               if v >= ROUNDOFF_SHARE * floor]
    diffs = list(leaf_differences(prog, ref).values())
    omega_p = {str(i): float(np.sqrt(x)) for i, x in enumerate(prog["omega"])}
    omega_r = {str(i): float(np.sqrt(x)) for i, x in enumerate(ref["omega"])}
    return {"loss_gap": loss,
            "grad_gap": _worst_gap(moment_p, moment_r),
            "grad_diff": float(np.max(diffs)),
            "change_gap": _worst_gap(change(prog["params"]),
                                     change(ref["params"]), counted),
            "omega_gap": _worst_gap(omega_p, omega_r)}


def verdict(values: Dict[str, float], limits: Dict[str, float]
            ) -> Dict[str, Dict[str, float]]:
    """``{name: {"value", "limit"}}`` for every number with a limit; a
    number is within its limit when it is finite and no larger."""
    missing = sorted(set(limits) - set(values))
    if missing:
        raise ValueError(f"no reading for limits {missing}")
    return {k: {"value": values[k], "limit": limits[k]}
            for k in sorted(limits)}


def is_correct(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
