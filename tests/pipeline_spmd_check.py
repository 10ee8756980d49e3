"""Standalone SPMD pipeline verification — run in a subprocess with
4 host CPU devices (this script sets JAX_PLATFORMS and XLA_FLAGS before
importing jax).  Asserts:

1. pipeline_loss == reference model.loss (same params/batch),
2. grads through the pipeline == reference grads,
3. checkfree_recover_spmd == the single-host recover_stage math for
   middle-stage merges (bit-level), edge stages (CheckFree+ twin copy),
   and the copy_prev degradation — including the full-params wrapper
   that leaves the replicated (de)embeddings untouched,
4. one fused train step (CheckFree+ swap schedule on) matches the host
   backend's fused step: updated params, loss/ce/aux/grad_norm/lr rings,
   and in-mesh psum omegas,
5. a short Trainer training run on ``backend="spmd"`` reproduces the
   host-loop backend's loss curve within tolerance for checkfree AND
   checkfree_plus, with a mid-run middle-stage and an edge-stage failure
   recovered in-mesh, compiling one executable per window size.
"""
import os

# four virtual CPU devices, always: the four-chip path is
# `chip_smoke.py --four-chips`, never this script
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis.runtime import compiled_variant_count  # noqa: E402
from repro.config import (ModelConfig, OptimizerConfig,  # noqa: E402
                          RecoveryConfig, TrainConfig)
from repro.configs import reduced  # noqa: E402
from repro.configs.paper_llama import SMALL  # noqa: E402
from repro.core.recovery import recover_stage  # noqa: E402
from repro.core.stages import StagePartition  # noqa: E402
from repro.core.trainer import (Trainer,  # noqa: E402
                                make_fused_train_step)
from repro.data.pipeline import make_batches  # noqa: E402
from repro.launch.mesh import make_host_pipeline_mesh  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.pipeline.spmd import (checkfree_recover_spmd,  # noqa: E402
                                 make_in_mesh_recover,
                                 make_spmd_fused_train_step, pipeline_loss)

K = 4
cfg = ModelConfig(
    name="pp-llama", arch_type="dense", num_layers=8, d_model=32,
    num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64, max_seq_len=32,
    dtype="float32", param_dtype="float32")

assert len(jax.devices()) == 4, jax.devices()
# version-compat mesh construction lives in launch/mesh.py (the shim that
# used to be hand-rolled here)
mesh = make_host_pipeline_mesh(K)

model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)
labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)

# --- 1) forward equivalence ------------------------------------------------
loss_fn = pipeline_loss(cfg, mesh, num_stages=K, num_microbatches=2)
got = float(loss_fn(params, tokens, labels))
want = float(model.loss(params, {"tokens": tokens, "labels": labels})[0])
print(f"pipeline loss {got:.6f}  reference {want:.6f}")
np.testing.assert_allclose(got, want, rtol=2e-5)

# --- 2) gradient equivalence (backward flows through reversed ppermutes) ---
g_pp = jax.grad(lambda p: loss_fn(p, tokens, labels))(params)
g_ref = jax.grad(
    lambda p: model.loss(p, {"tokens": tokens, "labels": labels})[0])(params)
for (ka, a), (kb, b) in zip(
        sorted(jax.tree_util.tree_leaves_with_path(g_pp),
               key=lambda kv: str(kv[0])),
        sorted(jax.tree_util.tree_leaves_with_path(g_ref),
               key=lambda kv: str(kv[0]))):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-5, rtol=1e-4,
                               err_msg=str(ka))
print("pipeline grads match reference")

# --- 3) collective recovery vs the single-host math -------------------------
part = StagePartition(cfg, K)
omegas = jnp.array([1.0, 3.0, 0.5, 2.0])
recover = checkfree_recover_spmd(mesh, K)

# middle-stage Alg. 1 merge (bit-level vs the host merge)
got_tower = recover(params["blocks"], omegas, 2)
want_params = recover_stage(params, part, 2, omegas, strategy="grad_norm")
for a, b in zip(jax.tree.leaves(got_tower),
                jax.tree.leaves(want_params["blocks"])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
print("spmd recovery matches single-host Alg. 1 merge")

# edge stages: the CheckFree+ twin-copy collective (S_0 <- S_1,
# S_{K-1} <- S_{K-2}) — exact copies, so bit-equal to the host path;
# this used to be an `assert 0 < failed < K-1` hole
in_mesh = make_in_mesh_recover(mesh, part)
for failed in (0, K - 1):
    got_params = in_mesh(params, omegas, failed, "grad_norm")
    want_params = recover_stage(params, part, failed, omegas,
                                strategy="grad_norm")
    for a, b in zip(jax.tree.leaves(got_params),
                    jax.tree.leaves(want_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the replicated (de)embeddings are untouched — replication IS the
    # edge restore for the stage-0/stage-K device's non-tower state
    for key in ("embed", "final_norm"):
        assert got_params[key] is params[key], key
print("spmd edge recovery (twin copy + replicated (de)embeddings) matches")

# copy_prev degradation (plain CheckFree hit by an unprotected edge event)
for failed in (0, 1, K - 1):
    got_params = in_mesh(params, omegas, failed, "copy_prev")
    want_params = recover_stage(params, part, failed, omegas,
                                strategy="copy_prev")
    for a, b in zip(jax.tree.leaves(got_params),
                    jax.tree.leaves(want_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("spmd copy_prev recovery matches")

# --- 4) one fused train step, swap schedule on ------------------------------
ocfg = OptimizerConfig(lr=1e-3, total_steps=10, warmup_steps=2)
from repro.optim.adam import init_adam  # noqa: E402

host_step = make_fused_train_step(model, ocfg, part, use_swap=True)
spmd_step = make_spmd_fused_train_step(model, ocfg, part, mesh, 2,
                                       use_swap=True)
# a loss_mask whose density varies per microbatch: the SPMD backend must
# reproduce the host's GLOBAL masked mean (valid-token weighting), not a
# mean of per-microbatch means
mask = (rng.random((8, 16)) < np.linspace(0.9, 0.3, 8)[:, None]
        ).astype(np.float32)
assert mask.sum() > 0 and mask.reshape(4, 2, 16).sum((1, 2)).std() > 0
stacked = {"tokens": tokens[None], "labels": labels[None],
           "loss_mask": jnp.asarray(mask)[None]}


def once(step):
    p = model.init(jax.random.PRNGKey(0))
    return step(p, init_adam(p), {k: jnp.asarray(v)
                                  for k, v in stacked.items()}, 1.0)


hp, ho, hls, hring = once(host_step)
sp, so, sls, sring = once(spmd_step)
for key in ("loss", "ce", "aux", "grad_norm", "lr"):
    np.testing.assert_allclose(np.asarray(hring[key]),
                               np.asarray(sring[key]), rtol=2e-4,
                               atol=1e-6, err_msg=key)
np.testing.assert_allclose(np.asarray(hring["omegas"]),
                           np.asarray(sring["omegas"]), rtol=2e-3)
# Adam's first moment is linear in the (clipped) gradients: hold it to a
# tight bound, relative to each leaf's largest entry.
def leaves_by_path(tree):
    return sorted(jax.tree_util.tree_leaves_with_path(tree),
                  key=lambda kv: str(kv[0]))


for (ka, a), (_, b) in zip(leaves_by_path(ho.m), leaves_by_path(so.m)):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, atol=1e-5 * np.abs(a).max(),
                               err_msg=f"adam m {ka}")
# params after one Adam step: the two backends sum the same gradients in a
# different order (one reduction over the whole tower vs per-stage partials
# plus a psum), and Adam's first step divides each element by its own
# magnitude (m / (sqrt(v) + eps)), so an element whose gradient is near
# zero turns a last-bit gradient difference into a visible share of one
# update.  Bound the difference by 10% of one update (lr); the moment check
# above is what holds the gradients themselves to float32 agreement.
lr_now = float(hring["lr"][0])
for (ka, a), (_, b) in zip(leaves_by_path(hp), leaves_by_path(sp)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=0.1 * lr_now, err_msg=str(ka))
print("swap-schedule fused step matches host backend "
      f"(loss {float(hring['loss'][0]):.6f})")

# --- 5) short training-run parity under failures ---------------------------
train_cfg = reduced(SMALL).replace(num_layers=8, max_seq_len=64)


class ForcedSchedule:
    def __init__(self, events):
        self._events = dict(events)

    def at(self, step):
        return self._events.get(step, [])


def train(backend, strategy, events):
    rcfg = RecoveryConfig(strategy=strategy, num_stages=K)
    tcfg = TrainConfig(global_batch=8, microbatch=4, seq_len=32, steps=6,
                       eval_every=100, fuse_window=4,
                       optimizer=OptimizerConfig(lr=1e-3, total_steps=6,
                                                 warmup_steps=2),
                       recovery=rcfg)
    trainer = Trainer(build_model(train_cfg), tcfg,
                      schedule=ForcedSchedule(events), backend=backend)
    if backend == "spmd" and strategy != "none":
        assert trainer.strategy._in_mesh_recover is not None
    out = trainer.run(make_batches(train_cfg, batch=8, seq=32, seed=0))
    # one executable per window size: the initial state is placed on the
    # mesh like every later one (no second compile for one-device inputs)
    assert compiled_variant_count(trainer.fused_step) == \
        len(trainer.dispatched_buckets), backend
    return out


# checkfree: mid-run middle-stage failure; checkfree_plus additionally
# loses an edge stage (S_0) — the new collective path
for strategy, events in (("checkfree", {3: [2]}),
                         ("checkfree_plus", {2: [0], 4: [2]})):
    (hs, hh) = train("host", strategy, events)
    (ss, sh) = train("spmd", strategy, events)
    assert hh.failures == sh.failures, (hh.failures, sh.failures)
    np.testing.assert_allclose(hh.loss, sh.loss, rtol=5e-3, atol=5e-4,
                               err_msg=f"{strategy} loss curve diverged")
    np.testing.assert_allclose(
        [e for _, e in hh.recovery_errors],
        [e for _, e in sh.recovery_errors], rtol=5e-3,
        err_msg=f"{strategy} recovery errors diverged")
    assert hs.effective_step == ss.effective_step == 6
    print(f"training parity [{strategy}]: host "
          f"{[round(x, 4) for x in hh.loss]} == spmd "
          f"{[round(x, 4) for x in sh.loss]} (rtol 5e-3)")

print("OK")
