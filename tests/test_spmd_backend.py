"""Single-device tests for the SPMD backend's host-side machinery: mesh
construction (``launch/mesh.make_mesh``) and the CPU-only host-device
forcing, the GPipe tick permutations, the swap-schedule block hops, the
swap-loss metrics fix, backend selection, and the Adam mesh-global
grad-norm override.  Everything that needs >1 device runs in the
subprocess check (``pipeline_spmd_check.py``)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig, OptimizerConfig, RecoveryConfig, \
    TrainConfig
from repro.core.stages import StagePartition
from repro.core.swap import swap_permutation
from repro.core.trainer import Trainer, _make_loss_fn
from repro.launch.mesh import (force_host_devices, make_host_pipeline_mesh,
                               make_mesh)
from repro.models.model import build_model
from repro.optim.adam import adam_update, global_norm, init_adam
from repro.pipeline.spmd import _swap_block_perm, _tick_perm

CFG = ModelConfig(
    name="spmd-unit-llama", arch_type="dense", num_layers=4, d_model=32,
    num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64, max_seq_len=32,
    dtype="float32", param_dtype="float32")


# ---------------------------------------------------------------------------
# mesh construction (launch/mesh.py)
# ---------------------------------------------------------------------------

def test_make_compat_mesh_builds_on_this_jax():
    """One construction path on the installed JAX: an Auto-typed mesh over
    the visible devices."""
    mesh = make_mesh((1,), ("stage",))
    assert mesh.axis_names == ("stage",)
    assert mesh.devices.shape == (1,)
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)


def test_make_compat_mesh_explicit_devices():
    mesh = make_mesh((1,), ("stage",), devices=jax.devices())
    assert mesh.devices[0] == jax.devices()[0]


def test_make_compat_mesh_rejects_device_shortfall():
    with pytest.raises(ValueError, match="needs 2 devices"):
        make_mesh((2,), ("stage",), devices=jax.devices()[:1])


@pytest.mark.parametrize("platforms,forced", [("cpu", True), ("", False),
                                              ("tpu", False)])
def test_force_host_devices_only_on_cpu(monkeypatch, platforms, forced):
    """Virtual host devices are asked for only under JAX_PLATFORMS=cpu: on
    an accelerator a missing chip must surface as the mesh error."""
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setenv("XLA_FLAGS", "")
    force_host_devices(4)
    assert ("device_count=4" in os.environ["XLA_FLAGS"]) == forced


def test_host_pipeline_mesh_explains_device_shortfall():
    with pytest.raises(RuntimeError, match="one device per stage") as err:
        make_host_pipeline_mesh(max(len(jax.devices()) + 1, 64))
    # the message names the platform it found
    assert f"{jax.devices()[0].platform} device(s)" in str(err.value)


def test_trainer_spmd_backend_surfaces_mesh_error():
    """Trainer(backend='spmd') on a 1-device process must fail with the
    actionable mesh error, not an opaque shard_map one."""
    tcfg = TrainConfig(global_batch=4, microbatch=4, seq_len=32, steps=2,
                       recovery=RecoveryConfig(strategy="checkfree",
                                               num_stages=4))
    with pytest.raises(RuntimeError, match="one device per stage"):
        Trainer(build_model(CFG), tcfg, backend="spmd")


def test_trainer_rejects_unknown_backend():
    tcfg = TrainConfig(global_batch=4, microbatch=4, seq_len=32, steps=2,
                       recovery=RecoveryConfig(strategy="none",
                                               num_stages=4))
    with pytest.raises(ValueError, match="unknown backend"):
        Trainer(build_model(CFG), tcfg, backend="tpu")


# ---------------------------------------------------------------------------
# GPipe tick permutations (the drain/fill bubble masking)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K,M", [(4, 2), (4, 4), (2, 1), (6, 3), (3, 8)])
def test_tick_perm_carries_every_live_hop(K, M):
    """Microbatch m leaves stage s at tick m+s: that hop (and no dead one)
    must be in the tick's permutation."""
    live = {(m + s, (s, s + 1)) for m in range(M) for s in range(K - 1)}
    for t in range(M + K - 2):
        perm = set(_tick_perm(t, K, M))
        want = {hop for (tt, hop) in live if tt == t}
        assert perm == want, (t, perm, want)


def test_tick_perm_bubble_edges():
    # fill: only stage 0 has data at tick 0; drain: only the last hop lives
    assert _tick_perm(0, 4, 2) == [(0, 1)]
    assert _tick_perm(3, 4, 2) == [(2, 3)]   # t=M+K-3: deepest drain tick
    # steady state covers every edge
    assert _tick_perm(3, 4, 4) == [(0, 1), (1, 2), (2, 3)]


# ---------------------------------------------------------------------------
# swap-schedule block hops
# ---------------------------------------------------------------------------

def test_swap_block_perm_matches_stage_permutations():
    assert set(_swap_block_perm(4)) == {(0, 1), (1, 0), (2, 3), (3, 2)}
    assert set(_swap_block_perm(6)) == {(0, 1), (1, 0), (4, 5), (5, 4)}
    assert _swap_block_perm(2) == []      # <4 stages: nothing to swap
    assert _swap_block_perm(3) == []


def test_swap_block_perm_is_a_permutation():
    for k in (4, 5, 6, 8):
        pairs = _swap_block_perm(k)
        srcs = [s for s, _ in pairs]
        dsts = [d for _, d in pairs]
        assert len(set(srcs)) == len(srcs)
        assert len(set(dsts)) == len(dsts)
        assert set(srcs) == set(dsts)     # slices trade places


# ---------------------------------------------------------------------------
# swap-loss metrics (the half-batch telemetry bugfix)
# ---------------------------------------------------------------------------

def test_swap_loss_metrics_average_both_halves():
    model = build_model(CFG)
    part = StagePartition(CFG, 4)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, 64, (8, 16)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 64, (8, 16)), jnp.int32)}
    loss_fn = _make_loss_fn(model, part, use_swap=True)
    loss, metrics = loss_fn(params, batch)

    first = {k: v[:4] for k, v in batch.items()}
    second = {k: v[4:] for k, v in batch.items()}
    perm = jnp.asarray(swap_permutation(part.num_layers, part.num_stages))
    l1, m1 = model.loss(params, first)
    swapped = dict(params)
    swapped["blocks"] = jax.tree.map(lambda a: jnp.take(a, perm, axis=0),
                                     params["blocks"])
    l2, m2 = model.loss(swapped, second)
    np.testing.assert_allclose(float(loss), 0.5 * (float(l1) + float(l2)),
                               rtol=1e-6)
    for key in m1:
        np.testing.assert_allclose(
            float(metrics[key]), 0.5 * (float(m1[key]) + float(m2[key])),
            rtol=1e-6, err_msg=key)
    # the halves genuinely differ, so the old m1-only metrics were wrong
    assert float(m1["ce"]) != pytest.approx(float(m2["ce"]), rel=1e-6)
    assert float(metrics["ce"]) != pytest.approx(float(m1["ce"]), rel=1e-6)


# ---------------------------------------------------------------------------
# Adam: mesh-global grad-norm override
# ---------------------------------------------------------------------------

def test_adam_grad_norm_override_is_equivalent_when_local():
    """Passing the locally computed norm must reproduce the default path
    bit-for-bit — the SPMD backend relies on this to match host clipping."""
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(1))
    grads = jax.tree.map(
        lambda p: jnp.full_like(p, 0.01), params)
    cfg = OptimizerConfig(lr=1e-3, grad_clip=0.5, total_steps=10)
    opt = init_adam(params)
    p1, s1, m1 = adam_update(cfg, params, grads, opt)
    p2, s2, m2 = adam_update(cfg, params, grads, init_adam(params),
                             grad_norm=global_norm(grads))
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(m1["grad_norm"]),
                                  np.asarray(m2["grad_norm"]))
    for a, b in zip(jax.tree.leaves(s1.m), jax.tree.leaves(s2.m)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_adam_grad_norm_override_drives_clipping():
    """A larger injected norm must clip harder — the override is load-
    bearing, not cosmetic."""
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(1))
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.01), params)
    cfg = OptimizerConfig(lr=1e-3, grad_clip=0.5, total_steps=10,
                          warmup_steps=0)
    p_small, _, _ = adam_update(cfg, params, grads, init_adam(params),
                                grad_norm=jnp.asarray(1.0))
    p_big, _, _ = adam_update(cfg, params, grads, init_adam(params),
                              grad_norm=jnp.asarray(100.0))
    d_small = sum(float(jnp.sum(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(p_small), jax.tree.leaves(params)))
    d_big = sum(float(jnp.sum(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(p_big), jax.tree.leaves(params)))
    assert d_big < d_small
