"""Per-architecture smoke tests (assignment requirement f).

For each of the 10 assigned architectures: instantiate the REDUCED variant
(2 layers, d_model <= 256, <= 4 experts), run one forward and one train step
on CPU, assert output shapes and absence of NaNs; plus a prefill+decode
round-trip for decoder-bearing archs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import TrainConfig, OptimizerConfig, RecoveryConfig
from repro.configs import ARCHS, reduced
from repro.data import batch_for
from repro.models import moe as MOE
from repro.models.model import build_model
from repro.optim import init_adam, adam_update
from repro.config import OptimizerConfig

ARCH_IDS = list(ARCHS.keys())


def make_batch(cfg, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, cfg.vocab_size, size=(b, s + 1))
    return {k: jnp.asarray(v) for k, v in batch_for(cfg, raw, rng).items()}


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch(request):
    cfg = reduced(ARCHS[request.param])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_forward_shapes_finite(arch):
    cfg, model, params = arch
    batch = make_batch(cfg)
    logits, aux = jax.jit(model.apply)(params, batch)
    s = batch["tokens"].shape[1]
    extra = cfg.num_patches if cfg.arch_type == "vlm" else 0
    assert logits.shape == (2, s + extra, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), cfg.name
    assert bool(jnp.isfinite(aux)), cfg.name


def test_one_train_step(arch):
    cfg, model, params = arch
    batch = make_batch(cfg)
    ocfg = OptimizerConfig(lr=1e-3, total_steps=10, warmup_steps=0)

    @jax.jit
    def step(params, opt_state, batch):
        (loss, m), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch)
        params, opt_state, om = adam_update(ocfg, params, grads, opt_state)
        return params, opt_state, loss, om["grad_norm"]

    opt_state = init_adam(params)
    p1, o1, loss, gn = step(params, opt_state, batch)
    assert bool(jnp.isfinite(loss)) and bool(jnp.isfinite(gn)), cfg.name
    assert float(gn) > 0
    # params actually changed
    delta = sum(float(jnp.abs(a - b).sum()) for a, b in
                zip(jax.tree.leaves(params), jax.tree.leaves(p1)))
    assert delta > 0


def test_prefill_decode_roundtrip(arch):
    cfg, model, params = arch
    batch = make_batch(cfg, s=12)
    logits_pf, cache = jax.jit(
        lambda p, b: model.prefill(p, b, 24))(params, batch)
    assert bool(jnp.isfinite(logits_pf).all()), cfg.name
    nxt = jnp.array([1, 2], dtype=jnp.int32)
    logits, cache2 = jax.jit(
        lambda p, c, t: model.decode_step(p, c, t))(params, cache, nxt)
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), cfg.name
    assert int(cache2["pos"][0]) == int(cache["pos"][0]) + 1


def test_decode_matches_forward_full_attention(arch):
    """Greedy decode equivalence vs full forward (full-attention archs)."""
    cfg, model, params = arch
    if cfg.sliding_window > 0:
        pytest.skip("SWA alters full-forward semantics")
    if cfg.arch_type == "moe":
        # capacity dropping depends on token grouping (prefill groups vs a
        # single-token decode group) — disable drops for the equivalence check
        import dataclasses
        from repro.models.model import build_model as _bm
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=16.0))
        model = _bm(cfg)
    batch = make_batch(cfg, s=12)
    cap = 16 + (cfg.num_patches if cfg.arch_type == "vlm" else 0)
    _, cache = model.prefill(params, batch, cap)
    nxt = jnp.array([3, 4], dtype=jnp.int32)
    lg_dec, _ = model.decode_step(params, cache, nxt)
    full_batch = dict(batch)
    full_batch["tokens"] = jnp.concatenate(
        [batch["tokens"], nxt[:, None]], axis=1)
    lg_full, _ = model.apply(params, full_batch)
    tol = 0.05 if cfg.dtype == "bfloat16" else 1e-3
    err = float(jnp.abs(lg_dec[:, 0].astype(jnp.float32) -
                        lg_full[:, -1].astype(jnp.float32)).max())
    scale = float(jnp.abs(lg_full[:, -1]).max()) + 1e-6
    assert err / scale < tol, (cfg.name, err, scale)


#: variables that once steered how the layers were traced
_STRAY_ENV = {"REPRO_UNROLL_SCAN": "1", "REPRO_ACT_SHARD": "seq",
              "REPRO_REMAT": "dots", "REPRO_PARAM_SHARD": "fsdp",
              "REPRO_MOE_GROUP": "2"}


def test_loss_ignores_environment(arch, monkeypatch):
    """The traced loss is a function of the configuration alone."""
    cfg, model, params = arch
    batch = make_batch(cfg)
    for name in _STRAY_ENV:
        monkeypatch.delenv(name, raising=False)
    clean = str(jax.make_jaxpr(model.loss)(params, batch))
    for name, value in _STRAY_ENV.items():
        monkeypatch.setenv(name, value)
    assert str(jax.make_jaxpr(model.loss)(params, batch)) == clean, cfg.name


@pytest.mark.parametrize("seq,group", [(512, 512), (1024, 1024),
                                       (8192, 4096), (100, 4), (6, 2)])
def test_moe_group_size(seq, group):
    """The largest power of two up to 4096 that divides the sequence."""
    assert MOE._group_size(4 * seq, seq) == group
