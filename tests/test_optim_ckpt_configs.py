"""Unit tests: optimizer + LR schedule, checkpoint module, config registry."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import (CheckpointError, Checkpointer,
                                   clean_stale_tmp, latest_step,
                                   load_checkpoint, save_checkpoint)
from repro.config import OptimizerConfig
from repro.configs import ARCHS, arch_ids, get_config, get_stages, reduced
from repro.data.pipeline import ByteCorpus
from repro.optim.adam import (OptState, adam_update, clip_by_global_norm,
                              global_norm, init_adam, lr_schedule)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adam_matches_reference_scalar():
    """One Adam step on a scalar against the closed form."""
    cfg = OptimizerConfig(lr=0.1, warmup_steps=0, schedule="constant",
                          grad_clip=0.0, total_steps=10)
    p = {"w": jnp.asarray(1.0)}
    g = {"w": jnp.asarray(0.5)}
    st = init_adam(p)
    p2, st2, _ = adam_update(cfg, p, g, st)
    b1, b2 = cfg.betas
    m = (1 - b1) * 0.5 / (1 - b1)
    v = (1 - b2) * 0.25 / (1 - b2)
    want = 1.0 - 0.1 * m / (np.sqrt(v) + cfg.eps)
    np.testing.assert_allclose(float(p2["w"]), want, rtol=1e-6)
    assert int(st2.step) == 1


def test_grad_clip():
    g = {"a": jnp.full((4,), 3.0)}   # norm 6
    clipped, gn = clip_by_global_norm(g, 1.5)
    np.testing.assert_allclose(float(gn), 6.0, rtol=1e-6)
    np.testing.assert_allclose(float(global_norm(clipped)), 1.5, rtol=1e-5)


def test_lr_schedule_shapes():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          schedule="cosine", min_lr_ratio=0.1)
    lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in
           [0, 5, 10, 50, 100]]
    assert lrs[0] == 0.0
    np.testing.assert_allclose(lrs[1], 0.5, rtol=1e-6)   # mid-warmup
    np.testing.assert_allclose(lrs[2], 1.0, rtol=1e-6)   # warmup done
    assert lrs[2] > lrs[3] > lrs[4]                      # decaying
    np.testing.assert_allclose(lrs[4], 0.1, rtol=1e-5)   # floor


def test_lr_scale_carries_boost():
    cfg = OptimizerConfig(lr=0.1, warmup_steps=0, schedule="constant",
                          grad_clip=0.0, total_steps=10)
    p = {"w": jnp.asarray(1.0)}
    g = {"w": jnp.asarray(0.5)}
    st = init_adam(p)
    p_a, _, ma = adam_update(cfg, p, g, st, lr_scale=1.0)
    p_b, _, mb = adam_update(cfg, p, g, st, lr_scale=1.1)
    np.testing.assert_allclose(float(mb["lr"]) / float(ma["lr"]), 1.1,
                               rtol=1e-6)
    assert abs(float(p_b["w"]) - 1.0) > abs(float(p_a["w"]) - 1.0)


# ---------------------------------------------------------------------------
# checkpointing (the baseline the paper compares against)
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    save_checkpoint(str(tmp_path), 7, tree)
    assert latest_step(str(tmp_path)) == 7
    step, loaded = load_checkpoint(str(tmp_path), tree)
    assert step == 7
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpointer_rollback_and_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), every=2, keep=2)
    tree = {"w": jnp.zeros((3,))}
    for step in range(1, 9):
        ck.maybe_save(step, jax.tree.map(lambda x: x + step, tree))
    # keep=2 -> only steps 6 and 8 remain
    assert latest_step(str(tmp_path)) == 8
    step, loaded, lost = ck.rollback(11, tree)
    assert step == 8 and lost == 3
    np.testing.assert_allclose(np.asarray(loaded["w"]), 8.0)


def test_checkpointer_no_checkpoint_raises(tmp_path):
    ck = Checkpointer(str(tmp_path), every=5)
    with pytest.raises(RuntimeError):
        ck.rollback(3, {"w": jnp.zeros(())})


def test_checkpoint_bf16_roundtrip(tmp_path):
    """bf16 leaves must come back as bf16 bit-exactly (np.savez alone
    degrades them to |V2 void records)."""
    tree = {"w": jnp.linspace(-3, 3, 16, dtype=jnp.bfloat16),
            "b": jnp.ones((4,), jnp.float32)}
    save_checkpoint(str(tmp_path), 1, tree)
    _, loaded = load_checkpoint(str(tmp_path), tree)
    got = np.asarray(loaded["w"])
    want = np.asarray(tree["w"])
    assert got.dtype == want.dtype
    assert got.tobytes() == want.tobytes()


def test_load_checkpoint_real_exceptions(tmp_path):
    """Missing/corrupted/mismatched checkpoints raise CheckpointError even
    under ``python -O`` (no bare asserts)."""
    tpl = {"w": jnp.zeros((3,), jnp.float32)}
    with pytest.raises(CheckpointError, match="no checkpoints"):
        load_checkpoint(str(tmp_path), tpl)
    save_checkpoint(str(tmp_path), 2, tpl)
    with pytest.raises(CheckpointError, match="step 5"):
        load_checkpoint(str(tmp_path), tpl, step=5)
    # corrupted file
    (tmp_path / "ckpt_00000002.npz").write_bytes(b"not an npz")
    with pytest.raises(CheckpointError):
        load_checkpoint(str(tmp_path), tpl, step=2)
    # shape mismatch against the template
    save_checkpoint(str(tmp_path), 3, {"w": jnp.zeros((4,), jnp.float32)})
    with pytest.raises(CheckpointError):
        load_checkpoint(str(tmp_path), tpl, step=3)


def test_rollback_recovers_from_corrupted_latest(tmp_path):
    """A partially-written/corrupted newest checkpoint must not strand the
    older intact one: rollback falls back instead of dying."""
    ck = Checkpointer(str(tmp_path), every=1, keep=3)
    tpl = {"w": jnp.zeros((3,))}
    ck.maybe_save(1, {"w": jnp.full((3,), 1.0)})
    ck.maybe_save(2, {"w": jnp.full((3,), 2.0)})
    (tmp_path / "ckpt_00000002.npz").write_bytes(b"truncated garbage")
    with pytest.warns(RuntimeWarning, match="skipping"):
        step, tree, lost = ck.rollback(4, tpl)
    assert step == 1 and lost == 3
    np.testing.assert_allclose(np.asarray(tree["w"]), 1.0)


def test_interrupted_save_never_corrupts_latest_step(tmp_path):
    """Leftover tmp files from a crashed save are invisible to latest_step
    and are swept on startup."""
    tpl = {"w": jnp.zeros((2,), jnp.float32)}
    save_checkpoint(str(tmp_path), 4, tpl)
    # simulate saves interrupted mid-write, in both tmp conventions
    (tmp_path / "ckpt_00000009.npz.tmp").write_bytes(b"half a snapshot")
    (tmp_path / "ckpt_00000012.npz.tmp.npz").write_bytes(b"legacy tmp")
    assert latest_step(str(tmp_path)) == 4
    removed = clean_stale_tmp(str(tmp_path))
    assert sorted(removed) == ["ckpt_00000009.npz.tmp",
                               "ckpt_00000012.npz.tmp.npz"]
    assert latest_step(str(tmp_path)) == 4
    step, loaded = load_checkpoint(str(tmp_path), tpl)
    assert step == 4


def test_legacy_checkpoint_format_still_loads(tmp_path):
    """Pre-statestore checkpoints (typed leaf_<i> arrays, no manifest)
    load through the shim — including bf16 leaves the old writer stored
    as raw void records."""
    tpl = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
           "b": jnp.linspace(0, 1, 8, dtype=jnp.bfloat16)}
    leaves = jax.tree.leaves(tpl)
    np.savez(str(tmp_path / "ckpt_00000003.npz"),
             **{f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)})
    step, loaded = load_checkpoint(str(tmp_path), tpl)
    assert step == 3
    for x, y in zip(jax.tree.leaves(tpl), jax.tree.leaves(loaded)):
        assert np.asarray(y).dtype == np.asarray(x).dtype
        assert np.asarray(y).tobytes() == np.asarray(x).tobytes()


# ---------------------------------------------------------------------------
# config registry (assignment f)
# ---------------------------------------------------------------------------

def test_all_assigned_archs_registered():
    assert sorted(arch_ids()) == sorted([
        "granite-moe-3b-a800m", "deepseek-moe-16b", "h2o-danube-3-4b",
        "gemma-2b", "zamba2-2.7b", "qwen3-4b", "internvl2-76b",
        "whisper-large-v3", "mamba2-1.3b", "deepseek-coder-33b"])
    for a in arch_ids():
        cfg = get_config(a)
        cfg.validate()
        assert cfg.source, a                      # citation present
        assert get_stages(a) >= 2
        assert cfg.num_layers % get_stages(a) == 0, a


EXPECTED = {  # assignment table: (layers, d_model, heads, kv, vocab)
    "granite-moe-3b-a800m": (32, 1536, 24, 8, 49155),
    "deepseek-moe-16b": (28, 2048, 16, 16, 102400),
    "h2o-danube-3-4b": (24, 3840, 32, 8, 32000),
    "gemma-2b": (18, 2048, 8, 1, 256000),
    "zamba2-2.7b": (54, 2560, 32, 32, 32000),
    "qwen3-4b": (36, 2560, 32, 8, 151936),
    "internvl2-76b": (80, 8192, 64, 8, 128256),
    "whisper-large-v3": (32, 1280, 20, 20, 51866),
    "mamba2-1.3b": (48, 2048, 0, 0, 50280),
    "deepseek-coder-33b": (62, 7168, 56, 8, 32256),
}


@pytest.mark.parametrize("arch", sorted(EXPECTED))
def test_assigned_config_values(arch):
    cfg = get_config(arch)
    L, d, h, kv, v = EXPECTED[arch]
    assert cfg.num_layers == L and cfg.d_model == d
    assert cfg.vocab_size == v
    if cfg.arch_type != "ssm":
        assert cfg.num_heads == h and cfg.num_kv_heads == kv


def test_param_count_matches_actual():
    """Analytic param_count must match the real init within 2% (it sizes
    the wall-clock model's state transfers)."""
    from repro.models.model import build_model
    for a in ["gemma-2b", "granite-moe-3b-a800m", "mamba2-1.3b"]:
        cfg = reduced(get_config(a))
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        actual = sum(np.prod(s.shape) for s in jax.tree.leaves(shapes))
        est = cfg.param_count()
        assert abs(est - actual) / actual < 0.02, (a, est, actual)


def test_reduced_invariants():
    for a in arch_ids():
        cfg = reduced(get_config(a))
        assert cfg.num_layers <= 2 and cfg.d_model <= 512
        if cfg.arch_type == "moe":
            assert cfg.moe.num_experts <= 4


# ---------------------------------------------------------------------------
# byte corpus
# ---------------------------------------------------------------------------

def test_byte_corpus(tmp_path):
    p = tmp_path / "corpus.txt"
    p.write_text("hello world, this is a tiny corpus for byte-level tests")
    src = ByteCorpus(str(p))
    out = src.sample(np.random.default_rng(0), 3, 16)
    assert out.shape == (3, 17)
    assert out.min() >= 0 and out.max() < 256
