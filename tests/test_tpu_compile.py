"""Compile the kernels and the training step for a TPU v5e chip that is
described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes that
are not legal tiles, value slices with a dynamic start, in-kernel scans,
programs that do not fit the chip's memory.  These tests lower each kernel
with ``interpret=False`` at the widths of the models that would use it,
and the host backend's fused train step at full ``paper-llama-124m``
width, so a later change that the chip would refuse fails here on the CPU.

The topology is described inside a module-scoped fixture (never at import
time): only one process may load the TPU library, and a test worker that
is never handed this file must not touch it.  Where it cannot be
described, every test here skips.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import OptimizerConfig
from repro.configs.paper_llama import SMALL, SMALL_STAGES
from repro.core.stages import StagePartition
from repro.core.trainer import make_fused_train_step
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.stage_merge import stage_merge
from repro.models.model import build_model
from repro.optim.adam import init_adam

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the persistent compilation cache cannot read back an entry compiled
    # for a described chip; keep these compiles out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel is there
    return compiled


# (batch, heads, seq, head_dim): paper-llama-124m (d_model 512 / 8 heads,
# seq 512, batch 8) and paper-llama-1.5b (d_model 2048 / 16 heads, seq 4096)
FLASH_SHAPES = {"124m": (8, 8, 512, 64), "1.5b": (1, 16, 4096, 128)}


@pytest.mark.parametrize("model", sorted(FLASH_SHAPES))
def test_flash_attention_forward_compiles(one_chip, model):
    qkv = [_spec(one_chip, FLASH_SHAPES[model], jnp.bfloat16)] * 3
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             interpret=False), *qkv)


@pytest.mark.parametrize("model", sorted(FLASH_SHAPES))
def test_flash_attention_grad_compiles(one_chip, model):
    qkv = [_spec(one_chip, FLASH_SHAPES[model], jnp.bfloat16)] * 3

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), *qkv)


def test_stage_merge_compiles_for_largest_124m_leaf(one_chip):
    """One 124m stage holds 3 layers; its largest tower leaves are the MLP
    weights, 3 x 512 x 1376 float32."""
    part = StagePartition(SMALL, SMALL_STAGES)
    stage = jax.eval_shape(
        lambda key: part.get_stage(build_model(SMALL).init(key), 1),
        jax.random.PRNGKey(0))
    largest = max(jax.tree.leaves(stage), key=lambda s: s.size)
    assert largest.size == 3 * 512 * 1376
    xy = [_spec(one_chip, largest.shape, largest.dtype)] * 2
    _compile(lambda x, y: stage_merge(x, y, 0.25, 0.75, interpret=False),
             *xy)


def test_ssd_scan_compiles_at_mamba2_1p3b_width(one_chip):
    """mamba2-1.3b: 64 heads of P=64, state N=128, one group, chunk 64."""
    b, h, t, p, g, n = 1, 64, 512, 64, 1, 128
    args = [_spec(one_chip, (b, h, t, p), jnp.float32),
            _spec(one_chip, (b, h, t), jnp.float32),
            _spec(one_chip, (b, g, t, n), jnp.float32),
            _spec(one_chip, (b, g, t, n), jnp.float32)]
    _compile(lambda x, a, bm, cm: ssd_scan(x, a, bm, cm, chunk=64,
                                           interpret=False), *args)


@pytest.mark.parametrize("use_swap", [False, True],
                         ids=["checkfree", "checkfree_plus"])
def test_fused_train_step_compiles_at_124m_width(one_chip, use_swap):
    """The host backend's fused step at window 1, batch 8 x seq 512, fits
    one v5e chip (parameters, Adam moments, activations and the donated
    outputs)."""
    model = build_model(SMALL)
    part = StagePartition(SMALL, SMALL_STAGES)
    step = make_fused_train_step(model, OptimizerConfig(lr=3e-4), part,
                                 use_swap=use_swap)

    def placed(tree):
        return jax.tree.map(
            lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(init_adam, params)
    batch = {k: _spec(one_chip, (1, 8, 512), jnp.int32)
             for k in ("tokens", "labels")}
    compiled = step._jitted.lower(
        placed(params), placed(opt), batch,
        _spec(one_chip, (), jnp.float32),
        _spec(one_chip, (), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total
