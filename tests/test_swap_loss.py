"""CheckFree+'s swap loss (``core/trainer._make_loss_fn`` with
``use_swap=True``) against the formulation it replaced, written out here:
each half-batch through ``model.loss`` on the float32 parameters, the
second on a float32 ``jnp.take`` of the tower in the swapped stage order.

The loss, its metrics and every gradient leaf must be bit-identical, and
the compiled program must hold no scatter shaped like a tower leaf (the
transpose of the float32 gather) and cast each float32 leaf to the compute
dtype once, where the replaced formulation cast the whole tree once a
half."""
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig, MoEConfig, SSMConfig
from repro.core.stages import StagePartition
from repro.core.swap import swap_permutation
from repro.core.trainer import _make_loss_fn, _reorder, _runs
from repro.models.model import build_model

VOCAB = 96


def _moe(dtype):
    return ModelConfig(
        name=f"swap-moe-{dtype}", arch_type="moe", num_layers=4, d_model=32,
        num_heads=4, num_kv_heads=2, head_dim=8, d_ff=0, vocab_size=VOCAB,
        max_seq_len=32, tie_embeddings=True, dtype=dtype,
        param_dtype="float32",
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=24))


SSM = ModelConfig(
    name="swap-mamba", arch_type="ssm", num_layers=8, d_model=32,
    num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=VOCAB, max_seq_len=32,
    ssm=SSMConfig(state_dim=8, head_dim=8, expand=2, conv_width=2,
                  chunk_size=8, ngroups=1),
    dtype="bfloat16", param_dtype="float32")

DENSE = ModelConfig(
    name="swap-dense", arch_type="dense", num_layers=6, d_model=32,
    num_heads=2, num_kv_heads=2, d_ff=48, vocab_size=VOCAB, max_seq_len=32,
    dtype="bfloat16", param_dtype="float32")

#: (config, per-stage layer counts; None is the equal split), 4 stages each
CASES = {
    "moe-bf16": (_moe("bfloat16"), None),
    "ssm-8-layers": (SSM, None),
    "dense-elastic": (DENSE, (2, 1, 2, 1)),
    "moe-f32": (_moe("float32"), None),
}


def _parent_loss_fn(model, part):
    """The swap loss as it was: both halves cast the float32 tree, the
    second after a float32 gather of the tower."""
    perm = jnp.asarray(swap_permutation(
        part.num_layers, part.num_stages,
        bounds=[part.stage_bounds(i) for i in range(part.num_stages)]))

    def loss_fn(params, batch):
        half = batch["tokens"].shape[0] // 2
        first = {k: v[:half] for k, v in batch.items()}
        second = {k: v[half:] for k, v in batch.items()}
        swapped = dict(params)
        swapped[part.tower_key] = jax.tree.map(
            lambda a: jnp.take(a, perm, axis=0), params[part.tower_key])
        l1, m1 = model.loss(params, first)
        l2, m2 = model.loss(swapped, second)
        return 0.5 * (l1 + l2), {k: 0.5 * (m1[k] + m2[k]) for k in m1}

    return loss_fn


def _lowered(loss_fn, params, batch):
    return jax.jit(jax.value_and_grad(loss_fn, has_aux=True)).lower(
        params, batch)


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    cfg, counts = CASES[request.param]
    model = build_model(cfg)
    part = StagePartition(cfg, 4, layer_counts=counts)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)
    batch = {k: jnp.asarray(rng.integers(0, VOCAB, (4, 16)), jnp.int32)
             for k in ("tokens", "labels")}
    new = _lowered(_make_loss_fn(model, part, use_swap=True), params, batch)
    old = _lowered(_parent_loss_fn(model, part), params, batch)
    return SimpleNamespace(cfg=cfg, part=part, params=params, batch=batch,
                           new=new, new_exe=new.compile(),
                           old=old, old_exe=old.compile())


def test_swap_loss_is_bit_identical_to_the_parent_formulation(case):
    (loss, metrics), grads = case.new_exe(case.params, case.batch)
    (loss0, metrics0), grads0 = case.old_exe(case.params, case.batch)
    np.testing.assert_array_equal(np.asarray(loss), np.asarray(loss0))
    assert metrics.keys() == metrics0.keys()
    for k in metrics:
        np.testing.assert_array_equal(np.asarray(metrics[k]),
                                      np.asarray(metrics0[k]), err_msg=k)
    flat, tree = jax.tree_util.tree_flatten_with_path(grads)
    assert tree == jax.tree.structure(grads0)
    for (path, g), g0 in zip(flat, jax.tree.leaves(grads0)):
        assert g.dtype == g0.dtype == jnp.float32, path
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g0),
                                      err_msg=jax.tree_util.keystr(path))


def _tower_shapes(case):
    return {tuple(a.shape)
            for a in jax.tree.leaves(case.params[case.part.tower_key])}


def _scatter_shapes(hlo_text):
    """Result shapes of every scatter in a compiled HLO module."""
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"= \w+\[([\d,]*)\]\S* scatter\(",
                                   hlo_text)]


def test_swap_step_has_no_tower_shaped_scatter(case):
    tower = _tower_shapes(case)
    new = [s for s in _scatter_shapes(case.new_exe.as_text()) if s in tower]
    assert new == []
    # the replaced formulation's gather transposes to such scatters: the
    # match is not vacuous
    old = [s for s in _scatter_shapes(case.old_exe.as_text()) if s in tower]
    assert old


def _f32_to_bf16_casts(lowered_text):
    """Shapes of every float32 -> bfloat16 convert in a lowered module."""
    return [tuple(int(d) for d in dims.split("x"))
            for dims in re.findall(
                r"stablehlo\.convert \S+ : \(tensor<([\dx]+)xf32>\) -> "
                r"tensor<[\dx]+xbf16>", lowered_text)]


def _casts_per_leaf(case, lowered):
    """Per shape of a float32 parameter leaf of rank 2 or more, the
    float32 -> bfloat16 converts of that shape over the leaves that have
    it.  (A rank-1 scale shares its shape with per-layer values inside
    the layer scan; every leaf of the tower has rank 2 or more.)"""
    shapes = [tuple(a.shape) for a in jax.tree.leaves(case.params)
              if a.dtype == jnp.float32 and a.ndim >= 2]
    casts = _f32_to_bf16_casts(lowered.as_text())
    return {s: casts.count(s) / shapes.count(s) for s in set(shapes)}


def test_swap_views_cast_each_float32_leaf_once(case):
    per_leaf = _casts_per_leaf(case, case.new)
    once = 1 if case.cfg.dtype == "bfloat16" else 0
    assert per_leaf and set(per_leaf.values()) == {once}, per_leaf
    if once:
        # the replaced formulation cast every leaf once a half
        assert set(_casts_per_leaf(case, case.old).values()) == {2}


@pytest.mark.parametrize("counts", [None, (2, 1, 2, 1), (1, 3, 1, 1),
                                    (1, 1, 1, 1, 1, 1), (2, 2, 2)])
def test_reorder_realizes_the_swap_and_transposes_to_its_inverse(counts):
    """The static-slice reorder equals the gather by ``swap_permutation``
    (fewer than 4 stages: the identity), and its backward pass is the
    gather by the inverse permutation."""
    counts = counts or (2, 2, 2, 2)
    bounds = list(zip(np.cumsum((0,) + counts[:-1]), np.cumsum(counts)))
    perm = swap_permutation(sum(counts), len(counts), bounds=bounds)
    runs, inverse = _runs(perm), _runs(np.argsort(perm))
    a = jnp.arange(sum(counts) * 3.0).reshape(-1, 3)
    out, vjp = jax.vjp(lambda x: _reorder(x, runs, inverse), a)
    np.testing.assert_array_equal(out, a[perm])
    g = jnp.arange(sum(counts) * 3.0).reshape(-1, 3) * 7.0
    np.testing.assert_array_equal(vjp(g)[0], g[np.argsort(perm)])
