"""A merge recovery is one compiled program: it gives what the eager
composition of ``repro.core.recovery`` gives, touches nothing outside the
lost stages, zeroes their Adam moments, and compiles once per (layout,
stages, reinit)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.runtime import compiled_variant_count
from repro.config import ModelConfig, RecoveryConfig
from repro.core.recovery import (recover_consecutive, recover_stage,
                                 recovery_error)
from repro.core.stages import StagePartition
from repro.core.state import History, TrainState
from repro.models.model import build_model
from repro.optim.adam import OptState
from repro.recovery import FailureContext, make_strategy

CFG = ModelConfig(
    name="unit-llama", arch_type="dense", num_layers=8, d_model=32,
    num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64, max_seq_len=32,
    dtype="float32", param_dtype="float32")
K = 4
KEY = jax.random.PRNGKey(7)


@pytest.fixture(scope="module")
def state():
    params = build_model(CFG).init(jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), 2 * len(leaves))

    def moments(ks):
        return jax.tree_util.tree_unflatten(treedef, [
            jax.random.normal(k, x.shape, jnp.float32)
            for k, x in zip(ks, leaves)])
    opt = OptState(moments(keys[:len(leaves)]), moments(keys[len(leaves):]),
                   jnp.asarray(5, jnp.int32))
    return TrainState(params, opt, lr_scale=1.0)


def _bound(strategy, layout):
    s = make_strategy(RecoveryConfig(strategy=strategy, num_stages=K))
    s.bind(StagePartition(CFG, K))
    if layout is not None:   # an elastic shrink re-cut the tower
        new = StagePartition(CFG, len(layout), layer_counts=layout)
        s.on_layout_change(None, s.part, new)
    return s


def _lost_rows(part, stages):
    return part.stage_bounds(min(stages))[0], part.stage_bounds(max(stages))[1]


@pytest.mark.parametrize("strategy,layout,stages,reinit", [
    ("checkfree_plus", None, (1,), "grad_norm"),
    ("checkfree_plus", None, (0,), "grad_norm"),      # twin copy, first
    ("checkfree_plus", None, (3,), "grad_norm"),      # twin copy, last
    ("uniform", None, (2,), "uniform"),
    ("copy", None, (2,), "copy_prev"),
    ("checkfree", None, (0,), "copy_prev"),           # edge degrades
    ("random", None, (1,), "random"),
    ("checkfree_plus", None, (1, 2), None),           # consecutive run
    ("checkfree", None, (2, 3), None),                # run at the edge
    ("elastic", (3, 3, 2), (1,), "grad_norm"),        # after a shrink
    ("elastic", (3, 3, 2), (2,), "copy_prev"),
], ids=["grad_norm", "twin_first", "twin_last", "uniform", "copy_prev",
        "checkfree_edge", "random", "run", "run_edge", "shrunk_interior",
        "shrunk_edge"])
def test_program_matches_eager_recovery(state, strategy, layout, stages,
                                        reinit):
    s = _bound(strategy, layout)
    part = s.part
    omegas = np.linspace(1.0, 4.0, part.num_stages).astype(np.float32)
    state = TrainState(state.params, state.opt_state, 1.0, omegas)
    hist = History()
    event = FailureContext(stage=stages[0], wall_step=3, key=KEY, hist=hist)
    if len(stages) == 1:
        out = s.on_failure(state, event)
        want = recover_stage(state.params, part, stages[0],
                             jnp.asarray(omegas), strategy=reinit, key=KEY)
    else:
        out = s.on_consecutive(state, list(stages), event)
        want = recover_consecutive(state.params, part, list(stages),
                                   jnp.asarray(omegas))
    assert event.path == "program"
    # the pre-failure state is not donated: it stays readable
    assert not any(x.is_deleted() for x in jax.tree.leaves(
        (state.params, state.opt_state)))

    tk = part.tower_key
    lo, hi = _lost_rows(part, stages)
    # per leaf, relative to its norm: compiled, the merge may contract
    # a*x + b*y into one rounding, which moves elements that cancel
    for got, ref in zip(jax.tree.leaves(out.params[tk]),
                        jax.tree.leaves(want[tk])):
        got, ref = np.asarray(got), np.asarray(ref)
        assert np.linalg.norm(got - ref) <= 1e-6 * np.linalg.norm(ref)
    # outside the lost rows: bit-identical weights and moments
    for new, old in ((out.params, state.params),
                     (out.opt_state.m, state.opt_state.m),
                     (out.opt_state.v, state.opt_state.v)):
        for key in new:
            for a, b in zip(jax.tree.leaves(new[key]),
                            jax.tree.leaves(old[key])):
                a, b = np.asarray(a), np.asarray(b)
                if key == tk:
                    a = np.concatenate([a[:lo], a[hi:]])
                    b = np.concatenate([b[:lo], b[hi:]])
                np.testing.assert_array_equal(a, b)
    assert out.opt_state.step is state.opt_state.step
    for moment in (out.opt_state.m, out.opt_state.v):
        for a in jax.tree.leaves(moment[tk]):
            assert not np.asarray(a)[lo:hi].any()
    want_errs = [float(recovery_error(state.params, want, part, st))
                 for st in stages]
    np.testing.assert_allclose([e for _, e in hist.recovery_errors],
                               want_errs, rtol=1e-6)
    assert out.lr_scale == pytest.approx(s._boosted(1.0))


def test_program_is_built_lazily_and_compiled_once_per_layout(state):
    s = _bound("elastic", None)
    assert s._programs == {}       # bind() builds nothing
    omegas = np.ones((K,), np.float32)
    st = TrainState(state.params, state.opt_state, 1.0, omegas)
    for wall_step in (2, 5, 9):
        st = s.on_failure(st, FailureContext(stage=1, wall_step=wall_step,
                                             key=KEY, hist=History()))
    (prog,) = s._programs.values()
    assert compiled_variant_count(prog) in (-1, 1)
    # a re-layout builds its own program and leaves the old one as it was
    new = StagePartition(CFG, 3)
    s.on_layout_change(st, s.part, new)
    st = TrainState(st.params, st.opt_state, 1.0, np.ones((3,), np.float32))
    s.on_failure(st, FailureContext(stage=1, wall_step=12, key=KEY,
                                    hist=History()))
    assert len(s._programs) == 2
    assert [compiled_variant_count(p) for p in s._programs.values()] in (
        [-1, -1], [1, 1])
    assert (new.layer_counts, (1,), "grad_norm") in s._programs
