"""End-to-end system behaviour tests: trainer x recovery strategies,
checkpoint rollback, failure bookkeeping, wall-clock model and data
pipeline."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (ModelConfig, OptimizerConfig, RecoveryConfig,
                          TrainConfig)
from repro.core.failures import FailureSchedule
from repro.core.trainer import Trainer
from repro.core.walltime import WallClockModel
from repro.data.pipeline import SyntheticLM, batch_for, make_batches
from repro.models.model import build_model

CFG = ModelConfig(
    name="sys-llama", arch_type="dense", num_layers=4, d_model=32,
    num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=128, max_seq_len=32,
    dtype="float32", param_dtype="float32")
STAGES = 4


class ForcedSchedule:
    """Deterministic failure injection for tests."""

    def __init__(self, events):
        self._events = dict(events)

    def at(self, step):
        return self._events.get(step, [])


def make_trainer(strategy, steps=8, events=None, tmpdir="/tmp/repro_test"):
    rcfg = RecoveryConfig(strategy=strategy, num_stages=STAGES,
                          checkpoint_every=3,
                          checkpoint_dir=os.path.join(tmpdir, strategy))
    tcfg = TrainConfig(global_batch=4, microbatch=4, seq_len=32, steps=steps,
                      eval_every=100,
                      optimizer=OptimizerConfig(lr=1e-3, total_steps=steps,
                                                warmup_steps=2),
                      recovery=rcfg)
    model = build_model(CFG)
    sched = ForcedSchedule(events) if events else None
    return Trainer(model, tcfg, schedule=sched)


def batches():
    return make_batches(CFG, batch=4, seq=32, seed=0)


@pytest.mark.parametrize("strategy", ["checkfree", "checkfree_plus",
                                      "checkpoint", "redundant", "none"])
def test_trainer_completes_under_failures(strategy, tmp_path):
    events = {2: [1], 5: [2]}
    tr = make_trainer(strategy, steps=8, events=events,
                      tmpdir=str(tmp_path))
    state, hist = tr.run(batches())
    assert state.effective_step == 8
    assert len(hist.failures) == 2
    assert all(np.isfinite(hist.loss)), strategy
    if strategy in ("checkfree", "checkfree_plus"):
        assert len(hist.recovery_errors) == 2
        assert all(e > 0 for _, e in hist.recovery_errors)
        assert state.lr_scale > 1.0  # Alg. 1 line 4 boost still decaying


def test_checkfree_plus_edge_stage_recovery(tmp_path):
    events = {3: [0], 5: [STAGES - 1]}
    tr = make_trainer("checkfree_plus", steps=8, events=events,
                      tmpdir=str(tmp_path))
    state, hist = tr.run(batches())
    assert len(hist.failures) == 2
    assert all(np.isfinite(hist.loss))


def test_checkpoint_rollback_loses_progress(tmp_path):
    """A failure under checkpointing reverts effective progress; the same
    failure under CheckFree does not (the paper's central wall-clock
    argument)."""
    events = {5: [1]}
    tr_ck = make_trainer("checkpoint", steps=8, events=events,
                         tmpdir=str(tmp_path))
    _, hist_ck = tr_ck.run(batches())
    tr_cf = make_trainer("checkfree", steps=8, events=events,
                         tmpdir=str(tmp_path))
    _, hist_cf = tr_cf.run(batches())
    assert hist_ck.wall_iters > hist_cf.wall_iters  # rollback replays iters


def test_redundant_failure_is_lossless(tmp_path):
    """Redundant computation recovers exact weights -> the loss series is
    identical to the no-failure run (only wall-clock differs)."""
    events = {4: [2]}
    tr_red = make_trainer("redundant", steps=6, events=events,
                          tmpdir=str(tmp_path))
    _, hist_red = tr_red.run(batches())
    tr_none = make_trainer("none", steps=6, events=None,
                           tmpdir=str(tmp_path))
    _, hist_none = tr_none.run(batches())
    np.testing.assert_allclose(hist_red.loss, hist_none.loss, rtol=1e-6)
    assert hist_red.wall_time[-1] > hist_none.wall_time[-1]


def test_checkfree_beats_random_after_failure(tmp_path):
    """Fig. 2's ordering on a micro scale: after the same failures, weighted
    averaging must not be worse than random reinit at the end of training."""
    events = {3: [1], 4: [2]}
    losses = {}
    for strategy in ("checkfree", "random"):
        tr = make_trainer(strategy, steps=14, events=events,
                          tmpdir=str(tmp_path))
        _, hist = tr.run(batches())
        losses[strategy] = float(np.mean(hist.loss[-3:]))
    assert losses["checkfree"] <= losses["random"] + 0.05, losses


def test_walltime_model_table2_structure():
    w = WallClockModel()
    assert w.iteration_cost("redundant") > w.iteration_cost("checkfree")
    assert w.iteration_cost("checkpoint", 100) >= w.iteration_cost("none")
    assert w.failure_cost("checkpoint") > w.failure_cost("checkfree") > \
        w.failure_cost("redundant")
    np.testing.assert_allclose(w.iteration_cost("redundant") /
                               w.iteration_cost("checkfree"),
                               151.0 / 91.3, rtol=1e-6)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_synthetic_lm_deterministic_and_entropic():
    src = SyntheticLM(128, seed=7)
    r1 = src.sample(np.random.default_rng(0), 2, 64)
    r2 = src.sample(np.random.default_rng(0), 2, 64)
    np.testing.assert_array_equal(r1, r2)
    assert 0 < src.entropy_floor < np.log(128)
    assert r1.shape == (2, 65) and r1.min() >= 0 and r1.max() < 128


def test_batch_for_adds_modalities():
    vlm_cfg = CFG.replace(arch_type="vlm", num_patches=4)
    raw = np.zeros((2, 17), np.int64)
    b = batch_for(vlm_cfg, raw)
    assert b["patches"].shape[:2] == (2, 4)
    assert b["tokens"].shape == (2, 16)
