"""The chunked SSD scan (``models/ssm.ssd_chunked``) at the published decay
range: A = 1..16 over the heads and step sizes dt = 0.1, at chunk 64 and at
a whole 256-token sequence as one chunk.  Above a chunk's diagonal the
segment sums reach (chunk - 1) * dt * A, past float32's exponent range, so
the output and every gradient must stay finite and equal both the
benchmark's plain reference (``bench.reference.mamba2.ssd``) and the
token-by-token recurrence to float32 round-off.  A small SSM then trains
through ``Trainer`` at chunk 64 across a stage failure."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.reference.mamba2 import ssd as reference_ssd  # noqa: E402
from repro.config import (ModelConfig, OptimizerConfig,  # noqa: E402
                          RecoveryConfig, SSMConfig, TrainConfig)
from repro.core.trainer import Trainer  # noqa: E402
from repro.data.pipeline import make_batches  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.models.ssm import ssd_chunked  # noqa: E402

SEQ, HEADS, P, N = 256, 16, 8, 16
DT = 0.1
#: relative error of float32 round-off over these sums (the three agree to
#: about 1e-6 here; an overflow or a lost term reads NaN or order 1)
ROUNDOFF = 2e-5


def _inputs():
    kx, kb, kc, kw = jax.random.split(jax.random.PRNGKey(0), 4)
    a_heads = -DT * jnp.linspace(1.0, 16.0, HEADS)          # dt * A per head
    return {
        "x": DT * jax.random.normal(kx, (1, SEQ, HEADS, P)),
        "a": jnp.broadcast_to(a_heads, (1, SEQ, HEADS)),
        "b": jax.random.normal(kb, (1, SEQ, 1, N)),
        "c": jax.random.normal(kc, (1, SEQ, 1, N)),
    }, jax.random.normal(kw, (1, SEQ, HEADS, P))


def _program(chunk):
    return lambda x, a, b, c: ssd_chunked(x, a, b, c, chunk)[0]


def _reference(chunk):
    return lambda x, a, b, c: reference_ssd(x, a, b, c, chunk, "float32")


def _recurrence(x, a, b, c):
    """state_t = exp(a_t) state_{t-1} + x_t B_t^T;  y_t = state_t C_t."""
    def step(state, inp):
        x_t, a_t, b_t, c_t = inp                 # (H,P) (H,) (N,) (N,)
        state = state * jnp.exp(a_t)[:, None, None] \
            + x_t[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t,
                                 precision=jax.lax.Precision.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((HEADS, P, N), jnp.float32),
                        (x[0], a[0], b[0, :, 0], c[0, :, 0]))
    return y[None]


def _value_and_grads(fn, inputs, weight):
    """The output and the gradients of <output, weight> with respect to
    x, a, B and C."""
    args = (inputs["x"], inputs["a"], inputs["b"], inputs["c"])
    y = fn(*args)
    grads = jax.grad(lambda *xs: jnp.sum(fn(*xs) * weight),
                     argnums=(0, 1, 2, 3))(*args)
    return dict(zip(("y", "x", "a", "b", "c"), (y,) + grads))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@functools.lru_cache(maxsize=None)
def _readings(chunk):
    inputs, weight = _inputs()
    return {name: _value_and_grads(fn, inputs, weight) for name, fn in (
        ("program", jax.jit(_program(chunk))),
        ("reference", jax.jit(_reference(chunk))),
        ("recurrence", jax.jit(_recurrence)))}


def test_the_inputs_reach_past_float32s_exp():
    """Above the diagonal of a 64-token chunk the segment sums of these
    decays reach 63 * 0.1 * 16 = 100.8, past log(float32 max) = 88.7."""
    cs = np.cumsum(np.asarray(_inputs()[0]["a"][0, :64], np.float64), axis=0)
    seg = cs[:, None, :] - cs[None, :, :]             # seg[q, k] = cs_q - cs_k
    upper = seg[np.triu_indices(64, 1)]               # k > q: masked out
    assert upper.max() > np.log(np.finfo(np.float32).max)


@pytest.mark.parametrize("chunk", [64, 256])
@pytest.mark.parametrize("what", ["y", "x", "a", "b", "c"])
def test_ssd_is_finite_at_the_published_decays(chunk, what):
    got = _readings(chunk)["program"][what]
    assert np.all(np.isfinite(np.asarray(got))), (chunk, what)


@pytest.mark.parametrize("chunk", [64, 256])
@pytest.mark.parametrize("what", ["y", "x", "a", "b", "c"])
@pytest.mark.parametrize("against", ["reference", "recurrence"])
def test_ssd_equals_the_reference_and_the_recurrence(chunk, what, against):
    readings = _readings(chunk)
    err = _rel(readings["program"][what], readings[against][what])
    assert err < ROUNDOFF, (chunk, what, against, err)


def test_ssd_lower_triangle_is_unchanged_by_the_mask():
    """Where no exponent overflows (dt * A small), the scan equals the
    recurrence as before: the mask touches only the upper triangle."""
    inputs, weight = _inputs()
    inputs["a"] = inputs["a"] * 0.01
    got = _value_and_grads(jax.jit(_program(64)), inputs, weight)
    want = _value_and_grads(jax.jit(_recurrence), inputs, weight)
    for what in got:
        assert _rel(got[what], want[what]) < ROUNDOFF, what


class _Failure:
    """Stage 1 fails at wall step 4, stage 0 (an edge) at wall step 8."""

    def at(self, step):
        return {4: [1], 8: [0]}.get(step, [])


def test_small_ssm_trains_finite_through_a_failure():
    """d_model 64, chunk 64, seq 128: two chunks per sequence, 8 heads with
    A = 1..16 and the published dt range.  Every loss, every stage's
    gradient norm after each window and every recovery error is finite."""
    cfg = ModelConfig(
        name="ssd-numerics", arch_type="ssm", num_layers=4, d_model=64,
        num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=128,
        tie_embeddings=True, dtype="float32", param_dtype="float32",
        ssm=SSMConfig(state_dim=16, head_dim=16, expand=2, conv_width=4,
                      chunk_size=64, ngroups=1))
    tcfg = TrainConfig(
        global_batch=2, microbatch=2, seq_len=128, steps=12, fuse_window=4,
        seed=21, optimizer=OptimizerConfig(lr=3e-3, total_steps=12,
                                           warmup_steps=2),
        recovery=RecoveryConfig(strategy="checkfree_plus", num_stages=4))
    trainer = Trainer(build_model(cfg), tcfg, schedule=_Failure())
    omegas = []
    after_step = trainer.strategy.after_step

    def record(state, hist):
        omegas.append(np.asarray(state.omegas))
        return after_step(state, hist)

    trainer.strategy.after_step = record
    state, hist = trainer.run(make_batches(cfg, batch=2, seq=128, seed=0))
    assert len(hist.loss) == 12 and np.all(np.isfinite(hist.loss))
    assert hist.failures == [(4, 1), (8, 0)]
    assert omegas and np.all(np.isfinite(np.stack(omegas)))
    assert np.all(np.stack(omegas) > 0)
    assert all(np.isfinite(e) for _, e in hist.recovery_errors)
    assert all(np.all(np.isfinite(np.asarray(leaf)))
               for leaf in jax.tree.leaves(state.params))
