"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.failures import FailureSchedule
from repro.core.swap import stage_permutations, swap_permutation
from repro.kernels.stage_merge import stage_merge

SETTINGS = dict(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# failure schedule invariants (paper §3 constraints)
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(rate=st.floats(0.01, 0.5), stages=st.integers(3, 12),
       seed=st.integers(0, 10_000), protect=st.booleans())
def test_failure_schedule_invariants(rate, stages, seed, protect):
    fs = FailureSchedule(rate_per_hour=rate, iteration_time_s=600.0,
                         num_stages=stages, steps=200, seed=seed,
                         protect_edges=protect)
    by_step = {}
    for e in fs.events:
        assert 0 <= e.step < 200
        lo, hi = (1, stages - 1) if protect else (0, stages)
        assert lo <= e.stage < hi, (e, protect)
        by_step.setdefault(e.step, []).append(e.stage)
    # no two consecutive stages fail in the same step (paper assumption)
    for step, failed in by_step.items():
        s = sorted(failed)
        assert all(b - a >= 2 for a, b in zip(s, s[1:])), (step, s)


@settings(**SETTINGS)
@given(rate=st.floats(0.01, 0.3), seed=st.integers(0, 1000))
def test_failure_schedule_deterministic(rate, seed):
    mk = lambda: FailureSchedule(rate_per_hour=rate, iteration_time_s=91.3,
                                 num_stages=6, steps=100, seed=seed)
    assert mk().events == mk().events


# ---------------------------------------------------------------------------
# swap schedule invariants (CheckFree+ §4.3)
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(stages=st.integers(1, 16),
       lps=st.integers(1, 8))
def test_swap_permutation_is_permutation(stages, lps):
    n = stages * lps
    idx = swap_permutation(n, stages)
    assert sorted(idx.tolist()) == list(range(n))


@settings(**SETTINGS)
@given(stages=st.integers(4, 16))
def test_swap_only_touches_edge_pairs(stages):
    normal, swapped = stage_permutations(stages)
    assert swapped[0] == 1 and swapped[1] == 0
    assert swapped[-1] == stages - 2 and swapped[-2] == stages - 1
    assert swapped[2:-2] == normal[2:-2]


def test_swap_degenerate_small():
    for k in (1, 2, 3):
        normal, swapped = stage_permutations(k)
        assert normal == swapped


# ---------------------------------------------------------------------------
# stage-merge kernel: convex-combination invariants for arbitrary weights
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 300), w=st.floats(0.0, 1.0),
       seed=st.integers(0, 100))
def test_merge_convexity_property(n, w, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (n,), jnp.float32)
    y = jax.random.normal(k2, (n,), jnp.float32)
    got = np.asarray(stage_merge(x, y, w, 1.0 - w, interpret=True))
    lo = np.minimum(np.asarray(x), np.asarray(y)) - 1e-5
    hi = np.maximum(np.asarray(x), np.asarray(y)) + 1e-5
    assert (got >= lo).all() and (got <= hi).all()
    assert got.shape == (n,)
