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
from repro.launch.shardings import batch_spec, cache_spec, param_spec

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


# ---------------------------------------------------------------------------
# sharding rules: always valid, never shard indivisible dims
# ---------------------------------------------------------------------------

class _FakeMesh:
    def __init__(self, data=16, model=16, pod=0):
        self.axis_names = (("pod",) if pod else ()) + ("data", "model")
        self.shape = dict(data=data, model=model)
        if pod:
            self.shape["pod"] = pod


@settings(**SETTINGS)
@given(dims=st.lists(st.integers(1, 4096), min_size=0, max_size=4),
       model=st.sampled_from([4, 8, 16, 64]))
def test_param_spec_divisibility(dims, model):
    mesh = _FakeMesh(model=model)
    spec = param_spec(tuple(dims), mesh)
    for dim, s in zip(dims, spec):
        if s == "model":
            assert dim % model == 0 and dim >= model
    # the stacked-layer axis of >=3D leaves is never sharded
    if len(dims) >= 3:
        assert spec[0] is None


@settings(**SETTINGS)
@given(batch=st.integers(1, 512), rest=st.lists(st.integers(1, 64),
                                                max_size=2),
       data=st.sampled_from([8, 16]), pod=st.sampled_from([0, 2]))
def test_batch_spec_divisibility(batch, rest, data, pod):
    mesh = _FakeMesh(data=data, pod=pod)
    total = data * (pod or 1)
    spec = batch_spec((batch, *rest), mesh)
    if batch % total == 0 and batch >= total:
        # PartitionSpec normalizes 1-tuples to bare axis names
        want = ("pod", "data") if pod else "data"
        assert spec[0] in (want, (want,) if isinstance(want, str) else want)
    else:
        assert spec[0] is None


@settings(**SETTINGS)
@given(shape=st.lists(st.integers(1, 2048), min_size=1, max_size=5),
       model=st.sampled_from([8, 16]))
def test_cache_spec_valid(shape, model):
    mesh = _FakeMesh(model=model)
    spec = cache_spec(tuple(shape), mesh)
    for dim, s in zip(shape, spec):
        if s == "model":
            assert dim % model == 0
        if s == ("data",):
            assert dim % 16 == 0


# ---------------------------------------------------------------------------
# perf levers (hillclimb) keep the rules valid
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(dims=st.lists(st.integers(1, 8192), min_size=1, max_size=4),
       model=st.sampled_from([8, 16]), data=st.sampled_from([8, 16]))
def test_param_spec_fsdp_divisibility(dims, model, data):
    import os
    mesh = _FakeMesh(data=data, model=model)
    os.environ["REPRO_PARAM_SHARD"] = "fsdp"
    try:
        spec = param_spec(tuple(dims), mesh)
    finally:
        del os.environ["REPRO_PARAM_SHARD"]
    for dim, s in zip(dims, spec):
        if s == ("data", "model"):
            assert dim % (data * model) == 0
        elif s == "model":
            assert dim % model == 0
        elif s == "data":
            assert dim % data == 0
    if len(dims) >= 3:
        assert spec[0] is None   # stacked-layer axis still never sharded


def test_activation_constraint_noop_without_env():
    import jax.numpy as jnp
    from repro.launch.perf import activation_spec, constrain_activations
    assert activation_spec() is None
    x = jnp.ones((2, 4, 8))
    assert constrain_activations(x) is x


def test_activation_spec_modes():
    import os
    from jax.sharding import PartitionSpec as P
    from repro.launch.perf import activation_spec
    try:
        os.environ["REPRO_ACT_SHARD"] = "feature"
        assert activation_spec() == P(None, None, "model")
        os.environ["REPRO_ACT_SHARD"] = "seq"
        assert activation_spec() == P(None, "model", None)
    finally:
        del os.environ["REPRO_ACT_SHARD"]
