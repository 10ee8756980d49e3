"""The program's named scopes and anchors, and the reduction that reads
them (``bench/devscope.py``): scopes in the lowered fused step, anchors in
the profiler's trace, and the reduction on a small trace kept beside the
tests, whose two windows' anchors differ in offset."""
import json
import os
import re
from types import SimpleNamespace

import pytest

from bench import devscope, harness
from tiny import tiny_config

DATA = os.path.join(os.path.dirname(__file__), "data")


def _scoped_trace():
    with open(os.path.join(DATA, "trace_scoped.json")) as f:
        doc = json.load(f)
    return doc, doc["spans"], {e["wall_step"]: 1 for e in doc["events"]}


# ---------------------------------------------------------------------------
# the program: scopes in the lowered step, anchors in the profiler's trace
# ---------------------------------------------------------------------------

_OP_NAMES = {}


def _op_names(config_name):
    """Op-name metadata of the tiny configuration's lowered fused step
    (window of 2, bfloat16 compute so that the parameter cast is there)."""
    if config_name not in _OP_NAMES:
        import jax
        import jax.numpy as jnp
        from repro.core.trainer import Trainer
        from repro.models.model import build_model
        from repro.optim.adam import init_adam
        config = tiny_config(config_name)
        config["model"]["dtype"] = "bfloat16"
        model_cfg, tcfg = harness.program_configs(config, seed=3)
        trainer = Trainer(build_model(model_cfg), tcfg)

        def init():
            params = trainer.model.init(jax.random.PRNGKey(0))
            return params, init_adam(params)

        params, opt_state = jax.eval_shape(init)
        window = jax.ShapeDtypeStruct(
            (2, tcfg.global_batch, tcfg.seq_len), jnp.int32)
        lowered = trainer.fused_step._jitted.lower(
            params, opt_state, {"tokens": window, "labels": window}, 1.0,
            jnp.int32(2))
        _OP_NAMES[config_name] = re.findall(
            r'op_name="([^"]*)"', lowered.as_text(dialect="hlo",
                                                  debug_info=True))
    return _OP_NAMES[config_name]


@pytest.mark.parametrize("config_name,scope", [
    ("granite-moe-3b.L4", s) for s in (
        "window_loop", "embed", "param_cast", "layer_scan", "attention",
        "moe_dispatch", "moe_experts", "logits_loss", "stage_omegas",
        "tower_swap", "adam")] + [
    ("mamba2-1.3b.L8", "ssd_scan")])
def test_the_fused_step_carries_each_named_scope(config_name, scope):
    names = _op_names(config_name)
    assert scope in devscope.SCOPES
    assert any(scope in devscope.scopes_of(n) for n in names), scope


def test_anchors_land_in_the_profilers_trace(tmp_path):
    """Each anchor is an annotation in the profiler's trace that carries
    the recorder's clock reading, and the extraction finds them all."""
    import jax
    from repro import telemetry
    rec = telemetry.Recorder(stream=False)
    prev = telemetry.set_recorder(rec)
    try:
        jax.profiler.start_trace(str(tmp_path))
        for _ in range(3):
            telemetry.anchor()
        jax.profiler.stop_trace()
    finally:
        telemetry.set_recorder(prev)
    raw = devscope.extract(str(tmp_path))
    assert [t for t, _ in raw["anchors"]] == pytest.approx(rec.anchors,
                                                            abs=1e-9)
    offsets = devscope.anchor_offsets_ns(raw["anchors"])
    assert max(offsets) - min(offsets) < 1e6       # one clock, to a ms


def _pb(field, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = b""
        while n >= 0x80:
            out += bytes([n & 0x7F | 0x80])
            n >>= 7
        return out + bytes([n])
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(value)) + value


def test_op_names_from_the_device_planes_tf_op_stats():
    """The op name is the ``tf_op`` stat of the event metadata, as a
    string or as a reference to an interned one, found by the event's name
    or display name; an operation without one has none."""
    stat = _pb(1, 3)
    md = [_pb(1, 7) + _pb(2, "fusion.1") + _pb(4, "%fusion.1 = f32[2] x")
          + _pb(5, stat + _pb(5, "jit(f)/while/body/adam/sub")),
          _pb(1, 8) + _pb(2, "%dot.2 = f32[2] y")
          + _pb(5, stat + _pb(7, 9))]
    plane = (_pb(2, "/device:TPU:0")
             + b"".join(_pb(4, _pb(1, i) + _pb(2, m))
                        for i, m in zip((7, 8), md))
             + _pb(5, _pb(1, 3) + _pb(2, _pb(1, 3) + _pb(2, "tf_op")))
             + _pb(5, _pb(1, 9) + _pb(2, _pb(1, 9) + _pb(
                 2, "jit(f)/while/body/attention/dot_general"))))
    names = devscope.OpNames(_pb(1, plane))
    ops = [["%fusion.1 = f32[2] x", 0, 1], ["%dot.2 = f32[2] y", 1, 1],
           ["%copy.3 = f32[2] z", 2, 1]]
    assert names.of("/device:TPU:0", ops) == [
        "jit(f)/while/body/adam/sub",
        "jit(f)/while/body/attention/dot_general", ""]


# ---------------------------------------------------------------------------
# the reduction, on a small trace
# ---------------------------------------------------------------------------

def test_scope_paths_drop_the_transformations_around_each_scope():
    path = "jit(fused_step)/while/body/transpose(jvp(attention))/dot_general"
    assert devscope.scope_path(path) == [
        "fused_step", "while", "body", "attention", "dot_general"]
    assert devscope.scopes_of(path) == ["attention"]
    nested = "jit(f)/while/body/jvp(layer_scan)/while/body/attention/dot"
    assert devscope.scopes_of(nested) == ["layer_scan", "attention"]
    assert devscope.scopes_of("jit(_one_hot)/eq") == []
    assert devscope.scopes_of("adam_update/sub") == []


def test_spans_map_through_the_nearer_anchor():
    """The anchors' offsets are 500 ns at 10 us and 700 ns at 30 and 40 us
    of the recorder's clock: each time takes the offset of the anchor
    nearest to it."""
    doc, _, _ = _scoped_trace()
    ns = devscope.clock_map(doc["anchors"])
    assert ns(10e-6) == pytest.approx(10500)
    assert ns(19.9e-6) == pytest.approx(20400)
    assert ns(20.1e-6) == pytest.approx(20800)
    assert ns(50e-6) == pytest.approx(50700)
    assert devscope.anchor_offsets_ns(doc["anchors"]) == pytest.approx(
        [500, 700, 700])


def test_scoped_busy_time_per_step():
    """adam: 2000 + 3000 + 2000 ns on TPU:0 and 1000 on TPU:1 inside the
    three windows of 4 steps; averaged over the two chips."""
    doc, spans, failed = _scoped_trace()
    scoped = devscope.summarize(doc, spans, 16, failed)
    ctx = SimpleNamespace(scoped=scoped, steps=12)
    assert devscope.scope_ms_per_step(ctx, "adam") == pytest.approx(
        4000e-6 / 12)
    assert devscope.scope_ms_per_step(ctx, "attention") == pytest.approx(
        1000e-6 / 12)
    assert devscope.scope_ms_per_step(ctx, "moe_dispatch") == \
        pytest.approx(1000e-6 / 12)
    assert devscope.scope_ms_per_step(ctx, "moe_experts") is None
    # an operation counts for its innermost scope only: the attention
    # inside the layer scan is attention's, the scan's own stacking the
    # scan's
    assert devscope.scope_ms_per_step(ctx, "layer_scan") == pytest.approx(
        1500e-6 / 12)
    # TPU:0 busy 16300 ns inside the windows, 14000 of it scoped
    assert scoped["window_busy_s"] == pytest.approx((16300 + 1000) / 2e9)
    assert scoped["scoped_busy_s"] == pytest.approx((14000 + 1000) / 2e9)
    for name, scope in (("adam_device_ms", "adam"),
                        ("attention_ms", "attention"),
                        ("moe_dispatch_ms", "moe_dispatch")):
        reader = __import__(f"bench.metrics.{name}", fromlist=["read"])
        assert reader.read(ctx) == devscope.scope_ms_per_step(ctx, scope)


def test_device_time_and_programs_per_failure_boundary():
    """The boundary at wall step 20 runs from the drain's end (16 us, 16500
    ns) to the next dispatch (30 us, 30700 ns): TPU:0 runs 4700 ns of eager
    programs there and TPU:1 1000 ns, three programs each."""
    doc, spans, failed = _scoped_trace()
    scoped = devscope.summarize(doc, spans, 16, failed)
    (b,) = scoped["failure_boundaries"]
    assert b["wall_step"] == 20 and b["failures"] == 1
    assert b["host_s"] == pytest.approx(14e-6)
    assert b["device_s"] == pytest.approx(2850e-9)
    assert b["programs"] == 3
    assert b["covered_s"] == pytest.approx(13.6e-6)
    # the idle time, split among the innermost spans over it (host_gap
    # where none is), and the device time add up to the boundary
    assert b["idle_s"] == pytest.approx({
        "host_gap": 400e-9, "window_bookkeeping": 800e-9,
        "failures": 400e-9, "recovery": 400e-9, "recovery_merge": 2800e-9,
        "recovery_error_drain": 800e-9, "recovery_moment_reset": 3100e-9,
        "window_prepare": 800e-9})
    assert sum(b["idle_s"].values()) + 4700e-9 == pytest.approx(14.2e-6)
    ctx = SimpleNamespace(scoped=scoped, steps=12)
    for name, want in (("recovery_device_ms", 2850e-6),
                       ("recovery_programs", 3)):
        reader = __import__(f"bench.metrics.{name}", fromlist=["read"])
        assert reader.read(ctx) == pytest.approx(want)


def test_idle_gaps_are_named_by_the_innermost_span():
    """Each gap takes the name of the innermost span that holds most of
    it.  The gap from 25200 to 26000 ns is 500 ns in the error drain
    (which ends at 25 us + 700 ns) and 300 in the moment reset, as the
    nearer anchor maps them; the farther one would give the reset 500."""
    doc, spans, failed = _scoped_trace()
    scoped = devscope.summarize(doc, spans, 16, failed)
    assert scoped["idle_gaps"] == [
        ["window_prepare", pytest.approx(4.5e-6)],
        ["recovery_moment_reset", pytest.approx(4.3e-6)],
        ["recovery_merge", pytest.approx(3e-6)],
        ["window_bookkeeping", pytest.approx(1.6e-6)],
        ["window_drain", pytest.approx(0.9e-6)],
        ["recovery_error_drain", pytest.approx(0.8e-6)],
        ["window_dispatch", pytest.approx(0.1e-6)]]


def test_a_trace_without_scopes_or_anchors_reads_nothing():
    """The harness's own trace data (no anchor, no scope path), and a run
    without a scoped trace, give no value and raise nothing."""
    with open(os.path.join(DATA, "trace_small.json")) as f:
        small = json.load(f)
    assert devscope.summarize(small, [], 16, {}) is None
    ctx = SimpleNamespace(trace=None, steps=12)
    for name in ("adam_device_ms", "moe_dispatch_ms", "attention_ms",
                 "recovery_device_ms", "recovery_programs"):
        reader = __import__(f"bench.metrics.{name}", fromlist=["read"])
        assert reader.read(ctx) is None
