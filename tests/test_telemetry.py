"""Telemetry layer tests (repro.telemetry): recorder primitives, the
structured event schema, Chrome trace export, derived run-level metrics,
the report CLI contract, and the instrumented trainer/statestore streams.

The load-bearing assertions:

* **overhead contract** — with telemetry disabled the fused hot path is
  bit-identical (loss trace) and dispatch-identical to the enabled run;
* **host-side only** — the whole instrumented loop passes under the PR 6
  ``sync_free()`` guard *with a recorder installed*;
* **CI contract** — ``repro.telemetry.report --strict`` exits 0 only when
  goodput, a per-strategy recovery breakdown, and the per-tier snapshot
  section are all derivable from the stream.
"""
import json
import os
import sys
import warnings

import numpy as np
import pytest

from repro import telemetry
from repro.analysis import runtime
from repro.config import (ModelConfig, OptimizerConfig, RecoveryConfig,
                          TrainConfig)
from repro.core.state import History
from repro.core.trainer import Trainer
from repro.core.walltime import WallClockModel
from repro.data.pipeline import make_batches
from repro.models.model import build_model
from repro.statestore import DiskTier, MemoryTier, StateStore
from repro.telemetry import (Recorder, chrome_trace, load_chrome_trace,
                             validate_events, validate_record)
from repro.telemetry.log import log, set_verbosity
from repro.telemetry.metrics import (compute_metrics, render_text,
                                     strict_problems)
from repro.telemetry.report import main as report_main

CFG = ModelConfig(
    name="tel-llama", arch_type="dense", num_layers=4, d_model=32,
    num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=128, max_seq_len=32,
    dtype="float32", param_dtype="float32")
STAGES = 4
SPECS = WallClockModel().tier_specs()


@pytest.fixture
def rec():
    """A scoped in-memory recorder installed process-wide."""
    r = Recorder(stream=False)
    prev = telemetry.set_recorder(r)
    try:
        yield r
    finally:
        telemetry.set_recorder(prev)


class ForcedSchedule:
    def __init__(self, events):
        self._events = dict(events)

    def at(self, step):
        return self._events.get(step, [])


def make_trainer(*, strategy="none", window=4, steps=12, events=None,
                 checkpoint_dir=None):
    rcfg = RecoveryConfig(strategy=strategy, num_stages=STAGES,
                          checkpoint_every=1000,
                          checkpoint_dir=checkpoint_dir or "/tmp/tel_ckpt")
    tcfg = TrainConfig(
        global_batch=4, microbatch=4, seq_len=32, steps=steps,
        eval_every=100, fuse_window=window,
        optimizer=OptimizerConfig(lr=1e-3, total_steps=steps,
                                  warmup_steps=2),
        recovery=rcfg)
    return Trainer(build_model(CFG), tcfg,
                   schedule=ForcedSchedule(events) if events else None)


def _batches(seed=0):
    return make_batches(CFG, batch=4, seq=32, seed=seed)


# ---------------------------------------------------------------------------
# recorder primitives
# ---------------------------------------------------------------------------

def test_counters_gauges_histograms(rec):
    """Counters are the recorder's one metric primitive."""
    telemetry.inc("dispatches")
    telemetry.inc("dispatches", 2)
    assert rec.snapshot() == {"counters": {"dispatches": 3}}


def test_event_stream_writes_jsonl(tmp_path):
    r = Recorder(str(tmp_path))
    prev = telemetry.set_recorder(r)
    try:
        telemetry.emit("log", message="hello", level=1)
        telemetry.emit("sim_node", what="fail", step=3, stage=1, node_id=7)
    finally:
        telemetry.set_recorder(prev)
        r.close()
    lines = (tmp_path / "events.jsonl").read_text().splitlines()
    events = [json.loads(ln) for ln in lines]
    assert [e["kind"] for e in events] == ["log", "sim_node"]
    assert validate_events(events) == []
    # the envelope is stamped on every record
    from repro.telemetry.events import SCHEMA_VERSION
    assert all(e["v"] == SCHEMA_VERSION and e["t_s"] >= 0.0 for e in events)
    # events also feed the per-kind counters
    assert r.counters["events.log"] == 1


def test_event_payloads_are_sanitized(rec):
    telemetry.emit("log", message="x", level=np.int64(2),
                   extra=np.float32(1.5), seq=(np.int32(1), 2))
    e = rec.events[0]
    assert e["level"] == 2 and type(e["level"]) is int
    assert e["extra"] == 1.5 and type(e["extra"]) is float
    assert e["seq"] == [1, 2]
    assert validate_record(e) == []


def test_validate_record_rejects_malformed():
    ok = {"v": 1, "kind": "failure", "t_s": 0.1, "wall_step": 3,
          "stage": 1, "cost_s": 2.0, "overhead_s": 0.0}
    assert validate_record(ok) == []
    assert validate_record("nope")                      # not an object
    assert validate_record({"kind": "failure", "t_s": 0.0})  # no version
    assert any("newer" in p for p in validate_record(dict(ok, v=99)))
    assert any("unknown" in p
               for p in validate_record(dict(ok, kind="wat")))
    missing = dict(ok)
    del missing["stage"]
    assert any("missing required field 'stage'" in p
               for p in validate_record(missing))
    # bools are not ints: a swapped synchronous/nbytes must not validate
    bad = {"v": 1, "kind": "snapshot_save", "t_s": 0.0, "step": 1,
           "shard_id": "s0", "tier": "mem", "nbytes": True,
           "synchronous": 1}
    probs = validate_record(bad)
    assert any("'nbytes'" in p for p in probs)
    assert any("'synchronous'" in p for p in probs)
    # extra fields are always allowed (schemas grow by addition)
    assert validate_record(dict(ok, novel_field=123)) == []


def test_disabled_helpers_are_noops():
    assert telemetry.get_recorder() is None
    assert not telemetry.enabled()
    telemetry.emit("log", message="dropped", level=1)   # no sink, no error
    telemetry.inc("x")
    telemetry.complete("span", 0.0)
    telemetry.anchor()
    assert telemetry.clock() == 0.0
    # the disabled span is ONE shared null context — no per-call allocation
    assert telemetry.span("a") is telemetry.span("b")


# ---------------------------------------------------------------------------
# spans and the Chrome trace
# ---------------------------------------------------------------------------

def test_spans_export_as_chrome_trace(tmp_path, rec):
    with telemetry.span("outer", cat="test", k=8):
        telemetry.emit("log", message="mark", level=1)
    t0 = telemetry.clock()
    telemetry.complete("manual", t0, cat="test")
    path = rec.write_chrome_trace(str(tmp_path / "trace.json"))
    trace = load_chrome_trace(path)
    evs = trace["traceEvents"]
    spans = {e["name"] for e in evs if e.get("ph") == "X"}
    assert spans == {"outer", "manual"}
    outer = next(e for e in evs if e.get("ph") == "X"
                 and e["name"] == "outer")
    assert outer["args"]["k"] == 8 and outer["dur"] >= 0
    # emitted events ride along as instants
    instants = [e for e in evs if e.get("ph") == "i"]
    assert any(e["name"] == "log" for e in instants)
    # process metadata names the trace
    assert any(e.get("ph") == "M" for e in evs)


def test_spans_record_the_innermost_open_span_as_parent(rec):
    """A span's parent is the innermost ``span`` open on its thread when it
    ends; a manual clock/complete span takes one but is never one."""
    import threading
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            t0 = telemetry.clock()
            telemetry.complete("manual", t0)
        worker = threading.Thread(
            target=lambda: telemetry.complete("other_thread",
                                              telemetry.clock()))
        worker.start()
        worker.join()
    with telemetry.span("next"):
        pass
    parents = {s["name"]: s["parent"] for s in rec.spans}
    assert parents == {"manual": "inner", "inner": "outer",
                       "other_thread": None, "outer": None, "next": None}
    trace = rec.chrome_trace()["traceEvents"]
    args = {e["name"]: e["args"] for e in trace if e.get("ph") == "X"}
    assert args["inner"]["parent"] == "outer"
    assert "parent" not in args["outer"]


def test_anchor_stores_the_recorders_clock(rec):
    """Outside a profiler session the anchor still lands in the recorder,
    on the recorder's own clock."""
    t0 = rec.now()
    telemetry.anchor()
    telemetry.anchor()
    assert len(rec.anchors) == 2
    assert t0 <= rec.anchors[0] <= rec.anchors[1] <= rec.now()


def test_report_cli_runs_without_jax(tmp_path):
    """The report CLI and the telemetry package import nothing of jax."""
    import subprocess
    run = _write_stream(tmp_path, _synthetic_events())
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro.telemetry.report import main\n"
            f"sys.exit(main([{run!r}, '--strict']))\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, PYTHONPATH=src))
    assert out.returncode == 0, out.stderr
    assert "recovery[checkfree]" in out.stdout


def test_load_chrome_trace_rejects_malformed(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X", "ts": 0}]}))
    with pytest.raises(ValueError):
        load_chrome_trace(str(bad))
    notdict = tmp_path / "nd.json"
    notdict.write_text("[1, 2]")
    with pytest.raises(ValueError):
        load_chrome_trace(str(notdict))


def test_async_snapshot_spans_get_their_own_track(tmp_path, rec):
    """The AsyncSnapshotter worker emits from its own thread; its spans
    must carry a distinct tid so the Chrome trace shows a separate row."""
    store = StateStore([MemoryTier(SPECS["mem"]),
                        DiskTier(SPECS["disk"], str(tmp_path))])
    tree = {"w": np.ones((4, 4), np.float32)}
    store.put(tree, step=1, shard_id="s0", tier="disk")   # async write
    store.flush()
    store.close()
    tids = {s["tid"] for s in rec.spans if s["name"] == "tier_write"}
    assert tids and all(t != 0 for t in tids)


# ---------------------------------------------------------------------------
# derived metrics + strict contract
# ---------------------------------------------------------------------------

def _synthetic_events():
    mk = lambda kind, t, **kw: dict({"v": 1, "kind": kind, "t_s": t}, **kw)
    return [
        mk("run_start", 0.0, arch="tel-llama", strategy="checkfree",
           backend="host", steps=8, num_stages=4, tokens_per_step=128),
        mk("step_window", 1.0, wall_step=0, k=4, effective_step=4,
           loss=3.0, clock_s=100.0, stretch=1.0),
        mk("failure", 1.5, wall_step=4, stage=2, cost_s=90.0,
           overhead_s=10.0),
        mk("recovery", 1.6, wall_step=4, stage=2, strategy="checkfree",
           duration_s=0.25, stages=[2]),
        mk("step_window", 2.0, wall_step=5, k=4, effective_step=8,
           loss=2.5, clock_s=200.0, stretch=1.5),
        mk("snapshot_save", 2.1, step=8, shard_id="s0", tier="mem",
           nbytes=1000, synchronous=True),
        mk("snapshot_save", 2.2, step=8, shard_id="s0", tier="disk",
           nbytes=1000, synchronous=False),
        mk("snapshot_restore", 2.3, step=8, shard_id="s0", tier="mem",
           nbytes=1000, read_time_s=0.5),
        mk("run_end", 4.0, effective_steps=8, wall_iters=9, dispatches=3,
           failures=1, truncated=False, clock_s=300.0),
    ]


def test_metrics_from_synthetic_stream():
    events = _synthetic_events()
    assert validate_events(events) == []
    m = compute_metrics(events)
    assert m["goodput"] == pytest.approx(8 / 9)
    assert m["wall_iters"] == 9 and m["dispatches"] == 3
    r = m["recovery"]
    assert r["events"] == 1 and r["failures"] == 1
    assert r["by_strategy"]["checkfree"]["count"] == 1
    assert r["by_strategy"]["checkfree"]["measured_s"] == pytest.approx(.25)
    assert r["modelled_cost_s"] == pytest.approx(100.0)
    tiers = m["snapshots"]["by_tier"]
    assert tiers["mem"]["saves"] == 1 and tiers["mem"]["restores"] == 1
    assert tiers["disk"]["saved_bytes"] == 1000
    assert tiers["mem"]["read_time_s"] == pytest.approx(0.5)
    # stretch is k-weighted: (1.0*4 + 1.5*4) / 8
    assert m["straggler"]["mean_stretch"] == pytest.approx(1.25)
    assert m["straggler"]["max_stretch"] == pytest.approx(1.5)
    assert strict_problems(m) == []
    text = render_text(m)
    assert "goodput" in text and "recovery[checkfree]" in text
    assert "tier[mem]" in text


def test_strict_contract_names_missing_metrics():
    events = [e for e in _synthetic_events()
              if e["kind"] not in ("recovery",)]
    m = compute_metrics(events)
    probs = strict_problems(m)
    assert any("recovery" in p for p in probs)
    assert strict_problems({}) != []        # empty metrics fail everything


def test_goodput_falls_back_to_step_windows():
    events = [e for e in _synthetic_events() if e["kind"] != "run_end"]
    m = compute_metrics(events)
    # last window: effective 8 over wall_step 5 + k 4
    assert m["goodput"] == pytest.approx(8 / 9)


# ---------------------------------------------------------------------------
# report CLI (the CI contract)
# ---------------------------------------------------------------------------

def _write_stream(tmp_path, events):
    p = tmp_path / "events.jsonl"
    p.write_text("".join(json.dumps(e) + "\n" for e in events))
    return str(tmp_path)


def test_report_cli_ok(tmp_path, capsys):
    run = _write_stream(tmp_path, _synthetic_events())
    assert report_main([run, "--strict"]) == 0
    assert "recovery[checkfree]" in capsys.readouterr().out


def test_report_cli_json(tmp_path, capsys):
    run = _write_stream(tmp_path, _synthetic_events())
    assert report_main([run, "--json"]) == 0
    m = json.loads(capsys.readouterr().out)
    assert m["goodput"] == pytest.approx(8 / 9)
    assert m["recovery"]["by_strategy"]["checkfree"]["count"] == 1


def test_report_cli_strict_fails_without_recovery(tmp_path):
    events = [e for e in _synthetic_events() if e["kind"] != "recovery"]
    run = _write_stream(tmp_path, events)
    assert report_main([run]) == 0          # lax mode still reports
    assert report_main([run, "--strict"]) == 1


def test_report_cli_rejects_schema_violations(tmp_path):
    events = _synthetic_events()
    events[0] = {"v": 1, "kind": "wat", "t_s": 0.0}
    run = _write_stream(tmp_path, events)
    assert report_main([run, "--strict"]) == 2


def test_report_cli_rejects_missing_or_corrupt_stream(tmp_path):
    assert report_main([str(tmp_path / "nope")]) == 2
    (tmp_path / "events.jsonl").write_text("{not json\n")
    assert report_main([str(tmp_path)]) == 2


# ---------------------------------------------------------------------------
# the logging sink + verbosity knob
# ---------------------------------------------------------------------------

def test_log_respects_verbosity_and_mirrors_events(rec, capsys):
    prev = set_verbosity(1)
    try:
        log("progress line", level=1)
        log("detail line", level=2)         # above the knob: not printed
        log("result line", level=0)
    finally:
        set_verbosity(prev)
    out = capsys.readouterr().out
    assert "progress line" in out and "result line" in out
    assert "detail line" not in out
    # every message lands in the event stream regardless of verbosity
    msgs = [e["message"] for e in rec.events if e["kind"] == "log"]
    assert msgs == ["progress line", "detail line", "result line"]
    assert validate_events(rec.events) == []


# ---------------------------------------------------------------------------
# History JSON round-trip
# ---------------------------------------------------------------------------

def test_history_json_roundtrip():
    hist = History(steps=[1, 2], wall_time=[10.0, 20.0], loss=[3.0, 2.5],
                   eval_loss=[(2, 20.0, 2.4)], failures=[(1, 2)],
                   recovery_errors=[(1, 0.5)], wall_iters=3, dispatches=2,
                   truncated=True)
    back = History.from_json(hist.to_json())
    assert back == hist
    assert History.from_json(History().to_json()) == History()


# ---------------------------------------------------------------------------
# instrumented trainer: overhead contract + event stream
# ---------------------------------------------------------------------------

def test_disabled_telemetry_is_bit_identical_to_enabled():
    """The overhead contract's correctness half: instrumentation must not
    perturb the run.  Loss traces bit-identical, dispatch counts equal."""
    off_t = make_trainer(strategy="checkfree", events={5: [1]})
    _, off = off_t.run(_batches())
    assert telemetry.get_recorder() is None   # baseline ran dark

    r = Recorder(stream=False)
    prev = telemetry.set_recorder(r)
    try:
        on_t = make_trainer(strategy="checkfree", events={5: [1]})
        _, on = on_t.run(_batches())
    finally:
        telemetry.set_recorder(prev)

    assert on.loss == off.loss               # bit-identical, not approx
    assert on.dispatches == off.dispatches
    assert on.wall_iters == off.wall_iters
    # and the expected dispatch count: 12 steps, window 4, one mid-window
    # failure truncation — never fewer than ceil(steps / window)
    assert off.dispatches >= 3


def test_trainer_emits_schema_valid_stream(rec):
    trainer = make_trainer(strategy="checkfree", events={5: [1]})
    trainer.run(_batches())
    assert validate_events(rec.events) == []
    kinds = {e["kind"] for e in rec.events}
    assert {"run_start", "run_end", "step_window",
            "failure", "recovery"} <= kinds
    start = next(e for e in rec.events if e["kind"] == "run_start")
    assert start["strategy"] == "checkfree"
    assert start["tokens_per_step"] == 4 * 32
    end = next(e for e in rec.events if e["kind"] == "run_end")
    assert end["effective_steps"] == 12 and not end["truncated"]
    recov = next(e for e in rec.events if e["kind"] == "recovery")
    assert recov["strategy"] == "checkfree" and recov["stages"] == [1]
    assert recov["path"] == "program"
    # wall-iter accounting in the windows matches the run
    ks = [e["k"] for e in rec.events if e["kind"] == "step_window"]
    assert sum(ks) == end["wall_iters"]
    # dispatch/drain spans cover every window
    names = [s["name"] for s in rec.spans]
    assert names.count("window_dispatch") == end["dispatches"]
    assert names.count("window_drain") == end["dispatches"]
    assert names.count("recovery") == 1
    # the whole recorder exports a loadable Chrome trace
    trace = rec.chrome_trace()
    assert any(e["name"] == "window_dispatch"
               for e in trace["traceEvents"] if e.get("ph") == "X")


def test_failure_boundary_spans_nest_and_cover_the_boundary(rec):
    """A checkfree_plus failure: ``failures`` holds ``recovery``, which
    holds the dispatch of the recovery program and the drain of its
    error, all with the boundary's wall step; the boundary's own spans cover all but
    5% of the time from the drain before it to the next dispatch."""
    trainer = make_trainer(strategy="checkfree_plus", events={5: [1]})
    trainer.run(_batches())
    spans = rec.spans
    at5 = {s["name"]: s for s in spans
           if s["args"].get("wall_step") == 5 and s["name"] not in
           ("window_dispatch",)}
    assert {n: s["parent"] for n, s in at5.items()} == {
        "window_bookkeeping": None, "failures": None,
        "recovery": "failures", "recovery_merge": "recovery",
        "recovery_error_drain": "recovery", "window_prepare": None}
    phases = sum(at5[n]["dur_us"] for n in (
        "recovery_merge", "recovery_error_drain"))
    assert phases <= at5["recovery"]["dur_us"] <= at5["failures"]["dur_us"]
    dispatch = [s for s in spans if s["name"] == "window_dispatch"]
    drain = [s for s in spans if s["name"] == "window_drain"]
    inner = [s for s in spans if s["name"] in (
        "window_bookkeeping", "failures", "window_prepare")]
    for before, after in zip(drain, dispatch[1:]):
        lo = before["ts_us"] + before["dur_us"]
        hi = after["ts_us"]
        covered = sum(max(0.0, min(hi, s["ts_us"] + s["dur_us"])
                          - max(lo, s["ts_us"])) for s in inner)
        if after["args"]["wall_step"] == 5:
            assert covered >= 0.95 * (hi - lo), (covered, hi - lo)
    # one anchor per dispatched window
    assert len(rec.anchors) == len(dispatch)


def test_instrumented_loop_stays_sync_free(rec):
    """Spans/events are host-side only: the fused loop passes the PR 6
    implicit-transfer guard WITH a recorder installed."""
    trainer = make_trainer(strategy="checkfree", events={5: [1]})
    with runtime.sync_free():
        _, hist = trainer.run(_batches())
    assert hist.wall_iters == 12
    assert any(e["kind"] == "recovery" for e in rec.events)


def test_truncation_emits_structured_event(rec, tmp_path):
    """The max_wall safety bound produces a machine-readable truncation
    record alongside the human-facing RuntimeWarning."""
    sched = {s: [2] for s in range(200)}     # fail every step, never save
    trainer = make_trainer(strategy="checkpoint", steps=3, window=1,
                           events=sched,
                           checkpoint_dir=str(tmp_path / "ckpt"))
    with pytest.warns(RuntimeWarning, match="truncated at max_wall"):
        _, hist = trainer.run(_batches())
    assert hist.truncated
    trunc = [e for e in rec.events if e["kind"] == "truncation"]
    assert len(trunc) == 1
    assert trunc[0]["target_steps"] == 3
    assert trunc[0]["wall_iters"] == hist.wall_iters
    end = next(e for e in rec.events if e["kind"] == "run_end")
    assert end["truncated"] is True
    assert validate_events(rec.events) == []


def test_statestore_emits_save_and_restore_events(rec, tmp_path):
    store = StateStore([MemoryTier(SPECS["mem"]),
                        DiskTier(SPECS["disk"], str(tmp_path))])
    tree = {"w": np.ones((8, 8), np.float32)}
    store.put(tree, step=1, shard_id="s0", tier="mem")    # sync (memory)
    store.put(tree, step=2, shard_id="s0", tier="disk")   # async
    store.flush()
    res = store.restore("s0", template=tree)
    store.close()
    assert res.step == 2
    assert validate_events(rec.events) == []
    saves = [e for e in rec.events if e["kind"] == "snapshot_save"]
    assert {(e["tier"], e["synchronous"]) for e in saves} == {
        ("mem", True), ("disk", False)}
    assert all(e["nbytes"] > 0 for e in saves)
    restores = [e for e in rec.events if e["kind"] == "snapshot_restore"]
    assert len(restores) == 1 and restores[0]["tier"] == "disk"
    # metrics aggregate both directions per tier
    tiers = compute_metrics(rec.events)["snapshots"]["by_tier"]
    assert tiers["mem"]["saves"] == 1
    assert tiers["disk"]["saves"] == 1 and tiers["disk"]["restores"] == 1


# ---------------------------------------------------------------------------
# benchmark environment fingerprint
# ---------------------------------------------------------------------------

def test_bench_results_carry_env_fingerprint(tmp_path, monkeypatch):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import common
    fp = common.env_fingerprint()
    assert {"jax", "numpy", "python", "backend", "device_kind",
            "device_count", "pallas_interpret"} <= set(fp)
    assert fp["device_count"] >= 1
    monkeypatch.setattr(common, "RESULTS_DIR", str(tmp_path))
    path = common.save_json("stamped.json", {"metric": 1.0})
    with open(path) as f:
        data = json.load(f)
    assert data["metric"] == 1.0
    assert data["env"]["jax"] == fp["jax"]
    # explicit env survives (no double stamping)
    path = common.save_json("kept.json", {"env": {"jax": "pinned"}})
    with open(path) as f:
        assert json.load(f)["env"] == {"jax": "pinned"}
    # the interpret flag is the platform's choice, recorded as a bool
    assert fp["pallas_interpret"] is (fp["backend"] != "tpu")


def test_bench_run_cache_is_keyed_on_the_device():
    """A CPU result must never be served for a chip run of the same
    parameters: the run cache key carries platform, kind and count."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import common
    params = dict(strategy="checkfree", rate=0.1, scenario=None, steps=8,
                  seed=0, ckpt_every=4, failure_seed=1, lr=1e-3)
    cpu = common.env_fingerprint()
    keys = {common._cache_key(common.run_key(env, **params))
            for env in (cpu, dict(cpu, backend="tpu"),
                        dict(cpu, device_kind="TPU v5 lite"),
                        dict(cpu, device_count=cpu["device_count"] + 3))}
    assert len(keys) == 4
    assert common.run_key(cpu, **params)["platform"] == cpu["backend"]
