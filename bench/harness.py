"""Run one cell of the benchmark once.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to it is found by name: its configuration
(``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``), the limits of its correctness numbers
(``bench/limits/<workload>.json``) and a reader per per-layer metric
(``bench/metrics/<metric>.py``).  Adding a cell adds files; nothing here
changes.

One run is one call of the program's ``Trainer.run`` with the strategy
and failure schedule the cell names.  Its first ``warm_steps`` wall steps
are set-up: the first window compiles, every stage of the rotation fails
and recovers once, and :class:`Probe` records what the correctness check
needs.  The measured window runs from the first window dispatched after
that to the drain of the first window that ends ``--seconds`` or more
later; both ends are read from the program's own telemetry spans.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "bench")
SRC = os.path.join(ROOT, "src")

#: the fused window is 8 steps (the trainer's default); with a failure
#: every 4 wall steps every window is 4.  16 wall steps cover the first
#: window's compile and one failure of each stage in the rotation.
WARM_STEPS = 16
CLOCK_MARK = "bench.clock"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


class WindowClosed(Exception):
    """Raised from the probe to end ``Trainer.run`` at a window boundary
    once the measured window has lasted ``--seconds``."""


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_workload(name: str) -> Dict[str, Any]:
    """The cell ``name`` with its configuration, traffic, limits and the
    metrics it reports, all read from the files named in
    ``BENCHMARK.json``."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(metric, reported=()):
        if "workloads" in metric:
            return name in metric["workloads"]
        return not reported or metric["moves"] in reported

    end_to_end = [m for m in bench["end_to_end"] if applies(m)]
    return {
        "name": name, "chips": cell["chips"],
        "config": _json(os.path.join(ROOT, config_entry["file"])),
        "traffic": _json(os.path.join(HERE, "traffic",
                                      cell["traffic"] + ".json")),
        "limits": _json(os.path.join(HERE, "limits", name + ".json")),
        "end_to_end": end_to_end,
        "per_layer": [m for m in bench["per_layer"]
                      if applies(m, [e["name"] for e in end_to_end])],
    }


def require_devices(chips: int):
    """The first ``chips`` accelerator devices; :class:`NoChip` if JAX
    runs on the CPU or sees fewer."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < chips:
        raise NoChip(f"this cell needs {chips} accelerator chip(s); JAX "
                     f"found {len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


def peak_flops(device_kind: str) -> float:
    """bf16 peak of one chip, from ``bench/peaks.json``; an unknown device
    is an error, not a default."""
    peaks = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peak for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(peaks)}")
    return float(peaks[device_kind]["bf16_flops_per_s"])


# ---------------------------------------------------------------------------
# building the program's objects from the configuration file
# ---------------------------------------------------------------------------

def _only_fields(cls, values: Dict) -> Dict:
    names = {f.name for f in dataclasses.fields(cls)}
    return {k: v for k, v in values.items() if k in names}


def program_configs(config: Dict[str, Any], seed: int):
    """(ModelConfig, TrainConfig) for the program, from the file's
    ``model`` and ``train`` sections (keys the program does not know, such
    as the reference's ``group_tokens``, are the reference's)."""
    from repro.config import (ModelConfig, MoEConfig, OptimizerConfig,
                              RecoveryConfig, SSMConfig, TrainConfig)
    m = dict(config["model"])
    m["moe"] = MoEConfig(**_only_fields(MoEConfig, m.get("moe", {})))
    m["ssm"] = SSMConfig(**_only_fields(SSMConfig, m.get("ssm", {})))
    m = ModelConfig(name=config["name"], **_only_fields(
        ModelConfig, {k: v for k, v in m.items() if k != "name"}))
    t = config["train"]
    opt = dict(t["optimizer"])
    opt["betas"] = tuple(opt["betas"])
    tcfg = TrainConfig(
        global_batch=t["global_batch"], microbatch=t["microbatch"],
        seq_len=t["seq_len"], steps=WARM_STEPS + 10 ** 6,
        eval_every=10 ** 9, fuse_window=t["fuse_window"], seed=seed,
        optimizer=OptimizerConfig(**opt),
        recovery=RecoveryConfig(num_stages=t["num_stages"],
                                **t["recovery"]))
    return m, tcfg


# ---------------------------------------------------------------------------
# the probe: warm-up checks and the end of the window
# ---------------------------------------------------------------------------

class Probe:
    """Instance-level hooks on the trainer's strategy.

    ``after_step`` (a no-op in the merge strategies, called once per
    window on the drained state) records the first window's state for
    the correctness check, opens the measured window at wall step
    ``warm`` and raises :class:`WindowClosed` at the first window
    boundary ``seconds`` after that.  ``on_failure`` wraps the strategy's
    own recovery during the warm-up only, recording the state before and
    after, and unhooks itself after the last warm-up failure.  The time
    spent in the checks is kept apart (``check_s``) and left out of
    ``setup_s``."""

    def __init__(self, strategy, config: Dict, seed: int, warm: int,
                 seconds: float, on_open):
        self.strategy = strategy
        self.config = config
        self.seed = seed
        self.warm = warm
        self.seconds = seconds
        self.on_open = on_open
        self.training: Optional[Dict] = None
        self.recoveries: List[Dict[str, float]] = []
        self.check_s = 0.0
        self.hist = None
        self.opened: Optional[float] = None
        self._recover = strategy.on_failure
        strategy.on_failure = self.on_failure
        strategy.after_step = self.after_step

    def after_step(self, state, hist) -> None:
        from bench import check
        self.hist = hist
        step = state.effective_step
        if self.training is None:
            t0 = time.perf_counter()
            self.training = check.training_capture(
                state.params, state.opt_state.m, state.omegas,
                hist.loss[:step])
            self.check_s += time.perf_counter() - t0
        if step == self.warm:
            self.on_open()
            self.opened = time.perf_counter()
        elif step > self.warm and \
                time.perf_counter() - self.opened >= self.seconds:
            raise WindowClosed

    def on_failure(self, state, event):
        from bench import check
        post = self._recover(state, event)
        if event.wall_step <= self.warm:
            t0 = time.perf_counter()
            self.recoveries.append(check.recovery_capture(
                state, post, event.stage,
                self.config["train"]["num_stages"],
                self.config["train"]["recovery"]))
            self.check_s += time.perf_counter() - t0
        if event.wall_step >= self.warm:
            del self.strategy.on_failure       # the window runs unhooked
            self.opened = time.perf_counter()  # its first dispatch is next
        return post


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _compile_log():
    """Host times of every program compiled or loaded from the persistent
    cache, from JAX's own monitoring events."""
    import jax
    times: List[float] = []

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            times.append(time.perf_counter())

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            times.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return times


def _windows(spans: List[Dict], base: float, warm: int):
    """``[(wall_step, k, dispatch_start, drain_end)]`` of the measured
    window, in host seconds."""
    dispatch = [s for s in spans if s["name"] == "window_dispatch"]
    drain = [s for s in spans if s["name"] == "window_drain"]
    out = []
    for d, r in zip(dispatch, drain):
        if d["args"]["wall_step"] >= warm:
            out.append((d["args"]["wall_step"], d["args"]["k"],
                        base + d["ts_us"] / 1e6,
                        base + (r["ts_us"] + r["dur_us"]) / 1e6))
    return out


def run(spec: Dict[str, Any], *, seed: int, seconds: float, trace: bool,
        started: float, require_chip: bool = True) -> Dict[str, Any]:
    """Run the cell once; returns the result line as a dict."""
    sys.path.insert(0, SRC)
    import jax
    from repro import telemetry
    from repro.core.trainer import Trainer
    from repro.launch.compile_cache import configure_compile_cache
    from repro.models.model import build_model

    from bench import check, traffic

    devices = require_devices(spec["chips"]) if require_chip \
        else jax.devices()[:spec["chips"]]
    configure_compile_cache()
    # every program, however quick to compile, goes to the persistent
    # cache, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = _compile_log()
    rec = telemetry.Recorder(None, stream=False)
    telemetry.set_recorder(rec)
    base = time.perf_counter() - rec.now()

    config, tr = spec["config"], spec["traffic"]
    model_cfg, tcfg = program_configs(config, seed)
    train = config["train"]
    schedule = traffic.schedule(tr)
    trainer = Trainer(build_model(model_cfg), tcfg, schedule=schedule,
                      backend=train["backend"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    mark = {}

    def on_open():
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation(CLOCK_MARK):
                mark["perf"] = time.perf_counter()

    probe = Probe(trainer.strategy, config, seed, WARM_STEPS, seconds,
                  on_open)
    batches = traffic.token_batches(seed, train["global_batch"],
                                    train["seq_len"], model_cfg.vocab_size)
    try:
        trainer.run(batches)
        raise RuntimeError("the run ended before the window closed")
    except WindowClosed:
        pass
    finally:
        if trace_dir is not None and mark:
            jax.profiler.stop_trace()
    telemetry.set_recorder(None)
    memory_peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
                      for d in devices) if require_chip else 0

    windows = _windows(rec.spans, base, WARM_STEPS)
    opened, closed = windows[0][2], windows[-1][3]
    steps = sum(k for _, k, _, _ in windows)
    boundaries = [(windows[i + 1][2] - windows[i][3],
                   bool(schedule.at(windows[i + 1][0])))
                  for i in range(len(windows) - 1)]
    tokens_per_s = steps * train["global_batch"] * train["seq_len"] \
        / (closed - opened)
    hist_losses = probe.hist.loss[WARM_STEPS:WARM_STEPS + steps]
    failures = [g for g, failed in boundaries if failed]
    e2e = {"tokens_per_s": tokens_per_s,
           "recover_ms": 1e3 * sum(failures) / len(failures)
           if failures else None,
           "setup_s": opened - started - probe.check_s}

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    ctx = SimpleNamespace(
        boundaries=boundaries, steps=steps, window_s=closed - opened,
        tokens_per_s=tokens_per_s, chips=len(devices),
        compiles=sum(opened <= t <= closed for t in compiles),
        recoveries=[e["duration_s"] for e in rec.events
                    if e["kind"] == "recovery"
                    and opened <= base + e["t_s"] <= closed],
        flops_per_token=check.reference_module(config)
        .train_flops_per_token(config["model"], train["seq_len"]),
        peak_flops=peak_flops(devices[0].device_kind) if require_chip
        else None,
        trace=None)
    breakdown = None
    if trace_dir is not None:
        from bench import devtrace
        ctx.trace, breakdown = devtrace.summarize(
            devtrace.extract(trace_dir, [CLOCK_MARK]), CLOCK_MARK,
            mark["perf"], rec.spans, base, windows)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.window_s

    # the correctness check runs once the program's state is freed
    training, recoveries = probe.training, probe.recoveries
    del trainer, probe
    gc.collect()
    reference = check.reference_capture(config, seed, len(training["loss"]))
    values = check.readings(training, reference)
    worst = sorted(check.leaf_differences(training, reference).items(),
                   key=lambda kv: -kv[1])[:5]
    print("grad_diff worst leaves: " + ", ".join(
        f"{k} {v:.4g}" for k, v in worst), file=sys.stderr)
    for key in ("recovery_gap", "untouched_moved", "lost_moments",
                "lr_boost_gap"):
        if recoveries:
            values[key] = float(np.max([r[key] for r in recoveries]))
    checks = check.verdict(values, spec["limits"])

    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            value = importlib.import_module(
                f"bench.metrics.{m['name']}").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"] if e2e[m["name"]] is not None}
    result = {"correct": check.is_correct(checks)
              and bool(np.all(np.isfinite(hist_losses))),
              "attempted": steps,
              "failed": int(np.sum(~np.isfinite(hist_losses))),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
