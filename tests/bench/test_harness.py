"""The benchmark's harness on the CPU: the failure schedule, finding cells
and their files by name, the trace reduction, the FLOP counts, and the
refusal to measure without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import devtrace, harness, traffic
from bench.check import reference_module

ROOT = harness.ROOT
DATA = os.path.join(os.path.dirname(__file__), "data")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the failure schedule
# ---------------------------------------------------------------------------

def test_periodic_schedule_rotates_stages_every_fail_every_steps():
    s = traffic.PeriodicFailures(4, [1, 2, 0, 3])
    got = {step: s.at(step) for step in range(0, 40) if s.at(step)}
    assert got == {4: [1], 8: [2], 12: [0], 16: [3], 20: [1], 24: [2],
                   28: [0], 32: [3], 36: [1]}
    assert traffic.PeriodicFailures(0).at(8) == []


@pytest.mark.parametrize("name", ["churn4", "steady"])
def test_failures_come_from_the_traffic_file_alone(name):
    """The schedule is built from the traffic file; the seed only feeds
    the token stream, so every seed sees the same failures."""
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        mix = json.load(f)
    first = [traffic.schedule(mix).at(step) for step in range(200)]
    again = [traffic.schedule(mix).at(step) for step in range(200)]
    assert first == again


def test_token_batches_follow_the_seed():
    a = traffic.token_batches(2 ** 31 + 17, 4, 16, 100)
    b = traffic.token_batches(2 ** 31 + 17, 4, 16, 100)
    c = traffic.token_batches(5, 4, 16, 100)
    for _ in range(3):
        x, y, z = next(a), next(b), next(c)
        assert (x["tokens"] == y["tokens"]).all()
        assert not (x["tokens"] == z["tokens"]).all()
        assert (x["tokens"][:, 1:] == x["labels"][:, :-1]).all()
    rows = [tuple(r) for _ in range(3) for r in next(a)["tokens"]]
    assert len(set(rows)) == len(rows)


@pytest.mark.parametrize("mix,window", [("churn4", 4), ("steady", 8)])
def test_trainer_windows_are_exactly_fail_every(mix, window):
    """Between failures every fused window has the same size: 4 under
    churn4, the trainer's full 8 without failures."""
    from tiny import tiny_config
    from repro.core.trainer import Trainer
    from repro.models.model import build_model
    with open(os.path.join(ROOT, "bench", "traffic", mix + ".json")) as f:
        schedule = traffic.schedule(json.load(f))
    config = tiny_config("granite-moe-3b.L4")
    model_cfg, tcfg = harness.program_configs(config, seed=3)
    trainer = Trainer(build_model(model_cfg), tcfg, schedule=schedule)
    sizes = {trainer._window_size(w, w, 10 ** 6) for w in range(0, 400,
                                                                window)}
    assert sizes == {window}


# ---------------------------------------------------------------------------
# everything is found by name
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_resolves_its_files(cell):
    spec = harness.load_workload(cell)
    config = spec["config"]
    ref = reference_module(config)
    for fn in ("init", "loss", "train_flops_per_token"):
        assert callable(getattr(ref, fn))
    assert {m["name"] for m in spec["end_to_end"]} >= {"tokens_per_s",
                                                        "setup_s"}
    assert spec["per_layer"], cell
    assert set(spec["limits"]) >= {"grad_gap", "grad_diff", "change_gap",
                                   "omega_gap"}
    for m in spec["per_layer"]:
        module = __import__(f"bench.metrics.{m['name']}",
                            fromlist=["read"])
        assert callable(module.read)


def test_a_new_cell_is_only_new_files(tmp_path):
    """A traffic mix, a cell, its limits and a per-layer metric added as
    files are found without an edit to any file already there."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _bench()
    bench["workloads"].append({
        "name": "granite-moe-3b.L4.churn8", "config": "granite-moe-3b.L4",
        "traffic": "churn8", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "windows_in_window", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "trainer loop",
        "moves": "tokens_per_s", "workloads": ["granite-moe-3b.L4.churn8"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench" / "traffic" / "churn8.json").write_text(
        json.dumps({"fail_every": 8, "rotation": [2, 1]}))
    shutil.copy(tmp_path / "bench" / "limits" / "granite-moe-3b.L4.churn4.json",
                tmp_path / "bench" / "limits" / "granite-moe-3b.L4.churn8.json")
    (tmp_path / "bench" / "metrics" / "windows_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.boundaries) + 1)\n")
    code = (
        "import json; from types import SimpleNamespace\n"
        "from bench import harness, traffic\n"
        "spec = harness.load_workload('granite-moe-3b.L4.churn8')\n"
        "names = [m['name'] for m in spec['per_layer']]\n"
        "mod = __import__('bench.metrics.windows_in_window', fromlist=['r'])\n"
        "s = traffic.schedule(spec['traffic'])\n"
        "print(json.dumps([names, mod.read(SimpleNamespace(boundaries=[1, 2])),"
        " s.at(8), s.at(16)]))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         capture_output=True, text=True, check=True)
    names, value, at8, at16 = json.loads(out.stdout.strip().splitlines()[-1])
    assert "windows_in_window" in names
    assert value == 3.0 and at8 == [2] and at16 == [1]


def test_every_per_layer_metric_has_a_reader_and_a_layer():
    bench = _bench()
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py")), m["name"]
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_unknown_device_has_no_peak():
    assert harness.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        harness.peak_flops("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# the trace reduction, on a small recorded trace
# ---------------------------------------------------------------------------

def _small_trace():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        return json.load(f)


def test_busy_time_is_the_union_of_device_ops():
    raw = _small_trace()
    ops = raw["ops"]["/device:TPU:0"]
    merged = devtrace.merged(devtrace.device_intervals(ops), 1500, 13000)
    assert merged == [(2000, 6000), (9000, 10000), (12500, 13000)]
    assert devtrace.busy_ns(merged) == 5500
    # the loop's own time is what the two operations inside it leave
    assert devtrace.top_ops(ops, 1500, 13000) == [
        ["fusion.adam f32[4,8]", 3.5e-06], ["dot.expert bf16[2,8]", 1e-06],
        ["while.7 s32[]", 5e-07], ["copy", 5e-07]]


def test_idle_gaps_are_named_by_the_host_span_over_them():
    raw = _small_trace()
    merged = devtrace.merged(devtrace.device_intervals(
        raw["ops"]["/device:TPU:0"]), 1500, 13000)
    labels = [("window_drain", 5500, 9500), ("recovery", 11000, 11500)]
    assert devtrace.idle_gaps(merged, 1500, 13000, labels) == [
        ["window_drain", 3e-06], ["recovery", 2.5e-06], ["host_gap", 5e-07]]


def test_summary_maps_host_time_through_the_clock_mark():
    """The mark began at host second 10.0 and trace time 1000 ns, so host
    second 10.0000005 is trace time 1500 ns.  Two dispatched windows span
    trace time 1500..8000 and 9000..13000 ns; the measured window is
    1500..13000 ns."""
    raw = _small_trace()
    windows = [(16, 4, 10.0000005, 10.000007), (20, 4, 10.000008, 10.000012)]
    spans = [{"name": "window_drain", "ts_us": 4.5, "dur_us": 4.0,
              "args": {}}]
    summary, breakdown = devtrace.summarize(
        raw, "bench.clock", 10.0, spans, 10.0, windows)
    # in the window TPU:0 is busy 5500 ns and TPU:1 5000 ns
    assert summary["busy_s"] == pytest.approx(5.25e-06)
    # inside the two dispatched windows TPU:0 5500 ns, TPU:1 3000 + 1000
    assert summary["window_busy_s"] == pytest.approx(4.75e-06)
    assert breakdown["idle_gaps"][0] == ["window_drain", pytest.approx(3e-6)]
    assert [g[0] for g in breakdown["idle_gaps"]] == [
        "window_drain", "host_gap", "host_gap"]
    assert breakdown["device_ops"][0][0] == "fusion.adam f32[4,8]"


# ---------------------------------------------------------------------------
# FLOP counts against hand-worked numbers
# ---------------------------------------------------------------------------

def test_granite_flops_per_token_by_hand():
    config = harness.load_workload("granite-moe-3b.L4.churn4")["config"]
    # per layer: attention 1536*1536*2 + 2*1536*512 = 6,291,456; router
    # 1536*40 = 61,440; 8 active experts * 3 * 1536 * 512 = 18,874,368;
    # 4 layers + tied head 49155*1536 = 75,502,080 -> 176,411,136 params
    # attention scores: 4 layers * 24 heads * 2 * 64 * 512 = 6,291,456
    want = 3 * (2 * 176_411_136 + 6_291_456)
    assert reference_module(config).train_flops_per_token(
        config["model"], config["train"]["seq_len"]) == want


def test_mamba2_flops_per_token_by_hand():
    with open(os.path.join(ROOT, "bench", "configs",
                           "mamba2-1.3b.L8.json")) as f:
        config = json.load(f)
    # per layer: in-projection 2048 * (2*4096 + 2*128 + 64) = 17,432,576,
    # out-projection 4096 * 2048 = 8,388,608; 8 layers + tied head
    # 50280 * 2048 = 102,973,440 -> 309,542,912 params
    # per layer SSD: 64*128 + 64*64*64 + 4*64*64*128 + 2*4*4352
    #   = 8,192 + 262,144 + 2,097,152 + 34,816 = 2,402,304
    want = 3 * (2 * 309_542_912 + 8 * 2_402_304)
    assert reference_module(config).train_flops_per_token(
        config["model"], config["train"]["seq_len"]) == want


# ---------------------------------------------------------------------------
# no chip, no measurement
# ---------------------------------------------------------------------------

def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "granite-moe-3b.L4.churn4", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 3
    assert "no chip" in out.stderr
    assert out.stdout.strip() == ""


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    """A directory that holds BENCHMARK.json and the benchmark's own
    directories, and not the program, gives no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in _bench()["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "granite-moe-3b.L4.churn4", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
