"""Run one cell once under the profiler, as ``bench.run --trace 1`` does,
and read what the harness does not yet hand its per-layer readers: device
time per named scope, the device work and programs of each failure
boundary, and idle gaps named by the program's innermost span, all mapped
onto the trace through the recorder's anchors (``bench/devscope.py``).

    python3 -m bench.scoped_run --workload <name> --seed <n> --seconds <s> \
        [--dump <dir>]

The last line is ``{"result": <bench.run's result>, "layers": {...}}``.
``--dump`` also writes the reduced trace (operations with their op names,
modules, anchors) and the recorder's spans as gzipped JSON, so that the
reduction can be read again without the chip.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import collections  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402


def layers(raw, spans, events, warm):
    """The readings of :mod:`bench.devscope` for one traced run."""
    import importlib
    from bench import devscope, devtrace
    failed = collections.Counter(e["wall_step"] for e in events
                                 if e["kind"] == "failure")
    scoped = devscope.summarize(raw, spans, warm, failed)
    if scoped is None:
        return {"scoped": None}
    windows, bounds = devscope.windows_and_boundaries(spans, warm, failed)
    steps = sum(k for _, k, _, _ in windows)
    window_s = windows[-1][3] - windows[0][2]
    ctx = SimpleNamespace(scoped=scoped, steps=steps)
    out = {}
    for name in ("adam_device_ms", "moe_dispatch_ms", "attention_ms",
                 "recovery_device_ms", "recovery_programs"):
        out[name] = importlib.import_module(f"bench.metrics.{name}").read(ctx)
    out["scope_ms_per_step"] = {
        s: devscope.scope_ms_per_step(ctx, s)
        for s in sorted(scoped["scope_busy_s"])}
    out["window_busy_ms_per_step"] = 1e3 * scoped["window_busy_s"] / steps
    out["scoped_share"] = scoped["scoped_busy_s"] / scoped["window_busy_s"]
    # device time by innermost scope, each operation's own time (a loop's
    # less what it runs), which sums to the windows' busy time; the
    # operations under no scope, the largest first
    ns = devscope.clock_map(raw["anchors"])
    starts = [ns(a) for _, _, a, _ in windows]
    ends = [ns(b) for _, _, _, b in windows]
    plane = sorted(raw["ops"])[0]
    names = {tuple(op[:3]): path for op, path in
             zip(raw["ops"][plane], raw["op_scopes"][plane])}
    own, rest = collections.Counter(), collections.Counter()
    for name, s, d, t in devtrace.self_times(raw["ops"][plane]):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= ends[i]:
            continue
        path = names[(name, s, d)]
        found = devscope.scopes_of(path)
        own[found[-1] if found else ""] += t
        if not found:
            rest[(devtrace.short_name(name), path[:120])] += t
    total = sum(own.values())
    out["own_ms_per_step"] = {k or "(none)": v / 1e6 / steps
                              for k, v in own.most_common()}
    out["scoped_share_without_window_loop"] = 1 - (
        own[""] + own["window_loop"]) / total
    out["unscoped_top"] = [[k[0], k[1], v / 1e9]
                           for k, v in rest.most_common(15)]
    fb = scoped["failure_boundaries"]
    out["failure_boundaries"] = len(fb)
    if fb:
        out["boundary_span_cover_min"] = min(b["covered_s"] / b["host_s"]
                                             for b in fb)
        out["boundary_device_plus_named_idle_max_error"] = max(
            abs(b["device_s"] + sum(t for k, t in b["idle_s"].items()
                                    if k != "host_gap") - b["host_s"])
            / b["host_s"] for b in fb)
        idle = collections.Counter()
        for b in fb:
            idle.update(b["idle_s"])
        out["boundary_idle_ms_by_span"] = {
            k: 1e3 * v / len(fb) for k, v in idle.most_common()}
        out["boundary_host_ms"] = 1e3 * sum(b["host_s"] for b in fb) / len(fb)
    # host time per span name inside the failure boundaries, per failure
    per_span = collections.Counter()
    for _, failures, a, b in bounds:
        if failures:
            for s in spans:
                t0 = s["ts_us"] / 1e6
                t1 = t0 + s["dur_us"] / 1e6
                if a <= t0 and t1 <= b:
                    per_span[s["name"]] += (t1 - t0) / failures
    nfail = sum(1 for _, f, _, _ in bounds if f)
    out["boundary_span_ms"] = {k: 1e3 * v / max(nfail, 1)
                               for k, v in per_span.most_common()}
    offsets = scoped["anchor_offsets_ns"]
    inside = [o for (t, _), o in zip(raw["anchors"], offsets)
              if windows[0][2] <= t <= windows[-1][3]]
    out["anchor_offset_spread_ns"] = max(inside) - min(inside) if inside \
        else None
    out["idle_gaps"] = scoped["idle_gaps"]
    # the end-to-end metrics of this traced run, read as bench.run reads
    # them without the profiler
    tokens = next(e["tokens_per_step"] for e in events
                  if e["kind"] == "run_start")
    out["traced_tokens_per_s"] = steps * tokens / window_s
    gaps = [b - a for _, f, a, b in bounds if f]
    out["traced_recover_ms"] = 1e3 * sum(gaps) / len(gaps) if gaps else None
    rec_events = [e["duration_s"] for e in events if e["kind"] == "recovery"
                  and windows[0][2] <= e["t_s"] <= windows[-1][3]]
    out["traced_recovery_span_ms"] = 1e3 * sum(rec_events) / len(
        rec_events) if rec_events else None
    return out


def dump(path, raw, spans, events):
    """The reduced trace and the recorder's record, gzipped JSON."""
    table, index, ops = [], {}, {}
    for plane, plane_ops in raw["ops"].items():
        rows = []
        for (name, s, d), op_name in zip(plane_ops, raw["op_scopes"][plane]):
            key = (name, op_name)
            if key not in index:
                index[key] = len(table)
                table.append([name, op_name])
            rows.append([index[key], s, d])
        ops[plane] = rows
    doc = {"names": table, "ops": ops, "modules": raw["modules"],
           "anchors": raw["anchors"], "marks": raw["marks"],
           "spans": spans, "events": events}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)


def load_dump(path):
    """``(raw, spans, events)`` back from :func:`dump`."""
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    names = doc["names"]
    raw = {"ops": {p: [[names[i][0], s, d] for i, s, d in rows]
                   for p, rows in doc["ops"].items()},
           "op_scopes": {p: [names[i][1] for i, _, _ in rows]
                         for p, rows in doc["ops"].items()},
           "modules": doc["modules"], "anchors": doc["anchors"],
           "marks": doc["marks"]}
    return raw, doc["spans"], doc["events"]


def measure(spec, *, seed, seconds, started, dump_dir="",
            require_chip=True):
    """``harness.run(..., trace=True)`` with the recorder and the reduced
    trace kept: ``(result, layers)``."""
    from bench import devscope, devtrace, harness
    sys.path.insert(0, harness.SRC)
    from repro import telemetry
    got = {}
    set_recorder, extract = telemetry.set_recorder, devtrace.extract

    def keep_recorder(rec):
        if rec is not None:
            got["rec"] = rec
        return set_recorder(rec)

    def keep_trace(trace_dir, marks):
        got["raw"] = devscope.extract(trace_dir, marks)
        return got["raw"]

    telemetry.set_recorder, devtrace.extract = keep_recorder, keep_trace
    try:
        result = harness.run(spec, seed=seed, seconds=seconds, trace=True,
                             started=started, require_chip=require_chip)
    finally:
        telemetry.set_recorder, devtrace.extract = set_recorder, extract
    rec, raw = got["rec"], got["raw"]
    if dump_dir:
        dump(os.path.join(dump_dir, f"{spec['name']}.{seed}.json.gz"),
             raw, rec.spans, rec.events)
    return result, layers(raw, rec.spans, rec.events, harness.WARM_STEPS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", default="",
                    help="directory for the gzipped reduced trace")
    args = ap.parse_args(argv)

    from bench import harness
    try:
        result, out = measure(harness.load_workload(args.workload),
                              seed=args.seed, seconds=args.seconds,
                              started=STARTED, dump_dir=args.dump)
    except harness.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 3
    print(json.dumps({"result": result, "layers": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
