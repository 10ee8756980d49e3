"""From the JAX profiler's trace to device busy time, idle share and the
breakdown of a window.

:func:`extract` reads an ``.xplane.pb`` into plain data: the device
operations of each ``/device:`` plane (its ``XLA Ops`` line) and the start
of each host annotation the benchmark set to tie the profiler's clock to
its own.  Everything after that works on that plain data, so the tests
check it on a small recorded trace kept beside them.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def extract(trace_dir: str, marks: Sequence[str]) -> Dict:
    """``{"ops": {plane: [[name, start_ns, dur_ns], ...]}, "marks": {name:
    start_ns}}`` from the one ``.xplane.pb`` the profiler wrote under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}: {files}")
    data = ProfileData.from_file(files[0])
    ops: Dict[str, List] = {}
    found: Dict[str, float] = {}
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                ops[plane.name] = [[e.name, e.start_ns, e.duration_ns]
                                   for e in line.events]
            elif not device:
                for e in line.events:
                    if e.name in marks and e.name not in found:
                        found[e.name] = e.start_ns
    if not ops or len(found) != len(marks):
        layout = [(p.name, [ln.name for ln in p.lines]) for p in data.planes]
        raise RuntimeError(f"trace has no {OPS_LINE!r} device line or lacks "
                           f"marks {sorted(set(marks) - set(found))}: "
                           f"{layout}")
    return {"ops": ops, "marks": found}


def merged(intervals: Sequence[Interval], lo: float, hi: float
           ) -> List[Interval]:
    """The union of ``intervals`` clipped to [lo, hi], sorted, disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def device_intervals(ops: List) -> List[Interval]:
    return [(s, s + d) for _, s, d in ops]


def short_name(hlo: str) -> str:
    """``%fusion.30 = (f32[4,40,512,1536]{...}, ...) fusion(...)`` ->
    ``fusion.30 f32[4,40,512,1536]``: the instruction and its first result
    shape."""
    if " = " not in hlo:
        return hlo[:80]
    name, rest = hlo.split(" = ", 1)
    shape = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0].rstrip(",)")
    return f"{name.lstrip('%')} {shape}"[:80]


def self_times(ops: List) -> List[list]:
    """``[name, start, dur, self]`` per operation: a loop or call on the
    ops line encloses the operations it runs, and its own time is what
    they leave uncovered."""
    out: List[list] = []
    stack: List[list] = []
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] + stack[-1][2] <= s:
            stack.pop()
        rec = [name, s, d, d]
        if stack:
            stack[-1][3] -= d
        stack.append(rec)
        out.append(rec)
    return out


def top_ops(ops: List, lo: float, hi: float, n: int = 10) -> List[list]:
    """The ``n`` operations (by :func:`short_name`) with the most device
    seconds of their own among those that start in [lo, hi]."""
    total: Dict[str, float] = {}
    for name, s, _d, own in self_times(ops):
        if lo <= s <= hi:
            key = short_name(name)
            total[key] = total.get(key, 0.0) + own
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(busy: Sequence[Interval], lo: float, hi: float,
              labels: Sequence[Tuple[str, float, float]], n: int = 10
              ) -> List[list]:
    """The ``n`` longest stretches of [lo, hi] with no device operation,
    each named by the host span (``labels``: name, start, end) that
    covers its middle, or ``host_gap`` where none does."""
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    out = []
    for a, b in gaps[:n]:
        mid = 0.5 * (a + b)
        name = next((lab for lab, s, e in labels if s <= mid <= e),
                    "host_gap")
        out.append([name, (b - a) / 1e9])
    return out


def summarize(raw: Dict, mark: str, mark_perf: float, spans: List[Dict],
              base: float, windows) -> Tuple[Dict, Dict]:
    """Busy seconds of the measured window and of its dispatched windows
    (each averaged over the device planes), and the breakdown of the
    first device.  ``mark_perf`` is the host clock (seconds) at which the
    annotation ``mark`` began; ``spans`` are the program's telemetry spans
    (microseconds from ``base``); ``windows`` is ``[(wall_step, k,
    dispatch_start, drain_end)]`` in host seconds."""
    origin = raw["marks"][mark] - mark_perf * 1e9

    def ns(t):
        return origin + t * 1e9

    lo, hi = ns(windows[0][2]), ns(windows[-1][3])
    labels = [(s["name"], ns(base + s["ts_us"] / 1e6),
               ns(base + (s["ts_us"] + s["dur_us"]) / 1e6))
              for s in spans if s["name"] in
              ("window_dispatch", "window_drain", "recovery")]
    planes = sorted(raw["ops"])
    busy, dispatched = [], []
    for plane in planes:
        intervals = device_intervals(raw["ops"][plane])
        busy.append(busy_ns(merged(intervals, lo, hi)))
        dispatched.append(sum(busy_ns(merged(intervals, ns(a), ns(b)))
                              for _, _, a, b in windows))
    first = raw["ops"][planes[0]]
    summary = {"busy_s": sum(busy) / len(busy) / 1e9,
               "window_busy_s": sum(dispatched) / len(dispatched) / 1e9}
    breakdown = {
        "device_ops": top_ops(first, lo, hi),
        "idle_gaps": idle_gaps(merged(device_intervals(first), lo, hi),
                               lo, hi, labels)}
    return summary, breakdown
