"""From the JAX profiler's trace to device time per named scope, to the
device work and the programs of each failure boundary, and to idle gaps
named by the program's own spans.

:func:`extract` reads an ``.xplane.pb`` into plain data: what
:func:`bench.devtrace.extract` gives (the device operations as ``[name,
start_ns, dur_ns]``, the marks), and beside it each operation's op name
(the :data:`OP_NAME_STAT` stat of its event metadata), the device's
``XLA Modules`` events (one a program launch) and the recorder's anchors
(``repro.telemetry.anchor``: the recorder's clock in seconds beside the
profiler's in nanoseconds).  :func:`summarize` maps the program's spans
onto the trace through the nearest anchor and reduces.  Everything after
:func:`extract` works on plain data, so the tests check it on a small
trace kept beside them; data without the new keys reduces to nothing.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bench.devtrace import OPS_LINE, busy_ns, device_intervals, merged

MODULES_LINE = "XLA Modules"
#: the stat of an ``XLA Ops`` event that holds the HLO op-name metadata
OP_NAME_STAT = "tf_op"
#: the annotation ``repro.telemetry.anchor`` writes, and its clock argument
ANCHOR = "repro.anchor"
ANCHOR_STAT = "t_s"
#: the program's named scopes (``jax.named_scope``) on the fused step
SCOPES = ("window_loop", "embed", "param_cast", "layer_scan", "attention",
          "moe_dispatch", "moe_experts", "logits_loss", "stage_omegas",
          "tower_swap", "adam", "ssd_scan")

_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")

Interval = Tuple[float, float]


def extract(trace_dir: str, marks: Sequence[str] = ()) -> Dict:
    """``{"ops", "marks"}`` as :func:`bench.devtrace.extract` gives them,
    plus ``"op_scopes"`` ({plane: [op name, ...]} parallel to ``ops``),
    ``"modules"`` ({plane: [[name, start_ns, dur_ns], ...]}) and
    ``"anchors"`` ([[recorder_s, trace_ns], ...]), from the one
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}: {files}")
    with open(files[0], "rb") as f:
        blob = f.read()
    names = OpNames(blob)
    data = ProfileData.from_serialized_xspace(blob)
    out: Dict = {"ops": {}, "marks": {}, "op_scopes": {}, "modules": {},
                 "anchors": []}
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = {line.name: line for line in plane.lines}
        if device and OPS_LINE in lines:
            ops = [[e.name, e.start_ns, e.duration_ns]
                   for e in lines[OPS_LINE].events]
            out["ops"][plane.name] = ops
            out["op_scopes"][plane.name] = names.of(plane.name, ops)
            out["modules"][plane.name] = [
                [e.name, e.start_ns, e.duration_ns]
                for e in lines[MODULES_LINE].events] \
                if MODULES_LINE in lines else []
        elif not device:
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        t = dict(e.stats).get(ANCHOR_STAT)
                        if t is not None:
                            out["anchors"].append([float(t), e.start_ns])
                    elif e.name in marks and e.name not in out["marks"]:
                        out["marks"][e.name] = e.start_ns
    out["anchors"].sort()
    return out


# ---------------------------------------------------------------------------
# op names: the device plane keeps each operation's op-name metadata as the
# ``tf_op`` stat of the operation's event *metadata*; ``ProfileData`` gives
# only an event's own stats, so the metadata is read from the serialized
# trace itself
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None):
    """``(field, value)`` of the protobuf message in ``buf[lo:hi]``: an
    int for a scalar, a ``(start, end)`` span for a length-delimited
    field."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _field(buf: bytes, span: Tuple[int, int], number: int):
    """The last value of field ``number`` in a message, or ``None``."""
    value = None
    for field, v in _fields(buf, *span):
        if field == number:
            value = v
    return value


# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MD_NAME, _MD_DISPLAY, _MD_STATS = 2, 4, 5
_STAT_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_ENTRY_KEY, _ENTRY_VALUE = 1, 2


class OpNames:
    """The op-name metadata (``jit(f)/while/body/adam/sub``) of the device
    operations in one serialized ``XSpace``, by plane and by the event's
    name or display name."""

    def __init__(self, blob: bytes):
        self.by_plane: Dict[str, Dict[str, str]] = {}
        for field, span in _fields(blob):
            if field == _SPACE_PLANES:
                self._plane(blob, span)

    def _plane(self, buf: bytes, span) -> None:
        name, event_md, stat_names = "", [], {}
        for field, v in _fields(buf, *span):
            if field == _PLANE_NAME:
                name = _text(buf, v)
            elif field == _PLANE_EVENT_MD:
                event_md.append(_field(buf, v, _ENTRY_VALUE))
            elif field == _PLANE_STAT_MD:
                md = _field(buf, v, _ENTRY_VALUE)
                stat_names[_field(buf, v, _ENTRY_KEY)] = _text(
                    buf, _field(buf, md, _MD_NAME))
        if not name.startswith("/device:"):
            return
        ops = self.by_plane.setdefault(name, {})
        for md in event_md:
            op = None
            for field, v in _fields(buf, *md):
                if field != _MD_STATS or \
                        stat_names.get(_field(buf, v, _STAT_ID)) != \
                        OP_NAME_STAT:
                    continue
                value = _field(buf, v, _STAT_STR)
                op = _text(buf, value) if value is not None else \
                    stat_names.get(_field(buf, v, _STAT_REF), "")
            if op is not None:
                for key in (_MD_NAME, _MD_DISPLAY):
                    text = _field(buf, md, key)
                    if text is not None:
                        ops[_text(buf, text)] = op

    def of(self, plane: str, ops: List) -> List[str]:
        """The op name of each of ``ops`` (``[name, start, dur]``) on
        ``plane``; ``""`` where the trace has none."""
        names = self.by_plane.get(plane, {})
        return [names.get(name, "") for name, _, _ in ops]


# ---------------------------------------------------------------------------
# the clock: recorder seconds -> trace nanoseconds through the anchors
# ---------------------------------------------------------------------------

def clock_map(anchors: Sequence[Sequence[float]]
              ) -> Callable[[float], float]:
    """Map a recorder time (seconds) to trace time (ns) through the anchor
    nearest to it on the recorder's clock."""
    ts = [a[0] for a in anchors]
    offsets = anchor_offsets_ns(anchors)
    if not ts:
        raise ValueError("the trace holds no anchor")

    def ns(t: float) -> float:
        i = bisect.bisect_left(ts, t)
        if i == len(ts) or (i > 0 and t - ts[i - 1] <= ts[i] - t):
            i -= 1
        return t * 1e9 + offsets[i]

    return ns


def anchor_offsets_ns(anchors: Sequence[Sequence[float]]) -> List[float]:
    """Each anchor's trace time less its recorder time, in ns: constant
    where the two clocks run at one rate."""
    return [a[1] - a[0] * 1e9 for a in anchors]


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------

def scope_path(op_name: str) -> List[str]:
    """The components of an HLO op name with the transformations around
    them taken off: ``jit(f)/while/body/transpose(jvp(attention))/dot``
    -> ``[f, while, body, attention, dot]``."""
    out = []
    for comp in op_name.split("/"):
        m = _WRAPPED.match(comp)
        while m:
            comp = m.group(1)
            m = _WRAPPED.match(comp)
        if comp:
            out.append(comp)
    return out


def scopes_of(op_name: str) -> List[str]:
    """The program's scopes (:data:`SCOPES`) on the path of ``op_name``."""
    return [c for c in scope_path(op_name) if c in SCOPES]


def scoped_busy_ns(ops: List, op_scopes: List[str],
                   windows: Sequence[Interval]) -> Dict[str, float]:
    """Per scope, the union of the device intervals of the operations
    whose innermost scope it is (``layer_scan`` keeps only the scan's own
    operations, not those of the layers it runs), clipped to ``windows``
    (trace ns); ``"*"`` is the union over every scoped operation and
    ``""`` that of all operations."""
    by: Dict[str, List[Interval]] = {"*": [], "": []}
    for (_name, s, d), path in zip(ops, op_scopes):
        iv = (s, s + d)
        by[""].append(iv)
        found = scopes_of(path)
        if found:
            by["*"].append(iv)
            by.setdefault(found[-1], []).append(iv)
    return {scope: sum(busy_ns(merged(ivs, lo, hi)) for lo, hi in windows)
            for scope, ivs in by.items()}


# ---------------------------------------------------------------------------
# spans on the trace's clock
# ---------------------------------------------------------------------------

def windows_and_boundaries(spans: List[Dict], warm: int,
                           failed: Dict[int, int]):
    """The dispatched windows from wall step ``warm`` on, as ``[(wall_step,
    k, dispatch_start_s, drain_end_s)]`` on the recorder's clock, and the
    boundaries between them as ``[(wall_step, failures, start_s, end_s)]``
    (``failed``: failures per wall step)."""
    dispatch = [s for s in spans if s["name"] == "window_dispatch"]
    drain = [s for s in spans if s["name"] == "window_drain"]
    windows = [(d["args"]["wall_step"], d["args"]["k"], d["ts_us"] / 1e6,
                (r["ts_us"] + r["dur_us"]) / 1e6)
               for d, r in zip(dispatch, drain)
               if d["args"]["wall_step"] >= warm]
    bounds = [(b[0], failed.get(b[0], 0), a[3], b[2])
              for a, b in zip(windows, windows[1:])]
    return windows, bounds


def covered_s(spans: List[Dict], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] (recorder clock) that some span covers."""
    return busy_ns(merged([(s["ts_us"] / 1e6, (s["ts_us"] + s["dur_us"])
                            / 1e6) for s in spans], lo, hi))


def innermost(spans_ns: Sequence[Tuple[str, float, float]], t: float
              ) -> Optional[str]:
    """The shortest span (name, start, end) that covers ``t``."""
    best = None
    for name, s, e in spans_ns:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else None


def idle_stretches(busy: Sequence[Interval], lo: float, hi: float
                   ) -> List[Interval]:
    """The stretches of [lo, hi] that ``busy`` (merged) leaves."""
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def attribute(a: float, b: float,
              spans_ns: Sequence[Tuple[str, float, float]]
              ) -> Dict[str, float]:
    """[a, b] split among the innermost spans over each of its parts, or
    ``host_gap`` where no span covers a part."""
    near = [sp for sp in spans_ns if sp[2] > a and sp[1] < b]
    cuts = sorted({a, b} | {x for _, s, e in near for x in (s, e)
                            if a < x < b})
    out: Dict[str, float] = {}
    for x, y in zip(cuts, cuts[1:]):
        name = innermost(near, 0.5 * (x + y)) or "host_gap"
        out[name] = out.get(name, 0.0) + (y - x)
    return out


def named_idle(busy: Sequence[Interval], lo: float, hi: float,
               spans_ns: Sequence[Tuple[str, float, float]],
               n: Optional[int] = None
               ) -> List[Tuple[str, float, Dict[str, float]]]:
    """The idle stretches of [lo, hi] (ns), the ``n`` longest first where
    ``n`` is given: each as ``(name, length, parts)``, ``parts`` its
    :func:`attribute` and ``name`` the span that holds most of it."""
    gaps = idle_stretches(busy, lo, hi)
    if n is not None:
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    near = [sp for sp in spans_ns if sp[2] >= lo and sp[1] <= hi]
    out = []
    for a, b in gaps:
        parts = attribute(a, b, near)
        out.append((max(parts, key=parts.get), b - a, parts))
    return out


def summarize(raw: Dict, spans: List[Dict], warm: int,
              failed: Dict[int, int]) -> Optional[Dict]:
    """Per-scope device seconds inside the dispatched windows (mean over
    device planes), and per failure boundary its host seconds, device
    seconds, programs launched, the seconds its spans cover and its idle
    seconds split among the innermost spans over them; the ten longest
    idle gaps of the window, each named by the span that holds most of
    it; the anchors' offsets.  ``spans`` are the
    recorder's (microseconds on its clock).  ``None`` where the trace has
    no anchor or no scope path."""
    if not raw.get("anchors") or not raw.get("op_scopes"):
        return None
    ns = clock_map(raw["anchors"])
    windows, bounds = windows_and_boundaries(spans, warm, failed)
    if not windows:
        return None
    spans_ns = [(s["name"], ns(s["ts_us"] / 1e6),
                 ns((s["ts_us"] + s["dur_us"]) / 1e6)) for s in spans]
    lo, hi = ns(windows[0][2]), ns(windows[-1][3])
    win_ns = [(ns(a), ns(b)) for _, _, a, b in windows]
    planes = sorted(raw["ops"])
    scope_ns: Dict[str, float] = {}
    for plane in planes:
        for scope, t in scoped_busy_ns(raw["ops"][plane],
                                       raw["op_scopes"][plane],
                                       win_ns).items():
            scope_ns[scope] = scope_ns.get(scope, 0.0) + t / len(planes)
    boundaries = []
    for wall_step, failures, a, b in bounds:
        if not failures:
            continue
        blo, bhi = ns(a), ns(b)
        device = programs = 0.0
        for plane in planes:
            device += busy_ns(merged(device_intervals(raw["ops"][plane]),
                                     blo, bhi)) / len(planes)
            programs += sum(blo <= s < bhi for _, s, _ in
                            raw.get("modules", {}).get(plane, ())
                            ) / len(planes)
        busy0 = merged(device_intervals(raw["ops"][planes[0]]), blo, bhi)
        idle: Dict[str, float] = {}
        for _, _, parts in named_idle(busy0, blo, bhi, spans_ns):
            for name, t in parts.items():
                idle[name] = idle.get(name, 0.0) + t / 1e9
        boundaries.append({
            "wall_step": wall_step, "failures": failures, "host_s": b - a,
            "device_s": device / 1e9, "programs": programs,
            "covered_s": covered_s(spans, a, b), "idle_s": idle})
    busy = merged(device_intervals(raw["ops"][planes[0]]), lo, hi)
    gaps = named_idle(busy, lo, hi, spans_ns, n=10)
    return {
        "scope_busy_s": {k: v / 1e9 for k, v in scope_ns.items()
                         if k not in ("", "*")},
        "scoped_busy_s": scope_ns.get("*", 0.0) / 1e9,
        "window_busy_s": scope_ns.get("", 0.0) / 1e9,
        "failure_boundaries": boundaries,
        "idle_gaps": [[name, t / 1e9] for name, t, _ in gaps],
        "anchor_offsets_ns": anchor_offsets_ns(raw["anchors"]),
    }


# ---------------------------------------------------------------------------
# what the per-layer readers share
# ---------------------------------------------------------------------------

def scope_ms_per_step(ctx, scope: str) -> Optional[float]:
    """Device ms of ``scope`` inside the dispatched windows, per step, or
    ``None`` where the run holds no scoped trace."""
    scoped = getattr(ctx, "scoped", None)
    if not scoped or not ctx.steps or scope not in scoped["scope_busy_s"]:
        return None
    return 1e3 * scoped["scope_busy_s"][scope] / ctx.steps


def per_failure(ctx, key: str) -> Optional[float]:
    """``key`` of the failure boundaries summed over the failures."""
    scoped = getattr(ctx, "scoped", None)
    bounds = scoped["failure_boundaries"] if scoped else []
    failures = sum(b["failures"] for b in bounds)
    if not failures:
        return None
    return sum(b[key] for b in bounds) / failures
