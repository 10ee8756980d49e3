"""The process-wide telemetry recorder.

One :class:`Recorder` owns everything a run produces: counters, the
structured JSONL event stream (:mod:`repro.telemetry.events`), host-side
trace *spans* exported as Chrome ``trace_event`` JSON
(:mod:`repro.telemetry.trace`), and the *anchors* that tie the recorder's
clock to the JAX profiler's (:func:`anchor`).  Installation
is process-global (``configure()`` / ``set_recorder()``) so deeply nested
layers — the fused-window trainer loop, the async snapshot writer thread,
the cluster simulator — all reach the same sink through the module-level
helpers without threading a handle through every constructor.

**Overhead contract.**  Telemetry is *disabled by default* and the
module-level helpers are the only thing hot paths call: when no recorder
is installed, :func:`emit` / :func:`inc` / :func:`complete` are a single
``None`` check and :func:`span` returns one shared reusable null context —
no allocation, no lock, no clock read.  The trainer's fused window must
stay within 2% of its telemetry-free throughput (see
``docs/observability.md``), which is why nothing here may run work on the
disabled path.

**Span nesting.**  Each span records its ``parent``: the innermost span
open on the same thread when it ends (``None`` at the top level).  Only
:func:`span` opens a span; one recorded through :func:`clock` /
:func:`complete` is never a parent, so a region that may hold child spans
uses :func:`span`.

**Host-side only.**  Spans and events record *around* dispatch/drain
boundaries, never inside traced code, and event payloads must already be
host values (drained numpy scalars, python numbers).  Passing a live
``jax.Array`` would force a device sync in the event serializer — exactly
what the PR 6 ``sync_free()`` guard exists to catch — so the sanitizer
makes no attempt to be clever about array types.

Thread-safety: the :class:`~repro.statestore.snapshot.AsyncSnapshotter`
worker emits from its own thread; all mutation happens under one lock and
per-thread ids are preserved so the Chrome trace shows background writes
on their own track.
"""
from __future__ import annotations

import contextlib
import io
import json
import numbers
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from repro.telemetry.events import SCHEMA_VERSION

EVENTS_FILENAME = "events.jsonl"
TRACE_FILENAME = "trace.json"
#: name of the profiler annotation :meth:`Recorder.anchor` writes
ANCHOR = "repro.anchor"


def _jsonable(v: Any) -> Any:
    """Coerce host scalars (python + numpy) to JSON primitives.

    Deliberately shallow about foreign types: anything unknown becomes
    ``str(v)`` instead of guessing — and a device array passed by mistake
    will sync (and trip the ``sync_free`` guard), which is the contract.
    """
    if isinstance(v, bool) or v is None or isinstance(v, str):
        return v
    if isinstance(v, numbers.Integral):
        return int(v)
    if isinstance(v, numbers.Real):
        return float(v)
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    item = getattr(v, "item", None)          # numpy scalars outside numbers
    if item is not None and getattr(v, "ndim", 1) == 0:
        try:
            return _jsonable(item())
        except (TypeError, ValueError):
            pass
    return str(v)


class Recorder:
    """Counters, events, trace spans and profiler anchors for one run."""

    def __init__(self, run_dir: Optional[str] = None, *,
                 stream: bool = True,
                 clock: Callable[[], float] = time.perf_counter):
        self.run_dir = run_dir
        self._clock = clock
        self._t0 = clock()
        self._lock = threading.Lock()
        self.counters: Dict[str, float] = {}
        self.events: List[dict] = []
        self.spans: List[dict] = []
        #: recorder seconds (:meth:`now`) of each anchor written into the
        #: profiler's trace
        self.anchors: List[float] = []
        self._open = threading.local()      # names of the open spans
        self._file: Optional[io.TextIOBase] = None
        if run_dir is not None and stream:
            os.makedirs(run_dir, exist_ok=True)
            self._file = open(os.path.join(run_dir, EVENTS_FILENAME), "w")

    # ---- clock --------------------------------------------------------
    def now(self) -> float:
        """Host seconds since the recorder was created."""
        return self._clock() - self._t0

    # ---- metrics ------------------------------------------------------
    def inc(self, name: str, n: float = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    # ---- events -------------------------------------------------------
    def event(self, kind: str, **fields: Any) -> dict:
        rec = {"v": SCHEMA_VERSION, "kind": kind, "t_s": self.now()}
        for k, v in fields.items():
            rec[k] = _jsonable(v)
        with self._lock:
            self.events.append(rec)
            self.counters[f"events.{kind}"] = \
                self.counters.get(f"events.{kind}", 0) + 1
            if self._file is not None:
                json.dump(rec, self._file)
                self._file.write("\n")
        return rec

    # ---- spans --------------------------------------------------------
    def _stack(self) -> List[str]:
        stack = getattr(self._open, "names", None)
        if stack is None:
            stack = self._open.names = []
        return stack

    def complete(self, name: str, t0: float, *, cat: str = "repro",
                 **args: Any) -> None:
        """Record a finished span that started at host time ``t0``
        (a value previously obtained from :func:`clock`); its parent is
        the innermost :meth:`span` open on this thread."""
        t1 = self._clock()
        stack = self._stack()
        with self._lock:
            self.spans.append({
                "name": name, "cat": cat,
                "ts_us": (t0 - self._t0) * 1e6,
                "dur_us": (t1 - t0) * 1e6,
                "tid": threading.get_ident(),
                "parent": stack[-1] if stack else None,
                "args": {k: _jsonable(v) for k, v in args.items()},
            })

    @contextlib.contextmanager
    def span(self, name: str, *, cat: str = "repro", **args: Any):
        stack = self._stack()
        t0 = self._clock()
        stack.append(name)
        try:
            yield
        finally:
            stack.pop()
            self.complete(name, t0, cat=cat, **args)

    # ---- anchors ------------------------------------------------------
    def anchor(self) -> None:
        """Write the recorder's clock into the JAX profiler's trace: a
        zero-length ``TraceAnnotation`` named :data:`ANCHOR` whose
        ``t_s`` argument is :meth:`now`, so a reader of the trace can map
        every span onto the profiler's clock through the nearest anchor.
        Outside a profiler session the annotation records nothing."""
        from jax.profiler import TraceAnnotation   # the report runs jax-free
        t = self.now()
        with TraceAnnotation(ANCHOR, t_s=t):
            pass
        with self._lock:
            self.anchors.append(t)

    # ---- export -------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Point-in-time counter values (JSON-able)."""
        with self._lock:
            return {"counters": dict(self.counters)}

    def chrome_trace(self) -> Dict[str, Any]:
        from repro.telemetry.trace import chrome_trace
        with self._lock:
            spans = list(self.spans)
            events = list(self.events)
        return chrome_trace(spans, events)

    def write_chrome_trace(self, path: Optional[str] = None) -> str:
        from repro.telemetry.trace import write_chrome_trace
        if path is None:
            if self.run_dir is None:
                raise ValueError("no path given and recorder has no run_dir")
            path = os.path.join(self.run_dir, TRACE_FILENAME)
        return write_chrome_trace(path, self)

    def flush(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.flush()

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None


# ---------------------------------------------------------------------------
# process-global installation + the hot-path helpers
# ---------------------------------------------------------------------------

_RECORDER: Optional[Recorder] = None
_NULL_SPAN = contextlib.nullcontext()     # shared, reentrant, allocation-free


def enabled() -> bool:
    return _RECORDER is not None


def get_recorder() -> Optional[Recorder]:
    return _RECORDER


def set_recorder(rec: Optional[Recorder]) -> Optional[Recorder]:
    """Install ``rec`` process-wide; returns the previous recorder (restore
    it in a ``finally`` when scoping telemetry to a test)."""
    global _RECORDER
    prev, _RECORDER = _RECORDER, rec
    return prev


def configure(run_dir: Optional[str] = None, *,
              stream: bool = True) -> Recorder:
    """Create a :class:`Recorder` (streaming JSONL into ``run_dir`` when
    given) and install it process-wide."""
    rec = Recorder(run_dir, stream=stream)
    set_recorder(rec)
    return rec


def emit(kind: str, **fields: Any) -> None:
    r = _RECORDER
    if r is not None:
        r.event(kind, **fields)


def inc(name: str, n: float = 1) -> None:
    r = _RECORDER
    if r is not None:
        r.inc(name, n)


def span(name: str, *, cat: str = "repro", **args: Any):
    """Context manager timing a host-side region (no-op when disabled)."""
    r = _RECORDER
    if r is None:
        return _NULL_SPAN
    return r.span(name, cat=cat, **args)


def clock() -> float:
    """Raw host clock for the manual-span pattern::

        t0 = telemetry.clock()
        ... dispatch ...
        telemetry.complete("window_dispatch", t0, k=k)

    Used where a ``with`` block would wrap a donating dispatch (the
    donation-liveness lint treats a with-statement as one unit, so the
    donated-arg read and the re-dispatch would collide).  Returns 0.0 when
    disabled — :func:`complete` ignores it then anyway.
    """
    r = _RECORDER
    if r is None:
        return 0.0
    return r._clock()


def complete(name: str, t0: float, *, cat: str = "repro",
             **args: Any) -> None:
    r = _RECORDER
    if r is not None:
        r.complete(name, t0, cat=cat, **args)


def anchor() -> None:
    """Write an anchor into the profiler's trace (:meth:`Recorder.anchor`);
    a no-op when disabled."""
    r = _RECORDER
    if r is not None:
        r.anchor()
