"""Always-on pipeline flight recorder, on two clocks.

Every pipeline stage (the fixed registry `utils/metric_names.STAGES`)
opens a span here where its work starts and closes it where the work
ends. A span does two things:

- it writes one slot of a preallocated ring (``time.perf_counter``
  pair, the **window epoch as the trace ID**, its own span id and the
  id of the span that caused it), which `/debug/trace`, the stage
  report and ``tpu_stage_seconds{stage}`` read; the stages a thread
  runs from start to finish on itself (`metric_names.CPU_STAGES`) also
  read ``time.thread_time()`` at both ends: the span's ``cpu_s``
  argument and ``tpu_stage_cpu_seconds_counter{stage}``, what the
  stage cost the host where its seconds include the waits (for the
  interpreter lock, for the device);
- it opens a ``jax.profiler.TraceAnnotation("retina:<stage>", ...)``
  with the same ids, so that whenever a profiler session is running
  (``POST /debug/profile``, a benchmark's traced run) the span lands in
  the host plane of the same ``.xplane.pb`` as the device events, on
  the profiler's clock. With no session the annotation costs under a
  microsecond; in a process that never imported JAX it is skipped.

One span shape: ``with rec.span(STAGE, trace_id=...) as sp:`` or, where
start and end sit in different scopes or threads, ``sp = rec.span(...)``
then ``sp.end(...)`` at the true end. A span used as a context manager
is the parent of spans opened on the same thread inside it; a span
ended elsewhere (the engine's ``device_step``, closed by the completion
thread when the device is done) names its parent explicitly. There is
no sampling: every site is per flush, per window or per publish, never
per event. There is one post-hoc form, for one stage: a ``stall``
(:meth:`FlightRecorder.post_hoc`) is written by the watchdog's scan
once both its ends are known, because what it describes (the process
taken off the CPU, the interpreter held, one thread stuck in a call)
could open no span of its own. It carries no ``cpu_s`` and, being
over when it is written, no annotation; the scan drops an instant
``retina:stall`` annotation where the hole ended.

Overhead contract (`tests/test_obs.py` gates it at <3% on the host-path
probe): long-lived threads take **no locks and allocate no ring
memory** — each owns a preallocated ring of mutable slots (created
once, registered under a creation-time-only lock). Threads that live
for one request (``ThreadingHTTPServer`` handlers) pass
``shared=True`` and write to one locked ring, so they never leave a
ring behind. Ring readers tolerate torn slots by construction: a slot
is a list overwritten in place, and a half-written slot merely yields
one bogus span in a diagnostic dump — never an exception on the
writer.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
from typing import Any

from retina_tpu.utils import metric_names as mn

# Spans retained per thread ring by default (each slot is 7 python
# refs; 4096 spans x ~10 threads is about a MB).
DEFAULT_CAPACITY = 4096

_PREFIX = "retina:"
_CPU_STAGES = mn.CPU_STAGES
_annotation_cls: Any = None


def _annotation():
    """``jax.profiler.TraceAnnotation``, once JAX is in the process. A
    profiler session cannot exist without JAX, so a process that has
    not imported it (a fleet child, a test of plain host code) has
    nothing to annotate and must not pay the import."""
    global _annotation_cls
    if _annotation_cls is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    return _annotation_cls


def annotate(name: str, **args: Any):
    """A bare profiler annotation ``retina:<name>`` (context manager),
    for what is too frequent for a ring slot: the device proxy's idle
    waits and its 100-a-second readiness polls."""
    cls = _annotation()
    if cls is None:
        return NULL_SPAN
    return cls(_PREFIX + name, **args)


class _Ring:
    """A preallocated span ring. The per-thread rings are single-writer
    by construction (thread-local); the shared ring is written under
    the recorder's lock. Both are read racily by dump/report paths."""

    __slots__ = ("name", "slots", "pos", "count")

    def __init__(self, name: str, capacity: int) -> None:
        self.name = name
        # slot = [stage, t0, t1, trace_id, span_id, parent_id, args];
        # stage None = never written.
        self.slots: list[list[Any]] = [
            [None, 0.0, 0.0, -1, 0, 0, None] for _ in range(capacity)
        ]
        self.pos = 0
        self.count = 0  # total spans recorded (wrap diagnostic)

    def write(self, stage, t0, t1, trace_id, span_id, parent, args) -> None:
        slot = self.slots[self.pos]
        slot[0] = stage
        slot[1] = t0
        slot[2] = t1
        slot[3] = trace_id
        slot[4] = span_id
        slot[5] = parent
        slot[6] = args
        self.pos = (self.pos + 1) % len(self.slots)
        self.count += 1


class Span:
    """One open span. ``id`` is what a child names as its ``parent``."""

    __slots__ = ("_rec", "stage", "t0", "trace_id", "id", "parent",
                 "_shared", "_ann", "_prev", "_open", "_args", "_late",
                 "_cpu0")

    def __init__(self, rec, stage, trace_id, span_id, parent, shared,
                 ann, args, cpu0) -> None:
        self._rec = rec
        self.stage = stage
        self.trace_id = trace_id
        self.id = span_id
        self.parent = parent
        self._shared = shared
        self._ann = ann
        self._prev = None
        self._open = True
        self._args = args  # known at the start: on the annotation already
        self._late = None  # learnt since: set() and end()
        self._cpu0 = cpu0  # this thread's CPU clock; None: not a CPU stage
        self.t0 = time.perf_counter()

    def __enter__(self) -> "Span":
        local = self._rec._local
        self._prev = getattr(local, "current", 0)
        local.current = self.id
        return self

    def __exit__(self, *exc) -> None:
        self._rec._local.current = self._prev
        self.end()

    def set(self, **args: Any) -> None:
        """Arguments learnt while the span is open (a ``with`` block
        cannot pass them to :meth:`end`)."""
        self._late = {**self._late, **args} if self._late else args

    def end(self, **args: Any) -> float:  # hot-path: event
        """Close the span now, on whichever thread calls. ``args`` are
        what is known only at the end (``n_steps``, ``ready_wait_s``,
        ``events_included``): they go into the ring slot and onto the
        annotation. Returns the span's seconds."""
        t1 = time.perf_counter()
        if not self._open:
            return 0.0
        self._open = False
        cpu_s = None
        if self._cpu0 is not None:
            args["cpu_s"] = cpu_s = time.thread_time() - self._cpu0
        if self._late:
            args = {**self._late, **args}
        ann = self._ann
        if ann is not None:
            if args:
                ann.set_metadata(**args)
            ann.__exit__(None, None, None)
        if self._args:
            args = {**self._args, **args}
        self._rec._commit(
            self.stage, self.t0, t1, self.trace_id, self.id, self.parent,
            args or None, self._shared, cpu_s,
        )
        return t1 - self.t0


class _NullSpan:
    """What a disabled recorder hands out: every site stays one shape
    and costs one boolean check."""

    id = 0
    parent = 0
    t0 = 0.0
    trace_id = -1

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **args: Any) -> None:
        return None

    def end(self, **args: Any) -> float:
        return 0.0


NULL_SPAN = _NullSpan()


class FlightRecorder:
    """Per-thread span rings + the drain/report API over them."""

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        enabled: bool = True,
    ) -> None:
        self.capacity = max(16, int(capacity))
        self.enabled = bool(enabled)
        self._local = threading.local()
        self._ids = itertools.count(1)  # next() is atomic under the GIL
        self._shared = _Ring("shared", self.capacity)
        self._shared_lock = threading.Lock()
        self._rings: list[_Ring] = [self._shared]
        self._rings_lock = threading.Lock()  # ring creation only
        # stage -> (histogram child, CPU-seconds child or None)
        self._hist: dict[str, Any] = {}
        self._hist_lock = threading.Lock()
        self._metrics_broken = False

    # -- hot path ------------------------------------------------------
    def _ring(self) -> _Ring:
        r = getattr(self._local, "ring", None)
        if r is None:
            r = _Ring(threading.current_thread().name, self.capacity)
            self._local.ring = r
            with self._rings_lock:
                self._rings.append(r)  # noqa: RT402 — one ring per long-lived producer thread, first call only; per-request threads write the shared ring
        return r

    def current_id(self) -> int:
        """Id of the span open (as a context manager) on this thread, 0
        if none: what a call handed to another thread names as its
        parent."""
        return getattr(self._local, "current", 0)

    def span(  # hot-path: event
        self,
        stage: str,
        trace_id: int = -1,
        parent: int | None = None,
        shared: bool = False,
        **args: Any,
    ) -> Span | _NullSpan:
        """Open a span of ``stage`` now. ``parent`` defaults to the
        span open on this thread; ``shared`` is for threads that live
        for one request. ``args`` known at the start go onto the
        annotation here; pass the others to :meth:`Span.end`."""
        if not self.enabled:
            return NULL_SPAN
        if parent is None:
            parent = getattr(self._local, "current", 0)
        span_id = next(self._ids)
        cls = _annotation()
        ann = None
        if cls is not None:
            ann = cls(_PREFIX + stage, trace_id=trace_id, span=span_id,
                      parent=parent, **args)
            ann.__enter__()
        cpu0 = time.thread_time() if stage in _CPU_STAGES else None
        return Span(self, stage, trace_id, span_id, parent, shared, ann,
                    args or None, cpu0)

    def post_hoc(self, stage: str, t0: float, t1: float,
                 trace_id: int = -1, parent: int = 0, **args: Any) -> int:
        """Write a span that is already over, from ``t0`` to ``t1`` on
        ``time.perf_counter``, into the calling thread's ring; returns
        its id (0 from a disabled recorder). For ``stall`` alone (module
        docstring): every other stage opens its span where its work
        starts."""
        if not self.enabled:
            return 0
        span_id = next(self._ids)
        self._commit(stage, t0, t1, trace_id, span_id, parent,
                     args or None)
        return span_id

    def _commit(self, stage, t0, t1, trace_id, span_id, parent, args,
                shared=False, cpu_s=None) -> None:
        """Write one finished span. The one writer of ring slots:
        :meth:`Span.end` for live spans, :meth:`post_hoc` for a stall,
        tests for hand-made ones.
        ``cpu_s`` (a CPU stage's, also in ``args``) goes to
        ``tpu_stage_cpu_seconds_counter``."""
        if shared:
            with self._shared_lock:
                self._shared.write(
                    stage, t0, t1, trace_id, span_id, parent, args
                )
        else:
            self._ring().write(
                stage, t0, t1, trace_id, span_id, parent, args
            )
        self._observe(stage, t1 - t0, cpu_s)

    def _observe(self, stage: str, dt: float, cpu_s: float | None) -> None:
        children = self._hist.get(stage)
        if children is None:
            if self._metrics_broken:
                return
            try:
                from retina_tpu.metrics import get_metrics

                with self._hist_lock:
                    children = self._hist.get(stage)
                    if children is None:
                        m = get_metrics()
                        children = (
                            m.stage_seconds.labels(stage=stage),
                            m.stage_cpu_seconds.labels(stage=stage)
                            if stage in _CPU_STAGES else None,
                        )
                        self._hist[stage] = children
            except Exception:  # noqa: RT101 — recorder must never take down a stage; drop exposition, keep spans
                self._metrics_broken = True
                return
        children[0].observe(dt)
        if cpu_s is not None:
            children[1].inc(cpu_s)

    # -- drain / report (diagnostic paths; racy-read tolerant) ---------
    def spans(
        self, last: int | None = None, trace_id: int | None = None,
    ) -> list[dict[str, Any]]:
        """All retained spans (of one window epoch, with ``trace_id``),
        oldest first. ``last`` keeps only the N newest (by end
        timestamp)."""
        out: list[dict[str, Any]] = []
        with self._rings_lock:
            rings = list(self._rings)
        for r in rings:
            for slot in r.slots:
                stage, t0, t1, tid, sid, parent, args = slot
                if stage is None or t1 < t0:
                    continue  # unwritten or torn slot
                if trace_id is not None and tid != trace_id:
                    continue
                out.append({
                    "stage": stage, "t0": t0, "t1": t1,
                    "trace_id": tid, "id": sid, "parent": parent,
                    "args": args or {}, "thread": r.name,
                })
        out.sort(key=lambda s: s["t1"])
        if last is not None and last >= 0:
            out = out[-last:]
        return out

    def chrome_trace(
        self, last: int | None = None, trace_id: int | None = None,
    ) -> dict[str, Any]:
        """Chrome trace-event JSON (load in Perfetto / chrome://tracing):
        one complete ("ph": "X") event per span, tid = recording thread,
        trace id, span id, parent and the span's own arguments in
        args."""
        spans = self.spans(last, trace_id)
        base = min((s["t0"] for s in spans), default=0.0)
        tids: dict[str, int] = {}
        events = []
        for s in spans:
            tid = tids.setdefault(s["thread"], len(tids) + 1)
            events.append({
                "name": s["stage"],
                "cat": "retina",
                "ph": "X",
                "ts": (s["t0"] - base) * 1e6,
                "dur": (s["t1"] - s["t0"]) * 1e6,
                "pid": 1,
                "tid": tid,
                "args": {**s["args"], "trace_id": s["trace_id"],
                         "span": s["id"], "parent": s["parent"]},
            })
        meta = [
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
             "args": {"name": name}}
            for name, tid in tids.items()
        ]
        return {"traceEvents": meta + events,
                "displayTimeUnit": "ms"}

    def stage_report(
        self, last: int | None = None, trace_id: int | None = None,
    ) -> dict[str, dict[str, float]]:
        """Critical-path report: per-stage count/total/p50/p99 seconds
        over the retained spans (of one window epoch, with
        ``trace_id``), in pipeline (registry) order."""
        by_stage: dict[str, list[float]] = {}
        for s in self.spans(last, trace_id):
            by_stage.setdefault(s["stage"], []).append(s["t1"] - s["t0"])
        out: dict[str, dict[str, float]] = {}
        order = {name: i for i, name in enumerate(mn.STAGES)}
        for stage in sorted(by_stage, key=lambda n: order.get(n, 99)):
            durs = sorted(by_stage[stage])
            n = len(durs)
            out[stage] = {
                "count": n,
                "total_s": sum(durs),
                "p50_s": durs[n // 2],
                "p99_s": durs[min(n - 1, (n * 99) // 100)],
            }
        return out

    def stats(self) -> dict[str, Any]:
        with self._rings_lock:
            rings = list(self._rings)
        return {
            "enabled": self.enabled,
            "capacity": self.capacity,
            "threads": {r.name: r.count for r in rings},
        }


# -- process singleton -------------------------------------------------
# Always-on by default: a span costs two perf_counter calls (a CPU
# stage's, two thread_time calls more), seven list writes and an
# inactive annotation, and spans are per-flush/per-window cadence, not
# per-event.
_singleton = FlightRecorder()
_singleton_lock = threading.Lock()


def get_recorder() -> FlightRecorder:
    return _singleton


def initialize_recorder(
    capacity: int = DEFAULT_CAPACITY,
    enabled: bool = True,
) -> FlightRecorder:
    """Replace the process recorder with one built from config (engine
    boot). Threads re-acquire their rings lazily on the next span."""
    global _singleton
    with _singleton_lock:
        _singleton = FlightRecorder(capacity=capacity, enabled=enabled)
        return _singleton
