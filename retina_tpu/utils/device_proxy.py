"""Single-threaded device call proxy.

The agent is aggressively multi-threaded on the host side (plugin feeds,
the engine dispatch loop, scrape handlers, watcher reconciles, the
metrics-module publisher). ALL engine-side JAX interaction routes through
this proxy: one daemon thread owns the calls, callers enqueue closures
and block on the result.

What the single thread buys on a directly attached chip is ordering, not
safety: the proxy queue is FIFO, so a table upload enqueued before a
batch is visible to that batch, a snapshot dispatched after a step reads
that step's state before the next donating step runs, and a blocking
call fences everything submitted before it (engine.py leans on all
three). Whether the attached chip's runtime would also tolerate
concurrent device_put / device_get / jit dispatch from several threads
is unverified; it gains nothing from it, since every bulk transfer and
step dispatch bottoms out in one serialized runtime anyway. Per-call
overhead is a queue round-trip (~tens of µs) against device operations
that are ms-scale.

Re-entrant calls (a proxied closure calling run_on_device) execute
directly on the proxy thread, as part of the call that made them.

The queue is observed, not changed. Every call carries a ``kind`` from
the fixed registry ``utils/metric_names.PROXY_KINDS``. Per call the
proxy records the wait (enqueue -> start) and the run (start -> end):
``tpu_proxy_wait_seconds{kind}``, ``tpu_proxy_run_seconds{kind}`` (its
``_count`` is the number of calls), and ``tpu_proxy_queue_depth``
(calls queued, set after every put and every take). Through the
flight recorder each call is a ``retina:proxy_run`` annotation with its
kind and the blocking ``q.get()`` a ``retina:proxy_idle`` one, so a
profile shows at every instant whether the proxy thread was idle or
which kind of call it ran; every kind but ``poll`` also writes a ring
span (``proxy_run``, with ``kind``, ``wait_s`` and the enqueuing span
as parent). A readiness poll runs a hundred times a second and would
wrap a ring in a run: polls are counted and annotated only.

Both threads own a liveness cell (``HEARTBEATS``;
``runtime/supervisor.py``) that whoever runs a watchdog adopts: the
proxy beats at the start of every call, with the call's kind and the
clock reading it has taken anyway, and parks at the call's end; the
completion thread beats before ``block_until_ready`` and parks in its
queue. A call that keeps the proxy for a second is then a ``stall``
span of cause ``thread`` with its kind, exact at both ends. The cells
are observed, never escalated: a cold compile keeps the proxy for
minutes and is no fault.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, TypeVar

import numpy as np

from retina_tpu.obs.recorder import NULL_SPAN, annotate, get_recorder
from retina_tpu.runtime.supervisor import NEVER, Heartbeat
from retina_tpu.utils import metric_names as mn

T = TypeVar("T")

_lock = threading.Lock()
_q: queue.Queue | None = None
_thread: threading.Thread | None = None
# kind -> (wait histogram child, run histogram child) of the registry
# in _children_of; filled on the proxy thread only.
_children: dict[str, tuple] = {}
_children_of: Any = None
# The two threads' liveness cells (module docstring), parked until the
# first call.
_hb = Heartbeat("device-proxy", NEVER, parked=True)
_hb_ready = Heartbeat("device-completion", NEVER, parked=True)
HEARTBEATS = (_hb, _hb_ready)


def _set_depth(q: queue.Queue) -> None:
    """The queue's depth to the gauge, where it changes: after a put
    and after the proxy took a call. Like ``_observe`` it must never
    take down its caller."""
    try:
        from retina_tpu.metrics import get_metrics

        get_metrics().proxy_queue_depth.set(q.qsize())
    except Exception:  # noqa: RT101 — see docstring
        pass


def _observe(kind: str, wait_s: float, run_s: float) -> None:
    """(proxy thread) One call's numbers to the exposition. The
    observer must never take down the proxy: a registry that cannot be
    reached drops the numbers and keeps the call."""
    global _children_of
    try:
        from retina_tpu.metrics import get_metrics

        m = get_metrics()
        if m is not _children_of:  # a new registry (tests reset it)
            _children.clear()
            _children_of = m
        ch = _children.get(kind)
        if ch is None:
            ch = _children[kind] = (
                m.proxy_wait_seconds.labels(kind=kind),
                m.proxy_run_seconds.labels(kind=kind),
            )
        ch[0].observe(wait_s)
        ch[1].observe(run_s)
    except Exception:  # noqa: RT101 — see docstring
        pass


# The idle wait is annotated in slices: an annotation that is open when
# a profiler session starts or stops is not in the session, so one long
# wait would leave the head and the tail of a trace unlabelled.
_IDLE_SLICE_S = 0.05


def _mark(make: Callable[[], Any]) -> Any:
    """(proxy thread) Open the span or annotation ``make()`` gives.
    Like ``_observe`` it must never take down the proxy, the one thread
    every ``run_on_device`` waits for: where the recorder or the
    profiler raises, the call runs unmarked."""
    try:
        span = make()
        span.__enter__()
        return span
    except Exception:  # noqa: RT101 — see docstring
        return NULL_SPAN


def _unmark(span: Any) -> None:
    try:
        span.__exit__(None, None, None)
    except Exception:  # noqa: RT101 — see _mark
        pass


def _loop(q: queue.Queue) -> None:
    while True:
        item = None
        while item is None:
            idle = _mark(lambda: annotate("proxy_idle"))
            try:
                item = q.get(timeout=_IDLE_SLICE_S)
            except queue.Empty:  # noqa: RT101 — an idle slice ended; wait on
                pass
            finally:
                _unmark(idle)
        fn, args, kwargs, box, done, kind, t_enq, parent = item
        t_start = time.perf_counter()
        _hb.beat(t_start, kind)
        _set_depth(q)
        span = _mark(
            (lambda: annotate("proxy_run", kind=kind))
            if kind == mn.KIND_POLL
            else (lambda: get_recorder().span(
                mn.STAGE_PROXY_RUN, parent=parent, kind=kind,
                wait_s=t_start - t_enq))
        )
        _hb.span = getattr(span, "id", 0)
        try:
            box.append(fn(*args, **kwargs))
        except BaseException as e:  # noqa: BLE001 — delivered to caller
            box.append(e)
            box.append(True)
        finally:
            t_end = time.perf_counter()
            _hb.park(t_end)
            done.set()
            _unmark(span)
            _observe(kind, t_start - t_enq, t_end - t_start)


def _ensure_thread() -> queue.Queue:
    global _q, _thread
    with _lock:
        if _q is None:
            # Proxy inbox: depth is already capped upstream by the
            # bounded in-flight semaphores (engine._inflight /
            # _close_inflight) and synchronous run_on_device waiters;
            # a maxsize here could deadlock a waiter against its own
            # done-event.
            _q = queue.Queue()  # noqa: RT102 — bounded upstream, see above
            _thread = threading.Thread(
                target=_loop, args=(_q,), name="device-proxy", daemon=True
            )
            _thread.start()
        return _q


def _enqueue(q: queue.Queue, fn, args, kwargs, box, done, kind,
             parent=None) -> None:
    if parent is None:
        parent = get_recorder().current_id()
    q.put((fn, args, kwargs, box, done, kind, time.perf_counter(), parent))
    _set_depth(q)


def run_on_device(
    fn: Callable[..., T], *args: Any, kind: str = mn.KIND_OTHER,
    parent: int | None = None, **kwargs: Any,
) -> T:
    """Execute ``fn(*args, **kwargs)`` on the device proxy thread and
    return (or re-raise) its result. ``kind`` names the call for the
    proxy's own metrics (module docstring); ``parent`` is the span that
    caused it, by default the one open on the calling thread."""
    if threading.current_thread() is _thread:
        return fn(*args, **kwargs)
    q = _ensure_thread()
    box: list = []
    done = threading.Event()
    _enqueue(q, fn, args, kwargs, box, done, kind, parent)
    done.wait()
    if len(box) == 2:
        raise box[0]
    return box[0]


def submit_on_device(
    fn: Callable[..., Any], *args: Any, kind: str = mn.KIND_OTHER,
    parent: int | None = None, **kwargs: Any,
) -> None:
    """Fire-and-forget: enqueue ``fn`` on the proxy thread and return
    immediately.

    The proxy queue is FIFO, so submissions execute in submission order,
    interleaved with (and ordered against) ``run_on_device`` calls — a
    later blocking call acts as a fence for everything submitted before
    it. Exceptions are swallowed (nobody awaits the result): ``fn`` MUST
    handle its own failures. Callers are responsible for bounding the
    number of outstanding submissions (the engine uses a semaphore
    released from inside the closure) or host memory pins the payloads
    of an unbounded backlog.
    """
    if threading.current_thread() is _thread:
        try:
            fn(*args, **kwargs)
        except BaseException:  # noqa: BLE001, RT101 — contract: fn self-handles errors (safe_* wrappers)
            pass
        return
    q = _ensure_thread()
    _enqueue(q, fn, args, kwargs, [], threading.Event(), kind, parent)


def fetch_on_device(
    arr: Any, poll_s: float = 0.01, timing: dict | None = None
) -> Any:
    """Device->host readback that blocks only the CALLER.

    A plain ``np.asarray(arr)`` on the proxy thread parks it for the
    full wait (queued compute ahead of ``arr`` plus the D2H copy), and
    every queued dispatch behind it. Doing the asarray on the caller's
    thread instead would break this module's one-thread rule.

    This does neither: the caller polls ``arr.is_ready()`` through
    short proxied calls (serviced between queued dispatches in ~µs),
    sleeping off-proxy between polls, and only when the computation has
    finished does the proxy run the asarray — which then costs just the
    D2H bytes, not the queue wait. Every JAX touch stays on the proxy
    thread.

    ``timing``, when given, receives the two halves in seconds:
    ``ready_wait_s`` (until ``is_ready`` said yes) and ``copy_s`` (the
    proxied asarray, its own queue wait included)."""
    t0 = time.perf_counter()
    check = getattr(arr, "is_ready", None)
    if check is not None:
        while not run_on_device(check, kind=mn.KIND_POLL):
            time.sleep(poll_s)
    t1 = time.perf_counter()
    host = run_on_device(np.asarray, arr, kind=mn.KIND_FETCH)
    if timing is not None:
        timing["ready_wait_s"] = t1 - t0
        timing["copy_s"] = time.perf_counter() - t1
    return host


# -- completion: waiting for the device off the proxy thread -----------
_ready_q: queue.SimpleQueue | None = None
_ready_thread: threading.Thread | None = None


def _ready_loop(q: queue.SimpleQueue) -> None:
    while True:
        arr, fn = q.get()
        _hb_ready.beat()
        err = None
        try:
            arr.block_until_ready()
        except BaseException as e:  # noqa: BLE001 — handed to fn
            err = e
        try:
            fn(err)
        except BaseException:  # noqa: BLE001, RT101 — contract: fn self-handles errors
            pass
        _hb_ready.park()


def on_ready(arr: Any, fn: Callable[[BaseException | None], Any]) -> None:
    """Call ``fn(error_or_None)`` on the completion thread once ``arr``
    is ready on the device. Returns at once; the proxy thread, where
    the engine calls this from, never waits. Hand-overs complete in
    order, as the device runs them.

    This is the one place where a thread other than the proxy touches
    a JAX array: ``block_until_ready`` waits, and neither dispatches nor
    copies. The direct wait was tried on one attached v5e chip (PR 26:
    a probe of 300 waits beside a dispatching thread, then every run of
    the benchmark) and is kept. On a four-chip mesh, where the awaited
    output lives on every device, it is what the engine runs with too:
    every run of the benchmark's four-chip cell since PR 33 boots the
    agent over the v5e-4 host's mesh and ends exact (ROADMAP D11). The
    fallback, should a runtime ever refuse the wait, is to poll
    ``is_ready`` through the proxy as ``fetch_on_device`` does.
    Callers bound the number of outstanding hand-overs (the engine
    hands over one per dispatch, inside its in-flight semaphore)."""
    global _ready_q, _ready_thread
    with _lock:
        if _ready_q is None:
            _ready_q = queue.SimpleQueue()  # noqa: RT102 — bounded upstream: one hand-over per dispatch, inside the engine's in-flight semaphore
            _ready_thread = threading.Thread(
                target=_ready_loop, args=(_ready_q,),
                name="device-completion", daemon=True,
            )
            _ready_thread.start()
    _ready_q.put((arr, fn))


def fence(timeout: float | None = None) -> bool:
    """Block until everything submitted before this call has executed.

    Returns False if ``timeout`` (seconds) elapsed first — a wedged
    proxy thread (the failure mode this module contains) must not turn
    a bounded shutdown into an unbounded hang.
    """
    if threading.current_thread() is _thread:
        return True
    q = _ensure_thread()
    done = threading.Event()
    _enqueue(q, lambda: None, (), {}, [], done, mn.KIND_OTHER)
    return done.wait(timeout)
