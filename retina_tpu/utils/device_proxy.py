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
directly on the proxy thread.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, TypeVar

import numpy as np

T = TypeVar("T")

_lock = threading.Lock()
_q: queue.Queue | None = None
_thread: threading.Thread | None = None


def _loop(q: queue.Queue) -> None:
    while True:
        fn, args, kwargs, box, done = q.get()
        try:
            box.append(fn(*args, **kwargs))
        except BaseException as e:  # noqa: BLE001 — delivered to caller
            box.append(e)
            box.append(True)
        finally:
            done.set()


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


def run_on_device(fn: Callable[..., T], *args: Any, **kwargs: Any) -> T:
    """Execute ``fn(*args, **kwargs)`` on the device proxy thread and
    return (or re-raise) its result."""
    if threading.current_thread() is _thread:
        return fn(*args, **kwargs)
    q = _ensure_thread()
    box: list = []
    done = threading.Event()
    q.put((fn, args, kwargs, box, done))
    done.wait()
    if len(box) == 2:
        raise box[0]
    return box[0]


def submit_on_device(fn: Callable[..., Any], *args: Any, **kwargs: Any) -> None:
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
    q.put((fn, args, kwargs, [], threading.Event()))


def fetch_on_device(arr: Any, poll_s: float = 0.01) -> Any:
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
    thread."""
    check = getattr(arr, "is_ready", None)
    if check is not None:
        while not run_on_device(check):
            time.sleep(poll_s)
    return run_on_device(np.asarray, arr)


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
    q.put((lambda: None, (), {}, [], done))
    return done.wait(timeout)
