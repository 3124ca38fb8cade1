"""Node-side snapshot shipper: window close -> wire frame -> relay.

At each window close the engine dispatches the on-device fleet export
(one psum/pmax/all_gather pass over the mesh, parallel/telemetry.py)
and hands the resulting device dict to this shipper's bounded queue.
The worker thread does everything slow OFF the device proxy: readback
(fetch_on_device per leaf — polls readiness, never parks the proxy),
encode (fleet/codec.py), and the transport send.

Backpressure contract (the repo-wide rule): never block the close path
— a full queue drops the snapshot and counts it. Overload contract:
under SHEDDING and above, the shipper backs off to shipping 1 window in
``fleet_shed_ship_every`` (the rollup is the cheapest remote work to
lose; local scrape metrics stay complete).

Delivery contract: a transport failure opens the send circuit and the
frame goes to a bounded in-memory spool (oldest-evicted, both counted)
instead of being lost. The worker retries with jittered exponential
backoff — recreating the gRPC channel on each retry so a bounced relay
is re-dialed fresh — and on heal replays the spool oldest-first before
new frames, so a transient relay outage costs latency, not data. The
circuit state is exported as a gauge (fleet_ship_circuit_open) and in
:meth:`stats` — the node-local health signal operators alert on.

Transport is pluggable: default is the in-process pubsub bus
(FLEET_TOPIC — the aggregator subscribes when co-located); when
``fleet_relay_addr`` is set, frames go over the hubble relay's
"retina.Fleet" Ship RPC instead (hubble/server.py).
"""

from __future__ import annotations

import os
import queue as queue_mod
import random
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

import numpy as np

from retina_tpu.fleet.codec import FLEET_TOPIC, FleetSnapshot, encode_snapshot
from retina_tpu.log import logger, rate_limited
from retina_tpu.metrics import get_metrics
from retina_tpu.obs.recorder import get_recorder
from retina_tpu.pubsub import get_pubsub
from retina_tpu.runtime.overload import SHEDDING
from retina_tpu.utils import metric_names as mn
from retina_tpu.utils.device_proxy import fetch_on_device

# Worker wake sentinel: a retry-timer tick, not a frame.
_TICK = object()


class SnapshotShipper:
    """Owns the ship queue + worker thread for one node agent."""

    def __init__(
        self,
        cfg,
        overload=None,  # OverloadController (state read only)
        supervisor=None,  # runtime/supervisor.py Supervisor
        transport: Optional[Callable[[bytes], None]] = None,
    ) -> None:
        self.cfg = cfg
        self.log = logger("fleet.shipper")
        self.node = cfg.fleet_node_name or cfg.node_name or (
            f"node-{os.getpid()}"
        )
        self.tenant = cfg.fleet_tenant
        self.priority = int(cfg.fleet_priority)
        # Live seed generation: rotated by set_seed_generation (or per
        # offer); tags every frame so the aggregator can tell a rotated
        # node from a misconfigured one.
        self.seed_gen = int(cfg.fleet_seed_generation)
        # Tier stamped on outgoing frames (0 = node agent; the
        # aggregator's re-shipper sets 1).
        self.tier = 0
        self._overload = overload
        self._supervisor = supervisor
        self._transport = transport
        self._grpc_client: Any = None
        self._q: queue_mod.Queue = queue_mod.Queue(
            maxsize=max(1, int(cfg.fleet_ship_queue))
        )
        self._seq = 0
        self._win_count = 0  # windows offered (shed-backoff modulus)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.shipped = 0  # frames actually sent (tests/dryrun)
        # -- spool / circuit state (worker thread only, read by stats) --
        self._spool: deque[bytes] = deque()
        self._spool_cap = max(0, int(cfg.fleet_ship_spool))
        self.circuit_open = False
        self._fail_streak = 0
        self._next_retry_t = 0.0
        self.spooled = 0
        self.spool_evicted = 0
        self.spool_replayed = 0
        self.reconnects = 0

    # -- lifecycle -----------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name=f"fleet-ship-{self.node}", daemon=True
        )
        self._thread.start()

    def stop(self, timeout_s: float = 5.0) -> None:
        self._stop.set()
        self._q.put(None)  # wake the worker
        t = self._thread
        if t is not None:
            t.join(timeout=timeout_s)
        if self._supervisor is not None and self._thread is not None:
            self._supervisor.deregister(f"fleet-ship-{self.node}")
        self._thread = None

    def set_seed_generation(self, gen: int) -> None:
        """Rotate the live seed generation (tags frames from the NEXT
        offer on; in-flight frames keep the generation they were built
        under). Single int write — safe from any thread."""
        self.seed_gen = int(gen)

    # -- close-path entry (device-proxy thread; must never block) ------
    def offer(  # hot-path: close
        self,
        epoch: int,
        arrays: dict[str, Any],
        window_s: float,
        seeds: dict[str, int],
        seed_gen: int | None = None,
    ) -> bool:  # runs-on: device-proxy
        """Enqueue one window's export for shipping. ``arrays`` values
        may be device arrays (fetched on the worker) or host numpy.
        Returns False when deferred (overload backoff) or dropped
        (queue full / stopped)."""
        if self._stop.is_set():
            return False
        m = get_metrics()
        with self._lock:
            self._win_count += 1
            count = self._win_count
        ov = self._overload
        if ov is not None and ov.state >= SHEDDING:
            every = max(1, int(self.cfg.fleet_shed_ship_every))
            if count % every != 0:
                m.fleet_ship_deferred.inc()
                return False
        gen = self.seed_gen if seed_gen is None else int(seed_gen)
        try:
            self._q.put_nowait((epoch, arrays, window_s, seeds, gen))
            return True
        except queue_mod.Full:
            m.fleet_ship_dropped.inc()
            if rate_limited("fleet.ship_queue_full"):
                self.log.warning(
                    "fleet ship queue full; dropping epoch %d", epoch
                )
            return False

    # -- worker --------------------------------------------------------
    def _run(self) -> None:  # runs-on: fleet-ship
        hb = None
        if self._supervisor is not None:
            hb = self._supervisor.register(
                f"fleet-ship-{self.node}", self.cfg.watchdog_deadline_s
            )
        while not self._stop.is_set():
            if hb is not None:
                hb.park()
            # With frames waiting in the spool, wake at the next retry
            # time even if the queue stays empty — the replay must not
            # depend on new window closes arriving.
            timeout = None
            if self._spool:
                timeout = max(
                    0.01, self._next_retry_t - time.monotonic()
                )
            try:
                item = self._q.get(timeout=timeout)
            except queue_mod.Empty:
                item = _TICK
            if item is None or self._stop.is_set():
                break
            if hb is not None:
                hb.beat()
            try:
                if item is _TICK:
                    self._try_drain()
                else:
                    self._ship_one(*item)
            except Exception:
                get_metrics().fleet_ship_errors.inc()
                if rate_limited("fleet.ship"):
                    self.log.exception("fleet snapshot ship failed")

    def _ship_one(
        self,
        epoch: int,
        arrays: dict[str, Any],
        window_s: float,
        seeds: dict[str, int],
        seed_gen: int = 0,
    ) -> None:
        rec = get_recorder()
        host: dict[str, np.ndarray] = {}
        with rec.span(mn.STAGE_SHIP_READBACK, int(epoch)):
            for name, arr in arrays.items():
                if isinstance(arr, np.ndarray):
                    host[name] = arr
                else:
                    host[name] = fetch_on_device(arr)
        with self._lock:
            seq = self._seq
            self._seq += 1
        snap = FleetSnapshot(
            node=self.node, tenant=self.tenant, priority=self.priority,
            epoch=int(epoch), seq=seq, window_s=float(window_s),
            seeds=seeds, arrays=host,
            # Trace context: the window epoch IS the trace ID; the
            # aggregator's merge span joins this lineage across the
            # process boundary (docs/observability.md).
            trace={"tid": int(epoch), "node": self.node},
            seed_gen=int(seed_gen),
            tier=int(self.tier),
        )
        with rec.span(mn.STAGE_SHIP_ENCODE, int(epoch)):
            frame = encode_snapshot(snap)
        with rec.span(mn.STAGE_SHIP_SEND, int(epoch)):
            self._deliver(frame)

    # -- delivery: circuit + spool + backoff ---------------------------
    def _deliver(self, frame: bytes) -> None:
        """Send one fresh frame, preserving epoch order: with frames
        already spooled the new frame queues BEHIND them (and a drain is
        attempted if the retry timer expired); otherwise it is sent
        directly and spooled on failure."""
        if self._spool:
            self._spool_frame(frame)
            self._try_drain()
            return
        try:
            self._send(frame)
        except Exception:
            self._note_send_failure(frame_lost=False)
            self._spool_frame(frame)
            return
        self._note_send_ok(len(frame))

    def _try_drain(self) -> None:
        """Replay the spool oldest-first once the backoff timer allows;
        a failure re-arms the timer and keeps the remaining frames."""
        if not self._spool or time.monotonic() < self._next_retry_t:
            return
        while self._spool:
            frame = self._spool[0]
            try:
                self._send(frame)
            except Exception:
                self._note_send_failure(frame_lost=False)
                return
            self._spool.popleft()
            self.spool_replayed += 1
            get_metrics().fleet_ship_spool_replayed.inc()
            self._note_send_ok(len(frame))

    def _spool_frame(self, frame: bytes) -> None:
        m = get_metrics()
        if self._spool_cap <= 0:
            # Spooling disabled: the legacy drop-on-error behavior
            # (the failure itself was already counted as a ship error).
            return
        while len(self._spool) >= self._spool_cap:
            self._spool.popleft()  # oldest-evicted
            self.spool_evicted += 1
            m.fleet_ship_spool_evicted.inc()
        self._spool.append(frame)
        self.spooled += 1
        m.fleet_ship_spooled.inc()

    def _note_send_ok(self, nbytes: int) -> None:
        m = get_metrics()
        m.fleet_snapshots_shipped.inc()
        m.fleet_ship_bytes.inc(nbytes)
        self.shipped += 1
        if self.circuit_open:
            self.log.info(
                "fleet ship circuit closed after %d failures "
                "(%d frames spooled)", self._fail_streak, len(self._spool),
            )
        self.circuit_open = False
        self._fail_streak = 0
        m.fleet_ship_circuit_open.set(0.0)

    def _note_send_failure(self, frame_lost: bool) -> None:
        m = get_metrics()
        m.fleet_ship_errors.inc()
        self._fail_streak += 1
        self.circuit_open = True
        m.fleet_ship_circuit_open.set(1.0)
        # Jittered exponential backoff: full-jitter style (uniform in
        # [base/2, backoff]) so a fleet of nodes cut off by one relay
        # outage does not re-dial in lockstep on heal.
        base = max(1e-3, float(self.cfg.fleet_ship_backoff_base_s))
        cap = max(base, float(self.cfg.fleet_ship_backoff_max_s))
        backoff = min(cap, base * (2.0 ** min(self._fail_streak - 1, 16)))
        delay = random.uniform(base / 2.0, backoff)
        self._next_retry_t = time.monotonic() + delay
        # A failed gRPC channel is torn down so the next attempt
        # re-dials (the relay may have restarted on the same address
        # with a new socket).
        if self._grpc_client is not None:
            try:
                self._grpc_client.close()
            except Exception:  # noqa: RT101 — best-effort channel teardown
                pass
            self._grpc_client = None
        if rate_limited("fleet.ship_circuit"):
            self.log.warning(
                "fleet ship failed (streak %d); retry in %.3fs, "
                "%d frames spooled", self._fail_streak, delay,
                len(self._spool),
            )

    def _send(self, frame: bytes) -> None:
        if self._transport is not None:
            self._transport(frame)
            return
        addr = self.cfg.fleet_relay_addr
        if addr:
            if self._grpc_client is None:
                # Lazy import: grpc is optional at module import time
                # (same gating as hubble/server.py).
                from retina_tpu.hubble.server import FleetShipClient

                if self._fail_streak:
                    self.reconnects += 1
                    get_metrics().fleet_ship_reconnects.inc()
                self._grpc_client = FleetShipClient(addr)
            self._grpc_client.ship(frame)
            return
        get_pubsub().publish(FLEET_TOPIC, frame)

    # -- observability -------------------------------------------------
    def stats(self) -> dict:
        return {
            "node": self.node,
            "tenant": self.tenant,
            "seq": self._seq,
            "shipped": self.shipped,
            "queue_depth": self._q.qsize(),
            "seed_gen": self.seed_gen,
            "circuit_open": self.circuit_open,
            "spool_depth": len(self._spool),
            "spooled": self.spooled,
            "spool_evicted": self.spool_evicted,
            "spool_replayed": self.spool_replayed,
            "reconnects": self.reconnects,
        }


def window_epoch(window_s: float, now: float | None = None) -> int:
    """Wall-clock window epoch — aligned across nodes whose clocks are
    NTP-close (a skew below window_s/2 lands in the right bucket; the
    aggregator's straggler timeout absorbs the rest)."""
    now = time.time() if now is None else now
    return int(now // max(window_s, 1e-6))
