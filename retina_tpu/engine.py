"""SketchEngine: the TPU worker that replaces the CPU aggregation loop.

Reference analog (what this replaces, SURVEY.md §3.2): the enricher output
ring → ``Module.run`` goroutine calling every metric's ``ProcessFlow`` per
flow (metrics_module.go:283-303) — single-threaded CPU hash aggregation,
the scaling bottleneck. Per the BASELINE north star, this engine is the
"tpusketch" plugin's backend: plugins feed fixed-width record blocks into
a bounded queue (QueueSink), the feed loop batches them into fixed-shape
device arrays, and ONE jit-compiled step updates every aggregator. Sharded
over a ``jax.sharding.Mesh`` when more than one device is available
(parallel/telemetry.py); scrape-time snapshots merge with psum/pmax/
all_gather over ICI.

Backpressure contract (the reference's universal rule,
packetparser_linux.go:692-697): never block a producer — drop and count.
Snapshot contract: scrapes read a cached merged snapshot at most
``snapshot_max_age_s`` old (<1s target, BASELINE) and never stall the feed
loop; JAX dispatch is async so the feed thread keeps the device busy while
snapshot results transfer back.
"""

from __future__ import annotations

import os
import queue as queue_mod
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from retina_tpu.config import Config
from retina_tpu.devprog import device_entry
from retina_tpu.events.schema import F, NUM_FIELDS
from retina_tpu.fleet.shipper import window_epoch as fleet_epoch
from retina_tpu.log import logger, rate_limited
from retina_tpu.metrics import get_metrics
from retina_tpu.models.identity import HostIdentityTable, IdentityMap
from retina_tpu.models.pipeline import PipelineConfig
from retina_tpu.obs.recorder import NULL_SPAN, initialize_recorder
from retina_tpu.parallel.combine import combine_blocks
from retina_tpu.parallel.feed import (
    FEED_PARK_MAX_S, PARK_MAX_S, FeedWorkerPool, park,
)
from retina_tpu.parallel.flowdict import flow_dict_stats, make_flow_dict
from retina_tpu.parallel.partition import (
    ShardedBatch, _next_bucket, fold_batches, partition_events,
)
from retina_tpu.parallel.telemetry import (
    SCOPE_INGEST_UNPACK, ShardedTelemetry, note_op_scopes,
    topk_from_snapshot,
)
from retina_tpu.plugins.api import QueueSink
from retina_tpu.runtime import faults
from retina_tpu.runtime.overload import OverloadController
from retina_tpu.runtime.supervisor import (
    Heartbeat, Supervisor, policy_from_config,
)
from retina_tpu.utils import metric_names as mnames
from retina_tpu.utils.device_proxy import (
    HEARTBEATS as PROXY_HEARTBEATS,
    fence, fetch_on_device, on_ready, run_on_device, submit_on_device,
)


def pipeline_config_from(cfg: Config) -> PipelineConfig:
    return PipelineConfig(
        n_pods=cfg.n_pods,
        cms_width=cfg.cms_width,
        cms_depth=cfg.cms_depth,
        topk_slots=cfg.topk_slots,
        hll_precision=cfg.hll_precision,
        entropy_buckets=cfg.entropy_buckets,
        conntrack_slots=cfg.conntrack_slots,
        enable_conntrack=cfg.enable_conntrack_metrics,
        bypass_filter=cfg.bypass_lookup_ip_of_interest
        or not cfg.enable_pod_level,
        # Annotation opt-in: ONLY the filter map (fed by the metrics
        # module's annotated-pod set) decides interest; identity alone
        # must not readmit an un-annotated pod's traffic.
        identity_implies_interest=not cfg.enable_annotations,
        # Low aggregation needs conntrack reports to drive the sketch
        # sampling; without conntrack, fall back to full per-packet feeds
        # (the reference likewise compiles DATA_AGGREGATION_LEVEL into the
        # datapath only alongside conntrack, packetparser.c:214-225).
        data_aggregation_level=(
            cfg.data_aggregation_level
            if cfg.enable_conntrack_metrics
            else "high"
        ),
        # Invertible heavy-key recovery (ops/invertible.py): the sketch
        # arrays live in device state whenever decode may be asked for.
        enable_invertible=cfg.heavy_keys_source in ("invertible", "both"),
        inv_depth=cfg.invertible_depth,
        inv_width=cfg.invertible_width,
        inv_hi_width=cfg.invertible_hi_width,
        priority_ip_mask=cfg.overload_priority_ip_mask,
        priority_ip_match=cfg.overload_priority_ip_match,
    )


def fold_side_windows(a, na, b, nb):
    """(traced) One step window out of two: per device, the ``na``
    valid rows of window ``a``, then window ``b``'s rows from position
    ``na`` on (``a``, ``b``: (D, capacity, F); ``na``, ``nb``: (D,)
    valid rows, ``na + nb <= capacity``). Returns the window and its
    ``na + nb``; what lies past that is padding."""
    with jax.named_scope(SCOPE_INGEST_UNPACK):
        cap = a.shape[1]
        idx = jnp.arange(cap, dtype=jnp.int32)[None, :]
        at = na.astype(jnp.int32)[:, None]
        moved = jnp.take_along_axis(
            b, jnp.mod(idx - at, cap)[..., None], axis=1
        )
        out = jnp.where((idx < at)[..., None], a, moved)
    return out, na + nb


class SketchEngine:
    """Owns device state + the feed/window loop; thread-safe facade."""

    def __init__(self, cfg: Config, devices: Optional[list] = None,
                 supervisor: Optional[Supervisor] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.cfg = cfg
        self.log = logger("engine")
        # The clock of everything the engine decides by time: flush
        # ages, window ticks, the overload controller's ticks and the
        # durations its signals are made of, and the deadlines its
        # feed threads sleep to (parallel/feed.park). Tests inject one
        # they advance by hand, so that a loaded machine cannot read
        # as a late device; a clock that can be advanced tells its
        # sleepers (``on_advance``). Span times stay on the wall clock.
        self._clock = clock
        on_advance = getattr(clock, "on_advance", None)
        if on_advance is not None:
            on_advance(self.wake)
        # Supervision (runtime/supervisor.py): when attached, every
        # long-lived engine thread registers a heartbeat with the
        # shared watchdog; standalone engines (tests, bench) get
        # detached Heartbeat cells that nothing scans.
        self._supervisor = supervisor
        if supervisor is not None:
            # The device proxy's two threads belong to no engine: the
            # engine that is supervised has their cells scanned.
            for hb in PROXY_HEARTBEATS:
                supervisor.adopt(hb)
        self.sink = QueueSink(max_blocks=1024)
        self.pcfg = pipeline_config_from(cfg)
        if (
            cfg.data_aggregation_level == "low"
            and self.pcfg.data_aggregation_level == "high"
        ):
            self.log.warning(
                "data_aggregation_level=low requires conntrack metrics; "
                "running at high (full per-packet sketch feeds)"
            )

        devs = devices if devices is not None else jax.devices()
        if cfg.mesh_devices > 0:
            devs = devs[: cfg.mesh_devices]
        self.n_devices = len(devs)
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        self.mesh = Mesh(np.array(devs), ("data",))
        self.sharded = ShardedTelemetry(
            self.pcfg, self.mesh, aot_cache_dir=cfg.aot_cache_dir
        )
        self.state = self.sharded.init_state()
        # Record batches are pre-placed with the step's input sharding
        # OUTSIDE the state lock, so the lock is held only for the async
        # step dispatch (snapshot-without-stall; VERDICT r1 weak #3).
        self._rec_sharding = NamedSharding(self.mesh, PartitionSpec("data"))
        self._replicated = NamedSharding(self.mesh, PartitionSpec())
        # Device-resident scalar constants (lazily placed on the proxy
        # thread): every Python-scalar jit argument costs its own
        # host->device commit per call, several per step before this
        # cache (what a commit costs is unverified on the attached
        # chip).
        self._zero_u32: Any = None
        self._zthresh: Any = None
        self._api_dev: Any = None
        self._api_val: int = -1
        # Bound on concurrent fire-and-forget device submissions: the
        # dispatch worker packs batch N+1 while the proxy thread still
        # owns batch N's transfer, and the proxy queue holds the rest —
        # the host->device link runs back-to-back transfers instead of
        # idling for a dispatch round-trip between quanta (VERDICT r3
        # weak #1).
        self._inflight = threading.Semaphore(cfg.feed_pipeline_depth)
        # Count of dispatches in flight: submitted, and their last step
        # not yet finished ON THE DEVICE (the completion thread says
        # when, _dispatch_done). The dispatch thread dispatches only
        # while it is under feed_pipeline_depth, and holds and folds the
        # flushes whatever the count, until a step's worth is held, the
        # oldest was staged flush_max_age_s ago, or a reader asks
        # (_dispatch_loop; _held_flushes is how many it holds and
        # _held_since when the oldest's first block was staged, for
        # feed_stats).
        self._busy_lock = threading.Lock()
        self._inflight_busy = 0
        self._submitted_n = 0  # dispatches ever submitted; guarded-by: self._busy_lock
        self._held_flushes = 0
        self._held_since: float | None = None  # the oldest's, on the clock
        # The dispatch thread (start() sets it, the thread clears it on
        # its way out) and the readers that wait for it to submit what
        # it holds (_release_held_for_read): requests made, the feed
        # pool's flush request of the last one, and the last one
        # served.
        self._reads = threading.Condition()
        self._dispatch_thread: threading.Thread | None = None  # guarded-by: self._reads
        self._reads_asked = 0  # guarded-by: self._reads
        self._read_epoch = 0  # guarded-by: self._reads
        self._reads_served = 0  # guarded-by: self._reads
        # Flow-descriptor dictionary (parallel/flowdict.py).
        # Host side assigns stable device-table slots; the device table
        # itself is created lazily ON device (zeros jit — a host-side
        # 48MB/device upload would saturate the link it exists to save).
        # heavy_keys_source="invertible" removes the dictionary from the
        # hot path ENTIRELY (ISSUE 7 / ROADMAP item 4): the wire falls
        # back to packed full rows and heavy keys come from the window
        # close invertible decode instead of host descriptor slots.
        self._flow_dict = (
            make_flow_dict(cfg.flow_dict_slots)
            if cfg.wire_flow_dict
            and cfg.heavy_keys_source != "invertible"
            else None
        )
        # Known-flow rows pack DENSE — (id_bits + 10 + 22) contiguous
        # bits per row streamed into one u32 word array (parallel/wire.py
        # dense layer): 6.25 B/row at an 18-bit id space. Rows whose
        # PACKETS/BYTES overflow the narrow lanes (or any new
        # descriptor) ship full rows instead (escalation is idempotent:
        # re-scattering a resident descriptor is a no-op for
        # correctness). Known rows' per-row timestamps are replaced by
        # the flush's base timestamp; rows where exact per-row time
        # matters — TSval/TSecr carriers (RTT matcher) and unstamped
        # rows (TS_REL=0 round-trip) — escalate to the full-row side
        # (see _dispatch_flowdict).
        self._fd_id_bits = max(1, (cfg.flow_dict_slots - 1).bit_length())
        self._fd_lock = threading.Lock()
        # AOT disk-cache signature for the per-bucket ingest
        # executables (_compile_cached): every config field that
        # changes their lowered programs. The topology/jax-version part
        # of the key lives in telemetry.aot_disk_path.
        self._aot_sig = "|".join(
            str(x) for x in (
                cfg.batch_capacity, cfg.flow_dict_slots,
                self._fd_id_bits, NUM_FIELDS,
            )
        )
        # heavy_keys_source="both": host-side per-key packet ground
        # truth (forward-verdict packets by 4-column flow key), fed in
        # _dispatch_flowdict under _fd_lock; the harvest thread scores
        # the invertible decode against it (recall/precision metrics).
        # Cumulative like the device sketches. None = not validating.
        self._hk_counts: Optional[dict] = (
            {} if cfg.heavy_keys_source == "both"
            and self._flow_dict is not None else None
        )  # guarded-by: self._fd_lock
        # Latest decoded heavy-key set (harvest thread writes, readers
        # via invertible_report()).
        self._inv_lock = threading.Lock()
        self._inv_last: Optional[dict] = None  # guarded-by: self._inv_lock
        self._desc_table: Any = None  # guarded-by: self._fd_lock
        # Bumped ONLY by failure resyncs (not by capacity-overflow
        # generation clears, which keep the device table intact and are
        # FIFO-safe for in-flight batches): a queued batch whose epoch
        # predates a resync references a table that no longer exists
        # and must drop itself rather than gather zeroed descriptors.
        self._fd_epoch = 0

        self._ident_lock = threading.Lock()
        self.ident = IdentityMap.zeros(cfg.identity_slots)
        # Sized like the identity table: the default deployment loads
        # every tracked pod IP into the IPs-of-interest map (the metrics
        # module filter sync), so 1024 slots overflowed at ~500 pods.
        self.filter_map = IdentityMap.zeros(cfg.identity_slots, seed=99)
        self.apiserver_ip = 0
        # Persistent host mirror for incremental identity churn: one pod
        # event costs O(chain) host mutations + one upload, not a full
        # re-place of every key (VERDICT r1 weak #5).
        self._ident_host = HostIdentityTable(n_slots=cfg.identity_slots)
        self._ident_dict: dict[int, int] = {}

        # (fn, name) pairs: the name lets the overload controller shed
        # a specific enrichment observer (e.g. "dns") by stage.
        self._observers: list[
            tuple[Callable[[np.ndarray, str], None], str]
        ] = []
        # bucket size -> jitted pad-to-capacity kernel (device-side zero
        # extension of a small transfer to the step's static shape).
        self._pad_cache: dict[int, Any] = {}
        # The ingest programs (by their _pad_cache key) this process has
        # run on the device at least once: a dispatch's transfer_enqueue
        # span says `first` where it is about to run one that is not
        # here (proxy thread only: dispatches and _warm_run_ingest).
        self._ran_keys: set = set()
        self._snap_lock = threading.Lock()
        self._snap_flight = threading.Lock()
        self._snap_cache: dict[str, Any] | None = None
        self._snap_time = 0.0
        # Closed windows' results awaiting publish on the harvest
        # thread (lazily started at the first close). Unbounded BY
        # DESIGN: items are (3,3)-float device handles produced at
        # window cadence (one per window_seconds), so even an
        # hours-long link stall accumulates only trivial host memory —
        # and never shedding means every anomalous window's
        # anomaly_windows increment survives to the next scrape (the
        # counter's contract). Items: ("win", stacked_device_array),
        # ("zero", None) for idle windows (FIFO through the same queue
        # so an in-flight active window can never publish AFTER the
        # idle zeroing and latch a stale anomaly flag), or None to
        # shut the thread down.
        self._harvest_q: queue_mod.Queue = queue_mod.Queue()  # noqa: RT102 — window-cadence items, see above
        self._harvest_thread: threading.Thread | None = None  # guarded-by: self._harvest_lock
        # Set by the shutdown path after the final drain: a straggler
        # (e.g. a warm_close racing stop) must not resurrect the
        # thread, or it would park on the queue forever pinning the
        # engine object graph. The lock serializes spawn-vs-retire: a
        # straggler close checking the flag concurrently with shutdown
        # setting it could otherwise spawn a fresh thread that never
        # sees the None sentinel (already consumed) and parks forever.
        self._harvest_retired = False  # guarded-by: self._harvest_lock
        self._harvest_lock = threading.Lock()
        # Bumped by _restart_harvest when the watchdog replaces a hung
        # harvest thread: a superseded instance exits after finishing
        # (or abandoning) its current item instead of racing the
        # replacement for the queue forever.
        self._harvest_gen = 0
        self._warm_thread: threading.Thread | None = None
        # Set once the background warm has made the window-close
        # program resident (or terminally failed to): until then, while
        # the warm thread is live, window ticks DEFER instead of
        # cold-compiling end_window inline on the proxy mid-feed
        # (windows_deferred counts them; the window just stays open).
        self._close_warmed = threading.Event()
        # Feed worker pool (parallel/feed.py), created by start().
        self._feed_pool: Any = None
        # Adaptive overload control (runtime/overload.py): the feed
        # loop ticks the controller against the engine's pressure
        # signals; feed workers sample through it, plugins consult
        # shed_active before enrichment work.
        self._overload = OverloadController(
            cfg, self._overload_signals, clock=clock
        )
        # Fleet rollup tier (fleet/): ship the device-merged sketch
        # snapshot at every window close instead of raw samples. The
        # shipper owns its worker thread (start()/stop() track the
        # engine run loop); offer() on the proxy never blocks the close
        # path, and the SHEDDING backoff consults the same controller.
        self._fleet_shipper: Any = None
        if cfg.fleet_enabled:
            from retina_tpu.fleet.shipper import SnapshotShipper

            self._fleet_shipper = SnapshotShipper(
                cfg, overload=self._overload, supervisor=self._supervisor
            )
        # Time-travel snapshot ring (timetravel/): retain the same
        # window-close export the fleet shipper puts on the wire, as N
        # host-side slots served to the range-query API. Shares the
        # shipper's offer/worker shape: O(1) enqueue on the close lane,
        # readback off-proxy.
        self._tt_ring: Any = None
        if cfg.timetravel_enabled:
            from retina_tpu.timetravel.ring import SnapshotRing

            self._tt_ring = SnapshotRing(
                cfg.timetravel_ring_windows, name="engine",
                overload=self._overload, supervisor=self._supervisor,
            )
        # Closed-loop capture hook (timetravel/autocapture.py): the
        # daemon wires AutoCapture.notify here; called from the harvest
        # thread when the entropy detector flags a window (must never
        # block — notify only enqueues).
        self.anomaly_hook: Any = None
        # Record tap (detect/base.py DetectorBank.observe): sees every
        # record block on the ingest path before partitioning — in
        # _build_quantum post-combine on the live feed (feed workers;
        # the bank serializes internally), and in
        # _dispatch for direct callers (step_records, recovery probe).
        # The two sites are disjoint, so no block is tapped twice.
        # Must stay cheap — the bank does vectorized feature folds
        # only; scoring happens at window close. Pre-overload-sampling
        # so detectors judge the full signal, not the sampled residue.
        self.record_hook: Any = None
        # Protected close lane: window ticks acquire THIS semaphore,
        # never the step in-flight one — a saturated step pipeline can
        # delay a close behind queued transfers but can never starve it
        # of a submission slot (a window is always eventually closed).
        # Two slots: one close may still be in flight on a slow link
        # when the next tick lands.
        self._close_inflight = threading.Semaphore(2)
        # Device-resident sample-k scalars, cached per k (same
        # rationale as _device_consts; cleared on recovery rebuild).
        self._sampk_dev: dict[int, Any] = {}
        # Overload signal bookkeeping: handoff-wait rate window and the
        # dispatch-latency EWMA (seconds, updated on the proxy thread
        # where device_step_seconds is observed).
        self._ov_wait_prev = 0.0
        self._ov_wait_t = clock()
        self._dispatch_lat_ewma = 0.0
        # Timestamp of the last EWMA sample: a stale measurement means
        # the pipeline is idle, not slow, and must not read as
        # pressure (an idle engine would otherwise never de-escalate).
        self._dispatch_lat_t = 0.0
        self.last_window: dict[str, np.ndarray] = {}
        self._state_lock = threading.Lock()
        self.started = threading.Event()
        # Set once start_background_warm has every reachable bucket key
        # compiled (tests and shutdown fences). bucket_warm_failed is
        # its terminal-failure sibling: set when the warm finished but
        # one or more keys failed (the agent stays up; those buckets
        # cold-compile inline) so waiters can fail fast with the real
        # cause instead of timing out on a done-event that will never
        # come.
        self.bucket_warm_done = threading.Event()
        self.bucket_warm_failed = threading.Event()
        self._steps = 0
        self._events_in = 0
        self._closed_events_in = 0
        # Events the sink accepted that were dropped (and counted under
        # lost_events) before any step held them: the publish watermark
        # must not read them as lag (_count_unheld, publish_lag_s).
        self._events_unheld = 0
        self._unheld_lock = threading.Lock()
        # Crash-only recovery (runtime/supervisor.py wiring): while
        # _degraded is set, async dispatches drop-and-count (stage
        # "degraded") instead of touching device state mid-rebuild;
        # recovery_failed latches when the recovery loop's circuit
        # opens — /healthz goes unhealthy and the orchestrator owns
        # the restart from there.
        self._degraded = threading.Event()
        self._recover_lock = threading.Lock()
        self._recovering = False
        self._recover_thread: threading.Thread | None = None  # guarded-by: self._recover_lock
        self.recovery_failed = threading.Event()
        self.restarts = 0
        self._last_resume_src = ""
        self._snapshot_path = (
            os.path.join(cfg.snapshot_dir, "sketch_state.npz")
            if cfg.snapshot_dir else None
        )
        # Flight recorder (obs/recorder.py): rebuild the process
        # singleton from config so every span site — here, the feed
        # workers, the device proxy, the fleet shipper/aggregator —
        # shares the same rings. Sites outside the engine fetch it via
        # get_recorder() per call, so the rebuild is visible everywhere.
        self._recorder = initialize_recorder(
            capacity=cfg.trace_ring_spans,
            enabled=cfg.trace_enabled,
        )
        self._start_monotonic = time.monotonic()
        self._publish_build_info()

    def _publish_build_info(self) -> None:
        """One-shot build/runtime identity gauge (value always 1; the
        labels are the payload) plus the uptime baseline — the classic
        *_build_info join-series pattern."""
        from retina_tpu.utils import buildinfo

        m = get_metrics()
        dev = self.mesh.devices.ravel()[0]
        m.build_info.labels(
            version=buildinfo.VERSION,
            jax=jax.__version__,
            backend=dev.platform,
            device_kind=dev.device_kind,
            devices=str(self.n_devices),
            config=self._aot_sig,
        ).set(1)
        m.uptime_seconds.set(0.0)

    # -- supervision helpers ------------------------------------------
    def _register_hb(  # runs-on: feed-worker*, engine-recover, window-harvest
        self, name: str, deadline_s: float | None = None,
        on_stall: Optional[Callable[[], None]] = None,
    ) -> Heartbeat:
        dl = deadline_s or self.cfg.watchdog_deadline_s
        if self._supervisor is not None:
            return self._supervisor.register(name, dl, on_stall)
        return Heartbeat(name, dl, on_stall)

    def _deregister_hb(self, name: str) -> None:  # runs-on: feed-worker*
        if self._supervisor is not None:
            self._supervisor.deregister(name)

    def _count_error(self, site: str) -> bool:
        """Broad-except audit contract: every swallowed exception bumps
        engine_errors{site} unconditionally; returns True when the
        caller should also emit its (rate-limited) log line."""
        get_metrics().engine_errors.labels(site=site).inc()
        return rate_limited(f"engine.{site}")

    # -- crash-only recovery ------------------------------------------
    @property
    def degraded(self) -> bool:
        return self._degraded.is_set()

    @staticmethod
    def _fatal_device_error(e: BaseException) -> bool:
        """Classify a step/transfer failure: fatal (device/runtime —
        the resident state is suspect, rebuild it) vs a bad-batch
        one-off (already dropped + counted; carry on)."""
        if isinstance(e, faults.InjectedFault):
            return True
        if type(e).__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
            return True
        msg = str(e).lower()
        return any(
            s in msg
            for s in ("device", "transfer failed", "dma",
                      "resource exhausted", "data loss")
        )

    def _request_recovery(self, reason: str) -> None:
        """Enter degraded drop-and-count mode and kick the recovery
        thread. Idempotent: concurrent fatal errors fold into the one
        in-flight recovery."""
        with self._recover_lock:
            if self._recovering or self.recovery_failed.is_set():
                return
            self._recovering = True
        self._degraded.set()
        get_metrics().degraded_mode.set(1)
        self.log.error(
            "engine entering DEGRADED mode (crash-only recovery): %s",
            reason,
        )
        t = threading.Thread(
            target=self._recover, name="engine-recover", daemon=True
        )
        # Publish under the lock: close()/join readers must never see
        # a half-written reference from a concurrent fatal-error path
        # (the _recovering flip above already serializes spawns, but
        # the reference itself was unguarded).
        with self._recover_lock:
            self._recover_thread = t
        t.start()

    def _recover(self) -> None:
        """Crash-only engine recovery: fence the proxy, tear down and
        rebuild device state, resume from the last periodic checkpoint
        (cold start when there is none), re-warm with a probe dispatch,
        then leave degraded mode. Retries under the restart policy; an
        open circuit latches recovery_failed (unhealthy)."""
        t0 = time.monotonic()
        hb = self._register_hb("engine-recover")
        policy = policy_from_config(self.cfg, seed_key="engine-recover")
        m = get_metrics()
        attempt = 0
        try:
            while True:
                attempt += 1
                hb.beat()
                policy.note_start()
                try:
                    self._recover_once(hb)
                    break
                except Exception:
                    if self._count_error("recovery"):
                        self.log.exception(
                            "engine recovery attempt %d failed", attempt
                        )
                    delay = policy.record_failure()
                    if delay is None:
                        self.log.error(
                            "engine recovery crash-looping; giving up "
                            "(unhealthy until the orchestrator restarts "
                            "the agent)"
                        )
                        self.recovery_failed.set()
                        return
                    hb.park()
                    time.sleep(delay)
            self._degraded.clear()
            m.degraded_mode.set(0)
            m.engine_restarts.inc()
            self.restarts += 1
            dt = time.monotonic() - t0
            m.recovery_seconds.observe(dt)
            self.log.warning(
                "engine recovered in %.2fs (attempt %d, %s)",
                dt, attempt, self._last_resume_src,
            )
        finally:
            with self._recover_lock:
                self._recovering = False
            self._deregister_hb("engine-recover")

    def _recover_once(self, hb: Heartbeat) -> None:
        # Injection site for chaos tests: lets a test hold the engine in
        # degraded mode deterministically (recover:hangN) to observe the
        # drop-and-count path, or fail attempts (recover:raise).
        faults.inject("recover")
        # 1) Drain the proxy queue: no stale closure may touch the
        #    state we are about to replace. Bounded — a wedged proxy
        #    fails this attempt and the policy retries.
        hb.park()
        if not fence(timeout=self.cfg.watchdog_deadline_s):
            raise RuntimeError("device proxy did not drain for recovery")
        hb.beat()
        path = self._snapshot_path

        def rebuild():
            # Device-resident scalars + descriptor table are rebuilt
            # lazily by the next dispatch; the flow dictionary resyncs
            # (epoch bump drops queued pre-recovery batches).
            self._zero_u32 = None
            self._api_val = -1
            self._sampk_dev = {}
            with self._fd_lock:
                self._desc_table = None
                if self._flow_dict is not None:
                    self._flow_dict.clear()
                    self._fd_epoch += 1
            resumed = False
            if path:
                from retina_tpu.checkpoint import load_state

                state, resumed = load_state(path, self.sharded, self.pcfg)
            else:
                state = self.sharded.init_state()
            with self._state_lock:
                self.state = state
            return resumed

        hb.park()  # rebuild may recompile init_state on a cold cache
        resumed = run_on_device(rebuild, kind=mnames.KIND_OTHER)
        hb.beat()
        self._last_resume_src = (
            f"resumed from {path}" if resumed else "cold start"
        )
        # 2) Probe: one zero-batch dispatch through the real transfer +
        #    step path proves the device works end to end before async
        #    traffic is readmitted.
        hb.park()
        self._dispatch(
            np.zeros((0, NUM_FIELDS), np.uint32),
            now_s=int(time.time()), record_metrics=False,
        )
        hb.beat()

    # -- identity / filter wiring (set by cache & filtermanager) ------
    def update_identities(self, ip_to_index: dict[int, int]) -> None:
        """Reconcile the device identity table to ``ip_to_index``.

        Incremental: diffs against the previous map and applies only
        changed keys to the persistent host cuckoo table (µs per key),
        then uploads the packed table once. The reference's enricher
        cache likewise mutates one entry per pod event (cache.go:196+).
        """
        new = {ip: idx for ip, idx in ip_to_index.items() if ip != 0}
        if len(new) > self._ident_host.capacity:
            # Clamp-and-count, never crash: an overfull cluster loses
            # observability for the overflow pods (visible in
            # lost_table_entries{table="identity"}) but the agent stays
            # up — the reference likewise counts per-entry map-write
            # failures and carries on (manager_linux.go:62-100).
            # Deterministic subset (sorted IPs) so repeated reconciles
            # keep the SAME pods rather than churning the table. The
            # clamp happens before the diff so a failed insert never
            # leaves the host table half-mutated with _ident_dict stale.
            dropped = len(new) - self._ident_host.capacity
            get_metrics().lost_table_entries.labels(
                table="identity"
            ).inc(dropped)
            self.log.warning(
                "identity map overfull: %d pods into %d slots; "
                "dropping %d (counted in lost_table_entries)",
                len(new), self._ident_host.capacity, dropped,
            )
            new = dict(
                (ip, new[ip])
                for ip in sorted(new)[: self._ident_host.capacity]
            )
        with self._ident_lock:
            old = self._ident_dict
            for ip in old.keys() - new.keys():
                self._ident_host.remove(ip)
            for ip, idx in new.items():
                if old.get(ip) != idx:
                    self._ident_host.insert(ip, idx)
            self._ident_dict = new

        # Upload AND swap inside one proxied closure: dispatches capture
        # self.ident at proxy-execution time, so FIFO order on the
        # proxy queue is exactly the visibility order — an identity
        # update enqueued before a batch's execution is guaranteed
        # visible to that batch, even when compiles/warm keys delay the
        # queue by seconds. The packed table is SNAPSHOTTED here, at
        # call time: uploading the live shared _ident_host from the
        # closure would let a later-enqueued update's host mutations
        # leak into this earlier-enqueued upload (visibility skew in
        # the other direction).
        with self._ident_lock:
            packed = self._ident_host.table.copy()
            seed = self._ident_host.seed

        def apply_ident():
            dev = IdentityMap(table=jnp.asarray(packed), seed=seed)
            with self._ident_lock:
                self.ident = dev

        run_on_device(apply_ident, kind=mnames.KIND_TABLE)

    def update_filter_ips(self, ips: set[int]) -> None:
        # Build the cuckoo table on the CALLING thread (pure numpy, O(n)
        # host work); only the device upload ties up the proxy thread.
        host = HostIdentityTable(n_slots=self.cfg.identity_slots, seed=99)
        live = sorted(ip for ip in ips if ip)
        if len(live) > host.capacity:
            # Clamp-and-count (deterministic: lowest IPs win) — an
            # overfull IPs-of-interest set must degrade coverage, not
            # kill the agent; retrying can't fix a deterministic
            # overflow (VERDICT r3 weak #4).
            dropped = len(live) - host.capacity
            get_metrics().lost_table_entries.labels(
                table="filter"
            ).inc(dropped)
            self.log.warning(
                "filter map overfull: %d IPs into %d slots; dropping %d "
                "(counted in lost_table_entries)",
                len(live), host.capacity, dropped,
            )
            live = live[: host.capacity]
        for ip in live:
            host.insert(ip, 1)

        # Upload AND swap in one proxied closure (see update_identities
        # above): a filter update enqueued before a batch executes is
        # visible to that batch — the pre-r5 swap-after-return left a
        # window where a one-shot traffic burst dispatched behind a
        # slow proxy queue was filtered by the OLD (possibly empty)
        # map, dropping it silently.
        def apply_filter():
            fmap = host.to_device()
            with self._ident_lock:
                self.filter_map = fmap

        run_on_device(apply_filter, kind=mnames.KIND_TABLE)

    def set_apiserver_ips(self, ips: list[int]) -> None:
        self.apiserver_ip = ips[0] if ips else 0

    def add_observer(
        self, fn: Callable[[np.ndarray, str], None], name: str = ""
    ) -> None:
        """Observers see every accepted record block on the feed thread
        (dns tally, flow export...). Must be fast and never raise.
        ``name`` ties an observer to an overload shed stage: while that
        stage is shed (runtime/overload.py) the observer is skipped and
        the skipped events are counted under events_shed{stage}."""
        self._observers.append((fn, name))

    def _device_consts(self):
        """(proxy thread) Lazily place the replicated scalar constants
        reused across step/window calls, refreshing the apiserver scalar
        when it changed."""
        if self._zero_u32 is None:
            self._zero_u32 = jax.device_put(
                np.uint32(0), self._replicated
            )
            self._zthresh = jax.device_put(
                np.float32(4.0), self._replicated
            )
        api = self.apiserver_ip  # single read: a concurrent
        # set_apiserver_ips must not land between the device_put and the
        # bookkeeping below, or the stale scalar would latch forever
        if self._api_val != api:
            self._api_dev = jax.device_put(
                np.uint32(api & 0xFFFFFFFF), self._replicated
            )
            self._api_val = api

    def _sampk(self, k: int):
        """(proxy thread) Device-resident sample-k scalar, cached per
        distinct k (in practice: 1 and overload_sample_k). Same
        rationale as _device_consts — a Python-scalar jit argument
        costs a host->device commit per call."""
        dev = self._sampk_dev.get(k)
        if dev is None:
            dev = jax.device_put(np.uint32(k), self._replicated)
            self._sampk_dev[k] = dev
        return dev

    # -- lifecycle ----------------------------------------------------
    def compile(self) -> None:
        """Warm the STEADY-STATE jit keys (the clang-compile analog) so
        the feed loop and the first scrape never pay compile latency:
        the full-capacity step, the window close + both snapshot
        programs, and the minimum wire bucket for every dispatch path.

        Deliberately NOT warmed here: the rest of the bucket grid.
        Warming every reachable bucket on the boot critical path cost a
        96s agent boot on a cold persistent cache (BENCH_r04) against
        the reference's 10s plugin-reconcile SLA
        (pluginmanager.go:25-28); the daemon warms the remaining grid in
        the background AFTER ready (start_background_warm), one proxy
        call per key so live dispatches interleave."""
        t0 = time.perf_counter()

        def mark(stage: str) -> None:
            self.log.info(
                "compile: %s at +%.1fs", stage, time.perf_counter() - t0
            )

        # Full-capacity dispatch (the steady-state jit key: packed-wire
        # ingest at bucket == batch_capacity + the step with
        # device-resident scalars) through the REAL dispatch path.
        full = ShardedBatch(
            records=np.zeros(
                (self.n_devices, self.cfg.batch_capacity, NUM_FIELDS),
                np.uint32,
            ),
            n_valid=np.zeros((self.n_devices,), np.uint32),
            lost=0,
        )
        self._dispatch_sharded(full, now_s=1, n_raw=0,
                               record_metrics=False)
        mark("full-capacity dispatch")

        # Window-close + snapshot programs warm in the BACKGROUND
        # (start_background_warm runs them before the bucket grid):
        # they gate only the first scrape / first window tick — not the
        # feed path — and their ~18s of warm-cache load time was most
        # of the boot critical path (44.9s observed in BENCH r5 dry
        # run). A scrape or window tick arriving inside the background
        # warm window compiles inline, exactly as a cold key would.
        # Warm the smallest plain bucket (idle and small flushes); the
        # rest of the bucket ladder is start_background_warm's job.
        self._dispatch(
            np.zeros((0, NUM_FIELDS), np.uint32), now_s=1,
            record_metrics=False,
        )
        mark("min plain bucket")
        # The min-bucket flow-dict pair (small-flush keys) is
        # NOT warmed here: it is the first grid entry in
        # start_background_warm (~12s of warm-cache load that would
        # otherwise sit on the ready path); a trickle flush arriving
        # before that warm lands compiles inline.
        self.log.info(
            "engine compiled: %d device(s), batch=%d, %.1fs",
            self.n_devices, self.cfg.batch_capacity,
            time.perf_counter() - t0,
        )

    def _reachable_buckets(self) -> list[int]:
        """Every wire bucket a dispatch can produce: the quantized
        ladder (_next_bucket) from the minimum transfer bucket up to
        batch_capacity * feed_coalesce_windows, inclusive."""
        coal_cap = (
            self.cfg.batch_capacity
            * max(1, self.cfg.feed_coalesce_windows)
        )
        b = self._wire_bucket(0)
        out = [b]
        while b < coal_cap:
            b = min(_next_bucket(b + 1), coal_cap)
            out.append(b)
        return out

    def _warm_close_job(self) -> None:  # runs-on: device-proxy
        """A REAL window close (with the close path's bookkeeping): its
        result rides the harvest queue like any window tick, so traffic
        (and any anomaly) ingested between ready and this warm
        publishes instead of vanishing — the only side effect is that
        the first entropy window is shorter than window_seconds."""
        ingested = self._events_in
        meta = self._overload.window_annotation()
        meta["events"] = ingested - self._closed_events_in
        with self._state_lock:
            self.state, win = self.sharded.end_window(
                self.state, self._zthresh
            )
        stacked = self._win_stack(win)
        self._closed_events_in = ingested
        self._ensure_harvest_thread()
        self._harvest_q.put(("win", stacked, meta))
        get_metrics().windows_closed.inc()

    def _warm_snap_job(self) -> None:  # runs-on: device-proxy
        snap = self.sharded.snapshot(self.state, 1)
        jax.block_until_ready(snap["totals"])

    def _warm_snap_flat_job(self) -> None:  # runs-on: device-proxy
        self.sharded.snapshot_host(self.state, 1)

    def _warm_jobs(self) -> list[tuple[Any, Callable, tuple]]:
        """The background-warm job list, in execution order.

        ``warm_close`` comes FIRST — before even the min-bucket dispatch
        pair: the first live window tick fires window_seconds after
        boot, almost always before any grid key finishes, and it used
        to beat the queued warm and cold-compile end_window inline on
        the proxy mid-feed (the r05 stall). With the close warm at the
        head of the FIFO proxy queue — and _close_window_impl deferring
        ticks until it lands — the first real close always finds the
        program resident. Then the min-bucket dispatch pair (a trickle
        feed needs it on its very first small flush), the snapshot
        programs (first scrape, in production 15-30s after boot), then
        the rest of the grid in ramp order. All moved off compile()'s
        critical path — together they were ~30s of the 45s boot
        observed in the r5 dry run.

        One flat job list, one throttle policy: every entry is a single
        proxied call followed by a yield, so live dispatches wait
        behind at most ONE trace+lower (multi-program closures parked
        the proxy ~18s)."""
        jobs: list[tuple[Any, Callable, tuple]] = [
            ("window close", self._warm_close_job, ()),
        ]
        if self._flow_dict is not None:
            # Flow-dict dispatch needs the device descriptor table on
            # its very first batch; building it here keeps even that
            # zeros-jit compile off the event path (it also seeds the
            # AOT disk cache entry a post-resync rebuild will hit).
            jobs.append(("desc table", self._ensure_desc_table, ()))
        buckets = self._reachable_buckets()
        for i, b in enumerate(buckets):
            if self._flow_dict is not None:
                jobs.append((("known", b), self._ingest_known_fn, (b,)))
                jobs.append((("new", b), self._ingest_new_fn, (b,)))
                # Resident is not run: see _warm_run_ingest.
                jobs.append((("run", b), self._warm_run_ingest, (b,)))
            else:
                jobs.append((b, self._ingest_fn, (b,)))
            if i == 0:
                if self._flow_dict is not None:
                    jobs.append((
                        ("fold", self.cfg.batch_capacity),
                        self._fold_sides_fn, (),
                    ))
                jobs.append(("snapshot", self._warm_snap_job, ()))
                jobs.append(
                    ("snapshot flat", self._warm_snap_flat_job, ())
                )
        return jobs

    def _warm_run_ingest(self, bucket: int) -> None:  # runs-on: device-proxy
        """(proxy thread) Run the flow-dict ingest pair of one bucket
        once, on all-padding wires of the bucket's own shapes (no valid
        row: the new side writes zeros to the sacrificial slot 0 of the
        descriptor table and nothing else). A resident program that has
        never run, and a transfer larger than any before it, are not
        warm on the chip: since the dispatch thread folds by what is
        held and not by what the device's idling hands it, a dispatch's
        bucket varies from one to the next, and the first dispatch of a
        bucket inside a measured window stalled its transfer and the
        completions behind it for 1.8-5 s (PERF.md, PR 34). The wires
        are the host->device traffic the first real dispatch of the
        bucket would have been."""
        from retina_tpu.parallel.wire import PACKED_FIELDS, dense_words

        D = self.n_devices
        meta = np.zeros((5 + D,), np.uint32)
        new_dev, known_dev, meta_dev = jax.device_put(
            (np.zeros((D, bucket, PACKED_FIELDS + 1), np.uint32),
             np.zeros((D, dense_words(bucket, self._fd_id_bits)),
                      np.uint32), meta),
            (self._rec_sharding, self._rec_sharding, self._replicated),
        )
        with self._fd_lock:
            epoch = self._fd_epoch
        table = self._ensure_desc_table()
        *_, table = self._ingest_new_fn(bucket)(new_dev, meta_dev, table)
        # As _dispatch_flowdict stores it: a resync meanwhile has
        # cleared the table this one was built against.
        with self._fd_lock:
            if self._fd_epoch == epoch:
                self._desc_table = table
        out = self._ingest_known_fn(bucket)(known_dev, meta_dev, table)
        jax.block_until_ready(out)
        self._ran_keys.update((("new", bucket), ("known", bucket)))

    def start_background_warm(
        self, stop: threading.Event | None = None
    ) -> threading.Thread:
        """Warm every remaining reachable bucket key OFF the boot
        critical path (VERDICT r4 #2: agent ready in <=15s).

        Runs on its own thread, one ``run_on_device`` per key: the
        window-close program first (see :meth:`_warm_jobs`), then the
        grid smallest bucket first — the proxy queue is FIFO, so a live
        dispatch waits behind at most ONE in-flight warm compile, and a
        post-ready feed ramps through the small/mid buckets before
        saturation reaches the multi-window keys — warming in ramp
        order (small keys also compile fastest) keeps the window where
        a reachable bucket is still cold as short as possible. A bucket
        the feed reaches before its warm simply compiles inline exactly
        as it would have — the warm then finds the key cached and skips
        it.
        ``bucket_warm_done`` is set when the grid is fully resident
        (tests fence on it). ``stop`` is checked between keys; an
        IN-FLIGHT compile cannot be aborted, so a shutdown racing the
        warm still waits for at most one key."""
        def _warm() -> None:
            t0 = time.perf_counter()
            n_warmed = 0
            n_failed = 0
            hb = self._register_hb("engine-bucket-warm")
            # Bounded duty-cycle scheduler: after each warmed key the
            # thread yields cost*(1-d)/d seconds (capped below) so live
            # dispatches interleave. d=0.5 is the historical equal
            # yield (~50% proxy share); bench raises it to finish the
            # warm faster while measurement waits on it.
            duty = min(max(self.cfg.warm_duty_cycle, 0.05), 1.0)
            try:
                jobs = self._warm_jobs()
                for key, fn, args in jobs:
                    if stop is not None and stop.is_set():
                        return
                    if key in self._pad_cache:
                        continue
                    ok = True
                    tk = time.perf_counter()
                    # A cold-cache trace+lower legitimately parks the
                    # proxy for 30-100s — parked, not stalled.
                    hb.park()
                    try:
                        run_on_device(fn, *args, kind=mnames.KIND_OTHER)
                        n_warmed += 1
                    except Exception:
                        ok = False
                        n_failed += 1
                        self._count_error("warm_key")
                        self.log.exception(
                            "background warm failed at %s", key
                        )
                    hb.beat()
                    if key == "window close":
                        # Resident — or terminally failed, in which
                        # case ticks must stop deferring and take the
                        # inline compile (better a one-off stall than
                        # windows that never close).
                        self._close_warmed.set()
                    if not ok:
                        continue
                    # Yield to live traffic: each key's trace+lower
                    # parks the proxy for seconds; back-to-back keys
                    # halved the live feed rate for the whole warm.
                    # The per-key yield is capped at 10s (beyond it —
                    # pathological compiles — finishing the warm wins
                    # over fairness).
                    sl = min(
                        (time.perf_counter() - tk)
                        * (1.0 - duty) / duty,
                        10.0,
                    )
                    if sl <= 0:
                        continue
                    hb.park()  # the yield is a wait, not work
                    if stop is not None:
                        stop.wait(sl)
                    else:
                        time.sleep(sl)
                if n_failed:
                    # A failed key means a reachable bucket can still
                    # cold-compile mid-feed — the done event must NOT
                    # claim otherwise.
                    self.log.warning(
                        "bucket grid warm incomplete: %d key(s) failed",
                        n_failed,
                    )
                    self.bucket_warm_failed.set()
                    return
                self.bucket_warm_done.set()
                if n_warmed:
                    self.log.info(
                        "bucket grid warm: %d key(s) in %.1fs "
                        "(background)",
                        n_warmed, time.perf_counter() - t0,
                    )
            except Exception:
                self._count_error("warm")
                self.log.exception("background bucket warm died")
            finally:
                self._deregister_hb("engine-bucket-warm")

        t = threading.Thread(
            target=_warm, name="engine-bucket-warm", daemon=True
        )
        self._warm_thread = t
        t.start()
        return t

    def step_records(self, records: np.ndarray, now_s: int | None = None) -> None:  # hot-path: event
        """Feed one host block synchronously (tests / direct callers)."""
        self._dispatch(records, now_s or int(time.time()))

    def _dispatch(  # hot-path: event
        self, records: np.ndarray, now_s: int,
        record_metrics: bool = True,
    ) -> None:
        if self.record_hook is not None:
            try:
                self.record_hook(records, now_s)
            except Exception:
                self._count_error("record_hook")
        sb = partition_events(
            records, self.n_devices, self.cfg.batch_capacity,
            min_bucket=self.cfg.transfer_min_bucket,
        )
        self._dispatch_sharded(sb, now_s, n_raw=len(records),
                               record_metrics=record_metrics)

    def _compile_cached(self, tag: str, key, lower):  # runs-on: device-proxy # may-block: AOT disk-cache consult — the warm jobs prefill every reachable key at startup; a miss is once-per-shape and a <10s disk load beats a 100s+ recompile
        """Compile one per-bucket ingest executable, consulting the AOT
        disk cache first. ``lower`` is a thunk returning the
        ``jax.stages.Lowered``; on a miss its compiled executable is
        persisted via ``serialize_executable`` keyed by (jax version,
        topology, engine config signature, tag, bucket key) — a
        restarted daemon then warms the whole bucket grid by
        deserializing instead of re-lowering every key, which is what
        turns the 214s r05 bucket warm into a <10s disk load. Same
        format, path scheme, and hit/miss counters as the telemetry
        step programs (telemetry.aot_disk_*)."""
        from retina_tpu.parallel.telemetry import (
            aot_disk_load, aot_disk_path, aot_disk_save,
        )

        path = None
        if self.cfg.aot_cache_dir:
            path = aot_disk_path(
                self.cfg.aot_cache_dir, self.mesh, tag,
                self._aot_sig, key,
            )
            ex = aot_disk_load(path, self.mesh, tag=tag)
            if ex is not None:
                return ex
        ex = lower().compile()
        note_op_scopes(ex)
        if path is not None:
            aot_disk_save(path, ex, tag=tag)
        return ex

    @device_entry("engine.ingest", kind="jit")
    def _ingest_fn(self, bucket: int):  # runs-on: device-proxy
        """Per-bucket jit that turns ONE transferred (D, bucket, P) wire
        array + a small metadata vector into step-ready device inputs:
        unpack the 12-lane wire format, slice the bucket
        into ceil(bucket/capacity) windows of the step's static
        (D, B, 16) shape (zero-extending the last), and derive each
        window's validity counts — the host->device link carries only the
        bucketed packed rows plus one metadata vector per flush; HBM
        bandwidth makes the expansion free. Coalescing several windows
        into one transfer amortizes per-transfer round-trip latency
        (VERDICT r3 weak #1).

        meta layout (u32): [base_lo, base_hi, now_s, lost, n_valid[D]].
        Returns (windows, window_n_valid, now_s, lost) — all on device,
        so the following step dispatches move no further host data.
        """
        fn = self._pad_cache.get(bucket)
        if fn is None:
            cap = self.cfg.batch_capacity
            n_win = max(1, -(-bucket // cap))
            from functools import partial as _partial

            from retina_tpu.parallel.wire import (
                PACKED_FIELDS, unpack_records_device,
            )

            out_sh = (
                (self._rec_sharding,) * n_win,
                (self._rec_sharding,) * n_win,
                self._replicated,
                self._replicated,
            )

            # donate_argnums=(0,): the wire array is freshly device_put
            # per flush and read exactly once here — donating it lets
            # XLA reuse the transfer buffer for the unpacked windows
            # instead of allocating a second (D, bucket, 16) block
            # (RT302; found by the device-program donation audit).
            @_partial(jax.jit, out_shardings=out_sh, donate_argnums=(0,))
            def ingest(small, meta):
                with jax.named_scope(SCOPE_INGEST_UNPACK):
                    small = unpack_records_device(small, meta[0], meta[1])
                    nv = meta[5:].astype(jnp.int32)
                    wins, nvs = [], []
                    for w in range(n_win):
                        lo = w * cap
                        hi = min(lo + cap, bucket)
                        c = small[:, lo:hi]
                        if hi - lo < cap:
                            c = jnp.pad(
                                c, ((0, 0), (0, cap - (hi - lo)), (0, 0))
                            )
                        wins.append(c)
                        nvs.append(
                            jnp.clip(nv - lo, 0, hi - lo).astype(
                                jnp.uint32
                            )
                        )
                return tuple(wins), tuple(nvs), meta[2], meta[3]

            # AOT-compile from shape specs: warming a bucket key moves
            # NO data over the host->device link (a real-array warm of a
            # 2M-row bucket would push ~100MB across it), and a
            # cache miss at feed time costs only the compile (persistent
            # XLA cache across restarts), never a mid-feed trace+infer
            # surprise on the proxy thread.
            fn = self._compile_cached("ingest", bucket, lambda: ingest.lower(
                jax.ShapeDtypeStruct(
                    (self.n_devices, bucket, PACKED_FIELDS), jnp.uint32,
                    sharding=self._rec_sharding,
                ),
                jax.ShapeDtypeStruct(
                    (5 + self.n_devices,), jnp.uint32,
                    sharding=self._replicated,
                ),
            ))
            self._pad_cache[bucket] = fn
        return fn

    # -- flow-descriptor dictionary path ------------------------------
    def _flowdict_resync(self) -> None:
        """Invalidate host dict + device table together after a failure
        that may have desynced them (one descriptor re-upload burst, no
        wrong data) and fence off in-flight batches built against the
        old table."""
        with self._fd_lock:
            self._flow_dict.clear()
            self._fd_epoch += 1
            self._desc_table = None

    @device_entry("engine.desc_table", kind="jit")
    def _desc_table_fn(self):
        """Zeros-on-device jit for the descriptor table (split from
        _ensure_desc_table so the device-program analysis can lower
        and audit the program without executing the ensure path)."""
        from functools import partial as _partial

        from retina_tpu.parallel.wire import PACKED_FIELDS

        shape = (
            self.n_devices, self.cfg.flow_dict_slots, PACKED_FIELDS,
        )

        @_partial(jax.jit, out_shardings=self._rec_sharding)
        def mk():
            return jnp.zeros(shape, jnp.uint32)

        return mk

    def _ensure_desc_table(self):  # runs-on: device-proxy
        """(proxy thread) Device descriptor table, created by a zeros
        jit ON device — never uploaded from host. The build runs
        outside _fd_lock; only this proxy-thread method CREATES the
        table, so a concurrent resync can at worst clear the slot, and
        storing a freshly-zeroed table over that clear is exactly the
        state a resync wants.

        Routed through _compile_cached: _desc_table_fn builds a FRESH
        jit closure per call, so every resync used to re-trace and
        recompile the zeros program inline on the dispatch lane
        (RT401) — the AOT disk cache turns that into a one-time cost,
        and the desc-table background warm job (see _warm_jobs) moves
        even the first touch off the event path."""
        with self._fd_lock:
            table = self._desc_table
        if table is None:
            mk = self._desc_table_fn()
            ex = self._compile_cached("desc_table", "zeros", mk.lower)
            table = ex()
            with self._fd_lock:
                self._desc_table = table
        return table

    @staticmethod
    def _slice_windows(full, nv_i32, bucket: int, cap: int):
        """(traced) Slice a (D, bucket, 16) array into step windows of
        the static (D, cap, 16) shape with per-window validity counts
        (same contract as _ingest_fn's window loop)."""
        n_win = max(1, -(-bucket // cap))
        wins, nvs = [], []
        for w in range(n_win):
            lo = w * cap
            hi = min(lo + cap, bucket)
            c = full[:, lo:hi]
            if hi - lo < cap:
                c = jnp.pad(c, ((0, 0), (0, cap - (hi - lo)), (0, 0)))
            wins.append(c)
            nvs.append(
                jnp.clip(nv_i32 - lo, 0, hi - lo).astype(jnp.uint32)
            )
        return tuple(wins), tuple(nvs)

    @device_entry("engine.ingest_new", kind="jit")
    def _ingest_new_fn(self, bucket: int):  # runs-on: device-proxy
        """Per-bucket jit for NEW flow descriptors: (D, bucket, 13) wire
        of [table_id | 12 packed lanes] + meta + descriptor table ->
        scatter the lanes into the table (donated; id 0 is the overflow
        sentinel slot, sacrificial), unpack, slice into step windows.

        Reference analog: the first packet of a flow inserting its key
        into the kernel map (conntrack.c ct_create entry) — descriptor
        becomes resident; only counters travel afterwards.
        """
        key = ("new", bucket)
        fn = self._pad_cache.get(key)
        if fn is None:
            cap = self.cfg.batch_capacity
            n_win = max(1, -(-bucket // cap))
            from functools import partial as _partial

            from retina_tpu.parallel.wire import (
                PACKED_FIELDS, unpack_records_device,
            )

            out_sh = (
                (self._rec_sharding,) * n_win,
                (self._rec_sharding,) * n_win,
                self._replicated,
                self._replicated,
                self._rec_sharding,
            )

            # donate (0, 2): the descriptor table (2) was always
            # donated (scatter in place); the wire array (0) is also
            # single-use per flush — fresh device_put, read once —
            # so its transfer buffer is reusable too (RT302; found by
            # the device-program donation audit).
            @_partial(
                jax.jit, out_shardings=out_sh, donate_argnums=(0, 2)
            )
            def ingest(wire, meta, table):
                with jax.named_scope(SCOPE_INGEST_UNPACK):
                    ids = wire[..., 0]
                    lanes = wire[..., 1:]
                    d_idx = jnp.arange(lanes.shape[0])[:, None]
                    table = table.at[d_idx, ids].set(lanes)
                    full = unpack_records_device(lanes, meta[0], meta[1])
                    nv = meta[5:].astype(jnp.int32)
                    wins, nvs = SketchEngine._slice_windows(
                        full, nv, bucket, cap
                    )
                return wins, nvs, meta[2], meta[3], table

            fn = self._compile_cached("ingest_new", key, lambda: ingest.lower(
                jax.ShapeDtypeStruct(
                    (self.n_devices, bucket, PACKED_FIELDS + 1),
                    jnp.uint32, sharding=self._rec_sharding,
                ),
                jax.ShapeDtypeStruct(
                    (5 + self.n_devices,), jnp.uint32,
                    sharding=self._replicated,
                ),
                jax.ShapeDtypeStruct(
                    (
                        self.n_devices, self.cfg.flow_dict_slots,
                        PACKED_FIELDS,
                    ),
                    jnp.uint32, sharding=self._rec_sharding,
                ),
            ))
            self._pad_cache[key] = fn
        return fn

    @device_entry("engine.ingest_known", kind="jit")
    def _ingest_known_fn(self, bucket: int):  # runs-on: device-proxy
        """Per-bucket jit for KNOWN flows: counter wire + meta +
        descriptor table -> gather the resident 12-lane descriptors
        from HBM, overlay the per-quantum counters, unpack, slice into
        step windows. meta[4] is the biased TS_REL flag for every known
        row (1 = stamped at the flush base meta[0:2], 0 = unstamped
        flush).

        Wire layout: a (D, W) bitstream of (id_bits + 10 + 22)-bit rows
        (parallel/wire.py dense layer) — 6.25 B/row at an 18-bit id
        space instead of the 48 B full row; the device side unpacks
        with two-word gathers.

        Reference analog: the kernel map hit path — established flows
        move counters only (conntrack.c ct_process_packet accumulate).
        """
        key = ("known", bucket)
        fn = self._pad_cache.get(key)
        if fn is None:
            cap = self.cfg.batch_capacity
            n_win = max(1, -(-bucket // cap))
            from functools import partial as _partial

            from retina_tpu.parallel.wire import (
                PACKED_FIELDS, dense_known_unpack_device, dense_words,
                unpack_records_device,
            )

            out_sh = (
                (self._rec_sharding,) * n_win,
                (self._rec_sharding,) * n_win,
                self._replicated,
                self._replicated,
            )

            # donate_argnums=(0,): the counter wire is single-use per
            # flush (RT302). The descriptor table (2) must NOT be
            # donated: it is RESIDENT — the same buffer is read by
            # every subsequent known-flow flush.
            @_partial(jax.jit, out_shardings=out_sh, donate_argnums=(0,))
            def ingest(wire, meta, table):
                with jax.named_scope(SCOPE_INGEST_UNPACK):
                    ids, pk, by = dense_known_unpack_device(
                        wire, bucket, self._fd_id_bits
                    )
                    d_idx = jnp.arange(ids.shape[0])[:, None]
                    desc = table[d_idx, ids]  # (D, bucket, 12)
                    desc = desc.at[..., 6].set(pk)  # PACKETS
                    desc = desc.at[..., 5].set(by)  # BYTES
                    desc = desc.at[..., 0].set(
                        jnp.broadcast_to(meta[4], ids.shape)  # TS_REL
                    )
                    full = unpack_records_device(desc, meta[0], meta[1])
                    nv = meta[5:].astype(jnp.int32)
                    wins, nvs = SketchEngine._slice_windows(
                        full, nv, bucket, cap
                    )
                return wins, nvs, meta[2], meta[3]

            fn = self._compile_cached("ingest_known", key, lambda: ingest.lower(
                jax.ShapeDtypeStruct(
                    (self.n_devices, dense_words(bucket, self._fd_id_bits)),
                    jnp.uint32, sharding=self._rec_sharding,
                ),
                jax.ShapeDtypeStruct(
                    (5 + self.n_devices,), jnp.uint32,
                    sharding=self._replicated,
                ),
                jax.ShapeDtypeStruct(
                    (
                        self.n_devices, self.cfg.flow_dict_slots,
                        PACKED_FIELDS,
                    ),
                    jnp.uint32, sharding=self._rec_sharding,
                ),
            ))
            self._pad_cache[key] = fn
        return fn

    @device_entry("engine.fold_sides", kind="jit")
    def _fold_sides_fn(self):  # runs-on: device-proxy
        """The jit that folds the new side's window and the known
        side's into ONE step window, for a flush whose two sides
        together fit one (``_dispatch_flowdict``: the step costs the
        same whatever it holds, so a flush with a few new rows and a
        few known ones used to pay it twice). Per device: the new
        window's ``na`` valid rows, then the known window's rows from
        position ``na`` on; what lies past ``na + nb`` is padding the
        step masks by its validity count, like any window's."""
        key = ("fold", self.cfg.batch_capacity)
        fn = self._pad_cache.get(key)
        if fn is None:
            cap = self.cfg.batch_capacity
            out_sh = (self._rec_sharding, self._rec_sharding)

            # Both windows are single-use outputs of this flush's
            # ingest programs; there is one output to reuse a buffer.
            fold = jax.jit(
                fold_side_windows, out_shardings=out_sh,
                donate_argnums=(0,),
            )
            win = jax.ShapeDtypeStruct(
                (self.n_devices, cap, NUM_FIELDS), jnp.uint32,
                sharding=self._rec_sharding,
            )
            nv = jax.ShapeDtypeStruct(
                (self.n_devices,), jnp.uint32,
                sharding=self._rec_sharding,
            )
            fn = self._compile_cached(
                "fold_sides", key, lambda: fold.lower(win, nv, win, nv)
            )
            self._pad_cache[key] = fn
        return fn

    def _wire_bucket(self, n_max: int) -> int:
        cap_total = self.cfg.batch_capacity * max(
            1, self.cfg.feed_coalesce_windows
        )
        return min(
            _next_bucket(max(n_max, self.cfg.transfer_min_bucket)),
            cap_total,
        )

    def _hk_account(self, rows: np.ndarray) -> None:  # runs-on: feed-worker*
        """("both" mode) Fold one dispatch's forward-verdict packets
        into the host ground-truth dict, keyed exactly like the device
        invertible/flow sketches: (src_ip, dst_ip, ports, proto). Caller
        holds self._fd_lock. Counts are post-sampling (unscaled) — under
        SAMPLING the heavy/priority tiers are exempt, so ground truth
        for keys at/above the heavy threshold stays exact."""
        from retina_tpu.events.schema import VERDICT_FORWARDED

        fwd = rows[:, F.VERDICT] == VERDICT_FORWARDED
        if not fwd.any():
            return
        r = rows[fwd]
        keys = np.stack(
            [r[:, F.SRC_IP], r[:, F.DST_IP], r[:, F.PORTS],
             r[:, F.META] >> np.uint32(24)],
            axis=1,
        ).astype(np.uint32)
        pk = r[:, F.PACKETS].astype(np.uint64)
        uniq, inv = np.unique(keys, axis=0, return_inverse=True)
        sums = np.zeros(len(uniq), np.uint64)
        np.add.at(sums, inv, pk)
        hk = self._hk_counts
        for kb, s in zip((u.tobytes() for u in uniq), sums):
            hk[kb] = hk.get(kb, 0) + int(s)

    def _dispatch_flowdict(
        self, sb: "ShardedBatch", now_s: int, n_raw: int,
        sync: bool, record_metrics: bool, n_flushes: int = 1,
        cause: str = "",
    ) -> None:
        """Flow-dictionary dispatch: split the partitioned batch into
        new-descriptor rows (full 12-lane upload + table insert) and
        known rows (dense [id, packets, bytes] bit-rows against the
        resident table, see __init__). Known rows whose packet or byte
        count overflows its narrow lane escalate to the new side
        (idempotent re-scatter). Both ride one proxy submission,
        FIFO-ordered so inserts land before gathers."""
        from retina_tpu.parallel.wire import (
            DENSE_BY_BITS, DENSE_PK_BITS, batch_ts_base,
            dense_known_rows, dense_words, pack_records,
        )

        tid = fleet_epoch(self.cfg.window_seconds)
        sp_build = self._recorder.span(mnames.STAGE_WIRE_BUILD, tid)
        m = get_metrics()
        lost = sb.lost
        D = self.n_devices
        with self._fd_lock:
            gen0 = self._flow_dict.generation
            per_dev = []
            for d in range(D):
                nv = int(sb.n_valid[d])
                rows = sb.records[d, :nv]
                ids, is_new = self._flow_dict.lookup_or_assign(rows)
                per_dev.append((rows, ids, is_new))
                if self._hk_counts is not None and len(rows):
                    self._hk_account(rows)
            epoch = self._fd_epoch
            # Snapshot here so the published gauges are consistent with
            # THIS batch's assignments (and no second lock acquisition
            # on the hot path).
            fd_entries = len(self._flow_dict)
            fd_generation = self._flow_dict.generation
        # Capacity clears this batch's assignments caused: a resync
        # bumps the generation too, but under _fd_lock, so never
        # between the two readings.
        fd_cleared = fd_generation - gen0
        # Rows the full table had no slot for (id 0 is never assigned).
        n_tableless = sum(int(np.count_nonzero(x[1] == 0)) for x in per_dev)
        base = batch_ts_base(sb.records)
        pk_cap = np.uint32(1) << np.uint32(DENSE_PK_BITS)
        by_cap = np.uint32(1) << np.uint32(DENSE_BY_BITS)
        id_bits = self._fd_id_bits
        # Escalate to the full-row side (exact per-row fields) any known
        # row the narrow lanes cannot represent faithfully: packet
        # counts over the 10-bit packets lane, bytes over the 22-bit
        # bytes lane, rows carrying TSval/TSecr (the RTT matcher needs
        # their EXACT send time — the flush-base stamp below would
        # record phantom times), and unstamped rows (TS_REL=0 must
        # round-trip to ts 0, wire.py:17-23). The masks are computed
        # once and reused for sizing + build. All in-tree sources stamp
        # and TSval rows are apiserver-RTT traffic only, so escalation
        # stays rare.
        sel_new = [
            x[2]
            | (x[0][:, F.PACKETS] >= pk_cap)
            | (x[0][:, F.BYTES] >= by_cap)
            | ((x[0][:, F.TSVAL] | x[0][:, F.TSECR]) != 0)
            | ((x[0][:, F.TS_LO] | x[0][:, F.TS_HI]) == 0)
            for x in per_dev
        ]
        n_new = [int(s.sum()) for s in sel_new]
        n_known = [len(x[0]) - nn for x, nn in zip(per_dev, n_new)]
        Bn = self._wire_bucket(max(n_new) if n_new else 0)
        Bk = self._wire_bucket(max(n_known) if n_known else 0)
        new_wire = np.zeros((D, Bn, 13), np.uint32)
        known_wire = np.zeros((D, dense_words(Bk, id_bits)), np.uint32)
        nv_new = np.zeros((D,), np.uint32)
        nv_known = np.zeros((D,), np.uint32)
        from retina_tpu.native import flowwire_dense_native

        for d, (rows, ids, _) in enumerate(per_dev):
            sel = sel_new[d]
            nn, nk = n_new[d], n_known[d]
            if nn > Bn or nk > Bk:
                # Unreachable from in-tree callers (partition capacity
                # == the _wire_bucket cap). Dropping new rows here
                # would be CORRUPTION, not loss: their descriptors are
                # already registered host-side, so later quanta would
                # reference never-written table slots. Fail loudly; the
                # caller's resync handler rebuilds both sides.
                raise RuntimeError(
                    f"flow-dict wire overflow: {nn}/{Bn} new, "
                    f"{nk}/{Bk} known rows on device {d}"
                )
            got = None
            if len(rows):
                # One native pass builds both sides in place — the
                # numpy path below pays two fancy-indexed row copies +
                # a pack pass + two bit-pack passes per device.
                got = flowwire_dense_native(
                    np.ascontiguousarray(rows), ids,
                    sel.astype(np.uint8), int(base), id_bits,
                    DENSE_PK_BITS, DENSE_BY_BITS,
                    new_wire[d], known_wire[d],
                )
            if got is not None:
                assert got == nn, (got, nn)
            elif len(rows):
                rn, idn = rows[sel], ids[sel]
                rk, idk = rows[~sel], ids[~sel]
                if len(rn):
                    packed12, _, _ = pack_records(rn, base=base)
                    new_wire[d, : len(rn), 0] = idn
                    new_wire[d, : len(rn), 1:] = packed12
                if len(rk):
                    dense_known_rows(rk, idk, id_bits, known_wire[d])
            nv_new[d] = nn
            nv_known[d] = nk
        sp_build.set(
            new_rows=sum(n_new), known_rows=sum(n_known),
            cleared=fd_cleared,
        )
        if record_metrics and lost:
            m.lost_events.labels(
                stage="partition", plugin="engine"
            ).inc(lost)
        b_lo = np.uint32(base & np.uint64(0xFFFFFFFF))
        b_hi = np.uint32(base >> np.uint64(32))
        meta_new = np.empty((5 + D,), np.uint32)
        meta_new[0], meta_new[1] = b_lo, b_hi
        meta_new[2] = np.uint32(int(now_s) & 0xFFFFFFFF)
        meta_new[3] = np.uint32(int(lost) & 0xFFFFFFFF)
        # Known rows' TS_REL: the flush base itself (rel 1 = "stamped,
        # at base"; 0 = the whole flush is unstamped). A flush spans
        # ~tens of ms, and rows needing exact per-row time (TSval/TSecr
        # carriers, unstamped rows) escalated above, so one
        # representative timestamp per flush is exact enough for
        # conntrack/windowing.
        meta_new[4] = 1 if int(base) > 0 else 0
        meta_new[5:] = nv_new
        have_new = bool(nv_new.any())
        have_known = bool(nv_known.any())
        meta_known = meta_new.copy()
        # Host losses fold into the device totals exactly once: on the
        # new side when it runs, else on the known side.
        meta_known[3] = 0 if have_new else meta_new[3]
        meta_known[5:] = nv_known
        n_events = int(sb.events)
        shard_rows = nv_new + nv_known
        samp_k = int(sb.sample_k)
        # One step instead of one a side, where the wire allows it:
        # each side is one window and together they fit one. The new
        # rows' descriptors still reach the table first (their ingest
        # runs first); the two windows are then folded on the device.
        cap = self.cfg.batch_capacity
        fold_sides = bool(
            have_new and have_known and Bn <= cap and Bk <= cap
            and int(shard_rows.max()) <= cap
        )

        def xfer_and_step(done=None):
            """``done`` (async dispatches) is called once, when the
            last step has finished on the device or when nothing
            reached it."""
            faults.inject("transfer")
            # A failure resync after this batch was built invalidated
            # the table its ids reference — drop rather than gather
            # zeroed descriptors (FIFO makes ordinary overflow clears
            # safe; only resyncs bump the epoch).
            with self._fd_lock:
                if self._fd_epoch != epoch:
                    if record_metrics:
                        m.lost_events.labels(
                            stage="dispatch", plugin="engine"
                        ).inc(n_events)
                        self._count_unheld(n_raw)
                    self.log.warning(
                        "dropping in-flight flow-dict batch from "
                        "pre-resync epoch"
                    )
                    if done is not None:
                        done()
                    return
            self._device_consts()
            # Identity/filter tables captured at proxy-EXECUTION time,
            # not dispatch-build time: update_identities /
            # update_filter_ips swap them inside proxied closures, so
            # FIFO queue order == visibility order — a table update
            # enqueued before this batch is guaranteed applied to it
            # even when warm-key compiles delay the queue by seconds
            # (build-time capture silently filtered a one-shot burst
            # with the pre-update map).
            with self._ident_lock:
                ident = self.ident
                fmap = self.filter_map
            table = self._ensure_desc_table()
            if record_metrics:
                # Wire accounting AFTER the epoch check: a dropped
                # pre-resync batch never ships, and these series are
                # the wire-savings evidence — counted at build time
                # they would overstate exactly in the failure windows
                # an operator inspects. Only sides that actually cross
                # the link count.
                m.transfer_bytes.inc(
                    (new_wire.nbytes if have_new else 0)
                    + (known_wire.nbytes if have_known else 0)
                )
                m.wire_rows.labels(kind=mnames.WIRE_NEW).inc(
                    int(nv_new.sum()) - n_tableless
                )
                m.wire_rows.labels(kind=mnames.WIRE_TABLELESS).inc(
                    n_tableless
                )
                m.wire_rows.labels(kind=mnames.WIRE_KNOWN).inc(
                    int(nv_known.sum())
                )
                m.flow_dict_entries.set(fd_entries)
                m.flow_dict_generation.set(fd_generation)
                m.flow_dict_clears.inc(fd_cleared)
            keys = [("new", Bn)] * have_new + [("known", Bk)] * have_known
            sp_x = self._step_span(
                mnames.STAGE_TRANSFER_ENQUEUE, tid, record_metrics,
                bucket=max(k[1] for k in keys),
                first=self._first_run(keys),
                bucket_new=Bn if have_new else 0,
                bucket_known=Bk if have_known else 0,
            )
            t_x0 = time.perf_counter()
            c_x0 = self._clock()
            # ONE batched device_put for everything this flush moves:
            # separate puts each pay a client round-trip.
            host_bufs, shardings = [], []
            if have_new:
                host_bufs += [new_wire, meta_new]
                shardings += [self._rec_sharding, self._replicated]
            if have_known:
                host_bufs += [known_wire, meta_known]
                shardings += [self._rec_sharding, self._replicated]
            devs = jax.device_put(tuple(host_bufs), tuple(shardings))
            devs = list(devs)
            sides = []
            # Skip a side with zero valid rows outright: steady state
            # has almost-no new flows, cold start almost-no known —
            # half the transfers and steps on the hot path either way.
            if have_new:
                new_dev, mn_dev = devs[0], devs[1]
                devs = devs[2:]
                wins, nvs, now_dev, lost_dev, table = (
                    self._ingest_new_fn(Bn)(new_dev, mn_dev, table)
                )
                # Re-check the epoch at the store: a resync landing
                # between this batch's entry check and here already
                # invalidated the ids this table was built against —
                # storing it would resurrect stale descriptors over
                # the resync's cleared table.
                with self._fd_lock:
                    if self._fd_epoch == epoch:
                        self._desc_table = table
                sides.append((wins, nvs, now_dev, lost_dev))
            if have_known:
                known_dev, mk_dev = devs[0], devs[1]
                wins, nvs, now_dev, lost_dev = self._ingest_known_fn(
                    Bk
                )(known_dev, mk_dev, table)
                sides.append((wins, nvs, now_dev, lost_dev))
            if fold_sides:
                (wn, nn_dev, now_dev, lost_dev), (wk, nk_dev, _, _) = sides
                win, nv_dev = self._fold_sides_fn()(
                    wn[0], nn_dev[0], wk[0], nk_dev[0]
                )
                # The new side's meta carries the host losses.
                sides = [((win,), (nv_dev,), now_dev, lost_dev)]
            sp_x.end()
            sp_s = self._step_span(
                mnames.STAGE_DEVICE_STEP, tid, record_metrics
            )
            t0 = time.perf_counter()
            n_steps = 0
            with self._state_lock:
                st = self.state
                first = True
                for wins, nvs, now_dev, lost_dev in sides:
                    for w in range(len(wins)):
                        st, summary = self.sharded.step(
                            st, wins[w], nvs[w], now_dev, ident,
                            self._api_dev, filter_map=fmap,
                            # meta_known carries lost=0, so folding on
                            # the FIRST side that runs counts host
                            # losses once whichever sides are present.
                            lost=lost_dev if first else self._zero_u32,
                            sample_k=self._sampk(samp_k),
                        )
                        first = False
                        n_steps += 1
                self.state = st
            if record_metrics:
                m.transfer_seconds.observe(t0 - t_x0)
                self._note_dispatched(
                    c_x0, shard_rows, n_steps, n_raw, n_flushes, cause
                )
            self._watch_steps(
                sp_s if record_metrics else None, summary["events"], t0,
                n_steps, done,
            )

        if not (have_new or have_known):
            sp_build.end()
            if record_metrics:
                self._count_unheld(n_raw)
            return  # nothing valid (pure padding batch)

        if sync:
            sp_build.end()
            run_on_device(
                xfer_and_step, kind=mnames.KIND_STEP, parent=sp_build.id
            )
            return

        def safe_xfer_and_step():
            try:
                xfer_and_step(self._dispatch_done)
            except Exception as e:
                # Raised before the steps were handed to the completion
                # thread (that hand-over is the call's last act).
                self._dispatch_done(e)
                if self._count_error("device_step"):
                    self.log.exception("flow-dict device step failed")
                get_metrics().lost_events.labels(
                    stage="device", plugin="engine"
                ).inc(n_events)
                self._count_unheld(n_raw)
                # The donated table may be gone and the host dict no
                # longer matches it — resync by rebuilding both (one
                # re-upload burst, no wrong data); queued batches from
                # this epoch self-drop.
                self._flowdict_resync()
                if self._fatal_device_error(e):
                    self._request_recovery(repr(e))

        sp_build.end()
        self._dispatch_submitted()
        submit_on_device(
            safe_xfer_and_step, kind=mnames.KIND_STEP, parent=sp_build.id
        )

    def _dispatch_sharded(
        self, sb: "ShardedBatch", now_s: int, n_raw: int,
        sync: bool = True, record_metrics: bool = True,
        n_flushes: int = 1, cause: str = "",
    ) -> None:
        """Pack + device_put + step dispatch for an already-partitioned
        batch.

        Degraded drop-and-count: while a crash-only recovery is
        rebuilding device state, async feed traffic must not race the
        rebuild — it drops here, counted under lost_events
        stage="degraded". Sync dispatches pass through (the recovery
        probe itself, and direct callers who want the error).

        Packing stays on the CALLING thread (the dispatch worker under
        the feed loop), overlapping the proxy thread's in-flight
        transfer. ``sync=True`` (tests, direct callers) blocks on the
        proxy round-trip and propagates errors; ``sync=False`` (the feed
        pipeline) is fire-and-forget onto the proxy queue, bounded by
        the in-flight semaphore, so transfers run back-to-back on the
        link while this thread packs the next quantum.
        """
        if not sync and self._degraded.is_set():
            if record_metrics:
                get_metrics().lost_events.labels(
                    stage="degraded", plugin="engine"
                ).inc(int(sb.events) + int(sb.lost))
                self._count_unheld(n_raw)
            return
        # The dictionary pays off per ROW saved; a tiny flush (idle
        # agent, a trickle feed) is cheaper as one plain transfer than
        # as a new/known pair of dispatches. Plain and dict flushes
        # interleave soundly: a plain flush simply ships full rows and
        # leaves the dictionary untouched.
        if self._flow_dict is not None and int(
            sb.n_valid.sum()
        ) >= self.cfg.transfer_min_bucket:
            try:
                self._dispatch_flowdict(
                    sb, now_s, n_raw, sync, record_metrics, n_flushes,
                    cause,
                )
            except Exception:
                # ANY failure after lookup_or_assign may leave
                # descriptors registered host-side whose lanes never
                # reached the device table — later "known" references
                # would gather zeros (silent corruption). Rebuild both
                # sides; in-flight batches from before the reset
                # self-drop via the epoch check in their closures.
                self._flowdict_resync()
                if not sync:
                    get_metrics().lost_events.labels(
                        stage="dispatch", plugin="engine"
                    ).inc(int(sb.events) + int(sb.lost))
                    self._count_unheld(n_raw)
                    if self._count_error("flowdict_dispatch"):
                        self.log.exception("flow-dict dispatch failed")
                    return
                raise
            return
        m = get_metrics()
        if sb.lost and record_metrics:
            m.lost_events.labels(stage="partition", plugin="engine").inc(sb.lost)
        tid = fleet_epoch(self.cfg.window_seconds)
        sp_build = self._step_span(
            mnames.STAGE_WIRE_BUILD, tid, record_metrics
        )
        from retina_tpu.parallel.wire import pack_records

        # A fresh array: the partition fast path may alias the caller's
        # buffer (ALIASING CONTRACT in partition_events), the packed
        # wire never does, so the producer can reuse it.
        wire, b_lo, b_hi = pack_records(sb.records)
        if record_metrics:
            m.transfer_bytes.inc(wire.nbytes)
        bucket = wire.shape[1]
        meta = np.empty((5 + self.n_devices,), np.uint32)
        meta[0], meta[1] = b_lo, b_hi
        meta[2] = np.uint32(int(now_s) & 0xFFFFFFFF)
        meta[3] = np.uint32(int(sb.lost) & 0xFFFFFFFF)
        meta[4] = 0  # ts_rel_rep: unused on the full-row path
        meta[5:] = sb.n_valid
        shard_rows = sb.n_valid
        n_events = int(sb.events)
        samp_k = int(sb.sample_k)
        sp_build.end()

        def xfer_and_step(done=None):
            faults.inject("transfer")
            self._device_consts()
            # Execution-time capture — see _dispatch_flowdict: proxy
            # FIFO order is the table-visibility order.
            with self._ident_lock:
                ident = self.ident
                fmap = self.filter_map
            sp_x = self._step_span(
                mnames.STAGE_TRANSFER_ENQUEUE, tid, record_metrics,
                bucket=bucket, first=self._first_run([bucket]),
            )
            t_x0 = time.perf_counter()
            c_x0 = self._clock()
            # One batched put (wire + meta): separate puts each pay a
            # client round-trip.
            wire_dev, meta_dev = jax.device_put(
                (wire, meta), (self._rec_sharding, self._replicated)
            )
            wins, nvs, now_dev, lost_dev = self._ingest_fn(bucket)(
                wire_dev, meta_dev
            )
            sp_x.end()
            sp_s = self._step_span(
                mnames.STAGE_DEVICE_STEP, tid, record_metrics
            )
            t0 = time.perf_counter()
            with self._state_lock:
                st = self.state
                for w in range(len(wins)):
                    st, summary = self.sharded.step(
                        st, wins[w], nvs[w], now_dev, ident,
                        self._api_dev, filter_map=fmap,
                        # Host-partition losses are folded into the
                        # device totals exactly once per flush.
                        lost=lost_dev if w == 0 else self._zero_u32,
                        sample_k=self._sampk(samp_k),
                    )
                self.state = st
            if record_metrics:
                # Warm-up dispatches (compile()) skip observation: a
                # one-shot 30-100s cold-compile sample would inflate
                # the histogram p99/max forever and seed transfer_bytes
                # with a synthetic zero batch.
                m.transfer_seconds.observe(t0 - t_x0)
                self._note_dispatched(
                    c_x0, shard_rows, len(wins), n_raw, n_flushes, cause
                )
            self._watch_steps(
                sp_s if record_metrics else None, summary["events"], t0,
                len(wins), done,
            )

        if sync:
            run_on_device(
                xfer_and_step, kind=mnames.KIND_STEP, parent=sp_build.id
            )
            return

        def safe_xfer_and_step():
            try:
                xfer_and_step(self._dispatch_done)
            except Exception as e:
                self._dispatch_done(e)
                if self._count_error("device_step"):
                    self.log.exception("device step failed")
                get_metrics().lost_events.labels(
                    stage="device", plugin="engine"
                ).inc(n_events)
                self._count_unheld(n_raw)
                if self._fatal_device_error(e):
                    self._request_recovery(repr(e))

        self._dispatch_submitted()
        submit_on_device(
            safe_xfer_and_step, kind=mnames.KIND_STEP, parent=sp_build.id
        )

    def _step_span(self, stage: str, tid: int, record_metrics: bool,
                   **args):
        """A span of the dispatch path, or the null span for a warm-up
        dispatch (``compile()``): a one-shot 30-100 s cold compile
        would sit in the stage histogram's p99 forever."""
        if not record_metrics:
            return NULL_SPAN
        return self._recorder.span(stage, tid, **args)

    def _first_run(self, keys: list) -> bool:  # runs-on: device-proxy
        """Whether any of these ingest programs (``_pad_cache`` keys)
        is about to run on the device for the first time in this
        process; they are then noted as run."""
        first = not self._ran_keys.issuperset(keys)
        if first:
            self._ran_keys.update(keys)
        return first

    def _note_dispatched(
        self, c_x0: float, shard_rows: np.ndarray, n_steps: int,
        n_raw: int, n_flushes: int, cause: str = "",
    ) -> None:
        """(proxy thread) What both dispatchers record once a
        dispatch's transfer and steps are enqueued: the overload
        signal (EWMA of the enqueue time of transfer + steps, on the
        engine's clock since ``c_x0``, each sample at most the budget
        it is read against; proxy thread only, so no lock),
        the fill of the step capacity dispatched (windows x
        batch_capacity: a 0..1 ratio for coalesced multi-window
        transfers too), the folding counters (``shard_rows``: the valid
        rows dispatched to each device; ``cause``: what released the
        rows the dispatch thread held, "" for a synchronous dispatch,
        which nobody held) and the engine's own totals."""
        now = self._clock()
        # One sample weighs at most its budget (half a window, what
        # _overload_signals divides by): enqueues that are slow one
        # after another still read 1.0 within a few, but one freeze of
        # the process caught inside an enqueue is one sample, not an
        # overload.
        lat = min(now - c_x0, 0.5 * self.cfg.window_seconds)
        self._dispatch_lat_ewma = (
            0.8 * self._dispatch_lat_ewma + 0.2 * lat
        )
        self._dispatch_lat_t = now
        m = get_metrics()
        n_rows = int(shard_rows.sum())
        m.device_batch_fill.set(
            n_rows
            / max(self.n_devices * self.cfg.batch_capacity * n_steps, 1)
        )
        m.steps.inc(n_steps)
        m.step_rows.inc(n_rows)
        for d, n in enumerate(shard_rows.tolist()):
            m.shard_rows.labels(device=str(d)).inc(n)
        m.dispatch_flushes.inc(n_flushes)
        if cause:
            m.dispatches.labels(cause=cause).inc()
        self._steps += n_steps
        self._events_in += n_raw

    def _watch_steps(
        self, span, out, t0: float, n_steps: int, then=None,
    ) -> None:
        """(proxy thread) Hand a dispatched group of steps to the
        completion thread: ``out`` is the last step's ``events`` output
        (not donated, unlike the state), ready when the whole group has
        run. The ``device_step`` span, open since the first dispatch at
        ``t0``, is closed there, and ``tpu_step_seconds`` observes
        completed seconds per step — what the device took (plus, on a
        backlogged device, the wait behind earlier groups), not what
        the enqueue took. ``span`` is None for a warm-up dispatch;
        ``then`` (an async dispatch's ``_dispatch_done``) is called
        after. The proxy thread does not wait. This is the dispatch's
        last act on the proxy: past it, ``then`` is the completion
        thread's to call."""
        if span is None and then is None:
            return
        hist = get_metrics().device_step_seconds

        def done(err: BaseException | None) -> None:
            if span is not None:
                args = {"error": type(err).__name__} if err else {}
                dt = span.end(n_steps=n_steps, **args)
                hist.observe((dt or time.perf_counter() - t0) / n_steps)
            if then is not None:
                then(err)

        on_ready(out, done)

    def _win_stack(self, win):
        """(proxy thread) Stack the 3 per-dimension window outputs into
        one array so the device->host readback is ONE transfer (per-leaf
        device_get costs a link round-trip per array) and start the copy
        moving without blocking."""
        stacked = jnp.stack(
            [
                jnp.asarray(win["entropy_bits"], jnp.float32),
                jnp.asarray(win["anomaly"], jnp.float32),
                jnp.asarray(win["zscore"], jnp.float32),
            ]
        )
        try:
            stacked.copy_to_host_async()
        except Exception:  # noqa: RT101 — backend without async copy: harvest blocks
            pass
        return stacked

    def _publish_window(
        self,
        win_host: dict[str, np.ndarray],
        meta: dict | None = None,
    ) -> None:
        # ``meta`` is the overload annotation captured AT CLOSE TIME
        # (overload state, sampled_fraction, shed stages, raw events in
        # the window): a window closed under sampling says so forever,
        # however late its readback publishes.
        if meta is not None:
            win_host = dict(win_host)
            win_host["overload"] = meta
        self.last_window = win_host
        m = get_metrics()
        # Uptime rides the window-publish cadence (>= one update per
        # window_seconds) — cheap, and always fresh at scrape time.
        m.uptime_seconds.set(time.monotonic() - self._start_monotonic)
        dims = ["src_ip", "dst_ip", "dst_port"]
        for i, dim in enumerate(dims):
            m.entropy_bits.labels(dimension=dim).set(
                float(win_host["entropy_bits"][i])
            )
            m.anomaly_flag.labels(dimension=dim).set(
                float(win_host["anomaly"][i])
            )
            m.anomaly_zscore.labels(dimension=dim).set(
                float(win_host["zscore"][i])
            )
            if win_host["anomaly"][i]:
                # Counter survives scrape cadence: a 0.2s anomalous
                # window must be visible at a 30s scrape.
                m.anomaly_windows.labels(dimension=dim).inc()
        flagged = [
            dim for i, dim in enumerate(dims)
            if i < len(win_host["anomaly"]) and win_host["anomaly"][i]
        ]
        if flagged and self.anomaly_hook is not None:
            # Closed-loop capture pivot (timetravel/autocapture.py):
            # notify only enqueues — the harvest thread never waits on
            # attribution or capture work.
            try:
                self.anomaly_hook(
                    fleet_epoch(self.cfg.window_seconds), flagged
                )
            except Exception:
                if self._count_error("anomaly_hook"):
                    self.log.exception("anomaly hook failed")

    def _ensure_harvest_thread(self) -> None:
        # Spawn-vs-retire is serialized by _harvest_lock: without it a
        # straggler close could pass the retired check, lose the CPU,
        # and spawn a fresh thread AFTER shutdown consumed the None
        # sentinel — a thread that parks on the queue forever, pinning
        # the engine object graph (ADVICE r5).
        with self._harvest_lock:
            if self._harvest_retired:
                return
            if (
                self._harvest_thread is None
                or not self._harvest_thread.is_alive()
            ):
                gen = self._harvest_gen
                self._harvest_thread = threading.Thread(
                    target=self._harvest_loop, args=(gen,),
                    name="window-harvest", daemon=True,
                )
                self._harvest_thread.start()

    def _restart_harvest(self) -> None:  # runs-on: watchdog
        """Watchdog escalation for a hung harvest thread (a wedged
        device_get on a dead link can block indefinitely): supersede it
        by bumping the generation and spawn a replacement. The hung
        instance exits at its next generation check instead of racing
        the replacement for the queue; its in-flight item publishes
        late (or never) — window gauges are refreshed by every later
        window, so staleness self-heals."""
        with self._harvest_lock:
            if self._harvest_retired:
                return
            self._harvest_gen += 1
            self._harvest_thread = None
        get_metrics().thread_restarts.labels(thread="window-harvest").inc()
        self.log.error(
            "harvest thread stalled; superseding with a replacement "
            "(gen %d)", self._harvest_gen,
        )
        self._ensure_harvest_thread()

    def _harvest_loop(self, gen: int) -> None:
        """(harvest thread) Block on each closed window's device->host
        readback and publish its gauges. Runs OFF the device-proxy
        thread: where copy_to_host_async is not available the
        device_get blocks for a full link round-trip per window, and a
        harvest that ran proxy-side parked every queued step behind
        scrape-cadence gauge traffic (its share of proxy time is
        unverified on the attached chip). FIFO order preserves window
        order.

        ``gen`` is this instance's generation: when the watchdog
        supersedes a hung instance (_restart_harvest), the stale one
        exits at its next check instead of competing for the queue."""
        hb = self._register_hb(
            "window-harvest", on_stall=self._restart_harvest
        )
        while True:
            hb.park()
            try:
                item = self._harvest_q.get(timeout=1.0)
            except queue_mod.Empty:
                if self._harvest_gen != gen:
                    return  # superseded while idle
                continue
            hb.beat()
            try:
                if item is None:
                    return
                kind, stacked, meta = item
                faults.inject("harvest")
                if kind == "zero":
                    z = np.zeros((3,), np.float32)
                    self._publish_window({
                        "entropy_bits": z, "anomaly": z, "zscore": z,
                    }, meta)
                else:
                    # fetch_on_device, NOT a direct device_get: every
                    # JAX call rides the proxy thread (one-thread rule
                    # of utils/device_proxy.py), but the queue-wait
                    # happens here, off-proxy.
                    tid = fleet_epoch(self.cfg.window_seconds)
                    timing: dict = {}
                    with self._recorder.span(
                        mnames.STAGE_HARVEST, tid
                    ) as sp:
                        host = fetch_on_device(stacked, timing=timing)
                        sp.set(**timing)
                    with self._recorder.span(mnames.STAGE_PUBLISH, tid):
                        self._publish_window({
                            "entropy_bits": host[0],
                            "anomaly": host[1],
                            "zscore": host[2],
                        }, meta)
                    inv_dec = meta.pop("inv_decode", None)
                    if inv_dec is not None:
                        self._harvest_invertible(inv_dec)
            except Exception:
                if self._count_error("harvest_readback"):
                    self.log.exception("window readback failed")
            finally:
                self._harvest_q.task_done()
            if self._harvest_gen != gen:
                # Superseded mid-item (the watchdog already spawned a
                # replacement): bow out after finishing this one.
                return

    def _harvest_invertible(self, dec) -> None:  # runs-on: window-harvest
        """Read back one window's invertible decode, dedupe (a key can
        decode from up to D row-buckets), publish tpu_invertible_*
        gauges, and — in "both" mode — score recall/precision against
        the host flow-dict ground truth (_hk_account)."""
        ok = np.asarray(fetch_on_device(dec["ok"]), bool)
        keys = np.asarray(fetch_on_device(dec["keys"]))[ok]
        est = np.asarray(fetch_on_device(dec["est"]))[ok]
        tier = np.asarray(fetch_on_device(dec["tier"]))[ok]
        if len(keys):
            uniq, idx = np.unique(keys, axis=0, return_index=True)
            keys, est, tier = uniq, est[idx], tier[idx]
        m = get_metrics()
        m.invertible_keys_recovered.set(len(keys))
        with self._inv_lock:
            self._inv_last = {"keys": keys, "est": est, "tier": tier}
        if self._hk_counts is None:
            return
        thr = max(1, int(self.cfg.invertible_min_weight))
        with self._fd_lock:
            truth = dict(self._hk_counts)
        heavy = {k for k, v in truth.items() if v >= thr}
        rec = {k.tobytes() for k in keys}
        if heavy:
            m.invertible_recall.set(len(heavy & rec) / len(heavy))
        if rec:
            m.invertible_precision.set(
                sum(1 for k in rec if truth.get(k, 0) >= thr) / len(rec)
            )

    def invertible_report(self) -> dict:
        """Latest window's recovered heavy-key set (host arrays):
        ``keys (N, 4) u32`` rows of (src_ip, dst_ip, ports, proto),
        ``est (N,)`` CMS count estimates, ``tier (N,)`` (0 = main
        region, 1 = priority region). Empty arrays before the first
        decoded window or when invertible is disabled."""
        with self._inv_lock:
            last = self._inv_last
        if last is None:
            return {
                "keys": np.zeros((0, 4), np.uint32),
                "est": np.zeros((0,), np.uint32),
                "tier": np.zeros((0,), np.uint32),
            }
        return dict(last)

    def _harvest_window(self, timeout: float | None = None) -> None:
        """Drain pending window readbacks (shutdown / tests): returns
        once every window enqueued so far has published, or after
        ``timeout`` (default cfg.harvest_timeout_s — a wedged link must
        not hang shutdown)."""
        if timeout is None:
            timeout = self.cfg.harvest_timeout_s
        deadline = time.monotonic() + timeout
        while (
            self._harvest_q.unfinished_tasks
            and time.monotonic() < deadline
        ):
            time.sleep(0.01)

    def _close_window(self) -> None:
        """End the entropy/anomaly window (self-proxying: the body —
        including the harvest's device_get — always executes on the
        device-proxy thread, whatever thread calls this)."""
        run_on_device(self._close_window_impl, kind=mnames.KIND_CLOSE)

    def _close_window_impl(self) -> None:  # hot-path: close
        """(proxy thread) End the entropy/anomaly window. Runs as a
        fire-and-forget proxy submission from the dispatch worker, so it
        stays ordered after the step submissions that fed the window.

        The close only DISPATCHES end_window and hands the stacked
        result to the harvest thread — the blocking device->host
        readback happens there (:meth:`_harvest_loop`), never on the
        proxy. Gauges publish as soon as the copy lands (typically well
        inside the window interval)."""
        # Idle fast path: end_window SKIPS empty windows on-device (no
        # flag, no baseline update — AnomalyEWMA.observe active gating),
        # so when nothing arrived since the last close the dispatch +
        # readback round-trip is pure waste; an idle agent then costs
        # zero device traffic between scrapes.
        wt = self._warm_thread
        if (
            wt is not None
            and wt.is_alive()
            and not self._close_warmed.is_set()
        ):
            # The close program is still queued as the background
            # warm's FIRST job. Running end_window here would
            # cold-compile it inline on the proxy mid-feed — the
            # multi-second stall episodes r05 measured. Defer: the
            # window simply stays open (every event intact) and the
            # next tick closes a longer window against the then-warm
            # program. Bounded by the warm thread's own lifetime — a
            # dead or finished warm never defers a close.
            get_metrics().windows_deferred.inc()
            return
        if self._degraded.is_set():
            # Crash-only recovery in flight: the state is mid-rebuild;
            # defer exactly like the warm case — the window stays open
            # and the next tick closes it against recovered state.
            get_metrics().windows_deferred.inc()
            return
        if self._events_in == self._closed_events_in:
            get_metrics().windows_closed.inc()
            # Mirror what a real empty close reports (flag 0, z 0,
            # entropy 0) so a flag raised by the LAST active window
            # doesn't latch on an idle node. Routed through the harvest
            # queue, NOT set directly: a still-pending active window's
            # readback publishing after a direct zeroing would re-latch
            # the stale flag — FIFO through one queue keeps publish
            # order = close order.
            meta = self._overload.window_annotation()
            meta["events"] = 0  # idle, not stalled: nothing arrived
            self._ensure_harvest_thread()
            self._harvest_q.put(("zero", None, meta))
            return
        ingested = self._events_in
        # Annotation snapshot BEFORE _closed_events_in advances: the
        # raw-event count this window actually ingested, plus the
        # controller's per-window sampling accounting. A window closed
        # while sampling is NEVER reported as empty — its event count
        # and sampled_fraction say exactly what was kept.
        meta = self._overload.window_annotation()
        meta["events"] = ingested - self._closed_events_in

        def close():
            sp_close = self._recorder.span(
                mnames.STAGE_WINDOW_CLOSE,
                fleet_epoch(self.cfg.window_seconds),
            )
            self._device_consts()
            with self._state_lock:
                if (self._fleet_shipper is not None
                        or self._tt_ring is not None):
                    # Export MUST dispatch before end_window: end_window
                    # resets the entropy window and donates the state
                    # buffers, so this is the last moment the closing
                    # window's sketches exist on device. Pure dispatch —
                    # one export feeds both the fleet shipper and the
                    # time-travel ring; their workers do the blocking
                    # readback off the proxy, and offer() never blocks.
                    try:
                        export = self.sharded.fleet_export(self.state)
                        epoch = fleet_epoch(self.cfg.window_seconds)
                        seeds = self.sharded.fleet_seeds(self.state)
                        if self._fleet_shipper is not None:
                            self._fleet_shipper.offer(
                                epoch, export,
                                self.cfg.window_seconds, seeds,
                            )
                        if self._tt_ring is not None:
                            self._tt_ring.offer(
                                epoch, export,
                                self.cfg.window_seconds, seeds,
                            )
                    except Exception:
                        get_metrics().fleet_ship_errors.inc()
                        if self._count_error("fleet_export"):
                            self.log.exception("fleet export failed")
                inv = None
                if self.pcfg.enable_invertible:
                    # Same before-end_window contract as the fleet
                    # export: decode reads the closing window's sketch
                    # state, end_window donates it. Pure dispatch; the
                    # harvest thread does the blocking readback.
                    try:
                        inv = self.sharded.inv_decode(
                            self.state, self.cfg.invertible_min_weight
                        )
                    except Exception:
                        get_metrics().invertible_decode_failed.inc()
                        if self._count_error("inv_decode"):
                            self.log.exception("invertible decode failed")
                self.state, win = self.sharded.end_window(
                    self.state, self._zthresh
                )
            sp_close.end()
            return self._win_stack(win), inv

        stacked, inv_dec = run_on_device(close, kind=mnames.KIND_CLOSE)
        # Advance only after a SUCCESSFUL dispatch: if end_window
        # raised, the next tick must retry this window, not skip it
        # forever.
        self._closed_events_in = ingested
        if inv_dec is not None:
            meta["inv_decode"] = inv_dec
        self._ensure_harvest_thread()
        self._harvest_q.put(("win", stacked, meta))
        get_metrics().windows_closed.inc()

    def _submit_close_window(self) -> None:  # hot-path: close
        """Fire-and-forget window close on the PROTECTED close lane:
        FIFO-ordered after step submissions on the proxy queue, but
        bounded by its own semaphore — a step pipeline that has eaten
        every in-flight slot can never starve a window tick of a
        submission slot (overload contract: a window is always closed,
        possibly annotated, never silently skipped). Non-blocking: when
        both close slots are in flight behind a stalled link, the tick
        defers (counted) and the next tick closes a longer window."""

        def safe_close():
            try:
                self._close_window()
            except Exception as e:
                if self._count_error("window_close"):
                    self.log.exception("window close failed")
                if self._fatal_device_error(e):
                    self._request_recovery(repr(e))
            finally:
                self._close_inflight.release()

        if not self._close_inflight.acquire(blocking=False):
            get_metrics().windows_deferred.inc()
            return
        submit_on_device(safe_close, kind=mnames.KIND_CLOSE)

    def _resolve_feed_workers(self) -> int:
        """Size of the feed-worker pool, at least 1: the config value,
        or auto-sized to the machine (cores minus one for the
        distributor+dispatch threads, capped at 4 — staging memory and
        combine-lock contention grow past that with no measured
        throughput gain)."""
        n = self.cfg.feed_workers
        if n <= 0:
            cores = os.cpu_count() or 1
            n = max(1, min(4, cores - 1))
        return n

    def _busy_count(self) -> int:  # runs-on: engine-dispatch
        """In-flight dispatch count: gates the dispatch thread's
        releases (``_has_slot``)."""
        with self._busy_lock:
            return self._inflight_busy

    def _dispatch_submitted(self) -> None:  # runs-on: engine-dispatch
        """Take a slot of the pipeline for one dispatch (waits for one
        while ``feed_pipeline_depth`` are in flight)."""
        self._inflight.acquire()
        with self._busy_lock:
            self._inflight_busy += 1
            self._submitted_n += 1

    def _dispatch_done(  # runs-on: device-completion, device-proxy
        self, err: BaseException | None = None
    ) -> None:
        """Give the slot back: called once per submitted dispatch, by
        the completion thread when its last step has finished on the
        device, or by the proxy thread when it never reached the
        device (dropped, failed)."""
        with self._busy_lock:
            self._inflight_busy -= 1
        self._inflight.release()
        # The count fell, then the wake: whoever waits for it (the
        # dispatch thread's held rows that are due, for a slot; a
        # readback for the dispatches ahead of it, _dispatches_landed)
        # re-reads it. The feed workers hold what they stage whatever
        # the pipeline does: none waits for it.
        with self._reads:
            self._reads.notify_all()
        pool = self._feed_pool
        if pool is not None:
            pool.mux.wake()

    def wake(self) -> None:
        """Every thread of the feed path that sleeps to a deadline on
        the engine's clock re-reads it: the hook of a clock advanced by
        hand (tests/clockdrive): the feed loop for its ticks, the
        workers for the ages of what they stage, the dispatch thread
        for the age of what it holds."""
        self.sink.data.set()
        pool = self._feed_pool
        if pool is not None:
            pool.wake_all()
            pool.mux.wake()

    # -- adaptive overload control (runtime/overload.py) --------------
    def _overload_signals(self) -> dict[str, float]:
        """Normalized [0,1] pressure signals for the overload
        controller — the max across them is the pipeline pressure.
        Called from the feed loop at tick cadence; every read here is
        lock-free or a single counter load."""
        sig: dict[str, float] = {}
        pool = self._feed_pool
        now = self._clock()
        if pool is not None:
            # Worst per-worker staging fill: the first queue to
            # overflow decides when blocks start dropping.
            sig["staging"] = pool.max_staging_fill()
            # Handoff wait RATE (seconds waited per second): workers
            # blocked on a full transfer queue mean the device side
            # can't keep up even though staging still has room.
            wait = pool.handoff_wait_total()
            dt = max(now - self._ov_wait_t, 1e-6)
            sig["handoff_wait"] = min(
                1.0, max(0.0, wait - self._ov_wait_prev) / dt
            )
            self._ov_wait_prev = wait
            self._ov_wait_t = now
        # Harvest lag: closed windows whose readback hasn't landed.
        sig["harvest"] = min(
            1.0, self._harvest_q.unfinished_tasks / 4.0
        )
        # Dispatch latency EWMA against the window budget: device
        # steps eating a whole window interval starve the close lane.
        # A stale sample (no dispatch for >2 windows) means idle, not
        # slow — without the age gate the frozen EWMA would hold the
        # controller above the exit threshold forever.
        if now - self._dispatch_lat_t <= 2.0 * self.cfg.window_seconds:
            sig["dispatch_lat"] = min(
                1.0,
                self._dispatch_lat_ewma
                / max(0.5 * self.cfg.window_seconds, 1e-3),
            )
        # Chaos/bench injection (runtime/faults.py feed.backpressure):
        # a sustained synthetic pressure signal so tests drive the
        # NOMINAL -> SAMPLING -> SHEDDING arc without having to
        # actually saturate the host. 0.95 sits between the shed (0.90)
        # and degrade (0.98) thresholds: DEGRADED stays reserved for
        # real saturation / crash-only recovery.
        if faults.pressure("feed.backpressure"):
            sig["fault"] = 0.95
        # Crash-only recovery pins the controller at DEGRADED for the
        # duration (drop-and-count is the ultimate shed).
        if self._degraded.is_set():
            sig["degraded"] = 1.0
        return sig

    @property
    def overload(self) -> OverloadController:
        """The controller itself (plugins/modules call note_shed on
        it; tests drive tick with injected clocks)."""
        return self._overload

    def shed_active(self, stage: str) -> bool:
        """Plugins/modules consult this before enrichment work (dns
        qname hashing, conntrack scrape, label resolution)."""
        return self._overload.shed_active(stage)

    def overload_stats(self) -> dict[str, Any]:
        """Controller state for the control server's debug var and the
        bench diag."""
        return self._overload.stats()

    def _build_quantum(  # runs-on: feed-worker*  # hot-path: event
        self, blocks: list[np.ndarray], n_raw: int, now_s: int
    ) -> list[tuple]:
        """Combine + partition one flush (every raw block a feed worker
        held, up to its quantum) into dispatchable step items: ONE
        combine over all of them. Pure host work that the feed workers
        (parallel/feed.py) run concurrently — the native combiner
        releases the GIL and partition is numpy."""
        cap = self.cfg.batch_capacity * self.n_devices
        coal = cap * max(1, self.cfg.feed_coalesce_windows)
        coal_per_dev = self.cfg.batch_capacity * max(
            1, self.cfg.feed_coalesce_windows
        )
        with self._recorder.span(
            mnames.STAGE_COMBINE, fleet_epoch(self.cfg.window_seconds)
        ):
            all_rec = combine_blocks(blocks)
            get_metrics().combine_ratio.set(n_raw / max(len(all_rec), 1))
        if self.record_hook is not None:
            try:
                self.record_hook(all_rec, now_s)
            except Exception:
                self._count_error("record_hook")
        # Overload sampling sits POST-combine / PRE-partition: a row's
        # packet weight is final here, so the device step can recompute
        # the same exemption predicate over the same rows and rescale
        # the non-exempt survivors by k (Horvitz-Thompson — see
        # runtime/overload.py). k rides the ShardedBatch to the
        # dispatch paths.
        all_rec, samp_k = self._overload.sample_rows(all_rec)
        items: list[tuple] = []
        # One span a flush: on one device the partition is a zero-copy
        # view, on a mesh it is the connection hash and a masked copy
        # per device.
        with self._recorder.span(
            mnames.STAGE_PARTITION, fleet_epoch(self.cfg.window_seconds),
            rows=len(all_rec), devices=self.n_devices,
        ):
            for off in range(0, len(all_rec), coal):
                chunk = all_rec[off : off + coal]
                sb = partition_events(
                    chunk, self.n_devices, coal_per_dev,
                    min_bucket=self.cfg.transfer_min_bucket,
                )
                sb.sample_k = samp_k
                # raw-row accounting goes to the chunk that carries it;
                # chunk boundaries are an implementation detail
                items.append(
                    ("step", sb, now_s, n_raw if off == 0 else 0)
                )
        return items

    def feed_stats(self) -> dict[str, Any]:
        """Feed-path self-observability for the control server's
        ``feed`` debug var and bench result JSON: per-worker fill /
        staged backlog / handoff wait, pool drop counters, and the
        flow-dict residency summary."""
        pool = self._feed_pool  # None until start()
        st = pool.stats() if pool is not None else {}
        st["flow_dict"] = flow_dict_stats(self._flow_dict)
        st["overload"] = self._overload.stats()
        # The dispatch thread's folding: dispatches the device has not
        # finished, the flushes it holds and how long the oldest.
        since = self._held_since
        st["dispatch"] = {
            "in_flight": self._busy_count(),
            "held_flushes": self._held_flushes,
            "held_age_s": 0.0 if since is None
            else round(max(0.0, self._clock() - since), 3),
        }
        return st

    def _dispatch_loop(self, q) -> None:
        """Dispatch thread: folds the feed's flushes, packs them and
        submits them (and window closes) to the device proxy in feed
        order, without waiting for the device round-trip. Packing batch
        N+1 here overlaps batch N's in-flight transfer on the proxy
        thread, and the bounded pipeline keeps the host->device link
        busy back-to-back (VERDICT r2 weak #1, r3 weak #1). ``q`` is the
        feed pool's TransferMux: ``get()`` blocks and delivers ``None``
        as the shutdown sentinel.

        **Folding.** A fused step costs the device the same whatever
        it holds and every dispatch costs the host, so steps follow the
        rows offered and the reads of the state, not the hand-overs
        and not the device falling idle. The feed workers hold what is
        dealt to them raw and flush it combined when the same causes
        hold for it (``FeedWorker._loop``); a flush taken off the mux is
        HELD here too, and what is held (across workers, up to one
        coalesced transfer) is folded into one batch
        (``fold_batches``) and dispatched when the pipeline has a slot
        (``feed_pipeline_depth``) and one of these holds
        (``tpu_dispatches_counter{cause}``):

        * ``full``: the fullest device's held rows reach
          ``batch_capacity`` (holding longer could not save a step);
        * ``age``: the first block of the oldest held flush was staged
          ``flush_max_age_s`` of the engine's clock ago: the bound
          counts the worker's staging and this hold together;
        * ``read``: something is about to read the state the rows
          belong in, and has asked the workers to flush what they
          stage (``FeedWorkerPool.request_flush``). A window tick
          carries that request: the thread takes what the workers hand
          off until every live one has answered (``_take_flushed``),
          then dispatches what is held before the close is submitted,
          so rows dealt before the tick land in its window (with the
          pipeline full the close overtakes what is held: no close
          waits). A snapshot about to submit its readback asks
          (``_release_held_for_read``) and is served once every live
          worker has answered, the mux holds no step item and what was
          held has been submitted (``_serve_reads``). A readback that
          released rows onto an idle device waits for their step to
          finish before it is submitted (``_dispatches_landed``: the
          tick here, for some 250 ms at most, the snapshot on its own
          thread);
        * ``drain``: shutdown.

        ``get`` blocks until an item, a wake (a completion gave a slot
        back: ``_dispatch_done``; a reader asked; the clock was
        advanced by hand: ``wake``) or the age bound of what is held
        with a slot free; its timeout is a safety bound. The thread
        parks its watchdog heartbeat before each wait and beats only
        when processing, so a long wait is not a stall."""
        hb = self._register_hb("engine-dispatch")
        coal = self.cfg.batch_capacity * max(
            1, self.cfg.feed_coalesce_windows
        )
        # Step items off the mux, not dispatched: each ends with the
        # clock's reading when its flush's first block was staged.
        held: list[tuple] = []
        try:
            while True:
                hb.park()
                try:
                    # Holding all one transfer may carry: take no more
                    # step items (the workers then wait on their
                    # handoff, which the controller reads), only ticks.
                    # With the pipeline full nothing is due by time:
                    # the completion wakes this wait.
                    item = q.get(
                        timeout=PARK_MAX_S,
                        steps=self._held_rows(held) < coal,
                        due=self._held_due(held)
                        if held and self._has_slot() else None,
                    )
                except queue_mod.Empty:
                    item = ()
                hb.beat()
                if item is None:
                    while held:
                        self._dispatch_held(
                            held, coal, mnames.DISPATCH_DRAIN)
                    return
                if item and item[0] == "step":
                    held.append(item)
                    self._note_held(held)
                elif item:
                    # Never for a window's length: ticks behind this
                    # one find what arrived meanwhile held.
                    bound = min(self.cfg.flush_max_age_s,
                                self.cfg.window_seconds / 4)
                    if item[1] is not None:
                        self._take_flushed(q, held, coal, item[1], bound)
                    onto_idle = bool(held) and self._busy_count() == 0
                    self._dispatch_for_read(held, coal)
                    if onto_idle:
                        self._dispatches_landed(bound)
                    try:
                        self._submit_close_window()
                    except Exception:
                        if self._count_error("dispatch"):
                            self.log.exception("window dispatch failed")
                self._serve_reads(q, held, coal)
                while held and (cause := self._release_cause(held)):
                    self._dispatch_held(held, coal, cause)
        finally:
            with self._reads:
                self._dispatch_thread = None
                self._reads.notify_all()
            self._deregister_hb("engine-dispatch")

    @staticmethod
    def _held_rows(held: list[tuple]) -> int:
        """Rows of the fullest device over the held step items."""
        if not held:
            return 0
        return int(sum(
            it[1].n_valid.astype(np.int64) for it in held
        ).max())

    @staticmethod
    def _held_since_of(held: list[tuple]) -> float:
        """When the first block of the oldest held flush was staged."""
        return min(it[4] for it in held)

    def _held_due(self, held: list[tuple]) -> float:
        """When the oldest held flush reaches ``flush_max_age_s``."""
        return self._held_since_of(held) + self.cfg.flush_max_age_s

    def _has_slot(self) -> bool:
        return self._busy_count() < self.cfg.feed_pipeline_depth

    def _release_cause(self, held: list[tuple]) -> str:
        """Why what is held goes to the device now, or "": a full
        pipeline takes nothing; otherwise a step's worth of rows goes
        at once (folding more could not save a step) and anything goes
        once its oldest flush's first block was staged
        ``flush_max_age_s`` ago. An idle
        pipeline alone is no reason: nobody reads what an early step
        wrote before the next window close or snapshot, and those ask
        (``_dispatch_for_read``)."""
        if not self._has_slot():
            return ""
        if self._held_rows(held) >= self.cfg.batch_capacity:
            return mnames.DISPATCH_FULL
        if self._clock() >= self._held_due(held):
            return mnames.DISPATCH_AGE
        return ""

    def _dispatch_for_read(self, held: list[tuple], coal: int) -> bool:
        """Something is about to read the device state: dispatch what
        is held while the pipeline has a slot. True when nothing is
        left held."""
        while held and self._has_slot():
            self._dispatch_held(held, coal, mnames.DISPATCH_READ)
        return not held

    def _serve_reads(self, q, held: list[tuple], coal: int) -> None:
        """(the dispatch thread, after each item or wake) Serve the
        readers that asked: once every live feed worker has answered
        the last one's flush request and no step item waits on the mux
        (what they handed off for it is held: an answer comes after its
        hand-off), what is held is submitted and they are released."""
        with self._reads:
            asked, epoch = self._reads_asked, self._read_epoch
        if asked <= self._reads_served:
            return
        pool = self._feed_pool
        if pool is not None and not pool.flushed(epoch):
            return
        if q.has_steps() or not self._dispatch_for_read(held, coal):
            return
        with self._reads:
            self._reads_served = asked
            self._reads.notify_all()

    def _take_flushed(
        self, q, held: list[tuple], coal: int, epoch: int, bound: float
    ) -> None:
        """(the dispatch thread, on a window tick) Take what the feed
        workers hand off for the tick's flush request ``epoch`` until
        every live worker has answered it and no step item is left on
        the mux, for ``bound`` seconds at most. Holding a transfer's
        worth, it dispatches while the pipeline has a slot and stops
        where it has none: a worker may then wait on its hand-off, and
        the tick waits for no answer it refuses the items of; its close
        overtakes what is still staged, as it overtakes what is held."""
        pool = self._feed_pool
        t_end = time.monotonic() + bound
        while not (pool.flushed(epoch) and not q.has_steps()):
            left = t_end - time.monotonic()
            if left <= 0:
                return
            if self._held_rows(held) >= coal:
                if not self._has_slot():
                    return
                self._dispatch_held(held, coal, mnames.DISPATCH_READ)
                continue
            try:
                item = q.get(timeout=left, ctl=False)
            except queue_mod.Empty:
                continue
            held.append(item)
            self._note_held(held)

    def _release_held_for_read(self) -> None:
        """(a reader's thread, before it submits a readback) Have the
        feed workers flush what they stage and the dispatch thread
        submit it with what it holds, and wait until it has and the
        device has finished it (``_dispatches_landed``): the proxy is
        FIFO, so the readback then holds every event dealt to the
        workers before this call. Bounded by ``flush_max_age_s`` (past
        it the rows have gone by age; 1 s at most), and a dispatch
        thread that is not running, has died or is on its way out is
        not waited for."""
        pool, t = self._feed_pool, self._dispatch_thread
        if pool is None or t is None or not t.is_alive():
            return
        bound = min(self.cfg.flush_max_age_s, PARK_MAX_S)
        t_end = time.monotonic() + bound
        with self._reads:
            self._read_epoch = pool.request_flush()
            self._reads_asked += 1
            want = self._reads_asked
            pool.mux.wake()
            self._reads.wait_for(
                lambda: self._reads_served >= want
                or self._dispatch_thread is not t or not t.is_alive(),
                timeout=bound,
            )
        self._dispatches_landed(max(0.0, t_end - time.monotonic()))

    def _dispatches_landed(self, bound: float | None = None) -> bool:
        """Wait until the device has finished every dispatch submitted
        so far, for ``bound`` seconds at most (None:
        ``flush_max_age_s``, 1 s at most); True if it has. A readback
        (a snapshot's, a window close's) released rows onto an idle
        device just before: it would wait for their step on the device
        anyway, and waits for it here, so that it is launched onto a
        device that has just finished and not on the heels of a
        dispatch that has just woken it. Measured, not understood: on
        the v5e a dispatch that met a wire bucket for the first time
        stalled the runtime (cured by ``_warm_run_ingest``), and where
        a readback had been launched right behind such a dispatch the
        stall was 2.5-5 s and piled window readbacks up into SAMPLING,
        against 0.9-1.8 s with the readback kept apart (PERF.md, PR 34:
        four cases each). Every launch pattern this leaves is one the
        one-block-a-second cells have always had."""
        if bound is None:
            bound = min(self.cfg.flush_max_age_s, PARK_MAX_S)
        def done() -> int:
            with self._busy_lock:
                return self._submitted_n - self._inflight_busy

        with self._busy_lock:
            target = self._submitted_n
        with self._reads:
            return self._reads.wait_for(
                lambda: done() >= target, timeout=bound)

    def _dispatch_held(
        self, held: list[tuple], coal: int, cause: str
    ) -> None:
        """Fold the longest prefix of the held flushes that fits one
        transfer and dispatch it (waits for a pipeline slot if none is
        free: only the shutdown drain calls it without one)."""
        sb, took = fold_batches(
            [it[1] for it in held], coal,
            min_bucket=self.cfg.transfer_min_bucket,
        )
        items = held[:took]
        del held[:took]
        n_raw = sum(it[3] for it in items)
        try:
            self._dispatch_sharded(
                sb, max(it[2] for it in items), n_raw, sync=False,
                n_flushes=took, cause=cause,
            )
        except Exception:
            if self._count_error("dispatch"):
                self.log.exception("step dispatch failed")
            # Raised before the batch reached the proxy (the sites
            # past that point count their own).
            self._count_unheld(n_raw)
        finally:
            self._note_held(held)

    def _note_held(self, held: list[tuple]) -> None:
        self._held_flushes = len(held)
        self._held_since = self._held_since_of(held) if held else None

    def start(self, stop: threading.Event) -> None:
        """Feed loop: drain sink → combine → partition → device; close
        windows on time.

        Sits where Enricher.Run + Module.run sit in the reference
        (enricher.go:68-99, metrics_module.go:266-330). This loop is the
        DISTRIBUTOR: it drains the sink, runs observers, and deals
        blocks to the feed workers (parallel/feed.py), which
        combine+partition in parallel and hand finished batches to the
        dispatch thread through the pool's double-buffered transfer mux.
        Flow-dict/wire/submit stay on the one dispatch thread (wire
        ordering contract), so batch N's transfer overlaps batch N+1's
        host-side prep; no edge blocks a producer (a saturated pool
        drops and counts, as the bounded sink does)."""
        self.started.set()
        if self._fleet_shipper is not None:
            self._fleet_shipper.start()
        if self._tt_ring is not None:
            self._tt_ring.start()
        cap = self.cfg.batch_capacity * self.n_devices
        # Flush threshold: accumulating beyond one device batch raises the
        # combine ratio (more duplicate descriptors per pass);
        # flush_max_age_s and the readers bound latency. Coalescing into
        # device batches happens inside _build_quantum. Per-worker
        # quantum splits the configured flush quantum so total staged
        # latency stays put as workers scale.
        quantum = max(cap, self.cfg.flush_max_events)
        n_workers = self._resolve_feed_workers()

        def drop_item(item):
            """Dead-worker path: account the loss, never enqueue into a
            queue nobody drains (silent vanishing)."""
            self.log.error("dispatch worker dead; dropping %s", item[0])
            if item[0] == "step":
                # Packet-weighted, like every other loss site: a
                # combined row stands for many events. Include the
                # batch's partition-overflow losses too — they are
                # normally counted inside _dispatch_sharded, which will
                # never run for a dropped item.
                get_metrics().lost_events.labels(
                    stage="dispatch", plugin="engine"
                ).inc(int(item[1].events) + int(item[1].lost))
                self._count_unheld(item[3])

        pool = FeedWorkerPool(
            n_workers=n_workers,
            quantum=max(cap, quantum // n_workers),
            staging_blocks=self.cfg.feed_staging_blocks,
            flush_max_age_s=self.cfg.flush_max_age_s,
            build_steps=self._build_quantum,
            drop=drop_item,
            alive=lambda: worker.is_alive(),
            register_hb=self._register_hb,
            deregister_hb=self._deregister_hb,
            restart_policy=lambda name: policy_from_config(
                self.cfg, seed_key=name
            ),
            clock=self._clock,
        )
        self._feed_pool = pool
        q = pool.mux
        worker = threading.Thread(
            target=self._dispatch_loop, args=(q,),
            name="engine-dispatch", daemon=True,
        )
        with self._reads:
            self._dispatch_thread = worker
        worker.start()
        pool.start()

        m = get_metrics()
        clock = self._clock
        next_window = clock() + self.cfg.window_seconds

        hb_feed = self._register_hb("engine-feed")
        try:
            while not stop.is_set():
                hb_feed.beat()
                # Overload controller tick: cheap no-op inside
                # overload_tick_s; transitions happen here, on the one
                # thread that sees every block.
                self._overload.tick()
                blocks = self.sink.drain(max_blocks=64)
                shed_dns = self._overload.shed_active("dns")
                # Span covers the deal: blocks leave the sink and are
                # dealt into the feed (observers + staging) — only when
                # there IS a drain, so an idle spin writes no span.
                sp_deal = self._recorder.span(
                    mnames.STAGE_DISTRIBUTOR_DEAL,
                    fleet_epoch(self.cfg.window_seconds),
                ) if blocks else NULL_SPAN
                for rec, plugin in blocks:
                    for obs, oname in self._observers:
                        if shed_dns and oname == "dns":
                            # SHEDDING: dns qname hashing is the first
                            # enrichment stage dropped — raw events
                            # still reach the device untouched.
                            self._overload.note_shed("dns", len(rec))
                            continue
                        try:
                            obs(rec, plugin)
                        except Exception:
                            # Observers run per block — a persistently
                            # failing one must not log at feed rate.
                            if self._count_error("observer"):
                                self.log.exception("observer failed")
                    # Deal the block to a worker and move on — the
                    # distributor NEVER blocks on a saturated pool
                    # (backpressure contract: drop and count,
                    # packet-weighted like every other loss site).
                    if not pool.stage(rec):
                        pool.count_drop(len(rec))
                        self._count_unheld(len(rec))
                        m.lost_events.labels(
                            stage="handoff", plugin="engine"
                        ).inc(int(rec[:, F.PACKETS].sum()))
                sp_deal.end()
                now = clock()
                if now >= next_window:
                    # Window ticks ride the mux control lane: closes
                    # overtake the step backlog and stay on cadence
                    # under overload. Never into a queue nobody drains.
                    # A tick is a read: the workers flush what was
                    # dealt before it, and the tick carries the
                    # request, which the dispatch thread waits on.
                    if worker.is_alive():
                        q.put_ctl(("window", pool.request_flush(), 0, 0))
                    else:
                        drop_item(("window", None, 0, 0))
                    # Batched tick: one close per catch-up, however many
                    # boundaries a stall skipped. Advancing by the missed
                    # count keeps the cadence phase-locked to the start
                    # time (ticks do not drift later under load) without
                    # queueing a burst of back-to-back closes on the ctl
                    # lane after the stall clears.
                    n_missed = int(
                        (now - next_window) // self.cfg.window_seconds
                    )
                    next_window += (
                        (n_missed + 1) * self.cfg.window_seconds
                    )
                if not blocks:
                    # Sleep until a block arrives (the sink sets its
                    # event) or the next thing due by the clock: the
                    # window tick, the controller's tick. The caller's
                    # stop event cannot signal this wait, hence its
                    # bound.
                    due = next_window
                    tick = self._overload.next_tick()
                    if tick is not None:
                        due = min(due, tick)
                    hb_feed.park()
                    park(self.sink.data, mnames.WAKE_FEED, clock,
                         due, max_s=FEED_PARK_MAX_S)
        finally:
            hb_feed.park()
            self._deregister_hb("engine-feed")
            # Stop the workers FIRST so their final flushes land in
            # the transfer mux, then send the sentinel down the control
            # lane — the mux hands it to the dispatch thread only after
            # every worker queue drains, so nothing staged at shutdown
            # is silently lost. put_ctl never blocks; the join timeout
            # bounds a wedged worker.
            pool.stop(timeout=30.0)
            q.put_ctl(None)
            worker.join(timeout=30.0)
            if not worker.is_alive():
                # A dispatch thread that died mid-run leaves whatever
                # was handed off before its death sitting in the
                # transfer queues: count it, like every other item the
                # dead-worker path drops.
                for item in q.drain_unconsumed():
                    drop_item(item)
            # Drain fire-and-forget submissions (FIFO fence) so the
            # state a follow-up checkpoint saves includes every batch
            # submitted before shutdown. Bounded like the queue/join
            # above: a wedged proxy must not hang shutdown forever.
            if not fence(timeout=60.0):
                self.log.error(
                    "device proxy did not drain within 60s at shutdown"
                )
            else:
                # Publish the final window's pending readback so
                # shutdown gauges aren't one window stale.
                try:
                    self._harvest_window()
                except Exception:
                    self._count_error("harvest_final")
                    self.log.exception("final window harvest failed")
            # Retire the harvest thread (it closes over self: left
            # parked on the queue it would pin the engine object graph
            # across restart cycles). Join the background warm FIRST —
            # a warm key in flight past its stop check could otherwise
            # enqueue one more window after the sentinel; the retired
            # flag then stops _ensure_harvest_thread from resurrecting
            # the thread for any straggler that still slips through.
            if self._warm_thread is not None:
                self._warm_thread.join(timeout=30.0)
            with self._harvest_lock:
                self._harvest_retired = True
                ht = self._harvest_thread
            if ht is not None:
                self._harvest_q.put(None)
                ht.join(timeout=5.0)
            # Stop the fleet shipper AFTER the fence: the final close's
            # export is already queued by then, so the last window still
            # ships before the worker parks.
            if self._fleet_shipper is not None:
                self._fleet_shipper.stop()
            # Same ordering for the time-travel ring: the final close's
            # export is queued before the fence returns.
            if self._tt_ring is not None:
                self._tt_ring.stop()

    @property
    def timetravel_ring(self):
        """The engine's snapshot ring (None unless timetravel_enabled);
        the daemon wires it into the QueryService."""
        return self._tt_ring

    # -- scrape-time readout -----------------------------------------
    def snapshot(self, max_age_s: float = 0.5) -> dict[str, Any]:
        """Merged numpy snapshot, cached up to ``max_age_s`` (scrape
        latency budget: <1s per BASELINE)."""
        now = time.monotonic()
        with self._snap_lock:
            if self._snap_cache is not None and now - self._snap_time < max_age_s:
                return self._snap_cache
        # Single-flight: with the fire-and-forget feed pipeline the
        # proxy queue may hold several in-flight transfers ahead of this
        # snapshot; concurrent readers must share ONE queued readback
        # (each re-checks the cache after acquiring), not pile N of them
        # behind the backlog.
        with self._snap_flight:
            with self._snap_lock:
                if (
                    self._snap_cache is not None
                    and time.monotonic() - self._snap_time < max_age_s
                ):
                    return self._snap_cache

            def snap_dispatch():
                # ONE device->host transfer for the whole tree (leaves
                # are concatenated on device): per-leaf readback paid a
                # full link round trip per array — measured 2.7-21s at
                # production shapes on a congested link vs the <1s
                # scrape budget. Only the DISPATCH runs on the proxy
                # (ordered against in-flight steps; later donating
                # steps execute after it on the device stream); the
                # queue-wait for the result happens on THIS thread via
                # fetch_on_device's readiness polling, so scrape/GC
                # traffic never parks the step pipeline — while every
                # actual JAX call still rides the proxy (one-thread rule
                # of utils/device_proxy.py).
                # _steps/_events_in advance on this thread in FIFO
                # order: read here they are exactly what the snapshot
                # holds (the publish watermark rests on that).
                with self._state_lock:
                    return self.sharded.snapshot_flat_dispatch(
                        self.state, int(time.time())
                    ), self._steps, self._events_in, self._events_unheld

            # shared=True: a snapshot may be taken on a thread that
            # lives for one request (a query handler).
            rec = self._recorder
            tid = fleet_epoch(self.cfg.window_seconds)
            timing: dict = {}
            with rec.span(mnames.STAGE_SNAPSHOT, tid, shared=True):
                with rec.span(
                    mnames.STAGE_SNAPSHOT_DISPATCH, tid, shared=True
                ):
                    # The rows the dispatch thread holds go first: the
                    # readback is submitted behind them.
                    self._release_held_for_read()
                    flat_dev, steps, events_in, unheld = run_on_device(
                        snap_dispatch, kind=mnames.KIND_SNAPSHOT
                    )
                with rec.span(
                    mnames.STAGE_SNAPSHOT_FETCH, tid, shared=True
                ) as sp:
                    flat_host = fetch_on_device(flat_dev, timing=timing)
                    sp.set(**timing)
                with rec.span(
                    mnames.STAGE_SNAPSHOT_FINISH, tid, shared=True
                ):
                    host = self.sharded.snapshot_flat_finish(flat_host)
            get_metrics().readback_bytes.inc(int(flat_host.nbytes))
            host["steps"] = steps
            host["events_in"] = events_in
            host["events_unheld"] = unheld
            with self._snap_lock:
                self._snap_cache = host
                self._snap_time = time.monotonic()
            return host

    def _count_unheld(self, n_raw: int) -> None:
        """``n_raw`` events the sink accepted were dropped before a
        step held them (every site also counts them, packet-weighted,
        under ``lost_events``). Called from the feed, dispatch and
        proxy threads, hence the lock; drops are rare."""
        if n_raw:
            with self._unheld_lock:
                self._events_unheld += n_raw

    def publish_lag_s(self, snap: dict[str, Any]) -> tuple[float, int]:
        """(seconds, events the snapshot holds): now minus the accept
        time of the oldest event the sink accepted that ``snap``
        neither holds nor ever will; 0 when none is. The sink's count
        of accepts and ``_events_in`` are both cumulative, so events
        dropped between the sink and the device would otherwise read
        as lag for the life of the process: ``snap`` carries their
        count as of its dispatch and they are passed over. They are
        passed over as a count, not by position, so between a drop and
        the landing of the blocks accepted before it the lag reads low
        by at most the time in which the dropped events were accepted;
        ``lost_events`` says that there was a drop."""
        included = int(snap.get("events_in", 0))
        t = self.sink.oldest_unheld(
            included + int(snap.get("events_unheld", 0))
        )
        return (max(0.0, time.monotonic() - t) if t is not None else 0.0,
                included)

    def top_flows(self, k: int = 20) -> tuple[np.ndarray, np.ndarray]:
        return topk_from_snapshot(self.snapshot(), "flow_hh", k)

    def top_services(self, k: int = 20) -> tuple[np.ndarray, np.ndarray]:
        return topk_from_snapshot(self.snapshot(), "svc_hh", k)

    def top_dns(self, k: int = 20) -> tuple[np.ndarray, np.ndarray]:
        return topk_from_snapshot(self.snapshot(), "dns_hh", k)

    def conntrack_gc(self) -> dict[str, int]:
        """Scrape conntrack liveness + accounting (expiry itself is
        timestamp-based in the table — the GC 'loop' is an accounting
        pass, like the reference GC summing conntrackmetadata while
        iterating the map, conntrack_linux.go:95-163).

        packets/bytes are the cumulative totals carried by conntrack
        reports, reassembled from per-device two-limb u32 counters.
        """
        snap = self.snapshot(max_age_s=5.0)
        totals = snap["totals"]
        ctt = np.asarray(snap["ct_totals"]).reshape(-1, 4).astype(np.uint64)
        pkts = int((ctt[:, 0] + (ctt[:, 1] << np.uint64(32))).sum())
        byts = int((ctt[:, 2] + (ctt[:, 3] << np.uint64(32))).sum())
        return {
            "active": int(snap["active_conns"]),
            "reports": int(totals[6]),
            "packets": pkts,
            "bytes": byts,
        }

    # -- checkpoint/resume (reference: pinned BPF maps survive agent
    # restarts, pkg/bpf/setup_linux.go; SURVEY.md §5.4) ---------------
    def save_snapshot_state(self, path: str) -> None:
        from retina_tpu.checkpoint import save_state

        def save():
            # Snapshot the reference only: state is replaced
            # functionally (never mutated in place), so the file write
            # — seconds of IO — must not hold _state_lock and convoy
            # the dispatch/close lanes behind it (RT403).
            with self._state_lock:
                state = self.state
            save_state(path, state, self.pcfg)

        run_on_device(save, kind=mnames.KIND_OTHER)

    def load_snapshot_state(self, path: str) -> bool:
        """Restore sketch state from ``path``. Crash-only: a missing or
        unusable checkpoint cold-starts (quarantined by load_state) —
        returns True only when state was actually resumed."""
        from retina_tpu.checkpoint import load_state

        def load():
            state, resumed = load_state(path, self.sharded, self.pcfg)
            with self._state_lock:
                self.state = state
            return resumed

        return run_on_device(load, kind=mnames.KIND_OTHER)
