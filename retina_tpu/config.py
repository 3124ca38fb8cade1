"""Layered agent configuration.

Reference analog: pkg/config/config.go:59-125 — viper merges a YAML file
with ``RETINA_``-prefixed environment variables into one static ``Config``
struct consumed by the daemon. Same layering here: dataclass defaults ←
YAML file ← ``RETINA_*`` env vars (env wins), via :func:`load_config`.

TPU-specific knobs (batch capacity, window length, mesh shape, pipeline
table sizes) live alongside the reference's flags because in this framework
the "kernel" is the jit-compiled pipeline and its compile-time shape IS
configuration — the analog of the reference injecting config into eBPF via
generated dynamic.h macros (packetparser_linux.go:82-127).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any

import yaml

# Data aggregation levels (reference pkg/config/config.go:16-23).
AGG_LOW = "low"
AGG_HIGH = "high"

DEFAULT_PLUGINS = ["packetparser", "dropreason", "packetforward", "dns"]


@dataclasses.dataclass
class Config:
    """Static agent configuration (reference Config, config.go:59-77)."""

    # --- reference-parity fields ---
    api_server_addr: str = "127.0.0.1:10093"
    enabled_plugins: list[str] = dataclasses.field(
        default_factory=lambda: list(DEFAULT_PLUGINS)
    )
    metrics_interval_s: float = 10.0  # map-read plugin cadence
    # /metrics render cache TTL (rendering tens of thousands of pod
    # series is Python-heavy; gauges only change at publish cadence, so
    # a sub-interval cache is lossless). 0 = render every scrape.
    metrics_cache_ttl_s: float = 0.5
    enable_telemetry: bool = False
    enable_pod_level: bool = True
    remote_context: bool = False
    enable_annotations: bool = False
    enable_conntrack_metrics: bool = True
    bypass_lookup_ip_of_interest: bool = False
    data_aggregation_level: str = AGG_LOW
    telemetry_interval_s: float = 900.0
    enable_hubble: bool = False  # flow-relay control plane (cmd/hubble)
    hubble_addr: str = "127.0.0.1:4244"
    hubble_ring_capacity: int = 1 << 12
    # Dedicated hubble metrics mux (reference :9965); "" disables.
    hubble_metrics_addr: str = ""
    # TLS for the flow relay (reference hubble TLS options). PEM paths;
    # client CA set => mutual TLS required.
    hubble_tls_cert: str = ""
    hubble_tls_key: str = ""
    hubble_tls_client_ca: str = ""
    # Local-client unix endpoint beside TCP (the reference serves
    # unix:///var/run/cilium/hubble.sock, SURVEY §3.5). "" disables.
    hubble_sock_path: str = ""
    # Static peer list for the peer service: [{"name", "address"}].
    hubble_peers: list = dataclasses.field(default_factory=list)
    node_name: str = ""
    # Identity from a real cluster: core/v1 pods/services/nodes list+watch
    # feeding the cache (pkg/k8s watcher analog). "" = in-process only.
    kubeconfig: str = ""
    kube_namespace: str = ""  # namespace scope for pod/service watches
    # Pod identity source when watching a cluster: "pods" (core/v1) or
    # "cilium" (consume the Cilium CNI's CiliumEndpoints — the
    # cilium-crds interop mode; services/nodes still come from core/v1).
    identity_source: str = "pods"

    # --- multi-host distributed runtime (jax.distributed over DCN;
    # SURVEY.md §5.8: cross-slice merges ride the distributed runtime
    # while intra-slice psum rides ICI). "" = single-process. ---
    distributed_coordinator: str = ""  # "host:port" of process 0
    distributed_num_processes: int = 1
    distributed_process_id: int = 0
    log_level: str = "info"
    log_file: str = ""  # empty = stderr only

    # --- event source (the kernel-hook analog; SURVEY.md §7 mapping) ---
    event_source: str = "synthetic"  # synthetic | pcap | live | external
    pcap_path: str = ""  # replay file for event_source=pcap
    pcap_loop: bool = True  # loop the replay
    synthetic_rate: float = 1e6  # target events/s for the generator
    synthetic_flows: int = 100_000
    # Pre-generate this many 8192-event blocks at compile() and cycle
    # them in the feed loop (0 = generate live). Keeps the numpy
    # generator out of the hot loop for max-rate benchmarking — the
    # trafficgen-replay analog.
    synthetic_pregen: int = 0
    # Generator regime preset (events/synthetic.py PRESETS): "default"
    # keeps the generator's own parameters; "zipf" is the heavy-tail
    # regime (steeper Zipf exponent, fewer dominating flows — the
    # PSketch-style skew the detector/attribution arc is validated
    # against); "uniform" flattens the flow-size distribution (the
    # worst case for top-k recall).
    gen_preset: str = "default"
    capture_iface: str = ""  # live AF_PACKET interface ("" = default)
    external_socket: str = "/tmp/retina-events.sock"  # external feed
    # Cilium agent monitor socket (gob payload stream) for the
    # ciliumeventobserver plugin (reference config.go MonitorSockPath).
    monitor_sock_path: str = "/var/run/cilium/monitor1_2.sock"
    # pktmon plugin (Windows): stream-server command + its socket. ""
    # command = the platform default (controller-pktmon.exe).
    pktmon_command: str = ""
    pktmon_socket: str = ""

    # --- TPU runtime knobs ---
    device_platform: str = ""  # "" = let JAX pick; "cpu" to force host
    # Persistent XLA compilation cache: full-shape pipeline compile is
    # ~100 s on TPU; caching it makes agent restarts (and the <1 s scrape
    # SLA after restart) feasible. "" disables (default: opt in via the
    # deploy configmap — DEFAULT_CACHE_DIR — so bare library/test use
    # never touches global host state).
    compilation_cache_dir: str = ""
    batch_capacity: int = 1 << 15  # events per device batch
    window_seconds: float = 1.0  # entropy/anomaly window
    # The longest a row waits on the host for the device, counted from
    # when its block was dealt to a feed worker: its staging there and
    # the dispatch thread's hold together. A feed worker holds the raw
    # blocks dealt to it (one combine over more blocks merges more
    # rows, and every flush costs the host) until its quantum is full,
    # a window close or a snapshot is about to read the state, or the
    # oldest has waited this long; the dispatch thread holds the
    # flushes it is handed (a step costs the device the same whatever
    # it holds, and every dispatch costs the host) until a step's worth
    # of rows is held, a reader asks, or the oldest flush's first block
    # is this old. Must stay below the metrics publish interval (1s) or
    # scrapes lag.
    flush_max_age_s: float = 0.4
    mesh_devices: int = 0  # 0 = all local devices
    # Bound on dispatches in flight behind the dispatch thread
    # (engine.py): submitted, and their last step not yet finished on
    # the device (transfers queue back-to-back on the device proxy so
    # the host->device link never idles between dispatch round-trips).
    # The dispatch thread dispatches what it holds (a step's worth, rows
    # of age flush_max_age_s, rows a reader asked for) only while a slot
    # is free. At least 1.
    feed_pipeline_depth: int = 3
    # Host feed pool (parallel/feed.py): N feed workers each own a
    # staging buffer, combine+partition their quantum in parallel (the
    # native combiner releases the GIL), and hand finished batches to
    # the single dispatch thread through a double-buffered transfer
    # queue. 0 = auto (cores-1 capped at 4, at least 1); n >= 1 = a
    # pool of n.
    feed_workers: int = 0
    # Per-worker staging bound, in raw sink blocks. A block that finds
    # every worker's staging full is dropped + counted (lost_events
    # stage="handoff") — backpressure never blocks the distributor.
    # Sized so one worker can stage a FULL flush quantum even from
    # small sink blocks (quantum / typical-block-rows with headroom):
    # at 256 the staging ring capped quanta at ~18% fill under
    # sustained load (BENCH_r05 staging fill 0.184) — flushes were
    # capacity-cut, not age-cut, and every fixed per-flush cost was
    # paid 5x too often. Memory bound: blocks are staged by reference
    # (the sink's arrays, no copy), so the bound is backlog, not
    # allocation.
    feed_staging_blocks: int = 1024
    # Background bucket-grid warm proxy duty cycle: after each warmed
    # key the warm thread yields cost*(1-d)/d seconds (capped at 10s)
    # to live traffic. 0.5 = equal yield (~50% proxy share, the
    # historical behavior); raise toward 1.0 to finish the warm faster
    # at the cost of feed throughput while it runs.
    warm_duty_cycle: float = 0.5
    # Max windows of batch_capacity coalesced into ONE host->device
    # transfer when a flush quantum combines to more than one device
    # batch: the wire crosses the link once and is sliced into
    # batch_capacity-sized step inputs on device. Amortizes per-transfer
    # round-trip latency (dominant on high-RTT links; one RTT per flush
    # instead of one per device batch).
    feed_coalesce_windows: int = 4
    # Smallest power-of-two host->device transfer shape: batches cross the
    # link at their own (bucketed) size and are padded to batch_capacity
    # on device, where HBM bandwidth makes padding free (engine pad jit).
    transfer_min_bucket: int = 1 << 12
    # Device-resident flow-descriptor dictionary over the 12-lane packed
    # wire (parallel/wire.py). Each distinct combined-flow descriptor
    # crosses the link ONCE (12 lanes + id); every later occurrence
    # crosses as a dense (id_bits + 10 + 22)-bit row (6.25 B at an
    # 18-bit id space; rows whose PACKETS/BYTES overflow the narrow
    # lanes ship full rows) and the descriptor lanes are gathered back
    # from HBM (parallel/flowdict.py + engine ingest). Off = every row
    # ships packed in full.
    wire_flow_dict: bool = True
    # Device descriptor-table slots (48 B/slot/device). Must exceed the
    # live distinct-descriptor count or the dictionary cycles
    # (generation clear -> one re-upload burst).
    flow_dict_slots: int = 1 << 18
    # Under sustained load, accumulate up to this many events per
    # combine+flush quantum (bigger quanta raise the combine ratio — more
    # duplicate descriptors per pass — at bounded added latency).
    # flush_max_age_s and the readers still bound latency at low rates.
    flush_max_events: int = 1 << 21
    snapshot_dir: str = ""  # sketch-state checkpoint dir ("" = off)
    snapshot_interval_s: float = 0.0  # 0 = only on shutdown

    # --- supervised runtime (runtime/supervisor.py) ---
    # A registered thread that neither beats nor parks for this long is
    # a stall: counted in watchdog_stalls and escalated (hung harvest
    # threads get replaced). Also the default bound on blocking fences
    # in the crash-only recovery path.
    watchdog_deadline_s: float = 30.0
    # Watchdog scan cadence.
    watchdog_interval_s: float = 0.5
    # Shutdown drain bound for the final harvest queue flush (was a
    # hard-coded 30.0 in engine._harvest_window).
    harvest_timeout_s: float = 30.0
    # Restart policy: exponential backoff base/cap with multiplicative
    # jitter; after restart_max_failures consecutive crashes inside
    # restart_window_s the circuit OPENS (the plugin/thread stops being
    # restarted and /healthz goes unhealthy) and half-open probes run
    # every circuit_half_open_s until one stays healthy.
    restart_backoff_base_s: float = 0.2
    restart_backoff_max_s: float = 30.0
    restart_backoff_jitter: float = 0.2
    restart_max_failures: int = 5
    restart_window_s: float = 60.0
    circuit_half_open_s: float = 30.0
    # Deterministic fault injection (runtime/faults.py), e.g.
    # "transfer:raise@3,plugin.packetparser:raise@1". Empty = disarmed.
    # Settable via RETINA_FAULT_SPEC for chaos drills against a
    # deployed agent.
    fault_spec: str = ""

    # --- adaptive overload control (runtime/overload.py) ---
    # NOMINAL -> SAMPLING -> SHEDDING -> DEGRADED driven by the max of
    # the normalized pressure signals (worker staging fill, handoff
    # wait rate, harvest lag, dispatch latency).
    overload_enabled: bool = True
    # Controller cadence; the feed loop calls tick() at least this often.
    overload_tick_s: float = 0.1
    # 1-in-k row sampling applied by feed workers in SAMPLING and above;
    # the device step rescales surviving non-exempt rows by k so every
    # packet-weighted estimate stays unbiased (Horvitz-Thompson).
    overload_sample_k: int = 8
    # Combined rows with at least this packet weight are heavy-hitter
    # candidates: exempt from sampling on the host AND from rescaling on
    # the device (the predicates must agree — both read F.PACKETS of
    # the post-combine row). 0 exempts everything (sampling disabled).
    overload_exempt_packets: int = 64
    # Hysteresis thresholds on the [0, 1] pressure scale. Escalation is
    # immediate at enter/shed/degrade; de-escalation needs pressure at
    # or below exit continuously for dwell_s (one level per dwell).
    overload_enter_pressure: float = 0.75
    overload_exit_pressure: float = 0.45
    overload_shed_pressure: float = 0.90
    overload_degrade_pressure: float = 0.98
    overload_dwell_s: float = 2.0
    # In SHEDDING the shed set widens one stage per this many seconds
    # of sustained at-or-above-shed pressure.
    overload_shed_escalate_s: float = 1.0
    # Enrichment shed order (cheapest-to-lose first); a prefix is shed
    # before ANY raw event is dropped. Stages: dns (qname hashing),
    # conntrack (accounting/GC scrape), labels (per-pod resolution).
    overload_shed_order: list[str] = dataclasses.field(
        default_factory=lambda: ["dns", "conntrack", "labels"]
    )
    # Priority-tier lattice (runtime/overload.py row_tiers): rows whose
    # src OR dst IP matches (ip & mask) == match form the per-(tenant,
    # service) priority class — exempt from sampling, and routed into
    # the invertible sketch's full-accuracy high-priority region.
    # mask 0 disables the class.
    overload_priority_ip_mask: int = 0
    overload_priority_ip_match: int = 0

    # --- invertible sketch (ops/invertible.py; heavy-key recovery) ---
    # Where heavy-flow KEYS come from:
    #   flowdict   — host flow-descriptor dictionary (the historical
    #                path; serialized, unbounded-memory)
    #   invertible — decode keys from device sketch state at window
    #                close; the flow dict leaves the hot path entirely
    #   both       — run both, report recovery recall/precision as
    #                metrics (the migration validation mode)
    heavy_keys_source: str = "flowdict"
    # Sketch shape: D hash rows x W buckets x 160 bit planes (u32), per
    # region. Update cost scales with D*B per row; decode with D*W*B.
    invertible_depth: int = 2
    invertible_width: int = 1 << 12
    # High-priority region width (receives only priority-class rows —
    # small because the priority class is small by construction).
    invertible_hi_width: int = 1 << 9
    # Decoded keys with a CMS estimate under this weight are rejected
    # (noise floor for the recovered-key set).
    invertible_min_weight: int = 0

    # --- AOT executable disk cache (parallel/telemetry.py AotProgram) ---
    # Persist AOT-compiled step/end-window executables keyed by (jax
    # version, topology, config signature) so bucket-grid warm survives
    # process restarts. "" disables (bench/deploy opt in).
    aot_cache_dir: str = ""

    # --- fleet rollup tier (fleet/) ---
    # Node side: ship the window-close sketch export over the relay.
    fleet_enabled: bool = False
    # Operator side: run the FleetAggregator (epoch-aligned merge +
    # fleet_* metric families). Both may be on in one process (the
    # in-process pubsub transport loops back).
    fleet_aggregator: bool = False
    fleet_node_name: str = ""  # wire identity ("" = node_name or pid)
    fleet_tenant: str = "default"
    # Higher priority tenants are shed LAST by the cardinality
    # guardrails (PSketch-style priority awareness).
    fleet_priority: int = 0
    # gRPC Ship target ("host:port"); "" ships over the in-process bus.
    fleet_relay_addr: str = ""
    # Close an epoch as soon as this many nodes reported; 0 = close on
    # the straggler timeout only.
    fleet_expected_nodes: int = 0
    # Epoch close deadline measured from the FIRST arrival — a dead
    # node delays the rollup at most this long, never forever.
    fleet_straggler_timeout_s: float = 2.0
    # Max open (unclosed) epochs buffered before the oldest is
    # force-closed: bounds aggregator memory under clock skew.
    fleet_epoch_history: int = 8
    # Node-side ship queue depth; a full queue drops the snapshot
    # (never blocks the window close).
    fleet_ship_queue: int = 4
    # Under SHEDDING and above, ship only 1 window in this many.
    fleet_shed_ship_every: int = 4
    # Node-side seed generation stamped on shipped frames; bump it when
    # rotating sketch seeds so the aggregator and fleet query plane
    # re-admit the node under the new generation instead of
    # quarantining it forever (fleet/codec.py "sgen" header field).
    fleet_seed_generation: int = 0
    # Send-failure spool: frames held in memory while the relay is
    # unreachable, replayed oldest-first on heal; the oldest frame is
    # evicted (and counted) when full. 0 disables spooling and restores
    # drop-on-error (still counted, never silent).
    fleet_ship_spool: int = 64
    # Jittered exponential backoff between send retries while the ship
    # circuit is open: delay is uniform in [base/2, min(max, base*2^n)].
    fleet_ship_backoff_base_s: float = 0.05
    fleet_ship_backoff_max_s: float = 2.0
    # Two-level rollup: re-ship each merged epoch as a valid RFLT
    # snapshot to a parent aggregator's relay at this address (the
    # zone -> root hop). "" disables — this aggregator is the root.
    fleet_reship_addr: str = ""
    # Defer quorum-closed epoch merges to the aggregator's poll thread
    # instead of running them inline on the ingest (gRPC handler)
    # thread. Keeps ingest latency flat through merge jit compiles —
    # otherwise the quorum-completing node's ship RPC pays the whole
    # merge and can blow its deadline, pushing that node into
    # spool/backoff every epoch. Off by default: inline merges publish
    # the rollup before ingest returns, which synchronous callers
    # (tests, co-located daemons) rely on.
    fleet_merge_async: bool = False
    fleet_topk_k: int = 32  # cluster-wide heavy-hitter series cap
    fleet_service_top: int = 16  # per-service cardinality series cap
    # Per-tenant exported-series cap (the label-space guardrail).
    fleet_tenant_series_max: int = 64
    # Max tenants exported per epoch; lowest-priority shed first.
    fleet_max_tenants: int = 16

    # --- time-travel query ring (timetravel/) ---
    # Retain the last N window-close sketch exports in a bounded ring
    # and serve [t0, t1) range queries over them (one jitted
    # semilattice fold). Off by default: the ring holds ~N x the
    # fleet-export footprint in host memory.
    timetravel_enabled: bool = False
    timetravel_ring_windows: int = 32  # ring capacity (slots)
    # Range-query result cache TTL; concurrent/overlapping queries are
    # served from cache so at most one fold runs at a time (the p99
    # bound). Under SHEDDING the TTL is ignored (serve stale freely).
    timetravel_query_cache_ttl_s: float = 1.0
    timetravel_query_topk: int = 32  # default k for /timetravel/query

    # --- closed-loop capture (timetravel/autocapture.py) ---
    # When the entropy burst detector fires, pivot the query ring to
    # the burst range, attribute sources via invertible decode, and
    # record a targeted capture of only the attributed keys. Needs
    # timetravel_enabled + enable_invertible for attribution.
    autocapture_enabled: bool = False
    autocapture_cooldown_s: float = 60.0  # min spacing between captures
    # Query range around burst window W: [W - lookback, W + lookahead].
    autocapture_lookback_windows: int = 2
    autocapture_lookahead_windows: int = 1
    autocapture_max_sources: int = 8  # top attributed src IPs captured
    autocapture_duration_s: float = 2.0  # capture recording window
    autocapture_max_size_mb: int = 8  # evidence bound: a few MB
    # Artifact sink directory (capture host_path output).
    autocapture_output_dir: str = "/tmp/retina-autocapture"

    # --- fleet query plane (fleetquery/) ---
    # Federated [t0, t1) range queries: GET /fleet/query scatter-gathers
    # per-node ring slots (or folds the aggregator's epoch ring) into
    # cluster-wide answers, with the node tier's bounded-latency
    # contract plus per-node deadline / hedged retry / partial coverage.
    fleetquery_enabled: bool = False
    fleetquery_node_deadline_s: float = 0.25  # per-node answer budget
    # After this long with nodes still unanswered, send ONE hedged
    # duplicate request per straggler (tail ≠ dead).
    fleetquery_hedge_delay_s: float = 0.05
    fleetquery_fanout: int = 16  # scatter pool concurrency bound
    fleetquery_cache_ttl_s: float = 1.0  # fleet result cache TTL
    fleetquery_topk: int = 32  # default k for /fleet/query

    # --- pluggable detector bank (detect/) ---
    # Derived device-program detectors (port-scan HLL, DNS-tunnel qname
    # entropy, SYN-flood asymmetry) over the engine's record tap; the
    # per-window winner (priority arbitration + cooldown) feeds the
    # same AutoCapture sink as the entropy detector.
    detectors_enabled: bool = False
    detector_cooldown_s: float = 60.0  # per-detector min firing spacing
    detector_z_thresh: float = 8.0  # adaptive (EWMA z-flag) threshold
    detector_min_windows: int = 3  # EWMA warmup before z-flags count

    # --- flight recorder + on-demand profiling (obs/) ---
    # Always-on span recorder over every pipeline stage
    # (docs/observability.md). Off only for A/B overhead measurement —
    # the recorder is the instrument every perf PR reads.
    trace_enabled: bool = True
    # Per-thread span ring capacity (preallocated slots).
    trace_ring_spans: int = 4096
    # POST /debug/profile: jax.profiler session + all-thread stack
    # dump artifacts land under this dir, newest profile_max_artifacts
    # session dirs kept.
    profile_artifact_dir: str = "/tmp/retina-profile"
    profile_max_seconds: float = 10.0  # per-session trace length cap
    profile_cooldown_s: float = 30.0  # min spacing between sessions
    profile_max_artifacts: int = 4

    # --- endurance soak harness (soak/; bench.py --soak) ---
    # Total soak wall clock for the default rotating schedule of
    # heavy-tail regimes + injected faults (docs/operations.md §9).
    soak_seconds: float = 1800.0
    # Per-phase duration; 0 = divide soak_seconds evenly over the
    # default schedule's phases.
    soak_phase_seconds: float = 0.0
    # After a phase's fault spec is cleared, the overload controller
    # must report NOMINAL within this bound (the no-latch-up
    # sentinel; recovery_seconds in the SOAK artifact).
    soak_recovery_deadline_s: float = 30.0
    # Post-warmup RSS leak gate: least-squares slope of the sampled
    # RSS series must stay under this (MB per minute).
    soak_rss_slope_mb_per_min: float = 5.0
    # Flow-descriptor dictionary generation bumps tolerated per phase
    # (the churn regimes cycle the table by design — but boundedly).
    soak_fd_generations_per_phase: int = 8
    # SOAK_*.json scorecard artifact directory.
    soak_artifact_dir: str = "/tmp/retina-soak"

    # --- pipeline shapes (jit keys; see models/pipeline.py) ---
    n_pods: int = 1 << 12
    cms_width: int = 1 << 15
    cms_depth: int = 4
    topk_slots: int = 1 << 11
    hll_precision: int = 12
    entropy_buckets: int = 1 << 12
    conntrack_slots: int = 1 << 18
    identity_slots: int = 1 << 16

    def validate(self) -> None:
        if self.identity_source not in ("pods", "cilium"):
            raise ValueError(
                f"identity_source must be 'pods' or 'cilium', "
                f"got {self.identity_source!r}"
            )
        if self.data_aggregation_level not in (AGG_LOW, AGG_HIGH):
            raise ValueError(
                f"dataAggregationLevel must be {AGG_LOW!r} or {AGG_HIGH!r}, "
                f"got {self.data_aggregation_level!r}"
            )
        if self.feed_pipeline_depth < 1:
            raise ValueError(
                f"feed_pipeline_depth must be >= 1, "
                f"got {self.feed_pipeline_depth}"
            )
        if not (0.0 < self.warm_duty_cycle <= 1.0):
            raise ValueError(
                f"warm_duty_cycle must be in (0, 1], "
                f"got {self.warm_duty_cycle}"
            )
        for f in ("watchdog_deadline_s", "watchdog_interval_s",
                  "harvest_timeout_s", "restart_backoff_base_s",
                  "restart_backoff_max_s", "restart_window_s",
                  "circuit_half_open_s"):
            if getattr(self, f) <= 0:
                raise ValueError(f"{f} must be > 0, got {getattr(self, f)}")
        if self.restart_max_failures < 1:
            raise ValueError(
                f"restart_max_failures must be >= 1, "
                f"got {self.restart_max_failures}"
            )
        if self.restart_backoff_jitter < 0:
            raise ValueError(
                f"restart_backoff_jitter must be >= 0, "
                f"got {self.restart_backoff_jitter}"
            )
        if self.fault_spec:
            # Fail at config load, not mid-flight in a hot-path hook:
            # faults.configure re-parses the same grammar when the
            # daemon arms it, so a parse-only dry run here is cheap.
            import re as _re

            # Keep this pattern in sync with faults._ENTRY.
            for raw in self.fault_spec.split(","):
                raw = raw.strip()
                if raw and not _re.match(
                    r"^[\w.\-]+:(raise|corrupt|hang(\d+(\.\d+)?)?"
                    r"|press(\d+(\.\d+)?)?)(@\d+)?$",
                    raw,
                ):
                    raise ValueError(f"bad fault_spec entry {raw!r}")
        for f in ("batch_capacity", "n_pods", "cms_width", "topk_slots",
                  "entropy_buckets", "conntrack_slots", "identity_slots"):
            v = getattr(self, f)
            if v <= 0 or (v & (v - 1)):
                raise ValueError(f"{f} must be a positive power of two, got {v}")
        if self.overload_sample_k < 1:
            raise ValueError(
                f"overload_sample_k must be >= 1, "
                f"got {self.overload_sample_k}"
            )
        if self.overload_exempt_packets < 0:
            raise ValueError(
                f"overload_exempt_packets must be >= 0, "
                f"got {self.overload_exempt_packets}"
            )
        thresholds = (
            self.overload_exit_pressure, self.overload_enter_pressure,
            self.overload_shed_pressure, self.overload_degrade_pressure,
        )
        if not all(0.0 < t <= 1.0 for t in thresholds) or any(
            a >= b for a, b in zip(thresholds, thresholds[1:])
        ):
            raise ValueError(
                "overload thresholds must satisfy 0 < exit < enter < "
                f"shed < degrade <= 1, got {thresholds}"
            )
        for f in ("overload_tick_s", "overload_dwell_s",
                  "overload_shed_escalate_s"):
            if getattr(self, f) <= 0:
                raise ValueError(f"{f} must be > 0, got {getattr(self, f)}")
        from retina_tpu.runtime.overload import validate_shed_order

        validate_shed_order(self.overload_shed_order)
        if self.fleet_straggler_timeout_s <= 0:
            raise ValueError(
                f"fleet_straggler_timeout_s must be > 0, "
                f"got {self.fleet_straggler_timeout_s}"
            )
        for f in ("fleet_epoch_history", "fleet_ship_queue",
                  "fleet_shed_ship_every", "fleet_topk_k",
                  "fleet_service_top", "fleet_tenant_series_max"):
            if getattr(self, f) < 1:
                raise ValueError(
                    f"{f} must be >= 1, got {getattr(self, f)}"
                )
        for f in ("fleet_expected_nodes", "fleet_max_tenants",
                  "fleet_seed_generation", "fleet_ship_spool"):
            if getattr(self, f) < 0:
                raise ValueError(
                    f"{f} must be >= 0, got {getattr(self, f)}"
                )
        if self.fleet_ship_backoff_base_s <= 0:
            raise ValueError(
                f"fleet_ship_backoff_base_s must be > 0, "
                f"got {self.fleet_ship_backoff_base_s}"
            )
        if self.fleet_ship_backoff_max_s < self.fleet_ship_backoff_base_s:
            raise ValueError(
                "fleet_ship_backoff_max_s must be >= "
                f"fleet_ship_backoff_base_s, got "
                f"{self.fleet_ship_backoff_max_s}"
            )
        # Single source of truth for legal preset names: the PRESETS
        # table in events/synthetic.py (a name added there is legal
        # here automatically — no hand-maintained copy to drift, the
        # RT230 philosophy). Local import like validate_shed_order
        # above: synthetic pulls numpy at module load, which config
        # must not do for bare Config() construction.
        from retina_tpu.events.synthetic import PRESETS as _gen_presets

        if self.gen_preset not in _gen_presets:
            raise ValueError(
                f"gen_preset must be one of {sorted(_gen_presets)}, "
                f"got {self.gen_preset!r}"
            )
        for f in ("timetravel_ring_windows", "timetravel_query_topk",
                  "autocapture_max_sources", "autocapture_max_size_mb"):
            if getattr(self, f) < 1:
                raise ValueError(
                    f"{f} must be >= 1, got {getattr(self, f)}"
                )
        for f in ("timetravel_query_cache_ttl_s",
                  "autocapture_cooldown_s",
                  "autocapture_lookback_windows",
                  "autocapture_lookahead_windows"):
            if getattr(self, f) < 0:
                raise ValueError(
                    f"{f} must be >= 0, got {getattr(self, f)}"
                )
        for f in ("fleetquery_fanout", "fleetquery_topk"):
            if getattr(self, f) < 1:
                raise ValueError(
                    f"{f} must be >= 1, got {getattr(self, f)}"
                )
        if self.fleetquery_node_deadline_s <= 0:
            raise ValueError(
                f"fleetquery_node_deadline_s must be > 0, "
                f"got {self.fleetquery_node_deadline_s}"
            )
        for f in ("fleetquery_hedge_delay_s", "fleetquery_cache_ttl_s",
                  "detector_cooldown_s"):
            if getattr(self, f) < 0:
                raise ValueError(
                    f"{f} must be >= 0, got {getattr(self, f)}"
                )
        if self.detector_z_thresh <= 0:
            raise ValueError(
                f"detector_z_thresh must be > 0, "
                f"got {self.detector_z_thresh}"
            )
        if self.detector_min_windows < 1:
            raise ValueError(
                f"detector_min_windows must be >= 1, "
                f"got {self.detector_min_windows}"
            )
        if self.autocapture_duration_s <= 0:
            raise ValueError(
                f"autocapture_duration_s must be > 0, "
                f"got {self.autocapture_duration_s}"
            )
        if self.heavy_keys_source not in ("flowdict", "invertible", "both"):
            raise ValueError(
                "heavy_keys_source must be 'flowdict', 'invertible' or "
                f"'both', got {self.heavy_keys_source!r}"
            )
        if self.heavy_keys_source == "both" and not self.wire_flow_dict:
            raise ValueError(
                "heavy_keys_source='both' validates the invertible decode "
                "against the flow dict, which requires wire_flow_dict"
            )
        for f in ("invertible_width", "invertible_hi_width"):
            v = getattr(self, f)
            if v <= 0 or (v & (v - 1)):
                raise ValueError(
                    f"{f} must be a positive power of two, got {v}"
                )
        if self.invertible_depth < 1:
            raise ValueError(
                f"invertible_depth must be >= 1, got {self.invertible_depth}"
            )
        if self.invertible_min_weight < 0:
            raise ValueError(
                f"invertible_min_weight must be >= 0, "
                f"got {self.invertible_min_weight}"
            )
        for f in ("soak_seconds", "soak_recovery_deadline_s",
                  "soak_rss_slope_mb_per_min"):
            if getattr(self, f) <= 0:
                raise ValueError(
                    f"{f} must be > 0, got {getattr(self, f)}"
                )
        if self.soak_phase_seconds < 0:
            raise ValueError(
                f"soak_phase_seconds must be >= 0, "
                f"got {self.soak_phase_seconds}"
            )
        if self.soak_fd_generations_per_phase < 1:
            raise ValueError(
                f"soak_fd_generations_per_phase must be >= 1, "
                f"got {self.soak_fd_generations_per_phase}"
            )
        for f in ("trace_ring_spans", "profile_max_artifacts"):
            if getattr(self, f) < 1:
                raise ValueError(
                    f"{f} must be >= 1, got {getattr(self, f)}"
                )
        if self.profile_max_seconds <= 0:
            raise ValueError(
                f"profile_max_seconds must be > 0, "
                f"got {self.profile_max_seconds}"
            )
        if self.profile_cooldown_s < 0:
            raise ValueError(
                f"profile_cooldown_s must be >= 0, "
                f"got {self.profile_cooldown_s}"
            )
        for f in ("overload_priority_ip_mask", "overload_priority_ip_match"):
            v = getattr(self, f)
            if not (0 <= v <= 0xFFFFFFFF):
                raise ValueError(f"{f} must fit in u32, got {v}")


_BOOL_TRUE = {"1", "true", "yes", "on"}


def _coerce(value: str, target_type: Any) -> Any:
    if target_type is bool:
        return value.strip().lower() in _BOOL_TRUE
    if target_type is int:
        return int(value, 0)
    if target_type is float:
        return float(value)
    if target_type is list or target_type == list[str]:
        return [p.strip() for p in value.split(",") if p.strip()]
    return value


# YAML keys accepted in camelCase (reference configmap style) or snake_case.
def _normalize_key(key: str) -> str:
    out = []
    for ch in key:
        if ch.isupper():
            out.append("_")
            out.append(ch.lower())
        else:
            out.append(ch)
    return "".join(out).lstrip("_")


_ALIASES = {
    "enabled_plugin": "enabled_plugins",
    "enabled_plugin_linux": "enabled_plugins",
    "metrics_interval_duration": "metrics_interval_s",
    "telemetry_interval": "telemetry_interval_s",
}


def load_config(
    path: str | None = None,
    overrides: dict[str, Any] | None = None,
    env: dict[str, str] | None = None,
) -> Config:
    """YAML file ← RETINA_* env ← explicit overrides (later wins)."""
    cfg = Config()
    fields = {f.name: f for f in dataclasses.fields(Config)}

    def apply(key: str, raw: Any, from_env: bool) -> None:
        key = _ALIASES.get(_normalize_key(key), _normalize_key(key))
        if key not in fields:
            return  # unknown keys ignored, like viper
        f = fields[key]
        ftype = f.type if not isinstance(f.type, str) else {
            "str": str, "int": int, "float": float, "bool": bool,
            "list[str]": list,
        }.get(f.type, str)
        if from_env or isinstance(raw, str) and ftype is not str:
            raw = _coerce(str(raw), ftype)
        setattr(cfg, key, raw)

    if path:
        with open(path) as fh:
            doc = yaml.safe_load(fh) or {}
        if not isinstance(doc, dict):
            raise ValueError(f"config file {path} must be a YAML mapping")
        for k, v in doc.items():
            apply(k, v, from_env=False)

    env = dict(os.environ if env is None else env)
    for k, v in env.items():
        if k.startswith("RETINA_"):
            apply(k[len("RETINA_"):].lower(), v, from_env=True)

    for k, v in (overrides or {}).items():
        apply(k, v, from_env=False)

    cfg.validate()
    return cfg


# The value the deploy manifests write into their configmap's
# compilation_cache_dir. Nothing in the tree opens it on its own.
DEFAULT_CACHE_DIR = "/var/cache/retina-tpu/xla"

# Where bench.py and chip_smoke.py keep compiled programs when the
# caller has not placed the cache with JAX_COMPILATION_CACHE_DIR: one
# fixed, git-ignored directory of the checkout. The path is part of the
# persistent cache's key, so it must never move between runs.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".retina_cache",
)


def harness_cache_dirs() -> tuple[str, str]:
    """``(xla_dir, aot_dir)`` for bench.py and chip_smoke.py.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places both: JAX's
    persistent cache at that directory and the AOT executable cache
    (``cfg.aot_cache_dir``) in its ``aot`` subdirectory. Unset, both
    live under :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if env:
        return env, os.path.join(env, "aot")
    return (
        os.path.join(CHECKOUT_CACHE_DIR, "xla"),
        os.path.join(CHECKOUT_CACHE_DIR, "aot"),
    )


def enable_harness_caches() -> tuple[str, str]:
    """Turn on both caches for bench.py / chip_smoke.py and return
    ``(xla_dir, aot_dir)`` as used. An unusable directory is an error
    here, not a warning."""
    xla_dir, aot_dir = harness_cache_dirs()
    xla_dir = enable_compilation_cache(xla_dir, strict=True)
    try:
        os.makedirs(aot_dir, exist_ok=True)
    except OSError as e:
        raise RuntimeError(f"AOT cache at {aot_dir} unusable: {e}") from e
    return xla_dir, aot_dir


def enable_compilation_cache(cache_dir: str, strict: bool = False) -> str:
    """Turn on JAX's persistent compilation cache; returns the directory
    in use ("" = off).

    ``JAX_COMPILATION_CACHE_DIR`` wins: when it is set JAX has already
    read it and ``cache_dir`` is ignored — no code path then calls
    ``jax.config.update``. Otherwise the cache is pointed at
    ``cache_dir`` ("" leaves it off). JAX's default min-compile-time
    threshold is kept: the target is the minutes-long fused-step
    compile, and the threshold stops trivial compiles from growing the
    directory unboundedly.

    A directory that cannot be created or written is a logged warning
    for the agent (it still boots; restarts pay the full compile) and,
    with ``strict``, an error: a harness that reports warm-boot seconds
    must not run without the cache it reports on."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    target = env or cache_dir
    if not target:
        return ""
    try:
        os.makedirs(target, exist_ok=True)
        if not os.access(target, os.W_OK | os.X_OK):
            raise PermissionError(f"{target} is not writable")
    except OSError as e:
        if strict:
            raise RuntimeError(
                f"compilation cache at {target} unusable: {e}"
            ) from e
        from retina_tpu.log import logger

        logger("config").warning(
            "compilation cache at %s unavailable (%s: %s); "
            "restarts will pay full XLA compile",
            target, type(e).__name__, e,
        )
        return ""
    import jax

    # The cache's key leaves out an operation's metadata by default, so
    # a program compiled by another tree is a hit whose executable
    # carries THAT tree's op_name metadata: the operator scopes of this
    # one's device programs (models/pipeline.STEP_SCOPES) would be
    # missing from every profile and from op_scopes.json (seen on the
    # chip, PR 26: a step cached by the parent commit had no scope).
    # With the metadata in the key a hit is this tree's own program.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if not env:
        jax.config.update("jax_compilation_cache_dir", target)
    return target
