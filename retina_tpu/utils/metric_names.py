"""Prometheus metric name constants.

Reference analog: pkg/utils/metric_names.go:14-36 — every exported series
carries the ``networkobservability_`` prefix; basic (node-level) names and
advanced (pod-level, ``adv_``) names are distinct families.
"""

PREFIX = "networkobservability_"

# Basic node-level metrics (default registry).
DROP_COUNT = PREFIX + "drop_count"
DROP_BYTES = PREFIX + "drop_bytes"
FORWARD_COUNT = PREFIX + "forward_count"
FORWARD_BYTES = PREFIX + "forward_bytes"
TCP_STATE = PREFIX + "tcp_state"
TCP_CONNECTION_REMOTE = PREFIX + "tcp_connection_remote"
TCP_CONNECTION_STATS = PREFIX + "tcp_connection_stats"
TCP_FLAG_COUNTERS = PREFIX + "tcp_flag_counters"
IP_CONNECTION_STATS = PREFIX + "ip_connection_stats"
UDP_CONNECTION_STATS = PREFIX + "udp_connection_stats"
INTERFACE_STATS = PREFIX + "interface_stats"
INFINIBAND_COUNTER_STATS = PREFIX + "infiniband_counter_stats"
INFINIBAND_STATUS_PARAMS = PREFIX + "infiniband_status_params"
DNS_REQUEST_COUNT = PREFIX + "dns_request_count"
DNS_RESPONSE_COUNT = PREFIX + "dns_response_count"
NODE_CONNECTIVITY_STATUS = PREFIX + "node_connectivity_status"
NODE_CONNECTIVITY_LATENCY = PREFIX + "node_connectivity_latency_seconds"
CONNTRACK_PACKETS = PREFIX + "conntrack_packets"
CONNTRACK_BYTES = PREFIX + "conntrack_bytes"

# Advanced pod-level metrics (resettable advanced registry).
ADV_PREFIX = PREFIX + "adv_"
ADV_FORWARD_COUNT = ADV_PREFIX + "forward_count"
ADV_FORWARD_BYTES = ADV_PREFIX + "forward_bytes"
ADV_DROP_COUNT = ADV_PREFIX + "drop_count"
ADV_DROP_BYTES = ADV_PREFIX + "drop_bytes"
ADV_TCP_FLAG_COUNTERS = ADV_PREFIX + "tcpflags_count"
ADV_TCP_RETRANS_COUNT = ADV_PREFIX + "tcpretrans_count"
ADV_DNS_REQUEST_COUNT = ADV_PREFIX + "dns_request_count"
ADV_DNS_RESPONSE_COUNT = ADV_PREFIX + "dns_response_count"
ADV_API_LATENCY = ADV_PREFIX + "node_apiserver_latency"
ADV_API_NO_RESPONSE = ADV_PREFIX + "node_apiserver_no_response"

# Sketch-derived series (new in the TPU framework).
SKETCH_PREFIX = PREFIX + "sketch_"
HEAVY_HITTER_FLOWS = SKETCH_PREFIX + "heavy_hitter_flow_packets"
HEAVY_HITTER_SERVICES = SKETCH_PREFIX + "service_graph_packets"
HEAVY_HITTER_DNS = SKETCH_PREFIX + "dns_heavy_hitter_count"
DISTINCT_FLOWS = SKETCH_PREFIX + "distinct_flows"
DISTINCT_SRC_PER_REASON = SKETCH_PREFIX + "distinct_sources_per_drop_reason"
DISTINCT_SRC_PER_POD = SKETCH_PREFIX + "distinct_sources_per_pod"
ENTROPY_BITS = SKETCH_PREFIX + "entropy_bits"
ANOMALY_FLAG = SKETCH_PREFIX + "anomaly_flag"
ANOMALY_ZSCORE = SKETCH_PREFIX + "anomaly_zscore"
# Monotonic count of anomalous windows: the flag gauge only shows
# the CURRENT window, which a 10-30s scrape cadence would miss for
# sub-second windows.
ANOMALY_WINDOWS = SKETCH_PREFIX + "anomaly_windows_total"
ACTIVE_CONNECTIONS = PREFIX + "conntrack_active_connections"

# Control-plane self metrics (reference pkg/metrics/metrics.go:14-120).
PLUGIN_RECONCILE_FAILURES = PREFIX + "plugin_manager_failed_to_reconcile"
LOST_EVENTS = PREFIX + "lost_events_counter"
# Table entries (filter IPs / pod identities) dropped because a
# fixed-capacity device table was full — the agent clamps and stays up
# (reference counts per-IP map-write failures the same way,
# manager_linux.go:62-100).
LOST_TABLE_ENTRIES = PREFIX + "lost_table_entries_counter"
# Filter-map device pushes that exhausted every retry (transient device
# failure outlasting the backoff): the device filter set is stale until
# the next successful push — invisible without this counter.
FILTER_PUSH_FAILURES = PREFIX + "filter_push_failures_counter"
# v2-wire flow dictionary self-observability: resident descriptors,
# generation (bumps = capacity cycles or failure resyncs), capacity
# clears alone (a dispatch would have overflowed the table: every flow
# re-uploads its descriptor; a failure resync is not one), and wire
# rows by kind — known/new ratio IS the wire savings factor. ``new``
# rows cross as full 52-byte rows and enter the device table;
# ``tableless`` rows cross the same way but found the table full (more
# new descriptors in one dispatch than it has slots) and enter nothing;
# ``known`` rows cross dense against the table.
FLOW_DICT_ENTRIES = PREFIX + "tpu_flow_dict_entries"
FLOW_DICT_GENERATION = PREFIX + "tpu_flow_dict_generation"
FLOW_DICT_CLEARS = PREFIX + "tpu_flow_dict_clears_counter"
WIRE_ROWS = PREFIX + "tpu_wire_rows_counter"
L_KIND = "kind"
WIRE_NEW = "new"
WIRE_KNOWN = "known"
WIRE_TABLELESS = "tableless"
PARSED_PACKETS = PREFIX + "parsed_packets_counter"
# Sharded feed-worker backpressure (parallel/feed.py): per-worker
# quantum fill at flush, seconds spent waiting for a free handoff slot
# (a persistently growing wait means the dispatch/device side is the
# bottleneck, not the host), and blocks dropped because every worker's
# staging was full.
FEED_WORKER_FILL = PREFIX + "tpu_feed_worker_fill_ratio"
FEED_HANDOFF_WAIT = PREFIX + "tpu_feed_handoff_wait_seconds"
FEED_BLOCKS_DROPPED = PREFIX + "tpu_feed_blocks_dropped"
L_WORKER = "worker"
# Returns from a wait of the feed path (parallel/feed.park), by the
# thread that waited and by what ended the wait: ``data`` (the event it
# parks on was set: a block, a finished batch, a window tick, a stop,
# the pipeline going idle) or ``deadline`` (the wait ran out: a flush
# age, a window tick, the controller's tick, or the safety bound of an
# idle thread). A few hundred a second at any rate; thousands mean a
# thread polls.
FEED_WAKEUPS = PREFIX + "tpu_feed_wakeups_counter"
L_CAUSE = "cause"
WAKE_FEED = "feed"
WAKE_WORKER = "worker"
WAKE_DISPATCH = "dispatch"
CAUSE_DATA = "data"
CAUSE_DEADLINE = "deadline"
# Flushes of the feed workers (parallel/feed.FeedWorker._flush), by
# what released the raw blocks a worker held: its staged rows reached
# its quantum (``full``), its oldest staged block reached
# flush_max_age_s (``age``), a reader asked (``read``: a window tick or
# a snapshot, FeedWorkerPool.request_flush), shutdown (``drain``). Each
# flush is one combine and one partition.
FEED_FLUSHES = PREFIX + "tpu_feed_flushes_counter"
FLUSH_FULL = "full"
FLUSH_AGE = "age"
FLUSH_READ = "read"
FLUSH_DRAIN = "drain"
# Window ticks deferred because the close program was still queued in
# the background warm (engine._close_window_impl): the window stays
# open instead of cold-compiling end_window inline mid-feed.
WINDOWS_DEFERRED = PREFIX + "tpu_windows_deferred"
# Supervised-runtime robustness counters (runtime/supervisor.py).
# engine_restarts counts full crash-only engine recoveries (device
# state rebuilt, resumed from the last checkpoint); watchdog_stalls
# counts missed-heartbeat escalations per thread; plugin_restarts and
# thread_restarts count supervised restarts of plugin runners and of
# engine-internal threads; engine_errors is the named-counter side of
# the broad-except audit (every swallow bumps a site label);
# degraded_mode is 1 while the engine is dropping-and-counting during
# a recovery; recovery_seconds is the teardown→re-warm→resume latency.
ENGINE_RESTARTS = PREFIX + "tpu_engine_restarts"
WATCHDOG_STALLS = PREFIX + "watchdog_stalls_counter"
PLUGIN_RESTARTS = PREFIX + "plugin_restarts_counter"
THREAD_RESTARTS = PREFIX + "thread_restarts_counter"
ENGINE_ERRORS = PREFIX + "engine_errors_counter"
DEGRADED_MODE = PREFIX + "tpu_degraded_mode"
RECOVERY_SECONDS = PREFIX + "tpu_recovery_seconds"
# Stalls of seconds, far under the watchdog's deadline
# (runtime/supervisor.py; docs/observability.md, "Stalls"). The scan
# that wakes every watchdog_interval_s counts itself (watchdog_scans)
# and how late it woke (wake_late_seconds: woke less due, summed; over
# the scans it is what a Python thread of this process pays to get the
# CPU and the interpreter back after a wait). stall_seconds{cause} sums
# the gaps of the `stall` spans: `paused` (a scan woke late and the
# process had burnt hardly any CPU: nobody ran), `held` (it woke late
# and somebody had run), `thread` (one thread mid-work and silent).
TPU_WAKE_LATE_SECONDS = PREFIX + "tpu_wake_late_seconds_counter"
TPU_WATCHDOG_SCANS = PREFIX + "tpu_watchdog_scans_counter"
TPU_STALL_SECONDS = PREFIX + "tpu_stall_seconds_counter"
STALL_PAUSED = "paused"
STALL_HELD = "held"
STALL_THREAD = "thread"
STALL_CAUSES = (STALL_PAUSED, STALL_HELD, STALL_THREAD)
# Adaptive overload control (runtime/overload.py). overload_state is
# the controller state as a number (0=NOMINAL 1=SAMPLING 2=SHEDDING
# 3=DEGRADED); events_sampled counts raw (packet-weighted) events
# dropped by the feed-worker 1-in-k sampler and re-represented on
# device by x k rescaling; events_shed counts shed enrichment work per
# stage (events for dns, passes for conntrack/labels, raw handoff
# drops under stage="raw"); accuracy_debt is the cumulative packet
# weight SYNTHESIZED by the device rescaling — the estimated (not
# observed) share of the sketch totals.
OVERLOAD_STATE = PREFIX + "tpu_overload_state"
# What the controller was told at its last tick: overload_pressure is
# the largest signal (what the thresholds are compared with),
# overload_signal{signal} each one (staging, handoff_wait, harvest,
# dispatch_lat, fault, degraded).
OVERLOAD_PRESSURE = PREFIX + "tpu_overload_pressure"
OVERLOAD_SIGNAL = PREFIX + "tpu_overload_signal"
L_SIGNAL = "signal"
EVENTS_SAMPLED = PREFIX + "tpu_events_sampled_counter"
EVENTS_SHED = PREFIX + "tpu_events_shed_counter"
ACCURACY_DEBT = PREFIX + "tpu_accuracy_debt_counter"
DEVICE_STEP_SECONDS = PREFIX + "tpu_step_seconds"
DEVICE_BATCH_FILL = PREFIX + "tpu_batch_fill_ratio"
# The dispatch thread's folding (engine._dispatch_loop): fused steps
# dispatched, valid rows folded into them (rows / (steps x
# batch_capacity x devices) is the mean step fill), and flushes of the
# feed (worker or inline quanta) folded into dispatches; a dispatch is
# one observation of tpu_step_seconds.
STEPS = PREFIX + "tpu_steps_counter"
STEP_ROWS = PREFIX + "tpu_step_rows_counter"
DISPATCH_FLUSHES = PREFIX + "tpu_dispatch_flushes_counter"
# Dispatches of the feed path by what released the rows the dispatch
# thread held (engine._dispatch_loop): a step's worth was held
# (``full``), the oldest held flush reached flush_max_age_s (``age``),
# a window close or a snapshot was about to read the state (``read``),
# shutdown (``drain``). Counted where tpu_dispatch_flushes_counter is,
# so summed over its label it is tpu_step_seconds_count less the
# synchronous dispatches (tests, the recovery probe), which nobody held.
DISPATCHES = PREFIX + "tpu_dispatches_counter"
DISPATCH_FULL = "full"
DISPATCH_AGE = "age"
DISPATCH_READ = "read"
DISPATCH_DRAIN = "drain"
# Valid rows dispatched to each device of the mesh (the host partition's
# shares: partition.partition_events); summed over its label it is
# tpu_step_rows_counter. The fullest device sizes every device's wire
# and overflows first.
SHARD_ROWS = PREFIX + "tpu_shard_rows_counter"
L_DEVICE = "device"
WINDOWS_CLOSED = PREFIX + "tpu_windows_closed"
COMBINE_RATIO = PREFIX + "host_combine_ratio"
TRANSFER_SECONDS = PREFIX + "tpu_transfer_seconds"
TRANSFER_BYTES = PREFIX + "tpu_transfer_bytes"
READBACK_BYTES = PREFIX + "tpu_readback_bytes"

# Fleet rollup tier (fleet/): cluster-wide series published by the
# operator-side aggregator, plus node-side shipper self-metrics.
# Shipper: snapshots_shipped counts frames actually sent;
# ship_bytes the encoded wire bytes; ship_deferred windows skipped by
# the SHEDDING backoff (1-in-fleet_shed_ship_every); ship_dropped
# windows lost to a full ship queue; ship_errors failed sends.
# Aggregator: snapshots_received{node} accepted frames;
# snapshots_dropped{reason} rejects (decode/late/duplicate/
# seed_mismatch/shape_mismatch); windows_merged closed epochs;
# windows_stragglers epochs closed by timeout instead of quorum;
# merge_errors failed poll/merge passes; merge_seconds the last
# epoch's merge wall time; nodes_reporting the node count of the last
# merged epoch. Keyed families are cleared and re-published per epoch
# so their label space is bounded by the guardrail knobs:
# top_flow_packets{key} <= fleet_topk_k series,
# tenant_top_flow_packets{tenant,key} <= fleet_tenant_series_max per
# tenant over <= fleet_max_tenants tenants (tenant_series{tenant}
# reports each tenant's exported count; series_capped/tenants_shed
# count guardrail enforcement), service_cardinality{service} <=
# fleet_service_top series; entropy_bits{dimension} and
# distinct_flows are fixed-cardinality cluster estimates.
FLEET_PREFIX = PREFIX + "fleet_"
FLEET_SNAPSHOTS_SHIPPED = FLEET_PREFIX + "snapshots_shipped_counter"
FLEET_SHIP_BYTES = FLEET_PREFIX + "ship_bytes_counter"
FLEET_SHIP_DEFERRED = FLEET_PREFIX + "ship_deferred_counter"
FLEET_SHIP_DROPPED = FLEET_PREFIX + "ship_dropped_counter"
FLEET_SHIP_ERRORS = FLEET_PREFIX + "ship_errors_counter"
FLEET_SHIP_SPOOLED = FLEET_PREFIX + "ship_spooled_counter"
FLEET_SHIP_SPOOL_EVICTED = FLEET_PREFIX + "ship_spool_evicted_counter"
FLEET_SHIP_SPOOL_REPLAYED = FLEET_PREFIX + "ship_spool_replayed_counter"
FLEET_SHIP_RECONNECTS = FLEET_PREFIX + "ship_reconnects_counter"
FLEET_SHIP_CIRCUIT_OPEN = FLEET_PREFIX + "ship_circuit_open"
FLEET_ROLLUPS_RESHIPPED = FLEET_PREFIX + "rollups_reshipped_counter"
FLEET_SNAPSHOTS_RECEIVED = FLEET_PREFIX + "snapshots_received_counter"
FLEET_SNAPSHOTS_DROPPED = FLEET_PREFIX + "snapshots_dropped_counter"
FLEET_WINDOWS_MERGED = FLEET_PREFIX + "windows_merged_counter"
FLEET_WINDOWS_STRAGGLERS = FLEET_PREFIX + "windows_stragglers_counter"
FLEET_MERGE_ERRORS = FLEET_PREFIX + "merge_errors_counter"
FLEET_MERGE_SECONDS = FLEET_PREFIX + "merge_seconds"
FLEET_NODES_REPORTING = FLEET_PREFIX + "nodes_reporting"
FLEET_TOP_FLOWS = FLEET_PREFIX + "top_flow_packets"
FLEET_TENANT_TOP_FLOWS = FLEET_PREFIX + "tenant_top_flow_packets"
FLEET_SERVICE_CARDINALITY = FLEET_PREFIX + "service_cardinality"
FLEET_ENTROPY_BITS = FLEET_PREFIX + "entropy_bits"
FLEET_DISTINCT_FLOWS = FLEET_PREFIX + "distinct_flows"
FLEET_TENANT_SERIES = FLEET_PREFIX + "tenant_series"
FLEET_SERIES_CAPPED = FLEET_PREFIX + "series_capped_counter"
FLEET_TENANTS_SHED = FLEET_PREFIX + "tenants_shed_counter"

# Invertible sketch (ops/invertible.py): heavy-flow keys recovered from
# sketch state at window close. Node side (tpu_invertible_*):
# keys_recovered is the last window's verified decoded-key count;
# decode_failed counts decode dispatch errors; recall/precision are
# scored against the host flow-dict ground truth and only published in
# heavy_keys_source="both" validation mode. Fleet side
# (fleet_invertible_*): keys_recovered is the last epoch's cluster-wide
# decoded-key count from MERGED sketch state (no node shipped raw
# keys); source_packets{key} attributes decoded heavy traffic to source
# IPs (DDoS attribution, cleared+republished per epoch, <= fleet_topk_k
# series); decode_failed counts merged-state decode errors.
INVERTIBLE_KEYS_RECOVERED = PREFIX + "tpu_invertible_keys_recovered"
INVERTIBLE_DECODE_FAILED = PREFIX + "tpu_invertible_decode_failed_counter"
INVERTIBLE_RECALL = PREFIX + "tpu_invertible_recall"
INVERTIBLE_PRECISION = PREFIX + "tpu_invertible_precision"
FLEET_INVERTIBLE_KEYS = FLEET_PREFIX + "invertible_keys_recovered"
FLEET_INVERTIBLE_SOURCES = FLEET_PREFIX + "invertible_source_packets"
FLEET_INVERTIBLE_DECODE_FAILED = (
    FLEET_PREFIX + "invertible_decode_failed_counter"
)

# Time-travel query ring (retina_tpu/timetravel): ring_appended/
# ring_dropped/ring_depth track each bounded snapshot ring (label
# ring=engine|fleet — fixed set, one per producer); queries counts
# range-query requests by terminal status (ok/stale/busy/empty/
# bad_request/error — fixed set), query_seconds is the HTTP handler
# latency histogram the p99 bound is read from, query_windows the slot
# count folded by the last query.
TIMETRAVEL_PREFIX = PREFIX + "tpu_timetravel_"
TIMETRAVEL_RING_APPENDED = TIMETRAVEL_PREFIX + "ring_appended_counter"
TIMETRAVEL_RING_DROPPED = TIMETRAVEL_PREFIX + "ring_dropped_counter"
TIMETRAVEL_RING_DEPTH = TIMETRAVEL_PREFIX + "ring_depth"
TIMETRAVEL_QUERIES = TIMETRAVEL_PREFIX + "queries_counter"
TIMETRAVEL_QUERY_SECONDS = TIMETRAVEL_PREFIX + "query_seconds"
TIMETRAVEL_QUERY_WINDOWS = TIMETRAVEL_PREFIX + "query_windows"

# Closed-loop capture (timetravel/autocapture.py): triggered counts
# detector firings accepted for capture; suppressed counts firings
# absorbed by reason (cooldown/busy/no_keys — fixed set); completed/
# failed count finished capture jobs; attributed_keys and
# artifact_bytes describe the last completed capture; last_epoch is
# the burst window-epoch it covered.
AUTOCAPTURE_PREFIX = PREFIX + "tpu_autocapture_"
AUTOCAPTURE_TRIGGERED = AUTOCAPTURE_PREFIX + "triggered_counter"
AUTOCAPTURE_SUPPRESSED = AUTOCAPTURE_PREFIX + "suppressed_counter"
AUTOCAPTURE_COMPLETED = AUTOCAPTURE_PREFIX + "completed_counter"
AUTOCAPTURE_FAILED = AUTOCAPTURE_PREFIX + "failed_counter"
AUTOCAPTURE_KEYS = AUTOCAPTURE_PREFIX + "attributed_keys"
AUTOCAPTURE_ARTIFACT_BYTES = AUTOCAPTURE_PREFIX + "artifact_bytes"
AUTOCAPTURE_LAST_EPOCH = AUTOCAPTURE_PREFIX + "last_epoch"

# Pluggable detector bank (retina_tpu/detect/): fired counts accepted
# firings per detector (the ones handed to the capture sink);
# suppressed counts firings absorbed by reason (cooldown/warmup/
# disabled — fixed set); score is the last raw detector statistic
# (ports-per-source estimate, qname-length entropy bits, SYN:ACK
# ratio), zscore the EWMA z it was judged by; last_epoch is the last
# window-epoch each detector fired on.
DETECTOR_PREFIX = PREFIX + "tpu_detector_"
DETECTOR_FIRED = DETECTOR_PREFIX + "fired_counter"
DETECTOR_SUPPRESSED = DETECTOR_PREFIX + "suppressed_counter"
DETECTOR_SCORE = DETECTOR_PREFIX + "score"
DETECTOR_ZSCORE = DETECTOR_PREFIX + "zscore"
DETECTOR_LAST_EPOCH = DETECTOR_PREFIX + "last_epoch"

# Fleet query plane (retina_tpu/fleetquery/): requests counts
# /fleet/query requests by terminal status (ok/partial/stale/busy/
# empty/bad_request/error — fixed set), seconds is the handler latency
# histogram the fleet p99 bound is read from; nodes_answered is the
# per-gather answered-node count and coverage_ratio the matching
# answered/total fraction (1.0 = full coverage); node_errors counts
# per-node scatter failures by reason (timeout/dead/seed_mismatch —
# fixed set); hedges counts hedged second attempts issued.
FLEET_QUERY_PREFIX = PREFIX + "fleet_query_"
FLEET_QUERY_REQUESTS = FLEET_QUERY_PREFIX + "requests_counter"
FLEET_QUERY_SECONDS = FLEET_QUERY_PREFIX + "seconds"
FLEET_QUERY_NODES_ANSWERED = FLEET_QUERY_PREFIX + "nodes_answered"
FLEET_QUERY_NODE_ERRORS = FLEET_QUERY_PREFIX + "node_errors_counter"
FLEET_QUERY_HEDGES = FLEET_QUERY_PREFIX + "hedges_counter"
FLEET_QUERY_COVERAGE = FLEET_QUERY_PREFIX + "coverage_ratio"

# Endurance soak harness (retina_tpu/soak/): phase progress and
# sentinel verdicts for a live `bench.py --soak` run, scrapeable
# mid-soak so an operator (or the alert rules) can watch a multi-hour
# run without waiting for the SOAK_*.json artifact. `sentinel` is the
# fixed verdict set the runner evaluates (rss_flat, fd_churn,
# stalled_windows, recorder, aot_cache, overload_recovery);
# last_recovery_seconds is the most recent fault-clear -> NOMINAL
# latency.
TPU_SOAK_PREFIX = PREFIX + "tpu_soak_"
TPU_SOAK_PHASES = TPU_SOAK_PREFIX + "phases_completed_counter"
TPU_SOAK_SENTINEL_FAILURES = TPU_SOAK_PREFIX + "sentinel_failures_counter"
TPU_SOAK_RECOVERY_SECONDS = TPU_SOAK_PREFIX + "last_recovery_seconds"

# Flight recorder (retina_tpu/obs/): per-window stage-latency
# breakdown. tpu_stage_seconds{stage} is observed once per span by the
# recorder; build_info is a constant-1 gauge whose labels
# identify the running build (version/jax/backend/devices/config
# signature — the scrape-side answer to "what exactly is running?");
# uptime_seconds is seconds since engine start.
TPU_STAGE_SECONDS = PREFIX + "tpu_stage_seconds"
RETINA_BUILD_INFO = PREFIX + "retina_build_info"
TPU_UPTIME_SECONDS = PREFIX + "tpu_uptime_seconds"

# Device proxy (utils/device_proxy.py): per proxied call, by kind, how
# long it waited in the FIFO (enqueue -> start) and how long it ran
# (start -> end; its _count is the number of calls, readiness polls
# included); the queue's depth. publish_lag_seconds is the program's own
# reading of freshness: at the end of a publish cycle, now minus the
# accept time of the oldest accepted event its snapshot does not hold.
TPU_PROXY_WAIT_SECONDS = PREFIX + "tpu_proxy_wait_seconds"
TPU_PROXY_RUN_SECONDS = PREFIX + "tpu_proxy_run_seconds"
TPU_PROXY_QUEUE_DEPTH = PREFIX + "tpu_proxy_queue_depth"
TPU_PUBLISH_LAG_SECONDS = PREFIX + "tpu_publish_lag_seconds"
# Gathers of the combined exposition (exporter.Exporter.gather) by what
# they did with the advanced registry: "rendered" once after every
# publish cycle and on every gather while no publisher has declared the
# registry's state complete, "reused" when the bytes kept from that one
# render were joined to the default registry's fresh ones. The `render`
# span carries the same word under the same key.
TPU_EXPOSITION_GATHERS = PREFIX + "tpu_exposition_gathers_counter"
L_ADVANCED = "advanced"
ADVANCED_RENDERED = "rendered"
ADVANCED_REUSED = "reused"
# The pod-level families are row tables (exporter.SeriesTable), and a
# publish cycle sets only what changed. publish_rows counts the rows
# the cycles looked at (the active entries of the snapshot, after the
# namespace filter), publish_rows_changed those they appended or
# rewrote; publish_cpu_seconds is what the cycle costs the process:
# time.thread_time() of the publisher's thread inside the cycle's
# snapshot (``snapshot``) and inside series_publish (``series``), and of
# a gathering thread inside a render of the pod-level bytes
# (``render``; a gather that reuses the kept bytes adds nothing).
TPU_PUBLISH_ROWS = PREFIX + "tpu_publish_rows_counter"
TPU_PUBLISH_ROWS_CHANGED = PREFIX + "tpu_publish_rows_changed_counter"
TPU_PUBLISH_CPU_SECONDS = PREFIX + "tpu_publish_cpu_seconds_counter"
L_PART = "part"
PART_SNAPSHOT = "snapshot"
PART_SERIES = "series"
PART_RENDER = "render"
PUBLISH_PARTS = (PART_SNAPSHOT, PART_SERIES, PART_RENDER)

# The host-CPU account of the agent's process (obs/cpuaccount.py), two
# readings of one quantity taken at the same instant every two seconds.
# process_cpu_seconds is getrusage(RUSAGE_SELF) user + system.
# thread_cpu_seconds{role} is the CPU seconds of the process's threads
# by the role of the thread: every tid of /proc/self/task is mapped to
# a Python thread by its native id and to a role by its name
# (THREAD_ROLE_PREFIXES); a tid that is no Python thread (XLA's,
# libtpu's and PJRT's pools) is ``runtime``; a Python thread whose name
# the table does not hold (an embedding program's: its main thread, a
# benchmark's load generator) is ``foreign``. Threads that live shorter
# than a sample period add their own time.thread_time() as they end
# (SELF_ACCOUNTING_PREFIXES; the sampler skips them). Summed over its
# label the first is the second, less what threads that died unseen
# had burnt. stage_cpu_seconds{stage} is the second level, inside a
# role: time.thread_time() between the two ends of the spans of
# CPU_STAGES, beside tpu_stage_seconds (whose seconds include waits:
# for the interpreter lock, for the device).
TPU_THREAD_CPU_SECONDS = PREFIX + "tpu_thread_cpu_seconds_counter"
TPU_PROCESS_CPU_SECONDS = PREFIX + "tpu_process_cpu_seconds_counter"
TPU_STAGE_CPU_SECONDS = PREFIX + "tpu_stage_cpu_seconds_counter"
L_ROLE = "role"

# Thread-role registry (the ONLY legal values of the `role` label).
# RT226 holds these constants, the THREAD_ROLES tuple, the roles the
# prefix tables name and the role table in docs/observability.md
# together, as it does the stages and the proxy kinds; a tier-1 test
# (tests/test_cpuaccount.py) holds every thread name the tree spawns
# to a role other than ``foreign``.
ROLE_FEED = "feed"
ROLE_DISPATCH = "dispatch"
ROLE_PROXY = "proxy"
ROLE_HARVEST = "harvest"
ROLE_PUBLISH = "publish"
ROLE_SERVE = "serve"
ROLE_CONTROL = "control"
ROLE_HUBBLE = "hubble"
ROLE_ACCOUNT = "account"
ROLE_RUNTIME = "runtime"
ROLE_FOREIGN = "foreign"

THREAD_ROLES = (
    ROLE_FEED,
    ROLE_DISPATCH,
    ROLE_PROXY,
    ROLE_HARVEST,
    ROLE_PUBLISH,
    ROLE_SERVE,
    ROLE_CONTROL,
    ROLE_HUBBLE,
    ROLE_ACCOUNT,
    ROLE_RUNTIME,
    ROLE_FOREIGN,
)

# Thread-name prefix -> role, for the names the program gives the
# threads it spawns; the longest prefix that matches wins. ``Dummy-``
# is what `threading` calls a thread it did not start (a runtime thread
# that once ran a Python callback).
THREAD_ROLE_PREFIXES = (
    ("engine", ROLE_FEED),  # the distributor loop
    ("feed-worker-", ROLE_FEED),
    ("plugin-", ROLE_FEED),
    ("engine-dispatch", ROLE_DISPATCH),
    ("device-proxy", ROLE_PROXY),
    ("device-completion", ROLE_PROXY),
    ("window-harvest", ROLE_HARVEST),
    ("checkpointer", ROLE_HARVEST),
    ("engine-bucket-warm", ROLE_HARVEST),
    ("tt-ring-", ROLE_HARVEST),
    ("fleet-ship-", ROLE_HARVEST),
    ("fleet-agg", ROLE_HARVEST),
    ("metricsmodule", ROLE_PUBLISH),
    ("http-server", ROLE_SERVE),
    ("http-handler", ROLE_SERVE),
    ("metrics-render", ROLE_SERVE),
    ("fleetquery", ROLE_SERVE),
    ("watchdog", ROLE_CONTROL),
    ("engine-recover", ROLE_CONTROL),
    ("watchermanager", ROLE_CONTROL),
    ("identity-rebuild", ROLE_CONTROL),
    ("telemetry-heartbeat", ROLE_CONTROL),
    ("pubsub", ROLE_CONTROL),
    ("autocapture", ROLE_CONTROL),
    ("na-control", ROLE_CONTROL),
    ("leaderelection", ROLE_CONTROL),
    ("filebridge", ROLE_CONTROL),
    ("kubewatch-", ROLE_CONTROL),
    ("kubebridge-", ROLE_CONTROL),
    ("ciliumwatch", ROLE_CONTROL),
    ("adopt-", ROLE_CONTROL),
    ("capture-", ROLE_CONTROL),
    ("monitoragent", ROLE_HUBBLE),
    ("hubble-grpc", ROLE_HUBBLE),
    ("relay-", ROLE_HUBBLE),
    ("cpu-account", ROLE_ACCOUNT),
    ("Dummy-", ROLE_RUNTIME),
)
# Threads that add their own CPU seconds to their role as they end,
# because they live shorter than a sample period: the sampler skips
# them, so nothing is counted twice.
SELF_ACCOUNTING_PREFIXES = ("http-handler",)


def thread_role(name: str) -> str:
    """The role of a Python thread of this name (``foreign`` where the
    table does not hold it)."""
    best, role = -1, ROLE_FOREIGN
    for prefix, r in THREAD_ROLE_PREFIXES:
        if len(prefix) > best and name.startswith(prefix):
            best, role = len(prefix), r
    return role


# Pipeline stage-name registry (the ONLY legal values of the
# tpu_stage_seconds `stage` label and of every recorder span). The
# RT226 analyzer machine-checks three-way agreement between these
# constants, the span names actually emitted through the recorder, and
# the stage table in docs/observability.md — add the constant, the
# emission site and the doc row together.
STAGE_DISTRIBUTOR_DEAL = "distributor_deal"
STAGE_COMBINE = "combine"
STAGE_PARTITION = "partition"
STAGE_FEED_FILL = "feed_fill"
STAGE_STAGING_HANDOFF = "staging_handoff"
STAGE_WIRE_BUILD = "wire_build"
STAGE_TRANSFER_ENQUEUE = "transfer_enqueue"
STAGE_DEVICE_STEP = "device_step"
STAGE_PROXY_RUN = "proxy_run"
STAGE_WINDOW_CLOSE = "window_close"
STAGE_HARVEST = "harvest"
STAGE_PUBLISH = "publish"
STAGE_POD_PUBLISH = "pod_publish"
STAGE_SNAPSHOT = "snapshot"
STAGE_SNAPSHOT_DISPATCH = "snapshot_dispatch"
STAGE_SNAPSHOT_FETCH = "snapshot_fetch"
STAGE_SNAPSHOT_FINISH = "snapshot_finish"
STAGE_SERIES_PUBLISH = "series_publish"
STAGE_RENDER = "render"
STAGE_SHIP_READBACK = "ship_readback"
STAGE_SHIP_ENCODE = "ship_encode"
STAGE_SHIP_SEND = "ship_send"
STAGE_AGG_MERGE = "aggregator_merge"
STAGE_HUBBLE_CONSUME = "hubble_consume"
STAGE_STALL = "stall"

# Ordered registry (pipeline order); drives the fixed label space of
# tpu_stage_seconds and the bench critical-path report.
STAGES = (
    STAGE_DISTRIBUTOR_DEAL,
    STAGE_COMBINE,
    STAGE_PARTITION,
    STAGE_FEED_FILL,
    STAGE_STAGING_HANDOFF,
    STAGE_WIRE_BUILD,
    STAGE_TRANSFER_ENQUEUE,
    STAGE_DEVICE_STEP,
    STAGE_PROXY_RUN,
    STAGE_WINDOW_CLOSE,
    STAGE_HARVEST,
    STAGE_PUBLISH,
    STAGE_POD_PUBLISH,
    STAGE_SNAPSHOT,
    STAGE_SNAPSHOT_DISPATCH,
    STAGE_SNAPSHOT_FETCH,
    STAGE_SNAPSHOT_FINISH,
    STAGE_SERIES_PUBLISH,
    STAGE_RENDER,
    STAGE_SHIP_READBACK,
    STAGE_SHIP_ENCODE,
    STAGE_SHIP_SEND,
    STAGE_AGG_MERGE,
    STAGE_HUBBLE_CONSUME,
    STAGE_STALL,
)

# Stages whose spans also read time.thread_time() at their two ends
# (span argument ``cpu_s``, tpu_stage_cpu_seconds_counter{stage}):
# those a thread runs from start to finish on itself. Not device_step
# (the completion thread closes it), nor the spans that only wrap
# others (feed_fill, pod_publish, snapshot) or a wait
# (staging_handoff), nor stall (written after the fact by the watchdog,
# about another thread or the whole process).
CPU_STAGES = frozenset((
    STAGE_DISTRIBUTOR_DEAL,
    STAGE_COMBINE,
    STAGE_PARTITION,
    STAGE_WIRE_BUILD,
    STAGE_TRANSFER_ENQUEUE,
    STAGE_PROXY_RUN,
    STAGE_WINDOW_CLOSE,
    STAGE_HARVEST,
    STAGE_SNAPSHOT_DISPATCH,
    STAGE_SNAPSHOT_FETCH,
    STAGE_SNAPSHOT_FINISH,
    STAGE_SERIES_PUBLISH,
    STAGE_RENDER,
    STAGE_HUBBLE_CONSUME,
))

# Device-proxy call-kind registry (the ONLY legal values of the `kind`
# label of tpu_proxy_* and of the `kind` argument of every proxied
# call). RT226 holds these constants, the kinds passed at the call
# sites and the kind table in docs/observability.md together, as it
# does the stages: a kind is added with its first call site.
KIND_STEP = "step"
KIND_CLOSE = "close"
KIND_SNAPSHOT = "snapshot"
KIND_FETCH = "fetch"
KIND_POLL = "poll"
KIND_TABLE = "table"
KIND_OTHER = "other"

PROXY_KINDS = (
    KIND_STEP,
    KIND_CLOSE,
    KIND_SNAPSHOT,
    KIND_FETCH,
    KIND_POLL,
    KIND_TABLE,
    KIND_OTHER,
)

# Label keys (reference pkg/utils/metric_names.go label constants).
L_DIRECTION = "direction"
L_REASON = "reason"
L_FLAG = "flag"
L_POD = "podname"
L_NAMESPACE = "namespace"
L_WORKLOAD = "workload_kind"
L_IP = "ip"
L_PORT = "port"
L_PROTO = "protocol"
L_QTYPE = "query_type"
L_RCODE = "return_code"
L_DIMENSION = "dimension"
L_STAGE = "stage"
L_TABLE = "table"
L_PLUGIN = "plugin"
L_STATE = "state"
L_THREAD = "thread"
L_SITE = "site"
L_INTERFACE = "interface_name"
L_STAT = "statistic_name"
L_BUCKET = "le_ms"
L_TENANT = "tenant"
L_KEY = "key"
L_NODE = "node"
L_SERVICE = "service"
L_RING = "ring"
L_STATUS = "status"
L_SENTINEL = "sentinel"
L_DETECTOR = "detector"
