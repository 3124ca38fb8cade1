"""Standard agent daemon: the boot sequence.

Reference analog: cmd/standard/daemon.go:80-323 — Daemon.Start loads
config, sets up zap + telemetry + metrics, builds the controller-runtime
manager, wires pubsub/cache/enricher/filtermanager/metrics-module when
pod-level is on (:239-295), then runs the controller manager until SIGTERM
cancels the context and the Stop cascade runs.

Here: config → logging → ControllerManager (server + engine + plugins +
watchers) → MetricsModule (pod-level) → signal-driven stop event. The
driver-facing entry is :func:`run_agent`; ``python -m retina_tpu`` calls
it via the CLI.
"""

from __future__ import annotations

import signal
import threading
from typing import Any, Optional

from retina_tpu.config import Config, enable_compilation_cache, load_config
from retina_tpu.crd.types import MetricsConfiguration
from retina_tpu.log import logger, setup_logger
from retina_tpu.managers.controllermanager import ControllerManager
from retina_tpu.module.metrics_module import MetricsModule


class Daemon:
    def __init__(self, cfg: Config, apiserver_host: str = ""):
        self.cfg = cfg
        self.log = logger("daemon")
        if cfg.device_platform:
            # Must land before the first device use in this process;
            # jax.config is a no-op once a backend is initialized.
            import jax

            jax.config.update("jax_platforms", cfg.device_platform)
            self.log.info("device platform forced: %s",
                          cfg.device_platform)
        cache_dir = enable_compilation_cache(cfg.compilation_cache_dir)
        if cache_dir:
            self.log.info("XLA compilation cache at %s", cache_dir)
        if cfg.fault_spec:
            # Deterministic fault injection (chaos testing): armed only
            # when explicitly configured (RETINA_FAULT_SPEC / config).
            from retina_tpu.runtime import faults

            faults.configure(cfg.fault_spec)
            self.log.warning("fault injection armed: %s", cfg.fault_spec)
        self.cm = ControllerManager(cfg, apiserver_host=apiserver_host)
        # Identity from a real cluster (pkg/k8s watcher analog): core/v1
        # pods/services/nodes land in the same cache the CRD-store path
        # feeds, so enrichment works without our operator running.
        # Selected by an explicit kubeconfig OR automatically when running
        # in-cluster with a service account (the daemonset deployment).
        self.kubewatch = None
        self.ciliumwatch = None
        from retina_tpu.operator.kubeclient import in_cluster_available

        if cfg.kubeconfig or in_cluster_available():
            from retina_tpu.operator.kubewatch import CoreWatcher

            use_cilium = cfg.identity_source == "cilium"
            self.kubewatch = CoreWatcher(
                self.cm.cache, cfg.kubeconfig,
                namespace=cfg.kube_namespace,
                include_pods=not use_cilium,
                include_namespaces=cfg.enable_annotations,
            )
            if use_cilium:
                # Identity from the foreign CNI's objects (cilium-crds
                # interop): CEPs instead of core/v1 pods.
                if cfg.enable_annotations:
                    # CEPs carry identity labels, not pod annotations:
                    # per-POD retina.sh=observe opt-in cannot work in
                    # this mode; namespace-level opt-in still does.
                    self.log.warning(
                        "identity_source=cilium: per-pod observe "
                        "annotations are invisible (CiliumEndpoints "
                        "carry no pod annotations); use the namespace "
                        "annotation instead"
                    )
                from retina_tpu.operator.cilium import CiliumWatcher

                self.ciliumwatch = CiliumWatcher(
                    self.cm.cache, cfg.kubeconfig,
                    namespace=cfg.kube_namespace,
                )
        self.metrics_module: Optional[MetricsModule] = None
        self._mm_thread: Optional[threading.Thread] = None
        self.hubble = None
        self.monitoragent = None
        # Fleet rollup tier (fleet/): the aggregator role is explicit
        # config, not inferred — one operator-side process merges the
        # cluster's shipped sketch snapshots. Built before the relay so
        # the relay can front its ingest (retina.Fleet/Ship).
        self.fleet_aggregator = None
        if cfg.fleet_aggregator:
            from retina_tpu.fleet import FleetAggregator

            self.fleet_aggregator = FleetAggregator(
                cfg, supervisor=self.cm.supervisor
            )
        # Time-travel query tier (timetravel/): one QueryService owns
        # the jitted fold cache and every ring in this process — the
        # engine's per-window ring, plus a merged-epoch ring when the
        # aggregator role is on. The closed loop (autocapture) rides
        # the same service.
        self.query_service = None
        self.autocapture = None
        if cfg.timetravel_enabled:
            from retina_tpu.timetravel.query import QueryService

            self.query_service = QueryService(
                cfg, overload=self.cm.engine._overload
            )
            if self.cm.engine.timetravel_ring is not None:
                self.query_service.add_ring(
                    self.cm.engine.timetravel_ring
                )
            if (
                self.fleet_aggregator is not None
                and self.fleet_aggregator.epoch_ring is not None
            ):
                # The aggregator owns its merged-epoch ring; the query
                # tier just folds over it (RingProtocol).
                self.query_service.add_ring(
                    self.fleet_aggregator.epoch_ring
                )
            if cfg.autocapture_enabled:
                from retina_tpu.timetravel.autocapture import AutoCapture

                self.autocapture = AutoCapture(
                    cfg, self.query_service, ring_name="engine",
                    engine=self.cm.engine,
                    supervisor=self.cm.supervisor,
                )
                self.cm.engine.anomaly_hook = self.autocapture.notify
        # Detector bank (detect/): every registered detector judged at
        # window close over the engine's record tap; accepted firings
        # land in the same closed loop as the entropy hook
        # (AutoCapture.notify) when autocapture is on.
        self.detector_bank = None
        if cfg.detectors_enabled:
            from retina_tpu.detect import build_default_bank
            from retina_tpu.fleet.shipper import window_epoch

            sink = (
                self.autocapture.notify
                if self.autocapture is not None else None
            )
            self.detector_bank = build_default_bank(cfg, sink=sink)

            def _record_tap(
                records, now_s,
                _bank=self.detector_bank, _win=cfg.window_seconds,
            ):
                _bank.observe(
                    window_epoch(_win), records, now_s=float(now_s)
                )

            self.cm.engine.record_hook = _record_tap
        # Fleet query plane (fleetquery/): cluster-wide range answers
        # over whatever fleet sources this process has — the merged
        # epoch ring when the aggregator role is on, plus any node
        # clients the operator registers.
        self.fleetquery = None
        if cfg.fleetquery_enabled:
            from retina_tpu.fleetquery import FleetQueryService

            self.fleetquery = FleetQueryService(
                cfg, overload=self.cm.engine._overload
            )
            if (
                self.fleet_aggregator is not None
                and self.fleet_aggregator.epoch_ring is not None
            ):
                self.fleetquery.add_ring(
                    self.fleet_aggregator.epoch_ring
                )
        if cfg.enable_hubble:
            # Hubble CP rides alongside (cmd/hubble cell graph analog):
            # plugins mirror events into the external channel; the monitor
            # agent fans them out to the flow observer; the gRPC relay
            # serves GetFlows (SURVEY.md §3.5).
            from retina_tpu.hubble import (
                FlowObserver,
                HubbleServer,
                MonitorAgent,
            )

            self.monitoragent = MonitorAgent()
            dns_plugin = self.cm.pluginmanager.plugins.get("dns")
            self.observer = FlowObserver(
                capacity=cfg.hubble_ring_capacity,
                cache=self.cm.cache,
                dns_resolver=(dns_plugin.resolve if dns_plugin else None),
            )
            self.monitoragent.register_consumer(self.observer.consume)
            self.cm.pluginmanager.setup_channel(self.monitoragent.channel)
            # Peer set = static config peers + the node store (nodes the
            # operator publishes land in the cache; the peer service then
            # reflects live cluster membership, not boot-time config).
            def _peers() -> list[dict[str, str]]:
                # Peers serve on the same configured hubble port; with an
                # ephemeral bind (tests) fall back to our bound port.
                port = cfg.hubble_addr.rsplit(":", 1)[1]
                if port == "0" and self.hubble is not None:
                    port = str(self.hubble.port)
                out = [dict(p) for p in cfg.hubble_peers]
                seen = {p.get("address") for p in out}
                for n in self.cm.cache.list_nodes():
                    if n.ip and n.name != cfg.node_name:
                        addr = f"{n.ip}:{port}"
                        if addr not in seen:
                            out.append({"name": n.name, "address": addr})
                return out

            self.hubble = HubbleServer(
                self.observer,
                addr=cfg.hubble_addr,
                peers=_peers,
                node_name=cfg.node_name,
                tls_cert=cfg.hubble_tls_cert,
                tls_key=cfg.hubble_tls_key,
                tls_client_ca=cfg.hubble_tls_client_ca,
                unix_socket=cfg.hubble_sock_path,
                fleet_ingest=(
                    self.fleet_aggregator.ingest
                    if self.fleet_aggregator is not None else None
                ),
            )
            self.hubble_metrics_server = None
            if cfg.hubble_metrics_addr:
                # Dedicated hubble metrics mux (:9965 analog): serves ONLY
                # the hubble registry so scraping both muxes never
                # double-ingests the node/pod families.
                from retina_tpu.exporter import get_exporter
                from retina_tpu.server import Server

                self.hubble_metrics_server = Server(
                    cfg.hubble_metrics_addr,
                    gather=get_exporter().gather_hubble_text,
                    metrics_cache_ttl_s=cfg.metrics_cache_ttl_s,
                )
        if cfg.enable_pod_level:
            dns_plugin = self.cm.pluginmanager.plugins.get("dns")
            self.metrics_module = MetricsModule(
                cfg,
                engine=self.cm.engine,
                cache=self.cm.cache,
                filtermanager=self.cm.filtermanager,
                pubsub=self.cm.pubsub,
                dns_resolver=(dns_plugin.resolve if dns_plugin else None),
                supervisor=self.cm.supervisor,
            )
        # Per-flow trace sampling off the record stream (module/traces):
        # idle until a TracesConfiguration reconcile names targets,
        # queried via /debug/vars -> CLI `retina-tpu trace`.
        from retina_tpu.module.traces import TracesModule

        self.traces_module = TracesModule()
        self.traces_module.attach(self.cm.engine)
        # Agent-side CRD reconcile (the reference daemon watches its
        # module CRDs itself, pkg/controllers/daemon): a list+watch
        # bridge feeds a local store whose watches drive the metrics +
        # traces modules — without this, only the OPERATOR process would
        # see the CRs and the agent's modules would never reconcile.
        self.crd_bridge = None
        if cfg.kubeconfig or in_cluster_available():
            try:
                from retina_tpu.operator.bridge import KubeBridge
                from retina_tpu.operator.store import CRDStore

                crd_store = CRDStore()
                crd_store.watch(
                    "MetricsConfiguration", self._on_metrics_crd
                )
                crd_store.watch(
                    "TracesConfiguration", self._on_traces_crd
                )
                self.crd_bridge = KubeBridge(
                    crd_store, cfg.kubeconfig,
                    namespace=cfg.kube_namespace,
                    # Only the module CRs: Captures are the operator's
                    # business, and N agents each LISTing every Capture
                    # is pure apiserver load.
                    kinds=["MetricsConfiguration",
                           "TracesConfiguration"],
                )
            except Exception as e:
                self.log.warning("agent CRD bridge unavailable: %s", e)

    # -- module CRD reconciles (agent side) ---------------------------
    def _on_metrics_crd(self, event: str, conf: Any) -> None:
        if self.metrics_module is None:
            return
        try:
            if event == "deleted":
                self.metrics_module.reconcile(
                    MetricsConfiguration.default()
                )
            elif event == "applied":
                self.metrics_module.reconcile(conf)
        except Exception:
            self.log.exception("metrics CRD reconcile failed")

    def _on_traces_crd(self, event: str, conf: Any) -> None:
        from retina_tpu.crd.types import TracesConfiguration

        try:
            if event == "deleted":
                self.traces_module.reconcile(TracesConfiguration())
            elif event == "applied":
                self.traces_module.reconcile(conf)
        except Exception:
            self.log.exception("traces CRD reconcile failed")

    def start(self, stop: threading.Event) -> None:
        self.log.info(
            "starting retina-tpu agent: plugins=%s source=%s pod_level=%s",
            self.cfg.enabled_plugins, self.cfg.event_source,
            self.cfg.enable_pod_level,
        )
        self.cm.init()
        if self.cm.server is not None:
            from retina_tpu.module.traces import MAX_EVENTS_PER_TARGET

            self.cm.server.expose_var(
                "traces",
                lambda: self.traces_module.traces(
                    limit=MAX_EVENTS_PER_TARGET
                ),
            )
            self.cm.server.expose_var(
                "traces_stats", self.traces_module.stats
            )
        if self.query_service is not None and self.cm.server is not None:
            # /timetravel/query + the ring debug var ride the existing
            # agent mux; registration is a dict insert, safe while the
            # server serves.
            self.query_service.attach(self.cm.server)
        if self.fleetquery is not None and self.cm.server is not None:
            # /fleet/query + the fleetquery debug var, same shape.
            self.fleetquery.attach(self.cm.server)
        if self.cm.server is not None:
            # Flight-recorder debug API (obs/debug.py): GET /debug/trace
            # + POST /debug/profile, same attach shape as the query
            # service; SHEDDING-aware via the engine's controller.
            from retina_tpu.obs.debug import DebugObservability

            DebugObservability(
                self.cfg, overload=self.cm.engine._overload
            ).attach(self.cm.server)
        if self.autocapture is not None:
            self.autocapture.start()
        if self.monitoragent is not None:
            self.monitoragent.start(stop)
        if self.fleet_aggregator is not None:
            self.fleet_aggregator.start()
        if self.hubble is not None:
            self.hubble.start()
            if getattr(self, "hubble_metrics_server", None) is not None:
                self.hubble_metrics_server.start()
        if self.metrics_module is not None:
            self.metrics_module.reconcile(MetricsConfiguration.default())
            self._mm_thread = threading.Thread(
                target=self.metrics_module.start, args=(stop,),
                name="metricsmodule", daemon=True,
            )
            self._mm_thread.start()
        if self.cfg.snapshot_dir:
            import os

            path = os.path.join(self.cfg.snapshot_dir, "sketch_state.npz")
            if os.path.exists(path):
                # Crash-only contract: load_state never raises — an
                # unreadable checkpoint (stale fingerprint, corrupt or
                # truncated npz) is quarantined to .bad inside
                # checkpoint.load_state and we cold-start.
                if self.cm.engine.load_snapshot_state(path):
                    self.log.info("resumed sketch state from %s", path)
                else:
                    self.log.warning(
                        "checkpoint at %s unusable; cold-starting", path
                    )
        if self.kubewatch is not None:
            self.kubewatch.start()
        if self.ciliumwatch is not None:
            self.ciliumwatch.start()
        if self.crd_bridge is not None:
            self.crd_bridge.start()
        try:
            self.cm.start(stop)  # blocks until stop fires; runs shutdown
        finally:
            if self.crd_bridge is not None:
                self.crd_bridge.stop()
            if self.ciliumwatch is not None:
                self.ciliumwatch.stop()
            if self.kubewatch is not None:
                self.kubewatch.stop()
            if self.hubble is not None:
                self.hubble.stop()
                if getattr(self, "hubble_metrics_server", None) is not None:
                    self.hubble_metrics_server.stop()
            if self.fleet_aggregator is not None:
                self.fleet_aggregator.stop()
                ring = self.fleet_aggregator.timetravel_ring
                if ring is not None:
                    ring.stop()
            if self.autocapture is not None:
                self.autocapture.stop()
            if self.detector_bank is not None:
                # Judge the in-progress window before the loop dies.
                self.detector_bank.flush()
            if self.fleetquery is not None:
                self.fleetquery.close()


def run_agent(
    config_path: str | None = None,
    overrides: dict[str, Any] | None = None,
    apiserver_host: str = "",
    install_signals: bool = True,
) -> Daemon:
    """Build + run the agent (blocking). SIGTERM/SIGINT → clean stop."""
    cfg = load_config(config_path, overrides=overrides)
    setup_logger(cfg.log_level, cfg.log_file)
    if cfg.distributed_coordinator:
        # Multi-host mesh: must run before any backend use so every
        # process sees the global device set (jax.devices() spans hosts;
        # shard_map collectives then ride ICI within a slice and DCN
        # across hosts — no hand-written NCCL/MPI analog).
        import jax

        jax.distributed.initialize(
            coordinator_address=cfg.distributed_coordinator,
            num_processes=cfg.distributed_num_processes,
            process_id=cfg.distributed_process_id,
        )
    stop = threading.Event()
    if install_signals:
        for sig in (signal.SIGTERM, signal.SIGINT):
            signal.signal(sig, lambda *_: stop.set())
    d = Daemon(cfg, apiserver_host=apiserver_host)
    d.start(stop)
    return d
