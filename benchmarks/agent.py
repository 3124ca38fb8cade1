"""Booting the system under test: copies of what ``chip_smoke.py`` proved
on the chip in PR 21 (``register_source``, ``build_config``, ``Agent``,
``CompileLog``), changed only to take the configuration from a file of
the benchmark instead of from the smoke's constants.

From the program this takes the agent itself (``Daemon`` through its
normal entry), its plugin registry, its configuration loader and its
cache helpers. Nothing here measures.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
import urllib.error
import urllib.request

POD_NET = 0x0A000000  # 10.0.0.0: endpoint i owns POD_NET + i


class BenchFailure(RuntimeError):
    """A phase could not finish (deadline, dead thread, bad answer)."""


def wait_for(what: str, pred, deadline_s: float, poll_s: float = 0.05,
             alive=None) -> float:
    """Poll ``pred`` until true; BenchFailure past the deadline or when
    ``alive`` says the thing waited on is dead. Returns seconds waited."""
    t0 = time.monotonic()
    while True:
        if pred():
            return time.monotonic() - t0
        if alive is not None and not alive():
            raise BenchFailure(f"{what}: agent thread died")
        if time.monotonic() - t0 > deadline_s:
            raise BenchFailure(f"{what}: not done after {deadline_s:.0f}s")
        time.sleep(poll_s)


def ip_str(u: int) -> str:
    return ".".join(str((u >> s) & 0xFF) for s in (24, 16, 8, 0))


def http_get(port: int, path: str, timeout: float = 30.0) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def device_identity() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileLog:
    """Every XLA compile request of the process, from jax.monitoring:
    program name, seconds, when. (AOT disk-cache hits never reach XLA.)"""

    def __init__(self) -> None:
        self.records: list[dict] = []

    def install(self) -> None:
        from jax import monitoring

        def on_duration(event, secs, **kw):
            if event != "/jax/core/compile/backend_compile_duration":
                return
            self.records.append({
                "program": kw.get("fun_name", "?"),
                "seconds": round(float(secs), 3),
                "at": time.monotonic(),
            })

        monitoring.register_event_duration_secs_listener(on_duration)

    def between(self, t0: float, t1: float) -> list[dict]:
        """Compiles that ended in [t0, t1)."""
        return [r for r in self.records if t0 <= r["at"] < t1]


def register_source() -> None:
    """A plugin like any other (registry, reconcile, supervised start,
    sink wiring) standing where packetparser's live capture stands on a
    machine with a NIC: it emits exactly the blocks it is handed."""
    from retina_tpu.plugins import registry
    from retina_tpu.plugins.api import Plugin

    if "seededsource" in registry.names():
        return

    @registry.register
    class SeededSource(Plugin):
        name = "seededsource"

        def __init__(self, cfg):
            super().__init__(cfg)
            self.inbox: queue.Queue = queue.Queue()
            self.offered = 0
            self.accepted = 0

        def start(self, stop: threading.Event) -> None:
            while not stop.is_set():
                try:
                    block = self.inbox.get(timeout=0.05)
                except queue.Empty:
                    continue
                self.offered += len(block)
                self.accepted += self.emit(block)


def build_config(config: dict, work: str, aot_dir: str, xla_dir: str,
                 rehearse: bool):
    """The configuration's file through ``load_config``: its ``agent``
    group is written out as the config.yaml the agent would be started
    with; what a machine without a NIC or a cluster forces, and the
    sizing and the file's other groups of changes, go on top as
    overrides."""
    import yaml

    from retina_tpu.config import load_config

    os.makedirs(work, exist_ok=True)
    path = os.path.join(work, "config.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(config["agent"], f)
    overrides = {
        "enabled_plugins": [
            "seededsource" if p == "packetparser" else p
            for p in config["agent"]["enabled_plugins"]
        ],
        **config["machine"],
        # Everything the agent writes stays under the checkout.
        "snapshot_dir": os.path.join(work, "snapshots"),
        "compilation_cache_dir": xla_dir,
        "aot_cache_dir": aot_dir,
        "profile_artifact_dir": os.path.join(work, "profile"),
        "autocapture_output_dir": os.path.join(work, "autocapture"),
        "soak_artifact_dir": os.path.join(work, "soak"),
        **config["sizing"],
        **(config.get("rehearse", {}) if rehearse else {}),
    }
    return load_config(path, overrides=overrides)


class Agent:
    """One boot of the daemon on a background thread."""

    def __init__(self, cfg, n_endpoints: int, ready_deadline_s: float,
                 warm_deadline_s: float):
        from retina_tpu.common import RetinaEndpoint
        from retina_tpu.daemon import Daemon

        self.n_endpoints = n_endpoints
        self.ready_deadline_s = ready_deadline_s
        self.warm_deadline_s = warm_deadline_s
        self.daemon = Daemon(cfg)
        # One filter-table push for all endpoints: pushed per pod event
        # the table is rebuilt from scratch each time, which for 2,047
        # endpoints is most of a minute of host time.
        fm = self.daemon.cm.filtermanager
        with fm.deferred_push():
            for i in range(1, n_endpoints):
                self.daemon.cm.cache.update_endpoint(RetinaEndpoint(
                    name=f"pod-{i}", namespace="default",
                    ips=(ip_str(POD_NET + i),),
                ))
            wait_for("endpoints reach the filter manager",
                     lambda: fm.ip_count() == n_endpoints - 1, 60.0)
        self.t_boot = time.monotonic()
        self.stop = threading.Event()
        self.error: BaseException | None = None
        self.thread = threading.Thread(
            target=self._run, name="agent", daemon=True
        )
        self.thread.start()
        self.port = 0

    def _run(self) -> None:
        try:
            self.daemon.start(self.stop)
        except BaseException as e:  # noqa: BLE001 — reported by alive()
            self.error = e
            raise

    @property
    def engine(self):
        return self.daemon.cm.engine

    @property
    def source(self):
        return self.daemon.cm.pluginmanager.plugins["seededsource"]

    def alive(self) -> bool:
        return self.thread.is_alive()

    def wait_ready(self) -> float:
        cm = self.daemon.cm

        def up() -> bool:
            if cm.server is None or cm.server._httpd is None:
                return False
            self.port = cm.server.port
            return http_get(self.port, "/readyz", 5.0)[0] == 200

        wait_for("agent ready", up, self.ready_deadline_s, 0.2, self.alive)
        return time.monotonic() - self.t_boot

    def wait_tables(self) -> None:
        """Identity and filter tables: every endpoint registered before
        the first event (their uploads ride the proxy FIFO ahead of any
        later dispatch)."""
        n = self.n_endpoints - 1

        def tables() -> bool:
            v = json.loads(http_get(self.port, "/debug/vars")[1])
            return v.get("pods") == n and v.get("filter_ips") == n

        wait_for("identity and filter tables", tables, 120.0, 0.5,
                 self.alive)

    def wait_warm(self) -> dict:
        """Wait until the whole background warm is done: window close,
        descriptor table, both snapshot programs and every ingest
        bucket the feed can reach. A compile that went on behind the
        window would sit in the agent's CPU account."""
        eng = self.engine
        t0 = time.monotonic()

        def resident() -> bool:
            if eng.bucket_warm_failed.is_set():
                raise BenchFailure("background warm failed")
            return (
                eng.bucket_warm_done.is_set()
                and eng._close_warmed.is_set()
                and eng._desc_table is not None
                and eng.sharded._snapshot is not None
                and eng.sharded._snapshot_flat is not None
            )

        wait_for("background warm done", resident, self.warm_deadline_s,
                 0.1, self.alive)
        return {"warm_s": round(time.monotonic() - t0, 2),
                "ingest_keys": len(eng._pad_cache)}

    def program_counts(self) -> dict:
        """Executables held per warmed program: growth inside the window
        means a program compiled a second time."""
        eng = self.engine
        sh = eng.sharded
        out = {"ingest_keys": len(eng._pad_cache)}
        for tag, prog in (("step", sh._step), ("end_window", sh._end_window),
                          ("snapshot", sh._snapshot),
                          ("snapshot_flat", sh._snapshot_flat
                           and sh._snapshot_flat[0])):
            out[tag] = prog._cache_size() if prog is not None else 0
        return out

    def settled(self) -> bool:
        """Every accepted event belongs to a closed, harvested window."""
        eng = self.engine
        return (eng._closed_events_in == eng._events_in
                and not eng._harvest_q.unfinished_tasks)

    def shutdown(self) -> float:
        t0 = time.monotonic()
        self.stop.set()
        self.thread.join(120.0)
        if self.thread.is_alive():
            raise BenchFailure("agent did not stop within 120s")
        return time.monotonic() - t0
