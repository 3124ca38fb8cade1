#!/usr/bin/env python3
"""chip_smoke.py: run the node agent's main path once on the chip.

The quickest proof that the system still starts on a TPU. One process
boots the agent through ``Daemon(cfg).start`` with the repo's documented
deployment (``deploy/manifests/configmap.yaml``, loaded by
``load_config`` as ``retina-tpu agent --config`` does), feeds a known,
finite, seeded set of events through the plugin/sink seam (BASELINE
config 2: 1,000,000 distinct flows over 2,048 endpoints), scrapes
``/metrics`` over HTTP and compares what the agent says with a plain
numpy reference kept in this file. Then it stops the agent, boots it a
second time against the caches and the checkpoint the first boot wrote,
and compares again.

    python chip_smoke.py             one chip; what the driver runs
    python chip_smoke.py --chips 4   only the four-device mesh and the
                                     one-device run it is compared with
    python chip_smoke.py --rehearse  tiny sizes, for a CPU rehearsal;
                                     ends with "ok": false off a TPU

Standard output is one JSON object per line; the last line is
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times on the earlier lines are set-up facts on the host's clock, not
results. Exit code 0 only when every check passed on a TPU.

One process per chip: both boots are Daemon instances of this process,
which owns the chip from its first JAX call; it starts no child that
needs a device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import queue
import shutil
import sys
import threading
import time
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
# retina_tpu is importable from the repo root only (no install): do not
# depend on the caller's PYTHONPATH or working directory.
sys.path.insert(0, ROOT)

POD_NET = 0x0A000000  # 10.0.0.0: endpoint i owns POD_NET + i
PREFIX = "networkobservability_"
# The whole script must end inside the driver's 1200 s.
WATCHDOG_S = 1150.0


@dataclasses.dataclass(frozen=True)
class Size:
    """Everything --rehearse changes, and nothing else does."""

    n_flows: int
    n_endpoints: int
    block_rows: int  # rows per emitted block
    # Blocks per feed quantum. Two, so that at most two feed workers
    # flush at once: the overload controller reads three dispatches in
    # flight (feed_pipeline_depth) as full pressure and starts 1-in-k
    # sampling, after which counters are estimates, not exact.
    quantum_blocks: int
    quanta_first: int  # quanta fed to the first boot
    quanta_second: int  # quanta fed after the restart
    overrides: dict  # config fields on top of the configmap
    ready_deadline_s: float
    warm_deadline_s: float


REAL = Size(
    n_flows=1_000_000, n_endpoints=2048, block_rows=65536,
    quantum_blocks=2, quanta_first=64, quanta_second=8,
    # The flow dictionary is sized to the working set, as bench.py
    # explains for the same traffic: undersized, the Zipf tail churns
    # through the table and re-registers descriptors every flush.
    overrides={"flow_dict_slots": 1 << 21},
    ready_deadline_s=700.0, warm_deadline_s=300.0,
)
REHEARSAL = Size(
    n_flows=5000, n_endpoints=64, block_rows=1024,
    quantum_blocks=2, quanta_first=12, quanta_second=3,
    overrides={
        "flow_dict_slots": 1 << 14, "batch_capacity": 2048,
        "transfer_min_bucket": 256, "n_pods": 256, "cms_width": 4096,
        "topk_slots": 256, "conntrack_slots": 4096,
        "identity_slots": 1024, "entropy_buckets": 256,
        "hll_precision": 10,
    },
    ready_deadline_s=240.0, warm_deadline_s=240.0,
)


class SmokeFailure(RuntimeError):
    """A phase could not finish (deadline, dead thread, bad answer)."""


# -- output -------------------------------------------------------------
_T0 = time.monotonic()
FAILED: list[str] = []


def emit(**obj) -> None:
    obj.setdefault("t", round(time.monotonic() - _T0, 2))
    print(json.dumps(obj, default=str), flush=True)


def check(name: str, ok: bool, **detail) -> bool:
    emit(check=name, ok=bool(ok), **detail)
    if not ok:
        FAILED.append(name)
    return bool(ok)


def wait_for(what: str, pred, deadline_s: float, poll_s: float = 0.05,
             alive=None) -> float:
    """Poll ``pred`` until true; SmokeFailure past the deadline or when
    ``alive`` says the thing waited on is dead. Returns seconds waited."""
    t0 = time.monotonic()
    while True:
        if pred():
            return time.monotonic() - t0
        if alive is not None and not alive():
            raise SmokeFailure(f"{what}: agent thread died")
        if time.monotonic() - t0 > deadline_s:
            raise SmokeFailure(
                f"{what}: not done after {deadline_s:.0f}s"
            )
        time.sleep(poll_s)


# -- compile accounting ---------------------------------------------------
class CompileLog:
    """Every XLA compile request of the process, from jax.monitoring:
    program name, seconds, and whether JAX's persistent cache served it.
    (AOT disk-cache hits never reach XLA; telemetry.aot_disk_cache_stats
    counts those.)"""

    def __init__(self) -> None:
        self.records: list[dict] = []
        self._hit = threading.local()

    def install(self) -> None:
        from jax import monitoring

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self._hit.flag = True

        def on_duration(event, secs, **kw):
            if event != "/jax/core/compile/backend_compile_duration":
                return
            hit = getattr(self._hit, "flag", False)
            self._hit.flag = False
            self.records.append({
                "program": kw.get("fun_name", "?"),
                "seconds": round(float(secs), 3),
                "served_by": "xla-cache" if hit else "compiled",
                "at": time.monotonic(),
            })

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    def since(self, t: float) -> list[dict]:
        return [r for r in self.records if r["at"] >= t]

    @staticmethod
    def summary(recs: list[dict]) -> dict:
        big = sorted(
            (r for r in recs if r["seconds"] >= 0.5),
            key=lambda r: -r["seconds"],
        )
        return {
            "requests": len(recs),
            "compiled": sum(r["served_by"] == "compiled" for r in recs),
            "from_xla_cache": sum(
                r["served_by"] == "xla-cache" for r in recs
            ),
            "seconds": round(sum(r["seconds"] for r in recs), 2),
            "programs_over_half_second": [
                {k: r[k] for k in ("program", "seconds", "served_by")}
                for r in big[:12]
            ],
        }


# -- the plain reference ---------------------------------------------------
class Reference:
    """Exact answers for the fed events, in numpy and dicts only:
    independent of retina_tpu.ops and of the device. Cumulative over
    both boots, because the second boot resumes the first's checkpoint."""

    def __init__(self, n_endpoints: int, n_reasons: int = 16):
        self.n = n_endpoints
        self.r = n_reasons
        self.fwd = np.zeros((n_endpoints, 2, 2), np.int64)  # pod,dir,{pk,by}
        self.drop = np.zeros((n_endpoints, n_reasons, 2), np.int64)
        self.events = 0
        self._keys: list = []  # per-quantum 5-tuple hashes
        self._rows: list = []  # per-quantum (src, dst, ports, proto)

    def add(self, rec) -> None:
        from retina_tpu.events.schema import (
            DIR_INGRESS, F, VERDICT_DROPPED, VERDICT_FORWARDED,
        )

        meta = rec[:, F.META]
        ingress = ((meta >> np.uint32(4)) & np.uint32(0xF)) == DIR_INGRESS
        src = rec[:, F.SRC_IP].astype(np.int64) - POD_NET
        dst = rec[:, F.DST_IP].astype(np.int64) - POD_NET
        known = lambda p: (p >= 1) & (p < self.n)
        # The deployment filters to IPs of interest: an event counts
        # when either endpoint is a registered pod.
        interest = known(src) | known(dst)
        local = np.where(ingress, dst, src)
        ok = interest & known(local)
        pk = rec[:, F.PACKETS].astype(np.int64)
        by = rec[:, F.BYTES].astype(np.int64)
        self.events += int(pk[interest].sum())
        d = np.where(ingress, 0, 1)
        fwd = ok & (rec[:, F.VERDICT] == VERDICT_FORWARDED)
        idx = (local[fwd] * 2 + d[fwd]).astype(np.int64)
        for lane, w in ((0, pk), (1, by)):
            self.fwd[:, :, lane] += np.bincount(
                idx, weights=w[fwd], minlength=self.n * 2
            ).astype(np.int64).reshape(self.n, 2)
        drp = ok & (rec[:, F.VERDICT] == VERDICT_DROPPED)
        reason = np.minimum(rec[:, F.DROP_REASON], self.r - 1).astype(np.int64)
        idx = local[drp] * self.r + reason[drp]
        for lane, w in ((0, pk), (1, by)):
            self.drop[:, :, lane] += np.bincount(
                idx, weights=w[drp], minlength=self.n * self.r
            ).astype(np.int64).reshape(self.n, self.r)
        # Per-flow truth, for the sketch contracts: a 64-bit mix of the
        # 5-tuple (collisions among ~1e6 keys in 2^64 are negligible).
        proto = meta >> np.uint32(24)
        cols = (rec[:, F.SRC_IP], rec[:, F.DST_IP], rec[:, F.PORTS], proto)
        key = np.zeros(len(rec), np.uint64)
        for c, mul in zip(cols, (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F,
                                 0x165667B19E3779F9, 0x27D4EB2F165667C5)):
            key = (key ^ c.astype(np.uint64)) * np.uint64(mul)
            key ^= key >> np.uint64(29)
        self._keys.append(key[interest])
        self._rows.append(np.stack(cols, axis=1)[interest])

    def flows(self, k: int) -> tuple[int, list[tuple]]:
        """(distinct 5-tuples, the k heaviest as scrape label tuples)."""
        keys = np.concatenate(self._keys)
        rows = np.concatenate(self._rows)
        _, first, counts = np.unique(
            keys, return_index=True, return_counts=True
        )
        top = np.argsort(-counts, kind="stable")[:k]
        out = []
        for src, dst, ports, proto in rows[first[top]].tolist():
            out.append((
                ip_str(src), ip_str(dst), str(ports >> 16),
                str(ports & 0xFFFF),
                {6: "TCP", 17: "UDP"}.get(proto, str(proto)),
            ))
        return len(counts), out

    def pod_series(self) -> tuple[dict, dict]:
        """Nonzero cells keyed like the scrape: forward by
        (pod, direction, lane), drop by (pod, reason name, lane)."""
        from retina_tpu.plugins.dropreason import DROP_REASONS

        fwd, drop = {}, {}
        lanes = ("count", "bytes")
        for p, d, lane in zip(*np.nonzero(self.fwd)):
            fwd[(f"pod-{p}", ("ingress", "egress")[d], lanes[lane])] = int(
                self.fwd[p, d, lane]
            )
        for p, r, lane in zip(*np.nonzero(self.drop)):
            drop[(f"pod-{p}", DROP_REASONS.get(int(r), str(int(r))),
                  lanes[lane])] = int(self.drop[p, r, lane])
        return fwd, drop


def ip_str(u: int) -> str:
    return ".".join(str((u >> s) & 0xFF) for s in (24, 16, 8, 0))


# -- reading the agent over HTTP ----------------------------------------
def http_get(port: int, path: str, timeout: float = 30.0) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


class Scrape:
    """One parsed /metrics body."""

    def __init__(self, text: str):
        from prometheus_client.parser import text_string_to_metric_families

        self.samples: dict[str, list] = {}
        for fam in text_string_to_metric_families(text):
            for s in fam.samples:
                self.samples.setdefault(s.name, []).append(s)

    def get(self, name: str) -> list:
        """Samples of a series, with or without the counter suffix."""
        full = PREFIX + name
        return self.samples.get(full, []) + self.samples.get(
            full + "_total", []
        )

    def total(self, name: str, **labels) -> float:
        return sum(
            s.value for s in self.get(name)
            if all(s.labels.get(k) == v for k, v in labels.items())
        )

    def pod_series(self) -> tuple[dict, dict]:
        fwd, drop = {}, {}
        for lane in ("count", "bytes"):
            for s in self.get(f"adv_forward_{lane}"):
                if s.value:
                    fwd[(s.labels["podname"], s.labels["direction"],
                         lane)] = int(s.value)
            for s in self.get(f"adv_drop_{lane}"):
                if s.value:
                    drop[(s.labels["podname"], s.labels["reason"],
                          lane)] = int(s.value)
        return fwd, drop

    def heavy_flows(self) -> set:
        return {
            tuple(s.labels[k] for k in (
                "src_ip", "dst_ip", "src_port", "dst_port", "protocol"))
            for s in self.get("sketch_heavy_hitter_flow_packets")
        }


def compare_exact(name: str, got: dict, want: dict) -> bool:
    """Exact equality of two series dicts; prints a few differences."""
    diff = [
        (k, got.get(k, 0), want.get(k, 0))
        for k in sorted(set(got) | set(want))
        if got.get(k, 0) != want.get(k, 0)
    ]
    return check(
        name, not diff, series=len(want), mismatched=len(diff),
        first_mismatches=[
            {"key": list(k), "agent": g, "reference": w}
            for k, g, w in diff[:5]
        ],
    )


# Series that must read zero for the smoke to pass. The agent keeps
# running through every one of them, which is right in production (drop
# and count, rebuild, restart) and would hide a dead device here.
ZERO_SERIES = (
    "tpu_degraded_mode", "tpu_engine_restarts", "engine_errors_counter",
    "watchdog_stalls_counter", "thread_restarts_counter",
    "plugin_restarts_counter", "lost_events_counter",
    "tpu_overload_state", "tpu_events_sampled_counter",
)


def health_verdict(scrape: Scrape) -> dict:
    """Nonzero members of ZERO_SERIES, as {series{labels}: value}."""
    bad = {}
    for name in ZERO_SERIES:
        for s in scrape.get(name):
            if s.value and not s.name.endswith("_created"):
                lab = ",".join(f"{k}={v}" for k, v in sorted(s.labels.items()))
                bad[f"{name}{{{lab}}}"] = s.value
    return bad


# -- the seeded source ------------------------------------------------------
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


# -- the deployment ----------------------------------------------------------
def build_config(size: Size, work: str, aot_dir: str, xla_dir: str,
                 **extra):
    """The documented deployment through load_config, changed only by
    what a machine without a NIC or a cluster forces, and by the size."""
    import yaml

    from retina_tpu.config import load_config

    with open(os.path.join(ROOT, "deploy", "manifests",
                           "configmap.yaml")) as f:
        text = yaml.safe_load(f)["data"]["config.yaml"]
    os.makedirs(work, exist_ok=True)
    path = os.path.join(work, "config.yaml")
    with open(path, "w") as f:
        f.write(text)
    plugins = yaml.safe_load(text)["enabled_plugins"]
    overrides = {
        # No NIC: the seeded source stands in for packetparser's live
        # capture (conntrack GC, which rides on packetparser, is listed
        # by the configmap itself).
        "enabled_plugins": [
            "seededsource" if p == "packetparser" else p for p in plugins
        ],
        "event_source": "synthetic",
        # No cluster: loopback port, no Hubble listeners.
        "api_server_addr": "127.0.0.1:0",
        "enable_hubble": False,
        "hubble_metrics_addr": "",
        # Everything the agent writes stays under the checkout.
        "snapshot_dir": os.path.join(work, "snapshots"),
        "compilation_cache_dir": xla_dir,
        "aot_cache_dir": aot_dir,
        "profile_artifact_dir": os.path.join(work, "profile"),
        "autocapture_output_dir": os.path.join(work, "autocapture"),
        "soak_artifact_dir": os.path.join(work, "soak"),
        **size.overrides,
        **extra,
    }
    return load_config(path, overrides=overrides)


class Agent:
    """One boot of the daemon on a background thread."""

    def __init__(self, cfg, size: Size):
        from retina_tpu.common import RetinaEndpoint
        from retina_tpu.daemon import Daemon

        self.size = size
        self.daemon = Daemon(cfg)
        # One filter-table push for all endpoints: pushed per pod event
        # the table is rebuilt from scratch each time, which for 2,047
        # endpoints is most of a minute of host time.
        fm = self.daemon.cm.filtermanager
        with fm.deferred_push():
            for i in range(1, size.n_endpoints):
                self.daemon.cm.cache.update_endpoint(RetinaEndpoint(
                    name=f"pod-{i}", namespace="default",
                    ips=(ip_str(POD_NET + i),),
                ))
            wait_for("endpoints reach the filter manager",
                     lambda: fm.ip_count() == size.n_endpoints - 1, 60.0)
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

    def alive(self) -> bool:
        return self.thread.is_alive()

    def wait_ready(self) -> float:
        cm = self.daemon.cm

        def up() -> bool:
            if cm.server is None or cm.server._httpd is None:
                return False
            self.port = cm.server.port
            return http_get(self.port, "/readyz", 5.0)[0] == 200

        wait_for("agent ready", up, self.size.ready_deadline_s, 0.2,
                 self.alive)
        return time.monotonic() - self.t_boot

    def needed_programs(self) -> dict:
        """The device programs this script's own traffic reaches. A
        quantum is dealt over the feed workers and each flushes at most
        its share, so no wire bucket above that share's is reached."""
        eng = self.engine
        workers = eng._resolve_feed_workers()
        share = -(-self.size.quantum_blocks // workers)
        top = eng._wire_bucket(share * self.size.block_rows)
        buckets = [b for b in eng._reachable_buckets() if b <= top]
        keys = [(kind, b) for b in buckets for kind in ("known", "new")]
        return {"feed_workers": workers, "max_rows_per_flush":
                share * self.size.block_rows, "ingest_keys": keys}

    def wait_warm(self) -> dict:
        """Wait for the programs the traffic reaches — window close,
        descriptor table, the ingest pair of every bucket up to the
        flush size, both snapshot programs — and not for the rest of
        the bucket grid, which goes on warming in the background."""
        eng = self.engine
        need = self.needed_programs()
        t0 = time.monotonic()

        def resident() -> bool:
            if eng.bucket_warm_failed.is_set():
                raise SmokeFailure("background warm failed")
            return (
                eng._close_warmed.is_set()
                and eng._desc_table is not None
                and all(k in eng._pad_cache for k in need["ingest_keys"])
                and eng.sharded._snapshot is not None
                and eng.sharded._snapshot_flat is not None
            )

        wait_for("needed programs resident", resident,
                 self.size.warm_deadline_s, 0.1, self.alive)
        need["needed_resident_s"] = round(time.monotonic() - t0, 2)
        need["whole_grid_resident"] = eng.bucket_warm_done.is_set()
        need["grid_keys_reachable"] = 2 * len(eng._reachable_buckets())
        need["ingest_keys"] = [list(k) for k in need["ingest_keys"]]
        return need

    def shutdown(self) -> float:
        t0 = time.monotonic()
        self.stop.set()
        self.thread.join(120.0)
        if self.thread.is_alive():
            raise SmokeFailure("agent did not stop within 120s")
        return time.monotonic() - t0


def program_counts(eng) -> dict:
    """Executables held per warmed program: growth after the warm was
    reported done means a program compiled a second time."""
    sh = eng.sharded
    out = {"ingest_keys": len(eng._pad_cache)}
    for tag, prog in (("step", sh._step), ("end_window", sh._end_window),
                      ("snapshot", sh._snapshot),
                      ("snapshot_flat", sh._snapshot_flat
                       and sh._snapshot_flat[0])):
        out[tag] = prog._cache_size() if prog is not None else 0
    return out


def feed(agent: Agent, gen, ref: Reference, n_quanta: int,
         fed_before: int) -> dict:
    """Feed ``n_quanta`` quanta through the seeded source, one at a
    time: the next goes in when the engine has taken the last, and no
    sooner than the pace that spreads the feed over four windows."""
    size, eng = agent.size, agent.engine
    src = agent.daemon.cm.pluginmanager.plugins["seededsource"]
    q_rows = size.quantum_blocks * size.block_rows
    win_s = eng.cfg.window_seconds
    pace = 4.5 * win_s / max(n_quanta, 1)
    fed = 0
    t0 = time.monotonic()
    for i in range(n_quanta):
        rec = gen.batch(q_rows)
        ref.add(rec)
        for j in range(0, q_rows, size.block_rows):
            src.inbox.put(rec[j:j + size.block_rows])
        fed += q_rows
        target = fed_before + fed
        wait_for(f"quantum {i} taken by the engine",
                 lambda: eng._events_in >= target, 120.0, 0.005,
                 agent.alive)
        delay = t0 + (i + 1) * pace - time.monotonic()
        if delay > 0:
            time.sleep(delay)
    # Every fed event belongs to a closed window: none deferred forever.
    settle = wait_for(
        "last window closed and harvested",
        lambda: (eng._closed_events_in == eng._events_in
                 and not eng._harvest_q.unfinished_tasks),
        10 * win_s + 20.0, 0.05, agent.alive,
    )
    return {"events": fed, "offered": src.offered, "accepted": src.accepted,
            "feed_s": round(time.monotonic() - t0 - settle, 2),
            "settle_s": round(settle, 2)}


def scrape_until(agent: Agent, want_events: int, deadline_s: float) -> Scrape:
    """Scrape until the pod series account for every fed event (the
    first scrape after boot is the boot-warmed render cache; pod gauges
    follow the 1 s publish cadence), then once more: the answer must
    hold still."""
    def total(s: Scrape) -> int:
        return int(s.total("adv_forward_count") + s.total("adv_drop_count"))

    t0 = time.monotonic()
    n = 0
    while True:
        n += 1
        code, body = http_get(agent.port, "/metrics")
        s = Scrape(body.decode())
        if code == 200 and total(s) >= want_events:
            break
        if time.monotonic() - t0 > deadline_s:
            break
        time.sleep(0.3)
    time.sleep(1.5)
    s2 = Scrape(http_get(agent.port, "/metrics")[1].decode())
    check("scrape_stable", total(s2) == total(s), scrapes=n + 1,
          wait_s=round(time.monotonic() - t0, 2),
          pod_series_total=total(s2), fed_events=want_events)
    return s2


def verify(agent: Agent, ref: Reference, label: str,
           sketches: bool) -> Scrape:
    """Scrape, read /debug/vars and /healthz, compare with the
    reference, and hold the agent's self-metrics to zero."""
    eng = agent.engine
    s = scrape_until(agent, ref.events, 30.0)
    got_f, got_d = s.pod_series()
    want_f, want_d = ref.pod_series()
    compare_exact(f"{label}_pod_forward_exact", got_f, want_f)
    compare_exact(f"{label}_pod_drop_exact", got_d, want_d)
    code, body = http_get(agent.port, "/debug/vars")
    dvars = json.loads(body) if code == 200 else {}
    device_totals = [int(x) for x in eng.snapshot(max_age_s=0)["totals"]]
    check(f"{label}_total_events_exact",
          device_totals[0] == ref.events and device_totals[7] == 0,
          device_events=device_totals[0], device_lost=device_totals[7],
          reference_events=ref.events,
          engine_var=dvars.get("engine", {}))
    code, _ = http_get(agent.port, "/healthz")
    bad = health_verdict(s)
    ov = dvars.get("overload", {})
    check(f"{label}_health", code == 200 and not bad
          and not eng.bucket_warm_failed.is_set()
          and ov.get("transitions", 0) == 0,
          healthz=code, nonzero=bad,
          bucket_warm_failed=eng.bucket_warm_failed.is_set(),
          overload=ov.get("state"), overload_transitions=ov.get("transitions"))
    check(f"{label}_both_wires",
          s.total("tpu_wire_rows_counter", kind="new") > 0
          and s.total("tpu_wire_rows_counter", kind="known") > 0,
          new_rows=s.total("tpu_wire_rows_counter", kind="new"),
          known_rows=s.total("tpu_wire_rows_counter", kind="known"),
          flow_dict=dvars.get("feed", {}).get("flow_dict"))
    if sketches:
        # docs/metrics.md contracts: recall@50 ~0.98 under Zipf traffic
        # (the floor below allows for ties at the 50th rank); HLL error
        # ~1.6% at precision 12 (three standard errors).
        distinct, top = ref.flows(50)
        scraped = s.heavy_flows()
        recall = sum(t in scraped for t in top) / max(len(top), 1)
        check(f"{label}_heavy_hitter_recall", recall >= 0.9,
              recall_at_50=recall, scraped_series=len(scraped))
        est = s.total("sketch_distinct_flows")
        err = abs(est - distinct) / max(distinct, 1)
        check(f"{label}_hll_distinct_flows", err <= 0.05,
              estimate=est, exact=distinct, rel_err=round(err, 4))
    return s


def aot_files(aot_dir: str) -> dict:
    out = {}
    for name in os.listdir(aot_dir) if os.path.isdir(aot_dir) else ():
        if name.endswith(".aotx"):
            out[name] = os.stat(os.path.join(aot_dir, name)).st_mtime_ns
    return out


def aot_delta(before: dict) -> dict:
    from retina_tpu.parallel.telemetry import aot_disk_cache_stats

    now = aot_disk_cache_stats()
    return {k: now[k] - before.get(k, 0)
            for k in ("hits", "misses", "errors")}


def memory_line(label: str) -> None:
    import jax

    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        emit(memory=label, device=d.id,
             bytes_in_use=ms.get("bytes_in_use"),
             peak_bytes_in_use=ms.get("peak_bytes_in_use"))


def real_closes() -> int:
    """Window closes that ran end_window (idle ticks close without
    one), from the flight recorder's window_close spans."""
    from retina_tpu.obs.recorder import get_recorder
    from retina_tpu.utils import metric_names as mn

    rep = get_recorder().stage_report()
    return int(rep.get(mn.STAGE_WINDOW_CLOSE, {}).get("count", 0))


def run_boot(label: str, cfg, size: Size, gen, ref: Reference,
             n_quanta: int, clog: CompileLog, sketches: bool,
             after=None) -> dict:
    """Boot, warm, feed, verify, stop. Returns set-up facts."""
    from retina_tpu.parallel.telemetry import aot_disk_cache_stats

    aot0 = aot_disk_cache_stats()
    t_boot = time.monotonic()
    agent = Agent(cfg, size)
    try:
        ready_s = agent.wait_ready()
        eng = agent.engine
        emit(phase=f"{label}_ready", ready_s=round(ready_s, 2),
             devices=eng.n_devices,
             compiles=CompileLog.summary(clog.since(t_boot)),
             aot_disk=aot_delta(aot0))
        # Identity and filter tables: every endpoint registered before
        # the first event (their uploads ride the proxy FIFO ahead of
        # any later dispatch).
        n = size.n_endpoints - 1

        def tables() -> bool:
            v = json.loads(http_get(agent.port, "/debug/vars")[1])
            return v.get("pods") == n and v.get("filter_ips") == n

        wait_for("identity and filter tables", tables, 120.0, 0.5,
                 agent.alive)
        warm = agent.wait_warm()
        counts0 = program_counts(eng)
        emit(phase=f"{label}_warm", **warm, programs=counts0,
             compiles=CompileLog.summary(clog.since(t_boot)),
             aot_disk=aot_delta(aot0))
        # Resumed state: what the agent holds before this boot's feed.
        fed_before = eng._events_in
        closed0 = real_closes()
        t_feed = time.monotonic()
        fed = feed(agent, gen, ref, n_quanta, fed_before)
        in_feed = clog.since(t_feed)
        counts1 = program_counts(eng)
        emit(phase=f"{label}_fed", **fed,
             compiles_during_feed=CompileLog.summary(in_feed))
        check(f"{label}_events_accepted",
              fed["accepted"] == fed["offered"] == fed["events"], **fed)
        # A program whose warm was reported done must not compile again
        # inside the fed windows; ingest keys may only be added above
        # the buckets that were waited for (the background warm).
        waited = {tuple(k) for k in warm["ingest_keys"]}
        regrown = {k: (counts0[k], counts1[k]) for k in counts0
                   if k != "ingest_keys" and counts1[k] != counts0[k]}
        check(f"{label}_no_recompile_in_feed",
              not regrown and waited <= set(eng._pad_cache),
              regrown=regrown, ingest_keys_before=counts0["ingest_keys"],
              ingest_keys_after=counts1["ingest_keys"])
        s = verify(agent, ref, label, sketches)
        closed = real_closes() - closed0
        check(f"{label}_windows_closed", closed >= 3,
              closed_with_events_during_feed=closed,
              deferred_ticks=s.total("tpu_windows_deferred"))
        emit(phase=f"{label}_build_info",
             labels=[b.labels for b in s.get("retina_build_info")])
        if after is not None:
            after(agent, s)
        memory_line(label)
    finally:
        stop_s = agent.shutdown()
    aot = aot_delta(aot0)
    emit(phase=f"{label}_stopped", stop_s=round(stop_s, 2), aot_disk=aot)
    check(f"{label}_aot_disk_errors", aot["errors"] == 0, **aot)
    return {"ready_s": ready_s, "aot": aot}


# -- the two modes ------------------------------------------------------------
def one_chip(args, size: Size, work: str, xla_dir: str, aot_dir: str,
             clog: CompileLog) -> None:
    from retina_tpu.events.synthetic import TrafficGen

    cfg = build_config(size, work, aot_dir, xla_dir)
    gen = TrafficGen(n_flows=size.n_flows, n_pods=size.n_endpoints,
                     seed=args.seed)
    ref = Reference(size.n_endpoints)
    first = run_boot("boot1", cfg, size, gen, ref, size.quanta_first,
                     clog, sketches=True)
    snap = os.path.join(cfg.snapshot_dir, "sketch_state.npz")
    check("checkpoint_written", os.path.exists(snap), path=snap)
    persisted = aot_files(aot_dir)
    # Second boot of the same deployment, against the caches and the
    # checkpoint the first wrote: the agent's restart path.
    second = run_boot("boot2", cfg, size, gen, ref, size.quanta_second,
                      clog, sketches=False)
    after = aot_files(aot_dir)
    rewritten = sorted(k for k in persisted if after.get(k) != persisted[k])
    check("second_boot_recompiled_nothing_persisted",
          not rewritten and second["aot"]["hits"] > 0,
          persisted_by_first_boot=len(persisted), rewritten=rewritten[:8],
          new_files=len(set(after) - set(persisted)), **second["aot"])
    emit(phase="restart", cold_ready_s=round(first["ready_s"], 2),
         second_ready_s=round(second["ready_s"], 2))


def four_chips(args, size: Size, work: str, xla_dir: str, aot_dir: str,
               clog: CompileLog) -> None:
    """The same deployment and the same events on a four-device mesh
    and on one device of the same host; nothing else."""
    import jax

    from retina_tpu.events.synthetic import TrafficGen

    answers = {}
    for n_dev in (4, 1):
        sub = os.path.join(work, f"mesh{n_dev}")
        cfg = build_config(size, sub, aot_dir, xla_dir, mesh_devices=n_dev)
        gen = TrafficGen(n_flows=size.n_flows, n_pods=size.n_endpoints,
                         seed=args.seed)
        ref = Reference(size.n_endpoints)

        def evidence(agent, s, n_dev=n_dev, ref=ref):
            from retina_tpu.utils.device_proxy import run_on_device

            eng = agent.engine

            def shards() -> dict:
                # Each device's own shard of the state: events it
                # counted (it executed steps on its share of the
                # traffic) and bytes it holds.
                with eng._state_lock:
                    st = eng.state
                out: dict = {}
                for sh in st.totals.addressable_shards:
                    out[sh.device.id] = {
                        "events": int(np.asarray(sh.data)[0, 0])}
                for leaf in jax.tree_util.tree_leaves(st):
                    for sh in leaf.addressable_shards:
                        d = out.setdefault(sh.device.id, {})
                        d["state_bytes"] = (
                            d.get("state_bytes", 0) + sh.data.nbytes)
                return out

            per_dev = run_on_device(shards)
            sizes = {v.get("state_bytes") for v in per_dev.values()}
            check(f"mesh{n_dev}_state_and_work_spread",
                  len(per_dev) == n_dev and len(sizes) == 1
                  and all(v.get("events", 0) > 0 for v in per_dev.values())
                  and sum(v["events"] for v in per_dev.values())
                  == ref.events,
                  per_device=per_dev)
            answers[n_dev] = (s.pod_series(), s.heavy_flows(),
                              s.total("sketch_distinct_flows"))

        run_boot(f"mesh{n_dev}", cfg, size, gen, ref, size.quanta_first,
                 clog, sketches=True, after=evidence)
    if set(answers) == {1, 4}:
        (f4, d4), hh4, hll4 = answers[4]
        (f1, d1), hh1, hll1 = answers[1]
        compare_exact("mesh4_equals_one_device_forward", f4, f1)
        compare_exact("mesh4_equals_one_device_drop", d4, d1)
        emit(phase="mesh_vs_one_device",
             heavy_hitters_common=len(hh4 & hh1), hll_mesh4=hll4,
             hll_one_device=hll1)


# -- entry ------------------------------------------------------------------------
def device_identity() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21,
                    help="seed of the generated traffic")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-device mesh and the "
                         "one-device run it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes for a rehearsal without the chip; "
                         "only the sizes change")
    args = ap.parse_args(argv)
    size = REHEARSAL if args.rehearse else REAL

    # The device check comes first: off a TPU nothing is generated or
    # compiled (a rehearsal goes on, and still ends with "ok": false).
    device = device_identity()
    if args.chips == 4:
        device["count"] = min(device["count"], 4)

    def finish(error: str | None = None) -> int:
        if error:
            FAILED.append("error")
            emit(error=error)
        ok = not FAILED
        emit(summary="chip_smoke", ok=ok, failed=FAILED,
             mode=f"{args.chips}-chip", rehearsal=args.rehearse)
        print(json.dumps({"ok": ok, "device": device}), flush=True)
        return 0 if ok else 1

    on_tpu = device["platform"] == "tpu"
    check("platform", on_tpu, **device)
    if not on_tpu and not args.rehearse:
        return finish()
    import jax

    if args.chips == 4 and len(jax.devices()) < 4:
        return finish(f"--chips 4 needs four devices, "
                      f"found {len(jax.devices())}")

    def watchdog() -> None:
        FAILED.append("watchdog")
        emit(error=f"not finished after {WATCHDOG_S:.0f}s; giving up")
        print(json.dumps({"ok": False, "device": device}), flush=True)
        os._exit(3)

    timer = threading.Timer(WATCHDOG_S, watchdog)
    timer.daemon = True
    timer.start()
    try:
        import jaxlib

        from retina_tpu import native
        from retina_tpu.config import (
            CHECKOUT_CACHE_DIR, enable_harness_caches,
        )
        from retina_tpu.log import setup_logger

        try:
            from importlib.metadata import version

            libtpu = version("libtpu")
        except Exception:  # noqa: BLE001 — a version string, not a check
            libtpu = None
        emit(versions={"python": sys.version.split()[0],
                       "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                       "libtpu": libtpu, "numpy": np.__version__},
             device=device, seed=args.seed, mode=f"{args.chips}-chip",
             rehearsal=args.rehearse,
             cache_dir_env=os.environ.get(
                 "JAX_COMPILATION_CACHE_DIR"))
        setup_logger("info")
        # Caches: where the caller placed them, else one fixed
        # directory of the checkout. Unusable is an error here.
        xla_dir, aot_dir = enable_harness_caches()
        work = os.path.join(CHECKOUT_CACHE_DIR, "smoke")
        # This run's own checkpoint and config; a checkpoint left by an
        # earlier run would be resumed by the first boot.
        shutil.rmtree(work, ignore_errors=True)
        emit(caches={"xla": xla_dir, "aot": aot_dir,
                     "aot_files_at_start": len(aot_files(aot_dir)),
                     "work": work})
        # The native library is built on demand from the tracked .cpp
        # files; a failed build degrades to Python with a warning.
        had_so = os.path.exists(native._so_path)
        check("native_library", native.native_available(),
              how="loaded" if had_so else "built", path=native._so_path)
        clog = CompileLog()
        clog.install()
        register_source()
        mode = four_chips if args.chips == 4 else one_chip
        mode(args, size, work, xla_dir, aot_dir, clog)
        return finish()
    except SmokeFailure as e:
        return finish(str(e))
    except Exception as e:  # noqa: BLE001 — every failure ends in the last line
        import traceback

        traceback.print_exc()
        return finish(f"{type(e).__name__}: {e}")
    finally:
        timer.cancel()


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Skip interpreter teardown: daemon threads (device proxy, HTTP
    # server, watchers) may still sit inside runtime calls, and the exit
    # code must say what the checks said (that teardown under them is
    # safe is unverified on the attached chip).
    os._exit(code)
