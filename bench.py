"""Benchmark: single-chip fused telemetry pipeline throughput.

Measures flow-events/sec through the jitted TelemetryPipeline step — the
path that replaces the reference's single-threaded Go ProcessFlow loop
(pkg/module/metrics/metrics_module.go:283-303, the scaling bottleneck per
SURVEY.md §3.2) — on a 2M-event replay over a 1M-flow Zipf set
(BASELINE config 2), plus
heavy-hitter recall vs exact ground truth.

- stage progress to stderr (devices, state init, compile seconds, steps);
- the device-step and e2e phases run on a TPU or not at all: any other
  platform is an error, and every result names platform, device kind and
  device count under ``"device"``;
- ``--smoke`` runs reduced shapes;
- ALWAYS prints exactly one JSON line on stdout. On failure it carries
  an "error" field and no metric, and the exit code is nonzero: a failed
  phase is never replaced by another phase's number.

Prints ONE JSON line. The default run's headline is the END-TO-END
system rate (the north-star claim):
  {"metric": "flow_events_per_sec_e2e", "value": N, "unit": "events/s",
   "vs_baseline": value / 10e6,
   "extra": {"e2e": {...}, "device_step": {...}}}
with the device-resident step rate in extra.device_step. --no-e2e emits
the device-step metric (flow_events_per_sec_per_chip) as before.
vs_baseline is measured against the north-star target of 10M
flow-events/sec/node (BASELINE.md; the reference publishes no absolute
numbers, so the target is the baseline).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T0:8.2f}s] {msg}", file=sys.stderr, flush=True)


T0 = time.perf_counter()


def require_tpu() -> dict:
    """Device identity as JAX reports it, for every result line. A
    platform that is not a TPU is an error: a rate measured on the CPU
    backend must never be filed under a per-chip name."""
    import jax

    devs = jax.devices()
    dev = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if dev["platform"] != "tpu":
        raise RuntimeError(
            f"bench needs a TPU; jax.devices() reports {dev}"
        )
    return dev


def enable_caches() -> tuple[str, str]:
    """Persistent XLA cache + AOT executable cache for this run: where
    JAX_COMPILATION_CACHE_DIR says, else one fixed directory of the
    checkout (config.enable_harness_caches)."""
    from retina_tpu.config import enable_harness_caches

    xla_dir, aot_dir = enable_harness_caches()
    log(f"XLA compilation cache at {xla_dir}, AOT executables at {aot_dir}")
    return xla_dir, aot_dir


def run(smoke: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from retina_tpu.events.synthetic import TrafficGen
    from retina_tpu.models.identity import IdentityMap
    from retina_tpu.models.pipeline import PipelineConfig, TelemetryPipeline

    device = require_tpu()
    log(f"devices acquired: {device}")
    # Persistent XLA cache: a warm rerun skips the minutes-long
    # full-shape compile, which is what an agent restart experiences.
    enable_caches()

    out: dict = {
        "metric": "flow_events_per_sec_per_chip",
        "value": 0,
        "unit": "events/s",
        "vs_baseline": 0.0,
        "device": device,
        "extra": {"smoke": smoke, "backend": device["platform"]},
    }

    if smoke:
        batch = 1 << 14
        n_batches = 4
        timed_steps = 8
        cfg = PipelineConfig(
            n_pods=256, cms_width=1 << 12, topk_slots=1 << 8,
            conntrack_slots=1 << 12, latency_slots=1 << 8,
            entropy_buckets=1 << 8,
        )
        n_flows, n_pods_gen = 50_000, 256
    else:
        # 2^21 events per step (128 MiB of records) fits HBM beside
        # production-shape state; two resident device batches bound
        # the up-front host->device transfer at 256 MiB. How step time
        # scales with batch size is not measured on the current tree
        # (ROADMAP S1).
        batch = 1 << 21  # 2,097,152 events/step
        n_batches = 2  # 4.2M-event replay over a 1M-flow Zipf set
        timed_steps = 24
        cfg = PipelineConfig()  # production shapes (2^18-slot conntrack, etc.)
        n_flows, n_pods_gen = 1_000_000, 2048

    pipeline = TelemetryPipeline(cfg)
    step = pipeline.jitted_step()

    log(f"generating traffic: {n_flows} flows, batch={batch}, "
        f"{n_batches} batches")
    gen = TrafficGen(n_flows=n_flows, n_pods=n_pods_gen, seed=42)
    ident = IdentityMap.build_host(
        {0x0A000000 + i: i for i in range(1, n_pods_gen)},
        n_slots=1 << (10 if smoke else 16),
    )
    host_batches = [gen.batch(batch) for i in range(n_batches)]
    dev_batches = [jax.device_put(b) for b in host_batches]
    n_valid = jnp.uint32(batch)
    api_ip = jnp.uint32(0)

    log("state init")
    state = pipeline.init_state()

    log("compile start (jit first call)")
    tc = time.perf_counter()

    state, _ = step(
        state, dev_batches[0], n_valid, jnp.uint32(1), ident, api_ip
    )
    jax.block_until_ready(state.totals)
    compile_s = time.perf_counter() - tc
    log(f"compile end: {compile_s:.1f}s")
    out["extra"]["compile_seconds"] = round(compile_s, 2)

    # Second warm step (steady-state cache touch).
    state, _ = step(state, dev_batches[1], n_valid, jnp.uint32(1),
                    ident, api_ip)
    jax.block_until_ready(state.totals)

    # Pre-place the per-step timestamps: a fresh jnp scalar per
    # iteration costs a host->device commit inside the timed loop.
    now_vals = [
        jax.device_put(jnp.uint32(2 + i // 8))
        for i in range(0, timed_steps, 8)
    ]
    log(f"timed loop: {timed_steps} steps")
    t0 = time.perf_counter()
    for i in range(timed_steps):
        state, _ = step(
            state,
            dev_batches[i % n_batches],
            n_valid,
            now_vals[i // 8],
            ident,
            api_ip,
        )
    jax.block_until_ready(state.totals)
    dt = time.perf_counter() - t0
    events_per_sec = timed_steps * batch / dt
    log(f"timed loop done: {dt * 1e3 / timed_steps:.2f} ms/step, "
        f"{events_per_sec / 1e6:.2f}M ev/s")

    out["value"] = round(events_per_sec)
    out["vs_baseline"] = round(events_per_sec / 10_000_000, 4)
    out["extra"]["batch"] = batch
    out["extra"]["timed_steps"] = timed_steps
    out["extra"]["step_ms"] = round(dt * 1e3 / timed_steps, 3)
    out["extra"]["events_total"] = int(np.asarray(state.totals)[0])

    # Heavy-hitter recall@k vs exact ground truth (BASELINE config 2).
    log("heavy-hitter recall readback")
    k = 50
    keys, _ = state.flow_hh.table.top_k_host(256)
    reported = {tuple(kk) for kk in keys}
    true_ids = gen.true_top_k(k)
    hits = 0
    for fid in true_ids:
        key = (
            int(gen.src_ip[fid]),
            int(gen.dst_ip[fid]),
            int((gen.sport[fid] << np.uint32(16)) | gen.dport[fid]),
            int(gen.proto[fid]),
        )
        hits += key in reported
    recall = hits / k
    out["extra"]["heavy_hitter_recall_at_50"] = recall
    log(f"recall@50 = {recall}")

    # BASELINE configs 3-5 ride along with the device phase (they were
    # tested but never benchmarked): cardinality, entropy-anomaly, and
    # service-graph micro-benches on the same device. A failure there
    # fails the run.
    out["extra"]["baseline_configs"] = run_baseline_configs(smoke)
    return out


def run_baseline_configs(smoke: bool) -> dict:
    """BASELINE configs 3-5 micro-benches (BASELINE.md §configs):

    - Config 3: per-(reason,pod) HLL distinct-src cardinality with a
      cross-node max-merge, scored by worst-group relative error.
    - Config 4: streaming src-IP entropy window + EWMA anomaly flag on
      a trafficgen-style burst trace (flag must fire on the burst and
      stay quiet before it).
    - Config 5: pod x pod service-graph top-k vs exact ground truth.

    Each reports update throughput and its accuracy score; emitted
    alongside the headline metric, never in its place."""
    import jax
    import jax.numpy as jnp

    from retina_tpu.ops.entropy import AnomalyEWMA, EntropyWindow
    from retina_tpu.ops.hyperloglog import HyperLogLog
    from retina_tpu.ops.topk import HeavyHitterSketch

    rng = np.random.default_rng(3)
    batch = 1 << (12 if smoke else 16)
    iters = 4 if smoke else 16
    res: dict = {}

    def _rate(fn, state, batches) -> tuple:
        s = fn(state, batches[0])  # compile
        jax.block_until_ready(jax.tree_util.tree_leaves(s)[0])
        t0 = time.perf_counter()
        for i in range(iters):
            s = fn(s, batches[i % len(batches)])
        jax.block_until_ready(jax.tree_util.tree_leaves(s)[0])
        return s, iters * batch / (time.perf_counter() - t0)

    # -- Config 3: per-(reason,pod) distinct-src HLL, merge-exact ------
    groups = 16 if smoke else 64
    distinct = 1 << (10 if smoke else 14)
    srcs = rng.integers(0, distinct, size=(2, iters, batch)).astype(np.uint32)
    grp = rng.integers(0, groups, size=(iters, batch)).astype(np.int32)
    ones = jnp.ones((batch,), jnp.float32)
    upd = jax.jit(
        lambda h, b: h.update([b[0]], b[1], ones)
    )
    halves = []
    for node in range(2):  # two "nodes", max-merged like a psum
        batches = [
            (jnp.asarray(srcs[node, i]), jnp.asarray(grp[i]))
            for i in range(iters)
        ]
        h = HyperLogLog.zeros(groups, 10, seed=11)
        h, hll_rate = _rate(upd, h, batches)
        halves.append(h)
    est = np.asarray(halves[0].merge(halves[1]).estimate())
    err = 0.0
    for g in range(groups):
        truth = len(
            set(srcs[0][grp == g].tolist()) | set(srcs[1][grp == g].tolist())
        )
        if truth:
            err = max(err, abs(float(est[g]) - truth) / truth)
    res["config3_hll_cardinality"] = {
        "events_per_sec": round(hll_rate),
        "groups": groups,
        "max_rel_err": round(err, 4),
        "ok": err <= 0.15,
    }

    # -- Config 4: entropy window + anomaly flag on a burst trace ------
    n_win = 16
    ent0 = EntropyWindow.zeros(1, 1 << 10, seed=12)
    det = AnomalyEWMA.zeros(1)
    flags = []
    ent_rate = 0.0

    @jax.jit
    def ent_win(ent, det, col):
        ent = ent.reset().update(
            [col], jnp.zeros((batch,), jnp.int32), ones
        )
        det, flag, _z = det.observe(
            ent.entropy_bits(), min_windows=8
        )
        return ent, det, flag

    for wi in range(n_win):
        if wi == n_win - 1:  # single-source flood: entropy collapses
            col = jnp.full((batch,), 0x0A0A0A0A, jnp.uint32)
        else:
            col = jnp.asarray(
                rng.integers(0, 1 << 16, size=batch).astype(np.uint32)
            )
        t0 = time.perf_counter()
        ent0, det, flag = ent_win(ent0, det, col)
        flag = bool(np.asarray(flag)[0])
        ent_rate = batch / (time.perf_counter() - t0)
        flags.append(flag)
    res["config4_entropy_anomaly"] = {
        "events_per_sec": round(ent_rate),
        "windows": n_win,
        "burst_flagged": flags[-1],
        "false_positives": int(sum(flags[8:-1])),
        "ok": flags[-1] and not any(flags[8:-1]),
    }

    # -- Config 5: pod x pod service-graph top-k ------------------------
    pods = 256 if smoke else 2048
    kk = 32
    # Zipf-ish edge weights: a handful of hot service edges.
    hot = rng.integers(0, pods, size=(kk, 2)).astype(np.uint32)
    svc = HeavyHitterSketch.zeros(
        2, depth=4, width=1 << 12, n_slots=1 << 10, seed=13
    )
    edge_batches = []
    exact: dict = {}
    for i in range(iters):
        cold = rng.integers(0, pods, size=(batch - kk * 8, 2)).astype(np.uint32)
        edges = np.concatenate([np.repeat(hot, 8, axis=0), cold])
        w = np.concatenate([
            np.repeat(rng.integers(50, 100, size=kk), 8),
            np.ones(len(cold), np.int64),
        ]).astype(np.float32)
        for row, wt in zip(edges, w):
            t = (int(row[0]), int(row[1]))
            exact[t] = exact.get(t, 0) + float(wt)
        edge_batches.append((
            [jnp.asarray(edges[:batch, 0]), jnp.asarray(edges[:batch, 1])],
            jnp.asarray(w[:batch]),
        ))
    svc_upd = jax.jit(lambda s, b: s.update(b[0], b[1]))
    svc, svc_rate = _rate(svc_upd, svc, edge_batches)
    keys, _counts = svc.table.top_k_host(kk * 2)
    got = {tuple(int(x) for x in row) for row in keys}
    true_top = sorted(exact, key=exact.get, reverse=True)[:kk]
    svc_recall = sum(1 for t in true_top if t in got) / kk
    res["config5_service_graph_topk"] = {
        "events_per_sec": round(svc_rate),
        "pods": pods,
        "recall_at_32": round(svc_recall, 4),
        "ok": svc_recall >= 0.9,
    }
    log(
        "baseline configs: "
        f"c3 hll err {err:.3f}, c4 burst_flagged {flags[-1]}, "
        f"c5 recall {svc_recall:.2f}"
    )
    return res


def _measure_link_bandwidth() -> float:
    """Median host->device bandwidth (MB/s) for a transfer-sized
    buffer, measured beside the e2e number so that wire bytes per event
    can be read against what the link carries."""
    import jax

    a = np.random.default_rng(0).integers(
        0, 2**31, size=(1 << 18, 12), dtype=np.int64
    ).astype(np.uint32)
    import jax.numpy as jnp

    jax.device_put(a).block_until_ready()  # warm (and compile the sum)
    float(jnp.sum(jax.device_put(a)))
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        # Force real materialization on device: a compute round trip on
        # the transferred buffer, not just a future handle.
        float(jnp.sum(jax.device_put(a)))
        rates.append(a.nbytes / 1e6 / (time.perf_counter() - t0))
    return sorted(rates)[1]


def wait_bucket_warm(
    eng, deadline_s: float, emit=log, sleep_s: float = 0.5,
) -> tuple[float | None, bool]:
    """Wait for the background bucket-grid warm to reach a TERMINAL
    state, polling BOTH events: a failed warm sets bucket_warm_failed
    and never sets bucket_warm_done, so waiting on done alone would
    burn the full deadline before measuring a system that already
    knows some keys will cold-compile mid-window.

    Returns ``(bucket_warm_s, warm_incomplete)``: seconds until the
    warm completed (None when it failed — some keys WILL cold-compile
    mid-measurement), and True when the deadline passed with the warm
    still running (measurement windows are warm-contaminated)."""
    t_warm = time.monotonic()
    while time.monotonic() - t_warm < deadline_s:
        if eng.bucket_warm_failed.is_set():
            emit("e2e: WARNING bucket grid warm FAILED "
                 f"{time.monotonic() - t_warm:.0f}s after first "
                 "traffic; some keys will cold-compile mid-measurement")
            return None, False
        if eng.bucket_warm_done.is_set():
            dt = time.monotonic() - t_warm
            emit(f"e2e: bucket grid warm complete "
                 f"{dt:.0f}s after first traffic")
            return dt, False
        time.sleep(sleep_s)
    # Deadline hit with the warm still running: record how long it had
    # been going when measurement started (a null here used to erase
    # the fact that the warm consumed the whole budget — BENCH diag
    # satellite, PR 13) and flag the window as warm-contaminated.
    emit(f"e2e: WARNING bucket grid warm not done after "
         f"{deadline_s:.0f}s; measuring anyway")
    return time.monotonic() - t_warm, True


def run_e2e(smoke: bool, duration_s: float | None = None) -> dict:
    """Full-system benchmark: boot the REAL agent (daemon: plugins ->
    sink -> combine/pack/partition feed -> device step -> metrics module
    -> HTTP /metrics) and measure sustained flow-events/s plus scrape
    latency over live HTTP — the loop the reference runs in
    pkg/module/metrics/metrics_module.go:266-330, measured end to end
    against the BASELINE north star (10M ev/s/node, <1s scrape)."""
    import threading
    import urllib.request

    from retina_tpu.common import RetinaEndpoint
    from retina_tpu.config import Config
    from retina_tpu.daemon import Daemon
    from retina_tpu.metrics import get_metrics

    device = require_tpu()
    _, aot_dir = enable_caches()
    # Per-window duration: three windows run back to back (median
    # reported), so each window is shorter than the old single one.
    dur = duration_s if duration_s is not None else (5.0 if smoke else 15.0)
    warmup = 2.0 if smoke else 5.0

    link_mbs = _measure_link_bandwidth()
    log(f"e2e: link bandwidth probe {link_mbs:.0f} MB/s")

    # Host-path capability probe (no device): the REAL per-quantum feed
    # work — combine + partition + flow-dict assign + dense wire build —
    # the ceiling the host CPU side imposes when the link stops being
    # the bottleneck (production PCIe). Median of 3 quanta; the steady
    # state (all descriptors known) is what it measures.
    from retina_tpu.events.synthetic import TrafficGen
    from retina_tpu.parallel.combine import combine_blocks
    from retina_tpu.parallel.flowdict import make_flow_dict
    from retina_tpu.parallel.partition import partition_events
    from retina_tpu.events.schema import F
    from retina_tpu.native import flowwire_dense_native
    from retina_tpu.parallel.wire import (
        DENSE_BY_BITS, DENSE_PK_BITS, dense_known_rows, dense_words,
    )

    probe_gen = TrafficGen(
        n_flows=50_000 if smoke else 1_000_000,
        n_pods=256 if smoke else 2048, seed=7,
    )
    blocks = [
        probe_gen.batch(1 << 13) for _ in range(32 if smoke else 256)
    ]
    n_quantum = sum(len(b) for b in blocks)
    fd_bits = 18 if smoke else 21
    fdict = make_flow_dict(1 << fd_bits)
    comb0 = combine_blocks(blocks)
    fdict.lookup_or_assign(
        partition_events(comb0, 1, 1 << 19, min_bucket=1 << 12)
        .records[0]
    )  # warm pass: descriptors resident, like a running agent
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        comb = combine_blocks(blocks)
        sb = partition_events(comb, 1, 1 << 19, min_bucket=1 << 12)
        rows = sb.records[0, : int(sb.n_valid[0])]
        ids, is_new = fdict.lookup_or_assign(rows)
        # Same mask and builders as the engine's dispatch (rows here
        # are stamped and carry no TSval) — the probe must price the
        # real wire build, not an approximation of it.
        sel = (
            is_new
            | (rows[:, F.PACKETS] >= 1 << DENSE_PK_BITS)
            | (rows[:, F.BYTES] >= 1 << DENSE_BY_BITS)
        )
        n_new = int(sel.sum())
        new_wire = np.zeros((n_new, 13), np.uint32)
        known_wire = np.zeros(
            dense_words(len(rows) - n_new, fd_bits), np.uint32
        )
        if flowwire_dense_native(
            np.ascontiguousarray(rows), ids, sel.astype(np.uint8), 0,
            fd_bits, DENSE_PK_BITS, DENSE_BY_BITS, new_wire, known_wire,
        ) is None:
            dense_known_rows(rows[~sel], ids[~sel], fd_bits, known_wire)
        rates.append(n_quantum / (time.perf_counter() - t0))
    host_path_rate = sorted(rates)[1]
    log(f"e2e: host-path probe {host_path_rate / 1e6:.1f}M ev/s median "
        f"of {[round(r / 1e6, 1) for r in rates]} "
        f"(combine ratio {n_quantum / len(comb0):.1f})")

    cfg = Config()
    cfg.api_server_addr = "127.0.0.1:0"
    cfg.enabled_plugins = ["packetparser"]
    cfg.event_source = "synthetic"
    # AOT executable disk cache (parallel/telemetry.py): a warm rerun
    # skips serialize/lower for the step + end-window programs; hit/miss
    # counts ride the diag line and the result.
    cfg.aot_cache_dir = aot_dir
    # Heavy-key source selector (docs/sketches.md migration path):
    # RETINA_BENCH_HEAVY_KEYS=invertible runs the e2e bench with the
    # host flow dict absent from the hot path entirely.
    hk = os.environ.get("RETINA_BENCH_HEAVY_KEYS", "")
    if hk:
        cfg.heavy_keys_source = hk
        log(f"e2e: heavy_keys_source={hk}")
    # Chaos drills: the bench builds its Config directly (no
    # load_config env layering), so honor RETINA_FAULT_SPEC here —
    # e.g. feed.backpressure:press drives the overload controller for
    # the window_overload/stalled_windows acceptance run.
    cfg.fault_spec = os.environ.get("RETINA_FAULT_SPEC", "")
    if cfg.fault_spec:
        log(f"e2e: fault injection armed: {cfg.fault_spec}")
    cfg.synthetic_rate = 1e12  # unthrottled: measure the system ceiling
    cfg.synthetic_flows = 50_000 if smoke else 1_000_000
    cfg.synthetic_pregen = 16 if smoke else 256  # 131k / 2.1M event ring
    cfg.batch_capacity = 1 << (14 if smoke else 19)
    if not smoke:
        # The host feed is fixed-cost-per-flush bound on a 1-core agent
        # box: bigger quanta amortize combine/assign/dispatch fixed
        # costs, and one coalesced transfer keeps the link busy
        # back-to-back. (A 2^21 step capacity was tried and rejected
        # for the compile time of the programs it doubles in size;
        # unverified on the attached chip.)
        cfg.flush_max_events = 1 << 22
        cfg.feed_coalesce_windows = 8
        # Size the flow dictionary to the workload's working set (1M
        # distinct flows), exactly like the reference sizes its
        # conntrack map to the expected connection count
        # (conntrack.h:21-29: 262,144 LRU entries). Undersized, ~26% of
        # combined rows re-registered as 52-byte new-descriptor rows
        # every flush (the Zipf tail churning through the table) — 2.3x
        # the wire bytes and twice the device-step work of the 8-byte
        # known-row path. 2^21 slots hold the whole working set at load
        # factor 0.5: table HBM is 2^21 x 12 lanes x 4B = 100 MB/device,
        # and the id lane keeps 11 bits of packet headroom. Sizing
        # guidance: docs/operations.md.
        cfg.flow_dict_slots = 1 << 21
        # Full quanta before the age bound cuts them (0.4s default was
        # age-flushing at ~2.9M of the 4.2M quantum), and a deeper
        # in-flight window so a slow transfer drains queued work
        # instead of stalling the feed.
        cfg.flush_max_age_s = 0.8
        cfg.feed_pipeline_depth = 6
    # Sharded host feed: two workers so combine/partition overlap with
    # source parsing and dispatch even on this contended box (auto
    # sizing resolves to 1 on a 1-core harness, which would keep the
    # inline path the bench is meant to exercise).
    cfg.feed_workers = 2
    # The measurement windows wait for the background warm anyway, so
    # bias the duty-cycle scheduler toward finishing it (the 0.5
    # default is tuned for production fairness, not for a bench that
    # blocks on bucket_warm_done).
    cfg.warm_duty_cycle = 0.9
    cfg.bypass_lookup_ip_of_interest = True
    n_pods = 256 if smoke else 2048

    d = Daemon(cfg)
    for i in range(1, n_pods):
        d.cm.cache.update_endpoint(
            RetinaEndpoint(
                name=f"pod-{i}", namespace="default",
                ips=(f"10.0.{(i >> 8) & 0xFF}.{i & 0xFF}",),
            )
        )
    stop = threading.Event()
    t = threading.Thread(target=d.start, args=(stop,), daemon=True)
    t.start()
    log("e2e: agent booting (compile from persistent cache)")
    deadline = time.monotonic() + 300
    port = None
    while time.monotonic() < deadline:
        if d.cm.server is not None and d.cm.engine.started.is_set():
            try:
                port = d.cm.server.port
                break
            except AssertionError:
                pass
        time.sleep(0.2)
    if port is None:
        stop.set()
        raise RuntimeError("e2e: agent did not come up in 300s")
    log(f"e2e: agent up on :{port}; warmup {warmup:.0f}s")

    def scrape() -> tuple[float, str]:
        t0 = time.perf_counter()
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ).read().decode()
        return time.perf_counter() - t0, body

    eng = d.cm.engine
    m = get_metrics()
    # The measurement window must not open while the source is still
    # compiling/pre-generating (plugin compile runs after the server is
    # up; a cold XLA cache plus 2M-event pregen can take minutes): wait
    # for the first real traffic to reach the engine.
    tstart = time.monotonic()
    while eng._events_in == 0:
        if not t.is_alive():
            raise RuntimeError(
                "e2e: agent thread died during source startup"
            )
        if time.monotonic() - tstart > 300:
            stop.set()
            raise RuntimeError(
                "e2e: no traffic from the synthetic source within 300s"
            )
        time.sleep(0.5)
    log(f"e2e: first traffic after {time.monotonic() - tstart:.0f}s")
    # Steady state starts once the background bucket-grid warm is done:
    # its cold compiles serialize on the device proxy and would turn the
    # measure windows into compile stalls (the agent is READY and
    # serving throughout — this wait is about what the windows measure,
    # not about boot latency, which is reported above).
    bucket_warm_s, warm_incomplete = wait_bucket_warm(eng, 600)
    time.sleep(warmup)

    def _shed_counts() -> dict[str, float]:
        # Labeled counter: the parent has no _value; read the children
        # through collect() samples (stage -> cumulative count).
        out: dict[str, float] = {}
        for metric in m.events_shed.collect():
            for s in metric.samples:
                if s.name.endswith("_total"):
                    out[s.labels.get("stage", "")] = s.value
        return out

    def _feed_dropped() -> int:
        # Blocks the feed path dropped (staging saturated or handoff to
        # a dead consumer) — a per-window delta > 0 marks a window whose
        # missing events never reached the device at all.
        pool = eng._feed_pool
        if pool is None:
            return 0
        return pool.staging_dropped_blocks + sum(
            w.handoff_dropped for w in pool.workers
        )

    def measure_window() -> dict:
        ev0 = eng._events_in
        bytes0 = m.transfer_bytes._value.get()
        rb0 = m.readback_bytes._value.get()
        samp0 = m.events_sampled._value.get()
        shed0 = _shed_counts()
        xf0 = m.transfer_seconds._sum.get()
        defer0 = m.windows_deferred._value.get()
        drop0 = _feed_dropped()
        t0 = time.monotonic()
        lat: list[float] = []
        while time.monotonic() - t0 < dur:
            dt, _ = scrape()
            lat.append(dt)
            time.sleep(max(0.0, 1.0 - dt))
        elapsed = time.monotonic() - t0
        ev1 = eng._events_in  # one snapshot: rate/events/bpe consistent
        bytes1 = m.transfer_bytes._value.get()
        rb1 = m.readback_bytes._value.get()
        shed1 = _shed_counts()
        ov = eng.overload_stats()
        return {
            "rate": (ev1 - ev0) / elapsed,
            "wire_bytes": bytes1 - bytes0,
            "readback_bytes": rb1 - rb0,
            "events": ev1 - ev0,
            "elapsed": elapsed,
            "lat": lat,
            # Stall-attribution inputs: was the bucket-grid warm still
            # running, and what share of the window's wall clock the
            # proxy spent inside transfer RPCs.
            "warm_done": eng.bucket_warm_done.is_set(),
            "transfer_share": (
                (m.transfer_seconds._sum.get() - xf0) / elapsed
            ),
            # How many window closes the protected close lane deferred
            # (both slots in flight behind a stalled link) and how many
            # blocks the feed path dropped during THIS window — the two
            # attribution signals the r05 0.00M windows were missing.
            "windows_deferred": int(
                m.windows_deferred._value.get() - defer0
            ),
            "feed_dropped": _feed_dropped() - drop0,
            # Per-window overload diagnostics: what the adaptive
            # controller did to KEEP this window's event count nonzero
            # (docs/operations.md §6). events_sampled is the
            # Horvitz-Thompson-rescaled share, not loss.
            "overload_state": ov["state"],
            "sample_k": ov["sample_k"],
            "events_sampled": int(
                m.events_sampled._value.get() - samp0
            ),
            "events_shed": {
                k: int(v - shed0.get(k, 0.0))
                for k, v in shed1.items()
                if v - shed0.get(k, 0.0) > 0
            },
        }

    def _proxy_seconds() -> float:
        try:
            return (m.transfer_seconds._sum.get()
                    + m.device_step_seconds._sum.get())
        except Exception:
            return 0.0

    # Median of three windows; every window's rate is attached. The
    # reported scrape latencies and wire efficiency come from the
    # MEDIAN-rate window.
    proxy_s0 = _proxy_seconds()
    t_win0 = time.monotonic()
    windows = [measure_window() for _ in range(3)]
    # A window below 10% of the 10M north star is a stall: it fails the
    # default run's stall gate and carries an attributed cause. The
    # floor is ABSOLUTE: a relative-to-best rule was tried and
    # rejected — one anomalously fast window would reclassify every
    # typical window as "stalled". A merely-slow system sits above the
    # floor in every window and is reported as-is.
    STALL_FLOOR = 1e6

    def _stall_cause(w: dict) -> str | None:
        """Attribute one stalled (sub-floor) window to its most likely
        cause, in evidence order: bucket-grid warm still compiling in
        the background > overload controller actively degrading >
        transfer RPCs owning the window's wall clock > window closes
        deferring on the protected close lane (the link wedged with
        both close slots in flight) > the feed path dropping blocks
        (staging saturated); a stall none of these explains is
        "unattributed"."""
        if w["rate"] >= STALL_FLOOR:
            return None
        if not w["warm_done"]:
            return "warm"
        if w["overload_state"] != "NOMINAL":
            return f"overload:{w['overload_state']}"
        if w["transfer_share"] >= 0.5:
            return "transfer_stall"
        if w.get("windows_deferred", 0) > 0:
            return "close_backlog"
        if w.get("feed_dropped", 0) > 0:
            return "staging_saturated"
        return "unattributed"

    # Steady-state proxy occupancy over EXACTLY the measured span (the
    # whole-run sums would fold boot compiles and warm waits in).
    proxy_share = (_proxy_seconds() - proxy_s0) / max(
        time.monotonic() - t_win0, 1e-9
    )
    log("e2e: windows "
        + ", ".join(
            f"{w['rate'] / 1e6:.2f}M[{w['overload_state']}]"
            for w in windows
        ))
    win = sorted(windows, key=lambda w: w["rate"])[len(windows) // 2]
    n_stalled = sum(1 for w in windows if w["rate"] < STALL_FLOOR)
    rate = win["rate"]
    lat = win["lat"]
    ev_delta = win["events"]
    bytes_delta = win["wire_bytes"]
    _, body = scrape()
    # Feed-path backpressure readout BEFORE stop: pool workers join on
    # shutdown and their staged/fill gauges zero out.
    feed = eng.feed_stats()
    warm_failed = eng.bucket_warm_failed.is_set()
    stop.set()
    t.join(60)

    # Per-dispatch self-diagnostics: where a slow window's time went.
    from retina_tpu.parallel.telemetry import aot_disk_cache_stats

    aot = aot_disk_cache_stats()
    # Critical-path report (obs/recorder.py): per-stage span p50/p99
    # over the run's flight-recorder rings — which pipeline stage owns
    # a slow window's wall clock (docs/observability.md).
    from retina_tpu.obs.recorder import get_recorder

    stage_breakdown = get_recorder().stage_report()
    try:
        log("e2e: stage breakdown " + " ".join(
            f"{s}[n={v['count']} p50={v['p50_s'] * 1e3:.2f}ms "
            f"p99={v['p99_s'] * 1e3:.2f}ms]"
            for s, v in stage_breakdown.items()
        ))
    except Exception:
        pass
    try:
        xf_s = m.transfer_seconds._sum.get()
        xf_n = sum(b.get() for b in m.transfer_seconds._buckets)
        st_s = m.device_step_seconds._sum.get()
        per_w = feed.get("per_worker", [])
        log(
            f"e2e: aot disk cache hits={aot['hits']} "
            f"misses={aot['misses']} errors={aot['errors']} "
            f"dir={cfg.aot_cache_dir}"
        )
        log(
            f"e2e: diag transfers={xf_n:.0f} "
            f"avg_transfer={xf_s / max(xf_n, 1) * 1e3:.1f}ms "
            f"step_sum={st_s:.1f}s steps={eng._steps} "
            f"proxy_share={proxy_share:.2f} "
            f"fill={m.device_batch_fill._value.get():.3f} "
            f"events_in={eng._events_in} "
            f"feed_workers={feed.get('workers', 0)} "
            "worker_fill="
            f"{[w['fill'] for w in per_w]} "
            "handoff_wait_s="
            f"{[w['handoff_wait_s'] for w in per_w]} "
            f"feed_dropped_blocks={feed.get('dropped_blocks', 0)}"
        )
    except Exception:
        pass
    # Overload-controller diag: per-window state + what sampling/shed
    # did during the measured span (the adaptive controller's answer to
    # backpressure — windows keep closing nonzero instead of stalling).
    try:
        ov = eng.overload_stats()
        total_sampled = sum(w["events_sampled"] for w in windows)
        total_shed: dict[str, int] = {}
        for w in windows:
            for k, v in w["events_shed"].items():
                total_shed[k] = total_shed.get(k, 0) + v
        log(
            "e2e: overload diag "
            f"state={ov['state']} pressure={ov['pressure']} "
            f"sample_k={ov['sample_k']} shed={ov['shed']} "
            f"transitions={ov['transitions']} "
            f"window_states={[w['overload_state'] for w in windows]} "
            f"events_sampled={total_sampled} "
            f"events_shed={total_shed} "
            f"accuracy_debt={m.accuracy_debt._value.get():.0f}"
        )
    except Exception:
        pass
    lat.sort()
    p50 = lat[len(lat) // 2]
    p99 = lat[min(int(len(lat) * 0.99), len(lat) - 1)]
    wire_bpe = bytes_delta / max(ev_delta, 1)
    combine_ratio = m.combine_ratio._value.get()
    # Sanity: the exposition must carry the data-plane families.
    assert "networkobservability_forward_count" in body
    # Link utilization counts BOTH directions: H2D wire transfers and
    # D2H snapshot readbacks (scrape/GC/module cadence). Whether the two
    # directions share a bottleneck is unverified on the attached chip.
    link_used_mbs = (
        (bytes_delta + win["readback_bytes"]) / win["elapsed"] / 1e6
    )
    if link_used_mbs >= 0.5 * link_mbs:
        bottleneck = "host->device link bandwidth"
    elif proxy_share >= 0.5:
        # The proxy thread spends most of its wall clock inside device
        # calls (enqueue time, ROADMAP S1): per-dispatch round trips
        # gate the system.
        bottleneck = "device dispatch round-trip latency"
    else:
        # Wire underfed AND the proxy mostly idle: the stage probes run
        # faster in isolation than the full agent sustains because
        # source+feed+combine+assign+server all share the host cores.
        bottleneck = "host feed path (core contention)"
    res = {
        # HEADLINE: median over EVERY measured window, stalls included.
        "events_per_sec": round(rate),
        "device": device,
        "scrape_p50_ms": round(p50 * 1e3, 1),
        "scrape_p99_ms": round(p99 * 1e3, 1),
        "scrapes": len(lat),
        "duration_s": round(win["elapsed"], 1),
        "measure_windows": [round(w["rate"]) for w in windows],
        # Per-window overload accounting (runtime/overload.py): the
        # controller state the window closed under, its raw event
        # count, and the events the 1-in-k sampler dropped (device
        # HT-rescale re-synthesizes their weight — sampled+events
        # accounts for the raw arrival gap under backpressure, and
        # `events` must stay > 0 whenever the feed is live).
        "window_overload": [
            {
                "state": w["overload_state"],
                "sample_k": w["sample_k"],
                "events": int(w["events"]),
                "events_sampled": w["events_sampled"],
                "events_shed": w["events_shed"],
            }
            for w in windows
        ],
        # Windows below STALL_FLOOR. Every stalled window carries an
        # attributed cause (warm / overload:<state> / transfer_stall /
        # close_backlog / staging_saturated / unattributed) — never
        # silently re-measured or dropped from the median.
        "stalled_windows": n_stalled,
        "stall_causes": [c for c in map(_stall_cause, windows) if c],
        # Background warm: seconds from first traffic to full grid
        # residency (None = did not finish inside the 600s cap).
        "bucket_warm_s": (
            None if bucket_warm_s is None else round(bucket_warm_s, 1)
        ),
        # True when the 600s deadline expired with the warm still
        # running: bucket_warm_s is then elapsed-at-measure-start, not
        # time-to-residency, and the windows measured a warming system.
        "warm_incomplete": warm_incomplete,
        "bucket_warm_failed": warm_failed,
        # Flight-recorder critical path: per-stage span count/p50/p99
        # seconds over the run (obs/recorder.py stage_report).
        "stage_breakdown": stage_breakdown,
        # Sharded-feed backpressure accounting (engine.feed_stats):
        # per-worker quantum fill and handoff wait, plus blocks dropped
        # because every worker's staging was saturated.
        "feed": {
            "workers": feed.get("workers", 0),
            "worker_fill": [
                w["fill"] for w in feed.get("per_worker", [])
            ],
            "handoff_wait_s": [
                w["handoff_wait_s"] for w in feed.get("per_worker", [])
            ],
            "dropped_blocks": feed.get("dropped_blocks", 0),
        },
        "combine_ratio": round(combine_ratio, 2),
        "wire_bytes_per_event": round(wire_bpe, 2),
        "link_bandwidth_mbs": round(link_mbs, 1),
        "link_used_mbs": round(link_used_mbs, 2),
        "readback_bytes": int(win["readback_bytes"]),
        "bottleneck": bottleneck,
        "host_path_events_per_sec": round(host_path_rate),
        # AOT executable disk cache accounting (hits = programs loaded
        # pre-lowered from cfg.aot_cache_dir; misses = lowered+saved).
        "aot_cache": aot,
        "heavy_keys_source": cfg.heavy_keys_source,
        # What the measured wire efficiency implies on a production PCIe
        # host (~8 GB/s nominal): the link stops binding and the host
        # feed path (combine/pack/partition, measured above) becomes the
        # per-node ceiling.
        "projected_pcie_events_per_sec": round(
            min(8e9 / max(wire_bpe, 1e-9), host_path_rate)
        ),
    }
    log(f"e2e: {rate / 1e6:.2f}M ev/s sustained "
        f"({n_stalled} stalled windows), scrape p50 "
        f"{res['scrape_p50_ms']}ms p99 {res['scrape_p99_ms']}ms, "
        f"{wire_bpe:.1f} wire B/ev, link {link_mbs:.0f} MB/s")
    return res


def _run_device_phase_subprocess(smoke: bool) -> dict:
    """Run the device-step phase as `bench.py --no-e2e` in a child
    process and return its JSON line. A child that fails, times out or
    prints no result fails the run: there is no in-process fallback.

    One process per chip: the child owns the chip while it runs, so
    this parent must not have imported JAX yet (main() calls this
    before anything that does) and the child has exited before the
    parent's e2e phase touches the device."""
    import subprocess

    if "jax" in sys.modules:
        raise RuntimeError(
            "device-phase child would be started by a parent that has "
            "already imported JAX and may hold the chip"
        )
    cmd = [sys.executable, os.path.abspath(__file__), "--no-e2e"]
    if smoke:
        cmd.append("--smoke")
    log("device phase in subprocess: " + " ".join(cmd))
    # stderr inherits the parent's so stage progress streams live (a
    # non-smoke device phase can run many minutes; buffering it would
    # make a hang indistinguishable from progress).
    res = subprocess.run(
        cmd, stdout=subprocess.PIPE, text=True, timeout=1200,
        env={**os.environ, "RETINA_BENCH_CHILD": "1"},
    )
    for line in reversed((res.stdout or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                out = json.loads(line)
            except json.JSONDecodeError:
                continue
            if res.returncode == 0 and "error" not in out:
                return out
            raise RuntimeError(
                f"device-phase subprocess rc={res.returncode}: "
                f"{out.get('error', '')}"
            )
    raise RuntimeError(
        f"device-phase subprocess produced no JSON (rc={res.returncode})"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shapes, completes in <60s")
    ap.add_argument("--e2e", action="store_true",
                    help="full-system bench only (agent boot -> scrape)")
    ap.add_argument("--no-e2e", action="store_true",
                    help="skip the e2e phase of the default run")
    ap.add_argument("--perf", action="store_true",
                    help="agent-overhead regression harness (loopback "
                         "workload with vs without the live agent)")
    ap.add_argument("--fleet-dryrun", action="store_true",
                    help="multi-agent fleet rollup dryrun: simulated "
                         "node agents ship sketch snapshots to one "
                         "aggregator; one is killed mid-run")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write the flight recorder's Chrome trace-"
                         "event JSON (Perfetto-loadable) here after "
                         "the run")
    ap.add_argument("--fleet-agents", type=int, default=8,
                    help="number of simulated node agents for "
                         "--fleet-dryrun (default 8; the slow-tier "
                         "test runs 100)")
    ap.add_argument("--invertible-dryrun", action="store_true",
                    help="cluster key-recovery dryrun: nodes ship "
                         "counter-only frames (no raw keys) and the "
                         "aggregator decodes heavy-flow keys from the "
                         "merged invertible sketch, through a forced "
                         "SHEDDING episode")
    ap.add_argument("--soak", action="store_true",
                    help="endurance soak: boot the live agent and walk "
                         "a rotating schedule of heavy-tail traffic "
                         "regimes + injected faults while leak "
                         "sentinels sample every window; writes a "
                         "SOAK_*.json scorecard (with --smoke: 2 "
                         "phases + 1 fault, <=90s for CI)")
    ap.add_argument("--soak-seconds", type=float, default=None,
                    metavar="S",
                    help="wall-clock budget for --soak (default: 60 "
                         "with --smoke, else cfg.soak_seconds = 1800)")
    ap.add_argument("--query-dryrun", action="store_true",
                    help="time-travel closed-loop dryrun: an entropy "
                         "burst is detected, the query ring is folded "
                         "over [W-2, W+2), burst sources are attributed "
                         "via invertible decode, and a targeted capture "
                         "artifact is produced — while concurrent "
                         "scrapes (half under forced SHEDDING) hammer "
                         "the query API")
    ap.add_argument("--churn-dryrun", action="store_true",
                    help="multi-process churn dryrun: >=64 real node-"
                         "agent child processes ship RFLT frames over "
                         "real gRPC relays into a two-level zone->root "
                         "rollup, through rolling restarts, asymmetric "
                         "partitions, and a live seed rotation (with "
                         "--smoke: 12 processes, 3 zones)")
    ap.add_argument("--churn-nodes", type=int, default=None,
                    help="child process count for --churn-dryrun "
                         "(default 64, or 12 with --smoke)")
    ap.add_argument("--churn-zones", type=int, default=None,
                    help="zone relay count for --churn-dryrun "
                         "(default 4, or 3 with --smoke)")
    ap.add_argument("--fleetquery-dryrun", action="store_true",
                    help="fleet query plane + detector diversity "
                         "dryrun: a 1,000-query storm over 64 simulated "
                         "nodes (10% killed mid-storm, final stretch "
                         "under SHEDDING) must hold p99 <= 100ms with "
                         "explicit partial coverage, AND each builtin "
                         "detector (synflood/portscan/dnstunnel) must "
                         "fire only on its matching regime and drive "
                         "the closed capture loop at recall >= 0.95 "
                         "(with --smoke: 8 nodes, 200 queries)")
    args = ap.parse_args()
    try:
        if args.soak:
            from retina_tpu.soak import run_soak

            res = run_soak(
                total_s=args.soak_seconds, smoke=args.smoke, log=log,
            )
            n_ok = sum(1 for v in res["sentinels"].values() if v["ok"])
            out = {
                # Acceptance: every leak/degradation sentinel green
                # across the full regime+fault rotation. The headline
                # is the sentinel pass fraction so a partial failure
                # is visible even before reading the artifact.
                "metric": "soak_sentinels_green",
                "value": n_ok,
                "unit": "sentinels",
                "vs_baseline": round(n_ok / len(res["sentinels"]), 4),
                "extra": res,
            }
            if not res["ok"]:
                bad = [k for k, v in res["sentinels"].items()
                       if not v["ok"]]
                out["error"] = f"soak sentinels failed: {bad}"
        elif args.churn_dryrun:
            from retina_tpu.fleet.churn import run_churn_dryrun

            # The window interval must leave every child enough CPU to
            # build its sketch pass each epoch (~50ms/child measured on
            # one core) — on a big host the full run holds the 1.0s
            # headline cadence, on a starved CI box it stretches so the
            # fleet stays epoch-aligned instead of collapsing into a
            # merge backlog that drains after the scored window.
            churn_nodes = args.churn_nodes or (12 if args.smoke else 64)
            churn_interval = (0.6 if args.smoke else max(
                1.0, 0.08 * churn_nodes / (os.cpu_count() or 1)
            ))
            res = run_churn_dryrun(
                nodes=churn_nodes,
                zones=args.churn_zones or (3 if args.smoke else 4),
                interval_s=churn_interval,
                log=log,
            )
            out = {
                # Acceptance: root-tier recall >= 0.95 through 10%
                # rolling churn + partitions + a live seed rotation,
                # with spooled frames replayed (no silent loss), every
                # node re-admitted post-rotation, and three-tier trace
                # lineage intact.
                "metric": "churn_root_recall",
                "value": res["recall_min"],
                "unit": "recall",
                "vs_baseline": round(res["recall_min"] / 0.95, 4),
                "extra": res,
            }
            if not res["ok"]:
                gates = {
                    "recall": res["recall_min"] >= 0.95,
                    "replay": (res["child_spool_replayed"] > 0
                               and res["reship_spool_replayed"] > 0),
                    "no_silent_loss": res["no_silent_frame_loss"],
                    "rotation": res["rotation_readmitted_all"],
                    "lineage": res["trace_lineage_ok"],
                    "epochs": res["epochs_scored"] >= 8,
                }
                bad = [g for g, okg in gates.items() if not okg]
                out["error"] = f"churn dryrun acceptance failed: {bad}"
        elif args.fleetquery_dryrun:
            from retina_tpu.fleetquery.dryrun import run_fleetquery_dryrun

            res = run_fleetquery_dryrun(
                nodes=8 if args.smoke else 64,
                storm_threads=4 if args.smoke else 8,
                storm_requests=50 if args.smoke else 125,
                log=log,
            )
            n_ok = sum(1 for v in res["checks"].values() if v)
            out = {
                # Acceptance: every storm gate (p99, coverage, hedging,
                # no 5xx besides explicit busy) AND every detector
                # closed-loop gate (fire/arbitrate/recall/capture, zero
                # benign firings) green. Headline = check pass
                # fraction so partial failures are visible up front.
                "metric": "fleetquery_checks_green",
                "value": n_ok,
                "unit": "checks",
                "vs_baseline": round(n_ok / len(res["checks"]), 4),
                "extra": res,
            }
            if not res["ok"]:
                bad = [k for k, v in res["checks"].items() if not v]
                out["error"] = f"fleetquery dryrun failed: {bad}"
        elif args.query_dryrun:
            from retina_tpu.timetravel.dryrun import run_query_dryrun

            res = run_query_dryrun(log=log)
            out = {
                # Acceptance: the whole detection -> attribution ->
                # evidence arc, with decode recall >= 0.95 against the
                # exact attack key set and query p99 bounded while the
                # feed runs at full rate.
                "metric": "timetravel_decode_recall",
                "value": res["recall"],
                "unit": "recall",
                "vs_baseline": round(res["recall"] / 0.95, 4),
                "extra": res,
            }
            if not res["ok"]:
                out["error"] = "query dryrun acceptance failed"
        elif args.invertible_dryrun:
            from retina_tpu.fleet.dryrun import run_invertible_dryrun

            res = run_invertible_dryrun(
                nodes=4 if args.smoke else 6,
                epochs=2 if args.smoke else 4,
                log=log,
            )
            out = {
                # Acceptance: keys recovered FROM SKETCH STATE must
                # cover >= 95% of the exact heavy set, with priority
                # tenants at full recall through the shedding episode.
                "metric": "invertible_key_recall",
                "value": res["recall_min"],
                "unit": "recall",
                "vs_baseline": round(res["recall_min"] / 0.95, 4),
                "extra": res,
            }
            if not res["ok"]:
                out["error"] = "invertible dryrun acceptance failed"
        elif args.fleet_dryrun:
            from retina_tpu.fleet.dryrun import run_dryrun

            res = run_dryrun(
                nodes=args.fleet_agents,
                epochs=3 if args.smoke else 6,
                kill_after=1 if args.smoke else 3,
                log=log,
            )
            out = {
                # North star: cluster top-k recall vs exact merged
                # counts must hold at >= 0.95 THROUGH a node dropout.
                "metric": "fleet_topk_recall",
                "value": res["recall_min"],
                "unit": "recall",
                "vs_baseline": round(res["recall_min"] / 0.95, 4),
                "extra": res,
            }
            if not res["ok"]:
                out["error"] = "fleet dryrun acceptance failed"
        elif args.perf:
            from retina_tpu.e2e.perf import (
                default_agent_factory, run_regression,
            )

            enable_caches()
            res = run_regression(
                duration_s=5.0 if args.smoke else 15.0,
                agent_factory=default_agent_factory,
            )
            reg = res.get("regression", {})
            out = {
                "metric": "agent_throughput_regression_pct",
                "value": reg.get("throughput_pct", 0.0),
                "unit": "percent",
                # North star is "minimal overhead"; report vs a 5%
                # budget like the reference's regression gate.
                "vs_baseline": round(
                    reg.get("throughput_pct", 0.0) / 5.0, 4
                ),
                "extra": res,
            }
        elif args.e2e:
            e2e = run_e2e(args.smoke)
            out = {
                "metric": "flow_events_per_sec_e2e",
                "value": e2e["events_per_sec"],
                "unit": "events/s",
                "vs_baseline": round(e2e["events_per_sec"] / 10_000_000, 4),
                "device": e2e["device"],
                "extra": e2e,
            }
        elif args.no_e2e or os.environ.get("RETINA_BENCH_CHILD"):
            # Device phase only — this is also what the subprocess
            # child below runs, so it must never spawn again.
            if not args.no_e2e:
                log("RETINA_BENCH_CHILD is set: skipping the e2e phase "
                    "(unset it for the combined run)")
            out = run(args.smoke)
        else:
            # Device phase in a SUBPROCESS, before this process imports
            # JAX: a chip belongs to one process at a time, so the
            # child runs and exits first and only then does the e2e
            # phase below take the chip (_run_device_phase_subprocess
            # checks the order). Either phase failing fails the run.
            device = _run_device_phase_subprocess(args.smoke)
            # HEADLINE = the end-to-end system number (the north-star
            # claim, BASELINE.md); the device-step rate rides along in
            # extra.device_step. Shorter windows than standalone --e2e
            # keep the combined run's wall clock bounded for the driver.
            e2e = run_e2e(
                args.smoke, duration_s=4.0 if args.smoke else 12.0
            )
            out = {
                "metric": "flow_events_per_sec_e2e",
                "value": e2e["events_per_sec"],
                "unit": "events/s",
                "vs_baseline": round(
                    e2e["events_per_sec"] / 10_000_000, 4
                ),
                "device": e2e["device"],
                "extra": {"e2e": e2e, "device_step": device},
            }
            # Stall gate (default run only): the acceptance target is
            # a median with zero stall windows — a run with a stalled
            # window fails loudly, with every window's attributed
            # cause in the error line.
            n_st = e2e.get("stalled_windows", 0)
            if n_st:
                out["error"] = (
                    f"stall gate: {n_st} stalled window(s), "
                    f"causes={e2e.get('stall_causes', [])}"
                )
    except Exception as e:  # noqa: BLE001 — always emit the JSON line
        log("FAILED:\n" + traceback.format_exc())
        # No metric name and no value: a failed run files nothing.
        out = {
            "ok": False,
            "error": f"{type(e).__name__}: {e}".splitlines()[0][:400],
        }
    if args.trace:
        # Trace artifact: every span the in-process recorder retained
        # (the e2e agent runs in THIS process; the device phase child
        # keeps its own rings and is not included).
        try:
            from retina_tpu.obs.recorder import get_recorder

            with open(args.trace, "w") as f:
                json.dump(get_recorder().chrome_trace(), f)
            log(f"trace artifact written to {args.trace}")
        except Exception:  # noqa: BLE001 — artifact is best-effort, never the exit code
            log("trace artifact FAILED:\n" + traceback.format_exc())
    print(json.dumps(out), flush=True)
    # Skip interpreter teardown on BOTH paths: daemon threads (device
    # proxy, watchers) may sit inside runtime calls, and tearing the
    # accelerator client down under them has aborted a process AFTER
    # its result line (pthread-cancel + C++ unwind -> std::terminate;
    # unverified on the attached chip). The JSON above is flushed; exit
    # codes must reflect the bench, not teardown ordering.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(1 if "error" in out else 0)


if __name__ == "__main__":
    main()
