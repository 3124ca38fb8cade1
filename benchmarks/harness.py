"""One boot of the agent, and paced loads offered to it.

``Bench`` is everything between the device check and the result line:
set-up (boot, endpoints, warm, the pool from the seed, the poller), one
or more loads (warm-up then a measured window, open loop), the settled
scrape and the comparison. ``run.py`` offers one load and prints the
contract's line; ``ladder.py`` offers a ladder of rates after one boot.

The harness knows no cell, configuration, traffic mix or per-layer
metric by name: each is a file found by the name ``BENCHMARK.json``
gives.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import resource
import shutil
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(HERE, "layer_metrics"), HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import measure  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
from agent import (  # noqa: E402
    Agent, BenchFailure, CompileLog, build_config, http_get,
    register_source, wait_for,
)

TRACE_START_S = 2.0  # into the window
TRACE_SECONDS = 5.0
# Published by the conntrack plugin's accounting pass, every 15 s.
CONNTRACK_GAUGE = "conntrack_packets"


def log(**obj) -> None:
    """Progress and set-up facts, one JSON object per line on stderr."""
    print(json.dumps(obj, default=str), file=sys.stderr, flush=True)


# -- what BENCHMARK.json names ------------------------------------------
def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench: dict, workload: str) -> tuple[dict, dict]:
    """(the cell, its configuration's file as a dict)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return cell, json.load(f)


def metrics_of(bench: dict, group: str, workload: str) -> list[dict]:
    """The metrics of one group that this cell reports."""
    return [m for m in bench[group]
            if workload in m.get("workloads", [workload])]


def load_reader(name: str):
    """A per-layer metric is a module of its own, found by its name."""
    path = os.path.join(HERE, "layer_metrics", f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"per-layer metric {name!r} has no reader at "
                         f"benchmarks/layer_metrics/{name}.py")
    return importlib.import_module(name)


# -- the poller ------------------------------------------------------------
class Poller:
    """The scraping child process and its log."""

    def __init__(self, port: int, interval_s: float, out: str,
                 counters: list[str]):
        self.out = out
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "poller.py"),
             "--port", str(port), "--interval", str(interval_s),
             "--out", out, "--counters", ",".join(counters)],
            stdin=subprocess.DEVNULL,
        )

    def rows(self) -> list[dict]:
        out = []
        if os.path.exists(self.out):
            with open(self.out) as f:
                for line in f:
                    if line.endswith("\n"):
                        out.append(json.loads(line))
        return out

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(35.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


# -- one load ----------------------------------------------------------------
@dataclasses.dataclass
class Load:
    """What one paced load measured; what the per-layer readers read."""

    config: dict
    device_kind: str
    sched: traffic.Schedule
    rate: int
    base_rows: int  # offered before this load, since boot
    t_start: float
    t_open: float
    t_close: float
    cpu_s: float  # user+system of this process over the window
    block_late: list[tuple[int, float, int]]
    scrapes: list[dict]
    counters_before: dict
    counters_after: dict
    compiles_in_window: list[dict]
    programs_regrown: dict
    accepted_rows: int  # by the source, since boot, after the settle
    settle_s: float
    trace: object = None

    @property
    def total_rows(self) -> int:
        """Rows of this load, warm-up and window."""
        return self.sched.total_rows

    @property
    def offered_since_boot(self) -> int:
        return self.base_rows + self.sched.total_rows

    @property
    def window_block_late_s(self) -> list[float]:
        return [s for tick, s, _ in self.block_late
                if tick >= self.sched.warm_ticks]

    def counter_delta(self, name: str) -> float:
        return self.counters_after.get(name, 0.0) \
            - self.counters_before.get(name, 0.0)

    def _scrape_tuples(self) -> list[tuple[float, float, int]]:
        return [(s["sent"], s["done"], s["events"])
                for s in self.scrapes if s["ok"]]

    def _ticks(self, first: int) -> list[tuple[float, int]]:
        rpt = self.sched.rows_per_tick
        return [(self.t_start + i * self.sched.tick_s,
                 self.base_rows + (i + 1) * rpt)
                for i in range(first, self.sched.n_ticks)]

    def freshness_s(self) -> list[float]:
        """Per tick of the window, due time to first scrape showing it."""
        return measure.freshness_s(self._ticks(self.sched.warm_ticks),
                                   self._scrape_tuples())

    def staleness_s(self) -> list[float]:
        """Per scrape sent in the window, how far behind the load it is
        (warm-up ticks still unseen count: they are load)."""
        inside = [s for s in self._scrape_tuples()
                  if self.t_open <= s[0] < self.t_close]
        return measure.staleness_s(self._ticks(0), inside)

    def round_trips_s(self) -> list[float]:
        every = [(s["sent"], s["done"], s["events"]) for s in self.scrapes]
        return measure.round_trips_s(every, self.t_open, self.t_close)

    def end_to_end(self, setup_s: float) -> dict[str, float]:
        trips = self.round_trips_s()
        return {
            "scrape_p95_ms": measure.percentile(trips, 95) * 1e3
            if trips else float("inf"),
            "host_cpu_us_per_event":
                self.cpu_s * 1e6 / max(self.sched.window_rows, 1),
            "setup_s": setup_s,
        }


class Bench:
    def __init__(self, config: dict, mix: traffic.Mix, seed: int,
                 workload: str, rehearse: bool, counters: list[str],
                 t0: float):
        self.config, self.mix, self.seed = config, mix, seed
        self.rehearse = rehearse
        # Limits of the numbers compared that are not exact.
        self.held = {**config["held"],
                     **(config.get("rehearse_held", {}) if rehearse else {})}
        self.counters = counters
        self.t0 = t0
        self.agent: Agent | None = None
        self.poller: Poller | None = None
        self.pool = None
        self.pos = 0  # where the walk through the pool stands
        self.offered_rows = 0  # since boot
        self.clog = CompileLog()
        # How long past the close an answer is waited for.
        self.settle_deadline_s = 60.0
        from retina_tpu.config import CHECKOUT_CACHE_DIR

        self.work = os.path.join(CHECKOUT_CACHE_DIR, "bench", workload)
        self.trace_dir = os.path.join(self.work, "trace")

    # -- set-up ---------------------------------------------------------
    def setup(self, device: dict) -> None:
        from retina_tpu import native
        from retina_tpu.config import enable_harness_caches
        from retina_tpu.log import setup_logger

        self.device = device
        # The pool needs only numpy: it is made beside the boot.
        pool_box: dict = {}

        def make() -> None:
            t = time.monotonic()
            pool_box["pool"] = traffic.make_pool(self.mix, self.seed)
            pool_box["s"] = time.monotonic() - t

        pool_thread = threading.Thread(target=make, name="pool")
        pool_thread.start()
        setup_logger("info")
        xla_dir, aot_dir = enable_harness_caches()
        # This run's own checkpoint and config: a checkpoint left by an
        # earlier run would be resumed, counters and all.
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work, exist_ok=True)
        had_so = os.path.exists(native._so_path)
        if not native.native_available():
            raise BenchFailure("native library unavailable: the agent "
                               "would run its Python fallback")
        log(phase="caches", xla=xla_dir, aot=aot_dir, work=self.work,
            native="loaded" if had_so else "built",
            t=round(time.monotonic() - self.t0, 2))
        self.clog.install()
        register_source()
        cfg = build_config(self.config, self.work, aot_dir, xla_dir,
                           self.rehearse)
        self.cfg = cfg
        self.agent = Agent(cfg, self.mix.n_endpoints,
                           ready_deadline_s=700.0, warm_deadline_s=400.0)
        ready_s = self.agent.wait_ready()
        log(phase="ready", ready_s=round(ready_s, 2),
            t=round(time.monotonic() - self.t0, 2))
        self.agent.wait_tables()
        warm = self.agent.wait_warm()
        pool_thread.join()
        self.pool = pool_box["pool"]
        log(phase="warm", **warm, pool_s=round(pool_box["s"], 2),
            compiled=len(self.clog.records),
            compile_s=round(sum(r["seconds"] for r in self.clog.records), 1),
            t=round(time.monotonic() - self.t0, 2))
        self.poller = Poller(self.agent.port, self.mix.poll_interval_s,
                             os.path.join(self.work, "scrapes.jsonl"),
                             sorted({*self.counters, CONNTRACK_GAUGE}))
        # Its first scrape is the boot-warmed render; wait for one.
        wait_for("poller's first scrape", lambda: self.poller.rows(), 60.0,
                 0.05, lambda: self.poller.proc.poll() is None)

    # -- one load -------------------------------------------------------
    def offer(self, mix: traffic.Mix, seconds: float,
              trace: bool = False) -> Load:
        """Offer ``mix``'s load (the cell's own, or a rung of the ladder
        with another rate or cadence) from where the walk stands."""
        agent, rate = self.agent, mix.rate_events_per_s
        sched = traffic.Schedule.of(mix, self.cfg.window_seconds, seconds)
        src = agent.source
        pacer = traffic.Pacer(self.pool, sched, mix.block_rows,
                              src.inbox.put, rate, self.pos)
        pacer.start()
        before = self.poller.rows()[-1]["c"]
        base = self.offered_rows
        t_start = time.monotonic() + 0.25
        pacer.begin(t_start)
        t_open, t_close = pacer.due(sched.warm_ticks), pacer.due(sched.n_ticks)
        _sleep_until(t_open)
        ru0 = resource.getrusage(resource.RUSAGE_SELF)
        programs0 = agent.program_counts()
        trace_window_s = 0.0
        if trace:
            trace_window_s = self._trace(t_open)
        _sleep_until(t_close)
        ru1 = resource.getrusage(resource.RUSAGE_SELF)
        programs1 = agent.program_counts()
        pacer.join(30.0)
        if pacer.is_alive() or pacer.error is not None:
            raise BenchFailure(f"pacer did not finish: {pacer.error!r}")
        self.pos = pacer.pos
        self.offered_rows = base + sched.total_rows
        settle_s = self._settle(self.offered_rows)
        rows = self.poller.rows()
        load = Load(
            config=self.config, device_kind=self.device["kind"],
            sched=sched, rate=rate, base_rows=base, t_start=t_start,
            t_open=t_open, t_close=t_close,
            cpu_s=(ru1.ru_utime + ru1.ru_stime) - (ru0.ru_utime
                                                   + ru0.ru_stime),
            block_late=pacer.late,
            scrapes=[r for r in rows if r["sent"] >= t_start - 1.0],
            counters_before=before, counters_after=rows[-1]["c"],
            compiles_in_window=self.clog.between(t_open, t_close),
            programs_regrown={k: (programs0[k], programs1[k])
                              for k in programs0
                              if programs1[k] != programs0[k]},
            accepted_rows=src.accepted, settle_s=settle_s,
        )
        if trace:
            load.trace = self._reduce_trace(trace_window_s)
        return load

    def _trace(self, t_open: float) -> float:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        _sleep_until(t_open + TRACE_START_S)
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        t_on = time.monotonic()
        _sleep_until(t_on + TRACE_SECONDS)
        window_s = time.monotonic() - t_on
        jax.profiler.stop_trace()
        return window_s

    def _reduce_trace(self, window_s: float):
        import trace_reduce

        path = trace_reduce.find_xplane(self.trace_dir)
        if path is None:
            raise BenchFailure("the profiler wrote no .xplane.pb")
        return trace_reduce.reduce(path, window_s)

    def _settle(self, want_rows: int) -> float:
        """Wait until the source has handed everything on, every window
        with events is closed and harvested, and a scrape of the poller
        shows every offered event and, where the configuration holds
        conntrack's accounting, that too at its limit (its pass runs
        every 15 s): up to a minute past the close. An answer that
        comes late is late, and the staleness counts it."""
        agent, src = self.agent, self.agent.source
        ct_min = want_rows * self.held.get(
            "conntrack_packets_share_min", 0.0)
        t0 = time.monotonic()

        def done() -> bool:
            if src.offered < want_rows or not agent.settled():
                return False
            rows = self.poller.rows()
            return bool(rows) and rows[-1]["events"] >= want_rows \
                and rows[-1]["c"][CONNTRACK_GAUGE] >= ct_min

        try:
            wait_for("settle", done, self.settle_deadline_s, 0.1, agent.alive)
        except BenchFailure as e:
            log(phase="settle", error=str(e))
        return time.monotonic() - t0

    # -- after the window -------------------------------------------------
    def final_scrape(self) -> tuple[reference.Scrape, dict]:
        code, body = http_get(self.agent.port, "/metrics")
        if code != 200:
            raise BenchFailure(f"/metrics answered {code}")
        code, dv = http_get(self.agent.port, "/debug/vars")
        return reference.Scrape(body.decode()), \
            (json.loads(dv) if code == 200 else {})

    def memory_peak_bytes(self) -> int:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        return int(max(peaks))

    def judge(self, load: Load, scrape: reference.Scrape, dvars: dict,
              on_tpu: bool) -> reference.Verdict:
        """Every number compared, beside its limit."""
        v = reference.Verdict()
        v.hold("platform_is_tpu", int(on_tpu), 1, ">=")
        reference.compare(scrape, self.pool, load.offered_since_boot,
                          self.mix.n_endpoints, self.held, v)
        v.hold("events_not_accepted",
               load.offered_since_boot - load.accepted_rows, 0)
        bad = reference.health_nonzero(scrape)
        v.hold("health_series_nonzero", len(bad), 0)
        ov = dvars.get("overload", {})
        # No answer is no proof: a missing var reads as a transition.
        v.hold("overload_transitions", ov.get("transitions", 1), 0)
        v.hold("compiles_in_window",
               len(load.compiles_in_window) + len(load.programs_regrown), 0)
        fresh = load.freshness_s()
        v.hold("ticks_never_visible",
               sum(f == float("inf") for f in fresh), 0)
        v.notes.update(health_nonzero=bad, overload_state=ov.get("state"),
                       compiles=load.compiles_in_window,
                       regrown=load.programs_regrown)
        return v

    def close(self) -> None:
        if self.poller is not None:
            self.poller.stop()
        if self.agent is not None:
            self.agent.shutdown()


def _sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)
