"""Host spans beside device events: who the chip was waiting for, and
which operator owns the step's device time.

The program's flight recorder writes every span as a ``retina:<stage>``
annotation into the host plane of the profiler's trace, on the same
clock as the device events (``retina_tpu/obs/recorder.py``), and the
device proxy marks its own thread the same way: ``retina:proxy_idle``
round its blocking wait, ``retina:proxy_run`` with a ``kind`` round
every call. This module opens the ``.xplane.pb`` the harness already
wrote and reads both sides:

- **Idle attribution.** Every idle gap of chip 0 (the complement of the
  union of its operations over the traced span) is cut by the proxy
  thread's state: ``proxy_run:<kind>`` while a call ran, and inside
  ``proxy_idle`` by the feed-side span open at the time (``feed_fill``,
  ``combine``, ``wire_build``, ``staging_handoff``,
  ``transfer_enqueue``), or ``no_work`` where none was: nothing in
  hand for the chip. What neither covers is ``unlabelled``.
- **Operator split.** A device event carries its HLO line but not its
  ``op_name`` (read on the v5e: its stats are an offset, a duration and
  a time scale), so the program keeps a map from each of its
  programs' instruction names to ``jax.named_scope`` scopes, read here
  in-process (``telemetry.op_scope_map()``); a fusion belongs to the
  scope of its root. Device time per scope is the union of the
  intervals of the scope's operations, so nested events (a loop and
  its body) count once.

Spans outside the traced seconds are read from the in-process recorder
(``get_recorder().spans()``): ``time.perf_counter`` and
``time.monotonic`` are one clock on Linux, which the harness's window
times are on. Where the program has no such annotation, span or map
(a parent commit), every function here returns nothing and raises
nothing, and the metric is left out of the line.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = "/device:TPU:0"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
PREFIX = "retina:"
FEED_SIDE = ("feed_fill", "combine", "wire_build", "staging_handoff",
             "transfer_enqueue")
NO_WORK = "no_work"
UNLABELLED = "unlabelled"

_INSTR = re.compile(r"^%?([\w.\-]+) = ")

Interval = tuple[float, float]


# -- interval arithmetic ---------------------------------------------------
def union(intervals: list[Interval]) -> list[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals: list[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs: list[Interval], ys: list[Interval]) -> list[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs: list[Interval], ys: list[Interval]) -> list[Interval]:
    """What of ``xs`` no interval of ``ys`` covers (both sorted,
    disjoint)."""
    out = []
    starts = [y[0] for y in ys]
    for a, b in xs:
        k = max(0, bisect.bisect_right(starts, a) - 1)
        while k < len(ys) and ys[k][0] < b:
            if ys[k][1] > a:
                if ys[k][0] > a:
                    out.append((a, ys[k][0]))
                a = max(a, ys[k][1])
            k += 1
        if b > a:
            out.append((a, b))
    return out


# -- the attribution, on plain lists ---------------------------------------
def attribute(span: Interval, device_ops: list[Interval],
              proxy: list[tuple[str, float, float]],
              feed: list[tuple[str, float, float]]) -> dict[str, float]:
    """Seconds of every idle gap of the chip inside ``span``, by label.

    ``device_ops``: intervals in which an operation ran. ``proxy``:
    (state, start, end) of the proxy thread, state ``proxy_idle`` or
    ``proxy_run:<kind>``. ``feed``: (stage, start, end) of the
    feed-side spans, any thread. All in nanoseconds of one clock; the
    result is in seconds. Where two feed-side spans are open at once
    the gap goes to the one named first in ``FEED_SIDE``."""
    idle = subtract([span], union(device_ops))
    out: dict[str, float] = {}

    def give(label: str, part: list[Interval]) -> None:
        if part:
            out[label] = out.get(label, 0.0) + length(part) / 1e9

    states: dict[str, list[Interval]] = {}
    for state, a, b in proxy:
        states.setdefault(state, []).append((a, b))
    rest = idle
    proxy_idle = intersect(idle, union(states.pop("proxy_idle", [])))
    for state in sorted(states):
        part = intersect(rest, union(states[state]))
        give(state, part)
        rest = subtract(rest, part)
    proxy_idle = intersect(proxy_idle, rest)
    rest = subtract(rest, proxy_idle)
    for stage in FEED_SIDE:
        open_ = union([(a, b) for s, a, b in feed if s == stage])
        part = intersect(proxy_idle, open_)
        give(stage, part)
        proxy_idle = subtract(proxy_idle, part)
    give(NO_WORK, proxy_idle)
    give(UNLABELLED, rest)
    return out


def host_bound_s(table: dict[str, float]) -> float:
    """Idle seconds in which the program had work in hand for the chip:
    a proxy call running, or a feed-side span open."""
    return sum(s for label, s in table.items()
               if label not in (NO_WORK, UNLABELLED))


def scope_seconds(module: Interval, ops: list[tuple[str, float, float]],
                  scopes: dict[str, str]) -> dict[str, float]:
    """Device seconds of one execution of a program, by scope: for each
    scope the union of the intervals of its operations inside
    ``module``; ``""`` is what runs outside every scope."""
    inside = [(n, a, b) for n, a, b in ops
              if a >= module[0] and b <= module[1]]
    by: dict[str, list[Interval]] = {}
    for name, a, b in inside:
        scope = scopes.get(name)
        if scope:
            by.setdefault(scope, []).append((a, b))
    out = {s: length(union(v)) / 1e9 for s, v in by.items()}
    every = union([(a, b) for _, a, b in inside])
    scoped = union([i for v in by.values() for i in v])
    out[""] = length(subtract(every, scoped)) / 1e9
    return out


# -- reading the trace -------------------------------------------------------
def instruction(event_name: str) -> str:
    """``%fusion.46 = u32[…] fusion(…)`` → ``fusion.46``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def xplane_of(config_name: str) -> str | None:
    """The trace this run's harness wrote. The harness works in
    ``bench/<configuration>.<traffic>/`` and empties its ``trace``
    directory before it traces; a ``Load`` names its configuration but
    not its traffic, so of that configuration's cells the newest."""
    try:
        from retina_tpu.config import CHECKOUT_CACHE_DIR
    except ImportError:
        return None
    found = glob.glob(os.path.join(
        CHECKOUT_CACHE_DIR, "bench", glob.escape(config_name) + ".*",
        "trace", "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def program_scope_map() -> dict[str, dict[str, str]]:
    """``{program: {instruction: scope}}`` of the programs this process
    obtained; nothing from a program that keeps no such map."""
    try:
        from retina_tpu.parallel.telemetry import op_scope_map
    except ImportError:
        return {}
    return op_scope_map()


class HostTrace:
    """What one ``.xplane.pb`` holds of chip 0 and of the program's
    annotations, on the profiler's clock (nanoseconds)."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        self.path = path
        self.modules: list[tuple[str, float, float]] = []
        self.ops: list[tuple[str, float, float]] = []
        self.proxy: list[tuple[str, float, float]] = []
        self.spans: list[tuple[str, float, float]] = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name == DEVICE_PLANE:
                for line in plane.lines:
                    if line.name == MODULES_LINE:
                        self.modules = [
                            (re.sub(r"\(\d+\)$", "", e.name).strip(),
                             e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                    elif line.name == OPS_LINE:
                        self.ops = [
                            (instruction(e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                            for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(PREFIX):
                            self._host_event(e)
        # The traced span is what the events cover: the proxy thread
        # alone marks every 50 ms of it. (The session's own start and
        # stop times lie 0.3 s further apart on the v5e host: starting
        # and stopping the profiler, in which nothing is recorded.)
        every = self.modules + self.ops + self.proxy + self.spans
        self.span: Interval = (
            min((a for _, a, _ in every), default=0.0),
            max((b for _, _, b in every), default=0.0),
        )

    def _host_event(self, e) -> None:
        name = e.name[len(PREFIX):]
        a, b = e.start_ns, e.start_ns + e.duration_ns
        if name == "proxy_idle":
            self.proxy.append((name, a, b))
        elif name == "proxy_run":
            kind = dict(e.stats).get("kind", "other")
            self.proxy.append((f"proxy_run:{kind}", a, b))
        else:
            self.spans.append((name, a, b))

    def idle_attribution(self) -> dict[str, float]:
        return attribute(self.span, [(a, b) for _, a, b in self.ops],
                         self.proxy, self.spans)

    def step_scopes(self, step_program: str,
                    scope_map: dict[str, dict[str, str]],
                    ) -> tuple[int, dict[str, float]]:
        """(executions, mean device seconds per execution by scope) of
        the programs whose name matches ``step_program``."""
        rx = re.compile(step_program)
        total: dict[str, float] = {}
        n = 0
        for name, a, b in self.modules:
            if not rx.search(name):
                continue
            n += 1
            one = scope_seconds((a, b), self.ops, scope_map.get(name, {}))
            for scope, s in one.items():
                total[scope] = total.get(scope, 0.0) + s
        return n, {s: v / n for s, v in total.items()} if n else {}


# -- what the readers ask ---------------------------------------------------
_cache: dict[int, dict] = {}


def log(**obj) -> None:
    print(json.dumps(obj, default=str), file=sys.stderr, flush=True)


def analysis(run) -> dict:
    """Everything read from the trace of ``run`` (a harness ``Load``),
    once: ``{"idle": table, "span_s": s, "steps": n, "scopes":
    {scope: ms}}``, empty where there is no trace or no annotation in
    it. Logs the whole tables on standard error, one JSON line each."""
    if getattr(run, "trace", None) is None:
        return {}
    if id(run) in _cache:
        return _cache[id(run)]
    out: dict = {}
    try:
        path = xplane_of(run.config["name"])
        if path is not None:
            trace = HostTrace(path)
            out["span_s"] = (trace.span[1] - trace.span[0]) / 1e9
            if trace.proxy:
                out["idle"] = trace.idle_attribution()
                idle_s = sum(out["idle"].values())
                log(phase="idle_attribution", span_s=out["span_s"],
                    idle_s=idle_s, host_bound_s=host_bound_s(out["idle"]),
                    seconds=out["idle"], xplane=path)
            scope_map = program_scope_map()
            if scope_map:
                n, scopes = trace.step_scopes(
                    run.config["step_program"], scope_map)
                out["steps"] = n
                out["scopes"] = {s: v * 1e3 for s, v in scopes.items()}
                log(phase="step_scopes", executions=n,
                    ms_per_step=dict(sorted(
                        out["scopes"].items(), key=lambda kv: -kv[1])),
                    outside_every_scope_ms=out["scopes"].get(""))
            _log_stage_report(run)
    except Exception as e:  # noqa: BLE001 — a reader never fails a run
        log(phase="host_spans", error=f"{type(e).__name__}: {e}")
    _cache[id(run)] = out
    return out


def scope_ms(run, scopes: tuple[str, ...]) -> float | None:
    """Device milliseconds per step inside ``scopes``; nothing where
    the trace has no step, or the program left no scope map or has none
    of these scopes."""
    per = analysis(run).get("scopes")
    if not per or not any(s in per for s in scopes):
        return None
    return sum(per.get(s, 0.0) for s in scopes)


def window_spans(run, stage: str) -> list[dict]:
    """The in-process recorder's spans of ``stage`` that began inside
    the measured window."""
    try:
        from retina_tpu.obs.recorder import get_recorder

        spans = get_recorder().spans()
    except Exception:  # noqa: BLE001 — no recorder: nothing to read
        return []
    return [s for s in spans if s.get("stage") == stage
            and run.t_open <= s["t0"] < run.t_close]


def _log_stage_report(run) -> None:
    """The stage report of one window epoch from the middle of the
    window: what ``GET /debug/trace?epoch=E`` serves."""
    from retina_tpu.obs.recorder import get_recorder

    mid = (run.t_open + run.t_close) / 2
    stages: dict[int, set] = {}
    for s in get_recorder().spans():
        if s.get("trace_id", -1) > 0 and mid <= s["t0"] < mid + 10.0:
            stages.setdefault(s["trace_id"], set()).add(s["stage"])
    if not stages:
        return
    # A window's work spreads over the epoch it arrives in and the
    # next: show the fullest epoch of ten.
    epoch = max(sorted(stages), key=lambda e: len(stages[e]))
    rec = get_recorder()
    try:
        report = rec.stage_report(trace_id=epoch)
    except TypeError:  # a recorder that cannot pick an epoch
        return
    log(phase="stage_report", epoch=epoch, stages={
        k: {"count": v["count"], "total_ms": round(v["total_s"] * 1e3, 3),
            "p50_ms": round(v["p50_s"] * 1e3, 3)}
        for k, v in report.items()})
