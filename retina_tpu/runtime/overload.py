"""Closed-loop overload controller: degrade resolution, never availability.

BENCH_r05 showed the old failure mode: under sustained load the pipeline
either ran at full rate or collapsed to 0 ev/s windows (binary
nominal/degraded from the supervised-runtime PR). PSketch (PAPERS.md)
and "Sketchy With a Chance of Adoption" argue a production sketch
monitor must shed LOW-VALUE work first and keep heavy-hitter accuracy;
this module is that control loop.

The controller watches normalized pressure signals the engine feeds it
(per-worker staging fill, handoff wait rate, harvest lag, dispatch
latency — plus the ``feed.backpressure`` fault site for chaos tests)
and moves the pipeline through explicit states with hysteresis. A full
dispatch pipeline that is keeping up is not pressure, so the number of
dispatches in flight is not a signal: every signal measures something
piling up or something waiting::

    NOMINAL ──p≥enter──► SAMPLING ──p≥shed──► SHEDDING ──p≥degrade──► DEGRADED
       ◄──p≤exit for dwell_s── (one level per dwell period)

* ``SAMPLING``: feed workers keep 1-in-k of the combined rows.
  Priority-aware: heavy-hitter candidates (combined packet weight ≥
  ``overload_exempt_packets``) and apiserver latency probes
  (TSVAL/TSECR lanes) are exempt; the device step rescales the
  surviving non-exempt rows by k (models/pipeline.py) so Count-Min /
  HLL / entropy estimates stay unbiased (Horvitz-Thompson). The weight
  synthesized by that rescaling is accounted in ``accuracy_debt``.
* ``SHEDDING``: enrichment stages are dropped in the declared order
  (``overload_shed_order``: DNS qname hashing → conntrack accounting →
  per-pod label resolution) before any raw event is lost; the shed set
  widens one stage per ``overload_shed_escalate_s`` while pressure
  stays at/above the shed threshold.
* ``DEGRADED``: every stage shed + sampling active; this is also where
  crash-only recovery (engine._degraded) pins the controller.

Window ticks ride the transfer mux control lane and a dedicated close
semaphore (engine._submit_close_window), so a window is ALWAYS closed —
annotated with ``sampled_fraction`` — never silently emitted as zero.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Iterable

import numpy as np

from retina_tpu.events.schema import F
from retina_tpu.log import logger
from retina_tpu.metrics import get_metrics

NOMINAL, SAMPLING, SHEDDING, DEGRADED = 0, 1, 2, 3
STATE_NAMES = ("NOMINAL", "SAMPLING", "SHEDDING", "DEGRADED")

# Enrichment stages sheddable in SHEDDING, in the only legal order:
# cheapest-to-lose first (docs/operations.md §6).
SHED_STAGES = ("dns", "conntrack", "labels")

# Priority-tier lattice (PSketch, arxiv 2509.07338): higher tiers are
# exempt from sampling, and the invertible high-priority sketch region
# (models/pipeline.py inv_hi) only ever sees TIER_PRIORITY rows — so
# priority tenants keep exact counters while background degrades first.
TIER_BACKGROUND = 0  # sampled 1-in-k under SAMPLING+
TIER_PRIORITY = 1  # per-(tenant,service) priority class (IP mask match)
TIER_HEAVY = 2  # heavy-hitter candidates (packet weight)
TIER_CONTROL = 3  # apiserver latency probes / control lane


def priority_class_np(
    src_ip: np.ndarray, dst_ip: np.ndarray, mask: int, match: int
) -> np.ndarray:
    """Host mirror of models.pipeline.priority_class — the two MUST stay
    bit-identical: the feed worker drops rows with this predicate and
    the device step rescales survivors with the jnp twin; any skew
    biases the Horvitz-Thompson estimate. mask == 0 disables the class
    (no row is priority)."""
    if mask == 0:
        return np.zeros(src_ip.shape, bool)
    m, v = np.uint32(mask), np.uint32(match)
    return ((src_ip & m) == v) | ((dst_ip & m) == v)


def row_tiers(rec: np.ndarray, cfg) -> np.ndarray:
    """Classify combined rows into the priority lattice: (N,) uint8 of
    TIER_* values, taking the HIGHEST tier each row qualifies for.
    Exemption from sampling is simply ``tier > TIER_BACKGROUND``."""
    tiers = np.zeros(rec.shape[0], np.uint8)
    tiers[
        priority_class_np(
            rec[:, F.SRC_IP], rec[:, F.DST_IP],
            int(getattr(cfg, "overload_priority_ip_mask", 0)),
            int(getattr(cfg, "overload_priority_ip_match", 0)),
        )
    ] = TIER_PRIORITY
    heavy = rec[:, F.PACKETS] >= np.uint32(cfg.overload_exempt_packets)
    tiers[heavy] = TIER_HEAVY
    control = (rec[:, F.TSVAL] | rec[:, F.TSECR]) != 0
    tiers[control] = TIER_CONTROL
    return tiers


class OverloadController:
    """State machine + host-side sampler. Thread-safe; ``tick`` is called
    from the engine feed loop (bounded by ``overload_tick_s``), readers
    (``sample_rows``/``shed_active``) run on feed workers and plugin
    threads."""

    def __init__(
        self,
        cfg,
        signals: Callable[[], dict[str, float]] | None = None,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self.cfg = cfg
        self._signals = signals or (lambda: {})
        # The engine's clock (injected in tests): ticks, dwell and
        # escalation periods are read on it.
        self._clock = clock
        self.log = logger("overload")
        self._lock = threading.Lock()
        self._state = NOMINAL
        self._shed_level = 0
        self._pressure = 0.0
        self._sigvals: dict[str, float] = {}
        self._last_tick = 0.0
        self._below_since: float | None = None
        self._shed_above_since: float | None = None
        self._transitions = 0
        self._last_change = clock()
        self._phase = 0  # rotating 1-in-k phase  # guarded-by: self._lock
        # Window-scoped accounting the engine snapshots+resets at close.
        self._win_sampled = 0  # events dropped  # guarded-by: self._lock
        self._win_kept = 0  # events admitted  # guarded-by: self._lock
        self._win_priority = 0  # priority-tier events  # guarded-by: self._lock

    # -- state machine -------------------------------------------------
    def tick(self, now: float | None = None) -> int:  # runs-on: engine-dispatch
        """Advance the state machine from the current pressure signals.
        Cheap when called faster than ``overload_tick_s``."""
        cfg = self.cfg
        if not getattr(cfg, "overload_enabled", True):
            return self._state
        now = self._clock() if now is None else now
        if now < self._last_tick + cfg.overload_tick_s:  # = next_tick()
            return self._state
        self._last_tick = now
        try:
            sig = self._signals() or {}
        except Exception:
            self.log.exception("overload signal read failed")
            sig = {}
        p = max(sig.values(), default=0.0)
        self._publish_signals(sig, p)
        with self._lock:
            self._pressure = p
            self._sigvals = dict(sig)
            self._advance(p, now)
            return self._state

    def next_tick(self) -> float | None:
        """When, on the controller's clock, the next ``tick`` will do
        something (None: never, the controller is off): the deadline
        the feed loop sleeps to when no block arrives sooner."""
        if not getattr(self.cfg, "overload_enabled", True):
            return None
        return self._last_tick + self.cfg.overload_tick_s

    def _publish_signals(self, sig: dict[str, float], p: float) -> None:
        """What the controller was told, for the scrape: the pressure
        it compares with its thresholds and every signal behind it (a
        signal that was not reported this tick reads 0)."""
        m = get_metrics()
        m.overload_pressure.set(p)
        for name in self._sigvals.keys() - sig.keys():
            m.overload_signal.labels(signal=name).set(0.0)
        for name, v in sig.items():
            m.overload_signal.labels(signal=name).set(v)

    def _advance(self, p: float, now: float) -> None:
        cfg = self.cfg
        # Escalation is immediate: sustained saturation must not wait
        # out a dwell period while queues overflow.
        target = NOMINAL
        if p >= cfg.overload_enter_pressure:
            target = SAMPLING
        if p >= cfg.overload_shed_pressure:
            target = SHEDDING
        if p >= cfg.overload_degrade_pressure:
            target = DEGRADED
        if target > self._state:
            self._set_state(target, p, now)
            self._below_since = None
            self._shed_above_since = now
            return
        # De-escalation: one level per dwell period with pressure at or
        # below the EXIT threshold (enter > exit = the hysteresis band;
        # brief dips never flap the state).
        if self._state > NOMINAL and p <= cfg.overload_exit_pressure:
            if self._below_since is None:
                self._below_since = now
            elif now - self._below_since >= cfg.overload_dwell_s:
                self._set_state(self._state - 1, p, now)
                self._below_since = now
        else:
            self._below_since = None
        # Within SHEDDING, widen the shed set one stage per escalate
        # period while pressure holds at/above the shed threshold.
        if self._state == SHEDDING and p >= cfg.overload_shed_pressure:
            if self._shed_above_since is None:
                self._shed_above_since = now
            elif (
                now - self._shed_above_since >= cfg.overload_shed_escalate_s
                and self._shed_level < len(self._shed_order())
            ):
                self._shed_level += 1
                self._shed_above_since = now
                self.log.warning(
                    "overload: shedding widened to %s (pressure %.2f)",
                    list(self._shed_order()[: self._shed_level]), p,
                )
        elif self._state != SHEDDING:
            self._shed_above_since = None

    def _set_state(self, state: int, p: float, now: float) -> None:
        old = self._state
        self._state = state
        self._transitions += 1
        self._last_change = now
        if state >= SHEDDING:
            self._shed_level = max(1, self._shed_level)
        if state == DEGRADED:
            self._shed_level = len(self._shed_order())
        if state < SHEDDING:
            self._shed_level = 0
        get_metrics().overload_state.set(state)
        log = self.log.warning if state > old else self.log.info
        log(
            "overload: %s -> %s (pressure %.2f, signals %s)",
            STATE_NAMES[old], STATE_NAMES[state], p,
            {k: round(v, 3) for k, v in self._sigvals.items()},
        )

    def _shed_order(self) -> tuple[str, ...]:
        return tuple(getattr(self.cfg, "overload_shed_order", SHED_STAGES))

    # -- read side ------------------------------------------------------
    @property
    def state(self) -> int:
        return self._state

    @property
    def state_name(self) -> str:
        return STATE_NAMES[self._state]

    @property
    def sample_k(self) -> int:
        if self._state >= SAMPLING:
            return max(1, int(self.cfg.overload_sample_k))
        return 1

    def shed_stages(self) -> tuple[str, ...]:
        return self._shed_order()[: self._shed_level]

    def shed_active(self, stage: str) -> bool:
        return stage in self._shed_order()[: self._shed_level]

    # -- sampler (feed-worker side) ------------------------------------
    def sample_rows(self, rec: np.ndarray) -> tuple[np.ndarray, int]:  # runs-on: feed-worker*
        """Apply priority-aware 1-in-k sampling to combined rows.

        Runs POST-combine (parallel/combine.py) and PRE-partition so a
        row's packet weight is final: the device step recomputes the
        SAME exemption predicate over the same rows and scales the
        non-exempt survivors by k (models/pipeline.py), keeping every
        packet-weighted estimate unbiased. Exempt (never sampled): any
        row above TIER_BACKGROUND in the priority lattice (row_tiers) —
        heavy-hitter candidates (packets >= overload_exempt_packets),
        apiserver latency probes (TSVAL/TSECR != 0), and the configured
        per-(tenant,service) priority IP class; window ticks never pass
        through here at all (control lane).

        Returns ``(kept_rows, k)`` where k is 1 when not sampling.
        """
        k = self.sample_k
        n = rec.shape[0]
        if k <= 1 or n == 0:
            if n:
                kept_ev = int(rec[:, F.PACKETS].sum())
                with self._lock:
                    self._win_kept += kept_ev
            return rec, 1
        pk = rec[:, F.PACKETS]
        tiers = row_tiers(rec, self.cfg)
        exempt = tiers > TIER_BACKGROUND
        idx = np.nonzero(~exempt)[0]
        # Under the lock: N feed workers sample concurrently, and an
        # unlocked += here loses increments against both sibling
        # workers and window_annotation's snapshot-and-reset — the
        # window's sampled_fraction then lies about admitted traffic.
        # Only the scalar bookkeeping is locked; the row selection
        # stays outside.
        with self._lock:
            phase = self._phase
            self._phase = (phase + idx.size) % k
        keep = exempt.copy()
        keep[idx[(np.arange(idx.size) + phase) % k == 0]] = True
        kept = rec[keep]
        dropped_ev = int(pk.sum()) - int(kept[:, F.PACKETS].sum())
        if dropped_ev:
            m = get_metrics()
            m.events_sampled.inc(dropped_ev)
            # Weight the device will synthesize back via x k scaling on
            # the surviving non-exempt rows: the estimated (not
            # observed) share of every sketch/counter.
            debt = (k - 1) * int(kept[~exempt[keep], F.PACKETS].sum())
            if debt:
                m.accuracy_debt.inc(debt)
        kept_ev = int(kept[:, F.PACKETS].sum())
        pri_ev = int(pk[tiers == TIER_PRIORITY].sum())
        with self._lock:
            self._win_sampled += dropped_ev
            self._win_kept += kept_ev
            self._win_priority += pri_ev
        return kept, k

    def note_shed(self, stage: str, amount: int = 1) -> None:
        """Account one shed enrichment unit (events for dns, passes for
        conntrack/labels — see docs/metrics.md)."""
        if amount:
            get_metrics().events_shed.labels(stage=stage).inc(amount)

    # -- window annotation ---------------------------------------------
    def window_annotation(self) -> dict:  # runs-on: device-proxy
        """Snapshot + reset the per-window sampling accounting; the
        engine attaches this to every closed window (harvest item)."""
        with self._lock:
            sampled, kept = self._win_sampled, self._win_kept
            priority = self._win_priority
            self._win_sampled = 0
            self._win_kept = 0
            self._win_priority = 0
            total = sampled + kept
            return {
                "overload_state": STATE_NAMES[self._state],
                "sampled_fraction":
                    (sampled / total) if total else 0.0,
                "events_sampled": sampled,
                "priority_exempt_events": priority,
                "shed": list(self.shed_stages()),
            }

    # -- observability --------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            return {
                "state": STATE_NAMES[self._state],
                "pressure": round(self._pressure, 4),
                "signals": {
                    k: round(v, 4) for k, v in self._sigvals.items()
                },
                "sample_k": self.sample_k,
                "shed": list(self.shed_stages()),
                "transitions": self._transitions,
                "since_change_s": round(
                    self._clock() - self._last_change, 1
                ),
            }


def validate_shed_order(order: Iterable[str]) -> tuple[str, ...]:
    """Config-time check: a permutation-prefix of the known stages."""
    order = tuple(order)
    if len(set(order)) != len(order):
        raise ValueError(f"overload_shed_order has duplicates: {order}")
    unknown = set(order) - set(SHED_STAGES)
    if unknown:
        raise ValueError(
            f"unknown overload shed stage(s) {sorted(unknown)}; "
            f"known: {list(SHED_STAGES)}"
        )
    return order
