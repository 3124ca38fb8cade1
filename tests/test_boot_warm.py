"""Boot-latency contract (VERDICT r4 #2): ``compile()`` warms only the
steady-state jit keys — the agent is ready in seconds, not after the
full bucket grid — and ``start_background_warm`` then makes EVERY
reachable bucket key resident so no live dispatch can hit a cold compile
once the warm finishes.

Reference SLA spirit: pkg/managers/pluginmanager/pluginmanager.go:25-28
(the whole plugin reconcile budget is 10s)."""

from __future__ import annotations

import threading

import numpy as np

from retina_tpu.config import Config
from retina_tpu.engine import SketchEngine
from retina_tpu.events.synthetic import TrafficGen


def small_cfg(**kw) -> Config:
    cfg = Config()
    cfg.batch_capacity = 1 << 10
    cfg.n_pods = 1 << 6
    cfg.cms_width = 1 << 10
    cfg.cms_depth = 2
    cfg.topk_slots = 1 << 6
    cfg.hll_precision = 8
    cfg.entropy_buckets = 1 << 8
    cfg.conntrack_slots = 1 << 10
    cfg.identity_slots = 1 << 8
    cfg.flow_dict_slots = 1 << 12
    cfg.transfer_min_bucket = 64
    cfg.bypass_lookup_ip_of_interest = True
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def test_compile_warms_only_steady_state_keys():
    """The boot critical path compiles the full-capacity step and the
    min plain bucket ONLY — the flow-dict pairs (including the min
    bucket), window-close and snapshot programs all belong to the
    background warm (the min dict pair + snapshot warms were ~30s of
    the 45s boot observed in the r5 dry run; the 96s boot of BENCH_r04
    was the whole grid)."""
    eng = SketchEngine(small_cfg(feed_coalesce_windows=4))
    eng.compile()
    keys = set(eng._pad_cache)
    grid = [k for k in keys
            if isinstance(k, tuple) and k[0] in ("new", "known")]
    assert not grid, f"flow-dict keys on the critical path: {grid}"
    # Bounded: plain capacity key + plain min key (+ nothing that
    # scales with the grid).
    assert len(keys) <= 3, sorted(keys, key=str)


def test_background_warm_covers_every_reachable_bucket(tmp_path):
    """After bucket_warm_done, any bucket the feed can produce — every
    _wire_bucket(n) for n in [0, coal_cap] — must already be compiled:
    no mid-feed cold compile at any reachable bucket. Live dispatches
    interleave with the warm (FIFO proxy queue). A boot writes nothing
    under ``profile_artifact_dir`` (its default is a fixed path in
    /tmp): only a ``/debug/profile`` session does."""
    prof = tmp_path / "profile"
    eng = SketchEngine(small_cfg(feed_coalesce_windows=2,
                                 profile_artifact_dir=str(prof)))
    eng.compile()
    t = eng.start_background_warm()
    # Feed while the warm runs: dispatches must interleave, not wedge.
    gen = TrafficGen(n_flows=200, n_pods=32, seed=7)
    for i in range(3):
        eng.step_records(gen.batch(512), now_s=10 + i)
    assert eng.bucket_warm_done.wait(300.0), "background warm never done"
    t.join(10.0)
    coal_cap = eng.cfg.batch_capacity * eng.cfg.feed_coalesce_windows
    probes = set(range(0, coal_cap + 1, 97)) | {0, 1, coal_cap}
    for n in probes:
        wb = eng._wire_bucket(n)
        assert ("new", wb) in eng._pad_cache, (n, wb)
        assert ("known", wb) in eng._pad_cache, (n, wb)
    snap = eng.snapshot(max_age_s=0)
    assert int(np.asarray(snap["totals"]).sum()) > 0
    assert not prof.exists()
    # The operator scopes are kept in the process, for a reader there.
    from retina_tpu.parallel.telemetry import op_scope_map

    assert "cms_flow_hh" in op_scope_map()["jit_local_step"].values()


def test_background_warm_plain_mode_covers_coalesced_buckets():
    cfg = small_cfg(feed_coalesce_windows=3)
    cfg.wire_flow_dict = False
    eng = SketchEngine(cfg)
    assert eng._flow_dict is None
    eng.compile()
    eng.start_background_warm()
    assert eng.bucket_warm_done.wait(300.0)
    for b in eng._reachable_buckets():
        assert b in eng._pad_cache, b


def test_warm_close_is_first_background_job():
    """The window-close program heads the background-warm job list —
    ahead of even the min-bucket dispatch pair. The first live window
    tick fires window_seconds after boot, almost always before any
    grid key compiles; with warm_close queued first the tick finds the
    program resident (or deferring, below) instead of cold-compiling
    end_window inline on the proxy mid-feed."""
    eng = SketchEngine(small_cfg(feed_coalesce_windows=2))
    jobs = eng._warm_jobs()
    assert jobs[0][0] == "window close", [k for k, _, _ in jobs[:3]]
    # And the plain-wire grid keeps the same head.
    cfg = small_cfg(feed_coalesce_windows=2)
    cfg.wire_flow_dict = False
    assert SketchEngine(cfg)._warm_jobs()[0][0] == "window close"


def test_pre_warm_window_tick_defers_instead_of_inline_compile():
    """A window tick arriving while the close program is still queued in
    the background warm DEFERS (windows_deferred) instead of compiling
    end_window inline; once the program is resident the next tick
    closes normally."""
    from retina_tpu.events.synthetic import TrafficGen
    from retina_tpu.metrics import get_metrics

    eng = SketchEngine(small_cfg(feed_coalesce_windows=2))
    eng.compile()
    gen = TrafficGen(n_flows=100, n_pods=16, seed=11)
    eng.step_records(gen.batch(256), now_s=10)

    class _StuckWarm:
        """A warm thread that never finishes (compiles wedged)."""

        def is_alive(self) -> bool:
            return True

    eng._warm_thread = _StuckWarm()
    m = get_metrics()
    closed0 = m.windows_closed._value.get()
    eng._close_window()
    assert m.windows_deferred._value.get() == 1
    assert m.windows_closed._value.get() == closed0
    # Close program lands (warm's first job sets the event) -> the next
    # tick must close the (longer) window with every event intact.
    eng._close_warmed.set()
    eng._close_window()
    eng._harvest_window()
    assert m.windows_deferred._value.get() == 1
    assert m.windows_closed._value.get() == closed0 + 1


def test_background_warm_stops_early_on_shutdown():
    eng = SketchEngine(small_cfg(feed_coalesce_windows=2))
    eng.compile()
    stop = threading.Event()
    stop.set()  # shutdown before the warm starts walking the grid
    t = eng.start_background_warm(stop)
    t.join(30.0)
    assert not t.is_alive()
    # Done is NOT set on an aborted warm — nobody may conclude the grid
    # is resident.
    assert not eng.bucket_warm_done.is_set()


def test_desc_table_warm_job_in_flow_dict_mode():
    """Flow-dict dispatch needs the device descriptor table on its
    very first batch; the background warm builds it right behind the
    window-close program so the zeros-jit compile (and, post-resync,
    the AOT disk-cache load) stays off the event path (RT401)."""
    eng = SketchEngine(small_cfg(feed_coalesce_windows=2))
    jobs = [k for k, _, _ in eng._warm_jobs()]
    assert jobs[0] == "window close"
    assert jobs[1] == "desc table", jobs[:3]
    # Plain-wire mode has no flow dict and no desc table to warm.
    cfg = small_cfg(feed_coalesce_windows=2)
    cfg.wire_flow_dict = False
    plain = [k for k, _, _ in SketchEngine(cfg)._warm_jobs()]
    assert "desc table" not in plain


def test_wait_bucket_warm_polls_both_terminal_events():
    """bench.run_e2e's warm wait must react to bucket_warm_failed
    immediately — a failed warm never sets bucket_warm_done, and
    waiting on done alone burned the full 600s cap before measuring
    (ISSUE 20 satellite; WaitWarm's contract)."""
    import bench

    class StubEngine:
        def __init__(self):
            self.bucket_warm_done = threading.Event()
            self.bucket_warm_failed = threading.Event()

    logs: list[str] = []
    failed = StubEngine()
    failed.bucket_warm_failed.set()
    dt, incomplete = bench.wait_bucket_warm(
        failed, 600, emit=logs.append, sleep_s=0.01)
    assert dt is None and not incomplete
    assert any("FAILED" in line for line in logs)

    done = StubEngine()
    done.bucket_warm_done.set()
    dt, incomplete = bench.wait_bucket_warm(
        done, 600, emit=logs.append, sleep_s=0.01)
    assert dt is not None and dt < 5.0 and not incomplete

    stuck = StubEngine()
    dt, incomplete = bench.wait_bucket_warm(
        stuck, 0.05, emit=logs.append, sleep_s=0.01)
    assert incomplete and dt is not None and dt >= 0.05
