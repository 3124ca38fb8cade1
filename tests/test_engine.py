"""SketchEngine tests on the virtual 8-device CPU mesh (conftest.py):
feed→step→snapshot correctness vs exact numpy baselines, window/anomaly
closing, filter gating, checkpoint round-trip — the reference's pattern of
feeding synthetic flows and asserting metric outcomes (SURVEY.md §4)."""

import os
import threading
import time

import numpy as np
import pytest

from retina_tpu.config import Config
from retina_tpu.engine import SketchEngine
from retina_tpu.events.schema import (
    DIR_INGRESS,
    EV_FORWARD,
    F,
    NUM_FIELDS,
    OP_FROM_NETWORK,
    PROTO_TCP,
    VERDICT_FORWARDED,
)
from retina_tpu.events.synthetic import POD_NET, TrafficGen


def small_cfg(**kw) -> Config:
    cfg = Config()
    cfg.mesh_devices = kw.pop("mesh_devices", 2)
    cfg.batch_capacity = 1 << 10
    cfg.n_pods = 1 << 8
    cfg.cms_width = 1 << 10
    cfg.topk_slots = 1 << 7
    cfg.hll_precision = 8
    cfg.entropy_buckets = 1 << 8
    cfg.conntrack_slots = 1 << 10
    cfg.identity_slots = 1 << 10
    cfg.window_seconds = 0.2
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def mk_records(n, src_pods, dst_pods, verdict=VERDICT_FORWARDED, bytes_=100):
    rec = np.zeros((n, NUM_FIELDS), np.uint32)
    rec[:, F.SRC_IP] = POD_NET + np.asarray(src_pods, np.uint32)
    rec[:, F.DST_IP] = POD_NET + np.asarray(dst_pods, np.uint32)
    rec[:, F.PORTS] = (40000 << 16) | 80
    rec[:, F.META] = (
        (PROTO_TCP << 24) | (0x10 << 16) | (OP_FROM_NETWORK << 8)
        | (DIR_INGRESS << 4)
    )
    rec[:, F.BYTES] = bytes_
    rec[:, F.PACKETS] = 1
    rec[:, F.VERDICT] = verdict
    rec[:, F.EVENT_TYPE] = EV_FORWARD
    return rec


def test_engine_counts_match_exact():
    cfg = small_cfg()
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + i: i for i in range(1, 50)})
    eng.compile()
    # 3 batches: pod 7 receives 300 ingress packets of 100 bytes
    for _ in range(3):
        eng.step_records(mk_records(100, src_pods=np.arange(100) % 49 + 1,
                                    dst_pods=np.full(100, 7)))
    snap = eng.snapshot(max_age_s=0)
    assert snap["totals"][0] == 300  # events
    assert snap["totals"][1] == 300  # forwarded packets
    # pod 7 ingress packets/bytes (dense rectangle, dir 0 = ingress)
    assert snap["pod_forward"][7, 0, 0] == 300
    assert snap["pod_forward"][7, 0, 1] == 30000


def test_engine_feed_loop_and_window():
    """Driven by an injected clock (clockdrive): the flush ages, the
    window tick and what the overload controller is told are the
    test's, so a loaded machine (a cold ingest key compiling inline
    for longer than a 0.2 s window) cannot read as a late device and
    sample the counters."""
    from clockdrive import Drive, FakeClock

    cfg = small_cfg()
    clock = FakeClock()
    eng = SketchEngine(cfg, clock=clock)
    eng.update_identities({POD_NET + i: i for i in range(1, 20)})
    eng.compile()
    stop = threading.Event()
    t = threading.Thread(target=eng.start, args=(stop,), daemon=True)
    t.start()
    assert eng.started.wait(10.0)
    drive = Drive(eng, clock)
    gen = TrafficGen(n_flows=500, n_pods=16, seed=3)
    for _ in range(5):
        drive.hand_over(gen.batch(500), 0.05)
    drive.settle()
    drive.close_a_window()  # at least one close at 0.2 s cadence
    assert eng.overload.stats()["transitions"] == 0
    stop.set()
    t.join(30.0)
    snap = eng.snapshot(max_age_s=0)
    assert snap["totals"][0] == 2500
    assert "entropy_bits" in eng.last_window
    assert eng.last_window["entropy_bits"].shape == (3,)


def test_engine_heavy_hitters_recall():
    cfg = small_cfg()
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + i: i for i in range(1, 64)})
    eng.compile()
    gen = TrafficGen(n_flows=2000, n_pods=32, seed=11, drop_fraction=0,
                     dns_fraction=0)
    for _ in range(10):
        eng.step_records(gen.batch(2000))
    keys, counts = eng.top_flows(k=10)
    assert len(keys) == 10
    assert counts[0] >= counts[-1]
    # The generator's true hottest flow must appear in the sketch top-10
    # with roughly its true count.
    true = gen.true_counts()
    top_true = true.max()
    assert counts[0] >= 0.5 * top_true


def test_engine_filter_gates_unknown_endpoints():
    cfg = small_cfg()
    cfg.bypass_lookup_ip_of_interest = False
    cfg.enable_pod_level = True
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + 1: 1})  # only pod 1 known
    eng.compile()
    rec_known = mk_records(50, src_pods=np.full(50, 99),  # unknown src
                           dst_pods=np.full(50, 1))  # known dst
    rec_unknown = mk_records(70, src_pods=np.full(70, 88),
                             dst_pods=np.full(70, 77))  # both unknown
    eng.step_records(np.concatenate([rec_known, rec_unknown]))
    snap = eng.snapshot(max_age_s=0)
    assert snap["totals"][0] == 50  # unknown-both events filtered out
    # Explicit filter map admits an otherwise-unknown IP:
    eng.update_filter_ips({int(POD_NET + 88)})
    eng.step_records(rec_unknown)
    snap = eng.snapshot(max_age_s=0)
    assert snap["totals"][0] == 120


def test_engine_checkpoint_roundtrip(tmp_path):
    cfg = small_cfg()
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + 1: 1})
    eng.compile()
    eng.step_records(mk_records(100, np.full(100, 2), np.full(100, 1)))
    path = str(tmp_path / "state.npz")
    eng.save_snapshot_state(path)

    eng2 = SketchEngine(cfg)
    assert eng2.load_snapshot_state(path) is True
    snap = eng2.snapshot(max_age_s=0)
    assert snap["totals"][0] == 100
    assert snap["pod_forward"][1, 0, 0] == 100

    # Config mismatch: crash-only contract — never raises, quarantines
    # the stale checkpoint to .bad and cold-starts clean.
    cfg3 = small_cfg(cms_width=1 << 9)
    eng3 = SketchEngine(cfg3)
    assert eng3.load_snapshot_state(path) is False
    assert not os.path.exists(path)
    assert os.path.exists(path + ".bad")
    snap3 = eng3.snapshot(max_age_s=0)
    assert snap3["totals"][0] == 0


def test_engine_drop_accounting_on_overflow():
    cfg = small_cfg(batch_capacity=1 << 7)  # tiny shards force overflow
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + 3: 3, POD_NET + 4: 4})
    eng.compile()
    # One hot connection: every record lands on ONE device shard (conn-
    # consistent partitioning), so shard capacity 128 drops the rest.
    rec = mk_records(1000, np.full(1000, 3), np.full(1000, 4))
    eng.step_records(rec)
    snap = eng.snapshot(max_age_s=0)
    assert snap["totals"][0] <= 128
    assert snap["totals"][7] == 1000 - int(snap["totals"][0])  # lost


def test_identity_churn_incremental():
    """2k-pod identity churn: a single pod event must be cheap (VERDICT
    r1 weak #5) — host-side delta in µs, not an O(table) rebuild."""
    import time as _time

    import jax.numpy as jnp

    eng = SketchEngine(small_cfg(identity_slots=1 << 12))
    full = {POD_NET + i: i for i in range(1, 2001)}
    t0 = _time.perf_counter()
    eng.update_identities(full)
    full_s = _time.perf_counter() - t0

    # One pod added: diff + single cuckoo insert + one upload.
    full[POD_NET + 5000] = 2001
    t0 = _time.perf_counter()
    eng.update_identities(full)
    delta_s = _time.perf_counter() - t0
    assert delta_s < max(0.25, full_s), (delta_s, full_s)

    got = np.asarray(
        eng.ident.lookup(
            jnp.asarray(np.array([POD_NET + 1, POD_NET + 5000], np.uint32))
        )
    )
    assert list(got) == [1, 2001]

    # One pod removed.
    del full[POD_NET + 7]
    eng.update_identities(full)
    got = np.asarray(
        eng.ident.lookup(jnp.asarray(np.array([POD_NET + 7], np.uint32)))
    )
    assert got[0] == 0


def test_identity_overwrite_at_full_load():
    """Re-indexing an existing IP must succeed at exactly 50% load (an
    overwrite consumes no slot), and an overfull reconcile must leave the
    engine's previous table fully intact (transactional)."""
    import jax.numpy as jnp

    from retina_tpu.models.identity import HostIdentityTable

    h = HostIdentityTable(n_slots=1 << 4)
    for i in range(1, 9):  # exactly n_slots//2 keys
        h.insert(0x0A000000 + i, i)
    h.insert(0x0A000001, 99)  # overwrite at full load: must not raise
    assert h.get(0x0A000001) == 99
    with pytest.raises(ValueError):
        h.insert(0x0B000000, 1)  # a genuinely new key does raise

    eng = SketchEngine(small_cfg(identity_slots=1 << 4))
    eng.update_identities({POD_NET + i: i for i in range(1, 9)})
    # Overfull reconcile: clamp-and-count, never crash (VERDICT r3 weak
    # #4). The deterministic (sorted) subset keeps the lowest IPs, so
    # the previously-tracked pods survive; the overflow is visible in
    # lost_table_entries{table="identity"}.
    from retina_tpu.metrics import get_metrics

    eng.update_identities({POD_NET + i: i for i in range(1, 40)})
    lost = get_metrics().lost_table_entries.labels(table="identity")
    assert lost._value.get() == 39 - 8
    got = np.asarray(
        eng.ident.lookup(
            jnp.asarray(np.array([POD_NET + 3, POD_NET + 30], np.uint32))
        )
    )
    assert got[0] == 3  # kept (inside the clamped subset)
    assert got[1] == 0  # dropped (outside capacity)


def test_filter_overflow_clamps_and_counts():
    """2x-capacity IPs-of-interest push: the agent clamps to capacity,
    counts the overflow in lost_table_entries{table="filter"}, and stays
    up (manager_linux.go:62-100 counts per-IP failures the same way) —
    no retry loop, no exception into the pubsub callback."""
    from retina_tpu.managers.filtermanager import FilterManager
    from retina_tpu.metrics import get_metrics

    eng = SketchEngine(small_cfg(identity_slots=1 << 4))  # capacity 8
    fm = FilterManager(apply_fn=eng.update_filter_ips)
    fm.add_ips([int(POD_NET + i) for i in range(1, 17)], "test", "r1")
    lost = get_metrics().lost_table_entries.labels(table="filter")
    assert lost._value.get() == 16 - 8
    # The lowest 8 IPs won the deterministic clamp and are active.
    import jax.numpy as jnp

    got = np.asarray(
        eng.filter_map.lookup(
            jnp.asarray(np.array([POD_NET + 1, POD_NET + 12], np.uint32))
        )
    )
    assert got[0] == 1 and got[1] == 0
    # The exposition carries the counter (scrape visibility).
    from retina_tpu.exporter import get_exporter

    assert b"lost_table_entries" in get_exporter().gather_text()


def test_snapshot_never_stalls_feed():
    """Scrape-during-ingest contract (BASELINE: <1s scrape at sustained
    ingest; VERDICT r1 weak #3): forced snapshots from a scrape thread
    must not stall feed dispatches — the state lock is held only across
    async dispatches, never a device round-trip."""
    cfg = small_cfg(batch_capacity=1 << 12)
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + i: i for i in range(1, 200)})
    eng.compile()
    gen = TrafficGen(n_flows=5000, n_pods=190, seed=1)
    batches = [gen.batch(4096) for _ in range(8)]

    def run_feeder(duration: float, scrape: bool) -> np.ndarray:
        gaps: list[float] = []
        end = time.monotonic() + duration
        stop = threading.Event()

        def scraper():
            while not stop.is_set():
                eng.snapshot(max_age_s=0.0)
                time.sleep(0.01)

        ts = threading.Thread(target=scraper, daemon=True)
        if scrape:
            ts.start()
        i = 0
        last = time.perf_counter()
        while time.monotonic() < end:
            eng.step_records(batches[i % 8])
            now = time.perf_counter()
            gaps.append(now - last)
            last = now
            i += 1
        stop.set()
        if scrape:
            ts.join(1.0)
        return np.array(gaps[3:])

    # Cold ingest keys compile here: on a loaded machine they used to
    # take the whole 2 s of ``base`` (no gap left: a median of nan).
    for b in batches[:4]:
        eng.step_records(b)
    base = run_feeder(2.0, scrape=False)
    scraped = run_feeder(4.0, scrape=True)
    # Feed keeps moving under scrape pressure. Bounds are generous (CI
    # scheduler noise) — the contract they defend is "the state lock is
    # never held across a device round-trip", whose failure mode is feed
    # gaps of the full snapshot readback time on every scrape (p50 blow-
    # up), not a single straggler.
    assert scraped.max() < 2.0, f"max feed gap {scraped.max():.3f}s"
    assert np.median(scraped) < max(8 * np.median(base), 0.1), (
        np.median(scraped), np.median(base))


def test_jit_cache_stable_across_ragged_batches():
    """Ragged ingest (odd block sizes, partial final flush slices) must
    hit ONE compiled step — padding in partition_events keeps device
    shapes static (VERDICT r1 weak #9)."""
    cfg = small_cfg(batch_capacity=1 << 10)
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + 1: 1})
    eng.compile()
    rng = np.random.default_rng(0)
    for n in [1, 17, 333, 1024, 1500, 2047, 4096, 5000]:
        eng.step_records(
            mk_records(n, rng.integers(1, 5, n), rng.integers(1, 5, n))
        )
    assert eng.sharded._step._cache_size() == 1


def test_idle_window_close_skips_device_and_clears_gauges():
    """An idle agent's window ticks must cost zero device round-trips,
    must clear (not latch) the anomaly gauges, and must resume real
    closes when traffic returns."""
    from retina_tpu.metrics import get_metrics

    eng = SketchEngine(small_cfg())
    eng.compile()
    eng.step_records(mk_records(100, np.full(100, 2), np.full(100, 1)))
    calls = {"n": 0}
    real = eng.sharded.end_window

    def counting(state, *a, **kw):
        calls["n"] += 1
        return real(state, *a, **kw)

    eng.sharded.end_window = counting
    eng._close_window()  # has traffic: closes on device
    assert calls["n"] == 1
    # Pretend the last window flagged, then go idle.
    m = get_metrics()
    m.anomaly_flag.labels(dimension="src_ip").set(1.0)
    eng._close_window()
    eng._close_window()
    assert calls["n"] == 1  # idle ticks: no device call
    # Publishes (including the idle zeroing) ride the harvest queue in
    # close order; drain it before reading the gauges.
    eng._harvest_window()
    assert m.anomaly_flag.labels(
        dimension="src_ip")._value.get() == 0.0  # cleared, not latched
    # Traffic resumes: the close runs again.
    eng.step_records(mk_records(10, np.full(10, 3), np.full(10, 1)))
    eng._close_window()
    assert calls["n"] == 2


@pytest.mark.parametrize("workers", [1, 2, 4])
def test_feed_pipeline_modes_agree(workers):
    """The one feed shape lands every event whatever the pool's size
    (combining is lossless; the dispatch thread preserves step/window
    ordering).

    Overload must be OFF: this is an exactness contract, and on a
    loaded CI host the controller can slip into SAMPLING mid-feed —
    the HT-rescale then makes totals an estimate, not 1600, and the
    case flakes."""
    cfg = small_cfg(feed_pipeline_depth=2, feed_workers=workers,
                    overload_enabled=False)
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + i: i for i in range(1, 20)})
    eng.compile()
    stop = threading.Event()
    t = threading.Thread(target=eng.start, args=(stop,), daemon=True)
    t.start()
    assert eng.started.wait(2.0)
    gen = TrafficGen(n_flows=50, n_pods=16, seed=3)  # few flows: real RLE
    for _ in range(4):
        eng.sink.write_records(gen.batch(400), "test")
        time.sleep(0.03)
    # Generous: the feed needs several dispatch+harvest
    # round-trips and CI boxes stall for whole seconds under load.
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if int(eng.snapshot(max_age_s=0)["totals"][0]) == 1600:
            break
        time.sleep(0.05)
    stop.set()
    t.join(5.0)
    assert not t.is_alive()
    snap = eng.snapshot(max_age_s=0)
    assert int(snap["totals"][0]) == 1600
    assert int(snap["totals"][1]) == int(
        np.asarray(snap["pod_forward"])[:, :, 0].sum()
    )


def test_pipelined_window_close_ordered_with_steps():
    """A window close queued after steps must observe those steps'
    entropy contributions (ordering through the dispatch queue)."""
    cfg = small_cfg(feed_pipeline_depth=2, window_seconds=10.0)
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + i: i for i in range(1, 20)})
    eng.compile()
    stop = threading.Event()
    t = threading.Thread(target=eng.start, args=(stop,), daemon=True)
    t.start()
    assert eng.started.wait(2.0)
    gen = TrafficGen(n_flows=200, n_pods=16, seed=5)
    eng.sink.write_records(gen.batch(1000), "test")
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if int(eng.snapshot(max_age_s=0)["totals"][0]) == 1000:
            break
        time.sleep(0.05)
    # close directly while the engine is live (its loop window is 10s
    # so it never fired): entropy of the fed window must be non-zero —
    # steps preceded the close. The readback publishes on the harvest
    # thread; drain it explicitly. Must run BEFORE stop: engine
    # shutdown retires the harvest thread.
    eng._close_window()
    eng._harvest_window()
    stop.set()
    t.join(5.0)
    assert float(eng.last_window["entropy_bits"][0]) > 0.0


@pytest.mark.filterwarnings(
    # The injected fatal error escaping the worker thread IS the test.
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_dead_dispatch_worker_drops_and_counts(monkeypatch):
    """Failure injection for the dead-worker path (SURVEY §5.3): a
    dispatch worker killed by a fatal error escaping its loop must not
    wedge the feed loop — submissions drop with packet-weighted
    lost_events accounting and the engine keeps running."""
    from retina_tpu.engine import SketchEngine as Eng
    from retina_tpu.exporter import reset_for_tests as reset_exporter
    from retina_tpu.metrics import get_metrics, reset_for_tests

    reset_exporter()
    reset_for_tests()

    def fatal_loop(self, q):  # simulates a runtime error escaping
        raise RuntimeError("injected fatal dispatch error")

    monkeypatch.setattr(Eng, "_dispatch_loop", fatal_loop)
    cfg = small_cfg(feed_pipeline_depth=2)
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + i: i for i in range(1, 20)})
    eng.compile()
    stop = threading.Event()
    t = threading.Thread(target=eng.start, args=(stop,), daemon=True)
    t.start()
    assert eng.started.wait(2.0)
    gen = TrafficGen(n_flows=100, n_pods=16, seed=5)
    fed = 0
    for _ in range(6):
        eng.sink.write_records(gen.batch(400), "test")
        fed += 400
        time.sleep(0.05)
    time.sleep(0.3)
    assert t.is_alive(), "feed loop must survive a dead worker"
    stop.set()
    t.join(3.0)
    assert not t.is_alive()
    lost = get_metrics().lost_events.labels(
        stage="dispatch", plugin="engine"
    )._value.get()
    # Sink losses (if the bounded sink overflowed) are counted at a
    # different stage; everything the feed loop flushed must land in
    # the dispatch-stage counter, packet-weighted.
    sink_lost = get_metrics().lost_events.labels(
        stage="sink", plugin="test"
    )._value.get()
    assert lost > 0
    assert lost + sink_lost >= fed * 0.5, (lost, sink_lost, fed)


def test_table_update_enqueued_before_dispatch_is_visible():
    """FIFO-visibility invariant for identity/filter tables: an update
    whose proxied upload is ENQUEUED before a batch executes must be
    applied to that batch — even when earlier proxy work delays the
    queue by seconds. Regression for the r5 race where dispatch-build
    captured the tables and a one-shot burst right after a pod
    registration was silently dropped by the stale (empty) filter."""
    from retina_tpu.utils import device_proxy
    from retina_tpu.utils.device_proxy import submit_on_device

    cfg = small_cfg(bypass_lookup_ip_of_interest=False)
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + i: i for i in range(1, 20)})
    eng.compile()
    # Park the proxy: everything enqueued behind this sleeper waits,
    # simulating a background-warm compile occupying the queue.
    submit_on_device(time.sleep, 3.0)
    # Deterministic ordering: spy on the proxy queue for the update's
    # apply_filter closure landing in it, then dispatch — the batch is
    # then PROVABLY enqueued after the table upload.
    enqueued = threading.Event()
    orig_put = device_proxy._q.put

    def spy_put(item, *a, **kw):
        fn = item[0]
        if getattr(fn, "__qualname__", "").endswith("apply_filter"):
            enqueued.set()
        return orig_put(item, *a, **kw)

    device_proxy._q.put = spy_put
    try:
        # Enqueue the filter update BEHIND the sleeper (blocks its
        # caller until applied, so it runs on a side thread).
        t = threading.Thread(
            target=eng.update_filter_ips, args=({POD_NET + 7},),
            daemon=True,
        )
        t.start()
        assert enqueued.wait(2.5), "filter update never enqueued"
    finally:
        device_proxy._q.put = orig_put
    # Dispatch a one-shot burst to the now-interesting pod. Enqueued
    # after the filter upload -> must see it, not the empty pre-update
    # map (which drops everything when bypass is off).
    eng.step_records(mk_records(50, src_pods=np.full(50, 3),
                                dst_pods=np.full(50, 7)))
    t.join(10.0)
    snap = eng.snapshot(max_age_s=0)
    assert int(snap["totals"][0]) == 50, (
        "batch dispatched after a filter update was filtered by the "
        "stale map"
    )


def test_harvest_thread_retires_and_stays_retired():
    """Engine shutdown retires the window-harvest thread; a straggler
    close (e.g. a warm key racing stop) must not resurrect it — a
    parked resurrected thread pins the engine object graph forever."""
    cfg = small_cfg()
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + i: i for i in range(1, 20)})
    eng.compile()
    stop = threading.Event()
    t = threading.Thread(target=eng.start, args=(stop,), daemon=True)
    t.start()
    assert eng.started.wait(2.0)
    eng.sink.write_records(mk_records(20, np.full(20, 2), np.full(20, 7)),
                           "test")
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and eng._events_in < 20:
        time.sleep(0.05)
    # A real close so the harvest thread exists before shutdown.
    eng._close_window()
    eng._harvest_window()
    stop.set()
    t.join(10.0)
    assert eng._harvest_retired
    old = eng._harvest_thread
    assert old is None or not old.is_alive()
    # Straggler after shutdown: must not spawn a fresh thread.
    eng._ensure_harvest_thread()
    assert eng._harvest_thread is old or not eng._harvest_thread.is_alive()


def test_step_timer_ends_at_completion_and_snapshot_carries_the_watermark():
    """The `device_step` span and `tpu_step_seconds` come from the
    completion thread (dispatch to ready, per step), the stage that
    times the puts says enqueue, and a snapshot's `events_in` is read
    where it is dispatched — what the publish watermark rests on."""
    from retina_tpu.metrics import get_metrics
    from retina_tpu.utils import metric_names as mn

    cfg = small_cfg()
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + i: i for i in range(1, 50)})
    eng.compile()
    rec = eng._recorder
    assert not [s for s in rec.spans()
                if s["stage"] == mn.STAGE_DEVICE_STEP]  # warm-up: none
    hist = get_metrics().device_step_seconds
    n0 = sum(b.get() for b in hist._buckets)
    for _ in range(3):
        eng.step_records(mk_records(100, src_pods=np.arange(100) % 49 + 1,
                                    dst_pods=np.full(100, 7)))
    snap = eng.snapshot(max_age_s=0)
    assert snap["events_in"] == 300 and snap["steps"] == 3
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        steps = [s for s in rec.spans()
                 if s["stage"] == mn.STAGE_DEVICE_STEP]
        if len(steps) == 3:
            break
        time.sleep(0.01)
    assert len(steps) == 3
    assert all(s["thread"] == "device-completion" for s in steps)
    assert all(s["args"]["n_steps"] == 1 for s in steps)
    assert sum(b.get() for b in hist._buckets) - n0 == 3
    by = {}
    for s in rec.spans():
        by.setdefault(s["stage"], []).append(s)
    assert mn.STAGE_TRANSFER_ENQUEUE in by and "transfer" not in by
    # Each step's spans hang under the proxied call that ran them,
    # and that call under the dispatch's wire_build.
    runs = {s["id"]: s for s in by[mn.STAGE_PROXY_RUN]}
    builds = {s["id"] for s in by[mn.STAGE_WIRE_BUILD]}
    for s in steps + by[mn.STAGE_TRANSFER_ENQUEUE]:
        run = runs[s["parent"]]
        assert run["args"]["kind"] == mn.KIND_STEP
        assert run["parent"] in builds
    # The snapshot's own tree: dispatch, fetch (both halves), finish.
    (snap_span,) = by[mn.STAGE_SNAPSHOT]
    for stage in (mn.STAGE_SNAPSHOT_DISPATCH, mn.STAGE_SNAPSHOT_FETCH,
                  mn.STAGE_SNAPSHOT_FINISH):
        (child,) = by[stage]
        assert child["parent"] == snap_span["id"]
    fetch = by[mn.STAGE_SNAPSHOT_FETCH][0]["args"]
    assert fetch["ready_wait_s"] >= 0 and fetch["copy_s"] > 0
    # Direct step_records bypass the sink: the snapshot holds more
    # than the sink ever accepted, so nothing accepted is unheld.
    assert eng.publish_lag_s(snap) == (0.0, 300)
    eng.sink.write_records(mk_records(10, [1] * 10, [2] * 10), "t")
    time.sleep(0.02)
    lag, held = eng.publish_lag_s({"events_in": 5})
    assert held == 5 and 0.02 <= lag < 5.0


def test_a_dropped_block_is_not_publish_lag_for_ever():
    """The sink's accepts and `_events_in` are cumulative: a block
    dropped between the two must be passed over by the watermark, or
    every later publish reads it as lag."""
    from retina_tpu.parallel.partition import partition_events

    cfg = small_cfg()
    eng = SketchEngine(cfg)
    eng.update_identities({POD_NET + i: i for i in range(1, 50)})
    eng.compile()
    blocks = [mk_records(10, [1] * 10, [2] * 10) for _ in range(3)]
    for b in blocks:
        eng.sink.write_records(b, "t")
    time.sleep(0.02)
    eng.step_records(blocks[0])
    # The second block is dropped on its way, as the feed's dispatch
    # drops one while a recovery rebuilds the device state.
    eng._degraded.set()
    sb = partition_events(blocks[1], eng.n_devices, cfg.batch_capacity,
                          min_bucket=cfg.transfer_min_bucket)
    eng._dispatch_sharded(sb, int(time.time()), len(blocks[1]), sync=False)
    eng._degraded.clear()
    # Before the third lands it is the oldest the snapshot lacks ...
    snap = eng.snapshot(max_age_s=0)
    assert (snap["events_in"], snap["events_unheld"]) == (10, 10)
    lag, held = eng.publish_lag_s(snap)
    assert held == 10 and 0.02 <= lag < 5.0
    # ... and once it has, nothing accepted is waited for.
    eng.step_records(blocks[2])
    snap = eng.snapshot(max_age_s=0)
    assert (snap["events_in"], snap["events_unheld"]) == (20, 10)
    assert eng.publish_lag_s(snap) == (0.0, 20)
    # The counts alone would trail by the dropped block for ever.
    assert eng.publish_lag_s({"events_in": 20})[0] >= 0.02
