"""Concurrency stress (VERDICT r1 coverage #56): the engine's feed loop,
scrape path, identity churn, filter updates, and window closes all
running against each other under contention. Locks mirror the reference
structure; this exercises them instead of trusting them."""

import threading
import time

import numpy as np

from retina_tpu.config import Config
from retina_tpu.engine import SketchEngine
from retina_tpu.events.schema import NUM_FIELDS


def small_cfg() -> Config:
    cfg = Config()
    cfg.mesh_devices = 2
    cfg.batch_capacity = 1 << 10
    cfg.n_pods = 1 << 8
    cfg.cms_width = 1 << 10
    cfg.topk_slots = 1 << 7
    cfg.hll_precision = 8
    cfg.entropy_buckets = 1 << 8
    cfg.conntrack_slots = 1 << 10
    cfg.identity_slots = 1 << 10
    cfg.window_seconds = 0.1  # force frequent window closes
    cfg.bypass_lookup_ip_of_interest = True
    return cfg


def test_engine_under_contention():
    """4 producers + feed loop + 2 scrapers + identity churn + filter
    churn for ~3s: no exceptions anywhere, every accepted event reaches
    the device path, and the engine stays live afterwards."""
    eng = SketchEngine(small_cfg())
    eng.compile()
    stop = threading.Event()
    producers_stop = threading.Event()
    errors: list[BaseException] = []

    def guarded(fn):
        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
        return run

    accepted = [0] * 4
    rng = [np.random.default_rng(i) for i in range(4)]

    def producer(i: int):
        def run():
            while not producers_stop.is_set():
                n = int(rng[i].integers(1, 600))
                rec = rng[i].integers(
                    0, 2**31, size=(n, NUM_FIELDS), dtype=np.int64
                ).astype(np.uint32)
                accepted[i] += eng.sink.write_records(rec, f"prod{i}")
                time.sleep(0.002)
        return run

    def scraper():
        while not stop.is_set():
            snap = eng.snapshot(max_age_s=0.0)  # always fresh: max load
            assert "totals" in snap or "steps" in snap
            eng.top_flows(8)
            time.sleep(0.01)

    def identity_churn():
        gen = 0
        while not stop.is_set():
            gen += 1
            ips = {0x0A000000 + i: (i % 200) + 1 for i in range(gen % 150)}
            eng.update_identities(ips)
            time.sleep(0.005)

    def filter_churn():
        gen = 0
        while not stop.is_set():
            gen += 1
            eng.update_filter_ips({0x0A000000 + i for i in range(gen % 50)})
            time.sleep(0.007)

    producer_threads = [
        threading.Thread(target=guarded(producer(i)), daemon=True)
        for i in range(4)
    ]
    threads = [threading.Thread(target=guarded(lambda: eng.start(stop)),
                                daemon=True)]
    threads += producer_threads
    threads += [threading.Thread(target=guarded(scraper), daemon=True)
                for _ in range(2)]
    threads += [threading.Thread(target=guarded(identity_churn),
                                 daemon=True),
                threading.Thread(target=guarded(filter_churn),
                                 daemon=True)]
    for t in threads:
        t.start()
    eng.started.wait(10)
    time.sleep(3.0)

    # Stop producers FIRST so sum(accepted) freezes, then wait for the
    # still-running feed loop to drain the sink completely.
    producers_stop.set()
    target = None
    drain_deadline = time.monotonic() + 20
    while time.monotonic() < drain_deadline:
        if target is None and all(
                not t.is_alive() for t in producer_threads):
            target = sum(accepted)  # final, immutable total
        if target is not None and eng._events_in >= target:
            break
        time.sleep(0.1)
    stop.set()
    for t in threads:
        t.join(15)
        assert not t.is_alive(), f"thread {t.name} deadlocked"

    assert not errors, f"exceptions under contention: {errors!r}"
    assert target is not None, "producers never finished"
    # Every accepted event reached the device path once producers
    # stopped and the sink drained — nothing silently vanished.
    assert eng._events_in == target, (
        f"accepted={target} events_in={eng._events_in}"
    )
    # Liveness after the storm: the engine still steps and snapshots.
    post = np.zeros((64, NUM_FIELDS), np.uint32)
    eng.step_records(post, now_s=int(time.time()))
    snap = eng.snapshot(max_age_s=0.0)
    assert snap["steps"] == eng._steps
    assert eng._steps > 0
    assert eng._events_in == target + 64
