"""Server regression tests: scrape behavior under render stalls.

The /metrics render cache (server.py) must keep a scrape from ever
blocking on a render: while the renderer is slow or stalled outright
(device stall, harvest hang), scrapes serve the LAST COMPLETE exposition
body with bounded latency instead of hanging or 500ing — the overload
story's observability leg (docs/operations.md §6): a saturated pipeline
still answers its scrapes.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from retina_tpu.server import Server


def _get(port, path, timeout=5.0):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


@pytest.fixture
def srv_factory():
    servers = []

    def make(**kw):
        s = Server("127.0.0.1:0", **kw)
        s.start()
        servers.append(s)
        return s

    yield make
    for s in servers:
        s.stop()


def test_metrics_serves_last_complete_body_during_stall(srv_factory):
    """A stalled renderer must not take /metrics down: every scrape
    returns the last complete body, fast, for the whole serve-stale
    grace period."""
    stall = threading.Event()
    release = threading.Event()

    def gather():
        if stall.is_set():
            release.wait()  # renderer wedged (harvest hang analog)
        return b"retina_window_events 42\n"

    srv = srv_factory(gather=gather, metrics_cache_ttl_s=0.05)
    try:
        code, body = _get(srv.port, "/metrics")
        assert code == 200 and b"retina_window_events 42" in body

        stall.set()
        time.sleep(0.1)  # TTL expired: every render now hangs
        lats = []
        for _ in range(20):
            t0 = time.monotonic()
            code, body = _get(srv.port, "/metrics")
            lats.append(time.monotonic() - t0)
            assert code == 200
            # The LAST COMPLETE exposition, not an empty/partial one.
            assert b"retina_window_events 42" in body
            time.sleep(0.01)
        assert max(lats) < 1.0, f"scrape blocked on stalled render: {lats}"
    finally:
        release.set()  # unwedge so Server.stop() joins promptly


def test_scrape_p99_bounded_with_slow_render(srv_factory):
    """With a render costing 0.3s (≫ scrape budget), serve-stale keeps
    scrape latency flat: the render runs off the scrape path."""

    def gather():
        time.sleep(0.3)
        return b"retina_up 1\n"

    srv = srv_factory(gather=gather, metrics_cache_ttl_s=0.05)
    lats = []
    for _ in range(40):
        t0 = time.monotonic()
        code, _body = _get(srv.port, "/metrics")
        lats.append(time.monotonic() - t0)
        assert code == 200
    lats.sort()
    p99 = lats[int(len(lats) * 0.99)]
    assert p99 < 0.25, f"scrape p99 {p99:.3f}s; render leaked onto scrape path"


def _scrape_until(port, want: bytes, timeout: float = 5.0) -> bytes:
    deadline = time.monotonic() + timeout
    while True:
        code, body = _get(port, "/metrics")
        assert code == 200
        if want in body or time.monotonic() > deadline:
            return body
        time.sleep(0.01)


@pytest.mark.parametrize("ttl", [0, 0.05])
def test_scrape_carries_a_finished_publish_within_one_render(
        srv_factory, counting_render, ttl):
    """Over HTTP, with the exporter's own gatherer: scrapes between two
    publishes render the pod-level registry once however many TTLs
    expire, a finished publish shows one kick and one render later, and
    the agent's own series stay live throughout. TTL 0 renders inline
    and takes the same path."""
    from retina_tpu.exporter import Exporter

    ex = Exporter()
    adv = ex.new_adv_gauge("srv_adv_gauge", ["pod"])
    live = ex.new_gauge("srv_live_gauge", [])
    for i in range(20):
        adv.labels(pod=f"p{i}").set(1)
    ex.advanced_published()
    srv = srv_factory(exporter=ex, metrics_cache_ttl_s=ttl)
    for v in range(1, 4):
        live.set(v)
        body = _scrape_until(srv.port, f"srv_live_gauge {v}.0".encode())
        assert f"srv_live_gauge {v}.0".encode() in body
        assert b'srv_adv_gauge{pod="p7"} 1.0' in body
        time.sleep(2 * ttl)  # let the body cache expire
    assert counting_render.count(ex.advanced_registry) == 1
    assert counting_render.count(ex.default_registry) >= 3
    adv.labels(pod="p7").set(2)  # a publish cycle: writes, then the word
    ex.advanced_published()
    body = _scrape_until(srv.port, b'srv_adv_gauge{pod="p7"} 2.0')
    assert b'srv_adv_gauge{pod="p7"} 2.0' in body
    assert b'srv_adv_gauge{pod="p6"} 1.0' in body
    _scrape_until(srv.port, b"never there", timeout=3 * ttl)
    assert counting_render.count(ex.advanced_registry) == 2


def test_reconcile_reset_invalidates_the_served_body_at_once(srv_factory):
    """A reset of the advanced registry (CRD reconcile) must not leave
    the old pod-level bytes on the scrape: inline, the very next scrape
    is without them."""
    from retina_tpu.exporter import Exporter

    ex = Exporter()
    ex.new_adv_gauge("srv_reset_gauge", []).set(1)
    ex.advanced_published()
    srv = srv_factory(exporter=ex, metrics_cache_ttl_s=0)
    for _ in range(2):
        assert b"srv_reset_gauge 1.0" in _get(srv.port, "/metrics")[1]
    ex.reset_advanced()
    assert b"srv_reset_gauge" not in _get(srv.port, "/metrics")[1]
    ex.new_adv_gauge("srv_reset_gauge2", []).set(3)
    assert b"srv_reset_gauge2 3.0" in _get(srv.port, "/metrics")[1]


def test_debug_vars_exposes_overload_section(srv_factory):
    """The overload controller's stats ride /debug/vars (wired in
    controllermanager.init): state, pressure, and the active shed set
    are what an operator checks first during an incident."""
    stats = {"state": "SHEDDING", "pressure": 0.95, "shed": ["dns"]}
    srv = srv_factory()
    srv.expose_var("overload", lambda: stats)
    code, body = _get(srv.port, "/debug/vars")
    assert code == 200
    doc = json.loads(body)
    assert doc["overload"]["state"] == "SHEDDING"
    assert doc["overload"]["shed"] == ["dns"]


def test_health_routes(srv_factory):
    srv = srv_factory(ready_check=lambda: False)
    assert _get(srv.port, "/healthz")[0] == 200
    assert _get(srv.port, "/readyz")[0] == 503
    assert _get(srv.port, "/nope")[0] == 404
