"""Foundation-layer tests: config, pubsub, exporter, metrics, server,
common objects, telemetry.

Mirrors the reference's unit style (SURVEY.md §4): no cluster, no kernel —
pure in-process contracts, HTTP asserted over a real localhost socket the
way e2e metric checks parse the exposition format
(test/e2e/framework/prometheus/prometheus.go:25-50).
"""

import os
import threading
import time
import urllib.request

import pytest

from retina_tpu.common import DirtyCache, RetinaEndpoint, retry
from retina_tpu.config import AGG_HIGH, Config, load_config
from retina_tpu.exporter import Exporter
from retina_tpu.metrics import Metrics
from retina_tpu.pubsub import PubSub
from retina_tpu.server import Server
from retina_tpu.telemetry import Telemetry, new_telemetry


# ---------------------------------------------------------------- config
def test_config_defaults_valid():
    cfg = Config()
    cfg.validate()
    assert "packetparser" in cfg.enabled_plugins


def test_compilation_cache_enable(tmp_path, monkeypatch):
    """Persistent XLA cache knob points jax at the dir (a restart then
    skips the minutes-long fused-step compile); an unusable dir is a
    warning for the agent and an error for a harness."""
    import jax

    from retina_tpu.config import enable_compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        d = str(tmp_path / "xla-cache")
        assert enable_compilation_cache(d) == d
        assert jax.config.jax_compilation_cache_dir == d
        assert os.path.isdir(d)
        assert enable_compilation_cache("") == ""
        # Off by default: bare Config must not touch global host state.
        assert Config().compilation_cache_dir == ""
        blocked = tmp_path / "a-file"
        blocked.write_text("")
        bad = str(blocked / "cache")
        assert enable_compilation_cache(bad) == ""
        with pytest.raises(RuntimeError, match="unusable"):
            enable_compilation_cache(bad, strict=True)
        assert jax.config.jax_compilation_cache_dir == d
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compilation_cache_placed_from_outside(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when the caller sets it, places every
    cache and no code touches jax's own setting; unset, the harnesses
    use one fixed directory of the checkout."""
    import jax

    from retina_tpu import config

    prev = jax.config.jax_compilation_cache_dir
    env_dir = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    assert config.enable_compilation_cache(str(tmp_path / "other")) == env_dir
    assert config.enable_compilation_cache("", strict=True) == env_dir
    assert jax.config.jax_compilation_cache_dir == prev
    assert not (tmp_path / "other").exists()
    assert config.harness_cache_dirs() == (
        env_dir, os.path.join(env_dir, "aot")
    )
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert config.harness_cache_dirs() == (
        os.path.join(repo, ".retina_cache", "xla"),
        os.path.join(repo, ".retina_cache", "aot"),
    )


def test_config_yaml_env_layering(tmp_path):
    p = tmp_path / "config.yaml"
    p.write_text(
        "enabledPlugin: [dropreason, dns]\n"
        "metricsIntervalDuration: 5\n"
        "enablePodLevel: true\n"
        "dataAggregationLevel: high\n"
    )
    cfg = load_config(
        str(p),
        env={"RETINA_BATCH_CAPACITY": "4096", "RETINA_REMOTE_CONTEXT": "true"},
    )
    assert cfg.enabled_plugins == ["dropreason", "dns"]
    assert cfg.metrics_interval_s == 5
    assert cfg.data_aggregation_level == AGG_HIGH
    assert cfg.batch_capacity == 4096  # env wins over default
    assert cfg.remote_context is True


def test_config_rejects_bad_values(tmp_path):
    with pytest.raises(ValueError):
        load_config(None, overrides={"data_aggregation_level": "medium"})
    with pytest.raises(ValueError):
        load_config(None, overrides={"batch_capacity": 1000})  # not pow2


def test_config_refuses_feed_pipeline_depth_under_one(tmp_path):
    """There is no synchronous feed to select: the depth is a depth,
    and 0 is refused at load by name (YAML and overrides alike)."""
    p = tmp_path / "config.yaml"
    p.write_text("feed_pipeline_depth: 0\n")
    with pytest.raises(ValueError, match="feed_pipeline_depth"):
        load_config(str(p), env={})
    with pytest.raises(ValueError, match="feed_pipeline_depth"):
        load_config(None, overrides={"feed_pipeline_depth": -1})
    assert load_config(
        None, overrides={"feed_pipeline_depth": 1}
    ).feed_pipeline_depth == 1


# ---------------------------------------------------------------- pubsub
def test_pubsub_publish_subscribe_unsubscribe():
    ps = PubSub()
    got: list[int] = []
    done = threading.Event()

    def cb(msg):
        got.append(msg)
        done.set()

    sub = ps.subscribe("t", cb)
    ps.publish("t", 42)
    assert done.wait(2.0)
    assert got == [42]

    ps.unsubscribe("t", sub)
    ps.publish("t", 43)
    time.sleep(0.05)
    assert got == [42]
    with pytest.raises(KeyError):
        ps.unsubscribe("t", sub)
    ps.shutdown()


def test_pubsub_subscriber_exception_isolated():
    ps = PubSub()
    ok = threading.Event()
    ps.subscribe("t", lambda m: (_ for _ in ()).throw(RuntimeError("boom")))
    ps.subscribe("t", lambda m: ok.set())
    ps.publish("t", 1)
    assert ok.wait(2.0)
    ps.shutdown()


# ------------------------------------------------------------- exporter
def test_exporter_registries_and_reset():
    ex = Exporter()
    g = ex.new_gauge("test_basic_gauge", ["l"])
    g.labels(l="a").set(3)
    adv = ex.new_adv_gauge("test_adv_gauge", [])
    adv.set(7)
    text = ex.gather_text().decode()
    assert 'test_basic_gauge{l="a"} 3.0' in text
    assert "test_adv_gauge 7.0" in text

    fired = []
    ex.on_reset(lambda: fired.append(1))
    ex.reset_advanced()
    text = ex.gather_text().decode()
    assert "test_basic_gauge" in text  # default survives
    assert "test_adv_gauge" not in text  # advanced wiped
    assert fired == [1]


# ---------------------------------- pod-level bytes, once per publish
def _gather_counts(ex: Exporter) -> dict[str, float]:
    from retina_tpu.utils import metric_names as mn

    return {
        how: ex.default_registry.get_sample_value(
            mn.TPU_EXPOSITION_GATHERS + "_total", {mn.L_ADVANCED: how})
        for how in (mn.ADVANCED_REUSED, mn.ADVANCED_RENDERED)
    }


KINDS = ["plain_gauge", "row_table"]


def _adv_family(ex: Exporter, kind: str, name: str, labelnames=()):
    """A pod-level family of either kind, a plain prometheus_client
    gauge or a row table, as ``put(label_values, value)``."""
    if kind == "row_table":
        table = ex.new_adv_table(name, list(labelnames))
        return lambda labels, v: table.set(tuple(labels), float(v))
    gauge = ex.new_adv_gauge(name, list(labelnames))
    return lambda labels, v: (
        gauge.labels(*labels) if labels else gauge).set(v)


@pytest.mark.parametrize("kind", KINDS)
def test_published_generation_is_rendered_once(
        kind, counting_render, fresh_exposition):
    """(a) With a publisher-declared generation, gathers render the
    advanced registry once and the default registry every time, and
    every body is what rendering both registries gives."""
    ex = Exporter()
    put = _adv_family(ex, kind, "gen_adv_gauge", ["pod"])
    for i in range(40):
        put([f"p{i}"], i)
    ex.advanced_published()
    render = counting_render
    bodies = [ex.gather() for _ in range(4)]
    assert [how for _, how in bodies] == [
        "rendered", "reused", "reused", "reused"]
    assert render.count(ex.advanced_registry) == 1
    assert render.count(ex.default_registry) == 4
    # Identical but for the gather counter's own two lines.
    own = b"tpu_exposition_gathers_counter_total{"
    reused = [[ln for ln in body.splitlines() if own not in ln]
              for body, _ in bodies[1:]]
    assert reused[0] == reused[1] == reused[2]
    assert ex.gather_text() == fresh_exposition(ex)
    assert render.count(ex.advanced_registry) == 1


@pytest.mark.parametrize("kind", KINDS)
def test_publish_that_changes_one_series_shows_in_next_gather(
        kind, counting_render):
    """(b) One series changed and the generation declared: the next
    gather carries it, at the cost of one render."""
    ex = Exporter()
    put = _adv_family(ex, kind, "pub_adv_gauge", ["pod"])
    put(["a"], 1)
    put(["b"], 1)
    ex.advanced_published()
    render = counting_render
    assert b'pub_adv_gauge{pod="a"} 1.0' in ex.gather_text()
    assert b'pub_adv_gauge{pod="a"} 1.0' in ex.gather_text()
    put(["a"], 2)
    # Mid-cycle: the previous complete publish is what is served.
    assert b'pub_adv_gauge{pod="a"} 1.0' in ex.gather_text()
    ex.advanced_published()
    for _ in range(3):
        body = ex.gather_text()
        assert b'pub_adv_gauge{pod="a"} 2.0' in body
        assert b'pub_adv_gauge{pod="b"} 1.0' in body
    assert render.count(ex.advanced_registry) == 2


@pytest.mark.parametrize(
    "change", ["reset", "new_gauge", "new_counter", "new_table"])
def test_reset_and_new_family_invalidate_kept_bytes(
        change, fresh_exposition):
    """(c) A reset drops the kept bytes at once, and so does a newly
    registered family; neither is a publisher's declaration, so every
    gather renders until the next one."""
    ex = Exporter()
    ex.new_adv_gauge("inv_adv_gauge", []).set(7)
    ex.advanced_published()
    assert ex.gather()[1] == "rendered"
    assert ex.gather()[1] == "reused"
    if change == "reset":
        ex.reset_advanced()
        assert ex._adv_rendered == (-1, b"")
        body, how = ex.gather()
        assert b"inv_adv_gauge" not in body
    elif change == "new_gauge":
        ex.new_adv_gauge("inv_new_gauge", []).set(1)
        body, how = ex.gather()
        assert b"inv_new_gauge 1.0" in body and b"inv_adv_gauge 7.0" in body
    elif change == "new_counter":
        ex.new_adv_counter("inv_new_counter", []).inc(3)
        body, how = ex.gather()
        assert b"inv_new_counter_total 3.0" in body
    else:
        ex.new_adv_table("inv_new_table", ["pod"]).set(("a",), 2.0)
        body, how = ex.gather()
        assert body.endswith(b'inv_new_table{pod="a"} 2.0\n')
        assert b"inv_adv_gauge 7.0" in body
    assert how == "rendered"
    assert ex.gather()[1] == "rendered"  # nobody declared this state
    assert ex.gather_text() == fresh_exposition(ex)
    ex.advanced_published()
    assert [ex.gather()[1] for _ in range(2)] == ["rendered", "reused"]
    assert ex.gather_text() == fresh_exposition(ex)


@pytest.mark.parametrize("kind", KINDS)
def test_publish_landing_during_a_render_is_rendered_next(kind, monkeypatch):
    """(d) The generation is read BEFORE the render and kept with the
    bytes: a publish that ends while a render is in flight (held here
    on an event) makes the next gather render again instead of being
    lost under the bytes the first render would have kept."""
    import retina_tpu.exporter as exporter_mod

    ex = Exporter()
    put = _adv_family(ex, kind, "race_adv_gauge")
    put([], 1)
    ex.advanced_published()
    real = exporter_mod.render_rows
    in_render, release = threading.Event(), threading.Event()
    renders = []

    def held(registry) -> bytes:
        body = real(registry)
        if registry is ex.advanced_registry:
            renders.append(body)
            if len(renders) == 1:
                in_render.set()
                assert release.wait(10.0)
        return body

    got = []
    monkeypatch.setattr(exporter_mod, "render_rows", held)
    try:
        t = threading.Thread(target=lambda: got.append(ex.gather()))
        t.start()
        assert in_render.wait(10.0)
        put([], 2)  # the publisher's cycle lands mid-render
        ex.advanced_published()
        release.set()
        t.join(10.0)
        assert not t.is_alive()
        assert b"race_adv_gauge 1.0" in got[0][0]
        assert got[0][1] == "rendered"
        second, third = ex.gather(), ex.gather()
    finally:
        release.set()
    assert b"race_adv_gauge 2.0" in second[0] and second[1] == "rendered"
    assert b"race_adv_gauge 2.0" in third[0] and third[1] == "reused"
    assert len(renders) == 2


def test_exporter_nobody_publishes_into_reads_back_direct_writes(
        counting_render):
    """(e) Reuse engages only for a generation a publisher declared:
    without one, a gauge set directly is in the very next gather."""
    ex = Exporter()
    adv = ex.new_adv_gauge("direct_adv_gauge", [])
    render = counting_render
    for v in range(4):
        adv.set(v)
        body, how = ex.gather()
        assert f"direct_adv_gauge {float(v)}".encode() in body
        assert how == "rendered"
    assert render.count(ex.advanced_registry) == 4
    assert _gather_counts(ex) == {"reused": 0.0, "rendered": 4.0}


def test_default_registry_is_live_while_advanced_bytes_are_reused():
    """(f) The agent's own series change between publishes and show in
    every gather."""
    ex = Exporter()
    ex.new_adv_gauge("live_adv_gauge", []).set(5)
    live = ex.new_gauge("live_default_gauge", [])
    ex.advanced_published()
    for i in range(4):
        live.set(i)
        body, how = ex.gather()
        assert how == ("reused" if i else "rendered")
        assert f"live_default_gauge {float(i)}".encode() in body
        assert b"live_adv_gauge 5.0" in body
    # A gather counts itself before it renders: its body holds the count.
    assert (b'tpu_exposition_gathers_counter_total{advanced="reused"} 4.0'
            in ex.gather_text())


@pytest.mark.parametrize("ttl", [0, 60.0])
def test_render_span_and_counter_say_reused_or_rendered(ttl):
    """(g) The server's ``render`` span carries ``advanced`` and the
    counter counts both, inline (TTL 0) and through the render cache; a
    server with a gatherer of its own has nothing to say."""
    from retina_tpu.obs.recorder import get_recorder, initialize_recorder
    from retina_tpu.utils import metric_names as mn

    ex = Exporter()
    adv = ex.new_adv_gauge("span_adv_gauge", [])
    adv.set(1)
    old = get_recorder()
    rec = initialize_recorder(capacity=64)
    try:
        srv = Server("127.0.0.1:0", exporter=ex, metrics_cache_ttl_s=ttl)

        def scrape() -> bytes:
            srv._cache_time = 0.0  # expire the body cache, if any
            with srv._cache_lock:
                srv._cache_body = b""
            return srv._metrics_body()

        assert b"span_adv_gauge 1.0" in scrape()  # nobody published yet
        ex.advanced_published()
        for _ in range(3):
            assert b"span_adv_gauge 1.0" in scrape()
        adv.set(2)
        ex.advanced_published()
        assert b"span_adv_gauge 2.0" in scrape()
        own = Server("127.0.0.1:0", gather=lambda: b"up 1\n",
                     metrics_cache_ttl_s=ttl)
        assert own._metrics_body() == b"up 1\n"
        # (`render` is a CPU stage: each span also carries its `cpu_s`)
        args = [{k: v for k, v in s["args"].items() if k != "cpu_s"}
                for s in rec.spans() if s["stage"] == mn.STAGE_RENDER]
    finally:
        initialize_recorder(capacity=old.capacity, enabled=old.enabled)
    assert args == [
        {"advanced": "rendered"}, {"advanced": "rendered"},
        {"advanced": "reused"}, {"advanced": "reused"},
        {"advanced": "rendered"}, {},
    ]
    assert _gather_counts(ex) == {"reused": 2.0, "rendered": 3.0}


@pytest.mark.parametrize("kind", KINDS)
def test_concurrent_gathers_never_keep_stale_bytes_under_a_new_generation(
        kind):
    """A publisher writes every series and declares the cycle, cycle
    after cycle, while more gatherers than cores gather: whatever a
    gatherer reads once cycle v has been declared holds no value older
    than v (a render that overlapped the writes may hold newer ones).
    Bytes kept under a generation they do not belong to would break
    it. A row table is written as the metric objects write it: the
    rows by number, in one ``update``."""
    import sys

    import numpy as np

    ex = Exporter()
    n_series, cycles = 300, 60
    if kind == "row_table":
        table = ex.new_adv_table("stress_adv_gauge", ["pod"])
        rows = np.array([table.set((f"p{i}",), 0.0)[0]
                         for i in range(n_series)])

        def write(v: int) -> None:
            assert table.update(rows, np.full(n_series, float(v))) == (
                n_series if v else 0)
    else:
        g = ex.new_adv_gauge("stress_adv_gauge", ["pod"])
        children = [g.labels(pod=f"p{i}") for i in range(n_series)]

        def write(v: int) -> None:
            for c in children:
                c.set(v)

    write(0)
    ex.advanced_published()
    declared = [0]
    stop = threading.Event()
    failures: list[str] = []

    def values(body: bytes) -> list[float]:
        return [float(line.rsplit(b" ", 1)[1])
                for line in body.splitlines()
                if line.startswith(b"stress_adv_gauge{")]

    def gatherer() -> None:
        while not stop.is_set():
            floor = declared[0]
            got = values(ex.gather_text())
            if len(got) != n_series or min(got) < floor:
                failures.append(f"floor {floor}, read {sorted(set(got))}")
                return

    def publisher() -> None:
        for v in range(1, cycles + 1):
            write(v)
            ex.advanced_published()
            declared[0] = v
            time.sleep(0.002)  # let gathers start between cycles

    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=gatherer)
               for _ in range((os.cpu_count() or 4) + 2)]
    pub = threading.Thread(target=publisher)
    try:
        for t in threads:
            t.start()
        pub.start()
        pub.join(60.0)
        assert not pub.is_alive()
    finally:
        stop.set()
        for t in threads:
            t.join(30.0)
        sys.setswitchinterval(old_interval)
    assert not any(t.is_alive() for t in threads)
    assert failures == []
    assert set(values(ex.gather_text())) == {float(cycles)}
    counts = _gather_counts(ex)
    assert counts["rendered"] >= 1 and counts["reused"] >= 1


def test_fast_renderer_matches_generate_latest():
    """render_exposition must emit BYTE-identical text to
    prometheus_client.generate_latest — it replaces the library on the
    scrape path purely for speed (the library burns ~1.1s per render at
    production cardinality on regex escaping)."""
    from prometheus_client.exposition import generate_latest

    from retina_tpu.exporter import render_exposition

    ex = Exporter()
    g = ex.new_gauge("rend_gauge", ["pod", "ns"])
    for i in range(200):
        g.labels(pod=f"pod-{i}", ns="team-a").set(i * 1.5)
    g.labels(pod='we"ird\\pod', ns="x\ny").set(1e9)
    c = ex.new_counter("rend_counter", ["stage"])
    c.labels(stage="s1").inc(42)
    c.labels(stage="s2").inc(0.5)
    h = ex.new_histogram("rend_hist", ["l"], buckets=[0.1, 1, 10])
    h.labels(l="a").observe(0.05)
    h.labels(l="a").observe(5.0)
    ex.new_gauge("rend_empty", [])  # family with a single sample
    for reg in (ex.default_registry,):
        assert render_exposition(reg) == generate_latest(reg)


def test_metrics_declarations():
    ex = Exporter()
    m = Metrics(ex)
    m.forward_count.labels(direction="ingress").set(10)
    m.lost_events.labels(stage="buffered", plugin="packetparser").inc(5)
    text = ex.gather_text().decode()
    assert 'networkobservability_forward_count{direction="ingress"} 10.0' in text
    assert "networkobservability_lost_events_counter_total" in text


# --------------------------------------------------------------- server
def test_server_endpoints():
    ex = Exporter()
    g = ex.new_gauge("test_served_gauge", [])
    g.set(5)
    ready = {"ok": False}
    srv = Server("127.0.0.1:0", exporter=ex, ready_check=lambda: ready["ok"])
    srv.expose_var("answer", lambda: 42)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "test_served_gauge 5.0" in body
        assert urllib.request.urlopen(f"{base}/healthz").status == 200
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/readyz")
        assert ei.value.code == 503
        ready["ok"] = True
        assert urllib.request.urlopen(f"{base}/readyz").status == 200
        import json

        doc = json.loads(urllib.request.urlopen(f"{base}/debug/vars").read())
        assert doc["answer"] == 42
    finally:
        srv.stop()


def test_metrics_render_cache():
    """/metrics renders are cached inside the TTL (rendering ~50k pod
    series is Python-heavy; gauges only change at publish cadence); on
    TTL expiry the scrape serves the STALE body immediately and a
    background re-render refreshes the cache — a scrape never waits on a
    render. TTL 0 renders inline every time."""
    calls = {"n": 0}

    def gather() -> bytes:
        calls["n"] += 1
        return b"cached_metric 1.0\n"

    srv = Server("127.0.0.1:0", gather=gather, metrics_cache_ttl_s=60.0)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        # start() pre-warmed the cache: every scrape inside the TTL is a
        # hit on that one render.
        for _ in range(3):
            assert b"cached_metric" in urllib.request.urlopen(
                f"{base}/metrics").read()
        assert calls["n"] == 1
        srv._cache_time = 0.0  # expire
        # Expired: the scrape still returns the stale body without
        # rendering inline; the background worker re-renders.
        assert b"cached_metric" in urllib.request.urlopen(
            f"{base}/metrics").read()
        deadline = time.monotonic() + 5.0
        while calls["n"] < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert calls["n"] == 2
    finally:
        srv.stop()

    calls["n"] = 0
    srv = Server("127.0.0.1:0", gather=gather, metrics_cache_ttl_s=0)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        urllib.request.urlopen(f"{base}/metrics").read()
        urllib.request.urlopen(f"{base}/metrics").read()
        assert calls["n"] == 2
    finally:
        srv.stop()


def test_metrics_render_failure_surfaces_after_grace():
    """A persistently failing renderer must eventually FAIL the scrape
    (alertable) instead of serving a frozen cached body forever."""
    state = {"fail": False}

    def gather() -> bytes:
        if state["fail"]:
            raise RuntimeError("gauge callback broke")
        return b"ok_metric 1.0\n"

    srv = Server("127.0.0.1:0", gather=gather, metrics_cache_ttl_s=0.05)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        assert b"ok_metric" in urllib.request.urlopen(
            f"{base}/metrics").read()
        state["fail"] = True
        # Within the grace window: stale body still served (kick +
        # background failure marks _render_failing).
        urllib.request.urlopen(f"{base}/metrics").read()
        deadline = time.monotonic() + 5
        while not srv._render_failing and time.monotonic() < deadline:
            urllib.request.urlopen(f"{base}/metrics").read()
            time.sleep(0.02)
        assert srv._render_failing
        # Past the grace window (10xTTL floor-capped at 10s): simulate
        # prolonged staleness-under-demand by back-dating the
        # stale-since clock; the scrape must then 500 (this fires for a
        # HANGING renderer too — the clock, not the exception, is the
        # signal).
        srv._stale_since = (srv._stale_since or time.monotonic()) - 60.0
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/metrics")
        assert ei.value.code == 500
    finally:
        srv.stop()


# --------------------------------------------------------------- common
def test_retina_endpoint_and_dirtycache():
    ep = RetinaEndpoint(
        name="web-0",
        namespace="default",
        ips=("10.0.0.5",),
        labels=(("app", "web"),),
        owner_refs=(("StatefulSet", "web"),),
    )
    assert ep.key() == "default/web-0"
    assert ep.workload() == "web"
    assert ep.labels_dict() == {"app": "web"}

    dc = DirtyCache()
    dc.to_add("k", ep)
    dc.to_delete("k", ep)  # delete supersedes add
    assert dc.get_add_list() == []
    assert dc.get_delete_list() == [ep]
    dc.clear_delete()
    assert dc.get_delete_list() == []


def test_retry_backoff():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert retry(flaky, attempts=5, base_delay_s=0.001) == "ok"
    assert calls["n"] == 3

    with pytest.raises(OSError):
        retry(lambda: (_ for _ in ()).throw(OSError("always")),
              attempts=2, base_delay_s=0.001)


# ------------------------------------------------------------ telemetry
def test_telemetry_heartbeat_and_noop():
    ex = Exporter()
    ex.new_gauge("test_card_gauge", ["x"]).labels(x="1").set(1)
    t = Telemetry(interval_s=1e9, exporter=ex)
    hb = t.heartbeat()
    assert hb["metrics_cardinality"] >= 1
    assert hb["rss_bytes"] > 0
    with t.perf_span("reconcile"):
        pass

    noop = new_telemetry(enabled=False)
    assert noop.heartbeat() == {}


# ---------------------------------------------- row tables (exporter)
def test_row_table_is_a_gauge_family_to_its_registry():
    """A SeriesTable renders, collects and registers as the Gauge it
    stands for: the same bytes through the join of its lines
    (render_rows), sample by sample through collect()
    (render_exposition) and through the library's generate_latest;
    label values escaped, labels sorted, a family without labels 0.0
    from birth, rows in first-set order and past the first allocation;
    plain collectors beside it in registration order; a second family
    of the name refused."""
    import numpy as np
    from prometheus_client import CollectorRegistry, Gauge
    from prometheus_client.exposition import generate_latest

    from retina_tpu.exporter import render_exposition, render_rows

    ex, ref = Exporter(), CollectorRegistry()
    before = ex.new_adv_gauge("tbl_before", ["z", "a"])
    Gauge("tbl_before", "tbl_before", ["z", "a"], registry=ref).labels(
        z="1", a="2").set(5)
    before.labels(z="1", a="2").set(5)
    table = ex.new_adv_table("tbl_rows", ["zone", "pod"], "rows\nof \\ it")
    plain = Gauge("tbl_rows", "rows\nof \\ it", ["zone", "pod"], registry=ref)
    bare = ex.new_adv_table("tbl_bare", [])
    Gauge("tbl_bare", "tbl_bare", registry=ref)
    ex.new_adv_gauge("tbl_after", []).set(2)
    Gauge("tbl_after", "tbl_after", registry=ref).set(2)
    values = [0, 1, 999999, 1000000, 12345678, 2.5, float("inf"), -3]
    for i in range(200):
        labels = (f"z{i % 3}", 'p"\\\n' if i == 7 else f"pod-{i}")
        v = values[i % len(values)]
        assert table.set(labels, float(v)) == (i, True)
        plain.labels(*labels).set(v)
    assert len(table) == 200 and len(bare) == 1
    assert table.set(("z1", "pod-1"), 1.0) == (1, False)  # unchanged
    assert table.set(("z1", "pod-1"), 4.0) == (1, True)
    plain.labels("z1", "pod-1").set(4)

    def same():
        want = generate_latest(ref)
        got = render_rows(ex.advanced_registry)
        assert got == render_exposition(ex.advanced_registry) == want
        assert generate_latest(ex.advanced_registry) == want

    same()
    # row 6 keeps its value; row 5 twice in one call: the last wins
    assert table.update(np.array([5, 6, 5]),
                        np.array([1.0, float("inf"), 7.0])) == 2
    plain.labels("z2", "pod-5").set(7)
    same()
    assert ex.advanced_registry.get_sample_value(
        "tbl_rows", {"zone": "z2", "pod": "pod-5"}) == 7.0
    assert ex.advanced_registry.get_sample_value("tbl_bare") == 0.0
    with pytest.raises(ValueError, match="Duplicated"):
        ex.new_adv_table("tbl_rows", ["zone"])
