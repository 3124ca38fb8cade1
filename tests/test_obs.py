"""Flight-recorder observability tier (retina_tpu/obs/).

Covers the PR-13 acceptance gates: the recorder's bounded-overhead
contract (<3% on a host-path probe), RFLT codec compatibility in both
directions around the optional trace-context header field, the debug
endpoints (/debug/trace Chrome JSON, /debug/profile single-flight +
cooldown + SHEDDING refusal), and the AOT disk-cache regression fix
(a second warm from the same cache dir deserializes everything —
misses == 0).
"""

import dataclasses
import json
import os
import time
import urllib.error
import urllib.request

import msgpack
import numpy as np
import pytest

from retina_tpu.config import Config
from retina_tpu.fleet.codec import (
    FleetSnapshot, decode_snapshot, encode_snapshot,
)
from retina_tpu.obs.debug import DebugObservability, thread_stacks
from retina_tpu.obs.recorder import (
    FlightRecorder, get_recorder, initialize_recorder,
)
from retina_tpu.runtime.overload import SHEDDING
from retina_tpu.server import Server
from retina_tpu.utils import metric_names as mn


# ------------------------------------------------------------ recorder

class TestFlightRecorder:
    def test_begin_record_span(self):
        rec = FlightRecorder(capacity=64)
        t0 = rec.begin()
        assert t0 > 0.0
        rec.record(mn.STAGE_HARVEST, t0, trace_id=7)
        (span,) = rec.spans()
        assert span["stage"] == mn.STAGE_HARVEST
        assert span["trace_id"] == 7
        assert span["t1"] >= span["t0"] == t0

    def test_sampling_gate(self):
        rec = FlightRecorder(capacity=64, sample_every=4)
        kept = 0
        for _ in range(20):
            t0 = rec.begin()
            rec.record(mn.STAGE_PUBLISH, t0)
            kept += bool(t0)
        assert kept == 5
        assert len(rec.spans()) == 5

    def test_disabled_recorder_records_nothing(self):
        rec = FlightRecorder(capacity=64, enabled=False)
        assert rec.begin() == 0.0
        rec.record(mn.STAGE_PUBLISH, time.perf_counter())
        assert rec.spans() == []

    def test_explicit_t1_bypasses_gate(self):
        # Sites that already hold both timestamps (transfer/step) pass
        # t1 explicitly; sampling never drops them.
        rec = FlightRecorder(capacity=64, sample_every=1000)
        rec.record(mn.STAGE_TRANSFER, 1.0, trace_id=3, t1=2.0)
        (span,) = rec.spans()
        assert span["t1"] - span["t0"] == 1.0

    def test_torn_slot_tolerated(self):
        rec = FlightRecorder(capacity=16)
        rec.record(mn.STAGE_HARVEST, 1.0, t1=2.0)
        ring = rec._ring()
        # Simulate a torn (half-written) slot: t1 behind t0.
        ring.slots[5][0] = mn.STAGE_PUBLISH
        ring.slots[5][1] = 9.0
        ring.slots[5][2] = 1.0
        assert [s["stage"] for s in rec.spans()] == [mn.STAGE_HARVEST]

    def test_ring_wraps_bounded(self):
        rec = FlightRecorder(capacity=16)
        for i in range(100):
            rec.record(mn.STAGE_PUBLISH, float(i), t1=float(i) + 0.5)
        spans = rec.spans()
        assert len(spans) == 16
        assert spans[-1]["t0"] == 99.0

    def test_ring_wrap_thousands_reads_well_formed(self):
        """Tier-1 wrap gate: thousands of REAL wraps on a tiny ring,
        then every read surface (spans/chrome_trace/stage_report) must
        stay well-formed — including with a torn slot present and with
        the count counter pushed past 1M (Python ints: the counter
        must never truncate or go negative, there is no 32-bit wrap)."""
        rec = FlightRecorder(capacity=16)
        rec._metrics_broken = True  # skip exposition: pure ring path
        n = 64_000  # 4000 full wraps of the 16-slot ring
        for i in range(n):
            # t0 strictly > 0.0 (0.0 is the sampled-out sentinel)
            rec.record(mn.STAGE_PUBLISH, i + 1.0, trace_id=i,
                       t1=i + 1.5)
        ring = rec._ring()
        assert ring.count == n  # exact, monotonic
        assert ring.pos == n % 16
        # Torn slot mid-ring: reader must skip it, writer never cares.
        ring.slots[3][0] = mn.STAGE_HARVEST
        ring.slots[3][1] = 9e9
        ring.slots[3][2] = 1.0
        spans = rec.spans()
        assert len(spans) == 15  # capacity minus the torn slot
        assert all(s["t1"] >= s["t0"] for s in spans)
        assert spans[-1]["trace_id"] == n - 1  # newest retained
        # Fabricate a multi-million historical count (a long soak's
        # magnitude): diagnostics must report it exactly.
        ring.count = 3_141_592_653
        assert rec.stats()["threads"][ring.name] == 3_141_592_653
        doc = rec.chrome_trace()
        assert len(json.loads(json.dumps(doc))["traceEvents"]) >= 15
        rep = rec.stage_report()
        assert rep[mn.STAGE_PUBLISH]["count"] == 15

    @pytest.mark.slow
    def test_ring_wrap_past_one_million_real(self):
        """>1M REAL spans through one 16-slot ring (the soak's order of
        magnitude, no fabricated counters): count stays exact, reads
        stay bounded and well-formed, the trace dump stays valid JSON."""
        rec = FlightRecorder(capacity=16)
        rec._metrics_broken = True
        n = 1_200_000
        for i in range(n):
            rec.record(mn.STAGE_PUBLISH, i + 1.0, trace_id=i,
                       t1=i + 1.5)
        ring = rec._ring()
        assert ring.count == n
        assert ring.pos == n % 16
        spans = rec.spans()
        assert len(spans) == 16  # bounded by capacity, not history
        assert [s["trace_id"] for s in spans] == list(
            range(n - 16, n)
        )
        assert all(s["t1"] > s["t0"] for s in spans)
        doc = json.loads(json.dumps(rec.chrome_trace()))
        assert len([e for e in doc["traceEvents"]
                    if e["ph"] == "X"]) == 16
        assert rec.stage_report()[mn.STAGE_PUBLISH]["count"] == 16

    def test_stage_report_percentiles(self):
        rec = FlightRecorder(capacity=256)
        for i in range(100):
            rec.record(mn.STAGE_DEVICE_STEP, 1.0,
                       t1=1.0 + (i + 1) / 1000)
        rep = rec.stage_report()
        stats = rep[mn.STAGE_DEVICE_STEP]
        assert stats["count"] == 100
        assert stats["p50_s"] == pytest.approx(0.051)
        assert stats["p99_s"] == pytest.approx(0.100)

    def test_stage_report_pipeline_order(self):
        rec = FlightRecorder(capacity=64)
        rec.record(mn.STAGE_PUBLISH, 1.0, t1=2.0)
        rec.record(mn.STAGE_GENERATOR_EMIT, 1.0, t1=2.0)
        assert list(rec.stage_report()) == [
            mn.STAGE_GENERATOR_EMIT, mn.STAGE_PUBLISH,
        ]

    def test_chrome_trace_shape(self):
        rec = FlightRecorder(capacity=64)
        rec.record(mn.STAGE_HARVEST, 1.0, trace_id=42, t1=1.5)
        doc = rec.chrome_trace()
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        metas = [e for e in events if e["ph"] == "M"]
        xs = [e for e in events if e["ph"] == "X"]
        assert len(metas) == 1 and len(xs) == 1
        assert xs[0]["name"] == mn.STAGE_HARVEST
        assert xs[0]["dur"] == pytest.approx(0.5e6)
        assert xs[0]["args"]["trace_id"] == 42

    def test_observes_stage_histogram(self):
        from retina_tpu.metrics import get_metrics

        rec = FlightRecorder(capacity=64)
        rec.record(mn.STAGE_WINDOW_CLOSE, 1.0, t1=1.25)
        child = get_metrics().stage_seconds.labels(
            stage=mn.STAGE_WINDOW_CLOSE
        )
        assert child._sum.get() == pytest.approx(0.25)

    def test_initialize_replaces_singleton(self):
        old = get_recorder()
        try:
            rec = initialize_recorder(capacity=32, sample_every=2,
                                      enabled=True)
            assert get_recorder() is rec
            assert rec.capacity == 32 and rec.sample_every == 2
        finally:
            initialize_recorder(capacity=old.capacity,
                                sample_every=old.sample_every,
                                enabled=old.enabled)

    @pytest.mark.load
    def test_overhead_under_three_percent(self):
        """The acceptance gate: recorder on vs off on a host-path
        probe shaped like a feed-worker flush (a chunky numpy quantum
        bracketed by one begin/record pair).

        The 1.03 gate is the contract and stays; min-of-5 absorbs
        per-iteration noise but a busy box can still skew one whole
        measurement block (a concurrent bench run stealing the core
        mid-block flaked this in the PR-17 suite run). Two defenses,
        both measurement-side: on/off samples INTERLEAVE so a noise
        burst lands on both sides of the ratio instead of inflating
        only the numerator (sequential blocks flaked twice in the
        PR-19 suite runs), and the block is retried up to 6 times with
        the BEST ratio judged — scheduler interference can only
        inflate the ratio, never deflate it, so taking the quietest
        attempt measures the recorder, not the neighbors."""
        a = np.random.default_rng(0).random((256, 256))

        def probe(rec, iters=200):
            t = time.perf_counter()
            for _ in range(iters):
                t0 = rec.begin()
                (a @ a).sum()
                rec.record(mn.STAGE_FEED_FILL, t0, trace_id=1)
            return time.perf_counter() - t

        on = FlightRecorder(capacity=1024, enabled=True)
        off = FlightRecorder(capacity=1024, enabled=False)
        probe(on, 20)
        probe(off, 20)  # warm caches / histogram child
        best = float("inf")
        for _attempt in range(6):
            t_on, t_off = float("inf"), float("inf")
            for _ in range(5):
                t_on = min(t_on, probe(on))
                t_off = min(t_off, probe(off))
            best = min(best, t_on / t_off)
            if best < 1.03:
                break
        assert best < 1.03, best


# ------------------------------------------- RFLT codec trace context

def _snap(trace=None):
    return FleetSnapshot(
        node="n0", tenant="t0", priority=1, epoch=17, seq=3,
        window_s=15.0, seeds={"flow": 1},
        arrays={
            "flow_cms": np.arange(8, dtype=np.uint32).reshape(2, 4),
            "totals": np.arange(8, dtype=np.uint32),
        },
        trace=trace,
    )


class TestCodecTraceContext:
    def test_round_trip_with_trace(self):
        snap = _snap(trace={"tid": 17, "node": "n0"})
        out = decode_snapshot(encode_snapshot(snap))
        assert out.trace == {"tid": 17, "node": "n0"}
        assert out.epoch == 17
        np.testing.assert_array_equal(
            out.arrays["flow_cms"], snap.arrays["flow_cms"]
        )

    def test_traceless_frame_byte_identical_to_legacy(self):
        """trace=None is omitted from the wire entirely, so encoders
        without the field produce the exact same bytes (old and new
        agents interop byte-for-byte)."""
        frame = encode_snapshot(_snap(trace=None))
        (hlen,) = np.frombuffer(frame[5:9], np.uint32)
        hdr = msgpack.unpackb(frame[9:9 + int(hlen)], raw=False)
        assert "trace" not in hdr
        out = decode_snapshot(frame)
        assert out.trace is None
        # Adding then removing the field reproduces the legacy bytes.
        assert frame == encode_snapshot(
            dataclasses.replace(_snap(trace={"tid": 1}), trace=None)
        )

    def test_old_decoder_shape_tolerates_unknown_header_keys(self):
        """Forward compatibility: the decoder ignores header keys it
        does not know — the same property that lets a pre-trace
        decoder accept frames from a trace-stamping shipper."""
        frame = encode_snapshot(_snap(trace={"tid": 17}))
        (hlen,) = np.frombuffer(frame[5:9], np.uint32)
        hdr = msgpack.unpackb(frame[9:9 + int(hlen)], raw=False)
        hdr["future_field"] = {"x": 1}
        new_hdr = msgpack.packb(hdr, use_bin_type=True)
        rebuilt = (
            frame[:5]
            + np.uint32(len(new_hdr)).tobytes()
            + new_hdr
            + frame[9 + int(hlen):]
        )
        out = decode_snapshot(rebuilt)
        assert out.trace == {"tid": 17}
        assert out.node == "n0"

    def test_malformed_trace_field_degrades_to_none(self):
        frame = encode_snapshot(_snap(trace=None))
        (hlen,) = np.frombuffer(frame[5:9], np.uint32)
        hdr = msgpack.unpackb(frame[9:9 + int(hlen)], raw=False)
        hdr["trace"] = "not-a-dict"
        new_hdr = msgpack.packb(hdr, use_bin_type=True)
        rebuilt = (
            frame[:5]
            + np.uint32(len(new_hdr)).tobytes()
            + new_hdr
            + frame[9 + int(hlen):]
        )
        assert decode_snapshot(rebuilt).trace is None


# ------------------------------------------------- debug HTTP surface

def _request(port, path, method="GET", timeout=60.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=b"" if method == "POST" else None,
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


class _Overload:
    def __init__(self, state):
        self.state = state


@pytest.fixture
def debug_srv(tmp_path):
    servers = []

    def make(overload=None, **cfg_kw):
        cfg = Config(
            profile_artifact_dir=str(tmp_path / "prof"),
            profile_max_seconds=0.5,
            profile_cooldown_s=0.2,
            **cfg_kw,
        )
        srv = Server("127.0.0.1:0")
        srv.start()
        servers.append(srv)
        dbg = DebugObservability(cfg, overload=overload)
        dbg.attach(srv)
        return srv, dbg

    yield make
    for s in servers:
        s.stop()


class TestDebugEndpoints:
    def test_trace_endpoint_serves_chrome_json(self, debug_srv):
        srv, dbg = debug_srv()
        dbg.recorder.record(mn.STAGE_HARVEST, 1.0, trace_id=5, t1=1.5)
        code, body = _request(srv.port, "/debug/trace?last=10")
        assert code == 200
        doc = json.loads(body)
        names = {e["name"] for e in doc["traceEvents"]
                 if e["ph"] == "X"}
        assert mn.STAGE_HARVEST in names

    def test_trace_endpoint_valid_json_after_ring_wrap(self, debug_srv):
        """/debug/trace must serve valid Chrome JSON after the ring has
        wrapped thousands of times (bounded body, newest spans only) —
        the soak hits this endpoint with span counts in the millions."""
        srv, dbg = debug_srv()
        dbg.recorder._metrics_broken = True
        for i in range(20_000):  # many wraps of the default ring
            dbg.recorder.record(mn.STAGE_PUBLISH, i + 1.0,
                                trace_id=i, t1=i + 1.5)
        code, body = _request(srv.port, "/debug/trace")
        assert code == 200
        doc = json.loads(body)  # raises = endpoint served torn JSON
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert 0 < len(xs) <= dbg.recorder.capacity
        assert all(e["dur"] >= 0 for e in xs)

    def test_trace_bad_last_is_400(self, debug_srv):
        srv, _ = debug_srv()
        code, _ = _request(srv.port, "/debug/trace?last=bogus")
        assert code == 400

    def test_trace_post_is_405(self, debug_srv):
        srv, _ = debug_srv()
        code, _ = _request(srv.port, "/debug/trace", method="POST")
        assert code == 405

    def test_profile_get_is_405(self, debug_srv):
        srv, _ = debug_srv()
        code, _ = _request(srv.port, "/debug/profile")
        assert code == 405

    def test_profile_session_writes_artifacts(self, debug_srv):
        srv, dbg = debug_srv()
        code, body = _request(
            srv.port, "/debug/profile?seconds=0.1", method="POST"
        )
        assert code == 200, body
        doc = json.loads(body)
        assert doc["seconds"] == pytest.approx(0.1)
        assert os.path.isfile(
            os.path.join(doc["artifact_dir"], "threads.txt")
        )
        assert dbg.sessions == 1

    def test_profile_cooldown_503(self, debug_srv):
        srv, _ = debug_srv()
        code, _ = _request(
            srv.port, "/debug/profile?seconds=0.1", method="POST"
        )
        assert code == 200
        code, body = _request(
            srv.port, "/debug/profile?seconds=0.1", method="POST"
        )
        assert code == 503
        assert json.loads(body)["error"] == "cooldown"

    def test_profile_shedding_503(self, debug_srv):
        srv, _ = debug_srv(overload=_Overload(SHEDDING))
        code, body = _request(
            srv.port, "/debug/profile?seconds=0.1", method="POST"
        )
        assert code == 503
        assert json.loads(body)["error"] == "shedding"

    def test_thread_stacks_sees_main(self):
        stacks = thread_stacks()
        assert any("MainThread" in name for name in stacks)


# ------------------------------------- AOT disk cache (satellite fix)

class TestAotDiskCacheWarm:
    def test_second_telemetry_warm_all_hits(self, tmp_path):
        """BENCH_r06 regression (hits=1 misses=26): the snapshot /
        fleet-export / invertible-decode / flat-snapshot programs never
        consulted the disk cache. A second warm from the same cache dir
        must deserialize every program — zero fresh compiles."""
        import jax

        from retina_tpu.models.identity import IdentityMap
        from retina_tpu.models.pipeline import PipelineConfig
        from retina_tpu.parallel import (
            ShardedTelemetry, make_mesh, partition_events,
        )
        from retina_tpu.parallel.telemetry import aot_disk_cache_stats

        cfg = PipelineConfig(
            n_pods=1 << 4, cms_width=1 << 6, topk_slots=1 << 4,
            hll_precision=4, hll_pod_precision=4,
            entropy_buckets=1 << 6, conntrack_slots=1 << 6,
            latency_slots=1 << 4,
        )
        mesh = make_mesh(jax.devices())
        ident = IdentityMap.build_host({0x0A000001: 1}, n_slots=64)
        rec = np.zeros((64, 16), np.uint32)

        def warm():
            st = ShardedTelemetry(cfg, mesh,
                                  aot_cache_dir=str(tmp_path))
            state = st.init_state()
            sb = partition_events(rec, st.n_devices, capacity=64)
            state, _ = st.step(
                state, sb.records, sb.n_valid, np.uint32(1), ident
            )
            state, _ = st.end_window(state)
            st.snapshot(state, 1)
            st.fleet_export(state)
            st.inv_decode(state)
            st.snapshot_host(state, 1)

        s0 = aot_disk_cache_stats()
        warm()
        s1 = aot_disk_cache_stats()
        assert s1["misses"] - s0["misses"] >= 6, (s0, s1)
        assert s1["errors"] == s0["errors"], (s0, s1)

        warm()  # fresh ShardedTelemetry = restart: in-memory caches gone
        s2 = aot_disk_cache_stats()
        assert s2["misses"] - s1["misses"] == 0, (s1, s2)
        assert s2["errors"] == s1["errors"], (s1, s2)
        assert s2["hits"] - s1["hits"] >= 6, (s1, s2)
        # Per-program attribution: every regressed tag now hits.
        for tag in ("snapshot", "fleet_export", "inv_decode",
                    "snapshot_flat"):
            assert s2["by_tag"][tag]["hits"] >= 1, (tag, s2)

    def test_subset_mesh_executable_reloads_and_runs(self, tmp_path):
        """The agent's restart path on a host with more devices than
        the mesh uses (``mesh_devices`` < local devices): the cached
        executable must come back bound to the mesh's own devices and
        RUN. Loaded without ``execution_devices`` it is bound to every
        device of the backend, deserializes without complaint and
        fails at its first call."""
        import jax

        from retina_tpu.models.pipeline import PipelineConfig
        from retina_tpu.parallel import ShardedTelemetry, make_mesh
        from retina_tpu.parallel.telemetry import aot_disk_cache_stats

        assert len(jax.devices()) >= 4
        cfg = PipelineConfig(
            n_pods=1 << 4, cms_width=1 << 6, topk_slots=1 << 4,
            hll_precision=4, hll_pod_precision=4,
            entropy_buckets=1 << 6, conntrack_slots=1 << 6,
            latency_slots=1 << 4,
        )
        mesh = make_mesh(jax.devices()[:2])

        def boot():
            st = ShardedTelemetry(cfg, mesh, aot_cache_dir=str(tmp_path))
            state = st.init_state()
            state, win = st.end_window(state)
            return np.asarray(win["entropy_bits"])

        s0 = aot_disk_cache_stats()
        first = boot()
        second = boot()  # deserialized: must execute, not just load
        s2 = aot_disk_cache_stats()
        assert s2["hits"] - s0["hits"] >= 1, (s0, s2)
        assert s2["errors"] == s0["errors"], (s0, s2)
        np.testing.assert_array_equal(first, second)

    def test_disk_key_names_devices_and_source(self, monkeypatch):
        """Same program, different device set or different package
        source => different cache file: an executable is never served
        to devices it was not compiled for, nor to code it was not
        compiled from."""
        import jax

        from retina_tpu.parallel import make_mesh, telemetry

        devs = jax.devices()
        path = lambda mesh: telemetry.aot_disk_path(
            "/c", mesh, "step", "sig", ("k",)
        )
        a = path(make_mesh(devs[:2]))
        assert a == path(make_mesh(devs[:2]))
        assert a != path(make_mesh(devs[1:3]))
        assert a != path(make_mesh(devs[:1]))
        assert path(None) == path(make_mesh(jax.local_devices()[:1]))
        monkeypatch.setattr(
            telemetry, "_source_fingerprint", lambda: "another-tree"
        )
        assert a != path(make_mesh(devs[:2]))

    def test_second_fold_warm_all_hits(self, tmp_path):
        """Same contract for the timetravel query programs (fold /
        extract), which live outside AotProgram."""
        import retina_tpu.timetravel.fold as fold
        from retina_tpu.parallel.telemetry import aot_disk_cache_stats

        fold.set_aot_cache_dir(str(tmp_path))
        try:
            slots = [
                {"flow_cms": np.ones((2, 32), np.uint32),
                 "hll_flows": np.ones((1, 16), np.uint8)}
                for _ in range(2)
            ]

            def warm():
                rf = fold.RangeFold()
                merged = rf.fold(slots, {"flow": 1, "hll_flows": 4})
                fold.range_extract(merged, {"flow": 1, "hll_flows": 4})

            s0 = aot_disk_cache_stats()
            warm()
            s1 = aot_disk_cache_stats()
            assert s1["misses"] - s0["misses"] >= 2, (s0, s1)

            fold._AOT_EXEC_CACHE.clear()  # simulate restart
            warm()
            s2 = aot_disk_cache_stats()
            assert s2["misses"] - s1["misses"] == 0, (s1, s2)
            assert s2["hits"] - s1["hits"] >= 2, (s1, s2)
            assert s2["by_tag"]["range_fold"]["hits"] >= 1
            assert s2["by_tag"]["range_extract"]["hits"] >= 1
        finally:
            fold.set_aot_cache_dir("")
            fold._AOT_EXEC_CACHE.clear()
