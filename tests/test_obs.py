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
import threading
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

def _put(rec, stage, t0, t1, trace_id=-1, parent=0, **args):
    """A hand-made finished span, through the recorder's one slot
    writer (what ``Span.end`` calls)."""
    rec._commit(stage, t0, t1, trace_id, next(rec._ids), parent,
                args or None)


class TestFlightRecorder:
    def test_span_records(self):
        rec = FlightRecorder(capacity=64)
        with rec.span(mn.STAGE_HARVEST, trace_id=7) as sp:
            assert sp.id > 0
        (span,) = rec.spans()
        assert span["stage"] == mn.STAGE_HARVEST
        assert span["trace_id"] == 7
        assert span["id"] == sp.id and span["parent"] == 0
        assert span["t1"] >= span["t0"] == sp.t0

    def test_span_end_is_explicit_and_idempotent(self):
        rec = FlightRecorder(capacity=64)
        sp = rec.span(mn.STAGE_DEVICE_STEP, trace_id=3)
        assert rec.spans() == []  # open: nothing in the ring yet
        assert sp.end(n_steps=2) > 0.0
        assert sp.end() == 0.0  # a second end writes nothing
        (span,) = rec.spans()
        assert span["args"] == {"n_steps": 2}

    def test_nested_span_names_its_parent(self):
        rec = FlightRecorder(capacity=64)
        with rec.span(mn.STAGE_POD_PUBLISH, trace_id=9) as outer:
            assert rec.current_id() == outer.id
            with rec.span(mn.STAGE_SNAPSHOT, trace_id=9) as mid:
                with rec.span(mn.STAGE_SNAPSHOT_FETCH, trace_id=9) as sp:
                    sp.set(ready_wait_s=0.25)
            # A span opened and ended by hand is no one's parent.
            loose = rec.span(mn.STAGE_RENDER)
            assert rec.current_id() == outer.id
            loose.end()
        assert rec.current_id() == 0
        by = {s["stage"]: s for s in rec.spans()}
        assert by[mn.STAGE_SNAPSHOT]["parent"] == outer.id
        assert by[mn.STAGE_SNAPSHOT_FETCH]["parent"] == mid.id
        fetch = dict(by[mn.STAGE_SNAPSHOT_FETCH]["args"])
        assert fetch.pop("cpu_s") >= 0.0  # one of metric_names.CPU_STAGES
        assert fetch == {"ready_wait_s": 0.25}
        assert by[mn.STAGE_RENDER]["parent"] == outer.id
        assert by[mn.STAGE_POD_PUBLISH]["parent"] == 0

    def test_a_stall_is_written_after_the_fact(self):
        """The one post-hoc form: a span that is over when it is
        written lands where every other does: ``spans()``, the Chrome
        trace, ``tpu_stage_seconds{stage="stall"}``."""
        from retina_tpu.metrics import get_metrics

        rec = FlightRecorder(capacity=64)
        hist = get_metrics().stage_seconds.labels(stage=mn.STAGE_STALL)
        n0, s0 = sum(b.get() for b in hist._buckets), hist._sum.get()
        sid = rec.post_hoc(mn.STAGE_STALL, 100.0, 102.3, parent=5,
                           cause=mn.STALL_PAUSED, gap_s=2.3)
        (span,) = rec.spans()
        assert span["id"] == sid > 0 and span["parent"] == 5
        assert (span["t0"], span["t1"]) == (100.0, 102.3)
        assert span["thread"] == threading.current_thread().name
        assert span["args"] == {"cause": mn.STALL_PAUSED, "gap_s": 2.3}
        (ev,) = [e for e in rec.chrome_trace()["traceEvents"]
                 if e["ph"] == "X"]
        assert ev["name"] == mn.STAGE_STALL
        assert ev["dur"] == pytest.approx(2.3e6)
        assert ev["args"]["cause"] == mn.STALL_PAUSED
        assert sum(b.get() for b in hist._buckets) - n0 == 1
        assert hist._sum.get() - s0 == pytest.approx(2.3)
        assert list(rec.stage_report()) == [mn.STAGE_STALL]
        # Nobody ran it, so it has no CPU of its own to read.
        assert mn.STAGE_STALL in mn.STAGES
        assert mn.STAGE_STALL not in mn.CPU_STAGES
        assert "cpu_s" not in span["args"]
        off = FlightRecorder(capacity=64, enabled=False)
        assert off.post_hoc(mn.STAGE_STALL, 1.0, 2.0) == 0
        assert off.spans() == []

    def test_span_ended_on_another_thread(self):
        """The engine's device_step: opened on the proxy thread, closed
        by the completion thread, parent named explicitly."""
        rec = FlightRecorder(capacity=64)
        sp = rec.span(mn.STAGE_DEVICE_STEP, trace_id=4, parent=77)
        t = threading.Thread(target=sp.end, kwargs={"n_steps": 3},
                             name="closer")
        t.start()
        t.join()
        (span,) = rec.spans()
        assert span["thread"] == "closer"
        assert span["parent"] == 77 and span["args"]["n_steps"] == 3

    def test_span_reaches_ring_and_profiler_session(self, tmp_path):
        """One span, two clocks: the ring slot, and a TraceAnnotation in
        the host plane of a running profiler session, with the same
        trace id, span id and parent."""
        import glob

        import jax
        from jax.profiler import ProfileData

        rec = FlightRecorder(capacity=64)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            def work():
                with rec.span(mn.STAGE_POD_PUBLISH, trace_id=41) as outer:
                    with rec.span(mn.STAGE_SNAPSHOT, trace_id=41) as sp:
                        time.sleep(0.01)
                        sp.set(events_included=5)
                box.update(outer=outer.id, inner=sp.id)

            box = {}
            t = threading.Thread(target=work, name="publisher")
            t.start()  # not the main thread
            t.join()
        finally:
            jax.profiler.stop_trace()
        ring = {s["stage"]: s for s in rec.spans()}
        assert ring[mn.STAGE_SNAPSHOT]["parent"] == box["outer"]
        (path,) = glob.glob(
            str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
        )
        found = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("retina:"):
                        found[e.name] = (dict(e.stats), e.duration_ns)
        stats, dur = found["retina:" + mn.STAGE_SNAPSHOT]
        assert stats["trace_id"] == 41
        assert stats["span"] == box["inner"]
        assert stats["parent"] == box["outer"]
        assert stats["events_included"] == 5
        assert dur >= 10e6
        outer_stats, outer_dur = found["retina:" + mn.STAGE_POD_PUBLISH]
        assert outer_stats["parent"] == 0 and outer_dur >= dur

    def test_disabled_recorder_records_nothing(self):
        rec = FlightRecorder(capacity=64, enabled=False)
        with rec.span(mn.STAGE_PUBLISH) as sp:
            sp.set(x=1)
        assert sp.id == 0 and sp.end(n=1) == 0.0
        assert rec.spans() == []

    def test_torn_slot_tolerated(self):
        rec = FlightRecorder(capacity=16)
        _put(rec, mn.STAGE_HARVEST, 1.0, 2.0)
        ring = rec._ring()
        # Simulate a torn (half-written) slot: t1 behind t0.
        ring.slots[5][0] = mn.STAGE_PUBLISH
        ring.slots[5][1] = 9.0
        ring.slots[5][2] = 1.0
        assert [s["stage"] for s in rec.spans()] == [mn.STAGE_HARVEST]

    def test_ring_wraps_bounded(self):
        rec = FlightRecorder(capacity=16)
        for i in range(100):
            _put(rec, mn.STAGE_PUBLISH, float(i), float(i) + 0.5)
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
            _put(rec, mn.STAGE_PUBLISH, i + 1.0, i + 1.5, trace_id=i)
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
            _put(rec, mn.STAGE_PUBLISH, i + 1.0, i + 1.5, trace_id=i)
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
            _put(rec, mn.STAGE_DEVICE_STEP, 1.0, 1.0 + (i + 1) / 1000)
        rep = rec.stage_report()
        stats = rep[mn.STAGE_DEVICE_STEP]
        assert stats["count"] == 100
        assert stats["p50_s"] == pytest.approx(0.051)
        assert stats["p99_s"] == pytest.approx(0.100)

    def test_stage_report_pipeline_order(self):
        rec = FlightRecorder(capacity=64)
        _put(rec, mn.STAGE_PUBLISH, 1.0, 2.0)
        _put(rec, mn.STAGE_DISTRIBUTOR_DEAL, 1.0, 2.0)
        assert list(rec.stage_report()) == [
            mn.STAGE_DISTRIBUTOR_DEAL, mn.STAGE_PUBLISH,
        ]

    def test_stage_report_of_one_epoch(self):
        rec = FlightRecorder(capacity=64)
        _put(rec, mn.STAGE_HARVEST, 1.0, 2.0, trace_id=5)
        _put(rec, mn.STAGE_HARVEST, 2.0, 4.0, trace_id=6)
        _put(rec, mn.STAGE_PUBLISH, 4.0, 4.5, trace_id=6)
        rep = rec.stage_report(trace_id=6)
        assert rep[mn.STAGE_HARVEST]["total_s"] == 2.0
        assert list(rep) == [mn.STAGE_HARVEST, mn.STAGE_PUBLISH]
        assert [s["trace_id"] for s in rec.spans(trace_id=5)] == [5]

    def test_chrome_trace_shape(self):
        rec = FlightRecorder(capacity=64)
        _put(rec, mn.STAGE_HARVEST, 1.0, 1.5, trace_id=42, parent=8,
             ready_wait_s=0.4)
        doc = rec.chrome_trace()
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        metas = [e for e in events if e["ph"] == "M"]
        xs = [e for e in events if e["ph"] == "X"]
        assert len(metas) == 1 and len(xs) == 1
        assert xs[0]["name"] == mn.STAGE_HARVEST
        assert xs[0]["dur"] == pytest.approx(0.5e6)
        assert xs[0]["args"]["trace_id"] == 42
        assert xs[0]["args"]["parent"] == 8
        assert xs[0]["args"]["ready_wait_s"] == 0.4

    def test_observes_stage_histogram(self):
        from retina_tpu.metrics import get_metrics

        rec = FlightRecorder(capacity=64)
        child = get_metrics().stage_seconds.labels(
            stage=mn.STAGE_WINDOW_CLOSE
        )
        before = child._sum.get()
        _put(rec, mn.STAGE_WINDOW_CLOSE, 1.0, 1.25)
        assert child._sum.get() - before == pytest.approx(0.25)

    def test_initialize_replaces_singleton(self):
        old = get_recorder()
        try:
            rec = initialize_recorder(capacity=32, enabled=True)
            assert get_recorder() is rec
            assert rec.capacity == 32
        finally:
            initialize_recorder(capacity=old.capacity,
                                enabled=old.enabled)

    def test_short_lived_threads_share_one_ring(self):
        """A thread that lives for one request passes ``shared=True``
        and leaves no ring behind (a ring per scrape would be ~500 a
        benchmark run, each 4,096 slots, never freed)."""
        rec = FlightRecorder(capacity=64)
        n_rings = len(rec._rings)

        def one_request():
            with rec.span(mn.STAGE_RENDER, shared=True):
                pass

        for _ in range(50):
            t = threading.Thread(target=one_request)
            t.start()
            t.join()
        assert len(rec._rings) == n_rings
        spans = rec.spans()
        assert len(spans) == 50
        assert {s["thread"] for s in spans} == {"shared"}

    @pytest.mark.load
    def test_overhead_under_three_percent(self):
        """The acceptance gate: recorder on vs off on a host-path
        probe shaped like a feed-worker flush (a chunky numpy quantum
        inside one span, which with JAX in the process also opens an
        inactive profiler annotation).

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
        import jax  # noqa: F401 — the annotation is part of the cost

        a = np.random.default_rng(0).random((256, 256))

        def probe(rec, iters=200):
            t = time.perf_counter()
            for _ in range(iters):
                with rec.span(mn.STAGE_FEED_FILL, trace_id=1):
                    (a @ a).sum()
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
        _put(dbg.recorder, mn.STAGE_HARVEST, 1.0, 1.5, trace_id=5)
        code, body = _request(srv.port, "/debug/trace?last=10")
        assert code == 200
        doc = json.loads(body)
        names = {e["name"] for e in doc["traceEvents"]
                 if e["ph"] == "X"}
        assert mn.STAGE_HARVEST in names

    def test_trace_endpoint_one_epoch_with_stage_report(self, debug_srv):
        srv, dbg = debug_srv(trace_ring_spans=64)
        dbg.recorder = FlightRecorder(capacity=64)
        _put(dbg.recorder, mn.STAGE_HARVEST, 1.0, 1.5, trace_id=5)
        _put(dbg.recorder, mn.STAGE_HARVEST, 2.0, 3.0, trace_id=6)
        _put(dbg.recorder, mn.STAGE_POD_PUBLISH, 2.0, 2.25, trace_id=6,
             events_included=11)
        code, body = _request(srv.port, "/debug/trace?epoch=6")
        assert code == 200
        doc = json.loads(body)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["args"]["trace_id"] for e in xs} == {6}
        assert doc["stageReport"][mn.STAGE_HARVEST]["total_s"] == 1.0
        assert doc["stageReport"][mn.STAGE_POD_PUBLISH]["count"] == 1
        code, _ = _request(srv.port, "/debug/trace?epoch=x")
        assert code == 400

    def test_trace_endpoint_valid_json_after_ring_wrap(self, debug_srv):
        """/debug/trace must serve valid Chrome JSON after the ring has
        wrapped thousands of times (bounded body, newest spans only) —
        the soak hits this endpoint with span counts in the millions."""
        srv, dbg = debug_srv()
        dbg.recorder._metrics_broken = True
        for i in range(20_000):  # many wraps of the default ring
            _put(dbg.recorder, mn.STAGE_PUBLISH, i + 1.0, i + 1.5,
                 trace_id=i)
        code, body = _request(srv.port, "/debug/trace")
        assert code == 200
        doc = json.loads(body)  # raises = endpoint served torn JSON
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        # Bounded by the rings, not by the history: this thread's ring
        # is full, and the process recorder may hold other threads'.
        assert dbg.recorder.capacity <= len(xs) <= (
            dbg.recorder.capacity * len(dbg.recorder._rings)
        )
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
        from retina_tpu.parallel import telemetry
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

        # The operator scopes come from the executable's own text, so
        # a restart that compiles nothing still knows them.
        scopes1 = telemetry.op_scope_map()
        with telemetry._OP_SCOPE_LOCK:
            telemetry._OP_SCOPE_MAP.clear()
        warm()  # fresh ShardedTelemetry = restart: in-memory caches gone
        s2 = aot_disk_cache_stats()
        scopes2 = telemetry.op_scope_map()
        assert {"cms_flow_hh", "conntrack", "hll"} <= set(
            scopes2["jit_local_step"].values()
        )
        assert all(scopes2[m] == scopes1[m] for m in scopes2)
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


# ------------------------------------------------- device proxy, visible

class _FakeArray:
    """Stands for a device array: ready when told."""

    def __init__(self, polls_until_ready=0):
        self.polls = 0
        self._until = polls_until_ready
        self.gate = threading.Event()

    def is_ready(self):
        self.polls += 1
        return self.polls > self._until

    def block_until_ready(self):
        self.gate.wait(10.0)
        return self

    def __array__(self, dtype=None, copy=None):
        return np.arange(3, dtype=np.float32)


@pytest.fixture
def fresh_recorder():
    old = get_recorder()
    rec = initialize_recorder(capacity=256, enabled=True)
    yield rec
    initialize_recorder(capacity=old.capacity, enabled=old.enabled)


def _hist_count(metric, kind) -> float:
    """``<metric>_count{kind=...}``: what the exposition sums."""
    return sum(b.get() for b in metric.labels(kind=kind)._buckets)


class TestDeviceProxyVisible:
    def test_counts_wait_run_depth_and_calls_per_kind(
        self, fresh_recorder
    ):
        from retina_tpu.metrics import get_metrics
        from retina_tpu.utils.device_proxy import (
            fence, run_on_device, submit_on_device,
        )

        m = get_metrics()

        def val(metric, kind):
            return metric.labels(kind=kind)._sum.get()

        calls0 = _hist_count(m.proxy_run_seconds, mn.KIND_TABLE)
        wait0 = val(m.proxy_wait_seconds, mn.KIND_TABLE)
        run0 = val(m.proxy_run_seconds, mn.KIND_STEP)
        gate = threading.Event()
        # Other tests' engines may still be proxying in this process:
        # this test's calls are told apart by the parent they name.
        mine = 987654321
        # A step holds the proxy; two table calls queue behind it.
        submit_on_device(gate.wait, 10.0, kind=mn.KIND_STEP, parent=mine)
        out = []
        threads = [
            threading.Thread(
                target=lambda: out.append(run_on_device(
                    m.proxy_queue_depth._value.get, kind=mn.KIND_TABLE,
                    parent=mine,
                ))
            )
            for _ in range(2)
        ]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 5.0
        while (m.proxy_queue_depth._value.get() < 2
               and time.monotonic() < deadline):
            time.sleep(0.005)
        assert m.proxy_queue_depth._value.get() >= 2  # both queued
        time.sleep(0.05)
        gate.set()
        for t in threads:
            t.join()
        assert fence(5.0)
        # The first table call ran with the other still queued.
        assert len(out) == 2 and max(out) >= 1.0
        assert _hist_count(m.proxy_run_seconds, mn.KIND_TABLE) - calls0 >= 2
        assert val(m.proxy_wait_seconds, mn.KIND_TABLE) - wait0 >= 0.08
        assert val(m.proxy_run_seconds, mn.KIND_STEP) - run0 >= 0.04
        runs = [s for s in fresh_recorder.spans()
                if s["stage"] == mn.STAGE_PROXY_RUN
                and s["parent"] == mine]
        kinds = [s["args"]["kind"] for s in runs]
        assert kinds.count(mn.KIND_TABLE) == 2
        assert kinds.count(mn.KIND_STEP) == 1
        waited = [s["args"]["wait_s"] for s in runs
                  if s["args"]["kind"] == mn.KIND_TABLE]
        assert min(waited) >= 0.04
        assert {s["thread"] for s in runs} == {"device-proxy"}

    def test_a_recorder_that_raises_does_not_take_down_the_proxy(
        self, monkeypatch
    ):
        """Every ``run_on_device`` waits for the one proxy thread: a
        span or an annotation that cannot be opened or closed leaves
        the call unmarked, and the call still runs."""
        from retina_tpu.utils import device_proxy
        from retina_tpu.utils.device_proxy import run_on_device

        def boom(*a, **k):
            raise RuntimeError("no recorder today")

        class Down:
            span = staticmethod(boom)

            @staticmethod
            def current_id():
                return 0

        class BadClose:
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                raise RuntimeError("cannot close")

        monkeypatch.setattr(device_proxy, "get_recorder", Down)
        monkeypatch.setattr(device_proxy, "annotate", boom)
        assert run_on_device(lambda: 7, kind=mn.KIND_TABLE) == 7
        time.sleep(0.12)  # idle slices whose annotation raises
        assert run_on_device(lambda: 8, kind=mn.KIND_POLL) == 8
        Down.span = staticmethod(lambda *a, **k: BadClose())
        monkeypatch.setattr(device_proxy, "annotate",
                            lambda *a, **k: BadClose())
        assert run_on_device(lambda: 9, kind=mn.KIND_TABLE) == 9
        time.sleep(0.12)
        assert run_on_device(lambda: 10, kind=mn.KIND_POLL) == 10
        assert device_proxy._thread.is_alive()

    def test_proxied_call_names_the_span_that_caused_it(
        self, fresh_recorder
    ):
        from retina_tpu.utils.device_proxy import run_on_device

        rec = fresh_recorder
        with rec.span(mn.STAGE_SNAPSHOT_DISPATCH, trace_id=8) as sp:
            inner = run_on_device(rec.current_id, kind=mn.KIND_SNAPSHOT)
        (run,) = [s for s in rec.spans()
                  if s["stage"] == mn.STAGE_PROXY_RUN
                  and s["parent"] == sp.id]
        assert run["args"]["kind"] == mn.KIND_SNAPSHOT
        # What runs on the proxy is a child of the call that runs it.
        assert inner == run["id"]
        run_on_device(lambda: None, kind=mn.KIND_OTHER, parent=123123)
        assert [s["stage"] for s in rec.spans()
                if s["parent"] == 123123] == [mn.STAGE_PROXY_RUN]

    def test_poll_is_counted_and_writes_no_ring_slot(
        self, fresh_recorder
    ):
        from retina_tpu.metrics import get_metrics
        from retina_tpu.utils.device_proxy import fetch_on_device

        def polls():  # tpu_proxy_run_seconds_count{kind="poll"}
            return _hist_count(get_metrics().proxy_run_seconds,
                               mn.KIND_POLL)

        before = polls()
        arr = _FakeArray(polls_until_ready=3)
        timing = {}
        host = fetch_on_device(arr, poll_s=0.01, timing=timing)
        assert host.tolist() == [0.0, 1.0, 2.0]
        assert polls() - before >= 4  # three no, one yes
        with fresh_recorder.span(mn.STAGE_HARVEST) as sp:
            before_n = polls()
            fetch_on_device(_FakeArray(polls_until_ready=2), poll_s=0.01)
        assert polls() - before_n >= 3
        kinds = [s["args"]["kind"] for s in fresh_recorder.spans()
                 if s["stage"] == mn.STAGE_PROXY_RUN
                 and s["parent"] == sp.id]
        assert kinds == [mn.KIND_FETCH]  # the copy; no poll
        assert timing["ready_wait_s"] >= 0.03
        assert 0.0 <= timing["copy_s"] < timing["ready_wait_s"]

    def test_completion_closes_span_only_when_ready_off_the_proxy(
        self, fresh_recorder
    ):
        """The step timer: the span handed over with a dispatched
        output stays open until the device is done with it, and is
        closed on the completion thread, never on the proxy's."""
        from retina_tpu.utils.device_proxy import on_ready, run_on_device

        rec = fresh_recorder
        out = _FakeArray()
        seen = {}
        done = threading.Event()

        def dispatch():
            span = rec.span(mn.STAGE_DEVICE_STEP, trace_id=2, parent=0)

            def finish(err):
                seen["thread"] = threading.current_thread().name
                seen["err"] = err
                seen["dt"] = span.end(n_steps=4)
                done.set()

            on_ready(out, finish)  # returns at once, on the proxy
            return threading.current_thread().name

        assert run_on_device(dispatch, kind=mn.KIND_STEP) == "device-proxy"
        time.sleep(0.05)
        steps = [s for s in rec.spans()
                 if s["stage"] == mn.STAGE_DEVICE_STEP]
        assert steps == [] and not done.is_set()  # device not done
        out.gate.set()
        assert done.wait(5.0)
        (step,) = [s for s in rec.spans()
                   if s["stage"] == mn.STAGE_DEVICE_STEP]
        assert seen["thread"] == "device-completion" == step["thread"]
        assert seen["err"] is None and seen["dt"] >= 0.05
        assert step["args"] == {"n_steps": 4}

    def test_a_call_that_keeps_the_proxy_is_a_thread_stall_with_its_kind(
        self, fresh_recorder
    ):
        """The proxy's cell beats at the start of a call with its kind
        and parks at its end: a call that blocks (here on an event the
        test holds) is found by the watchdog's scan a second in, and
        its ``stall`` span ends where the call did, inside the call's
        ``proxy_run`` span."""
        from retina_tpu.runtime.supervisor import Supervisor
        from retina_tpu.utils import device_proxy
        from retina_tpu.utils.device_proxy import fence, submit_on_device

        assert fence(5.0)
        sup = Supervisor()
        hb, hb_ready = (sup.adopt(c) for c in device_proxy.HEARTBEATS)
        assert (hb.name, hb_ready.name) == ("device-proxy",
                                            "device-completion")
        gate, inside = threading.Event(), threading.Event()

        def held():
            inside.set()
            gate.wait(10.0)

        mine = 424242
        submit_on_device(held, kind=mn.KIND_STEP, parent=mine)
        assert inside.wait(5.0)
        assert not hb.parked and hb.what == mn.KIND_STEP and hb.span > 0
        began = hb._last
        # The scan, a second and more into the call, by a clock reading
        # handed in: nothing sleeps.
        assert sup.scan_once(now=began + 0.9) == [] and not sup._open
        sup.scan_once(now=began + 1.6)
        assert list(sup._open) == ["device-proxy"]
        gate.set()
        assert fence(5.0)
        assert hb.parked
        sup.scan_once(now=began + 2.1)
        (run,) = [s for s in fresh_recorder.spans()
                  if s["stage"] == mn.STAGE_PROXY_RUN
                  and s["parent"] == mine]
        (st,) = [s for s in fresh_recorder.spans()
                 if s["stage"] == mn.STAGE_STALL]
        assert st["args"]["cause"] == mn.STALL_THREAD
        assert st["args"]["thread"] == "device-proxy"
        assert st["args"]["kind"] == mn.KIND_STEP
        assert st["args"]["in_span"] == run["id"] == st["parent"]
        # Its ends are the proxy's own readings: the start and the end
        # of the call, which the span of the call holds too.
        assert st["t0"] == began
        assert st["t0"] == pytest.approx(run["t0"], abs=0.01)
        assert st["t0"] < st["t1"] <= run["t1"]
        # The 30 s deadline never applies to the proxy's cells.
        assert sup.scan_once(now=began + 3600.0) == []

    def test_the_cells_cost_the_proxy_no_clock_reading(
        self, monkeypatch, fresh_recorder
    ):
        """A call through the proxy read ``perf_counter`` three times
        before its thread had a liveness cell (enqueue, start, end) and
        reads it three times now: the cell is handed the start and the
        end. The cell's own clock is never read."""
        import types

        from retina_tpu.utils import device_proxy
        from retina_tpu.utils.device_proxy import fence, run_on_device

        assert fence(5.0)
        reads, own = [], []
        counting = types.SimpleNamespace(
            perf_counter=lambda: (reads.append(1), time.perf_counter())[1])
        monkeypatch.setattr(device_proxy, "time", counting)
        monkeypatch.setattr(
            device_proxy._hb, "_clock",
            lambda: (own.append(1), time.perf_counter())[1])
        for _ in range(20):
            assert run_on_device(lambda: 5, kind=mn.KIND_TABLE) == 5
            run_on_device(lambda: None, kind=mn.KIND_POLL)
        assert len(reads) == 3 * 40 and own == []
        assert device_proxy._hb.parked

    def test_the_completion_thread_beats_while_it_waits_for_the_device(
        self
    ):
        from retina_tpu.utils import device_proxy
        from retina_tpu.utils.device_proxy import on_ready

        out, done = _FakeArray(), threading.Event()
        on_ready(out, lambda err: done.set())
        hb = device_proxy._hb_ready
        deadline = time.monotonic() + 5.0
        while hb.parked and time.monotonic() < deadline:
            time.sleep(0.001)
        assert not hb.parked  # mid-work: in block_until_ready
        out.gate.set()
        assert done.wait(5.0)
        while not hb.parked and time.monotonic() < deadline:
            time.sleep(0.001)
        assert hb.parked  # back in its queue

    def test_completion_hands_a_failed_wait_to_the_callback(self):
        from retina_tpu.utils.device_proxy import on_ready

        class Broken:
            def block_until_ready(self):
                raise RuntimeError("device gone")

        got = []
        done = threading.Event()
        on_ready(Broken(), lambda err: (got.append(err), done.set()))
        assert done.wait(5.0)
        assert isinstance(got[0], RuntimeError)


# ------------------------------------------------ publish watermark

class TestPublishWatermark:
    ACCEPTS = [(1.0, 10), (2.0, 20), (3.5, 30)]

    @pytest.mark.parametrize("held, want", [
        (0, 1.0),     # holds nothing: the first block is the oldest
        (9, 1.0),     # part of a block is not the block
        (10, 2.0),    # first block whole: the second is unheld
        (15, 2.0),
        (20, 3.5),
        (30, None),   # holds every accepted event
        (45, None),   # direct step_records callers bypass the sink
    ])
    def test_oldest_unheld_on_a_hand_made_ring(self, held, want):
        from retina_tpu.plugins.api import oldest_unheld

        assert oldest_unheld(self.ACCEPTS, held) == want

    def test_empty_ring_holds_all(self):
        from retina_tpu.plugins.api import oldest_unheld

        assert oldest_unheld([], 0) is None

    def test_sink_stamps_accepts_and_forgets_old_ones(self):
        from retina_tpu.plugins.api import QueueSink

        sink = QueueSink(max_blocks=2)
        sink.ACCEPT_RING = 4
        sink._accepts = type(sink._accepts)(maxlen=4)
        t0 = time.monotonic()
        assert sink.write_records(np.zeros((5, 16), np.uint32), "p") == 5
        assert sink.write_records(np.zeros((7, 16), np.uint32), "p") == 7
        # Full queue: refused, and not stamped as accepted.
        assert sink.write_records(np.zeros((3, 16), np.uint32), "p") == 0
        assert [c for _, c in sink._accepts] == [5, 12]
        assert t0 <= sink.oldest_unheld(0) <= sink.oldest_unheld(5)
        assert sink.oldest_unheld(12) is None
        for _ in range(6):
            sink.drain()
            sink.write_records(np.zeros((1, 16), np.uint32), "p")
        # The ring forgot the first accepts: the oldest it knows.
        assert len(sink._accepts) == 4
        assert sink.oldest_unheld(0) == sink._accepts[0][0]


# ---------------------------------------------- render span, ring count

def test_render_span_leaves_no_ring_per_request(fresh_recorder):
    """ThreadingHTTPServer starts a thread per request; with no render
    cache every scrape renders on its handler thread. The render span
    is written to the shared ring: the ring count stays flat."""
    import http.client

    srv = Server("127.0.0.1:0", gather=lambda: b"up 1\n",
                 metrics_cache_ttl_s=0.0)
    srv.start()
    try:
        _request(srv.port, "/metrics")
        n_rings = len(fresh_recorder._rings)
        for _ in range(1000):
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=10.0)
            conn.request("GET", "/metrics")
            assert conn.getresponse().read() == b"up 1\n"
            conn.close()
    finally:
        srv.stop()
    assert len(fresh_recorder._rings) == n_rings
    renders = [s for s in fresh_recorder.spans()
               if s["stage"] == mn.STAGE_RENDER]
    assert len(renders) == fresh_recorder.capacity  # 1,001 through 256
    assert fresh_recorder._shared.count == 1001


def test_pprof_profile_route_is_gone():
    srv = Server("127.0.0.1:0", gather=lambda: b"up 1\n")
    srv.start()
    try:
        code, _ = _request(srv.port, "/debug/pprof/profile?seconds=0.1")
        assert code == 404
        code, _ = _request(srv.port, "/debug/pprof/heap")
        assert code in (200, 202)
    finally:
        srv.stop()


# ------------------------------------------- operator scopes (device)

def _small_step_lowered():
    import jax
    import jax.numpy as jnp

    from retina_tpu.events.schema import NUM_FIELDS
    from retina_tpu.models.identity import IdentityMap
    from retina_tpu.models.pipeline import (
        PipelineConfig, TelemetryPipeline,
    )

    cfg = PipelineConfig(
        n_pods=64, cms_width=1 << 10, topk_slots=64, hll_precision=8,
        entropy_buckets=1 << 8, conntrack_slots=1 << 10,
        latency_slots=1 << 8, enable_invertible=True, inv_width=1 << 8,
        inv_hi_width=1 << 6, bypass_filter=False,
        data_aggregation_level="low",
    )
    pipe = TelemetryPipeline(cfg)
    ident = IdentityMap.build_host({0x0A000001: 1})
    return jax.jit(pipe.step).lower(
        pipe.init_state(), jnp.zeros((256, NUM_FIELDS), jnp.uint32),
        jnp.uint32(0), jnp.uint32(0), ident, jnp.uint32(0),
        filter_map=ident,
    )


def _opcodes(hlo_text):
    import re

    return re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", hlo_text, re.M
    )


class TestOperatorScopes:
    def test_every_scope_is_in_the_lowered_step_and_changes_no_opcode(
        self, monkeypatch
    ):
        import contextlib

        import jax

        from retina_tpu.models.pipeline import STEP_SCOPES
        from retina_tpu.parallel.telemetry import scopes_of_text

        for name in ("rescale", "identity_join", "filter", "conntrack",
                     "pod_counters", "cms_flow_hh", "cms_svc_hh",
                     "cms_dns_hh", "invertible", "hll", "entropy",
                     "latency_match"):
            assert name in STEP_SCOPES
        lowered = _small_step_lowered()
        locations = lowered.as_text(debug_info=True)
        for name in STEP_SCOPES:
            assert f"/{name}/" in locations or f'"{name}/' in locations, name
        scoped = lowered.compile().as_text()
        module, table = scopes_of_text(scoped)
        assert module == "jit_step"
        assert set(table.values()) == set(STEP_SCOPES)
        # Metadata only: with the scopes patched out, the optimised
        # program has the same instructions, opcode for opcode.
        monkeypatch.setattr(
            jax, "named_scope", lambda name: contextlib.nullcontext()
        )
        bare = _small_step_lowered().compile().as_text()
        assert scopes_of_text(bare)[1] == {}
        assert _opcodes(bare) == _opcodes(scoped)
        assert len(_opcodes(scoped)) > 1000

    def test_scope_map_takes_the_outermost_registered_scope(self):
        from retina_tpu.parallel.telemetry import scopes_of_text

        text = "\n".join([
            "HloModule jit_local_step, entry_computation_layout={()->()}",
            "%fused_computation (p: u32[8]) -> u32[8] {",
            '  ROOT %add.1 = u32[8]{0} add(%p, %p), metadata={op_name='
            '"jit(local_step)/jit(main)/shmap_body/cms_flow_hh/add"}',
            "}",
            "ENTRY %main () -> u32[8] {",
            '  %fusion.46 = u32[8]{0} fusion(%a), kind=kCustom, '
            'calls=%fused_computation, metadata={op_name="jit(local_step)'
            '/shmap_body/cms_flow_hh/hll/scatter-add" stack_frame_id=4}',
            '  %copy.2 = u32[8]{0} copy(%a), metadata={op_name='
            '"jit(local_step)/shmap_body/squeeze"}',
            "  %bitcast.9 = u32[8]{0} bitcast(%copy.2)",
            '  ROOT %sort.3 = u32[8]{0} sort(%a), metadata={op_name='
            '"jit(local_step)/conntrack/sort"}',
            "}",
        ])
        module, table = scopes_of_text(text)
        assert module == "jit_local_step"
        assert table == {"add.1": "cms_flow_hh",
                         "fusion.46": "cms_flow_hh", "sort.3": "conntrack"}

    def test_scope_map_of_an_executable_is_kept_and_written(
        self, tmp_path
    ):
        """One way to the map: the executable's own text, whether it
        was compiled here or loaded from the AOT cache."""
        from retina_tpu.parallel import telemetry as tel

        class Exe:
            def __init__(self, text):
                self.text = text

            def as_text(self):
                return self.text

        head = "HloModule jit_probe, entry_computation_layout={()->f32[]}\n"
        tel.note_op_scopes(Exe(
            head
            + ' %fusion.1 = f32[] fusion(), metadata={op_name="jit(p)/hll/add"}\n'
            + ' %copy.7 = f32[] copy(), metadata={op_name="jit(p)/decode/c"}\n'
        ))
        tel.note_op_scopes(Exe(
            head
            + ' ROOT %fusion.2 = f32[] fusion(), metadata={op_name="jit(p)/entropy/x"}\n'
        ))

        class Mute:
            def as_text(self):
                raise RuntimeError("this runtime prints nothing")

        tel.note_op_scopes(Mute())  # best-effort: unscoped, no raise
        want = {"fusion.1": "hll", "copy.7": "decode", "fusion.2": "entropy"}
        assert tel.op_scope_map()["jit_probe"] == want
        path = tmp_path / "prof" / "op_scopes.json"
        tel.write_op_scopes(str(path))
        assert json.loads(path.read_text())["jit_probe"] == want
