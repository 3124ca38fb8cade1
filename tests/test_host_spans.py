"""benchmarks/host_spans.py on hand-built traces: the interval
arithmetic, the two-level idle attribution (proxy state, then the
feed-side span open inside ``proxy_idle``, else ``no_work``), the
per-scope split of a step with nested events, and the readers' silence
where the program left nothing to read."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
for p in (os.path.join(BENCH, "layer_metrics"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import host_spans  # noqa: E402

S = 1e9  # the trace's clock is in nanoseconds


def test_interval_arithmetic():
    u = host_spans.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)]
    assert host_spans.length(u) == 6
    assert host_spans.intersect(u, [(2, 6), (7.5, 20)]) == [
        (2, 3), (5, 6), (7.5, 8)]
    assert host_spans.subtract([(0, 10)], u) == [(3, 5), (8, 10)]
    assert host_spans.subtract(u, [(0, 10)]) == []
    assert host_spans.subtract(u, []) == u


def test_idle_gaps_by_proxy_state_then_feed_span_then_no_work():
    # 10 s traced. The chip works in [1, 2] and [6, 6.5]: 8.5 s idle.
    ops = [(1 * S, 2 * S), (6 * S, 6.5 * S)]
    proxy = [
        ("proxy_idle", 0.0, 0.9 * S),            # waiting for work
        ("proxy_run:step", 0.9 * S, 1.0 * S),    # enqueues the step
        ("proxy_idle", 1.0 * S, 5.0 * S),
        ("proxy_run:snapshot", 5.0 * S, 5.5 * S),
        ("proxy_run:step", 5.5 * S, 6.0 * S),
        ("proxy_idle", 6.0 * S, 9.5 * S),        # nothing until 9.5
    ]
    feed = [
        ("feed_fill", 0.5 * S, 0.8 * S),   # contains the combine below
        ("combine", 0.6 * S, 0.7 * S),
        ("wire_build", 0.8 * S, 0.9 * S),
        ("feed_fill", 1.5 * S, 1.9 * S),   # the chip is busy: no gap
        ("staging_handoff", 4.0 * S, 4.5 * S),
        ("render", 7.0 * S, 8.0 * S),      # not feed-side: ignored
    ]
    t = host_spans.attribute((0.0, 10 * S), ops, proxy, feed)
    assert t == pytest.approx({
        "proxy_run:step": 0.1 + 0.5,
        "proxy_run:snapshot": 0.5,
        "feed_fill": 0.3,          # the combine inside it is not twice
        "wire_build": 0.1,
        "staging_handoff": 0.5,
        # [0, .5] + [2, 4] + [4.5, 5] + [6.5, 9.5]
        "no_work": 0.5 + 2.0 + 0.5 + 3.0,
        "unlabelled": 0.5,         # [9.5, 10]: no proxy event covers it
    })
    assert sum(t.values()) == pytest.approx(8.5)
    assert host_spans.host_bound_s(t) == pytest.approx(2.0)


def test_busy_chip_has_no_gap_to_attribute():
    t = host_spans.attribute(
        (0.0, 2 * S), [(0.0, 2 * S)],
        [("proxy_run:step", 0.0, 2 * S)], [])
    assert t == {}


def test_scope_seconds_counts_nested_events_once():
    ops = [
        ("fusion.1", 0.0, 10.0),      # cms_flow_hh
        ("while.2", 10.0, 40.0),      # conntrack's loop ...
        ("add.3", 12.0, 14.0),        # ... and its body, inside it
        ("sort.4", 20.0, 30.0),
        ("copy.5", 40.0, 45.0),       # no scope: outside
        ("fusion.6", 45.0, 50.0),     # cms_svc_hh
        ("fusion.1", 100.0, 110.0),   # the next execution: not ours
    ]
    scopes = {"fusion.1": "cms_flow_hh", "while.2": "conntrack",
              "add.3": "conntrack", "sort.4": "conntrack",
              "fusion.6": "cms_svc_hh"}
    got = host_spans.scope_seconds((0.0, 60.0), ops, scopes)
    assert got == pytest.approx({
        "cms_flow_hh": 10e-9, "conntrack": 30e-9, "cms_svc_hh": 5e-9,
        "": 5e-9,
    })


def test_instruction_name_of_an_event():
    name = ("%fusion.46 = u32[524288]{0:T(1024)S(1)} fusion(u32[262144]"
            "{0:T(1024)S(1)} %copy.2), kind=kCustom, calls=%fused")
    assert host_spans.instruction(name) == "fusion.46"
    assert host_spans.instruction("%copy-done.1 = u32[8]{0} copy-done("
                                  "%copy-start.1)") == "copy-done.1"


def _run(**kw):
    base = dict(trace=None, t_open=10.0, t_close=60.0, scrapes=[],
                config={"name": "advanced-pod",
                        "step_program": "^jit_local_step$"})
    return types.SimpleNamespace(**{**base, **kw})


def test_the_trace_read_is_this_configurations_own(tmp_path, monkeypatch):
    """Cells are traced one after another in one cache directory: a
    run reads the trace under its own configuration's work directory,
    not the newest of them all, and says nothing where it has none.
    The scope map is the running program's own, or nothing."""
    import retina_tpu.config as config
    from retina_tpu.parallel import telemetry

    monkeypatch.setattr(config, "CHECKOUT_CACHE_DIR", str(tmp_path))

    def trace_of(workload, mtime):
        d = tmp_path / "bench" / workload / "trace/plugins/profile/s1"
        d.mkdir(parents=True)
        f = d / "host.xplane.pb"
        f.write_bytes(b"")
        os.utime(f, (mtime, mtime))
        return str(f)

    mine = trace_of("advanced-pod.zipf1m-block1s", 1000)
    trace_of("advanced-pod-noct.zipf1m-block1s", 2000)  # newer, not mine
    assert host_spans.xplane_of("advanced-pod") == mine
    assert host_spans.xplane_of("basic-node") is None
    telemetry.note_op_scopes(types.SimpleNamespace(as_text=lambda: (
        "HloModule jit_mine\n"
        ' %fusion.9 = f32[] fusion(), metadata={op_name="jit(m)/hll/add"}\n'
    )))
    assert host_spans.program_scope_map()["jit_mine"] == {"fusion.9": "hll"}
    # A program that keeps no such map (a parent commit).
    monkeypatch.delattr(telemetry, "op_scope_map")
    assert host_spans.program_scope_map() == {}


def test_readers_say_nothing_without_a_trace_or_spans(monkeypatch):
    import idle_host_bound_pct
    import publish_lag_ms
    import publish_wait_pct
    import step_complete_ms
    import step_conntrack_ms
    import step_countmin_ms

    run = _run()
    assert host_spans.analysis(run) == {}
    assert idle_host_bound_pct.read(run) is None
    assert step_countmin_ms.read(run) is None
    assert step_conntrack_ms.read(run) is None
    assert publish_lag_ms.read(run) is None
    # A program whose spans carry no ids or arguments (a parent).
    old = [{"stage": "device_step", "t0": 20.0, "t1": 20.1,
            "trace_id": 3, "thread": "x"},
           {"stage": "pod_publish", "t0": 21.0, "t1": 22.0,
            "trace_id": 3, "thread": "y"}]
    monkeypatch.setattr(host_spans, "window_spans",
                        lambda run, stage: [s for s in old
                                            if s["stage"] == stage])
    assert step_complete_ms.read(run) is None
    assert publish_wait_pct.read(run) is None


def test_span_readers_on_hand_made_spans(monkeypatch):
    import proxy_wait_p95_ms
    import publish_wait_pct
    import render_ms
    import step_complete_ms

    spans = [
        {"stage": "device_step", "t0": 20.0, "t1": 20.3, "id": 1,
         "parent": 0, "args": {"n_steps": 4}},
        {"stage": "device_step", "t0": 30.0, "t1": 30.1, "id": 2,
         "parent": 0, "args": {"n_steps": 1}},
        {"stage": "pod_publish", "t0": 21.0, "t1": 23.0, "id": 10,
         "parent": 0, "args": {}},
        {"stage": "snapshot", "t0": 21.0, "t1": 22.5, "id": 11,
         "parent": 10, "args": {}},
        {"stage": "snapshot_dispatch", "t0": 21.0, "t1": 21.5, "id": 12,
         "parent": 11, "args": {}},
        {"stage": "snapshot_fetch", "t0": 21.5, "t1": 22.4, "id": 13,
         "parent": 11, "args": {"ready_wait_s": 0.7, "copy_s": 0.2}},
        # conntrack's own snapshot: under no publish, not counted
        {"stage": "snapshot", "t0": 40.0, "t1": 41.0, "id": 20,
         "parent": 0, "args": {}},
        {"stage": "snapshot_dispatch", "t0": 40.0, "t1": 40.9, "id": 21,
         "parent": 20, "args": {}},
        {"stage": "render", "t0": 25.0, "t1": 25.2, "id": 30,
         "parent": 0, "args": {}},
        {"stage": "render", "t0": 26.0, "t1": 26.4, "id": 31,
         "parent": 0, "args": {}},
    ] + [
        {"stage": "proxy_run", "t0": 20.0 + i, "t1": 20.5 + i,
         "id": 100 + i, "parent": 0,
         "args": {"kind": "step", "wait_s": i / 1000}}
        for i in range(20)
    ]
    monkeypatch.setattr(host_spans, "window_spans",
                        lambda run, stage: [s for s in spans
                                            if s["stage"] == stage])
    run = _run()
    assert step_complete_ms.read(run) == pytest.approx(1e3 * 0.4 / 5)
    assert publish_wait_pct.read(run) == pytest.approx(
        100.0 * (0.5 + 0.7) / 2.0)
    assert render_ms.read(run) == pytest.approx(300.0)
    assert proxy_wait_p95_ms.read(run) == pytest.approx(18.0)


def test_window_spans_reads_the_recorder_inside_the_window():
    from retina_tpu.obs.recorder import get_recorder, initialize_recorder
    from retina_tpu.utils import metric_names as mn

    old = get_recorder()
    rec = initialize_recorder(capacity=64)
    try:
        for t0 in (5.0, 10.0, 59.9, 60.0):
            rec._commit(mn.STAGE_RENDER, t0, t0 + 0.5, 7, next(rec._ids),
                        0, None)
        got = host_spans.window_spans(_run(), mn.STAGE_RENDER)
        assert [s["t0"] for s in got] == [10.0, 59.9]
    finally:
        initialize_recorder(capacity=old.capacity, enabled=old.enabled)


def test_publish_lag_reads_the_histogram_between_the_windows_scrapes():
    import publish_lag_ms as m

    def row(sent, total, n, ok=True):
        return {"sent": sent, "ok": ok, "c": {m.SUM: total, m.COUNT: n}}

    run = _run(scrapes=[row(5.0, 1.0, 2), row(10.0, 3.0, 4),
                        row(30.0, 9.0, 6), row(59.0, 15.0, 10),
                        row(61.0, 99.0, 11)])
    assert m.read(run) == pytest.approx(1e3 * (15.0 - 3.0) / 6)
    # A program without the series: the poller sums nothing.
    assert m.read(_run(scrapes=[row(10.0, 0.0, 0), row(59.0, 0.0, 0)])) \
        is None
