"""What BENCHMARK.json names, and the per-layer readers added since
ISSUE 29 (ROADMAP D10): every configuration, traffic mix and per-layer
metric resolves to a file the harness finds by name; each new reader
returns a number on a recorded load and nothing where its counter, span
or trace is absent, as on a parent commit's runs."""

import dataclasses
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = os.path.join(ROOT, "benchmarks")
for p in (os.path.join(BENCH, "layer_metrics"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import host_spans  # noqa: E402
import traffic  # noqa: E402

DOC = harness.load_benchmark()
NEW_CELLS = ("advanced-pod.zipf1m-steady",
             "advanced-pod-hubble.zipf1m-steady",
             "advanced-pod-noct.zipf1m-steady")
MESH_CELL = "advanced-pod-v5e4.zipf1m-steady-x4"  # ISSUE 33, four chips
MESH_READERS = ("partition_ms_per_s", "shard_skew_pct",
                "collective_ms_per_s")
CHURN_CELL = "advanced-pod-churn.churn8m-steady"  # ISSUE 35
CHURN_READERS = ("dict_new_rows_pct", "dict_clears_per_s",
                 "ingest_device_ms_per_s")
# ISSUE 36: the program's CPU account, a reader a role and its check.
CPU_ROLE_READERS = {"host_cpu_feed_ms_per_s": "feed",
                    "host_cpu_dispatch_ms_per_s": "dispatch",
                    "host_cpu_proxy_ms_per_s": "proxy",
                    "host_cpu_publish_ms_per_s": "publish",
                    "host_cpu_serve_ms_per_s": "serve",
                    "host_cpu_runtime_ms_per_s": "runtime",
                    "host_cpu_generator_ms_per_s": "foreign"}
CPU_READERS = (*CPU_ROLE_READERS, "host_cpu_unnamed_pct")
NEW_READERS = ("steps_per_s", "step_fill_pct", "overload_pressure_p95",
               "hubble_mirror_ms_per_s", "feed_wakeups_per_s",
               "publish_cpu_ms_per_s", "publish_changed_pct",
               *MESH_READERS, *CHURN_READERS, *CPU_READERS,
               "feed_flushes_per_s")


def _config(name: str) -> dict:
    entry = {c["name"]: c for c in DOC["configs"]}[name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_a_cell_resolves_to_its_files(cell):
    """``harness.load_cell`` and ``traffic.load_mix`` find the cell's
    configuration and traffic by the names BENCHMARK.json gives, and
    the cell reports set-up, another end-to-end metric and a per-layer
    metric whose reader is a file."""
    w, config = harness.load_cell(DOC, cell)
    assert config["name"] == w["config"]
    assert w["name"] == f"{w['config']}.{w['traffic']}"
    for rehearse in (False, True):
        mix = traffic.load_mix(w["traffic"], rehearse)
        assert mix.rate_events_per_s >= mix.ticks_per_s
    e2e = [m["name"] for m in harness.metrics_of(DOC, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = harness.metrics_of(DOC, "per_layer", cell)
    assert layers
    for m in layers:
        assert hasattr(harness.load_reader(m["name"]), "read"), m["name"]
        assert m["moves"] in e2e


def test_the_ring_cadence_traffic_is_the_block_mix_at_sixteen_hand_overs():
    steady = traffic.load_mix("zipf1m-steady")
    block = traffic.load_mix("zipf1m-block1s")
    assert steady.ticks_per_s == 16
    assert steady.rate_events_per_s >= 262_144
    assert steady.block_rows == steady.rate_events_per_s // 16 <= 65_536
    for key in ("n_flows", "n_endpoints", "zipf_a", "drop_fraction",
                "dns_fraction", "pool_events", "warmup_windows",
                "poll_interval_s"):
        assert getattr(steady, key) == getattr(block, key), key


def test_the_hubble_configuration_is_the_configmap_with_nothing_off():
    """``advanced-pod.json``'s agent group, guarantees and limits, key
    for key; the machine group no longer switches Hubble off."""
    hub, base = _config("advanced-pod-hubble"), _config("advanced-pod")
    for group in ("agent", "sizing", "step_shapes", "guarantees", "held",
                  "assumed", "rehearse", "rehearse_held", "reduced"):
        assert hub[group] == base[group], group
    assert hub["agent"]["enable_hubble"] is True
    assert "enable_hubble" not in hub["machine"]
    assert base["machine"]["enable_hubble"] is False
    assert hub["machine"]["hubble_addr"] == "127.0.0.1:0"
    assert hub["machine"]["hubble_metrics_addr"] == "127.0.0.1:0"
    cells = {w["name"]: w for w in DOC["workloads"]}
    assert {MESH_CELL, *NEW_CELLS} <= cells.keys()
    for name in NEW_CELLS:
        assert cells[name]["chips"] == 1
        assert cells[name]["traffic"] == "zipf1m-steady"
    hubble = {m["name"]: m for m in DOC["per_layer"]}[
        "hubble_mirror_ms_per_s"]
    assert hubble["workloads"] == ["advanced-pod-hubble.zipf1m-steady"]


def test_the_v5e4_configuration_is_the_configmap_on_four_chips():
    """``advanced-pod.json`` key for key but the mesh, the name and the
    prose that says where it runs; its traffic the ``steady`` mix field
    for field but the rate, four times one chip's; its cell the one
    four-chip cell, the only one the mesh metrics list."""
    mesh, base = _config("advanced-pod-v5e4"), _config("advanced-pod")
    for group in ("agent", "machine", "step_shapes", "step_program",
                  "guarantees", "held", "rehearse", "rehearse_held",
                  "reduced"):
        assert mesh[group] == base[group], group
    assert mesh["sizing"] == {**base["sizing"], "mesh_devices": 4}
    assert mesh["assumed"][:len(base["assumed"])] == base["assumed"]
    said = {"name", "source", "deployment", "sizing", "assumed",
            "reference"}
    assert {k for k in mesh if mesh[k] != base.get(k)
            and not k.endswith("_note")} == said
    assert mesh["reduced"] == [] and "four" in mesh["deployment"]
    x4 = traffic.load_mix("zipf1m-steady-x4")
    steady = traffic.load_mix("zipf1m-steady")
    assert x4.rate_events_per_s <= 4 * steady.rate_events_per_s
    assert x4.rate_events_per_s % steady.rate_events_per_s == 0
    assert x4.block_rows == x4.rate_events_per_s // 16 <= 65_536
    for key in ("n_flows", "n_endpoints", "zipf_a", "drop_fraction",
                "dns_fraction", "pool_events", "ticks_per_s",
                "warmup_windows", "poll_interval_s"):
        assert getattr(x4, key) == getattr(steady, key), key
    assert traffic.load_mix("zipf1m-steady-x4", rehearse=True) == \
        dataclasses.replace(traffic.load_mix("zipf1m-steady", True),
                            name="zipf1m-steady-x4")
    four = [w for w in DOC["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [MESH_CELL]
    assert four[0]["config"] == "advanced-pod-v5e4"
    by_name = {m["name"]: m for m in DOC["per_layer"]}
    for name in MESH_READERS:
        assert by_name[name]["workloads"] == [MESH_CELL], name
    entry = {c["name"]: c for c in DOC["configs"]}["advanced-pod-v5e4"]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200


def test_the_churn_configuration_is_the_configmap_with_nothing_sized():
    """``advanced-pod.json`` key for key but what says where it runs
    and what was set by hand: ``sizing`` is empty, so the dictionary is
    the agent's default, the documented 262,144 that conntrack has too;
    the guarantees are carried over whole. Its traffic is the ``steady``
    mix field for field but the two numbers that make the regime."""
    from retina_tpu.config import Config

    churn, base = _config("advanced-pod-churn"), _config("advanced-pod")
    for group in ("agent", "machine", "step_shapes", "step_program",
                  "guarantees", "rehearse", "rehearse_held", "reduced"):
        assert churn[group] == base[group], group
    assert {k for k in {*churn, *base} if churn.get(k) != base.get(k)
            and not k.endswith("_note")} == {
        "name", "source", "deployment", "sizing", "assumed"} | (
        {"held"} if churn["held"] != base["held"] else set())
    assert churn["sizing"] == {} and churn["reduced"] == []
    assert Config().flow_dict_slots == 262_144 \
        == churn["agent"]["conntrack_slots"]
    assert churn["agent"]["enable_conntrack_metrics"] is True
    assert churn["held"].keys() == base["held"].keys()
    kept = [a for a in base["assumed"] if "flow_dict_slots" not in a]
    assert churn["assumed"][:len(kept)] == kept
    assert not any("flow_dict_slots" in a for a in churn["assumed"])
    said = " ".join(churn["assumed"][len(kept):])
    assert "n_flows 8388608" in said and "zipf_a 0.8" in said \
        and "rate" in said
    entry = {c["name"]: c for c in DOC["configs"]}["advanced-pod-churn"]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    cell = {w["name"]: w for w in DOC["workloads"]}[CHURN_CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (
        "advanced-pod-churn", "churn8m-steady")

    mix = traffic.load_mix("churn8m-steady")
    steady = traffic.load_mix("zipf1m-steady")
    assert (mix.n_flows, mix.zipf_a) == (8_388_608, 0.8)
    assert mix.block_rows == mix.rate_events_per_s // 16 <= 65_536
    for f in dataclasses.fields(traffic.Mix):
        if f.name not in ("name", "n_flows", "zipf_a"):
            assert getattr(mix, f.name) == getattr(steady, f.name), f.name
    # The rehearsal's dictionary of 16,384 turns over too.
    small = traffic.load_mix("churn8m-steady", rehearse=True)
    assert small == dataclasses.replace(
        traffic.load_mix("zipf1m-steady", True), name="churn8m-steady",
        n_flows=65_536, zipf_a=0.8)
    assert small.n_flows >= 4 * churn["rehearse"]["flow_dict_slots"]
    by_name = {m["name"]: m for m in DOC["per_layer"]}
    assert "workloads" not in by_name["dict_new_rows_pct"]
    assert by_name["dict_clears_per_s"]["workloads"] == [CHURN_CELL]
    assert by_name["ingest_device_ms_per_s"]["workloads"] == [
        CHURN_CELL, "advanced-pod.zipf1m-steady"]
    names = [m["name"] for m in DOC["per_layer"]]
    at = names.index(CHURN_READERS[0])
    assert names[at:at + 3] == list(CHURN_READERS)
    for name in CHURN_READERS:
        assert by_name[name]["layer"] == "flow dict + wire"


# -- the new readers on a recorded load -------------------------------------
def _scrape(sent, **c):
    return {"sent": sent, "done": sent + 0.01, "ok": True, "events": 0,
            "c": c}


def _load(scrapes, before=None, after=None, trace=None):
    """What the readers read of a ``harness.Load``: window [10, 60),
    the configuration, the scrapes, the counters at the start of the
    load and at the settled scrape, and the reduced trace."""
    before, after = before or {}, after or {}
    return types.SimpleNamespace(
        trace=trace, t_open=10.0, t_close=60.0, scrapes=scrapes,
        config=_config("advanced-pod-v5e4"),
        counter_delta=lambda n: after.get(n, 0.0) - before.get(n, 0.0))


def _shards(*rows):
    """Each device's ``tpu_shard_rows_counter`` sample as the poller
    keys it: by the full sample name it was asked for."""
    series = harness.load_reader("shard_skew_pct").SERIES
    return {series % d: float(n) for d, n in enumerate(rows)}


def _account(process, **roles):
    """The CPU account's counters as the poller keys them: the process's
    by its name, each role's by the full sample name it was asked for."""
    import cpu_account

    return {cpu_account.PROCESS: process,
            **{cpu_account.SERIES % r: s for r, s in roles.items()}}


def _traced(ops_by_chip, window_s=5.0, modules=()):
    import trace_reduce

    return trace_reduce.Trace(
        [trace_reduce.Chip(i, list(modules), ops)
         for i, ops in enumerate(ops_by_chip)], window_s)


STEP_OPS = [("%fusion.46 u32[524288]", 0, 60_000_000),
            ("%copy.3 u32[4,16]", 60_000_000, 1_000_000)]
# The programs each chip ran: two steps, and three ingest programs (a
# new side, a known side, a plain wire) of 4 + 1 + 2.5 ms.
PROGRAMS = [("jit_local_step", 0, 60_000_000),
            ("jit_ingest", 70_000_000, 4_000_000),
            ("jit_ingest", 75_000_000, 1_000_000),
            ("jit_fold_side_windows", 77_000_000, 300_000),
            ("jit_local_step", 80_000_000, 60_000_000),
            ("jit_ingest", 150_000_000, 2_500_000)]
# Two chips of a mesh: a plain all-reduce, the two halves of an
# asynchronous all-gather, and a fusion that is no collective.
MESH_TRACE = _traced(modules=PROGRAMS, ops_by_chip=[
    STEP_OPS + [("%all-reduce.7 u32[2]", 61_000_000, 2_000_000),
                ("%all-gather-start.1 u32[4,2048,5]", 63_000_000, 500_000),
                ("%all-gather-done.1 u32[4,2048,5]", 64_000_000, 1_500_000)],
    STEP_OPS + [("%all-reduce.7 u32[2]", 61_000_000, 4_000_000),
                ("%all-gather-start.1 u32[4,2048,5]", 65_000_000, 500_000),
                ("%all-gather-done.1 u32[4,2048,5]", 66_000_000, 1_500_000)],
])


# tpu_feed_wakeups_counter as the poller sums it over {thread, cause},
# tpu_feed_flushes_counter over {cause},
# tpu_publish_cpu_seconds_counter over {part}.
RECORDED = _load(
    [_scrape(9.0, tpu_steps_counter=100.0, tpu_overload_pressure=0.9,
             tpu_feed_wakeups_counter=900.0,
             tpu_feed_flushes_counter=5.0,
             tpu_publish_cpu_seconds_counter=0.5,
             tpu_publish_rows_counter=70_000.0,
             tpu_publish_rows_changed_counter=70_000.0,
             **_account(1.0, feed=0.9)),
     _scrape(10.0, tpu_steps_counter=110.0, tpu_overload_pressure=0.10,
             tpu_feed_wakeups_counter=1000.0,
             tpu_feed_flushes_counter=10.0,
             tpu_publish_cpu_seconds_counter=0.6,
             tpu_publish_rows_counter=105_000.0,
             tpu_publish_rows_changed_counter=90_000.0,
             tpu_flow_dict_clears_counter=7.0,
             **_shards(1000, 1000, 1000, 1000),
             **_account(30.0, feed=3.0, foreign=20.0)),
     _scrape(35.0, tpu_steps_counter=360.0, tpu_overload_pressure=0.30,
             tpu_feed_wakeups_counter=3500.0,
             tpu_feed_flushes_counter=90.0,
             tpu_publish_cpu_seconds_counter=2.0,
             tpu_publish_rows_counter=900_000.0,
             tpu_publish_rows_changed_counter=500_000.0,
             # The account's first sample to land in the window.
             **_account(36.0, feed=4.0, dispatch=1.0, proxy=1.0, harvest=0.5,
                        publish=2.0, serve=3.0, control=0.25, hubble=0.0,
                        account=0.25, runtime=2.0, foreign=21.0)),
     _scrape(47.0, tpu_steps_counter=480.0, tpu_overload_pressure=0.10,
             tpu_feed_wakeups_counter=4700.0,
             tpu_feed_flushes_counter=130.0,
             tpu_publish_cpu_seconds_counter=3.0,
             tpu_publish_rows_counter=1_300_000.0,
             tpu_publish_rows_changed_counter=800_000.0,
             **_account(42.5, feed=5.2, dispatch=1.6, proxy=1.48, harvest=0.6,
                        publish=2.84, serve=4.2, control=0.3, hubble=0.09,
                        account=0.26, runtime=3.2, foreign=21.6)),
     # Scraped again before the next sample: nothing has moved.
     _scrape(47.1, tpu_steps_counter=481.0, tpu_overload_pressure=0.10,
             tpu_feed_wakeups_counter=4710.0,
             tpu_feed_flushes_counter=130.0,
             tpu_publish_cpu_seconds_counter=3.0,
             tpu_publish_rows_counter=1_300_000.0,
             tpu_publish_rows_changed_counter=800_000.0,
             **_account(42.5, feed=5.2, dispatch=1.6, proxy=1.48, harvest=0.6,
                        publish=2.84, serve=4.2, control=0.3, hubble=0.09,
                        account=0.26, runtime=3.2, foreign=21.6)),
     _scrape(59.0, tpu_steps_counter=600.0, tpu_overload_pressure=0.20,
             tpu_feed_wakeups_counter=5900.0,
             tpu_feed_flushes_counter=157.0,
             tpu_publish_cpu_seconds_counter=4.03,
             tpu_publish_rows_counter=1_785_000.0,
             tpu_publish_rows_changed_counter=1_098_000.0,
             tpu_flow_dict_clears_counter=42.0,
             **_shards(3000, 4000, 7000, 6000),
             **_account(42.5, feed=5.2, dispatch=1.6, proxy=1.48, harvest=0.6,
                        publish=2.84, serve=4.2, control=0.3, hubble=0.09,
                        account=0.26, runtime=3.2, foreign=21.6)),
     _scrape(61.0, tpu_steps_counter=999.0, tpu_overload_pressure=0.95,
             tpu_feed_wakeups_counter=9999.0,
             tpu_feed_flushes_counter=999.0,
             tpu_publish_cpu_seconds_counter=9.0,
             tpu_publish_rows_counter=9_999_999.0,
             tpu_publish_rows_changed_counter=9_999_999.0,
             **_account(99.0, feed=9.0, serve=9.0))],
    before={"tpu_steps_counter": 50.0, "tpu_step_rows_counter": 1000.0},
    after={"tpu_steps_counter": 650.0,
           "tpu_step_rows_counter": 1000.0 + 600 * 131072 * 0.25},
    trace=MESH_TRACE)
# A parent's run: the poller sums nothing for a series that is not there.
PARENT = _load(
    [_scrape(t, tpu_steps_counter=0.0, tpu_overload_pressure=0.0,
             tpu_feed_wakeups_counter=0.0, tpu_feed_flushes_counter=0.0,
             tpu_publish_cpu_seconds_counter=0.0,
             tpu_publish_rows_counter=0.0,
             tpu_publish_rows_changed_counter=0.0,
             tpu_flow_dict_clears_counter=0.0, **_shards(0, 0, 0, 0),
             **_account(0.0, **dict.fromkeys(
                 ("feed", "dispatch", "proxy", "harvest", "publish", "serve",
                  "control", "hubble", "account", "runtime", "foreign"),
                 0.0)))
     for t in (9.0, 10.0, 35.0, 59.0)],
    trace=_traced([STEP_OPS]))  # one chip: no collective to read
SPANS = [{"stage": "hubble_consume", "t0": 12.0 + i, "t1": 12.004 + i,
          "args": {"rows": 16384}} for i in range(25)] + [
    {"stage": "partition", "t0": 12.5 + i, "t1": 12.506 + i,
     "args": {"rows": 40_000, "devices": 4}} for i in range(40)]
WANT = {"steps_per_s": (600.0 - 110.0) / 49.0, "step_fill_pct": 25.0,
        "overload_pressure_p95": 0.30, "hubble_mirror_ms_per_s": 2.0,
        "feed_wakeups_per_s": (5900.0 - 1000.0) / 49.0,
        "feed_flushes_per_s": (157.0 - 10.0) / 49.0,
        "publish_cpu_ms_per_s": 1e3 * (4.03 - 0.6) / 49.0,
        "publish_changed_pct": 100.0 * 1_008_000 / 1_680_000,
        "partition_ms_per_s": 40 * 6.0 / 50.0,
        # Rows of the window 2,000 / 3,000 / 6,000 / 5,000: mean 4,000.
        "shard_skew_pct": 50.0,
        # (2 + 0.5 + 1.5) ms and (4 + 0.5 + 1.5) ms over 5 s, the mean.
        "collective_ms_per_s": 1.0,
        # 600 new and 150 table-less rows of 1,000 under the dictionary.
        "dict_new_rows_pct": 75.0,
        "dict_clears_per_s": (42.0 - 7.0) / 49.0,
        # 7.5 ms of ingest programs on each chip over 5 s.
        "ingest_device_ms_per_s": 1.5,
        # Between the account's samples scraped at 35 s and at 47 s (the
        # process 36 -> 42.5 s): each role's increase over 12 s.
        "host_cpu_feed_ms_per_s": 100.0, "host_cpu_dispatch_ms_per_s": 50.0,
        "host_cpu_proxy_ms_per_s": 40.0, "host_cpu_publish_ms_per_s": 70.0,
        "host_cpu_serve_ms_per_s": 100.0, "host_cpu_runtime_ms_per_s": 100.0,
        "host_cpu_generator_ms_per_s": 50.0,
        # 6.37 of the 6.5 s are in a role (harvest, control, Hubble and
        # the sampler took 0.1, 0.05, 0.09 and 0.01).
        "host_cpu_unnamed_pct": 2.0}
# ``tpu_wire_rows_counter`` by kind, as the recorded process counted.
WIRE_ROWS = {"new": 600, "tableless": 150, "known": 250}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_a_number_on_a_recorded_load(
        name, monkeypatch):
    monkeypatch.setattr(
        host_spans, "window_spans",
        lambda run, stage: [s for s in SPANS if s["stage"] == stage])
    from retina_tpu.metrics import get_metrics

    for kind, rows in WIRE_ROWS.items():
        get_metrics().wire_rows.labels(kind=kind).inc(rows)
    reader = harness.load_reader(name)
    assert reader.read(RECORDED) == pytest.approx(WANT[name])
    entry = {m["name"]: m for m in DOC["per_layer"]}[name]
    assert reader.UNIT == entry["unit"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_says_nothing_where_its_source_is_absent(
        name, monkeypatch):
    monkeypatch.setattr(host_spans, "window_spans", lambda run, stage: [])
    assert harness.load_reader(name).read(PARENT) is None
    # Nor with no scrape inside the window at all.
    assert harness.load_reader(name).read(_load([])) is None


def test_the_wakeup_metric_is_read_in_every_cell_off_the_programs_counter():
    """``feed_wakeups_per_s`` names the feed's layer and the host's CPU,
    lists no cells (every cell runs the feed), and the counter it asks
    the poller for is the one the program registers, labels and all."""
    from retina_tpu.metrics import get_metrics
    from retina_tpu.utils import metric_names as mn

    entry = {m["name"]: m for m in DOC["per_layer"]}["feed_wakeups_per_s"]
    assert entry == {"name": "feed_wakeups_per_s", "unit": "wakeups/s",
                     "better": "lower", "source": "program_counter",
                     "layer": "feed + combine",
                     "moves": "host_cpu_us_per_event"}
    reader = harness.load_reader("feed_wakeups_per_s")
    assert mn.FEED_WAKEUPS == "networkobservability_" + reader.WAKEUPS
    assert reader.COUNTERS == (reader.WAKEUPS,)
    get_metrics().feed_wakeups.labels(
        thread=mn.WAKE_FEED, cause=mn.CAUSE_DATA).inc(3)
    get_metrics().feed_wakeups.labels(
        thread=mn.WAKE_WORKER, cause=mn.CAUSE_DEADLINE).inc(4)
    import poller
    from retina_tpu.exporter import get_exporter

    body = get_exporter().gather_text()
    name = mn.FEED_WAKEUPS.encode()
    assert poller.series_sum(body, (name,))[name] == 7.0


def test_the_flush_metric_is_read_in_every_cell_off_the_programs_counter():
    """``feed_flushes_per_s`` is the last per-layer entry, names the
    feed's layer and the host's CPU, lists no cells (every cell runs the
    feed), and the counter it asks the poller for is the one the
    program registers, summed over every cause."""
    from retina_tpu.metrics import get_metrics
    from retina_tpu.utils import metric_names as mn

    entry = DOC["per_layer"][-1]
    assert entry == {"name": "feed_flushes_per_s", "unit": "flushes/s",
                     "better": "lower", "source": "program_counter",
                     "layer": "feed + combine",
                     "moves": "host_cpu_us_per_event"}
    for w in DOC["workloads"]:
        names = [m["name"]
                 for m in harness.metrics_of(DOC, "per_layer", w["name"])]
        assert "feed_flushes_per_s" in names
    reader = harness.load_reader("feed_flushes_per_s")
    assert mn.FEED_FLUSHES == "networkobservability_" + reader.FLUSHES
    assert reader.COUNTERS == (reader.FLUSHES,)
    for n, cause in enumerate((mn.FLUSH_FULL, mn.FLUSH_AGE, mn.FLUSH_READ,
                               mn.FLUSH_DRAIN), 1):
        get_metrics().feed_flushes.labels(cause=cause).inc(n)
    import poller
    from retina_tpu.exporter import get_exporter

    body = get_exporter().gather_text()
    name = mn.FEED_FLUSHES.encode()
    assert poller.series_sum(body, (name,))[name] == 10.0


def test_the_publish_metrics_are_read_in_every_cell_off_the_programs_counters():
    """``publish_cpu_ms_per_s`` and ``publish_changed_pct`` (PR 32) name
    the publisher's layer and the host's CPU, list no cells (every cell
    publishes the pod-level series), and the
    counters they ask the poller for are the ones the program
    registers: a publish cycle and a gather that renders move all
    three, and the poller sums the CPU seconds over their parts."""
    import numpy as np
    import poller
    from retina_tpu.exporter import get_exporter
    from retina_tpu.utils import metric_names as mn

    by_name = {m["name"]: m for m in DOC["per_layer"]}
    entries = [by_name[n] for n in ("publish_cpu_ms_per_s",
                                    "publish_changed_pct")]
    assert entries == [
        {"name": name, "unit": unit, "better": "lower",
         "source": "program_counter", "layer": "snapshot + publish",
         "moves": "host_cpu_us_per_event"}
        for name, unit in (("publish_cpu_ms_per_s", "ms/s"),
                           ("publish_changed_pct", "%"))]
    cpu = harness.load_reader("publish_cpu_ms_per_s")
    pct = harness.load_reader("publish_changed_pct")
    assert mn.TPU_PUBLISH_CPU_SECONDS == "networkobservability_" + cpu.CPU
    assert mn.TPU_PUBLISH_ROWS == "networkobservability_" + pct.ROWS
    assert mn.TPU_PUBLISH_ROWS_CHANGED == (
        "networkobservability_" + pct.CHANGED)
    assert cpu.COUNTERS == (cpu.CPU,)
    assert pct.COUNTERS == (pct.ROWS, pct.CHANGED)
    ex = get_exporter()
    for part, seconds in zip(mn.PUBLISH_PARTS, (0.25, 0.5, 1.0)):
        ex.publish_cpu[part].inc(seconds)
    ex.publish_rows.inc(40)
    ex.publish_rows_changed.inc(10)
    table = ex.new_adv_table("bench_files_rows", ["pod"])
    table.update(np.array([table.set(("a",), 1.0)[0]]), np.array([2.0]))
    ex.advanced_published()
    assert ex.gather()[1] == "rendered"  # adds its render's CPU seconds
    names = tuple(n.encode() for n in (
        mn.TPU_PUBLISH_CPU_SECONDS, mn.TPU_PUBLISH_ROWS,
        mn.TPU_PUBLISH_ROWS_CHANGED))
    sums = poller.series_sum(ex.gather_text(), names)
    assert sums[names[0]] >= 1.75
    assert (sums[names[1]], sums[names[2]]) == (40.0, 10.0)


def test_the_skew_metric_reads_each_devices_series_off_the_programs_counter():
    """The poller sums a counter over its labels, so ``shard_skew_pct``
    asks for each device's sample by its full name: the name the
    program's ``tpu_shard_rows_counter{device}`` renders to."""
    import poller
    from retina_tpu.exporter import get_exporter
    from retina_tpu.metrics import get_metrics
    from retina_tpu.utils import metric_names as mn

    reader = harness.load_reader("shard_skew_pct")
    assert reader.SERIES.startswith(
        mn.SHARD_ROWS.removeprefix("networkobservability_") + "_total{"
        + mn.L_DEVICE + "=")
    assert all("," not in c for c in reader.COUNTERS)  # --counters a,b,c
    shard = get_metrics().shard_rows
    names = tuple(poller.PREFIX + c.encode() for c in reader.COUNTERS)
    before = poller.series_sum(get_exporter().gather_text(), names)
    for d, n in enumerate((5, 7, 11, 13)):
        shard.labels(device=str(d)).inc(n)
    after = poller.series_sum(get_exporter().gather_text(), names)
    assert [after[n] - before[n] for n in names] == [
        5.0, 7.0, 11.0, 13.0, 0.0, 0.0, 0.0, 0.0]


def test_the_dictionary_metrics_read_the_programs_counters():
    """``dict_clears_per_s`` asks the poller for the counter the program
    registers. ``dict_new_rows_pct`` cannot ask it for the kinds of
    ``tpu_wire_rows_counter``: ``combine_ratio`` asks for that counter's
    sum in every cell, and the poller's prefix match then gives each
    kind's line to the sum; so it parses the process's own exposition
    for the sample names the program's counter renders to, table-less
    rows counted with the new ones."""
    import poller
    from retina_tpu.exporter import get_exporter
    from retina_tpu.metrics import get_metrics
    from retina_tpu.utils import metric_names as mn

    clears = harness.load_reader("dict_clears_per_s")
    assert mn.FLOW_DICT_CLEARS == "networkobservability_" + clears.CLEARS
    assert clears.COUNTERS == (clears.CLEARS,)
    new = harness.load_reader("dict_new_rows_pct")
    assert not hasattr(new, "COUNTERS")
    assert set(new.KINDS) == {mn.WIRE_NEW, mn.WIRE_KNOWN, mn.WIRE_TABLELESS}
    assert set(new.FULL_ROWS) == {mn.WIRE_NEW, mn.WIRE_TABLELESS}
    assert new.SERIES.startswith(
        mn.WIRE_ROWS.removeprefix("networkobservability_") + "_total{"
        + mn.L_KIND + "=")
    m = get_metrics()
    for kind, rows in ((mn.WIRE_NEW, 30), (mn.WIRE_KNOWN, 50),
                       (mn.WIRE_TABLELESS, 20)):
        m.wire_rows.labels(kind=kind).inc(rows)
    m.flow_dict_clears.inc(3)
    assert new.rows_by_kind() == {"new": 30.0, "tableless": 20.0,
                                  "known": 50.0}
    assert new.read(None) == 50.0
    body = get_exporter().gather_text()
    total = poller.PREFIX + harness.load_reader(
        "combine_ratio").COUNTERS[0].encode()
    one = poller.PREFIX + (new.SERIES % "new").encode()
    both = poller.series_sum(body, tuple(sorted((total, one))))
    assert both == {total: 100.0, one: 0.0}  # why the poller is not asked
    name = mn.FLOW_DICT_CLEARS.encode()
    assert poller.series_sum(body, (name,))[name] == 3.0
    # A parent's counter has two kinds and no clears: the share stands.
    by_name = {m["name"]: m for m in DOC["per_layer"]}
    assert by_name["dict_new_rows_pct"]["source"] == "program_counter"
    assert by_name["ingest_device_ms_per_s"]["source"] == "device_trace"


def test_the_cpu_account_readers_read_the_programs_counters():
    """The eight ``host_cpu_*`` metrics (ISSUE 36) name the host's CPU
    and list no cells (every cell has every role); each asks the poller
    for its role's sample of ``tpu_thread_cpu_seconds_counter`` by its
    full name, never for the counter's sum, and for the process's
    counter; the check asks for every role of the program's registry."""
    import cpu_account
    import poller
    from retina_tpu.exporter import get_exporter
    from retina_tpu.obs.cpuaccount import CpuAccount
    from retina_tpu.utils import metric_names as mn

    layers = {"host_cpu_feed_ms_per_s": "feed + combine",
              "host_cpu_dispatch_ms_per_s": "flow dict + wire",
              "host_cpu_proxy_ms_per_s": "device proxy",
              "host_cpu_publish_ms_per_s": "snapshot + publish",
              "host_cpu_serve_ms_per_s": "snapshot + publish",
              "host_cpu_runtime_ms_per_s": "device",
              "host_cpu_generator_ms_per_s": "benchmark generator",
              "host_cpu_unnamed_pct": "device"}
    assert [m for m in DOC["per_layer"] if m["name"] in CPU_READERS] == [
        {"name": name, "unit": "%" if name.endswith("_pct") else "ms/s",
         "better": "lower", "source": "program_counter", "layer": layer,
         "moves": "host_cpu_us_per_event"}
        for name, layer in layers.items()]
    names = [m["name"] for m in DOC["per_layer"]]
    at = names.index(CPU_READERS[0])
    assert names[at:at + 8] == list(CPU_READERS)
    assert cpu_account.ROLES == mn.THREAD_ROLES
    assert mn.TPU_PROCESS_CPU_SECONDS == (
        "networkobservability_" + cpu_account.PROCESS)
    assert cpu_account.SERIES.startswith(
        mn.TPU_THREAD_CPU_SECONDS.removeprefix("networkobservability_")
        + "_total{" + mn.L_ROLE + "=")
    for name, role in CPU_ROLE_READERS.items():
        reader = harness.load_reader(name)
        assert reader.COUNTERS == (
            cpu_account.PROCESS, cpu_account.SERIES % role), name
    check = harness.load_reader("host_cpu_unnamed_pct")
    assert check.COUNTERS == cpu_account.counters(*mn.THREAD_ROLES)
    asked = {c for n in CPU_READERS
             for c in harness.load_reader(n).COUNTERS}
    assert all("," not in c for c in asked)  # --counters a,b,c
    assert mn.TPU_THREAD_CPU_SECONDS.removeprefix(
        "networkobservability_") not in asked
    # A sample of this process's account, as the poller reads it: the
    # process, and the roles by their full names (this thread's, the
    # test runner's main thread, is nobody the program spawned).
    CpuAccount().sample()
    names = tuple(poller.PREFIX + c.encode()
                  for c in cpu_account.counters(*mn.THREAD_ROLES))
    sums = poller.series_sum(get_exporter().gather_text(), names)
    foreign = poller.PREFIX + (cpu_account.SERIES % mn.ROLE_FOREIGN).encode()
    assert sums[names[0]] >= sums[foreign] > 0
    assert sum(sums[n] for n in names[1:]) <= sums[names[0]] * 1.05
    # A role that burnt nothing reads 0.0, not nothing: the account is
    # there, and the result line must hold the metric in every cell.
    assert cpu_account.role_ms_per_s(RECORDED, "nobody") == 0.0


# -- ISSUE 37: the stall readers --------------------------------------------
STALL_READERS = {"process_stall_longest_ms": ("program_span", "device"),
                 "thread_stall_longest_ms": ("program_span", "device proxy"),
                 "wake_late_mean_ms": ("program_counter", "device")}


def _stall(t0, t1, cause, **args):
    return {"stage": "stall", "t0": t0, "t1": t1, "thread": "watchdog",
            "args": {"cause": cause, "gap_s": round(t1 - t0, 4), **args}}


def _stall_run(monkeypatch, spans, has_stage=True):
    """The window [10, 60) of a run whose recorder holds ``spans``."""
    import stalls

    monkeypatch.setattr(stalls, "_spans", lambda: list(spans))
    monkeypatch.setattr(stalls, "has_stage", lambda: has_stage)
    return _load([])


def test_the_stall_metrics_are_read_in_every_cell_and_end_the_list():
    """Appended together, after every entry that was there before
    them; only entries added since follow them."""
    names = [m["name"] for m in DOC["per_layer"]]
    i = names.index(next(iter(STALL_READERS)))
    assert names[i:i + 3] == list(STALL_READERS)
    assert names[i + 3:] == ["feed_flushes_per_s"]
    by_name = {m["name"]: m for m in DOC["per_layer"]}
    for name, (source, layer) in STALL_READERS.items():
        m = by_name[name]
        assert "workloads" not in m  # every cell has a watchdog
        assert (m["source"], m["layer"], m["unit"], m["better"],
                m["moves"]) == (source, layer, "ms", "lower",
                                "scrape_p95_ms")
        assert harness.load_reader(name).UNIT == "ms"
    from retina_tpu.utils import metric_names as mn

    late = harness.load_reader("wake_late_mean_ms")
    assert [mn.PREFIX + c for c in late.COUNTERS] == [
        mn.TPU_WAKE_LATE_SECONDS, mn.TPU_WATCHDOG_SCANS]


@pytest.mark.parametrize("name", ["process_stall_longest_ms",
                                  "thread_stall_longest_ms"])
def test_a_sound_run_reads_zero_and_a_program_without_the_stage_nothing(
        name, monkeypatch):
    """0.0 is a reading: the program looked and found no stall. A
    parent's program never looked."""
    reader = harness.load_reader(name)
    other = [{"stage": "proxy_run", "t0": 20.0, "t1": 24.0,
              "args": {"kind": "step"}}]
    assert reader.read(_stall_run(monkeypatch, other)) == 0.0
    assert reader.read(_stall_run(monkeypatch, [])) == 0.0
    assert reader.read(_stall_run(monkeypatch, other, False)) is None


def test_the_stall_readers_take_the_longest_of_their_own_causes(
        monkeypatch):
    spans = [_stall(12.0, 14.5, "paused", cpu_user_s=0.01),
             _stall(30.0, 33.2, "held", top_thread="spinner"),
             _stall(40.0, 41.6, "thread", thread="device-proxy",
                    kind="step"),
             _stall(44.0, 48.25, "thread", thread="device-completion"),
             # Outside the window: boot's compile, and one after it.
             _stall(2.0, 9.5, "thread", thread="device-proxy",
                    kind="other"),
             _stall(60.0, 69.0, "paused")]
    run = _stall_run(monkeypatch, spans)
    assert harness.load_reader("process_stall_longest_ms").read(run) == \
        pytest.approx(3200.0)
    assert harness.load_reader("thread_stall_longest_ms").read(run) == \
        pytest.approx(4250.0)


def test_a_stall_that_began_before_the_window_and_ended_in_it_counts(
        monkeypatch):
    """``host_spans.window_spans`` takes the spans that began in the
    window; a hole that the window opens inside is the window's too,
    and so is one it closes inside."""
    process = harness.load_reader("process_stall_longest_ms")
    thread = harness.load_reader("thread_stall_longest_ms")
    run = _stall_run(monkeypatch, [_stall(7.0, 11.5, "paused"),
                                   _stall(58.0, 63.0, "thread")])
    assert process.read(run) == pytest.approx(4500.0)
    assert thread.read(run) == pytest.approx(5000.0)
    run = _stall_run(monkeypatch, [_stall(7.0, 10.0, "held"),
                                   _stall(60.0, 63.0, "thread")])
    assert process.read(run) == 0.0 and thread.read(run) == 0.0


def test_the_stalls_line_is_logged_once_a_run_with_what_straddles_each(
        monkeypatch, capsys):
    spans = [_stall(30.0, 32.4, "paused", unparked=["device-proxy:step"]),
             {"stage": "proxy_run", "t0": 29.9, "t1": 32.5,
              "thread": "device-proxy",
              "args": {"kind": "step", "wait_s": 0.0}},
             {"stage": "render", "t0": 31.0, "t1": 31.2, "thread": "shared",
              "args": {}}]
    run = _stall_run(monkeypatch, spans)
    harness.load_reader("process_stall_longest_ms").read(run)
    harness.load_reader("thread_stall_longest_ms").read(run)
    lines = [json.loads(ln) for ln in capsys.readouterr().err.splitlines()
             if '"stalls"' in ln]
    (line,) = lines
    assert line["phase"] == "stalls" and line["count"] == 1
    (st,) = line["stalls"]
    assert st["cause"] == "paused" and st["at_s"] == 20.0
    assert st["unparked"] == ["device-proxy:step"]
    (inside,) = st["straddling"]
    assert inside["stage"] == "proxy_run"
    assert inside["args"]["kind"] == "step"
    assert inside["began_before_s"] == pytest.approx(0.1)
    assert inside["ended_after_s"] == pytest.approx(0.1)


def test_the_wake_lateness_is_the_counters_ratio_over_the_window():
    late = harness.load_reader("wake_late_mean_ms")
    run = _load([
        _scrape(9.0, tpu_wake_late_seconds_counter=9.0,
                tpu_watchdog_scans_counter=10.0),
        _scrape(10.0, tpu_wake_late_seconds_counter=0.5,
                tpu_watchdog_scans_counter=100.0),
        _scrape(35.0, tpu_wake_late_seconds_counter=0.6,
                tpu_watchdog_scans_counter=150.0),
        _scrape(59.0, tpu_wake_late_seconds_counter=0.794,
                tpu_watchdog_scans_counter=198.0),
        _scrape(61.0, tpu_wake_late_seconds_counter=99.0,
                tpu_watchdog_scans_counter=202.0)])
    assert late.read(run) == pytest.approx(3.0)  # 0.294 s over 98 scans
    # A parent's program has neither counter: the poller sums nothing.
    assert late.read(PARENT) is None and late.read(_load([])) is None


def test_the_stall_readers_read_the_programs_own_recorder():
    """End to end on the real recorder: a stall written by the
    supervisor is what the reader finds."""
    import stalls
    from retina_tpu.obs.recorder import initialize_recorder
    from retina_tpu.utils import metric_names as mn

    rec = initialize_recorder()
    assert stalls.has_stage() and stalls.STAGE == mn.STAGE_STALL
    assert set(stalls.PROCESS + stalls.THREAD) == set(mn.STALL_CAUSES)
    rec.post_hoc(mn.STAGE_STALL, 20.0, 22.5, cause=mn.STALL_HELD,
                 gap_s=2.5)
    run = _load([])
    assert harness.load_reader("process_stall_longest_ms").read(run) == \
        pytest.approx(2500.0)
    assert harness.load_reader("thread_stall_longest_ms").read(run) == 0.0
