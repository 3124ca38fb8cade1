"""The benchmark's own tests. Run by hand, not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(BENCH, "layer_metrics"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import control  # noqa: E402
import measure  # noqa: E402
import roofline  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

CELL = "advanced-pod.zipf1m-block1s"
MIX = "zipf1m-block1s"
CPU = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _doc() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_resolves_to_a_file():
    """A cell, a configuration, a traffic mix and a per-layer metric are
    each a file found by the name BENCHMARK.json gives; the state bytes
    the roofline counts are the state's (12,191,148 at the configmap's
    shapes, PERF.md)."""
    doc = _doc()
    configs = {c["name"]: c for c in doc["configs"]}
    for c in configs.values():
        assert c["file"].startswith("benchmarks/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert sum(roofline.state_tables(
            cfg["step_shapes"], True).values()) == 12_191_148
        assert roofline.step_bytes(cfg) > 0
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    for w in doc["workloads"]:
        assert w["config"] in configs
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        traffic.load_mix(w["traffic"])
        traffic.load_mix(w["traffic"], rehearse=True)
    for m in doc["per_layer"]:
        reader = os.path.join(BENCH, "layer_metrics", f"{m['name']}.py")
        assert os.path.exists(reader), m["name"]
        assert m["moves"] in e2e
    with pytest.raises(KeyError):
        roofline.peak("TPU v9 imaginary")


def test_freshness_on_a_hand_made_schedule_with_a_stall():
    """Four ticks of 10 events, due at 0, 1, 2, 3 s. Scrapes end at
    0.5, 1.5, ... and show what the agent has published; it stalls after
    20 events until the scrape that ends at 5.5 s."""
    ticks = [(0.0, 10), (1.0, 20), (2.0, 30), (3.0, 40)]
    shown = [10, 20, 20, 20, 20, 40, 40]
    scrapes = [(i + 0.4, i + 0.5, n) for i, n in enumerate(shown)]
    assert measure.freshness_s(ticks, scrapes) == pytest.approx(
        [0.5, 0.5, 3.5, 2.5])
    # A tick nothing ever shows reads infinity, not the last scrape.
    assert measure.freshness_s([(0.0, 50)], scrapes) == [math.inf]
    # A scrape sent before the tick was due cannot vouch for it.
    assert measure.freshness_s([(0.45, 10)], scrapes) == pytest.approx(
        [1.05])
    # Per scrape: the oldest tick due when it was sent that it lacks.
    # The scrape ending at 1.5 s shows 20 (ticks 0 and 1) and was sent
    # at 1.4 s, before tick 2 was due: only its own round trip. The
    # stall: every scrape from 2.5 to 4.5 s lacks tick 2 (due at 2 s).
    assert measure.staleness_s(ticks, scrapes) == pytest.approx(
        [0.1, 0.1, 0.5, 1.5, 2.5, 0.1, 0.1])
    assert measure.percentile([1, 2, 3, 4], 95) == 4
    assert measure.percentile(list(range(1, 101)), 95) == 95
    assert measure.round_trips_s(scrapes, 1.0, 3.0) == pytest.approx(
        [0.1, 0.1])


def test_pacer_hands_every_row_on_time_and_records_a_late_consumer():
    mix = traffic.load_mix(MIX, rehearse=True)
    pool = traffic.make_pool(mix, seed=3)
    again = traffic.make_pool(mix, seed=3)
    assert np.array_equal(pool, again)  # the same seed, the same inputs
    assert not np.array_equal(pool, traffic.make_pool(mix, seed=2**31 + 5))
    sched = traffic.Schedule(rows_per_tick=2560, tick_s=0.02, warm_ticks=2,
                             window_ticks=8)
    got = []

    def put(block):
        if len(got) == 6:
            time.sleep(0.05)  # a consumer that blocks delays the next
        got.append(block)

    pacer = traffic.Pacer(pool, sched, mix.block_rows, put, rate=128000)
    pacer.start()
    pacer.begin(time.monotonic() + 0.05)
    pacer.join(10.0)
    assert not pacer.is_alive() and pacer.error is None
    rows = np.concatenate(got)
    assert len(rows) == sched.total_rows == 25600
    assert max(len(b) for b in got) <= mix.block_rows
    # In the pool's order, lap after lap, restamped and nothing else.
    want = pool[np.arange(sched.total_rows) % len(pool)]
    assert np.array_equal(rows[:, 2:], want[:, 2:])
    ts = rows[:, 0].astype(np.int64) | (rows[:, 1].astype(np.int64) << 32)
    assert abs(ts[0] - time.time_ns()) < 60e9 and (np.diff(ts) > 0).all()
    assert pacer.pos == sched.total_rows % len(pool)
    late = [s for _, s, _ in pacer.late]
    assert len(late) == len(got) and min(late) >= 0.0
    assert max(late) >= 0.04  # the blocked hand-over shows as lateness


def test_trace_reduction_on_a_recorded_trace():
    """A trace recorded on the chip in PR 25 (one TPU v5 lite, the
    advanced-pod agent under load), cut to its device plane."""
    path = os.path.join(HERE, "data", "step.xplane.pb")
    with open(os.path.join(HERE, "data", "step.expected.json")) as f:
        want = json.load(f)
    t = trace_reduce.reduce(path, window_s=want["window_s"])
    assert len(t.chips) == 1
    assert t.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < t.busy_s < t.window_s
    ms = t.program_ms(want["step_program"])
    assert len(ms) == want["step_executions"]
    assert sum(ms) / len(ms) == pytest.approx(want["step_device_ms"])
    ops = t.device_ops(3)
    assert [n for n, _ in ops] == want["top_ops"]
    assert t.idle_gaps(3)
    assert trace_reduce.program_name("jit_local_step(123)") == \
        "jit_local_step"


HELD = {"heavy_hitter_recall_at_50_min": 0.9,
        "hll_distinct_flows_rel_err_max": 0.1,
        "conntrack_packets_share_min": 0.25}


@pytest.mark.parametrize("which,caught_by", [
    ("lost_block", "events_unaccounted"),
    ("sampled", "pod_forward_series_mismatched"),
    ("sketch_unchanged", "heavy_hitter_recall_at_50"),
    ("sketch_unchanged", "hll_distinct_flows_rel_err"),
    ("sketch_unchanged", "conntrack_packets_share"),
    ("sketch_half", "hll_distinct_flows_rel_err"),
])
def test_the_control_comes_out_not_correct(which, caught_by):
    """The reference in the agent's place with one guarantee broken is
    not correct; with none broken it is."""
    # Flows enough that half of the events miss some of them.
    mix = dataclasses.replace(traffic.load_mix(MIX, rehearse=True),
                              n_flows=50000)
    pool = traffic.make_pool(mix, seed=9)
    total = 5 * len(pool) // 2
    assert control.read(pool, total, mix, HELD, None, 9).correct
    v = control.read(pool, total, mix, HELD, which, 9)
    assert not v.correct
    assert caught_by in v.failed_names()


def test_a_metric_is_reported_only_in_the_cells_it_lists():
    """An entry's ``workloads`` key names the cells that report it;
    without the key every cell does (the contract's rule for a metric
    that only some cells can read)."""
    import harness

    doc = {"per_layer": [{"name": "a"},
                         {"name": "b", "workloads": ["x.t"]}]}
    assert [m["name"] for m in harness.metrics_of(doc, "per_layer", "x.t")] \
        == ["a", "b"]
    assert [m["name"] for m in harness.metrics_of(doc, "per_layer", "y.t")] \
        == ["a"]


def test_rehearsal_ends_not_correct_and_off_the_chip_nothing_is_printed():
    run = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
           "--seed", "4000000007", "--seconds", "3", "--trace", "0"]
    bare = subprocess.run(run, env=CPU, capture_output=True, text=True,
                          timeout=120)
    assert bare.returncode != 0 and bare.stdout.strip() == ""
    r = subprocess.run(run + ["--rehearse"], env=CPU, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    c = line["compared"]
    assert c["platform_is_tpu"]["value"] == 0
    for exact in ("pod_forward_series_mismatched", "events_unaccounted",
                  "pod_drop_series_mismatched", "events_not_accepted",
                  "overload_transitions", "compiles_in_window"):
        assert c[exact]["value"] == 0, (exact, c[exact])
    assert set(line["metrics"]) == {m["name"] for m in _doc()["end_to_end"]}
    assert "compared pod_forward_series_mismatched = 0" in r.stderr


@pytest.mark.parametrize("fault,caught_by", [
    ("none", None),
    ("state_unchanged", "events_unaccounted"),
    ("half_batch", "events_unaccounted"),
    ("sketch_unchanged", "heavy_hitter_recall_at_50"),
    ("answer_altered", "pod_forward_series_mismatched"),
])
def test_a_broken_timed_path_comes_out_not_correct(fault, caught_by):
    """The rest of a run after the look for a chip, with the timed path
    broken underneath. (One chip: there is no exchange to leave out.)
    The sound run beside them comes out correct."""
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive_fault.py"), fault, CELL],
        env=CPU, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    v = json.loads(r.stdout.strip().splitlines()[-1])
    if caught_by is None:
        assert v["correct"] is True and not v["failed"]
    else:
        assert v["correct"] is False
        assert caught_by in v["failed"]
