"""``feed_wakeups_per_s`` (PR 30) on a hand-made load. Run by hand with
the benchmark's other tests, not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(BENCH, "layer_metrics"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import feed_wakeups_per_s as reader  # noqa: E402
import harness  # noqa: E402
import poller  # noqa: E402

NAME = "tpu_feed_wakeups_counter"


def _load(samples):
    """Window [10, 60); ``samples``: (sent, counter value or None)."""
    return types.SimpleNamespace(t_open=10.0, t_close=60.0, scrapes=[
        {"sent": t, "done": t + 0.01, "ok": True,
         "c": {} if v is None else {NAME: v}} for t, v in samples])


def test_the_entry_is_the_last_of_the_per_layer_list_and_lists_no_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entry = doc["per_layer"][-1]
    assert entry["name"] == "feed_wakeups_per_s"
    assert entry["unit"] == reader.UNIT == "wakeups/s"
    assert entry["moves"] == "host_cpu_us_per_event"
    assert "workloads" not in entry
    for w in doc["workloads"]:
        names = [m["name"]
                 for m in harness.metrics_of(doc, "per_layer", w["name"])]
        assert "feed_wakeups_per_s" in names
    assert harness.load_reader("feed_wakeups_per_s").COUNTERS == (NAME,)


def test_wakeups_a_second_between_the_windows_first_and_last_scrape():
    run = _load([(9.0, 900.0), (10.0, 1000.0), (35.0, 3500.0),
                 (59.0, 5900.0), (61.0, 9999.0)])
    assert reader.read(run) == pytest.approx(4900.0 / 49.0)


@pytest.mark.parametrize("samples", [
    [(10.0, 0.0), (35.0, 0.0), (59.0, 0.0)],     # the poller's sum of no series
    [(10.0, None), (35.0, None), (59.0, None)],  # a poller not asked for it
    [(35.0, 500.0)],                              # one scrape in the window
    [],
])
def test_a_program_without_the_counter_reads_nothing(samples):
    assert reader.read(_load(samples)) is None


def test_the_poller_sums_the_counter_over_its_labels():
    body = (
        b"# TYPE networkobservability_tpu_feed_wakeups_counter_total counter\n"
        b'networkobservability_tpu_feed_wakeups_counter_total'
        b'{cause="data",thread="feed"} 16.0\n'
        b'networkobservability_tpu_feed_wakeups_counter_total'
        b'{cause="deadline",thread="feed"} 10.0\n'
        b'networkobservability_tpu_feed_wakeups_counter_total'
        b'{cause="data",thread="dispatch"} 24.0\n')
    name = poller.PREFIX + NAME.encode()
    assert poller.series_sum(body, (name,))[name] == 50.0
