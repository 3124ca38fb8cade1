"""The node where nearly every packet is a new connection (ISSUE 35).

The deployment ``advanced-pod-churn`` at rehearsal shapes, on the CPU,
through the daemon's normal path as the benchmark boots it (plugin
``emit`` -> sink -> feed -> combine -> flow dictionary -> wire -> fused
step -> close -> publisher -> ``/metrics``), fed a ``churn8m``-shaped
pool from a seed, with a dictionary small enough to turn over ten times
and more and one hand-over that holds more new descriptors than the
dictionary has slots (the table-less arm). What the benchmark's cell
holds the agent to, against the plain reference: per-pod forward and
drop series equal, every event accounted, nothing lost or sampled,
NOMINAL with no transition; and the counters this deployment reads:
``tpu_flow_dict_clears_counter`` is the generation's advance, and the
wire rows by kind add up to the rows the steps were given. With the
native dictionary and with the Python one.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import agent as bench_agent  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402

from retina_tpu import native  # noqa: E402
from retina_tpu.metrics import get_metrics  # noqa: E402
from retina_tpu.parallel.flowdict import HostFlowDict  # noqa: E402
from retina_tpu.utils import metric_names as mn  # noqa: E402

# The dictionary, of ~40,000 distinct descriptors offered: over a usual
# dispatch's rows (a step's worth, 2,048 at rehearsal shapes, or 0.4 s
# of hand-overs), so that flows recur inside a generation, and under
# the big hand-over's.
DICT_SLOTS = 2560
HAND_OVER = 1024  # rows, sixteen a second: the rehearsal's ring cadence
BIG_HAND_OVER = 8192  # one flush with more new descriptors than slots
HAND_OVERS = 48
SEED = 4000035001


def _counters() -> dict[str, float]:
    m = get_metrics()
    return {**{k: m.wire_rows.labels(kind=k)._value.get()
               for k in (mn.WIRE_NEW, mn.WIRE_KNOWN, mn.WIRE_TABLELESS)},
            "clears": m.flow_dict_clears._value.get(),
            "step_rows": m.step_rows._value.get()}


def _stamped(rows: np.ndarray) -> np.ndarray:
    """A copy stamped as the benchmark's pacer stamps a hand-over: an
    unstamped row crosses in full whatever the dictionary knows."""
    block = rows.copy()
    ts = time.time_ns() + np.arange(len(block), dtype=np.int64) * 1000
    block[:, traffic.TS_LO] = (ts & 0xFFFFFFFF).astype(np.uint32)
    block[:, traffic.TS_HI] = (ts >> 32).astype(np.uint32)
    return block


@pytest.fixture
def seeded_source():
    """The benchmark's source plugin, registered for this test alone."""
    from retina_tpu.plugins import registry

    had = "seededsource" in registry.names()
    bench_agent.register_source()
    yield
    if not had:
        registry._registry.pop("seededsource", None)


@pytest.mark.parametrize("dictionary", ["native", "python"])
def test_the_churn_deployment_agrees_with_the_plain_reference(
        dictionary, tmp_path, monkeypatch, seeded_source):
    if dictionary == "native":
        if not native.native_available():
            pytest.skip("native library unavailable")
    else:
        monkeypatch.setattr("retina_tpu.engine.make_flow_dict",
                            HostFlowDict)
    with open(os.path.join(
            BENCH, "configs", "advanced-pod-churn.json")) as f:
        config = json.load(f)
    assert config["sizing"] == {}  # the dictionary is the agent's default
    mix = traffic.load_mix("churn8m-steady", rehearse=True)
    pool = traffic.make_pool(mix, SEED)
    cfg = bench_agent.build_config(config, str(tmp_path), "", "", True)
    cfg.flow_dict_slots = DICT_SLOTS
    # A feed worker holds what it is dealt up to its quantum or
    # flush_max_age_s. In the cell a flush (0.4 s of hand-overs, about
    # 91,000 rows) is under a step (131,072) and under the dictionary
    # (262,144); at rehearsal shapes 0.4 s of hand-overs is three steps
    # and twice this dictionary. A step's worth of quantum keeps the
    # cell's proportions, so that flows recur across flushes inside a
    # generation.
    cfg.flush_max_events = cfg.batch_capacity
    cfg.feed_workers = 2  # the pool, whatever the machine's cores
    cfg.mesh_devices = 1  # one chip, of the test session's eight virtual
    agent = bench_agent.Agent(cfg, mix.n_endpoints, ready_deadline_s=300.0,
                              warm_deadline_s=300.0)
    try:
        agent.wait_ready()
        agent.wait_tables()
        agent.wait_warm()
        eng, src = agent.engine, agent.source
        want_kind = "NativeFlowDict" if dictionary == "native" \
            else "HostFlowDict"
        assert type(eng._flow_dict).__name__ == want_kind
        assert eng._flow_dict.capacity == DICT_SLOTS
        gen0, c0 = eng._flow_dict.generation, _counters()
        pos = 0
        for i in range(HAND_OVERS):
            n = BIG_HAND_OVER if i == HAND_OVERS // 2 else HAND_OVER
            src.inbox.put(_stamped(pool[pos:pos + n]))
            pos += n
            time.sleep(1.0 / mix.ticks_per_s)
        assert pos <= len(pool)

        def shown() -> bool:
            if src.offered < pos or not agent.settled():
                return False
            body = bench_agent.http_get(agent.port, "/metrics")[1].decode()
            s = reference.Scrape(body)
            return s.total("adv_forward_count") \
                + s.total("adv_drop_count") >= pos

        bench_agent.wait_for("every event on a scrape", shown, 120.0, 0.2,
                             agent.alive)
        scrape = reference.Scrape(
            bench_agent.http_get(agent.port, "/metrics")[1].decode())
        dvars = json.loads(bench_agent.http_get(agent.port, "/debug/vars")[1])
        gen1, c1 = eng._flow_dict.generation, _counters()
    finally:
        agent.shutdown()

    # The cell's exact comparisons, by the code that decides `correct`.
    v = reference.Verdict()
    reference.compare(scrape, pool, pos, mix.n_endpoints,
                      {"heavy_hitter_recall_at_50_min": 0.0,
                       "hll_distinct_flows_rel_err_max": 1.0}, v)
    got = {name: value for name, value, _, _ in v.rows}
    assert got["pod_forward_series_mismatched"] == 0, v.notes
    assert got["pod_drop_series_mismatched"] == 0, v.notes
    assert got["events_unaccounted"] == 0, v.notes
    assert v.notes["forward_series"] > 0 and v.notes["drop_series"] > 0
    assert src.accepted == pos
    assert reference.health_nonzero(scrape) == {}
    ov = dvars["overload"]
    assert ov["state"] == "NOMINAL" and ov["transitions"] == 0, ov

    # The dictionary turned over, and the counter says how often.
    d = {k: c1[k] - c0[k] for k in c0}
    assert d["clears"] >= 10, d
    assert d["clears"] == gen1 - gen0, (d, gen0, gen1)
    assert scrape.total("tpu_flow_dict_clears_counter") == c1["clears"]
    assert scrape.total("tpu_flow_dict_generation") == gen1
    # Every row a step was given crossed as one of the three kinds; the
    # big hand-over's flush found the table full.
    assert d[mn.WIRE_NEW] + d[mn.WIRE_KNOWN] + d[mn.WIRE_TABLELESS] \
        == d["step_rows"] > 0, d
    assert d[mn.WIRE_TABLELESS] > 0 and d[mn.WIRE_KNOWN] > 0, d
    assert d[mn.WIRE_NEW] > d[mn.WIRE_KNOWN], d  # churn: mostly new
    assert scrape.total("tpu_wire_rows_counter", kind=mn.WIRE_TABLELESS) \
        == c1[mn.WIRE_TABLELESS]
