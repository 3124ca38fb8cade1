"""v2 wire: flow-descriptor dictionary (parallel/flowdict.py + engine).

The dictionary is a pure transport optimization — the device state after
feeding any traffic through the dict path must be EXACTLY the state the
plain packed path produces. These tests pin that equivalence, the
generation/overflow behavior, and the wire-size win.
"""

from __future__ import annotations

import numpy as np
import pytest

from retina_tpu.config import Config
from retina_tpu.engine import SketchEngine
from retina_tpu.events.synthetic import TrafficGen
from retina_tpu.parallel.flowdict import HostFlowDict


def small_cfg(**kw) -> Config:
    cfg = Config()
    cfg.batch_capacity = 1 << 10
    cfg.n_pods = 1 << 6
    cfg.cms_width = 1 << 10
    cfg.cms_depth = 2
    cfg.topk_slots = 1 << 6
    cfg.hll_precision = 8
    cfg.entropy_buckets = 1 << 8
    cfg.conntrack_slots = 1 << 10
    cfg.identity_slots = 1 << 8
    cfg.flow_dict_slots = 1 << 12
    # Small batches must still take the dict path in these tests (the
    # engine shortcuts sub-min_bucket flushes through the plain path).
    cfg.transfer_min_bucket = 64
    cfg.bypass_lookup_ip_of_interest = True
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


# ------------------------------------------------------------- host dict
def test_host_dict_assign_reuse_and_generation():
    gen = TrafficGen(n_flows=50, n_pods=16, seed=3)
    rec = gen.batch(256)
    d = HostFlowDict(capacity=1 << 10)
    ids1, new1 = d.lookup_or_assign(rec)
    # Exactly the FIRST occurrence of each distinct descriptor is new;
    # repeats within the same batch resolve to the id just assigned.
    n_distinct = len(d)
    assert new1.sum() == n_distinct
    assert ids1.min() >= 1  # slot 0 is the overflow sentinel
    # Same records again: everything known, same ids.
    ids2, new2 = d.lookup_or_assign(rec)
    assert not new2.any()
    np.testing.assert_array_equal(ids1, ids2)
    g = d.generation
    d.clear()
    assert d.generation == g + 1 and len(d) == 0
    ids3, new3 = d.lookup_or_assign(rec)
    assert new3.sum() == n_distinct  # re-assigned from scratch


def test_host_dict_overflow_clears_generation():
    d = HostFlowDict(capacity=64)
    a = TrafficGen(n_flows=40, n_pods=8, seed=1).batch(128)
    d.lookup_or_assign(a)
    g = d.generation
    # A second distinct batch that cannot fit forces a clear.
    b = TrafficGen(n_flows=200, n_pods=8, seed=9).batch(512)
    ids, new = d.lookup_or_assign(b)
    assert d.generation == g + 1
    # Distinct descriptors beyond capacity fall back to sentinel id 0.
    assert (ids == 0).sum() >= 0  # sentinel rows allowed
    assert new.any()


def test_native_matches_python_dict():
    """The C++ dictionary (native/flowdict.cpp) must agree with the
    Python reference on ids, newness, lengths, and generation behavior
    — including intra-batch repeats and overflow."""
    from retina_tpu.native import native_available

    if not native_available():
        pytest.skip("native library unavailable")
    from retina_tpu.native import NativeFlowDict

    for capacity, n_flows, batches in ((1 << 10, 80, 3), (64, 200, 2)):
        py = HostFlowDict(capacity)
        nat = NativeFlowDict(capacity)
        gen = TrafficGen(n_flows=n_flows, n_pods=16, seed=capacity)
        for _ in range(batches):
            rec = gen.batch(400)
            ids_p, new_p = py.lookup_or_assign(rec)
            ids_n, new_n = nat.lookup_or_assign(rec)
            np.testing.assert_array_equal(ids_p, ids_n)
            np.testing.assert_array_equal(new_p, new_n)
            assert len(py) == len(nat)
            assert py.generation == nat.generation
        nat.close()


# -------------------------------------------------------- engine parity
def _feed(eng: SketchEngine, quanta: list[np.ndarray]) -> dict:
    eng.compile()
    for i, q in enumerate(quanta):
        eng.step_records(q, now_s=10 + i)
    return eng.snapshot(max_age_s=0)


def test_dict_path_state_equals_plain_path():
    """Repeated-flow traffic over several quanta: the dict path (flows
    upload descriptors once, then 16B tuples) must reconstruct the SAME
    rows on device — every order-independent aggregator (counter
    rectangles, CMS, HLL, entropy, top-k without eviction pressure) is
    bit-identical to the plain packed path. Conntrack REPORT totals are
    step-boundary-dependent (the dict path splits a quantum into
    new/known sub-steps, changing when the sampler emits), so they get a
    tolerance, not equality."""
    # topk_slots > distinct keys: no eviction, so candidate tables are
    # insertion-order-invariant. Aggregation level "high": per-packet
    # sketch feeds — "low" samples via conntrack reports, whose
    # emission times are step-boundary-dependent by design.
    kw = dict(topk_slots=1 << 9, data_aggregation_level="high")
    gen = TrafficGen(n_flows=120, n_pods=48, seed=5)
    ring = [gen.batch(700) for _ in range(3)]
    quanta = ring + ring  # second pass: every descriptor already known

    eng_plain = SketchEngine(small_cfg(wire_flow_dict=False, **kw))
    eng_plain.update_identities({0x0A000000 + i: i for i in range(1, 40)})
    snap_a = _feed(eng_plain, quanta)

    eng_dict = SketchEngine(small_cfg(**kw))
    assert eng_dict._flow_dict is not None
    eng_dict.update_identities({0x0A000000 + i: i for i in range(1, 40)})
    snap_b = _feed(eng_dict, quanta)

    loose = {"steps", "ct_totals", "active_conns", "totals"}
    import jax

    strict_a = {k: v for k, v in snap_a.items() if k not in loose}
    strict_b = {k: v for k, v in snap_b.items() if k not in loose}
    leaves_a = jax.tree_util.tree_flatten_with_path(strict_a)[0]
    leaves_b = jax.tree_util.tree_flatten_with_path(strict_b)[0]
    assert len(leaves_a) == len(leaves_b)
    for (pa, va), (_pb, vb) in zip(leaves_a, leaves_b):
        path = jax.tree_util.keystr(pa)
        va, vb = np.asarray(va), np.asarray(vb)
        if "_hh" in path and "counts" in path:
            # Candidate-table counts are the CMS estimate AT UPDATE
            # TIME; sub-step boundaries shift when estimates are taken,
            # so hh counts carry the sketch's small error band while
            # the key sets stay exact.
            np.testing.assert_allclose(
                va.astype(np.float64), vb.astype(np.float64),
                atol=32, err_msg=f"snapshot{path} diverged",
            )
        else:
            np.testing.assert_array_equal(
                va, vb, err_msg=f"snapshot{path} diverged"
            )
    ta, tb = np.asarray(snap_a["totals"]), np.asarray(snap_b["totals"])
    assert ta[0] == tb[0]  # events admitted: exact
    assert ta[7] == tb[7]  # losses: exact
    np.testing.assert_allclose(
        np.asarray(snap_a["ct_totals"], np.float64),
        np.asarray(snap_b["ct_totals"], np.float64),
        rtol=0.1,
    )
    # And the dictionary actually dedup'd: second pass was all-known.
    assert len(eng_dict._flow_dict) > 0


def test_known_rows_escalate_on_packet_overflow():
    """Known rows ship dense, with a DENSE_PK_BITS-wide packets lane.
    Packet counts that overflow it must ESCALATE to the full-row side
    — never clamp — so pod packet counters stay exact."""
    from retina_tpu.events.schema import F
    from retina_tpu.metrics import get_metrics
    from retina_tpu.parallel.wire import DENSE_PK_BITS

    kw = dict(topk_slots=1 << 9, data_aggregation_level="high")
    gen = TrafficGen(n_flows=60, n_pods=24, seed=9)
    big = np.uint32(1 << 21)

    q = gen.batch(300)
    # Half the rows carry packet counts beyond the known-lane headroom,
    # half stay tiny.
    q[: len(q) // 2, F.PACKETS] = big
    quanta = [q, q.copy(), q.copy()]  # passes 2-3: all descriptors known

    eng_plain = SketchEngine(small_cfg(wire_flow_dict=False, **kw))
    eng_plain.update_identities({0x0A000000 + i: i for i in range(1, 20)})
    snap_a = _feed(eng_plain, quanta)

    eng_dict = SketchEngine(small_cfg(**kw))
    eng_dict.update_identities({0x0A000000 + i: i for i in range(1, 20)})
    assert int(big) >= (1 << DENSE_PK_BITS)
    m0 = get_metrics().wire_rows.labels(kind="known")._value.get()
    snap_b = _feed(eng_dict, quanta)
    known_rows = (
        get_metrics().wire_rows.labels(kind="known")._value.get() - m0
    )
    # Small-packet repeats DID ride the known side...
    assert known_rows > 0
    # ...and the exact counters agree with the plain path despite the
    # escalated rows.
    for k in ("pod_forward", "pod_drop"):
        np.testing.assert_array_equal(
            np.asarray(snap_a[k]), np.asarray(snap_b[k]), err_msg=k
        )
    assert (
        np.asarray(snap_a["totals"])[0] == np.asarray(snap_b["totals"])[0]
    )


def test_v3_latency_and_unstamped_rows_never_ride_known_path():
    """The 8-byte known lane replaces per-row time with the flush base,
    so rows where exact time matters must escalate: TSval/TSecr carriers
    (apiserver RTT matcher) and unstamped rows (TS_REL=0 must round-trip
    to ts 0, parallel/wire.py:17-23)."""
    from retina_tpu.events.schema import F
    from retina_tpu.metrics import get_metrics

    eng = SketchEngine(small_cfg(data_aggregation_level="high"))
    eng.compile()
    gen = TrafficGen(n_flows=40, n_pods=16, seed=11)
    q = gen.batch(200)
    q[: len(q) // 3, F.TSVAL] = 12345  # RTT-relevant
    third = len(q) // 3
    q[third : 2 * third, F.TS_LO] = 0  # unstamped
    q[third : 2 * third, F.TS_HI] = 0
    known = get_metrics().wire_rows.labels(kind="known")
    eng.step_records(q, now_s=5)
    k0 = known._value.get()
    eng.step_records(q.copy(), now_s=6)  # all descriptors now resident
    k1 = known._value.get()
    # Plain repeats rode the known side; the TSval + unstamped thirds
    # must NOT have (they escalate to full rows every quantum).
    expected_known_max = len(np.unique(q[2 * third :, : 16], axis=0))
    assert 0 < k1 - k0 <= expected_known_max, (k0, k1)


def test_dict_self_metrics_published():
    """Operators need the wire-savings evidence on /metrics: resident
    entries, generation, and new/known row counters."""
    from retina_tpu.metrics import get_metrics

    eng = SketchEngine(small_cfg())
    eng.compile()
    gen = TrafficGen(n_flows=80, n_pods=16, seed=12)
    q = gen.batch(400)
    eng.step_records(q, now_s=5)
    eng.step_records(q, now_s=6)  # second pass: all known
    m = get_metrics()
    assert m.flow_dict_entries._value.get() == len(eng._flow_dict) > 0
    new = m.wire_rows.labels(kind="new")._value.get()
    known = m.wire_rows.labels(kind="known")._value.get()
    assert new > 0 and known >= new  # pass 2 shipped known tuples


def test_dict_overflow_midstream_stays_lossless():
    """flow_dict_slots far below the flow count: generations cycle,
    every quantum re-uploads, but nothing is lost or double-counted."""
    cfg = small_cfg(flow_dict_slots=64)
    eng = SketchEngine(cfg)
    eng.compile()
    gen = TrafficGen(n_flows=300, n_pods=32, seed=8)
    total = 0
    for i in range(4):
        q = gen.batch(500)
        total += len(q)
        eng.step_records(q, now_s=20 + i)
    snap = eng.snapshot(max_age_s=0)
    assert int(np.asarray(snap["totals"])[0]) == total
    assert eng._flow_dict.generation >= 1  # it really cycled


def test_dict_path_failure_recovers():
    """After a device-side failure the donated table and host dict are
    rebuilt; the next dispatch works and counts stay exact."""
    eng = SketchEngine(small_cfg())
    eng.compile()
    gen = TrafficGen(n_flows=60, n_pods=16, seed=2)
    eng.step_records(gen.batch(300), now_s=5)
    # Simulate the async-failure recovery path.
    with eng._fd_lock:
        eng._flow_dict.clear()
    eng._desc_table = None
    eng.step_records(gen.batch(300), now_s=6)
    snap = eng.snapshot(max_age_s=0)
    assert int(np.asarray(snap["totals"])[0]) == 600
