"""Multi-chip sharding tests on the 8-device virtual CPU mesh.

The strongest property available: because every sketch update is built
from commutative scatter-add / scatter-max with device-independent hash
functions, the collective-merged sharded snapshot must EXACTLY equal the
single-device aggregate over the same events — psum of per-shard CMS
tables == one-device CMS table, pmax of HLL banks == one-device bank.
(The reference's analogous invariant: Prometheus scrape-side sums over
per-node counters equal a single hypothetical global counter.)
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from retina_tpu.events.schema import F, NUM_FIELDS
from retina_tpu.events.synthetic import TrafficGen
from retina_tpu.models.identity import IdentityMap
from retina_tpu.models.pipeline import PipelineConfig, TelemetryPipeline
from retina_tpu.parallel import (
    ShardedTelemetry,
    canonical_conn_hash,
    make_mesh,
    partition_events,
    topk_from_snapshot,
)

CFG = PipelineConfig(
    n_pods=1 << 9,
    cms_width=1 << 12,
    topk_slots=1 << 8,
    hll_precision=10,
    hll_pod_precision=6,
    entropy_buckets=1 << 10,
    conntrack_slots=1 << 12,
    latency_slots=1 << 8,
)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(jax.devices())


@pytest.fixture(scope="module")
def ident():
    # pod i at 10.0.0.0+i -> index i, within the config's pod space.
    return IdentityMap.build_host(
        {0x0A000000 + i: i for i in range(1, 256)}, n_slots=1 << 12
    )


def _events(n=4096, seed=3):
    gen = TrafficGen(n_flows=2000, n_pods=200, seed=seed)
    return gen.batch(n)


class TestPartition:
    def test_direction_independent(self):
        rec = _events(512)
        flipped = rec.copy()
        flipped[:, F.SRC_IP], flipped[:, F.DST_IP] = (
            rec[:, F.DST_IP].copy(),
            rec[:, F.SRC_IP].copy(),
        )
        ports = rec[:, F.PORTS]
        flipped[:, F.PORTS] = (
            (ports & np.uint32(0xFFFF)) << np.uint32(16)
        ) | (ports >> np.uint32(16))
        assert np.array_equal(
            canonical_conn_hash(rec), canonical_conn_hash(flipped)
        )

    def test_partition_preserves_and_counts_losses(self):
        rec = _events(4096)
        # Zipf traffic + connection-consistent hashing is skewed by design
        # (the hot flow's packets all share a shard); full-batch capacity
        # guarantees losslessness.
        sb = partition_events(rec, 8, capacity=4096)
        assert int(sb.n_valid.sum()) + sb.lost == 4096
        assert sb.lost == 0
        # Every placed row is a real row: multiset of row hashes matches.
        placed = np.concatenate(
            [sb.records[d, : sb.n_valid[d]] for d in range(8)]
        )
        assert sorted(map(tuple, placed)) == sorted(map(tuple, rec))

    def test_overflow_drops_never_blocks(self):
        rec = _events(4096)
        sb = partition_events(rec, 2, capacity=128)
        assert sb.lost == 4096 - int(sb.n_valid.sum())
        assert sb.lost > 0

    @pytest.mark.parametrize("capacity", [4096, 600])
    def test_four_devices_keep_a_connection_whole_and_conserve(
            self, capacity):
        """The v5e-4 host's partition (D = 4): both directions of a
        connection land on one device, the packets kept plus the
        packets counted lost are the input's, and the wire's minor
        dimension is the bucket of the fullest device."""
        from retina_tpu.parallel.partition import _bucket_for

        fwd = _events(2048)
        rev = fwd.copy()
        rev[:, F.SRC_IP], rev[:, F.DST_IP] = (
            fwd[:, F.DST_IP].copy(), fwd[:, F.SRC_IP].copy())
        ports = fwd[:, F.PORTS]
        rev[:, F.PORTS] = ((ports & np.uint32(0xFFFF)) << np.uint32(16)) \
            | (ports >> np.uint32(16))
        rec = np.concatenate([fwd, rev])
        rec[:, F.PACKETS] = np.arange(len(rec), dtype=np.uint32) % 7 + 1
        sb = partition_events(rec, 4, capacity, min_bucket=64)

        def conn(rows):
            """The connections of ``rows``, whichever way they point."""
            ends = set()
            for r in rows.tolist():
                a = (r[F.SRC_IP], r[F.PORTS] >> 16)
                b = (r[F.DST_IP], r[F.PORTS] & 0xFFFF)
                ends.add((min(a, b), max(a, b), r[F.META] >> 24))
            return ends

        homes = [conn(sb.records[d, :sb.n_valid[d]]) for d in range(4)]
        for a in range(4):
            assert sb.n_valid[a] > 0
            for b in range(a + 1, 4):
                assert not homes[a] & homes[b]
        kept = sum(int(sb.records[d, :sb.n_valid[d], F.PACKETS].sum())
                   for d in range(4))
        assert kept == sb.events
        assert kept + sb.lost == int(rec[:, F.PACKETS].sum())
        dev = canonical_conn_hash(rec) % np.uint32(4)
        fullest = int(np.bincount(dev, minlength=4).max())
        assert (sb.lost > 0) == (fullest > capacity)
        assert int(sb.n_valid.max()) == min(fullest, capacity)
        assert sb.records.shape == (
            4, _bucket_for(min(fullest, capacity), capacity, 64),
            NUM_FIELDS)
        assert not sb.records[0, sb.n_valid[0]:].any()  # padding is zero


class TestShardedMatchesSingle:
    @pytest.fixture(scope="class")
    def run(self, mesh, ident):
        rec = _events(8192)
        now = np.uint32(1000)

        single = TelemetryPipeline(CFG)
        s_state = single.init_state()
        step = jax.jit(single.step)
        s_state, _ = step(
            s_state,
            jnp.asarray(rec),
            jnp.uint32(len(rec)),
            now,
            ident,
            jnp.uint32(0),
        )

        sharded = ShardedTelemetry(CFG, mesh)
        m_state = sharded.init_state()
        sb = partition_events(rec, sharded.n_devices, capacity=8192)
        assert sb.lost == 0
        m_state, summary = sharded.step(
            m_state, sb.records, sb.n_valid, now, ident
        )
        snap = sharded.snapshot(m_state, now)
        return s_state, m_state, snap, summary, rec

    def test_event_totals(self, run):
        s_state, _, snap, summary, rec = run
        assert int(summary["events"]) == len(rec)
        np.testing.assert_array_equal(
            np.asarray(snap["totals"])[:6], np.asarray(s_state.totals)[:6]
        )

    def test_dense_rectangles_exact(self, run):
        s_state, _, snap, _, _ = run
        for name in (
            "pod_forward",
            "pod_drop",
            "pod_tcpflags",
            "pod_dns",
            "pod_retrans",
            "node_counters",
        ):
            np.testing.assert_array_equal(
                np.asarray(snap[name]),
                np.asarray(getattr(s_state, name)),
                err_msg=name,
            )

    def test_cms_psum_equals_single_table(self, run):
        s_state, m_state, _, _, _ = run
        merged = np.asarray(m_state.flow_hh.cms.table).sum(axis=0)
        np.testing.assert_array_equal(
            merged, np.asarray(s_state.flow_hh.cms.table)
        )

    def test_hll_pmax_equals_single_bank(self, run):
        s_state, m_state, snap, _, _ = run
        merged = np.asarray(m_state.hll_flows.registers).max(axis=0)
        np.testing.assert_array_equal(
            merged, np.asarray(s_state.hll_flows.registers)
        )
        est_single = float(s_state.hll_flows.estimate()[0])
        assert np.isclose(float(np.asarray(snap["hll_flows"])[0]), est_single)

    def test_entropy_window_merge(self, mesh, ident):
        rec = _events(4096, seed=9)
        now = np.uint32(5)
        single = TelemetryPipeline(CFG)
        s_state = single.init_state()
        s_state, _ = jax.jit(single.step)(
            s_state, jnp.asarray(rec), jnp.uint32(len(rec)), now, ident, jnp.uint32(0)
        )
        _, s_win = single.end_window(s_state)

        sharded = ShardedTelemetry(CFG, mesh)
        m_state = sharded.init_state()
        sb = partition_events(rec, sharded.n_devices, capacity=4096)
        assert sb.lost == 0
        m_state, _ = sharded.step(m_state, sb.records, sb.n_valid, now, ident)
        m_state, m_win = sharded.end_window(m_state)
        np.testing.assert_allclose(
            np.asarray(m_win["entropy_bits"]),
            np.asarray(s_win["entropy_bits"]),
            rtol=1e-5,
        )

    def test_topk_union_finds_heavy_hitter(self, run, ident):
        _, _, snap, _, rec = run
        keys, counts = topk_from_snapshot(snap, "flow_hh", k=10)
        assert len(keys) > 0
        # The true hottest 5-tuple must appear among the gathered top-10.
        cols = np.stack(
            [rec[:, F.SRC_IP], rec[:, F.DST_IP], rec[:, F.PORTS],
             rec[:, F.META] >> np.uint32(24)], axis=1
        )
        uniq, cnt = np.unique(cols, axis=0, return_counts=True)
        hottest = uniq[np.argmax(cnt)]
        assert any(np.array_equal(hottest, k) for k in keys)

    def test_lost_accounting_lands_in_totals(self, mesh, ident):
        rec = _events(4096, seed=21)
        sharded = ShardedTelemetry(CFG, mesh)
        state = sharded.init_state()
        sb = partition_events(rec, sharded.n_devices, capacity=128)
        assert sb.lost > 0
        state, _ = sharded.step(
            state, sb.records, sb.n_valid, np.uint32(1), ident, lost=sb.lost
        )
        snap = sharded.snapshot(state, np.uint32(1))
        assert int(np.asarray(snap["totals"])[7]) == sb.lost

    def test_svc_topk_sums_partial_counts_across_devices(self, mesh, ident):
        # One pod pair talking over many connections: its packets spread
        # across devices, so per-device svc_hh tables hold partial counts
        # that the host-side merge must sum (not rank independently).
        n = 2048
        rec = np.zeros((n, NUM_FIELDS), np.uint32)
        rec[:, F.SRC_IP] = 0x0A000000 + 1
        rec[:, F.DST_IP] = 0x0A000000 + 2
        rec[:, F.PORTS] = (
            (np.arange(n, dtype=np.uint32) % 1000 + 1024) << np.uint32(16)
        ) | np.uint32(80)
        rec[:, F.META] = (np.uint32(6) << np.uint32(24)) | (
            np.uint32(1) << np.uint32(4)
        )
        rec[:, F.BYTES] = 100
        rec[:, F.PACKETS] = 1
        rec[:, F.VERDICT] = 1
        sharded = ShardedTelemetry(CFG, mesh)
        state = sharded.init_state()
        sb = partition_events(rec, sharded.n_devices, capacity=n)
        assert sb.lost == 0
        assert int((sb.n_valid > 0).sum()) > 1  # really spread over devices
        state, _ = sharded.step(state, sb.records, sb.n_valid, np.uint32(1), ident)
        snap = sharded.snapshot(state, np.uint32(1))
        keys, counts = topk_from_snapshot(snap, "svc_hh", k=4)
        assert list(keys[0]) == [1, 2]
        assert int(counts[0]) == n  # summed across devices, deduped

    def test_conntrack_reports_match_single(self, run):
        s_state, _, snap, _, _ = run
        # totals[6] = conntrack reports; partitioning is connection-
        # consistent so sharded total equals single-device total.
        assert int(np.asarray(snap["totals"])[6]) == int(
            np.asarray(s_state.totals)[6]
        )


def test_partition_single_device_fast_path():
    """D=1 takes the no-hash fast path: a full contiguous batch is a
    zero-copy view (documented aliasing contract); partial batches pad
    with a fresh array; overflow still drops-and-counts."""
    rng = np.random.default_rng(11)
    cap = 256
    full = rng.integers(0, 2**31, size=(cap, NUM_FIELDS),
                        dtype=np.int64).astype(np.uint32)
    sb = partition_events(full, 1, cap)
    assert sb.records.shape == (1, cap, NUM_FIELDS)
    assert int(sb.n_valid[0]) == cap and sb.lost == 0
    np.testing.assert_array_equal(sb.records[0], full)
    # Zero-copy: the view shares the caller's buffer.
    assert np.shares_memory(sb.records, full)

    partial = full[:100]
    sb = partition_events(partial, 1, cap)
    assert int(sb.n_valid[0]) == 100 and sb.lost == 0
    np.testing.assert_array_equal(sb.records[0, :100], partial)
    assert not np.shares_memory(sb.records, full)  # padded copy

    over = rng.integers(0, 2**31, size=(cap + 40, NUM_FIELDS),
                        dtype=np.int64).astype(np.uint32)
    # Losses are counted in EVENTS (packet weights), not rows: a combined
    # row stands for F.PACKETS underlying events.
    over[:, F.PACKETS] = 1
    over[-1, F.PACKETS] = 5
    sb = partition_events(over, 1, cap)
    assert int(sb.n_valid[0]) == cap and sb.lost == 39 + 5
