"""Packed wire format: roundtrip fidelity host->device (parallel/wire.py)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from retina_tpu.events.schema import F, NUM_FIELDS
from retina_tpu.events.synthetic import TrafficGen
from retina_tpu.parallel.wire import (
    DENSE_BY_BITS,
    DENSE_PK_BITS,
    PACKED_FIELDS,
    batch_ts_base,
    dense_known_rows,
    dense_known_unpack_device,
    dense_known_unpack_numpy,
    dense_row_bits,
    dense_words,
    pack_records,
    unpack_records_device,
    unpack_records_numpy,
)


def test_roundtrip_exact_on_realistic_traffic():
    gen = TrafficGen(n_flows=5000, n_pods=64, seed=9)
    rec = gen.batch(4096)
    rec[:, F.IFINDEX] = np.arange(4096, dtype=np.uint32) % 100
    packed, lo, hi = pack_records(rec)
    assert packed.shape == (4096, PACKED_FIELDS)
    out = unpack_records_numpy(packed, lo, hi)
    np.testing.assert_array_equal(out, rec)


def test_device_and_numpy_unpack_agree():
    gen = TrafficGen(n_flows=500, n_pods=16, seed=2)
    rec = gen.batch(512)
    packed, lo, hi = pack_records(rec)
    a = unpack_records_numpy(packed, lo, hi)
    b = np.asarray(
        unpack_records_device(
            jnp.asarray(packed), jnp.uint32(lo), jnp.uint32(hi)
        )
    )
    np.testing.assert_array_equal(a, b)


def test_sharded_layout_roundtrip():
    gen = TrafficGen(n_flows=100, n_pods=8, seed=4)
    rec = gen.batch(256).reshape(2, 128, NUM_FIELDS)
    packed, lo, hi = pack_records(rec)
    assert packed.shape == (2, 128, PACKED_FIELDS)
    np.testing.assert_array_equal(
        unpack_records_numpy(packed, lo, hi), rec
    )


def test_ts_carry_across_u32_boundary():
    rec = np.zeros((2, NUM_FIELDS), np.uint32)
    # base just below a 2^32 ns boundary; second row crosses it.
    rec[0, F.TS_LO], rec[0, F.TS_HI] = 0xFFFFFF00, 5
    rec[1, F.TS_LO], rec[1, F.TS_HI] = 0x00000100, 6
    packed, lo, hi = pack_records(rec)
    out = unpack_records_numpy(packed, lo, hi)
    np.testing.assert_array_equal(out[:, F.TS_LO], rec[:, F.TS_LO])
    np.testing.assert_array_equal(out[:, F.TS_HI], rec[:, F.TS_HI])


def test_saturation_of_narrow_lanes():
    rec = np.zeros((1, NUM_FIELDS), np.uint32)
    rec[0, F.VERDICT] = 1000
    rec[0, F.DROP_REASON] = 1 << 20
    rec[0, F.EVENT_TYPE] = 99
    rec[0, F.IFINDEX] = 1 << 30
    packed, lo, hi = pack_records(rec)
    out = unpack_records_numpy(packed, lo, hi)
    assert out[0, F.VERDICT] == 7
    assert out[0, F.DROP_REASON] == 255
    assert out[0, F.EVENT_TYPE] == 15
    assert out[0, F.IFINDEX] == 0x1FFFF


def test_zero_timestamp_rows_keep_rel_zero():
    """A source that never stamps must round-trip to ts 0 exactly —
    NOT inherit the batch base timestamp, which would feed phantom
    values into the apiserver RTT latency matcher."""
    rec = np.zeros((3, NUM_FIELDS), np.uint32)
    rec[0, F.TS_LO], rec[0, F.TS_HI] = 100, 1  # the only stamped row
    rec[1, F.SRC_IP] = 7  # unstamped real row
    packed, lo, hi = pack_records(rec)
    assert packed[1, 0] == 0 and packed[2, 0] == 0
    out = unpack_records_numpy(packed, lo, hi)
    np.testing.assert_array_equal(out, rec)  # exact, incl. unstamped
    dev = np.asarray(
        unpack_records_device(
            jnp.asarray(packed), jnp.uint32(lo), jnp.uint32(hi)
        )
    )
    np.testing.assert_array_equal(dev, rec)


def test_spread_beyond_u32_saturates():
    rec = np.zeros((2, NUM_FIELDS), np.uint32)
    rec[0, F.TS_LO], rec[0, F.TS_HI] = 1, 0
    rec[1, F.TS_LO], rec[1, F.TS_HI] = 0, 2  # ~8.6 s later
    packed, lo, hi = pack_records(rec)
    out = unpack_records_numpy(packed, lo, hi)
    np.testing.assert_array_equal(out[0], rec[0])
    # saturated: clamped to base + (2^32 - 2), not wrapped past it (the
    # +1 TS_REL bias that reserves 0 for "unstamped" costs one count of
    # representable spread)
    got = (int(out[1, F.TS_HI]) << 32) | int(out[1, F.TS_LO])
    assert got == ((0 << 32) | 1) + 0xFFFFFFFE


# -- v4 dense known-row bitstream -------------------------------------
#
# Three implementations of one bit layout (numpy pack, native pack,
# device unpack) must agree bit-for-bit; the property test sweeps
# randomized field domains and dictionary widths, the golden frame
# below makes any layout change a loud, reviewed failure.


def _dense_batch(rng, n, id_bits):
    """Random rows whose PACKETS/BYTES fit the dense lanes (the
    escalation mask's invariant), ids spanning the full dictionary."""
    rows = rng.integers(
        0, 2**32, size=(n, NUM_FIELDS), dtype=np.uint32
    )
    rows[:, F.PACKETS] = rng.integers(
        0, 1 << DENSE_PK_BITS, n, dtype=np.uint32
    )
    rows[:, F.BYTES] = rng.integers(
        0, 1 << DENSE_BY_BITS, n, dtype=np.uint32
    )
    ids = rng.integers(0, 1 << id_bits, n, dtype=np.uint32)
    return rows, ids


def test_dense_pack_unpack_property():
    """Property: numpy pack -> {numpy, device} unpack round-trips
    (ids, packets, bytes) exactly, for every dictionary width in use,
    ragged row counts (word-boundary straddles included), and lane
    extremes."""
    rng = np.random.default_rng(77)
    for id_bits in (12, 18, 21, 32):
        assert dense_row_bits(id_bits) <= 64
        for n in (0, 1, 2, 31, 32, 33, 257, 1000):
            rows, ids = _dense_batch(rng, n, id_bits)
            if n >= 2:  # pin lane extremes into every sized batch
                rows[0, F.PACKETS] = (1 << DENSE_PK_BITS) - 1
                rows[0, F.BYTES] = (1 << DENSE_BY_BITS) - 1
                ids[0] = (1 << id_bits) - 1 if id_bits < 32 else 0xFFFFFFFF
                rows[1, F.PACKETS] = 0
                rows[1, F.BYTES] = 0
                ids[1] = 0
            out = np.zeros(dense_words(n, id_bits), np.uint32)
            dense_known_rows(rows, ids, id_bits, out)
            gi, gp, gb = dense_known_unpack_numpy(out, n, id_bits)
            np.testing.assert_array_equal(gi, ids)
            np.testing.assert_array_equal(gp, rows[:, F.PACKETS])
            np.testing.assert_array_equal(gb, rows[:, F.BYTES])
            di, dp, db = dense_known_unpack_device(
                jnp.asarray(out), n, id_bits
            )
            np.testing.assert_array_equal(np.asarray(di), ids)
            np.testing.assert_array_equal(
                np.asarray(dp), rows[:, F.PACKETS]
            )
            np.testing.assert_array_equal(
                np.asarray(db), rows[:, F.BYTES]
            )


def test_dense_native_pack_bit_identical_to_numpy():
    """Native rt_flowwire_dense's known stream must be WORD-identical
    to the numpy pack (not merely unpack-equal): the device reader
    consumes raw words, so any spare-bit disagreement is format
    drift."""
    from retina_tpu.native import flowwire_dense_native

    rng = np.random.default_rng(31)
    for id_bits in (12, 18, 21):
        n = 777
        rows, ids = _dense_batch(rng, n, id_bits)
        rows[:, F.TS_LO] = rng.integers(1, 2**31, n)
        rows[:, F.TS_HI] = 0
        # The shared row body's zero timestamps against a nonzero
        # base (the random rows already pass every saturation bound).
        rows[50:80, F.TS_LO] = 0  # unstamped
        base = batch_ts_base(rows)
        assert int(base) > 0
        sel = (rng.random(n) < 0.3).astype(np.uint8)
        rows = np.ascontiguousarray(rows)
        n_sel = int(sel.sum())
        new_nat = np.zeros((n, 13), np.uint32)
        known_nat = np.zeros(
            dense_words(n - n_sel, id_bits), np.uint32
        )
        got = flowwire_dense_native(
            rows, ids, sel, int(base), id_bits, DENSE_PK_BITS,
            DENSE_BY_BITS, new_nat, known_nat,
        )
        if got is None:
            import pytest

            pytest.skip("native library unavailable")
        assert got == n_sel
        keep = sel == 0
        known_ref = np.zeros_like(known_nat)
        dense_known_rows(rows[keep], ids[keep], id_bits, known_ref)
        np.testing.assert_array_equal(known_nat, known_ref)
        # New side: id lane + the 12 packed lanes.
        packed12, _, _ = pack_records(rows[sel == 1], base=base)
        np.testing.assert_array_equal(new_nat[:n_sel, 0], ids[sel == 1])
        np.testing.assert_array_equal(new_nat[:n_sel, 1:], packed12)


def test_dense_golden_frame():
    """Golden frame: the committed word values ARE the v4 format. A
    failure here means the wire layout changed — bump the format
    deliberately (native ABI + this fixture together), never silently."""
    id_bits = 18
    ids = np.array([1, 0x3FFFF, 0x2A5A5, 7, 0x1F0F0], np.uint32)
    pk = np.array([1, 1023, 512, 3, 77], np.uint32)
    by = np.array(
        [40, (1 << 22) - 1, 0x200000, 1514, 0x12345], np.uint32
    )
    rows = np.zeros((5, NUM_FIELDS), np.uint32)
    rows[:, F.PACKETS] = pk
    rows[:, F.BYTES] = by
    out = np.zeros(dense_words(5, id_bits), np.uint32)
    dense_known_rows(rows, ids, id_bits, out)
    golden = np.array(
        [0x80040001, 0xFFFC0002, 0xFFFFFFFF, 0x802A5A5F, 0x01E00000,
         0x17A80300, 0x35F0F000, 0x00123451, 0x00000000],
        np.uint32,
    )
    np.testing.assert_array_equal(out, golden)
    gi, gp, gb = dense_known_unpack_numpy(golden, 5, id_bits)
    np.testing.assert_array_equal(gi, ids)
    np.testing.assert_array_equal(gp, pk)
    np.testing.assert_array_equal(gb, by)
