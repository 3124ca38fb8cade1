"""Native C++ component tests: build, decoder bit-equivalence vs the numpy
reference, shm ring semantics (SPSC, drop-and-count, cross-process attach).

The reference's analog coverage is its bpf2go-generated stubs being
exercised through plugin tests; here the contract is exact equality with
the Python reference decoder on the same bytes."""

import multiprocessing
import os
import time

import numpy as np
import pytest

from retina_tpu.events.schema import NUM_FIELDS, PROTO_TCP, PROTO_UDP
from retina_tpu.sources.pcapdecode import (
    _decode_pcap_numpy,
    decode_pcap_bytes,
    synthesize_pcap,
)

native = pytest.importorskip("retina_tpu.native")

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native toolchain unavailable"
)


def _mixed_pcap(n=500, ns=True):
    pkts = []
    for i in range(n):
        p = dict(
            src_ip=0x0A000000 + i % 40, dst_ip=0x0A000100 + i % 11,
            sport=1024 + i, dport=[80, 443, 53, 8080][i % 4],
            proto=PROTO_TCP if i % 3 else PROTO_UDP,
            ts_ns=1_700_000_000_000_000_000 + i * 12345,
            tcp_flags=[0x10, 0x02, 0x11, 0x04][i % 4],
        )
        if i % 5 == 0:
            p["tsval"], p["tsecr"] = 1000 + i, 500 + i
        if i % 7 == 0:
            p.update(proto=PROTO_UDP, dport=53,
                     dns_qname=f"svc-{i % 13}.cluster.local",
                     dns_qtype=[1, 28, 5][i % 3],
                     dns_response=bool(i % 2), dns_rcode=i % 4)
        pkts.append(p)
    return synthesize_pcap(pkts, ns=ns)


@pytest.mark.parametrize("ns", [True, False])
def test_decoder_bit_equivalence(ns):
    data = _mixed_pcap(500, ns=ns)
    ref = _decode_pcap_numpy(data)
    records, total = native.decode_pcap_native(data)
    assert total == ref.n_packets_total
    assert len(records) == ref.n_decoded
    np.testing.assert_array_equal(records, ref.records)


def test_decode_pcap_bytes_uses_native_with_names():
    data = _mixed_pcap(100)
    res = decode_pcap_bytes(data, prefer_native=True)
    ref = _decode_pcap_numpy(data)
    np.testing.assert_array_equal(res.records, ref.records)
    assert res.dns_names == ref.dns_names
    assert res.dns_names  # non-empty table


def test_native_rejects_garbage():
    with pytest.raises(ValueError):
        native.decode_pcap_native(b"\x00" * 128)


# ------------------------------------------------------------------- ring
def test_ring_push_pop_and_drop_accounting():
    r = native.NativeRing(capacity=8)
    rec = np.arange(5 * NUM_FIELDS, dtype=np.uint32).reshape(5, NUM_FIELDS)
    assert r.push(rec) == 5
    assert len(r) == 5
    # overflow: only 3 free slots
    assert r.push(rec) == 3
    assert r.dropped == 2
    out = r.pop(100)
    assert len(out) == 8
    np.testing.assert_array_equal(out[:5], rec)
    np.testing.assert_array_equal(out[5:], rec[:3])
    assert len(r) == 0
    r.close()


def test_ring_wraparound():
    r = native.NativeRing(capacity=4)
    for i in range(10):
        rec = np.full((3, NUM_FIELDS), i, np.uint32)
        assert r.push(rec) == 3
        out = r.pop(10)
        np.testing.assert_array_equal(out, rec)
    r.close()


def test_ring_bad_capacity():
    with pytest.raises(ValueError):
        native.NativeRing(capacity=100)  # not a power of two


def _producer(path: str, n_blocks: int) -> None:
    from retina_tpu.native import NativeRing

    ring = NativeRing(capacity=1 << 12, path=path, create=False)
    for i in range(n_blocks):
        rec = np.full((64, NUM_FIELDS), i, np.uint32)
        while ring.push(rec) < 64:
            pass  # retry in the test producer (the agent never would)
    ring.close()


def test_ring_cross_process(tmp_path):
    path = str(tmp_path / "ring.shm")
    ring = native.NativeRing(capacity=1 << 12, path=path, create=True)
    p = multiprocessing.Process(target=_producer, args=(path, 50))
    p.start()
    got = 0
    import time

    deadline = time.monotonic() + 15
    while got < 50 * 64 and time.monotonic() < deadline:
        out = ring.pop(1024)
        got += len(out)
        if not len(out):
            time.sleep(0.002)
    p.join(5)
    assert got == 50 * 64
    assert ring.dropped == 0
    ring.close()
    os.unlink(path)


def _can_af_packet() -> bool:
    import socket as s

    if os.geteuid() != 0 or not hasattr(s, "AF_PACKET"):
        return False
    try:
        sock = s.socket(s.AF_PACKET, s.SOCK_RAW, s.htons(3))
        sock.close()
        return True
    except OSError:
        return False


@pytest.mark.skipif(not _can_af_packet(),
                    reason="needs root + AF_PACKET (linux)")
def test_afpacket_ring_captures_loopback():
    """TPACKET_V3 ring (afpacket.cpp): real UDP over loopback arrives as
    decoded 16-lane records (both directions), monotonic drop counter,
    records match the schema the engine consumes."""
    import socket as s

    from retina_tpu.events.schema import EV_FORWARD, F, PROTO_UDP
    from retina_tpu.native import AfPacketRing

    ring = AfPacketRing(iface="lo")
    try:
        tx = s.socket(s.AF_INET, s.SOCK_DGRAM)
        rx = s.socket(s.AF_INET, s.SOCK_DGRAM)
        rx.bind(("127.0.0.1", 0))
        port = rx.getsockname()[1]
        tx.connect(("127.0.0.1", port))
        for _ in range(500):
            tx.send(b"ring-test-payload")
        got = []
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and sum(map(len, got)) < 1000:
            rec, _seen, _dns = ring.poll(100)
            if len(rec):
                got.append(rec)
        rec = np.concatenate(got) if got else np.empty((0, 16), np.uint32)
        ours = rec[
            (rec[:, F.PORTS] & 0xFFFF) == port
        ]
        assert len(ours) >= 500  # tx direction at least
        assert (ours[:, F.SRC_IP] == 0x7F000001).all()
        assert ((ours[:, F.META] >> 24) == PROTO_UDP).all()
        assert (ours[:, F.EVENT_TYPE] == EV_FORWARD).all()
        assert (ours[:, F.BYTES] > 0).all()
        assert ring.drops() >= 0
    finally:
        ring.close()


@pytest.mark.skipif(not _can_af_packet(),
                    reason="needs root + AF_PACKET (linux)")
def test_afpacket_ring_resume_does_not_duplicate():
    """When the poll buffer is smaller than a burst, records continue on
    the next poll without duplication (mid-block resume)."""
    import socket as s

    from retina_tpu.events.schema import F
    from retina_tpu.native import AfPacketRing

    ring = AfPacketRing(iface="lo")
    ring.POLL_RECORDS = 64  # force mid-block resume
    ring._buf = np.empty((64, 16), np.uint32)
    try:
        tx = s.socket(s.AF_INET, s.SOCK_DGRAM)
        rx = s.socket(s.AF_INET, s.SOCK_DGRAM)
        rx.bind(("127.0.0.1", 0))
        port = rx.getsockname()[1]
        tx.connect(("127.0.0.1", port))
        n = 400
        for i in range(n):
            tx.send(b"seq-%06d" % i)
        time.sleep(0.3)
        recs = []
        for _ in range(40):
            rec, _seen, _dns = ring.poll(50)
            if len(rec) == 0:
                break
            recs.append(rec)
        rec = np.concatenate(recs)
        ours = rec[(rec[:, F.PORTS] & 0xFFFF) == port]
        # tx+rx over lo: exactly 2n frames, no duplicates from resume.
        assert len(ours) == 2 * n, len(ours)
    finally:
        ring.close()


def test_afpacket_ring_unavailable_without_privilege():
    from retina_tpu.native import AfPacketRing

    with pytest.raises(RuntimeError):
        AfPacketRing(iface="definitely-not-a-real-iface-9x")


@pytest.mark.skipif(not _can_af_packet(),
                    reason="needs root + AF_PACKET (linux)")
def test_afpacket_ring_dns_sidecar_names():
    """The ring's DNS sidecar carries raw frames of DNS packets so the
    host string pass resolves qnames — the fast path must not lose the
    DNS-name feature the socket loop has."""
    import socket as s

    from retina_tpu.events.schema import EV_DNS_REQ, F
    from retina_tpu.native import AfPacketRing
    from retina_tpu.sources.pcapdecode import (
        dns_names_from_frames,
        dns_qname_hash,
    )

    ring = AfPacketRing(iface="lo")
    try:
        q = (b"\x12\x34\x01\x00\x00\x01\x00\x00\x00\x00\x00\x00"
             b"\x07example\x03com\x00\x00\x01\x00\x01")
        tx = s.socket(s.AF_INET, s.SOCK_DGRAM)
        for _ in range(5):
            try:
                tx.sendto(q, ("127.0.0.1", 53))
            except OSError:
                pass  # ICMP port-unreachable from a previous send
            time.sleep(0.02)
        time.sleep(0.2)
        recs, names = [], {}
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not names:
            rec, _seen, dns = ring.poll(100)
            if len(rec):
                recs.append(rec)
            names.update(dns_names_from_frames(dns))
        rec = np.concatenate(recs)
        dnsr = rec[rec[:, F.EVENT_TYPE] == EV_DNS_REQ]
        h = dns_qname_hash(b"example.com")
        assert len(dnsr) >= 1
        assert names.get(h) == "example.com"
        assert (dnsr[:, F.DNS_QHASH] == np.uint32(h)).any()
    finally:
        ring.close()


def test_pack_native_matches_numpy_reference():
    """pack.cpp must be bit-identical to the numpy pack_records math on
    random batches, zero timestamps, saturating narrow lanes, and the
    ts < base unsigned wrap."""
    from retina_tpu.events.schema import F
    from retina_tpu.native import pack_native
    from retina_tpu.parallel import wire

    rng = np.random.default_rng(7)
    rec = rng.integers(
        0, 2 ** 32, size=(4096, NUM_FIELDS), dtype=np.uint32
    )
    rec[:128, F.TS_LO] = 0
    rec[:128, F.TS_HI] = 0  # unstamped rows keep TS_REL 0
    rec[128:192, F.VERDICT] = 9  # past every saturation bound
    rec[128:192, F.DROP_REASON] = 400
    rec[128:192, F.EVENT_TYPE] = 77
    rec[128:192, F.IFINDEX] = 1 << 20
    got = pack_native(rec)
    if got is None:
        pytest.skip("native library unavailable")
    out_nat, base_nat = got
    # The numpy path is reached via a 3-D view (native only takes 2-D).
    out_ref, lo, hi = wire.pack_records(rec[None])
    assert base_nat == (int(hi) << 32) | int(lo)
    np.testing.assert_array_equal(out_nat, out_ref[0])

    # Explicit base larger than some timestamps: u64 wrap saturates.
    base = int(wire.batch_ts_base(rec)) + (1 << 40)
    out_nat2, _ = pack_native(rec, base)
    out_ref2, _, _ = wire.pack_records(rec[None], base=np.uint64(base))
    np.testing.assert_array_equal(out_nat2, out_ref2[0])

    # Empty batch.
    out_e, base_e = pack_native(rec[:0])
    assert out_e.shape == (0, 12) and base_e == 0


def test_combine_blocks_bit_identical_to_concat():
    """rt_combine_multi consumes the flush's block list directly (no
    concat copy); its output must be BIT-identical — same rows, same
    first-appearance order — to combining the concatenation."""
    from retina_tpu.events.synthetic import TrafficGen
    from retina_tpu.parallel.combine import combine_blocks, combine_records

    gen = TrafficGen(n_flows=500, n_pods=32, seed=21)
    # Ragged block sizes, including empty and single-row blocks.
    blocks = [
        gen.batch(max(n, 1))[:n] for n in (512, 1, 730, 0, 256, 8192, 3)
    ]
    ref = combine_records(np.concatenate(blocks))
    out = combine_blocks(blocks)
    np.testing.assert_array_equal(ref, out)
    # Single-block and all-empty edge cases.
    np.testing.assert_array_equal(
        combine_blocks([blocks[0]]), combine_records(blocks[0])
    )
    empty = gen.batch(1)[:0]
    assert len(combine_blocks([empty, empty.copy()])) == 0


def test_combine_hint_grow_path_identical():
    """rt_combine_hint must return identical groups for any hint —
    including one that undershoots so far the table doubles repeatedly
    mid-pass (combine.cpp grow-and-rehash)."""
    import ctypes

    from retina_tpu.native import get_lib

    lib = get_lib()
    if lib is None:
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(11)
    n = 40_000
    rec = rng.integers(0, 2 ** 32, size=(n, NUM_FIELDS), dtype=np.uint32)
    rec[:, 7] = 1  # PACKETS
    # Half the rows repeat earlier descriptors so accumulation happens.
    rec[n // 2:] = rec[: n // 2]
    rows = np.ascontiguousarray(rec)
    p = rows.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))
    outs = []
    for hint in (0, 1, 1024, 1 << 20):
        out = np.empty_like(rows)
        g = lib.rt_combine_hint(
            p, n, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            hint,
        )
        assert g == n // 2, (hint, g)
        # Row order is first-appearance for every hint -> bit-identical.
        outs.append(out[:g].copy())
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)
    assert (outs[0][:, 7] == 2).all()  # every group accumulated 2 packets


def test_loaded_abi_version_matches_headers():
    """The loaded libretina_native.so must export exactly the ABI the
    Python loader was written against — a stale .so (rebuilt headers,
    old binary) must be a loud failure here, not a silent fallback in
    production. The loader itself force-rebuilds on mismatch, so this
    asserts the END state: whatever got loaded agrees."""
    from retina_tpu.native import (
        NATIVE_ABI_VERSION,
        get_lib,
        native_abi_version,
    )

    if get_lib() is None:
        pytest.skip("native library unavailable")
    assert native_abi_version() == NATIVE_ABI_VERSION
