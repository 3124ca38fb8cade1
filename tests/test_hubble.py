"""Hubble control-plane tests: record→flow decode + enrichment, monitor
agent fan-out, observer ring follow/loss semantics, the gRPC relay
end-to-end (stream flows over a real localhost channel) — covering the
reference's pkg/hubble + pkg/monitoragent surface."""

import threading
import time

import numpy as np
import pytest

from retina_tpu.common import RetinaEndpoint
from retina_tpu.controllers.cache import Cache
from retina_tpu.events.schema import (
    DIR_INGRESS,
    EV_DNS_REQ,
    EV_DROP,
    EV_FORWARD,
    F,
    NUM_FIELDS,
    OP_FROM_NETWORK,
    PROTO_TCP,
    TCP_ACK,
    TCP_SYN,
    VERDICT_DROPPED,
    VERDICT_FORWARDED,
    ip_to_u32,
)
from retina_tpu.hubble.flow import FlowFilter, record_to_flow
from retina_tpu.hubble.monitoragent import MonitorAgent
from retina_tpu.hubble.observer import FlowObserver
from retina_tpu.hubble.server import HubbleClient, HubbleServer


def mk_record(src="10.0.0.1", dst="10.0.0.2", verdict=VERDICT_FORWARDED,
              ev=EV_FORWARD, flags=TCP_ACK, sport=40000, dport=80):
    rec = np.zeros(NUM_FIELDS, np.uint32)
    rec[F.TS_LO] = 12345
    rec[F.SRC_IP] = ip_to_u32(src)
    rec[F.DST_IP] = ip_to_u32(dst)
    rec[F.PORTS] = (sport << 16) | dport
    rec[F.META] = (
        (PROTO_TCP << 24) | (flags << 16) | (OP_FROM_NETWORK << 8)
        | (DIR_INGRESS << 4)
    )
    rec[F.BYTES] = 100
    rec[F.PACKETS] = 1
    rec[F.VERDICT] = verdict
    rec[F.EVENT_TYPE] = ev
    return rec


def cache_with_pods():
    c = Cache()
    c.update_endpoint(RetinaEndpoint(
        name="web-0", namespace="default", ips=("10.0.0.1",),
        labels=(("app", "web"),), owner_refs=(("Deployment", "web"),),
    ))
    c.update_endpoint(RetinaEndpoint(
        name="db-0", namespace="prod", ips=("10.0.0.2",),
    ))
    return c


# ------------------------------------------------------------------ flow
def test_record_to_flow_decodes_and_enriches():
    f = record_to_flow(mk_record(flags=TCP_SYN | TCP_ACK),
                       cache=cache_with_pods())
    assert f["ip"] == {"source": "10.0.0.1", "destination": "10.0.0.2"}
    assert f["l4"]["protocol"] == "TCP"
    assert set(f["l4"]["flags"]) == {"SYN", "ACK"}
    assert f["verdict"] == "FORWARDED"
    assert f["traffic_direction"] == "INGRESS"
    assert f["source"]["pod_name"] == "web-0"
    assert f["source"]["labels"] == ["app=web"]
    assert f["destination"]["namespace"] == "prod"


def test_record_to_flow_dns_and_drop():
    rec = mk_record(ev=EV_DNS_REQ)
    rec[F.DNS] = (28 << 16) | (0 << 8) | 1
    rec[F.DNS_QHASH] = 0xAB
    f = record_to_flow(rec, dns_resolver=lambda h: f"name-{h:#x}")
    assert f["l7_dns"] == {"qtype": 28, "rcode": 0, "query": "name-0xab"}

    fd = record_to_flow(mk_record(verdict=VERDICT_DROPPED))
    assert fd["verdict"] == "DROPPED"


def test_flow_filter():
    f = record_to_flow(mk_record(), cache=cache_with_pods())
    assert FlowFilter(pod="web-0").matches(f)
    assert FlowFilter(namespace="prod").matches(f)
    assert not FlowFilter(pod="other").matches(f)
    assert FlowFilter(verdict="FORWARDED", protocol="TCP", port=80).matches(f)
    assert not FlowFilter(port=443).matches(f)
    assert FlowFilter(ip="10.0.0.1").matches(f)   # source endpoint
    assert FlowFilter(ip="10.0.0.2").matches(f)   # destination endpoint
    assert not FlowFilter(ip="10.9.9.9").matches(f)
    assert FlowFilter(event_type="flow").matches(f)
    assert not FlowFilter(event_type="drop").matches(f)
    fd = record_to_flow(mk_record(verdict=VERDICT_DROPPED, ev=EV_DROP))
    assert FlowFilter(event_type="drop").matches(fd)
    # time bounds: mk_record stamps TS_LO=12345 -> time_ns 12345
    assert FlowFilter(since_ns=12345).matches(f)
    assert not FlowFilter(since_ns=12346).matches(f)
    assert FlowFilter(until_ns=12345).matches(f)
    assert not FlowFilter(until_ns=12344).matches(f)
    assert FlowFilter(since_ns=12000, until_ns=13000).matches(f)
    # round-trips through the relay's dict wire encoding
    assert FlowFilter.from_dict(FlowFilter(ip="10.0.0.1").to_dict()).matches(f)
    assert FlowFilter.from_dict(
        FlowFilter(event_type="flow").to_dict()
    ).matches(f)
    assert not FlowFilter.from_dict(
        FlowFilter(since_ns=12346).to_dict()
    ).matches(f)


# ---------------------------------------------------------- monitoragent
def test_monitoragent_fanout_from_channel():
    ma = MonitorAgent()
    got: list[int] = []
    done = threading.Event()

    def consumer(records):
        got.append(len(records))
        done.set()

    ma.register_consumer(consumer)
    stop = threading.Event()
    ma.start(stop)
    ma.channel.put(np.stack([mk_record()] * 3))
    assert done.wait(2.0)
    assert got == [3]
    stop.set()


# -------------------------------------------------------------- observer
def test_observer_buffered_and_follow():
    obs = FlowObserver(capacity=8)
    obs.consume(np.stack([mk_record(dport=1000 + i) for i in range(4)]))
    flows = list(obs.get_flows())
    assert [f["l4"]["destination_port"] for f in flows] == [
        1000, 1001, 1002, 1003,
    ]
    # last=2 returns only the most recent two
    assert len(list(obs.get_flows(last=2))) == 2

    # follow: a late flow reaches a waiting reader
    stop = threading.Event()
    seen = []

    def reader():
        for f in obs.get_flows(follow=True, stop=stop):
            seen.append(f)
            if len(seen) >= 5:
                return

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    time.sleep(0.1)
    obs.consume(np.stack([mk_record(dport=2000)]))
    t.join(3.0)
    stop.set()
    assert any(f["l4"]["destination_port"] == 2000 for f in seen)


def test_observer_overwrite_oldest():
    obs = FlowObserver(capacity=4)
    obs.consume(np.stack([mk_record(dport=i) for i in range(10)]))
    ports = [f["l4"]["destination_port"] for f in obs.get_flows()]
    assert ports == [6, 7, 8, 9]  # oldest overwritten, newest kept
    assert obs.flows_seen == 10


# ------------------------------------------------------------ gRPC relay
def test_hubble_grpc_end_to_end():
    obs = FlowObserver(capacity=64, cache=cache_with_pods())
    srv = HubbleServer(obs, addr="127.0.0.1:0",
                       peers=[{"name": "local", "address": "127.0.0.1"}])
    srv.start()
    try:
        client = HubbleClient(f"127.0.0.1:{srv.port}")
        obs.consume(np.stack([mk_record(dport=80), mk_record(dport=443)]))

        flows = list(client.get_flows(last=10, timeout=5))
        assert len(flows) == 2
        assert flows[0]["source"]["pod_name"] == "web-0"

        only443 = list(client.get_flows(filter=FlowFilter(port=443),
                                        timeout=5))
        assert len(only443) == 1

        status = client.server_status()
        assert status["seen_flows"] == 2 and status["max_flows"] == 64
        assert client.list_peers()[0]["name"] == "local"

        # follow over the wire: stream sees a flow produced after connect
        it = client.get_flows(follow=True, timeout=10)
        obs.consume(np.stack([mk_record(dport=9999)]))
        got = []
        for f in it:
            got.append(f)
            if any(x["l4"]["destination_port"] == 9999 for x in got):
                break
        assert any(x["l4"]["destination_port"] == 9999 for x in got)
        client.close()
    finally:
        srv.stop()


def test_hubble_unix_socket_observe(tmp_path):
    """The server additionally listens on a unix socket for local
    clients (the reference serves unix:///var/run/cilium/hubble.sock,
    SURVEY §3.5); the observe path must work end-to-end over it."""
    sock = str(tmp_path / "hubble.sock")
    obs = FlowObserver(capacity=64, cache=cache_with_pods())
    srv = HubbleServer(obs, addr="127.0.0.1:0", unix_socket=sock)
    srv.start()
    try:
        client = HubbleClient(f"unix:{sock}")
        obs.consume(np.stack([mk_record(dport=80)]))
        flows = list(client.get_flows(last=10, timeout=5))
        assert len(flows) == 1
        assert flows[0]["l4"]["destination_port"] == 80
        status = client.server_status()
        assert status["seen_flows"] == 1
        client.close()
    finally:
        srv.stop()


def test_observer_lazy_decode_memoizes():
    """The writer stores raw rows (hot path ~9M flows/s); the FIRST read
    decodes and memoizes into the ring, so N readers decode once."""
    import numpy as np

    from retina_tpu.events.schema import EventBuilder
    from retina_tpu.hubble.observer import FlowObserver

    b = EventBuilder(8)
    for i in range(8):
        b.add(src_ip=0x0A000000 + i, dst_ip=0x0A0000FF,
              src_port=1000 + i, dst_port=80, bytes_=100)
    rec = b._batch.valid_rows()
    obs = FlowObserver(capacity=16)
    obs.consume(rec)
    # Raw (block, row) slots in the ring before any read, nothing
    # decoded.
    assert obs.raw_slots() == 8 and not obs._memo
    flows, _ = obs.snapshot_flows()
    assert len(flows) == 8
    assert flows[0]["ip"]["source"] == "10.0.0.0"
    # Memoized: every slot now reads as its decoded dict.
    assert obs.raw_slots() == 0
    assert len(obs._memo) == 8
    # Second read returns identical objects (no re-decode).
    flows2, _ = obs.snapshot_flows()
    assert flows2[0] is flows[0]


def test_msgpack_follow_lost_markers():
    """The msgpack surface's analog of the protobuf LostEvent: a lapped
    follower requesting lost_markers receives a {"lost_events": n}
    marker dict (bypassing any filter) before newer flows resume."""
    import numpy as np

    obs = FlowObserver(capacity=1 << 6)  # 64-slot ring, easy to lap
    srv = HubbleServer(obs, addr="127.0.0.1:0")
    srv.start()
    try:
        client = HubbleClient(f"127.0.0.1:{srv.port}")
        stream = client.get_flows(follow=True, lost_markers=True,
                                  timeout=15)
        it = iter(stream)
        obs.consume(np.stack([mk_record(src="10.7.0.1")]))
        first = next(it)
        assert first["ip"]["source"] == "10.7.0.1"
        # Lap the 64-slot ring in ONE consume (single lock hold): the
        # floor is guaranteed past the reader's cursor with no chance
        # for the server thread to drain between writes.
        obs.consume(np.stack([mk_record(src="10.7.0.2")] * 256))
        marker = None
        for f in it:
            if "lost_events" in f and "ip" not in f:
                marker = f
                break
        assert marker is not None and marker["lost_events"] > 0
        client.close()
    finally:
        srv.stop()


def test_relay_accounts_peer_reported_loss():
    """Loss reported BY a peer (its ring lapped the relay's follower)
    must surface at the relay — hubble_lost_events_total with
    source=PEER_STREAM — instead of reading as a complete cluster
    view."""
    from retina_tpu.exporter import get_exporter
    from retina_tpu.hubble.relay import HubbleRelay

    obs = FlowObserver(capacity=1 << 3)  # 8-slot ring: trivially lapped
    srv = HubbleServer(obs, addr="127.0.0.1:0")
    srv.start()
    relay = None
    try:
        relay = HubbleRelay(
            peers=[{"name": "node-a",
                    "address": f"127.0.0.1:{srv.port}"}],
            addr="127.0.0.1:0", node_name="relay-test",
        )
        relay.start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and relay.peer_lost == 0:
            obs.consume(np.stack([mk_record()] * 64))  # laps every time
            time.sleep(0.2)
        assert relay.peer_lost > 0, "peer LostEvent never accounted"
        text = get_exporter().gather_hubble_text().decode()
        assert 'hubble_lost_events_total{source="PEER_STREAM"}' in text
    finally:
        if relay is not None:
            relay.stop()
        srv.stop()


def _block(n, start=0):
    import numpy as np

    return np.stack([
        mk_record(src=f"10.{(start + i) >> 16 & 255}."
                  f"{(start + i) >> 8 & 255}.{(start + i) & 255}")
        for i in range(n)])


@pytest.mark.parametrize("sizes", [(5,), (64,), (200,), (40, 40, 40),
                                   (64, 1), (3, 300, 2)])
def test_observer_ring_is_the_last_capacity_records(sizes):
    """A write costs per block: only the last ``capacity`` rows of a
    block can ever be read, and the ring after any run of blocks is
    ``record_to_flow`` of the last ``capacity`` records, oldest first."""
    import numpy as np

    from retina_tpu.hubble.flow import record_to_flow

    obs = FlowObserver(capacity=64)
    every = []
    for k, n in enumerate(sizes):
        b = _block(n, start=1000 * k)
        obs.consume(b)
        every.append(b)
    rows = np.concatenate(every)
    flows, end = obs.snapshot_flows()
    assert end == obs.flows_seen == len(rows)
    assert flows == [record_to_flow(r) for r in rows[-64:]]
    # No block outlives its last readable row, and none is held
    # beyond the rows that can be read.
    assert len(obs._segs) <= 1 + sum(n < 64 for n in sizes)
    assert all(first + len(rows) > end - 64 and len(rows) <= 64
               for first, rows in obs._segs)
    # The memo of decoded rows is pruned as they are lapped.
    obs.consume(_block(64, start=9000))
    assert not obs._memo and obs.raw_slots() == 64


def test_observer_mixes_raw_blocks_and_decoded_flows():
    """Relay peers write decoded flows into the same ring; a raw slot
    they overwrite names its block no longer, and a reader that was
    lapped is told how many it lost."""
    obs = FlowObserver(capacity=8)
    obs.consume(_block(6))
    obs.consume_flows([{"peer": i} for i in range(4)])
    flows, end = obs.snapshot_flows()
    assert end == 10 and len(flows) == 8
    assert [f.get("peer") for f in flows[-4:]] == [0, 1, 2, 3]
    assert flows[0]["ip"]["source"] == "10.0.0.2"
    obs.consume(_block(20, start=500))
    got = []
    for kind, payload in obs.follow_from(end):
        got.append((kind, payload))
        if len(got) == 9:
            break
    assert got[0] == ("lost", 12)
    assert [p["ip"]["source"] for _, p in got[1:]] == [
        f"10.0.{(500 + i) >> 8}.{(500 + i) & 255}" for i in range(12, 20)]
    assert len(obs._segs) == 1 and obs.lost_observed == 12


def test_observer_write_is_one_span_a_block():
    from retina_tpu.obs.recorder import get_recorder
    from retina_tpu.utils import metric_names as mn

    rec = get_recorder()
    n0 = len([s for s in rec.spans()
              if s["stage"] == mn.STAGE_HUBBLE_CONSUME])
    obs = FlowObserver(capacity=64)
    obs.consume(_block(100))
    obs.consume(np.zeros((0, 16), np.uint32))  # nothing to write: no span
    spans = [s for s in rec.spans()
             if s["stage"] == mn.STAGE_HUBBLE_CONSUME]
    assert len(spans) == n0 + 1
    assert spans[-1]["args"]["rows"] == 100
    assert mn.STAGE_HUBBLE_CONSUME in mn.STAGES
