"""Snapshot + publish: of the ``pod_publish`` seconds in the window, the
share spent waiting for the device proxy and the device: the
``snapshot_dispatch`` spans (wait for and run of the dispatch on the
proxy) and the readiness wait of the ``snapshot_fetch`` spans, of the
snapshots those publishes took."""

import host_spans

UNIT = "%"


def read(run):
    publishes = host_spans.window_spans(run, "pod_publish")
    total = sum(s["t1"] - s["t0"] for s in publishes)
    ids = {s.get("id") for s in publishes} - {None, 0}
    if not total or not ids:
        return None
    snaps = {s["id"] for s in host_spans.window_spans(run, "snapshot")
             if s.get("parent") in ids}
    wait = sum(s["t1"] - s["t0"]
               for s in host_spans.window_spans(run, "snapshot_dispatch")
               if s.get("parent") in snaps)
    wait += sum(s["args"].get("ready_wait_s", 0.0)
                for s in host_spans.window_spans(run, "snapshot_fetch")
                if s.get("parent") in snaps)
    return 100.0 * wait / total
