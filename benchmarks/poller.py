#!/usr/bin/env python3
"""The benchmark's Prometheus: a child process that scrapes ``/metrics``
on a fixed cadence and writes one JSON line per scrape.

It imports no JAX (the parent owns the chip) and nothing of the program.
It is what a Prometheus server is to the agent, at a cadence chosen for
sample count, and it keeps scrape parsing out of the agent's CPU account.

    python3 benchmarks/poller.py --port P --interval 0.1 --out FILE \
        [--counters name,name,...]

Each line: ``{"sent": t, "done": t, "ok": bool, "bytes": n, "events": n,
"c": {name: value}}``. Times are ``time.monotonic()``, which on Linux is
one clock for every process of the machine. ``events`` is the sum of the
per-pod forward and drop packet counters: the events the agent has made
visible. ``c`` holds the summed value of each named counter. A scrape
is sent at the next multiple of the interval after the last one ended,
as a scraper that skips a beat does. SIGTERM ends the loop.
"""

from __future__ import annotations

import argparse
import http.client
import json
import signal
import sys
import time

PREFIX = b"networkobservability_"
EVENT_SERIES = (PREFIX + b"adv_forward_count", PREFIX + b"adv_drop_count")


def series_sum(body: bytes, names: tuple[bytes, ...]) -> dict[bytes, float]:
    """Sum of the samples of each named series (with or without the
    ``_total`` suffix, any labels) in one exposition body."""
    out = dict.fromkeys(names, 0.0)
    for line in body.split(b"\n"):
        if not line.startswith(names):
            continue
        for name in names:
            if not line.startswith(name):
                continue
            rest = line[len(name):]
            if rest.startswith(b"_total"):
                rest = rest[6:]
            if rest[:1] in (b"{", b" "):
                try:
                    out[name] += float(rest[rest.rfind(b" ") + 1:])
                except ValueError:
                    pass
                break
    return out


def scrape(port: int, timeout: float) -> tuple[bool, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = resp.read()
        return resp.status == 200, body
    except (OSError, http.client.HTTPException):
        return False, b""
    finally:
        conn.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--interval", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--counters", default="")
    args = ap.parse_args(argv)
    counters = tuple(PREFIX + c.encode() for c in args.counters.split(",")
                     if c)
    stop = []
    signal.signal(signal.SIGTERM, lambda *_: stop.append(1))
    t0 = time.monotonic()
    with open(args.out, "w") as out:
        while not stop:
            now = time.monotonic()
            k = int((now - t0) / args.interval) + 1
            time.sleep(max(0.0, t0 + k * args.interval - now))
            sent = time.monotonic()
            ok, body = scrape(args.port, 30.0)
            done = time.monotonic()
            sums = series_sum(body, EVENT_SERIES + counters)
            out.write(json.dumps({
                "sent": sent, "done": done, "ok": ok, "bytes": len(body),
                "events": int(sum(sums[n] for n in EVENT_SERIES)),
                "c": {n[len(PREFIX):].decode(): sums[n] for n in counters},
            }) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
