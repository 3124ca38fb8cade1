"""Arithmetic from schedules and scrape logs to the end-to-end metrics.

Pure functions of plain lists, so that the tests can hold them against
schedules made by hand. Every metric is taken over the whole window:
every tick, every scrape, all CPU seconds over all events.
"""

from __future__ import annotations

import bisect
import math


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it. No interpolation: the number
    reported is one that was measured."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def freshness_s(ticks: list[tuple[float, int]],
                scrapes: list[tuple[float, float, int]]) -> list[float]:
    """Per tick, seconds from when its rows were due to the end of the
    first scrape that counts them all.

    ``ticks``: (due time, events offered up to and including this tick,
    counted from the agent's boot). ``scrapes``: (sent, done, events
    the scrape shows), in the order they were made. A scrape sent
    before a tick was due cannot show it, whatever it counts. A tick
    no scrape covers reads ``inf``: its events never became visible."""
    scrapes = sorted(scrapes, key=lambda s: s[1])
    # The count a scrape shows never runs backwards for a sound agent;
    # a running maximum keeps the search well defined if one does.
    shown, top = [], 0
    for _, _, events in scrapes:
        top = max(top, events)
        shown.append(top)
    out = []
    for due, need in ticks:
        i = bisect.bisect_left(shown, need)
        while i < len(scrapes) and scrapes[i][0] < due:
            i += 1
        out.append(scrapes[i][1] - due if i < len(scrapes) else math.inf)
    return out


def staleness_s(ticks: list[tuple[float, int]],
                scrapes: list[tuple[float, float, int]]) -> list[float]:
    """Per scrape, how far behind the offered load it is: seconds from
    the due time of the oldest tick that was due when the scrape was
    sent and that it does not show in full, to the scrape's end. A
    scrape that shows everything due is as stale as its own round trip.
    A stall anywhere between the sink and the HTTP server shows as
    staleness that grows from one scrape to the next.

    ``ticks`` and ``scrapes`` as for :func:`freshness_s`; ticks in the
    order they are due."""
    dues = [due for due, _ in ticks]
    needs = [need for _, need in ticks]
    out = []
    for sent, done, events in scrapes:
        # Counts only grow along the ticks: the first tick the scrape
        # does not cover is the oldest one.
        i = bisect.bisect_right(needs, events)
        if i < len(ticks) and dues[i] <= sent:
            out.append(done - dues[i])
        else:
            out.append(done - sent)
    return out


def round_trips_s(scrapes: list[tuple[float, float, int]], t_open: float,
                  t_close: float) -> list[float]:
    """Round-trip seconds of every scrape sent inside the window."""
    return [done - sent for sent, done, _ in scrapes
            if t_open <= sent < t_close]
