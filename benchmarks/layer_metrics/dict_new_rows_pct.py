"""Flow dictionary + wire, by the program's own counter: of the rows
that crossed the link under the dictionary, the share that crossed as
full 52-byte descriptor rows (``tpu_wire_rows_counter`` kinds ``new``
and ``tableless``: a descriptor the dictionary had not seen this
generation, a known row escalated because a narrow lane could not hold
it, or one the full table had no slot for) and not as a 6-7 byte dense
row against the device's table (``known``). A working set the
dictionary holds reads a few percent after the first lap; one that turns
it over reads most of the rows.

Each kind is one sample of the counter, and the poller hands a counter
back summed over its labels once any reader asks for the sum, as
``combine_ratio`` does of this one in every cell (its prefix match
gives the line to the shorter name first). So the samples are read
where the program keeps them, from the exposition the process itself
renders after the settled scrape, with the poller's own parser: the
counter starts at the boot, and a run offers one load a boot, so that
is the load's start to its settled scrape. A program without the
counter, or one that shipped nothing under the dictionary, says
nothing."""

import poller

UNIT = "%"
SERIES = 'tpu_wire_rows_counter_total{kind="%s"}'
FULL_ROWS = ("new", "tableless")
KINDS = (*FULL_ROWS, "known")


def rows_by_kind() -> dict[str, float]:
    """``tpu_wire_rows_counter`` of this process, sample by sample."""
    try:
        from retina_tpu.exporter import get_exporter

        body = get_exporter().gather_text()
    except Exception:  # noqa: BLE001 — no program, no exposition: nothing
        return {}
    names = {k: poller.PREFIX + (SERIES % k).encode() for k in KINDS}
    sums = poller.series_sum(body, tuple(names.values()))
    return {k: sums[n] for k, n in names.items()}


def read(run):
    rows = rows_by_kind()
    total = sum(rows.values())
    if total <= 0:
        return None
    return 100.0 * sum(rows[k] for k in FULL_ROWS) / total
