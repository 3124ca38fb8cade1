"""Prometheus exporter registries.

Reference analog: pkg/exporter/prometheusexporter.go:17-40 — three
registries: **Default** (basic node-level metrics, lives for the process),
**Advanced** (pod-level metrics, RESET whenever a MetricsConfiguration CRD
reconcile changes the metric set, :35-40), and a **Combined** gatherer the
HTTP server scrapes. Constructor helpers mirror :46-88.

Built on prometheus_client's CollectorRegistry; the combined gatherer is a
merge of both registries' samples at scrape time, and reset callbacks let
the HTTP server re-register its handler like the reference does.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Iterable, Iterator, Sequence

import numpy as np
from prometheus_client import CollectorRegistry, Counter, Gauge, Histogram
from prometheus_client.metrics_core import Metric
from prometheus_client.samples import Sample

from retina_tpu.log import logger
from retina_tpu.utils import metric_names as mn

_log = logger("exporter")


_INF = float("inf")


def _escape_label(v: str) -> str:
    # The common case (no escapable chars) must cost containment
    # checks, not three regex passes per sample like prometheus_client.
    if "\\" in v:
        v = v.replace("\\", "\\\\")
    if "\n" in v:
        v = v.replace("\n", "\\n")
    if '"' in v:
        v = v.replace('"', '\\"')
    return v


def _float_str(d: float) -> str:
    """prometheus_client.utils.floatToGoString, regex-free."""
    d = float(d)
    if d == _INF:
        return "+Inf"
    if d == -_INF:
        return "-Inf"
    if d != d:
        return "NaN"
    s = repr(d)
    dot = s.find(".")
    if d > 0 and dot > 6:
        mantissa = f"{s[0]}.{s[1:dot]}{s[dot + 1:]}".rstrip("0.")
        return f"{mantissa}e+0{dot - 1}"
    return s


def _label_str(names: Iterable[str], values: Iterable[str]) -> str:
    """``{k="v",...}``, the labels sorted by name and the values
    escaped, or nothing where there are no labels: what a sample line
    carries between the name and the value."""
    lbl = ",".join(
        f'{k}="{_escape_label(v)}"' for k, v in sorted(zip(names, values))
    )
    return "{" + lbl + "}" if lbl else ""


def _sample_line(s) -> str:
    labelstr = _label_str(s.labels, s.labels.values())
    if s.timestamp is not None:
        ts = f" {int(float(s.timestamp) * 1000):d}"
    else:
        ts = ""
    return f"{s.name}{labelstr} {_float_str(s.value)}{ts}\n"


def _render_family(metric: Metric, output: list[str]) -> None:
    """HELP, TYPE and one line per sample of one metric family."""
    mname = metric.name
    mtype = metric.type
    if mtype == "counter":
        mname += "_total"
    elif mtype == "info":
        mname += "_info"
        mtype = "gauge"
    elif mtype == "stateset":
        mtype = "gauge"
    elif mtype == "gaugehistogram":
        mtype = "histogram"
    elif mtype == "unknown":
        mtype = "untyped"
    doc = metric.documentation.replace("\\", r"\\").replace(
        "\n", r"\n"
    )
    output.append(f"# HELP {mname} {doc}\n")
    output.append(f"# TYPE {mname} {mtype}\n")
    om_samples: dict[str, list[str]] = {}
    base = metric.name
    for s in metric.samples:
        name = s.name
        if (
            name == base + "_created"
            or name == base + "_gsum"
            or name == base + "_gcount"
        ):
            om_samples.setdefault(name[len(base):], []).append(
                _sample_line(s)
            )
        else:
            output.append(_sample_line(s))
    for suffix, lines in sorted(om_samples.items()):
        output.append(f"# HELP {base}{suffix} {doc}\n")
        output.append(f"# TYPE {base}{suffix} gauge\n")
        output.extend(lines)


def render_exposition(registry: CollectorRegistry) -> bytes:
    """Fast Prometheus text-format renderer (text/plain; version 0.0.4).

    Byte-identical to prometheus_client.generate_latest for the metric
    and label NAMES this framework emits (valid legacy identifiers by
    construction). The library routes every sample through three
    regex-validation/escaping passes — ~1.1s per render at 30k pod-level
    samples, the agent's single largest CPU cost under scrape load; this
    writer emits the same bytes with plain string operations. The test
    suite cross-checks byte equality against generate_latest.

    Every family goes sample by sample through ``collect()``: the path
    of the default and the hubble registry, and the oracle for
    :func:`render_rows`.
    """
    output: list[str] = []
    for metric in registry.collect():
        _render_family(metric, output)
    return "".join(output).encode("utf-8")


def render_rows(registry: CollectorRegistry) -> bytes:
    """The same bytes as :func:`render_exposition`, for a registry that
    keeps families as rows (:class:`SeriesTable`): such a family is its
    HELP and TYPE lines and the join of the lines its rows already
    hold; any other collector registered there is rendered sample by
    sample, each in registration order."""
    output: list[str] = []
    for metric in registry.collect():
        if isinstance(metric, RowsFamily):
            output.append(metric.text())
        else:
            _render_family(metric, output)
    return "".join(output).encode("utf-8")


class SeriesTable:
    """One pod-level gauge family kept as rows, in place of one
    prometheus_client child per series.

    A row is appended the first time its label values are set and
    stays: its label values, the line prefix ``name{k="v",...} `` built
    once, the value last set and the finished line. :meth:`set` is what
    ``gauge.labels(...).set(v)`` was (find the row by its label values,
    or append it); :meth:`update` takes rows the caller already holds
    the numbers of and compares their values with the last ones in
    numpy. Either touches a row only when its value changed: format the
    value, rebuild the line. A render is the join of the lines
    (:func:`render_rows`); ``collect()`` yields the same family sample
    by sample for whoever reads it through the registry
    (``generate_latest``, ``get_sample_value``).

    Written by one thread (the publisher's), read by any: a row is
    complete before its line is appended, and a reader takes the lines
    there are when it starts.
    """

    def __init__(self, name: str, labelnames: Sequence[str],
                 documentation: str) -> None:
        self._family = Metric(name, documentation, "gauge")  # no samples
        self.name = name
        self.labelnames = tuple(labelnames)
        head: list[str] = []
        _render_family(self._family, head)  # its HELP and TYPE lines
        self._head = "".join(head)
        self._by_labels: dict[tuple[str, ...], int] = {}
        self._labels: list[tuple[str, ...]] = []
        self._prefix: list[str] = []
        self._lines: list[str] = []
        self._values = np.zeros(64, np.float64)
        if not self.labelnames:
            self.set((), 0.0)  # a gauge without labels reads 0 from birth

    def __len__(self) -> int:
        return len(self._lines)

    def set(self, labels: tuple[str, ...], value: float) -> tuple[int, bool]:
        """Set the row of these label values, appended if it is new:
        its number, and whether it is new or its line was rewritten."""
        row = self._by_labels.get(labels)
        if row is not None:
            if value == self._values[row]:
                return row, False
            self._values[row] = value
            self._lines[row] = f"{self._prefix[row]}{_float_str(value)}\n"
            return row, True
        row = len(self._lines)
        if row == len(self._values):
            self._values = np.concatenate(
                [self._values, np.zeros(row, np.float64)])
        prefix = f"{self.name}{_label_str(self.labelnames, labels)} "
        self._values[row] = value
        self._labels.append(labels)
        self._prefix.append(prefix)
        self._by_labels[labels] = row
        self._lines.append(f"{prefix}{_float_str(value)}\n")
        return row, True

    def update(self, rows: np.ndarray, values: np.ndarray) -> int:
        """Set rows by number (float64 values): how many changed."""
        changed = np.nonzero(values != self._values[rows])[0]
        if changed.size:
            rows, values = rows[changed], values[changed]
            self._values[rows] = values
            lines, prefix, fmt = self._lines, self._prefix, _float_str
            for row, value in zip(rows.tolist(), values.tolist()):
                lines[row] = f"{prefix[row]}{fmt(value)}\n"
        return int(changed.size)

    # -- the registry's side: a collector ------------------------------
    def describe(self) -> Iterable[Metric]:
        return [self._family]

    def collect(self) -> Iterator[Metric]:
        yield RowsFamily(self, len(self._lines))


class RowsFamily(Metric):
    """What a :class:`SeriesTable` yields to its registry: the gauge
    family of the rows there were when it was collected. ``text()`` is
    the join of their lines; ``samples`` are built for whoever asks."""

    def __init__(self, table: SeriesTable, n_rows: int) -> None:
        self._table, self._n_rows = table, n_rows
        family = table._family
        Metric.__init__(self, family.name, family.documentation, "gauge")

    @property
    def samples(self) -> list[Sample]:
        t, n = self._table, self._n_rows
        return [
            Sample(t.name, dict(zip(t.labelnames, labels)), value, None)
            for labels, value in zip(t._labels[:n], t._values[:n].tolist())
        ]

    @samples.setter
    def samples(self, _unused: list[Sample]) -> None:
        """``Metric.__init__`` assigns an empty list."""

    def text(self) -> str:
        return self._table._head + "".join(self._table._lines[:self._n_rows])


class Exporter:
    """Holds the default + advanced registries (reference package state)."""

    def __init__(self) -> None:
        self.default_registry = CollectorRegistry()
        self.advanced_registry = CollectorRegistry()
        # Hubble self-metrics live in their OWN registry, served by the
        # dedicated hubble metrics mux (reference :9965) and NOT by the
        # combined gatherer — scraping both muxes must not double-ingest.
        self.hubble_registry = CollectorRegistry()
        self._reset_cbs: list[Callable[[], None]] = []
        self._lock = threading.Lock()
        # The advanced registry is written in bursts (a publish cycle of
        # the metrics module every 1-5 s, a reconcile) and gathered far
        # more often. A render of its ~35k pod-level series is the join
        # of their rows' lines: 12-13 ms of CPU on the chip's host,
        # where walking one prometheus_client child per series took
        # 0.4 s (PERF.md section 6, PR 32), and still the larger part
        # of a gather.
        # Every change to it advances the generation; a publisher that
        # has finished a cycle also declares the generation published,
        # and any other change takes that word back.
        # gather() keeps the bytes it rendered from a published
        # generation and reuses them for as long as that generation
        # stands. Until a publisher speaks (tests and doubles that set
        # gauges directly, basic mode, the operator) every gather
        # renders, so a direct write is read back at once.
        self._adv_gen = 0
        self._adv_published = False
        self._adv_rendered: tuple[int, bytes] = (-1, b"")  # none kept
        gathers = self.new_counter(
            mn.TPU_EXPOSITION_GATHERS, [mn.L_ADVANCED],
            "gathers of the combined exposition, by what they did with "
            "the advanced (pod-level) registry",
        )
        self._gathers = {
            how: gathers.labels(advanced=how)
            for how in (mn.ADVANCED_RENDERED, mn.ADVANCED_REUSED)
        }
        # How often the row tables engage, and what the publisher's
        # cycle costs this process: counted by the metrics module per
        # cycle (rows looked at; rows new or rewritten; CPU seconds of
        # its thread inside the snapshot and inside series_publish) and
        # by gather() below (CPU seconds of the gathering thread inside
        # a render of the pod-level bytes).
        self.publish_rows = self.new_counter(
            mn.TPU_PUBLISH_ROWS, [],
            "rows of the pod-level tables the publish cycles looked at",
        )
        self.publish_rows_changed = self.new_counter(
            mn.TPU_PUBLISH_ROWS_CHANGED, [],
            "rows of the pod-level tables the publish cycles appended "
            "or rewrote (the value differed from the one last set)",
        )
        cpu = self.new_counter(
            mn.TPU_PUBLISH_CPU_SECONDS, [mn.L_PART],
            "CPU seconds (time.thread_time) of the publisher's thread "
            "inside a cycle's snapshot and series_publish, and of a "
            "gathering thread inside a render of the pod-level bytes",
        )
        self.publish_cpu = {
            part: cpu.labels(part=part) for part in mn.PUBLISH_PARTS
        }

    # -- reset (prometheusexporter.go:35-40) --
    def reset_advanced(self) -> None:
        """Replace the advanced registry (CRD reconcile changed metrics)."""
        with self._lock:
            self.advanced_registry = CollectorRegistry()
            self._advanced_changed()
            self._adv_rendered = (-1, b"")
            cbs = list(self._reset_cbs)
        _log.info("advanced metrics registry reset")
        for cb in cbs:
            cb()

    def on_reset(self, cb: Callable[[], None]) -> None:
        with self._lock:
            self._reset_cbs.append(cb)

    def _advanced_changed(self) -> None:
        """Caller holds ``_lock``."""
        self._adv_gen += 1
        self._adv_published = False

    def advanced_published(self) -> None:
        """A publisher finished a cycle of writes to the advanced
        registry: the next gather renders it, and the gathers after
        that reuse those bytes until the next change. Called where the
        writes END: declared before them, a gather could keep half of
        them under the new generation."""
        with self._lock:
            self._adv_gen += 1
            self._adv_published = True

    # -- combined gatherer (prometheusexporter.go:17-33) --
    def gather(self) -> tuple[bytes, str]:
        """Prometheus text exposition of both registries, and what was
        done with the advanced one: ``mn.ADVANCED_RENDERED`` or
        ``mn.ADVANCED_REUSED``.

        Rendered by :func:`render_exposition` (the default registry)
        and :func:`render_rows` (the advanced one), not
        prometheus_client's generate_latest: at production cardinality
        (~30k pod-level samples) the library's per-sample regex
        validation/escaping cost ~1.1s per render on one core. Both
        emit the same text format; tests pin them byte-compatible.

        The default registry (the agent's own counters, histograms and
        health series, which change all the time) is rendered on every
        call. The advanced registry is rendered unless bytes are kept
        for its generation. The generation is read BEFORE the render
        and kept with the bytes, and only a published generation that
        still stands when the render ends is kept: a publish that lands
        mid-render costs one more render, never a lost update, and a
        render that overlapped a half-written cycle is replaced by the
        one after that cycle's declaration.
        """
        with self._lock:
            default, advanced = self.default_registry, self.advanced_registry
            gen, published = self._adv_gen, self._adv_published
            kept_gen, adv_bytes = self._adv_rendered
        how = mn.ADVANCED_REUSED if kept_gen == gen else mn.ADVANCED_RENDERED
        # Counted before the default registry is rendered: a gather's
        # own count is in its bytes.
        self._gathers[how].inc()
        if how == mn.ADVANCED_RENDERED:
            t0 = time.thread_time()
            adv_bytes = render_rows(advanced)
            self.publish_cpu[mn.PART_RENDER].inc(time.thread_time() - t0)
            if published:
                with self._lock:
                    if self._adv_gen == gen:
                        self._adv_rendered = (gen, adv_bytes)
        return render_exposition(default) + adv_bytes, how

    def gather_text(self) -> bytes:
        """The exposition alone (see :meth:`gather`)."""
        return self.gather()[0]

    # -- constructor helpers (prometheusexporter.go:46-88) --
    def new_gauge(self, name: str, labels: list[str], help_: str = "") -> Gauge:
        return Gauge(
            name, help_ or name, labels, registry=self.default_registry
        )

    def new_counter(self, name: str, labels: list[str], help_: str = "") -> Counter:
        return Counter(
            name, help_ or name, labels, registry=self.default_registry
        )

    def new_histogram(
        self, name: str, labels: list[str], buckets: list[float], help_: str = ""
    ) -> Histogram:
        return Histogram(
            name, help_ or name, labels,
            buckets=buckets, registry=self.default_registry,
        )

    def gather_hubble_text(self) -> bytes:
        """Exposition of the hubble registry only (:9965 mux)."""
        return render_exposition(self.hubble_registry)

    def new_hubble_gauge(self, name: str, labels: list[str],
                         help_: str = "") -> Gauge:
        return Gauge(
            name, help_ or name, labels, registry=self.hubble_registry
        )

    def new_hubble_counter(self, name: str, labels: list[str],
                           help_: str = "") -> Counter:
        return Counter(
            name, help_ or name, labels, registry=self.hubble_registry
        )

    # A new family is a change too (its HELP/TYPE lines appear):
    # registered and the generation advanced under the one lock
    # gather() reads registry and generation under.
    def new_adv_gauge(self, name: str, labels: list[str], help_: str = "") -> Gauge:
        with self._lock:
            self._advanced_changed()
            return Gauge(name, help_ or name, labels,
                         registry=self.advanced_registry)

    def new_adv_counter(
        self, name: str, labels: list[str], help_: str = ""
    ) -> Counter:
        with self._lock:
            self._advanced_changed()
            return Counter(name, help_ or name, labels,
                           registry=self.advanced_registry)

    def new_adv_table(
        self, name: str, labels: list[str], help_: str = ""
    ) -> SeriesTable:
        """A gauge family kept as rows: what the metric objects
        publish into."""
        table = SeriesTable(name, labels, help_ or name)
        with self._lock:
            self._advanced_changed()
            self.advanced_registry.register(table)
        return table


_singleton: Exporter | None = None
_lock = threading.Lock()


def get_exporter() -> Exporter:
    global _singleton
    with _lock:
        if _singleton is None:
            _singleton = Exporter()
        return _singleton


def reset_for_tests() -> None:
    """Fresh registries so tests don't collide on metric names."""
    global _singleton
    with _lock:
        _singleton = None
