"""Telemetry SPI: distributed tracing + metrics registry.

Analog of the reference's ``libs/telemetry`` (tracing/Tracer.java,
metrics/MetricsRegistry.java) with the OTel plugin's behavior folded in
at the fidelity this engine needs:

- ``Tracer``: contextvar-scoped spans carrying W3C trace-context ids
  (``traceparent`` header compatible, TracingContextPropagator analog).
  Finished spans land in a bounded in-memory exporter the
  ``GET /_nodes/trace`` debug endpoint reads — the InMemorySpanExporter
  technique from the reference's telemetry tests.
- ``MetricsRegistry``: named counters and fixed-bucket latency
  histograms with percentile readout, surfaced by ``_nodes/stats``
  under a ``telemetry`` section.

A span reads two clocks once, at its start: ``time.monotonic_ns`` (the
clock durations come from, and the one a profiler session's markers are
stamped with) and ``time.time_ns`` (the wall timestamp, for display).
One opened with ``cpu=True`` may also read ``time.thread_time_ns`` at
both ends (its thread's CPU time: duration less CPU is the time the
thread did not run inside the span, waiting for the interpreter lock, a
lock, a socket or the device).  That clock is a system call and not
every span's to pay: 0.3 us a read on a plain Linux host, but 6 us on a
sandboxed one, 18 to 35 us there while six threads are in it, all of it
holding the interpreter lock, and such a host counts thread CPU in ticks
of 10 ms.  So the call sites whose waiting somebody reads ask for it,
one trace in eight is metered (``CPU_METERED``: by the trace id's last
digit, so a request's spans meter together; the traces that start here
take that digit in turn, so every eighth meters), and a name's totals count
each metered span eight times (``CPU_WEIGHT``): sums over thousands of
spans are sound where one span's reading is a tick or nothing (a span
in which a tick lands takes its share of ``off_cpu_in_millis`` back).
Named parts (``Span.part``) split a span's duration where the work
happens, without more spans.
Spans opened with ``start_span`` also enter a
``jax.profiler.TraceAnnotation``, so a profiler session shows them on
host lines of the same trace as the device's operations.  Everything
stays always-on: a span is one small object appended to a ring, and its
dict is only built when somebody reads the ring.  The ring forgets; the
per-name totals a span is folded into where it ends (``Tracer.totals``)
do not, so a reader of ``_nodes/stats`` ``telemetry.spans`` sees every
span that ever ended, whatever the ring still holds.
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import itertools
import threading
import time
from bisect import bisect_left
from collections import deque
from random import getrandbits
from threading import get_ident
from time import monotonic_ns, thread_time_ns
from typing import Optional

# last hex digit of the trace ids whose ``cpu=True`` spans read the thread
# CPU clock: one trace in eight, so a metered span counts for eight.  A
# trace that starts here takes that digit in turn (``Tracer.begin_span``),
# so it is every eighth and not one in eight by luck; an id that came
# with the request (``traceparent``) is taken as it is.
CPU_METERED = "08"
CPU_WEIGHT = 16 // len(CPU_METERED)

_current_span: "contextvars.ContextVar[Optional[Span]]" = \
    contextvars.ContextVar("opensearch_tpu_span", default=None)

TRACEPARENT = "traceparent"

_trace_annotation = None      # jax.profiler.TraceAnnotation, on first use


def _load_trace_annotation():
    global _trace_annotation
    from jax.profiler import TraceAnnotation
    _trace_annotation = TraceAnnotation
    return TraceAnnotation


class SpanContext:
    """The propagatable identity of a span (trace_id + span_id) — what
    crosses process/transport boundaries via ``traceparent``."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def to_traceparent(self) -> str:
        # W3C trace-context: version-traceid-spanid-flags (sampled)
        return f"00-{self.trace_id}-{self.span_id}-01"

    @staticmethod
    def from_traceparent(value) -> "Optional[SpanContext]":
        if not value or not isinstance(value, str):
            return None
        parts = value.strip().split("-")
        if len(parts) < 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
            return None
        try:
            int(parts[1], 16)
            int(parts[2], 16)
        except ValueError:
            return None
        return SpanContext(parts[1], parts[2])


class _Part:
    """``with span.part(name)``: the block's wall time, added to the
    span's part of that name."""

    __slots__ = ("span", "name", "t0")

    def __init__(self, span: "Span", name: str):
        self.span = span
        self.name = name

    def __enter__(self) -> None:
        self.t0 = monotonic_ns()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.span.add_part(self.name, monotonic_ns() - self.t0)
        return False


class Span:
    """One timed operation.  ``end()`` freezes the duration and ships the
    span to the tracer's in-memory exporter."""

    __slots__ = ("tracer", "name", "trace_id", "span_id", "parent_span_id",
                 "attributes", "start_nanos", "start_wall_nanos",
                 "duration_nanos", "error", "cpu_nanos", "parts",
                 "_thread", "_cpu0")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 parent_span_id: Optional[str],
                 attributes: Optional[dict] = None, cpu: bool = False):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = f"{getrandbits(64):016x}"
        self.parent_span_id = parent_span_id
        self.attributes: dict = dict(attributes) if attributes else {}
        self.duration_nanos: Optional[int] = None
        self.error: Optional[str] = None
        # the thread's CPU time inside the span; None where it was not
        # asked for, the trace is not one that meters, or the span was
        # ended on another thread than the one that opened it
        self.cpu_nanos: Optional[int] = None
        self.parts: Optional[dict] = None       # name -> nanos
        self.start_wall_nanos = time.time_ns()  # wall-clock: display timestamp
        self.start_nanos = monotonic_ns()
        # the CPU reads lie inside the monotonic ones, here and in end():
        # an exact clock never reads more CPU than the duration (one that
        # counts in ticks may, for one span)
        if cpu and trace_id[-1] in CPU_METERED:
            self._thread = get_ident()
            self._cpu0 = thread_time_ns()
        else:
            self._thread = None

    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def set_attribute(self, key: str, value) -> "Span":
        self.attributes[key] = value
        return self

    def record_error(self, err) -> None:
        self.error = f"{type(err).__name__}: {err}"

    def add_part(self, name: str, nanos: int) -> None:
        """Add ``nanos`` of the span's wall time to its part ``name``
        (two clock reads at the caller and a dict add: no object in the
        ring).  Called on the span's own thread."""
        parts = self.parts
        if parts is None:
            self.parts = {name: nanos}
        else:
            parts[name] = parts.get(name, 0) + nanos

    def part(self, name: str) -> _Part:
        return _Part(self, name)

    def end(self) -> None:
        if self.duration_nanos is not None:
            return                       # idempotent
        if self._thread is not None and self._thread == get_ident():
            cpu = thread_time_ns() - self._cpu0
            self.duration_nanos = monotonic_ns() - self.start_nanos
            self.cpu_nanos = cpu
        else:
            self.duration_nanos = monotonic_ns() - self.start_nanos
        self.tracer._export(self)

    def to_dict(self) -> dict:
        out = {"name": self.name, "trace_id": self.trace_id,
               "span_id": self.span_id,
               "parent_span_id": self.parent_span_id,
               # wall clock, the fraction kept to the microsecond
               "start_time_in_millis": self.start_wall_nanos // 1000 / 1e3,
               # time.monotonic_ns: orders spans against each other and
               # against anything else stamped with that clock
               "start_time_in_nanos": self.start_nanos,
               "duration_in_nanos": self.duration_nanos,
               "attributes": dict(self.attributes)}
        if self.cpu_nanos is not None:
            out["cpu_in_nanos"] = self.cpu_nanos
        if self.parts:
            out["parts"] = dict(self.parts)
        if self.error is not None:
            out["error"] = self.error
        return out


class _SpanTotals:
    """What every finished span of one name adds up to.  Spans end on
    every request thread, so each name has a lock of its own.  A span
    that metered its CPU stands for ``CPU_WEIGHT`` spans of its name: its
    CPU time and the rest of its duration are added that many times,
    where it is folded, so ``cpu_in_millis`` and ``off_cpu_in_millis``
    are sums like the others (an estimate from one trace in eight, and
    no share taken at read time: a delta over a window holds that
    window's spans only)."""

    __slots__ = ("_lock", "count", "nanos", "metered", "cpu_nanos",
                 "off_nanos", "parts")

    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self.nanos = 0
        self.metered = 0             # the spans that metered their CPU,
        self.cpu_nanos = 0           # their CPU time, weighted
        self.off_nanos = 0           # and the rest of their wall time
        self.parts: dict = {}

    def add(self, span: Span) -> None:
        duration, cpu, parts = span.duration_nanos, span.cpu_nanos, span.parts
        with self._lock:
            self.count += 1
            self.nanos += duration
            if cpu is not None:
                self.metered += 1
                self.cpu_nanos += CPU_WEIGHT * cpu
                self.off_nanos += CPU_WEIGHT * (duration - cpu)
            if parts is not None:
                mine = self.parts
                for name, nanos in parts.items():
                    mine[name] = mine.get(name, 0) + nanos

    def stats(self) -> dict:
        with self._lock:
            out = {"count": self.count, "time_in_millis": self.nanos / 1e6,
                   "metered_count": self.metered,
                   "cpu_in_millis": self.cpu_nanos / 1e6,
                   "off_cpu_in_millis": self.off_nanos / 1e6}
            parts = dict(self.parts)
        if parts:
            out["parts"] = {name: {"time_in_millis": n / 1e6}
                            for name, n in sorted(parts.items())}
        return out


class _SpanScope:
    """``with tracer.start_span(...) as span``: the span is current for
    the body and mirrored into a profiler session, if one is running."""

    __slots__ = ("span", "_token", "_mirror")

    def __init__(self, span: Span):
        self.span = span

    def __enter__(self) -> Span:
        span = self.span
        self._token = _current_span.set(span)
        self._mirror = mirror = (_trace_annotation
                                 or _load_trace_annotation())(span.name)
        mirror.__enter__()
        return span

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._mirror.__exit__(exc_type, exc, tb)
        _current_span.reset(self._token)
        span = self.span
        if exc is not None:
            span.record_error(exc)
        span.end()
        return False


class Tracer:
    """Contextvar-scoped span stack + bounded finished-span buffer.

    ``start_span`` is a context manager: the new span becomes current for
    the ``with`` body, so nested instrumentation parents automatically;
    an explicit ``parent`` (a SpanContext extracted from transport
    headers) overrides the ambient current span — that is how remote
    shard executions join the coordinator's trace.
    """

    def __init__(self, max_spans: int = 8192):
        # appended to by every thread that ends a span: a deque's append
        # is thread-safe, so the ring takes no lock
        self._finished: "deque[Span]" = deque(maxlen=max_spans)
        # name -> _SpanTotals (a lock a name: no lock is shared between
        # names); names are code-level literals, a bounded set
        self._totals: dict = {}
        self._totals_lock = threading.Lock()    # guards creation only
        # the traces started here, counted: the low four bits are a new
        # trace id's last digit (next() of a count is atomic)
        self._traces = itertools.count()

    # -- span lifecycle ---------------------------------------------------

    def begin_span(self, name: str, attributes: Optional[dict] = None,
                   parent: "SpanContext | Span | None" = None,
                   cpu: bool = False) -> Span:
        """Non-context-manager start (callers that end() across scopes
        or threads; not mirrored into the profiler's trace).  ``cpu``:
        meter the thread's CPU time beside the wall time where the trace
        is one of ``CPU_METERED`` (two system calls a span; no reading
        where it ends on another thread)."""
        if parent is None:
            parent = _current_span.get()
        if parent is None:
            trace_id = f"{getrandbits(124):031x}{next(self._traces) & 15:x}"
            return Span(self, name, trace_id, None, attributes, cpu)
        return Span(self, name, parent.trace_id, parent.span_id, attributes,
                    cpu)

    def start_span(self, name: str, attributes: Optional[dict] = None,
                   parent: "SpanContext | Span | None" = None,
                   cpu: bool = False) -> _SpanScope:
        return _SpanScope(self.begin_span(name, attributes, parent, cpu))

    @staticmethod
    def current() -> Optional[Span]:
        return _current_span.get()

    def _export(self, span: Span) -> None:
        self._finished.append(span)
        totals = self._totals.get(span.name)
        if totals is None:
            with self._totals_lock:
                totals = self._totals.setdefault(span.name, _SpanTotals())
        totals.add(span)

    # -- context propagation (TracingContextPropagator analog) ------------

    @staticmethod
    def inject(headers: dict) -> dict:
        """Write the current span's ``traceparent`` into ``headers`` (a
        no-op outside any span)."""
        span = _current_span.get()
        if span is not None:
            headers[TRACEPARENT] = span.context().to_traceparent()
        return headers

    @staticmethod
    def extract(headers: Optional[dict]) -> Optional[SpanContext]:
        if not headers:
            return None
        value = headers.get(TRACEPARENT)
        if value is None:            # HTTP headers arrive case-insensitive
            for k, v in headers.items():
                if str(k).lower() == TRACEPARENT:
                    value = v
                    break
        return SpanContext.from_traceparent(value)

    # -- readout ----------------------------------------------------------

    def recent(self, limit: int = 100,
               trace_id: Optional[str] = None) -> list[dict]:
        """Most-recent finished spans, newest first."""
        while True:
            try:
                spans = list(self._finished)
                break
            except RuntimeError:         # a span ended during the copy
                continue
        spans.reverse()
        if trace_id:
            spans = [s for s in spans if s.trace_id == trace_id]
        return [s.to_dict() for s in spans[: max(0, int(limit))]]

    def totals(self) -> dict:
        """{name: count, time, CPU, off-CPU and parts} over every span
        that ended since the last ``reset``, in the ring or not."""
        with self._totals_lock:
            names = sorted(self._totals.items())
        return {name: t.stats() for name, t in names}

    def stats(self) -> dict:
        """``finished``: spans ended since the last ``reset``; ``ring``:
        how many of them the ring can hold.  A reader whose window ended
        more than ``ring`` spans ago finds its oldest spans gone."""
        with self._totals_lock:
            totals = list(self._totals.values())
        return {"finished": sum(t.count for t in totals),
                "ring": self._finished.maxlen}

    def prometheus_text(self) -> str:
        """The totals in the Prometheus text format, the span's name (a
        code-level literal) and the part as labels: the data
        ``_nodes/stats`` ``telemetry.spans`` / ``telemetry.tracer``
        report as JSON."""
        def series(metric: str, kind: str, doc: str, rows: list) -> list:
            return [f"# HELP {metric} {doc}", f"# TYPE {metric} {kind}",
                    *(f"{metric}{labels} {value:.10g}"
                      for labels, value in rows)]

        totals = self.totals()
        by_name = [(f'{{span="{name}"}}', t)  # label-ok: span names are code-level literals
                   for name, t in totals.items()]
        lines = []
        for key, metric, kind, doc in (
                ("count", "telemetry_spans_total", "counter",
                 "Finished spans"),
                ("time_in_millis", "telemetry_span_time_ms_total", "counter",
                 "Wall time inside finished spans (milliseconds)"),
                ("metered_count", "telemetry_spans_metered_total", "counter",
                 "Finished spans that metered their thread's CPU time (one "
                 f"trace in {CPU_WEIGHT})"),
                ("cpu_in_millis", "telemetry_span_cpu_ms_total", "counter",
                 f"Thread CPU time inside the metered spans, x {CPU_WEIGHT} "
                 "(milliseconds)"),
                # a sum, yet no counter: the CPU clock may count in ticks,
                # and a span in which one lands takes its share back
                ("off_cpu_in_millis", "telemetry_span_off_cpu_ms", "gauge",
                 "Wall time less thread CPU time inside the metered spans, "
                 f"x {CPU_WEIGHT} (milliseconds)")):
            lines += series(metric, kind, doc,
                            [(labels, t[key]) for labels, t in by_name])
        lines += series(
            "telemetry_span_part_time_ms_total", "counter",
            "Wall time inside a named part of finished spans (milliseconds)",
            [(f'{{span="{name}",part="{part}"}}', p["time_in_millis"])  # label-ok: span and part names are code-level literals
             for name, t in totals.items()
             for part, p in t.get("parts", {}).items()])
        own = self.stats()
        lines += series("telemetry_tracer_finished_total", "counter",
                        "Spans ended since the tracer was reset",
                        [("", own["finished"])])
        lines += series("telemetry_tracer_ring", "gauge",
                        "Finished spans the ring can hold",
                        [("", own["ring"])])
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        self._finished.clear()
        with self._totals_lock:
            self._totals.clear()


# default latency buckets in milliseconds (upper bounds; +inf implied) —
# the OTel explicit-bucket histogram shape the reference's metrics SPI
# defaults to, shifted down for sub-ms device dispatches
DEFAULT_BUCKETS_MS = (0.5, 1, 2, 5, 10, 25, 50, 100, 250, 500, 1000,
                      2500, 5000, 10000, 30000)


class Counter:
    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value


class Histogram:
    """Fixed-bucket latency histogram with percentile readout.

    Percentiles interpolate within the winning bucket (the Prometheus
    ``histogram_quantile`` estimation), so p50/p99 stay meaningful
    without storing raw samples.
    """

    def __init__(self, name: str, buckets=DEFAULT_BUCKETS_MS):
        self.name = name
        self.buckets = tuple(float(b) for b in buckets)
        self._counts = [0] * (len(self.buckets) + 1)   # last = +inf
        self._count = 0
        self._sum = 0.0
        self._max = 0.0
        self._lock = threading.Lock()

    def observe(self, value_ms: float) -> None:
        value_ms = float(value_ms)
        idx = bisect_left(self.buckets, value_ms)
        with self._lock:
            self._counts[idx] += 1
            self._count += 1
            self._sum += value_ms
            if value_ms > self._max:
                self._max = value_ms

    @property
    def count(self) -> int:
        return self._count

    def percentile(self, q: float) -> float:
        """q in [0, 100]; linear interpolation inside the target bucket."""
        with self._lock:
            counts = list(self._counts)
            total = self._count
            hi = self._max
        if total == 0:
            return 0.0
        rank = (q / 100.0) * total
        cum = 0
        for i, c in enumerate(counts):
            if c == 0:
                continue
            if cum + c >= rank:
                lo = self.buckets[i - 1] if i > 0 else 0.0
                up = self.buckets[i] if i < len(self.buckets) else hi
                # no estimate may exceed the observed maximum (the raw
                # bucket bound can overshoot badly for sparse data)
                up = max(lo, min(up, hi))
                frac = (rank - cum) / c
                return lo + (up - lo) * min(max(frac, 0.0), 1.0)
            cum += c
        return hi

    def bucket_counts(self) -> tuple:
        """Consistent snapshot of the raw histogram state:
        ``(bucket_upper_bounds, per_bucket_counts, count, sum_ms)`` —
        the last count is the +inf overflow bucket.  Both the JSON
        ``stats()`` readout and the Prometheus exposition render from
        THIS, so the two surfaces always report the same data."""
        with self._lock:
            return (self.buckets, list(self._counts), self._count,
                    self._sum)

    def stats(self) -> dict:
        buckets, counts, count, total = self.bucket_counts()
        with self._lock:
            mx = self._max
        out = {"count": count,
               "sum_in_millis": round(total, 3),
               "max_in_millis": round(mx, 3)}
        if count:
            out["avg_in_millis"] = round(total / count, 3)
            out["percentiles"] = {
                "50.0": round(self.percentile(50), 3),
                "90.0": round(self.percentile(90), 3),
                "99.0": round(self.percentile(99), 3)}
            # cumulative buckets (Prometheus ``le`` semantics): the raw
            # data behind the percentile estimates, so dashboards can
            # aggregate histograms across nodes correctly
            cum = 0
            rendered = []
            for le, c in zip(buckets, counts):
                cum += c
                rendered.append({"le": le, "count": cum})
            rendered.append({"le": "+Inf", "count": count})
            out["buckets"] = rendered
        return out


class MetricsRegistry:
    """Named counters + histograms (libs/telemetry MetricsRegistry
    analog).  Instruments are created on first use and live forever —
    matching the reference's register-once semantics."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}
        self._histograms: dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(name, Counter(name))
        return c

    def histogram(self, name: str,
                  buckets=DEFAULT_BUCKETS_MS) -> Histogram:
        h = self._histograms.get(name)
        if h is None:
            with self._lock:
                h = self._histograms.setdefault(name,
                                                Histogram(name, buckets))
        return h

    @contextlib.contextmanager
    def time_ms(self, name: str):
        """Time a block into histogram ``name`` (milliseconds)."""
        t0 = time.monotonic()
        try:
            yield
        finally:
            # pass-through: the metric-name lint enforces the CALLER's
            # literal, not this helper  # metric-name-ok
            self.histogram(name).observe((time.monotonic() - t0) * 1000)

    def stats(self) -> dict:
        with self._lock:
            counters = dict(self._counters)
            histograms = dict(self._histograms)
        return {"counters": {n: c.value
                             for n, c in sorted(counters.items())},
                "histograms": {n: h.stats()
                               for n, h in sorted(histograms.items())}}

    def prometheus_text(self) -> str:
        """Render the full registry in the Prometheus text exposition
        format (version 0.0.4): counters as ``<name>_total``, histograms
        as cumulative ``_bucket{le=...}`` series + ``_sum``/``_count``.
        Dotted metric names map to underscore-separated Prometheus
        names; histogram values are milliseconds (suffix ``_ms``).
        Served by ``GET /_metrics`` — the scrape surface for the same
        data ``_nodes/stats`` ``telemetry`` reports as JSON."""
        import re as _re

        def pn(name: str) -> str:
            return _re.sub(r"[^a-zA-Z0-9_:]", "_", name)

        def num(v: float) -> str:
            return f"{v:.10g}"

        with self._lock:
            counters = sorted(self._counters.items())
            histograms = sorted(self._histograms.items())
        lines = []
        for name, c in counters:
            p = pn(name) + "_total"
            lines.append(f"# HELP {p} Counter [{name}]")
            lines.append(f"# TYPE {p} counter")
            lines.append(f"{p} {c.value}")
        for name, h in histograms:
            p = pn(name)
            if not p.endswith("_ms"):    # unit suffix, never doubled
                p += "_ms"
            buckets, per_bucket, count, total = h.bucket_counts()
            lines.append(f"# HELP {p} Latency histogram [{name}] "
                         "(milliseconds)")
            lines.append(f"# TYPE {p} histogram")
            cum = 0
            for le, n in zip(buckets, per_bucket):
                cum += n
                lines.append(f'{p}_bucket{{le="{num(le)}"}} {cum}')  # label-ok: le values are the fixed code-level bucket bounds
            lines.append(f'{p}_bucket{{le="+Inf"}} {count}')  # label-ok: constant +Inf bound
            lines.append(f"{p}_sum {num(total)}")
            lines.append(f"{p}_count {count}")
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._histograms.clear()


class FlightRecorder:
    """Bounded ring of diagnostic captures taken the moment something
    already went wrong — a search slow-log threshold tripped, or a soak
    SLO breached (testing/workload.py attaches the capture to the
    breach verdict).  Each capture snapshots the recent finished spans
    and the counter registry, plus the trigger's own detail (slow query
    source, the slow query's profile when it ran with ``profile:true``,
    the breached SLO's limit/observed pair) — so a breach verdict ships
    with diagnosable evidence instead of a bare boolean.

    Always-on and cheap at steady state: recording only happens on
    trigger, the ring is bounded, and reads (``GET
    /_nodes/flight_recorder``) copy snapshots, never live state.
    """

    def __init__(self, max_captures: int = 32, span_limit: int = 64):
        self._ring: "deque[dict]" = deque(maxlen=max_captures)
        self._lock = threading.Lock()
        self.span_limit = int(span_limit)

    def record(self, trigger: str, reason: str,
               detail: Optional[dict] = None) -> dict:
        capture = {
            "trigger": trigger,
            "reason": reason,
            "timestamp_in_millis": int(time.time() * 1000),  # wall-clock
            "spans": tracer().recent(self.span_limit),
            "counters": dict(metrics().stats()["counters"]),
        }
        if detail:
            capture["detail"] = detail
        with self._lock:
            self._ring.append(capture)
        metrics().counter("flight_recorder.captures").inc()
        return capture

    def captures(self, limit: int = 32) -> list[dict]:
        """Most recent captures, newest first."""
        with self._lock:
            out = list(self._ring)
        out.reverse()
        return out[: max(0, int(limit))]

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()


class GcTimer:
    """A ``gc.callbacks`` hook: how many collections ran and how long
    they took (the reference's ``jvm.gc.collectors.*`` pair).  A
    collection stops every thread, so its time is a pause some request
    saw.  The collector calls the hook holding the interpreter lock and
    never nests collections: plain attributes are enough."""

    def __init__(self):
        self.count = 0
        self.nanos = 0
        self._started = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._started = time.monotonic_ns()
        elif self._started is not None:
            self.nanos += time.monotonic_ns() - self._started
            self.count += 1
            self._started = None

    def install(self) -> None:
        """Once per process, however many nodes it starts."""
        if self not in gc.callbacks:
            gc.callbacks.append(self)

    def stats(self) -> dict:
        return {"collection_count": self.count,
                "collection_time_in_millis": self.nanos / 1e6}


# -- process-wide defaults (the breaker_service() singleton pattern) -----
#
# Multi-node-in-one-process tests share these; spans carry a ``node``
# attribute where the owning node matters.

_tracer = Tracer()
_metrics = MetricsRegistry()
_flight_recorder = FlightRecorder()
_gc_timer = GcTimer()


def tracer() -> Tracer:
    return _tracer


def metrics() -> MetricsRegistry:
    return _metrics


def flight_recorder() -> FlightRecorder:
    return _flight_recorder


def gc_timer() -> GcTimer:
    return _gc_timer
