"""The reader kinds of the per-layer metrics.  A metric is a file
``benchmarks/metrics/<name>.json`` that names one of these with its
arguments; a reader takes the metric from counters, spans, the client's
own records or the device trace of the traced run, and returns None where
it finds nothing to read (the harness then leaves the metric out).

==================  ======================================================
kind                arguments
==================  ======================================================
``stats_delta``     ``path``: dotted path into the node's ``_nodes/stats``
                    entry; the value after the window minus the value
                    before it.  ``per``: ``"query"`` divides by the
                    requests completed in the window.
``span_mean``       ``span``: the mean duration (ms) of the program's
                    spans of that name still in its ring after the window.
``client``          ``key``: a number the load generator took itself
                    (``latency_mean_ms``, ``service_mean_ms``,
                    ``service_traced_ms``,
                    ``sched_lag_ms``, ``latency_p95_ms``,
                    ``compiles_in_window``).  ``loop``: only for cells of
                    that loop.  ``minus_span``: subtract the mean of that
                    span (the REST edge: the client's clock around a
                    request less the program's outermost span).
``trace_kernel_time``  ``match``: seconds of device operations inside
                    programs whose name contains it (all when empty), as
                    ms; ``per``: ``"query"`` divides by the requests that
                    began and ended inside the traced window.
``trace_idle``      100 * (1 - busy / window) of the traced window.
``roofline_bytes``  ``match`` as above; the least time the chip could take
                    for the work the traced window's requests need (bytes
                    and operations from the configuration kind's
                    ``work_bytes`` / ``work_flops``, peaks from
                    ``peaks.py``) over the device time, in %.
==================  ======================================================
"""

from __future__ import annotations

from benchmarks import peaks, trace


def _dig(obj, path: str):
    """``a.b.c`` into nested dicts; a key may itself hold dots (the
    program's counters are named ``search.queries``), so the longest key
    that is there is taken first."""
    if not path:
        return obj
    if not isinstance(obj, dict):
        return None
    parts = path.split(".")
    for n in range(len(parts), 0, -1):
        key = ".".join(parts[:n])
        if key in obj:
            found = _dig(obj[key], ".".join(parts[n:]))
            if found is not None:
                return found
    return None


def _span_mean_ms(ctx: dict, name: str):
    durs = [s["duration_in_nanos"] for s in ctx["spans"]
            if s["name"] == name and s.get("duration_in_nanos") is not None]
    return sum(durs) / len(durs) / 1e6 if durs else None


def stats_delta(ctx: dict, path: str, per: str = "") -> float | None:
    a, b = _dig(ctx["stats0"], path), _dig(ctx["stats1"], path)
    if a is None or b is None:
        return None
    value = float(b) - float(a)
    if per == "query":
        return value / ctx["completed"] if ctx["completed"] else None
    return value


def span_mean(ctx: dict, span: str) -> float | None:
    return _span_mean_ms(ctx, span)


def client(ctx: dict, key: str, loop: str = "",
           minus_span: str = "") -> float | None:
    if loop and ctx["loop"] != loop:
        return None
    value = ctx["client"].get(key)
    if value is None or not minus_span:
        return value
    inner = _span_mean_ms(ctx, minus_span)
    return None if inner is None else value - inner


def trace_kernel_time(ctx: dict, match: str = "",
                      per: str = "") -> float | None:
    if not ctx["trace"]:
        return None
    ms = trace.kernel_seconds(ctx["trace"], match) * 1e3
    if not ms:
        return None
    if per == "query":
        return ms / len(ctx["trace_queries"]) if ctx["trace_queries"] \
            else None
    return ms


def trace_idle(ctx: dict) -> float | None:
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None


def roofline_bytes(ctx: dict, match: str = "") -> float | None:
    if not ctx["trace"] or not ctx["trace_queries"]:
        return None
    seconds = trace.kernel_seconds(ctx["trace"], match)
    if not seconds:
        return None
    kind, cfg, data = ctx["kind"], ctx["cfg"], ctx["data"]
    least = sum(peaks.least_seconds(ctx["device_kind"],
                                    kind.work_bytes(cfg, data, q),
                                    kind.work_flops(cfg, data, q))
                for q in ctx["trace_queries"])
    return 100.0 * least / seconds


KINDS = {f.__name__: f for f in (stats_delta, span_mean, client,
                                 trace_kernel_time, trace_idle,
                                 roofline_bytes)}


def read(spec: dict, ctx: dict) -> float | None:
    reader = dict(spec["reader"])
    kind = reader.pop("kind")
    if kind not in KINDS:
        raise ValueError(f"unknown reader kind [{kind}]")
    return KINDS[kind](ctx, **reader)
