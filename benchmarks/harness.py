"""One run of one cell: set-up from the seed, the measured window, the
comparison with the plain reference, and the result line.

A cell joins a configuration (``configs/<config>.json``, whose ``kind``
picks the module of ``kinds/``, with its plain reference beside it), a
traffic mix (``traffic/<traffic>.json``) and the metrics that list it;
everything is found by the names in ``BENCHMARK.json``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

import numpy as np

from benchmarks import compare, readers, trace, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 4.0
REQUEST_TIMEOUT_S = 60.0


class NoChip(RuntimeError):
    """jax found no TPU, or fewer chips than the cell asks for."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# -- the cell, from data ------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    bench: dict              # BENCHMARK.json
    cfg: dict
    mix: dict
    kind: object             # module of benchmarks/kinds
    reference: object        # module beside the configuration's file

    def metrics(self, group: str) -> list:
        """The entries of ``end_to_end`` / ``per_layer`` this cell
        reports."""
        mine = [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]
        if group == "end_to_end":
            return mine
        # without a list a per-layer metric follows the metric it moves
        moved = {m["name"] for m in mine}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]


def _json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_reference(cfg_file: str):
    path = os.path.splitext(cfg_file)[0] + ".reference.py"
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + os.path.basename(path).split(".")[0].replace(
            "-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload [{name}] in BENCHMARK.json: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg_file = next(os.path.join(root, c["file"]) for c in bench["configs"]
                    if c["name"] == w["config"])
    cfg = _json(cfg_file)
    mix = _json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    kind = importlib.import_module(f"benchmarks.kinds.{cfg['kind']}")
    return Cell(name, int(w["chips"]), bench, cfg, mix, kind,
                load_reference(cfg_file))


def metric_spec(name: str) -> dict:
    return _json(os.path.join(HERE, "metrics", name + ".json"))


# -- the device ---------------------------------------------------------------

def find_chip(chips: int) -> dict:
    import opensearch_tpu.common.jaxenv  # noqa: F401  x64 + compile cache
    import jax

    devices = jax.devices()
    if jax.default_backend() != "tpu" or len(devices) < chips:
        raise NoChip(f"platform {jax.default_backend()}, {len(devices)} "
                     f"device(s); the cell needs {chips} TPU chip(s)")
    return device_info()


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class CompileCounter:
    """Executables jax got while this process ran (compiled, or loaded
    from the persistent cache), and how many of them the cache lacked."""

    GOT = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self.programs = 0
        self.seconds = 0.0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_kw) -> None:
        if event == self.GOT:
            self.programs += 1
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.MISS:
            self.misses += 1


# -- the node -----------------------------------------------------------------

class Served:
    """The node under test with one index installed from the seed."""

    def __init__(self, cell: Cell, data, platform: str):
        from opensearch_tpu.client import OpenSearch
        from opensearch_tpu.node import Node

        self.cell, self.platform = cell, platform
        self.index = cell.cfg["index"]
        self.data_path = tempfile.mkdtemp(prefix="bench_node_")
        self.node = Node(self.data_path, host="127.0.0.1", port=0).start()
        self.client = OpenSearch([f"http://127.0.0.1:{self.node.port}"],
                                 timeout=REQUEST_TIMEOUT_S)
        settings = cell.cfg.get("cluster_settings")
        if settings:
            self.client.cluster.put_settings({"transient": settings})
        self.client.indices.create(self.index, cell.kind.index_body(cell.cfg))
        cell.kind.install(self.node, self.index, cell.cfg, data)
        self.client.indices.refresh(self.index)
        stats = self.client.transport.perform_request(
            "GET", f"/{self.index}/_stats")["indices"][self.index]["total"]
        if (stats["docs"]["count"] != cell.cfg["n_docs"]
                or stats["segments"]["count"] != cell.cfg["segments"]):
            raise RuntimeError(f"installed {stats['docs']} docs in "
                               f"{stats['segments']} segments")

    def search(self, body: dict) -> dict:
        return self.client.search(index=self.index, body=body)

    def stats(self) -> dict:
        nodes = self.client.nodes.stats()["nodes"]
        return next(iter(nodes.values()))

    def spans(self) -> list:
        resp = self.client.transport.perform_request(
            "GET", "/_nodes/trace", params={"size": 4096})
        return next(iter(resp["nodes"].values()))["spans"]

    def device_faults(self) -> tuple:
        """(count, lines): anything that shows the host answered for the
        device (``chip_smoke.py``'s ``assert_device_clean``, counted)."""
        dev = self.stats()["device"]
        count, lines = 0, []
        for kind, b in dev["health"]["breakers"].items():
            if b["failures"] or b["trips"]:
                count += b["failures"] + b["trips"]
                lines.append(f"breaker [{kind}]: failures={b['failures']} "
                             f"trips={b['trips']} "
                             f"last_error={b.get('last_error')}")
        if dev["health"]["poisoned_results"]:
            count += dev["health"]["poisoned_results"]
            lines.append(f"poisoned_results="
                         f"{dev['health']['poisoned_results']}")
        if dev["budget"]["host_fallbacks"]:
            count += dev["budget"]["host_fallbacks"]
            lines.append(f"host_fallbacks={dev['budget']['host_fallbacks']}")
        if dev["backend"].get("platform") != self.platform:
            count += 1
            lines.append(f"backend={dev['backend']}")
        return count, lines

    def close(self) -> None:
        self.node.stop()
        shutil.rmtree(self.data_path, ignore_errors=True)


def served_on_the_device(resp: dict) -> None:
    """Raises unless every shard of a profiled response ran on the device
    (a shard that names no path ran there)."""
    shards = (resp.get("profile") or {}).get("shards")
    if not shards:
        raise RuntimeError("the profiled request came back with no shard's "
                           "profile")
    for n, shard in enumerate(shards):
        path = (shard.get("engine") or {}).get("execution_path", "device")
        if path != "device":
            raise RuntimeError(f"profiled request ran on [{path}] in shard "
                               f"{n} of {len(shards)}")


def warm_programs(cell: Cell, served: Served, data) -> int:
    """One crafted request per program the configuration can need, the
    first one profiled to see that the device serves every shard."""
    crafted = cell.kind.warmup_queries(cell.cfg, data)
    for n, (_sig, q) in enumerate(crafted):
        body = cell.kind.body(cell.cfg, q)
        if n == 0:
            body["profile"] = True
        resp = served.search(body)
        if not compare.usable(resp):
            raise RuntimeError(f"warm-up request degraded: {resp}")
        if n == 0:
            served_on_the_device(resp)
    return len(crafted)


# -- the traced seconds -----------------------------------------------------

class Tracing:
    """A profiler trace of a steady few seconds inside the window, taken
    by a thread of its own; two markers bracket what counts."""

    def __init__(self, delay_s: float, seconds: float, read_spans):
        self.delay_s, self.seconds = delay_s, seconds
        self.read_spans = read_spans
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t0 = self.t1 = None         # monotonic, at the markers
        self.spans = []                  # the program's, of those seconds
        self.error = None
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self.delay_s)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(trace.MARK_BEGIN):
                pass
            time.sleep(self.seconds)
            with jax.profiler.TraceAnnotation(trace.MARK_END):
                pass
            self.t1 = time.monotonic()
            # the program's ring holds 2,048 spans, a few seconds' worth:
            # read it now, not when the window has closed
            wall0 = (time.time() - (self.t1 - self.t0)) * 1e3
            self.spans = [s for s in self.read_spans()
                          if s["start_time_in_millis"] >= wall0]
            jax.profiler.stop_trace()
        except Exception as exc:   # read by the main thread after join
            self.error = exc

    def summary(self) -> dict:
        self.thread.join()
        try:
            if self.error is not None:
                raise self.error
            return trace.reduce(trace.load(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def gap_labeller(tracing: Tracing, summary: dict, spans: list,
                 records: list):
    """What the host was doing in an idle gap: the program's innermost
    span that covers the gap's middle, else whether a request was in
    flight at all.  Spans carry a wall-clock start in whole milliseconds,
    so a gap shorter than that may be given to a neighbour."""
    mono_minus_wall = time.monotonic() - time.time()
    timed = [(s["start_time_in_millis"] / 1e3 + mono_minus_wall,
              s["duration_in_nanos"] / 1e9, s["name"]) for s in spans
             if s.get("duration_in_nanos") is not None]
    flights = [(r.sent, r.done) for r in records]

    def label(a_ns: float, b_ns: float) -> str:
        mid = tracing.t0 + ((a_ns + b_ns) / 2 - summary["t0_ns"]) / 1e9
        cover = [(dur, name) for start, dur, name in timed
                 if start <= mid <= start + dur]
        if cover:
            return "in span " + min(cover)[1]
        if any(a <= mid <= b for a, b in flights):
            return "request in flight, outside the program's spans"
        return "no request in flight"

    return label


# -- one run ------------------------------------------------------------------

def percentile(values: list, q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def client_numbers(records: list, t_end: float, seconds: float) -> dict:
    """What the load generator measured itself.  A request that failed
    is given the window's length as its latency."""
    lat, service, lag = [], [], []
    completed = 0
    for r in records:
        ok = compare.usable(r.resp)
        lat.append((r.done - r.due) * 1e3 if ok else seconds * 1e3)
        if ok:
            service.append((r.done - r.sent) * 1e3)
            completed += r.done <= t_end
        lag.append((r.sent - r.due) * 1e3)
    out = {"completed": completed,
           "failed": sum(not compare.usable(r.resp) for r in records)}
    if records:
        out.update(latency_mean_ms=statistics.fmean(lat),
                   latency_p50_ms=percentile(lat, 50),
                   latency_p95_ms=percentile(lat, 95),
                   sched_lag_ms=statistics.fmean(lag))
    if service:
        out["service_mean_ms"] = statistics.fmean(service)
    out["qps"] = completed / seconds
    return out


def pick_compared(records: list, limit: int, seed: int) -> list:
    """The answered requests that are compared: all of them, or a sample
    drawn from the seed."""
    answered = [r for r in records if compare.usable(r.resp)]
    if len(answered) <= limit:
        return answered
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
    keep = rng.choice(len(answered), size=limit, replace=False)
    return [answered[i] for i in sorted(keep)]


class Session:
    """A node set up from the seed and warmed, ready for windows."""

    def __init__(self, cell: Cell, seed: int, device: dict, tamper=None):
        cfg, kind = cell.cfg, cell.kind
        self.cell, self.seed, self.device = cell, seed, device
        self.counter = CompileCounter()
        t0 = time.monotonic()
        self.data = kind.generate(cfg, seed)
        self.queries = kind.queries(cfg, self.data, seed)
        t_data = time.monotonic()
        self.served = served = Served(cell, self.data, device["platform"])
        try:
            t_install = time.monotonic()
            n_warm = warm_programs(cell, served, self.data)
            faults, lines = served.device_faults()
            if faults:
                raise RuntimeError("the device did not do the work in "
                                   f"set-up: {lines}")
            t_warm = time.monotonic()
            bodies = [kind.body(cfg, q) for q in self.queries]

            def send(qi: int) -> dict:
                resp = served.search(bodies[qi])
                return tamper(qi, resp) if tamper is not None else resp

            self.send = send
            # the cell's own traffic for a few seconds, on queries from
            # the list's end; the windows take theirs from its start
            n_tail = max(1, len(self.queries) // 8)
            self.head = list(range(len(self.queries) - n_tail))
            self.cursor = 0              # where the next window starts
            tail = list(range(len(self.queries) - n_tail, len(self.queries)))
            traffic.run(cell.mix, send, tail, float(cell.mix["warmup_s"]))
        except BaseException:
            served.close()
            raise
        self.programs_setup = self.counter.programs
        self.misses_setup = self.counter.misses
        # the process holds millions of set-up objects (a doc id per
        # passage): park them, so that the window's collections scan the
        # window's garbage only.  The collector stays on.
        gc.collect()
        gc.freeze()
        say(f"set-up: data {t_data - t0:.1f}s, install "
            f"{t_install - t_data:.1f}s, {n_warm} crafted warm-up requests "
            f"{t_warm - t_install:.1f}s, traffic warm-up "
            f"{cell.mix['warmup_s']}s; programs got {self.programs_setup} "
            f"({self.counter.seconds:.1f}s), compile-cache misses "
            f"{self.misses_setup}")

    def window(self, seconds: float, traced: bool = False,
               mix: dict | None = None) -> dict:
        """Drive ``mix`` (the cell's own by default) for ``seconds``."""
        served, counter = self.served, self.counter
        programs0, misses0 = counter.programs, counter.misses
        stats0 = served.stats()
        tracing = None
        if traced:
            tracing = Tracing(min(seconds * 0.3, 10.0),
                              min(TRACE_SECONDS, seconds * 0.4),
                              served.spans)
            tracing.thread.start()
        # a second window goes on where the first stopped (with room for
        # clients that ran ahead): the program keeps what it prepared for
        # a query, and a query sent twice would be served from that
        order = self.head[self.cursor:] + self.head[:self.cursor]
        records, _t_start, t_end = traffic.run(
            mix or self.cell.mix, self.send, order, seconds)
        self.cursor = (self.cursor + len(records) * 21 // 20 + 64) % len(
            self.head)
        out = {"records": records, "t_end": t_end,
               "tracing": tracing, "stats0": stats0,
               "programs": counter.programs - programs0,
               "misses": counter.misses - misses0}
        out["stats1"] = served.stats()
        out["trace"] = tracing.summary() if tracing is not None else {}
        out["spans"] = tracing.spans if tracing is not None else []
        out["nums"] = client_numbers(records, t_end, seconds)
        out["nums"]["compiles_in_window"] = out["programs"]
        wrapped = len(records) > len(self.head)
        say(f"window: {len(records)} requests"
            f"{' (the query list wrapped)' if wrapped else ''}, "
            f"{out['programs']} programs got, {out['misses']} "
            f"compile-cache misses")
        return out

    def close(self) -> None:
        self.served.close()


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             t_process: float, device: dict, tamper=None) -> dict:
    """The whole run; returns the result line as a dict.  ``tamper`` (tests
    only) alters a response where the client receives it."""
    cfg = cell.cfg
    session = Session(cell, seed, device, tamper)
    try:
        setup_s = time.monotonic() - t_process
        win = session.window(seconds, traced)
        peak = memory_peak_bytes()
        faults, fault_lines = session.served.device_faults()
    finally:
        session.close()
    for line in fault_lines:
        say(f"DEVICE VIOLATION: {line}")
    say(f"set-up took {setup_s:.1f}s from the start of the process")
    records, nums, queries = win["records"], win["nums"], session.queries

    # the comparison, once the window has closed and the node is gone
    t_cmp = time.monotonic()
    chosen = pick_compared(records, int(cfg["compare_max"]), seed)
    reference = cell.reference.Reference(cfg, session.data)
    numbers = compare.compare(
        reference, [queries[r.qi] for r in chosen],
        [compare.hit_rows(r.resp) for r in chosen], cfg["k"])
    numbers["failed"] = nums["failed"]
    numbers["device_faults"] = faults
    correct, lines = compare.verdict(numbers, cfg["limits"])
    correct = correct and bool(chosen)
    say(f"compared {len(chosen)} of {len(records)} responses in "
        f"{time.monotonic() - t_cmp:.1f}s")

    result = {"correct": correct, "attempted": len(records),
              "failed": nums["failed"], "metrics": {},
              "device": dict(device, memory_peak_bytes=peak)}
    nums["setup_s"] = setup_s
    if not traced:
        for m in cell.metrics("end_to_end"):
            result["metrics"][m["name"]] = {"value": nums[m["name"]],
                                            "unit": m["unit"]}
    else:
        tracing, summary = win["tracing"], win["trace"]
        inside = [r for r in records if tracing.t0 is not None
                  and r.sent >= tracing.t0 and r.done <= tracing.t1
                  and compare.usable(r.resp)]
        if inside:      # beside the spans of the same seconds
            nums["service_traced_ms"] = statistics.fmean(
                (r.done - r.sent) * 1e3 for r in inside)
        ctx = {"stats0": win["stats0"], "stats1": win["stats1"],
               "spans": win["spans"], "completed": nums["completed"],
               "client": nums, "loop": cell.mix["loop"], "trace": summary,
               "trace_queries": [queries[r.qi] for r in inside],
               "kind": cell.kind, "cfg": cfg,
               "data": session.data, "device_kind": device["kind"]}
        for m in cell.metrics("per_layer"):
            value = readers.read(metric_spec(m["name"]), ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if summary:
            say(f"trace: {summary['devices']} chip(s) busy "
                f"{summary['busy_s']:.4f}s each in the mean, "
                f"{summary['busy_any_s']:.4f}s any of them, of "
                f"{summary['window_s']:.4f}s")
            result["device"].update(busy_s=summary["busy_s"],
                                    window_s=summary["window_s"])
            result["breakdown"] = {
                "device_ops": trace.top(summary["ops"]),
                "idle_gaps": trace.gap_breakdown(
                    summary, gap_labeller(tracing, summary, win["spans"],
                                          records))}
    result["compared"] = {
        name: {"value": numbers[name], "limit": cfg["limits"][name]}
        for name in compare.NUMBERS}
    result["compared"]["responses"] = {"value": len(chosen), "limit": 1}
    for line in lines:
        say(f"compared: {line}")
    return result
