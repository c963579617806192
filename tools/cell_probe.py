#!/usr/bin/env python
"""One run of a benchmark cell, as ``benchmarks/run.py`` makes it, that
also keeps what a builder compares by hand:

- a digest of every response by query index (ids, scores, total, max
  score), to show that two commits answer alike;
- with ``--all-metrics 1``, every per-layer metric of the cell's loop
  (``.tput`` closed, ``.lat`` paced) read from the program's spans and
  counters, whatever its ``workloads`` list says: four cells' sets are
  pinned by tests of the benchmark (PERF.md section 7), and the
  program's counters are there all the same;
- the window's delta of ``_nodes/stats`` ``telemetry.spans`` (the
  tracer's per-name totals), per completed request: wall, off-CPU where
  metered, and each part, in ms; beside it (``per_query``) the process's
  CPU, the search tasks' CPU and the two waits before ``http.request``;
- each chip's peak memory (``memory_peak_bytes_by_chip``: the result
  line gives the largest);
- with ``--serial-seconds S``, first a closed loop of one client for S
  seconds after set-up (``serial``: its qps and median latency, the
  one-client point of ``benchmarks/sweep.py``), then the cell's window.

Run it from the root of the checkout to measure (it imports that
checkout's ``benchmarks`` and ``opensearch_tpu``); needs the chip.

    python tools/cell_probe.py --workload msmarco_closed --seed 7 \\
        --trace 1 --all-metrics 1 --out chiprun_out/x --tag change.1
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.getcwd())


def span_split(stats0: dict, stats1: dict, completed: int) -> dict:
    """{span: {count, ms, metered, cpu_ms, off_cpu_ms, parts: {part: ms}}},
    each the window's delta a completed request."""
    def spans(stats):
        return (stats.get("telemetry") or {}).get("spans") or {}

    n = max(completed, 1)
    before, out = spans(stats0), {}
    for name, t1 in spans(stats1).items():
        t0 = before.get(name, {})
        row = {"count": (t1["count"] - t0.get("count", 0)) / n,
               "ms": (t1["time_in_millis"]
                      - t0.get("time_in_millis", 0)) / n}
        if t1["metered_count"]:     # one trace in eight, each x 8
            row["metered"] = (t1["metered_count"]
                              - t0.get("metered_count", 0)) / n
            for key in ("cpu", "off_cpu"):
                row[key + "_ms"] = (t1[key + "_in_millis"]
                                    - t0.get(key + "_in_millis", 0)) / n
        parts0 = t0.get("parts", {})
        for part, p1 in t1.get("parts", {}).items():
            row.setdefault("parts", {})[part] = (
                p1["time_in_millis"]
                - parts0.get(part, {}).get("time_in_millis", 0)) / n
        if row["count"]:
            out[name] = row
    return out


_HIST = "telemetry.histograms.rest.%s.sum_in_millis"
PER_QUERY = {      # name -> path under _nodes/stats
    "process_cpu_ms": "process.cpu.total_in_millis",
    "task_cpu_us": "telemetry.counters.search.cpu_micros",
    "accept_wait_ms": _HIST % "accept_wait_ms",
    "head_read_ms": _HIST % "head_read_ms",
    "gc_ms": "runtime.gc.collection_time_in_millis",
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--all-metrics", type=int, choices=(0, 1), default=0)
    ap.add_argument("--serial-seconds", type=float, default=0.0)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from benchmarks import harness

    digests, stash = {}, {}

    def watch(qi, resp):
        try:
            h = resp["hits"]
            body = json.dumps([[x["_id"], x["_score"]] for x in h["hits"]]
                              + [h.get("total"), h.get("max_score")])
        except Exception as exc:  # noqa: BLE001  an unusable response
            body = f"unusable: {type(exc).__name__}"
        d = hashlib.sha256(body.encode()).hexdigest()[:12]
        if digests.setdefault(qi, d) != d:
            digests[qi] = "DIFFERS-WITHIN-RUN"
        return resp

    window = harness.Session.window

    def keeping(self, *a, **kw):
        if args.serial_seconds and "serial" not in stash:
            one = window(self, args.serial_seconds,
                         mix={"loop": "closed", "clients": 1})["nums"]
            stash["serial"] = {"qps": one["qps"],
                               "p50_ms": one["latency_p50_ms"],
                               "failed": one["failed"]}
        out = window(self, *a, **kw)
        stash.update(stats0=out["stats0"], stats1=out["stats1"],
                     completed=out["nums"]["completed"])
        return out

    harness.Session.window = keeping
    cell = harness.load_cell(args.workload)
    if args.all_metrics:
        suffix = ".tput" if cell.mix["loop"] == "closed" else ".lat"
        bench = json.loads(json.dumps(cell.bench))
        for m in bench["per_layer"]:
            # not a kernel's share of its roofline: that is one cell's
            if (m["name"].endswith(suffix) and "workloads" in m
                    and m["source"] != "device_trace"
                    and cell.name not in m["workloads"]):
                m["workloads"].append(cell.name)
        cell = dataclasses.replace(cell, bench=bench)
    device = harness.find_chip(cell.chips)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS,
                              device=device, tamper=watch)
    import jax

    result["device"]["memory_peak_bytes_by_chip"] = [
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()]
    result.update(tag=args.tag, workload=args.workload, seed=args.seed,
                  traced=args.trace, serial=stash.get("serial"))
    if stash:
        result["span_split"] = span_split(stash["stats0"], stash["stats1"],
                                          stash["completed"])
        result["completed"] = stash["completed"]
        ctx = {"stats0": stash["stats0"], "stats1": stash["stats1"],
               "completed": stash["completed"]}
        result["per_query"] = {
            name: harness.readers.stats_delta(ctx, path, per="query")
            for name, path in PER_QUERY.items()}
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, args.tag + ".json"), "w") as f:
        json.dump({"result": result, "digests": digests}, f)
    slim = {k: v for k, v in result.items()
            if k not in ("breakdown", "span_split")}
    print("RESULT " + json.dumps(slim), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
