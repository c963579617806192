"""Per-query phase-attributed profiler (the real Profile API).

Analog of the reference's ``search/profile/query/QueryProfiler`` +
``Profilers`` tree, reshaped for this engine's execution model: Lucene
profiles per-collector callbacks (``next_doc``/``score`` per leaf), but
here a segment is ONE fused XLA program — the observable phases are the
host-side stages around those programs:

    rewrite     query-DSL parse (QueryBuilder.rewrite analog)
    plan_cache  canonicalization + compiled-plan cache lookup
    compile     plan-tree construction (toQuery/Weight build analog)
    prepare     per-(plan, segment) bindings staging (incl. H2D)
    can_match   can-match + block-max pruning decisions per segment
    dispatch    device program launches (and a recovered segment's
                host scoring)
    reduce      host sync + cross-segment top-k merge (collector analog)
    fetch       source materialization, highlight, docvalues

plus *engine attribution* only this stack can report: plan-cache and
prepared-bindings hit/miss, segments pruned vs scanned (and why),
XLA retrace/compile events, host-vs-device execution path, and msearch
batch-coalescing group membership.

Zero-cost contract: a ``QueryProfiler`` exists only when the request
carried ``profile: true``; every other request gets ``NULL_PROFILER``,
whose methods do nothing and read no clock.  The engine calls either at
plan/segment granularity (never per-posting) without asking which it
holds, and profiled execution takes the *same* code path, so hits are
byte-identical with and without profiling (pinned in
tests/test_profile.py).
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

# response-breakdown phase keys, in pipeline order.  ``queue`` is the
# continuous batcher's wait window (search/engine.py): time a member
# spent parked before its group's shared dispatch — it precedes every
# execution phase and is never counted as query work.
PHASES = ("queue", "rewrite", "plan_cache", "compile", "prepare",
          "can_match", "dispatch", "reduce", "fetch")

# phases counted into the query section's time_in_nanos (the collector
# section owns "reduce", the fetch phase is its own response field in
# the reference too — no double-stamping)
_QUERY_PHASES = ("rewrite", "plan_cache", "compile", "prepare",
                 "can_match", "dispatch")

# keep the per-segment decision list bounded — a pathological segment
# count must not balloon the response
_MAX_SEGMENT_RECORDS = 256


def xla_program_count() -> int:
    """Live compiled-program count across the query-path jit entry
    points — a growing count across identical queries means the hot
    path is retracing (the attribution bench.py tracks per phase).

    Delegates to the per-kernel compile registry
    (``common/device_ledger.kernel_registry``), whose version-tolerant
    ``_cache_size`` shim degrades a removed jit introspection to a
    counted ``unavailable`` instead of breaking the profiler."""
    from opensearch_tpu.common.device_ledger import kernel_registry
    return kernel_registry().program_count()


class QueryProfiler:
    """Accumulates monotonic-clock phase timings + engine attribution
    for ONE query execution (or one msearch batch group — members of a
    coalesced group share the group's timings by construction)."""

    __slots__ = ("phases", "counts", "attrs", "segments", "_xla0",
                 "_spent", "_seg")

    def __init__(self):
        self.phases: dict[str, float] = {}       # name -> seconds
        self.counts: dict[str, int] = {}
        self.attrs: dict = {}
        self.segments: list[dict] = []
        self._xla0 = xla_program_count()
        self._spent = 0.0                        # every phase's seconds
        self._seg = ("", 0.0, 0.0)               # (id, start, _spent then)

    # -- timing ------------------------------------------------------------

    def add(self, phase: str, seconds: float, n: int = 1) -> None:
        self.phases[phase] = self.phases.get(phase, 0.0) + seconds
        self.counts[phase] = self.counts.get(phase, 0) + n
        self._spent += seconds

    @contextlib.contextmanager
    def phase(self, name: str):
        """Times the block into ``name``, less what the phases nested in
        it recorded meanwhile (a ``prepare`` inside a ``reduce`` pass):
        the phases stay disjoint."""
        t0, spent0 = time.monotonic(), self._spent
        try:
            yield
        finally:
            self.add(name, max(0.0, time.monotonic() - t0
                               - (self._spent - spent0)))

    # -- attribution -------------------------------------------------------

    def set(self, key: str, value) -> None:
        self.attrs[key] = value

    def inc(self, key: str, n: int = 1) -> None:
        self.attrs[key] = self.attrs.get(key, 0) + n

    # -- per-segment decisions ---------------------------------------------

    def segment(self, seg_id: str) -> "QueryProfiler":
        """Starts one segment's clock and returns the profiler: then
        ``pruned``, or ``decided`` and ``scanned``, record the segment's
        decision and its time, less the phases nested in it
        (``prepare``).  From the start to ``decided`` is the
        ``can_match`` share, from there to ``scanned`` the ``dispatch``
        share; all of a ``pruned`` segment's time is ``can_match``'s."""
        self._seg = (seg_id, time.monotonic(), self._spent)
        return self

    def _seg_elapsed(self) -> float:
        _seg_id, t0, spent0 = self._seg
        return max(0.0, time.monotonic() - t0 - (self._spent - spent0))

    def decided(self) -> None:
        self.add("can_match", self._seg_elapsed())
        self._seg = (self._seg[0], time.monotonic(), self._spent)

    def pruned(self, reason: str) -> None:
        """Skipped without dispatch: ``pruned_can_match`` /
        ``pruned_min_score`` / ``pruned_kth``."""
        self._decided(reason, "can_match")

    def scanned(self) -> None:
        """Dispatched: a device program launched, or the host scorer
        recovered it."""
        self._decided("scanned", "dispatch")

    def _decided(self, decision: str, phase: str) -> None:
        seconds = self._seg_elapsed()
        self.add(phase, seconds)
        if len(self.segments) < _MAX_SEGMENT_RECORDS:
            self.segments.append({"segment": self._seg[0],
                                  "decision": decision,
                                  "time_in_nanos": int(seconds * 1e9)})

    def segment_summary(self, total: int) -> dict:
        counts = {"total": int(total), "scanned": 0,
                  "pruned_can_match": 0, "pruned_min_score": 0,
                  "pruned_kth": 0}
        for rec in self.segments:
            d = rec["decision"]
            counts[d] = counts.get(d, 0) + 1
        reached = sum(v for k, v in counts.items() if k != "total")
        # deadline/cancellation can stop the scan early: the remainder
        # is reported, so scanned + pruned + not_reached == total
        counts["not_reached"] = max(0, int(total) - reached)
        return counts

    # -- rendering ---------------------------------------------------------

    def breakdown(self) -> dict:
        out = {}
        for name in PHASES:
            out[name] = int(self.phases.get(name, 0.0) * 1e9)
            out[f"{name}_count"] = self.counts.get(name, 0)
        return out

    def shard_section(self, index_name: str, shard_id, *,
                      plan_type: str, description: str,
                      total_segments: int,
                      query_json: Optional[dict] = None) -> dict:
        """One ``profile.shards[]`` element in the OpenSearch response
        shape (``shards[].searches[].query[].breakdown`` +
        ``rewrite_time`` + ``collector``), extended with the ``engine``
        attribution block and the per-segment decision list."""
        bd = self.breakdown()
        query_ns = sum(bd[p] for p in _QUERY_PHASES)
        engine = dict(self.attrs)
        engine.setdefault("plan_cache", "miss")
        engine.setdefault("execution_path", "device")
        # profile responses are never served from or stored into the
        # request cache (indices/service.py admission policy) — the
        # attribution states the policy instead of a meaningless miss
        engine.setdefault("request_cache", "bypass")
        engine["xla_compiles"] = max(
            0, xla_program_count() - self._xla0)
        engine["segments"] = self.segment_summary(total_segments)
        section = {
            "id": f"[{index_name}][{shard_id}]",
            "searches": [{
                "query": [{
                    "type": plan_type,
                    "description": description[:200],
                    "time_in_nanos": query_ns,
                    "breakdown": bd,
                    "children": [],
                }],
                "rewrite_time": bd["rewrite"],
                "collector": [{
                    "name": "SimpleTopDocsCollector",
                    "reason": "search_top_hits",
                    "time_in_nanos": bd["reduce"],
                }],
            }],
            "engine": engine,
        }
        if self.segments:
            section["segments"] = list(self.segments)
        return section


class _NullProfiler:
    """``QueryProfiler``'s stand-in for a request without ``profile``:
    every method does nothing and reads no clock."""

    __slots__ = ()
    _PHASE = contextlib.nullcontext()

    def phase(self, name: str) -> contextlib.nullcontext:
        return self._PHASE

    def segment(self, seg_id: str) -> "_NullProfiler":
        return self

    def _nothing(self, *args) -> None:
        pass

    set = inc = decided = pruned = scanned = _nothing


NULL_PROFILER = _NullProfiler()


def describe_plan(plan, bind) -> str:
    """Compact human-readable plan description for the profile response
    (``Query.toString()`` analog) — structural, never echoing document
    data beyond the query's own terms."""
    try:
        return plan.describe(bind)
    except Exception:
        return type(plan).__name__
