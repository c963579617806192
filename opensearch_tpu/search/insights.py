"""Query insights: always-on workload attribution for every search.

Analog of the reference's query-insights plugin (top-N query collection
with latency/cpu/memory rankings) extended with what ROADMAP item 1
actually needs before a continuous batcher can be built or tuned:
per-plan-signature workload statistics.  PR 9's profiler answers "why
was THIS query slow" when a client opts in with ``profile:true``;
this service answers "what is the FLEET doing" for every completed
search/msearch member at negligible cost:

- which canonical plan signatures (the PR-5 ``compiled``-cache key)
  dominate, how often they arrive, and what they cost (latency
  percentiles, task CPU/heap),
- how they executed (host/device/batched/mesh path, plan-cache and
  request-cache hit/miss, segments pruned vs scanned),
- how COALESCABLE the workload is: the fraction of arrivals landing
  within a configurable Δt of the previous arrival of the same
  signature — exactly the sizing input a continuous batcher keyed by
  plan signature needs (GPUSparse-style batch-parallel traversal only
  pays off when concurrent arrivals actually share shapes).

Wiring: execution layers *emit* lightweight records through a
contextvar sink (``collecting()`` installed by the edge that owns the
request — the REST dispatcher, the cluster data-node query-phase
handler, or the bench harness); the edge enriches them (X-Opaque-Id,
task CPU/heap, outcome) and feeds ``QueryInsightsService.record``.
Responses are NEVER mutated, so search responses are byte-identical
with insights enabled or disabled (pinned in tests/test_insights.py).

Bounded + breaker-accounted: the top-N ring and the per-signature
rollup table charge the ``request`` breaker and self-evict under
pressure (the common/cache.py discipline), so insights can stay
always-on without becoming its own memory incident.
"""

from __future__ import annotations

import contextlib
import contextvars
import hashlib
import json
import threading
import time
from collections import deque
from typing import Optional

from opensearch_tpu.common.telemetry import Histogram

# -- emission channel ------------------------------------------------------
#
# The executor runs under whatever edge installed a sink; with no sink
# installed (plain library use) emission is a contextvar read + a None
# check — effectively free, and nothing is retained.

_sink: "contextvars.ContextVar[Optional[list]]" = \
    contextvars.ContextVar("opensearch_tpu_insight_sink", default=None)


def emit(**fields) -> Optional[dict]:
    """Append one insight record to the ambient sink (no-op without
    one).  Returns the record so the emitter may keep annotating it."""
    sink = _sink.get()
    if sink is None:
        return None
    sink.append(fields)
    return fields


class _Unkept(dict):
    """Attribution of a pass no insight record reads (a count, a sort,
    a rescore): a write is dropped and a read is 0, so the executor
    writes it without asking whether anyone keeps it."""

    __slots__ = ()

    def __setitem__(self, key, value) -> None:
        pass

    def __missing__(self, key) -> int:
        return 0


UNKEPT = _Unkept()


def annotate_last(**fields) -> None:
    """Merge fields into the most recently emitted record (used by
    layers above the executor — e.g. the request-cache admission point
    knows hit/miss, the executor does not)."""
    sink = _sink.get()
    if sink:
        sink[-1].update(fields)


@contextlib.contextmanager
def collecting():
    """Install a fresh sink for one request scope; yields the list the
    execution layers emit into."""
    sink: list = []
    token = _sink.set(sink)
    try:
        yield sink
    finally:
        _sink.reset(token)


@contextlib.contextmanager
def suppressed():
    """Mask the ambient sink (inner scatter legs of a search that
    already emits its own single record — the mesh/host fallback
    scatter — must not double-count arrivals)."""
    token = _sink.set(None)
    try:
        yield
    finally:
        _sink.reset(token)


# -- signatures ------------------------------------------------------------

def canonical_query(query_json) -> Optional[str]:
    """The PR-5 plan-cache canonicalization: key order in the body never
    changes the signature.  None for unserializable bodies."""
    try:
        return json.dumps(query_json, sort_keys=True,
                          separators=(",", ":"))
    except (TypeError, ValueError):
        return None


def scored_for_body(body: dict) -> bool:
    """Mirror of the executor's needs_scores derivation (sort without
    _score skips BM25 scoring) so a coordinator computes the SAME plan
    signature a data node stamps (parity pinned in tests)."""
    sort = body.get("sort")
    if sort is None:
        return True
    specs = sort if isinstance(sort, list) else [sort]
    for s in specs:
        field = s if isinstance(s, str) else next(iter(s), None) \
            if isinstance(s, dict) else None
        if field == "_score":
            return True
    return body.get("min_score") is not None


def signature_hash(canonical: Optional[str], scored: bool = True) -> str:
    """Short stable id for a (canonical query, scored) plan key — THE
    bounded label value the Prometheus exposition is allowed to use."""
    if canonical is None:
        return "_unsigned"
    h = hashlib.sha1(
        (canonical + ("|s" if scored else "|u")).encode()).hexdigest()
    return h[:12]


# -- per-signature rollup --------------------------------------------------

_SOURCE_CHARS = 160          # operator-readable source excerpt
_CLIENT_SLOTS = 8            # top X-Opaque-Id values kept per signature


class _SignatureRollup:
    """Aggregate workload statistics for ONE plan signature."""

    __slots__ = ("signature", "source", "scored", "count", "first_ts",
                 "last_ts", "hist", "inter_sum", "inter_min", "inter_n",
                 "coalesced", "paths", "outcomes", "plan_cache_hits",
                 "request_cache_hits", "request_cache_total", "pruned",
                 "scanned", "cpu_nanos", "heap_peak", "clients",
                 "batched_members", "transfer_bytes",
                 "batch_size_sum", "batch_size_max",
                 "queue_wait_ms_sum", "queue_wait_ms_max",
                 "queue_waits")

    def __init__(self, signature: str, source: str, scored: bool,
                 now: float):
        self.signature = signature
        self.source = source
        self.scored = scored
        self.count = 0
        self.first_ts = now
        self.last_ts: Optional[float] = None
        self.hist = Histogram(signature)     # fixed buckets, tiny
        self.inter_sum = 0.0
        self.inter_min: Optional[float] = None
        self.inter_n = 0
        self.coalesced = 0
        self.paths: dict[str, int] = {}
        self.outcomes: dict[str, int] = {}
        self.plan_cache_hits = 0
        self.request_cache_hits = 0
        self.request_cache_total = 0
        self.pruned = 0
        self.scanned = 0
        self.cpu_nanos = 0
        self.heap_peak = 0
        self.clients: dict[str, int] = {}
        self.batched_members = 0
        # host↔device bytes (stage + fetch-back) the device ledger
        # attributed to this signature's executions
        self.transfer_bytes = 0
        # continuous-batcher attribution: realized group sizes of the
        # members this signature contributed, and the queue wait they
        # paid parking for the shared dispatch (search/engine.py)
        self.batch_size_sum = 0
        self.batch_size_max = 0
        self.queue_wait_ms_sum = 0.0
        self.queue_wait_ms_max = 0.0
        self.queue_waits = 0

    def add(self, rec: dict, now: float, coalesce_window_s: float) -> None:
        self.count += 1
        self.hist.observe(float(rec.get("took_ms", 0.0)))
        if self.last_ts is not None:
            delta = max(0.0, now - self.last_ts)
            self.inter_sum += delta
            self.inter_n += 1
            if self.inter_min is None or delta < self.inter_min:
                self.inter_min = delta
            if delta <= coalesce_window_s:
                self.coalesced += 1
        self.last_ts = now
        path = str(rec.get("execution_path") or "unknown")
        self.paths[path] = self.paths.get(path, 0) + 1
        outcome = str(rec.get("outcome") or "ok")
        self.outcomes[outcome] = self.outcomes.get(outcome, 0) + 1
        if rec.get("plan_cache") == "hit":
            self.plan_cache_hits += 1
        rc = rec.get("request_cache")
        if rc in ("hit", "miss"):
            self.request_cache_total += 1
            if rc == "hit":
                self.request_cache_hits += 1
        self.pruned += int(rec.get("pruned") or 0)
        self.scanned += int(rec.get("scanned") or 0)
        self.transfer_bytes += int(rec.get("transfer_bytes") or 0)
        self.cpu_nanos += int(rec.get("cpu_nanos") or 0)
        self.heap_peak = max(self.heap_peak,
                             int(rec.get("heap_bytes") or 0))
        if rec.get("batched"):
            self.batched_members += 1
            size = int(rec["batched"])
            self.batch_size_sum += size
            if size > self.batch_size_max:
                self.batch_size_max = size
        qw = rec.get("queue_wait_ms")
        if qw is not None:
            qw = float(qw)
            self.queue_waits += 1
            self.queue_wait_ms_sum += qw
            if qw > self.queue_wait_ms_max:
                self.queue_wait_ms_max = qw
        opaque = rec.get("opaque_id")
        if opaque:
            opaque = str(opaque)[:64]
            if opaque in self.clients or len(self.clients) < _CLIENT_SLOTS:
                self.clients[opaque] = self.clients.get(opaque, 0) + 1

    def coalescable_fraction(self) -> float:
        """Fraction of this signature's arrivals that landed within the
        coalesce window of the previous arrival — the continuous
        batcher's per-shape sizing input."""
        return self.coalesced / self.count if self.count else 0.0

    def to_dict(self) -> dict:
        h = self.hist.stats()
        out = {
            "signature": self.signature,
            "source": self.source,
            "scored": self.scored,
            "count": self.count,
            "latency_ms": {
                "avg": h.get("avg_in_millis", 0.0),
                "max": h.get("max_in_millis", 0.0),
                "p50": h.get("percentiles", {}).get("50.0", 0.0),
                "p90": h.get("percentiles", {}).get("90.0", 0.0),
                "p99": h.get("percentiles", {}).get("99.0", 0.0),
            },
            "coalesced": self.coalesced,
            "coalescable_fraction": round(
                self.coalescable_fraction(), 4),
            "execution_paths": dict(self.paths),
            "outcomes": dict(self.outcomes),
            "plan_cache_hits": self.plan_cache_hits,
            "segments": {"pruned": self.pruned, "scanned": self.scanned},
            "cpu_time_in_nanos": self.cpu_nanos,
            "peak_heap_in_bytes": self.heap_peak,
            "batched_members": self.batched_members,
            "device_transfer_bytes": self.transfer_bytes,
        }
        if self.batched_members:
            out["batched_group_size"] = {
                "mean": round(self.batch_size_sum
                              / self.batched_members, 3),
                "max": self.batch_size_max,
            }
        if self.queue_waits:
            out["queue_wait_ms"] = {
                "mean": round(self.queue_wait_ms_sum
                              / self.queue_waits, 3),
                "max": round(self.queue_wait_ms_max, 3),
            }
        if self.request_cache_total:
            out["request_cache"] = {
                "hits": self.request_cache_hits,
                "total": self.request_cache_total}
        if self.inter_n:
            out["interarrival_ms"] = {
                "mean": round(self.inter_sum / self.inter_n * 1000, 3),
                "min": round((self.inter_min or 0.0) * 1000, 3)}
        if self.clients:
            out["clients"] = dict(sorted(
                self.clients.items(), key=lambda kv: (-kv[1], kv[0])))
        return out


# -- per-tenant rollup -----------------------------------------------------

_TENANT_SIG_SLOTS = 8        # top plan signatures kept per tenant


class _TenantRollup:
    """Aggregate workload statistics for ONE tenant (X-Opaque-Id) —
    the attribution half of per-tenant QoS: who is sending what, how
    much it costs, and how often it was degraded (429/shed/partial)."""

    __slots__ = ("tenant", "count", "rejected", "took_sum", "took_max",
                 "cpu_nanos", "outcomes", "signatures", "first_ts",
                 "last_ts")

    def __init__(self, tenant: str, now: float):
        self.tenant = tenant
        self.count = 0
        self.rejected = 0          # admission 429s (no plan existed)
        self.took_sum = 0.0
        self.took_max = 0.0
        self.cpu_nanos = 0
        self.outcomes: dict[str, int] = {}
        self.signatures: dict[str, int] = {}
        self.first_ts = now
        self.last_ts = now

    def add(self, sig: str, took_ms: float, cpu_nanos: int,
            outcome: str, now: float) -> None:
        self.count += 1
        self.took_sum += took_ms
        if took_ms > self.took_max:
            self.took_max = took_ms
        self.cpu_nanos += cpu_nanos
        self.outcomes[outcome] = self.outcomes.get(outcome, 0) + 1
        if sig in self.signatures \
                or len(self.signatures) < _TENANT_SIG_SLOTS:
            self.signatures[sig] = self.signatures.get(sig, 0) + 1
        self.last_ts = now

    def to_dict(self) -> dict:
        return {
            "tenant": self.tenant,
            "count": self.count,
            "rejected": self.rejected,
            "latency_ms": {
                "avg": round(self.took_sum / self.count, 3)
                if self.count else 0.0,
                "max": round(self.took_max, 3),
            },
            "cpu_time_in_nanos": self.cpu_nanos,
            "outcomes": dict(self.outcomes),
            "top_signatures": dict(sorted(
                self.signatures.items(),
                key=lambda kv: (-kv[1], kv[0]))),
        }


# -- the service -----------------------------------------------------------

_RECORD_OVERHEAD_BYTES = 400        # per-record bookkeeping estimate
_ROLLUP_OVERHEAD_BYTES = 1200       # per-rollup (histogram + dicts)
_TENANT_OVERHEAD_BYTES = 600        # per-tenant rollup (small dicts)


class QueryInsightsService:
    """Always-on bounded recorder: a sliding-window top-N ring (ranked
    by latency, task CPU, or task heap at read time) plus per-signature
    rollups with latency percentiles, interarrival statistics, and the
    coalescability report.  Injectable clock for deterministic tests;
    ``request``-breaker accounted with self-evict-then-drop under
    pressure."""

    def __init__(self, *, node_id: str = "", top_n: int = 10,
                 window_s: float = 300.0,
                 coalesce_window_ms: float = 10.0,
                 ring_capacity: int = 256, max_signatures: int = 128,
                 max_tenants: int = 64,
                 clock=time.monotonic, breaker: str = "request"):
        self.node_id = node_id
        self.enabled = True
        self.top_n = int(top_n)
        self.window_s = float(window_s)
        self.coalesce_window_ms = float(coalesce_window_ms)
        self.ring_capacity = int(ring_capacity)
        self.max_signatures = int(max_signatures)
        self.max_tenants = int(max_tenants)
        self.clock = clock
        self._breaker_name = breaker
        self._lock = threading.Lock()
        self._ring: "deque[dict]" = deque()
        self._rollups: dict[str, _SignatureRollup] = {}
        self._tenants: dict[str, _TenantRollup] = {}
        self._outcomes: dict[str, int] = {}
        self._ring_bytes = 0
        self._total = 0
        self._coalesced_total = 0
        self._dropped = 0
        self._rejected = 0
        self._evictions = 0

    # -- settings (dynamic, _cluster/settings consumers) -------------------

    def set_enabled(self, v: bool) -> None:
        self.enabled = bool(v)

    def set_top_n(self, n: int) -> None:
        self.top_n = max(1, int(n))

    def set_window_s(self, s: float) -> None:
        self.window_s = max(1.0, float(s))

    def set_coalesce_window_ms(self, ms: float) -> None:
        self.coalesce_window_ms = max(0.0, float(ms))

    # -- breaker plumbing --------------------------------------------------

    def _breaker(self):
        from opensearch_tpu.common.breakers import breaker_service
        return getattr(breaker_service(), self._breaker_name, None)

    def _charge(self, n: int) -> bool:
        """True when the reservation landed; on pressure, evict the
        oldest ring entries once and retry (cache.py's self-evict-then-
        skip), else the caller drops the record."""
        from opensearch_tpu.common.breakers import CircuitBreakingError
        breaker = self._breaker()
        if breaker is None:
            return True
        try:
            breaker.add_estimate(n, label="query_insights")
            return True
        except CircuitBreakingError:
            self._evict_oldest(max(1, len(self._ring) // 4))
            try:
                breaker.add_estimate(n, label="query_insights")
                return True
            except CircuitBreakingError:
                return False

    def _release(self, n: int) -> None:
        breaker = self._breaker()
        if breaker is not None:
            breaker.release(n)

    def _evict_oldest(self, k: int) -> None:
        for _ in range(min(k, len(self._ring))):
            old = self._ring.popleft()
            freed = old.get("_bytes", _RECORD_OVERHEAD_BYTES)
            self._ring_bytes -= freed
            self._release(freed)
            self._evictions += 1

    # -- recording ---------------------------------------------------------

    def record(self, rec: dict, *, opaque_id: Optional[str] = None,
               cpu_nanos: Optional[int] = None,
               heap_bytes: Optional[int] = None,
               outcome: Optional[str] = None) -> None:
        """Ingest one completed search (or msearch member).  ``rec`` is
        an ``emit()`` record: signature (canonical query string or
        None), scored, took_ms, execution_path, plan_cache,
        request_cache, index, pruned, scanned, batched, timed_out."""
        if not self.enabled:
            return
        canonical = rec.get("signature")
        scored = bool(rec.get("scored", True))
        sig = signature_hash(canonical, scored)
        if opaque_id is not None:
            rec.setdefault("opaque_id", opaque_id)
        if cpu_nanos is not None:
            rec["cpu_nanos"] = int(cpu_nanos)
        if heap_bytes is not None:
            rec["heap_bytes"] = int(heap_bytes)
        if outcome is not None:
            rec["outcome"] = outcome
        elif "outcome" not in rec:
            rec["outcome"] = ("timeout" if rec.get("timed_out")
                              else "ok")
        now = self.clock()
        source = (canonical or "<unserializable>")[:_SOURCE_CHARS]
        entry = {
            "signature": sig,
            "source": source,
            "ts": now,
            "took_ms": float(rec.get("took_ms", 0.0)),
            "cpu_nanos": int(rec.get("cpu_nanos") or 0),
            "heap_bytes": int(rec.get("heap_bytes") or 0),
            "execution_path": rec.get("execution_path") or "unknown",
            "plan_cache": rec.get("plan_cache") or "miss",
            "request_cache": rec.get("request_cache") or "none",
            "outcome": rec["outcome"],
            "node": self.node_id,
        }
        if rec.get("index"):
            entry["index"] = rec["index"]
        if rec.get("opaque_id"):
            entry["x_opaque_id"] = str(rec["opaque_id"])[:64]
        if rec.get("batched"):
            entry["batched"] = int(rec["batched"])
        cost = _RECORD_OVERHEAD_BYTES + len(source)
        entry["_bytes"] = cost
        with self._lock:
            if not self._charge(cost):
                self._dropped += 1
                return
            self._ring.append(entry)
            self._ring_bytes += cost
            if len(self._ring) > self.ring_capacity:
                self._evict_oldest(len(self._ring) - self.ring_capacity)
            self._expire(now)
            roll = self._rollups.pop(sig, None)
            if roll is None:
                if not self._charge(_ROLLUP_OVERHEAD_BYTES):
                    self._dropped += 1
                    return
                if len(self._rollups) >= self.max_signatures:
                    # dict insertion order IS the recency order (every
                    # touch below reinserts), so the head is the LRU
                    # victim — O(1), no scan on the hot path
                    victim = next(iter(self._rollups))
                    del self._rollups[victim]
                    self._release(_ROLLUP_OVERHEAD_BYTES)
                    self._evictions += 1
                roll = _SignatureRollup(sig, source, scored, now)
            self._rollups[sig] = roll          # move-to-end on touch
            was_coalesced = roll.coalesced
            roll.add(rec, now, self.coalesce_window_ms / 1000.0)
            self._total += 1
            self._coalesced_total += roll.coalesced - was_coalesced
            self._outcomes[rec["outcome"]] = \
                self._outcomes.get(rec["outcome"], 0) + 1
            tenant = self._tenant_locked(rec.get("opaque_id"), now)
            if tenant is not None:
                tenant.add(sig, float(rec.get("took_ms", 0.0)),
                           int(rec.get("cpu_nanos") or 0),
                           rec["outcome"], now)

    def _tenant_locked(self, opaque_id, now: float):
        """The tenant rollup for this record's X-Opaque-Id (the
        anonymous default pool for unlabeled traffic) — same bounded
        LRU + breaker discipline as the signature rollups.  Caller
        holds the lock; None when the breaker refused the charge."""
        from opensearch_tpu.search.qos import tenant_label
        label = tenant_label(opaque_id)
        roll = self._tenants.pop(label, None)
        if roll is None:
            if not self._charge(_TENANT_OVERHEAD_BYTES):
                return None
            if len(self._tenants) >= self.max_tenants:
                victim = next(iter(self._tenants))
                del self._tenants[victim]
                self._release(_TENANT_OVERHEAD_BYTES)
                self._evictions += 1
            roll = _TenantRollup(label, now)
        self._tenants[label] = roll            # move-to-end on touch
        return roll

    def record_rejected(self, opaque_id: Optional[str] = None) -> None:
        """An admission-gate 429 happened before any plan existed —
        counted (the shed load is workload evidence too) but never a
        ring entry; attributed to the rejected client's tenant."""
        with self._lock:
            self._rejected += 1
            tenant = self._tenant_locked(opaque_id, self.clock())
            if tenant is not None:
                tenant.rejected += 1

    def _expire(self, now: float) -> None:
        cutoff = now - self.window_s
        while self._ring and self._ring[0]["ts"] < cutoff:
            self._evict_oldest(1)

    # -- readout -----------------------------------------------------------

    _RANKS = {"latency": "took_ms", "cpu": "cpu_nanos",
              "heap": "heap_bytes"}

    def top(self, by: str = "latency", n: Optional[int] = None,
            window_s: Optional[float] = None) -> list[dict]:
        """Top-N records in the sliding window ranked by latency / task
        CPU / task heap, newest-first within ties (deterministic)."""
        key = self._RANKS.get(by)
        if key is None:
            from opensearch_tpu.common.errors import IllegalArgumentError
            raise IllegalArgumentError(
                f"unknown top_queries ranking [{by}]; one of "
                f"{sorted(self._RANKS)}")
        n = self.top_n if n is None else max(1, int(n))
        cutoff = self.clock() - (window_s if window_s is not None
                                 else self.window_s)
        with self._lock:
            live = [dict(r) for r in self._ring if r["ts"] >= cutoff]
        for r in live:
            r.pop("_bytes", None)
        live.sort(key=lambda r: (-r[key], -r["ts"], r["signature"]))
        return live[:n]

    def coalescability(self) -> dict:
        """The batcher sizing report: overall fraction of arrivals that
        landed within Δt of the previous same-signature arrival, plus
        the most coalescable signatures."""
        with self._lock:
            total = self._total
            coalesced = self._coalesced_total
            rolls = list(self._rollups.values())
        best = sorted(
            (r for r in rolls if r.count >= 2),
            key=lambda r: (-r.coalescable_fraction(), -r.count,
                           r.signature))[:5]
        return {
            "window_ms": self.coalesce_window_ms,
            "arrivals": total,
            "coalesced": coalesced,
            "coalescable_fraction": round(coalesced / total, 4)
            if total else 0.0,
            "top_signatures": [
                {"signature": r.signature,
                 "count": r.count,
                 "coalescable_fraction": round(
                     r.coalescable_fraction(), 4)}
                for r in best],
        }

    def tenants(self) -> dict:
        """Per-tenant rollups keyed by tenant label (the QoS
        attribution surface: ``?by=tenant``, ``_nodes/stats``, the
        noisy-neighbor soak's evidence)."""
        with self._lock:
            return {label: r.to_dict()
                    for label, r in sorted(self._tenants.items())}

    def tenant_totals(self) -> dict:
        """Compact per-tenant (count, rejected) — the QoS controller's
        cheap per-tick signal."""
        with self._lock:
            return {label: {"count": r.count, "rejected": r.rejected}
                    for label, r in self._tenants.items()}

    def section(self, by: str = "latency",
                n: Optional[int] = None) -> dict:
        """The full per-node insights section (`_insights/top_queries`
        fan-in unit and the flight-recorder snapshot).  ``by=tenant``
        serves the same section with the latency top ranking — the
        per-tenant rollups are always included; any other unknown
        ranking still rejects (400) inside ``top``."""
        rank_by = "latency" if by == "tenant" else by
        with self._lock:
            rollups = {sig: r.to_dict()
                       for sig, r in sorted(self._rollups.items())}
        return {
            "node": self.node_id,
            "enabled": self.enabled,
            "window_s": self.window_s,
            "top_queries": self.top(by=rank_by, n=n),
            "signatures": rollups,
            "tenants": self.tenants(),
            "coalescability": self.coalescability(),
            "totals": self.stats(),
        }

    def stats(self) -> dict:
        """Compact `_nodes/stats` ``query_insights`` block."""
        with self._lock:
            total = self._total
            coalesced = self._coalesced_total
            return {
                "enabled": self.enabled,
                "records": total,
                "ring_size": len(self._ring),
                "ring_bytes": self._ring_bytes,
                "signatures": len(self._rollups),
                "tenants": len(self._tenants),
                "outcomes": dict(self._outcomes),
                "coalesced": coalesced,
                "coalescable_fraction": round(coalesced / total, 4)
                if total else 0.0,
                "rejected": self._rejected,
                "dropped": self._dropped,
                "evictions": self._evictions,
            }

    # -- Prometheus exposition ---------------------------------------------

    @staticmethod
    def _label_value(v: str) -> str:
        """Prometheus label-value escaping.  Every value flowing through
        here is a 12-hex signature hash or a node id — bounded by
        construction (ring/rollup caps), never raw request data; the
        label-cardinality lint (tools/check_prom_labels.py) enforces
        that discipline repo-wide."""
        return (str(v).replace("\\", "\\\\").replace('"', '\\"')
                .replace("\n", "\\n"))

    def prometheus_text(self) -> str:
        """Labeled exposition for the top signatures by count: the
        signature is always a LABEL, never part of the metric name, so
        the metric-name lint's bounded-name invariant holds and
        dashboards can aggregate across signatures."""
        with self._lock:
            rolls = sorted(self._rollups.values(),
                           key=lambda r: (-r.count, r.signature))
            rolls = rolls[: self.top_n]
            node = self._label_value(self.node_id)
        lines = [
            "# HELP opensearch_tpu_insights_signature_queries_total "
            "Completed searches per plan signature",
            "# TYPE opensearch_tpu_insights_signature_queries_total "
            "counter",
        ]
        for r in rolls:
            sig = self._label_value(r.signature)
            lines.append(
                f'opensearch_tpu_insights_signature_queries_total'
                f'{{signature="{sig}",node="{node}"}} {r.count}')  # label-ok: signature hashes via the bounded top-N path
        lines.append(
            "# HELP opensearch_tpu_insights_signature_latency_p99_ms "
            "p99 latency per plan signature (milliseconds)")
        lines.append(
            "# TYPE opensearch_tpu_insights_signature_latency_p99_ms "
            "gauge")
        for r in rolls:
            sig = self._label_value(r.signature)
            p99 = r.hist.percentile(99)
            lines.append(
                f'opensearch_tpu_insights_signature_latency_p99_ms'
                f'{{signature="{sig}",node="{node}"}} {p99:.6g}')  # label-ok: signature hashes via the bounded top-N path
        lines.append(
            "# HELP opensearch_tpu_insights_signature_coalescable_ratio "
            "Fraction of arrivals within the coalesce window")
        lines.append(
            "# TYPE opensearch_tpu_insights_signature_coalescable_ratio "
            "gauge")
        for r in rolls:
            sig = self._label_value(r.signature)
            frac = r.coalescable_fraction()
            lines.append(
                f'opensearch_tpu_insights_signature_coalescable_ratio'
                f'{{signature="{sig}",node="{node}"}} {frac:.6g}')  # label-ok: signature hashes via the bounded top-N path
        # per-tenant attribution: tenant is a LABEL from the bounded
        # (max_tenants, then top-N-by-count) rollup table, never a name
        with self._lock:
            trolls = sorted(self._tenants.values(),
                            key=lambda r: (-r.count, r.tenant))
            trolls = trolls[: self.top_n]
        lines.append(
            "# HELP opensearch_tpu_insights_tenant_queries_total "
            "Completed searches per tenant (X-Opaque-Id)")
        lines.append(
            "# TYPE opensearch_tpu_insights_tenant_queries_total "
            "counter")
        for r in trolls:
            ten = self._label_value(r.tenant)
            lines.append(
                f'opensearch_tpu_insights_tenant_queries_total'
                f'{{tenant="{ten}",node="{node}"}} {r.count}')  # label-ok: bounded tenant rollup slots via the top-N path
        lines.append(
            "# HELP opensearch_tpu_insights_tenant_rejected_total "
            "Admission 429s per tenant (X-Opaque-Id)")
        lines.append(
            "# TYPE opensearch_tpu_insights_tenant_rejected_total "
            "counter")
        for r in trolls:
            ten = self._label_value(r.tenant)
            lines.append(
                f'opensearch_tpu_insights_tenant_rejected_total'
                f'{{tenant="{ten}",node="{node}"}} {r.rejected}')  # label-ok: bounded tenant rollup slots via the top-N path
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        with self._lock:
            self._evict_oldest(len(self._ring))
            for _ in range(len(self._rollups)):
                self._release(_ROLLUP_OVERHEAD_BYTES)
            self._rollups.clear()
            for _ in range(len(self._tenants)):
                self._release(_TENANT_OVERHEAD_BYTES)
            self._tenants.clear()
            self._outcomes.clear()
            self._total = self._coalesced_total = 0
            self._dropped = self._rejected = self._evictions = 0


# -- cluster fan-in merge --------------------------------------------------

def merge_sections(sections: dict[str, dict], *, by: str = "latency",
                   n: int = 10) -> dict:
    """Coordinator-side merge of per-node insights sections into one
    cluster view, provenance-annotated like PR 9's profile merge: every
    merged top entry keeps the node that recorded it, every merged
    signature lists its per-node contributions, and unreachable nodes
    are reported as errors instead of silently dropped.  Deterministic:
    stable sort keys everywhere (rank metric desc, then node asc, then
    signature asc)."""
    rank_key = QueryInsightsService._RANKS.get(by, "took_ms")
    merged_top: list[dict] = []
    merged_sigs: dict[str, dict] = {}
    merged_tenants: dict[str, dict] = {}
    errors: dict[str, str] = {}
    total = coalesced = 0
    for node in sorted(sections):
        sec = sections[node]
        if not isinstance(sec, dict) or "error" in sec:
            errors[node] = (sec or {}).get("error", "unreachable") \
                if isinstance(sec, dict) else "unreachable"
            continue
        for entry in sec.get("top_queries", []):
            entry = dict(entry)
            entry.setdefault("node", node)
            merged_top.append(entry)
        tot = sec.get("totals", {})
        total += int(tot.get("records", 0))
        coalesced += int(tot.get("coalesced", 0))
        for sig, roll in (sec.get("signatures") or {}).items():
            m = merged_sigs.get(sig)
            if m is None:
                m = {"signature": sig, "source": roll.get("source"),
                     "count": 0, "coalesced": 0, "nodes": {}}
                merged_sigs[sig] = m
            m["count"] += int(roll.get("count", 0))
            m["coalesced"] += int(roll.get("coalesced", 0))
            m["nodes"][node] = roll
        for tenant, roll in (sec.get("tenants") or {}).items():
            m = merged_tenants.get(tenant)
            if m is None:
                m = {"tenant": tenant, "count": 0, "rejected": 0,
                     "cpu_time_in_nanos": 0, "outcomes": {},
                     "nodes": {}}
                merged_tenants[tenant] = m
            m["count"] += int(roll.get("count", 0))
            m["rejected"] += int(roll.get("rejected", 0))
            m["cpu_time_in_nanos"] += int(
                roll.get("cpu_time_in_nanos", 0))
            for outcome, c in (roll.get("outcomes") or {}).items():
                m["outcomes"][outcome] = \
                    m["outcomes"].get(outcome, 0) + int(c)
            m["nodes"][node] = roll
    for m in merged_sigs.values():
        m["coalescable_fraction"] = round(
            m["coalesced"] / m["count"], 4) if m["count"] else 0.0
    merged_top.sort(key=lambda r: (-float(r.get(rank_key, 0.0)),
                                   str(r.get("node", "")),
                                   str(r.get("signature", ""))))
    out = {
        "top_queries": merged_top[: max(1, int(n))],
        "signatures": dict(sorted(merged_sigs.items())),
        "tenants": dict(sorted(merged_tenants.items())),
        "coalescability": {
            "arrivals": total,
            "coalesced": coalesced,
            "coalescable_fraction": round(coalesced / total, 4)
            if total else 0.0,
        },
        "nodes": {node: sec for node, sec in sorted(sections.items())
                  if isinstance(sec, dict) and "error" not in sec},
    }
    if errors:
        out["failed_nodes"] = errors
    return out
