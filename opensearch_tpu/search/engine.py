"""One query engine: the single scoring entry every caller routes through.

Before this module, the execution paths were wired separately at each
call site: the sequential per-query path (``ShardSearcher.search``), the
msearch-batched kernel (``search/batch.py``) and the 8-device mesh
(``parallel/dist_search.py``).
Only clients that happened to speak ``_msearch`` reached the batched
kernel; independent REST requests each paid their own XLA dispatch even
when the insights coalescability report said most zipf-head arrivals
land within a coalesce window of an identical-signature predecessor.

Now ``QueryEngine`` is the one entry (``IndexService.search/msearch``,
the cluster data-node query phase, and the mesh router all call it) and
the kernels are backend decisions inside the one lowering pipeline
(parse -> plan cache -> prepare -> kernel choice); the tier-1 lint
``tools/check_execution_paths.py`` keeps it that way — scoring kernels
may only be invoked from the engine's sanctioned lowering sites.  A
scored term bag has ONE lowering on every backend (``plan.run_topk``,
``batch_impact_union_topk``); the host impact-table scorer
(``TermBagPlan.host_topk``) is the recovery from a device fault and the
parity reference, never chosen from the platform.

On top of the unified entry sit the two serving-scale pieces:

- ``ContinuousBatcher`` — inference-serving-style continuous batching
  at the REST edge: concurrent in-flight single searches whose plans
  share a batch group (same field / k family) park for a Δt window
  sized from the measured workload (``search.insights
  .coalesce_window_ms`` — the PR-10 coalescability report's knob) and
  execute as ONE ``batch_impact_union_topk`` dispatch, each caller
  receiving its own response with byte-identical hits.  Non-batchable
  bodies bypass with zero added latency, and a request only ever waits
  when concurrent batchable traffic is actually in flight — serial
  traffic never parks.  Parked members keep holding their REST-edge
  admission permits (the gate wraps the whole handler), so batcher
  occupancy is charged to the existing admission budget and the queue
  cannot become an unbounded buffer under overload; an internal
  ``max_parked`` bound additionally spills late arrivals to the
  sequential path instead of queueing.

- ``SearchThreadpool`` — a bounded pool of explicitly named daemon
  workers that runs the non-coalescable bodies of an ``_msearch`` (its
  sequential fallback) side by side.  Overflow work runs on the
  caller's thread (never queued unboundedly, never deadlocks), and
  ``stop()`` is an idempotent bounded join wired into ``Node.stop()`` /
  ``ClusterNode.stop()``.

Accounting: ``search.batcher.{batched,bypass,window_waits,dispatches}``
metrics, a ``queue`` profiler phase on batched profiled members, and
per-member ``batched`` group size + ``queue_wait_ms`` on the insight
records (rolled up as ``batched_group_size`` per signature).
"""

from __future__ import annotations

import contextvars
import queue
import threading
import time
from typing import Optional

from opensearch_tpu.common.telemetry import metrics as _metrics

# Dynamic settings (search.batcher.*) land on module globals, the same
# idiom as executor.DEFAULT_ALLOW_PARTIAL_RESULTS: Node's
# _cluster/settings consumers write them, the engine reads them per
# request.  BATCHER_WINDOW_MS == 0 means "auto": use the measured
# insights coalesce window (AUTO_WINDOW_MS mirrors the dynamic
# search.insights.coalesce_window_ms setting).
BATCHER_ENABLED = True
BATCHER_WINDOW_MS = 0.0
BATCHER_MAX_BATCH = 64
AUTO_WINDOW_MS = 10.0

# request-body keys the continuous batcher understands; anything else
# (sort, aggs, collapse, rescore, highlight, ...) bypasses to the
# sequential path — strictly narrower than msearch's plan_batches so a
# coalesced response can never differ from the sequential one
_BATCHABLE_KEYS = frozenset({"query", "size", "from", "_source",
                             "profile", "track_total_hits"})


class SearchThreadpool:
    """Bounded, named-daemon-thread worker pool for the engine.

    Workers spawn lazily on first use and respawn after ``stop()`` (the
    pool is process-global; one node stopping must not strand another
    live node's searches).  ``run_all`` preserves submission order and
    runs overflow work inline on the caller's thread, so it can never
    deadlock on its own queue.  Submitted callables run under a copy of
    the caller's context (insight sinks, current task, trace spans all
    propagate).
    """

    def __init__(self, size: Optional[int] = None, queue_cap: int = 256):
        import os
        self.size = int(size or max(2, min(8, os.cpu_count() or 4)))
        self.queue_cap = int(queue_cap)
        self._q: "queue.Queue" = queue.Queue(self.queue_cap)
        self._threads: list[threading.Thread] = []
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._spawned = 0
        self.inline_runs = 0
        self.submitted = 0

    def _ensure_workers(self) -> bool:
        with self._lock:
            self._threads = [t for t in self._threads if t.is_alive()]
            while len(self._threads) < self.size:
                self._spawned += 1
                t = threading.Thread(
                    target=self._worker,
                    name=f"search-engine-{self._spawned}", daemon=True)
                t.start()
                self._threads.append(t)
            return bool(self._threads)

    def _worker(self):
        self._tls.in_worker = True
        while True:
            item = self._q.get()
            if item is None:           # stop sentinel
                return
            fn, ctx, slot = item
            try:
                slot["result"] = ctx.run(fn)
            except BaseException as e:  # noqa: BLE001 — re-raised by waiter
                slot["error"] = e
            finally:
                slot["event"].set()

    def run_all(self, fns: list) -> list:
        """Run callables concurrently; results in submission order.  The
        first raised exception (by submission order) re-raises on the
        caller's thread after every callable finished.

        Called FROM a pool worker, everything runs inline instead:
        nested fan-out (a pooled msearch-fallback search that fans out
        itself) must never park a worker waiting on subtasks only
        another worker can run — with all workers waiting, the queue
        would deadlock."""
        if getattr(self._tls, "in_worker", False):
            self.inline_runs += len(fns)
            return [fn() for fn in fns]
        slots = []
        for fn in fns:
            slot: dict = {"event": threading.Event()}
            ctx = contextvars.copy_context()
            submitted = False
            if self._ensure_workers():
                try:
                    self._q.put_nowait((fn, ctx, slot))
                    self.submitted += 1
                    submitted = True
                except queue.Full:
                    pass
            if not submitted:
                # caller-runs overflow policy: bounded queue + guaranteed
                # progress (and the only behavior once stop() drained us
                # mid-flight)
                self.inline_runs += 1
                try:
                    slot["result"] = ctx.run(fn)
                except BaseException as e:  # noqa: BLE001
                    slot["error"] = e
                slot["event"].set()
            slots.append(slot)
        for slot in slots:
            slot["event"].wait()
        for slot in slots:
            if "error" in slot:
                raise slot["error"]
        return [slot["result"] for slot in slots]

    def stop(self, timeout: float = 5.0):
        """Idempotent bounded join: sends one sentinel per live worker
        and joins each against a shared deadline.  Safe without any
        prior use; a later ``run_all`` simply respawns workers."""
        with self._lock:
            threads, self._threads = self._threads, []
        for _ in threads:
            self._q.put(None)
        deadline = time.monotonic() + timeout
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))

    def stats(self) -> dict:
        with self._lock:
            alive = sum(1 for t in self._threads if t.is_alive())
        return {"threads": alive, "size": self.size,
                "submitted": self.submitted,
                "inline_runs": self.inline_runs}


class _Member:
    """One parked search inside an open batch group."""

    __slots__ = ("body", "bind", "event", "rows", "total", "max_score",
                 "error", "group_size", "wait_s", "stats", "path",
                 "gprof")

    def __init__(self, body: dict, bind: dict):
        self.body = body
        self.bind = bind
        self.event = threading.Event()
        self.rows = None
        self.total = 0
        self.max_score = None
        self.error: Optional[BaseException] = None
        self.group_size = 1
        self.wait_s = 0.0
        self.stats = {"pruned": 0, "scanned": 0}
        self.path = "device_batched"
        self.gprof = None


class _OpenGroup:
    __slots__ = ("key", "members", "sealed")

    def __init__(self, key):
        self.key = key
        self.members: list[_Member] = []
        self.sealed = False


class ContinuousBatcher:
    """Coalesce concurrent identical-shape searches into shared batch
    dispatches (module docstring).  Leader-driven: the first member of a
    group waits out the Δt window on its own request thread, then runs
    the whole group as one ``BatchGroup`` dispatch — no dedicated
    batcher thread exists, so there is nothing to leak or hang on
    shutdown.  Followers park on an event; every member renders its own
    response (and emits its own insight record) back on its own thread.
    """

    # backstop for follower waits: window + group execution; a leader
    # death (should be impossible — errors propagate to members) makes
    # the follower fall back to the sequential path instead of hanging
    FOLLOWER_TIMEOUT_S = 60.0

    def __init__(self):
        self._cond = threading.Condition()
        self._groups: dict[tuple, _OpenGroup] = {}
        self._active = 0           # in-flight batchable searches
        self._parked = 0
        self.max_parked = 256

    # -- sizing ------------------------------------------------------------

    @staticmethod
    def effective_window_s() -> float:
        w = BATCHER_WINDOW_MS if BATCHER_WINDOW_MS > 0 else AUTO_WINDOW_MS
        return max(0.0, float(w)) / 1000.0

    @staticmethod
    def simulate_occupancy(arrivals: list, window_s: float) -> float:
        """Deterministic replay of the grouping rule over ``(t,
        signature)`` arrival tuples: an arrival joins the open group of
        its signature when it lands within ``window_s`` of that group's
        LEADER, else it starts a new group.  Returns mean realized
        batch occupancy (arrivals per group) — the quantity the
        insights coalescability report predicts (its chain rule coalesces
        within-window successors, so it upper-bounds this)."""
        open_leader: dict = {}
        groups = 0
        for t, sig in sorted(arrivals):
            lead = open_leader.get(sig)
            if lead is not None and t - lead <= window_s:
                continue
            open_leader[sig] = t
            groups += 1
        return len(arrivals) / groups if groups else 0.0

    # -- admission ---------------------------------------------------------

    @staticmethod
    def _batchable(searcher, body: dict):
        """(plan, bind, k) when the body can take the batched kernel
        with response semantics identical to the sequential path, else
        None.  Narrower than msearch's plan_batches: only the keys the
        batch path fully reproduces are allowed (track_total_hits:false
        is excluded because sequential k-th pruning may legally return
        lower-bound totals there).

        The plan comes from a PEEK at the searcher's compiled-plan
        cache — never a compile: a first-seen shape runs the sequential
        path (which compiles it, with exact plan-cache miss
        attribution) and becomes batchable from its second arrival on.
        The zipf head the batcher amortizes is by definition the
        already-cached shapes."""
        import json as _json

        from opensearch_tpu.search import plan as P

        if set(body) - _BATCHABLE_KEYS:
            return None
        if int(body.get("from", 0) or 0) != 0:
            return None
        if body.get("track_total_hits") is False:
            return None
        k = int(body.get("size", 10) if body.get("size") is not None
                else 10)
        if k <= 0 or not searcher.segments:
            return None
        cache = getattr(searcher, "_plan_cache", None)
        if cache is None:
            return None
        try:
            ckey = (_json.dumps(body.get("query"), sort_keys=True,
                                separators=(",", ":")), True)
        except (TypeError, ValueError):
            return None
        out = cache.get(ckey)
        if out is None:
            return None
        plan, bind = out
        if not isinstance(plan, P.TermBagPlan) or not plan.bm25_scored:
            return None
        return plan, bind, k

    # -- execution ---------------------------------------------------------

    def execute(self, searcher, body: dict) -> Optional[dict]:
        """Serve one single-search body through the batcher, or return
        None to bypass (non-batchable).  A batchable body that finds no
        companions runs the plain sequential pipeline HERE, inside the
        in-flight count — that live count is the concurrency evidence a
        later arrival uses to decide the window wait is worth paying."""
        parsed = self._batchable(searcher, body)
        if parsed is None:
            _metrics().counter("search.batcher.bypass").inc()
            return None
        plan, bind, k = parsed
        t0 = time.monotonic()
        with self._cond:
            self._active += 1
        try:
            resp = self._coalesce(searcher, body, plan, bind, k, t0)
            if resp is not None:
                return resp
            # solo: no concurrent batchable traffic — zero added
            # latency, same sequential pipeline as ever
            return searcher.search(body)
        finally:
            with self._cond:
                self._active -= 1

    def _coalesce(self, searcher, body, plan, bind, k,
                  t0: float) -> Optional[dict]:
        key = (id(searcher), plan.field, k)
        member = _Member(body, bind)
        window = self.effective_window_s()
        with self._cond:
            g = self._groups.get(key)
            if g is not None and not g.sealed \
                    and len(g.members) < BATCHER_MAX_BATCH \
                    and self._parked < self.max_parked:
                g.members.append(member)
                self._parked += 1
                if len(g.members) >= BATCHER_MAX_BATCH:
                    g.sealed = True
                    self._groups.pop(key, None)
                    self._cond.notify_all()
                follower = True
            else:
                # no joinable group: this request leads.  It only parks
                # (and pays the window) when concurrent batchable
                # traffic exists RIGHT NOW — serial traffic sees
                # _active == 1 and proceeds with zero added latency.
                follower = False
                concurrent = (self._active > 1 or self._parked > 0)
                if not (concurrent and window > 0
                        and self._parked < self.max_parked):
                    return None            # solo: sequential path
                g = _OpenGroup(key)
                g.members.append(member)
                self._groups[key] = g
        if follower:
            if not member.event.wait(window + self.FOLLOWER_TIMEOUT_S):
                return None        # leader vanished: degrade, don't hang
            if member.error is not None:
                raise member.error
            member.wait_s = time.monotonic() - t0
            return self._render(searcher, member, t0)
        # leader: wait out the window (a max_batch seal wakes us early),
        # then run the whole group on this thread
        _metrics().counter("search.batcher.window_waits").inc()
        deadline = t0 + window
        with self._cond:
            while not g.sealed:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cond.wait(remaining)
            g.sealed = True
            self._groups.pop(key, None)
            members = list(g.members)
            self._parked -= max(0, len(members) - 1)
        member.wait_s = time.monotonic() - t0
        if len(members) == 1:
            # nobody arrived: don't pay the batch kernel's padding for a
            # group of one — the sequential path serves it
            return None
        try:
            self._run_group(searcher, plan.field, k, members)
        except BaseException as e:     # noqa: BLE001 — fan the error out
            for m in members:
                m.error = e
                m.event.set()
            raise
        for m in members:
            m.event.set()
        return self._render(searcher, member, t0)

    def _run_group(self, searcher, field: str, k: int,
                   members: list[_Member]):
        """ONE batched dispatch for the whole group (the leader's
        thread).  Reuses the msearch BatchGroup machinery — the device
        program, recovered on the host exactly like msearch, results
        byte-identical to the sequential path by the PR-5 invariant.
        Every member shares (field, k) by group-key construction."""
        from opensearch_tpu.search.batch import BatchGroup

        from opensearch_tpu.search.profile import (NULL_PROFILER,
                                                   QueryProfiler)

        gprof = (QueryProfiler() if any((m.body or {}).get("profile")
                                        for m in members)
                 else NULL_PROFILER)
        gprof.set("plan_cache", "batched")
        group = BatchGroup(field, k)
        for i, m in enumerate(members):
            group.add(i, m.bind)
        gprof.set("batch", {"field": field, "k": k,
                            "queries": len(members), "continuous": True})
        out = group.run(searcher, prof=gprof)
        _metrics().counter("search.batcher.dispatches").inc()
        _metrics().counter("search.batcher.batched").inc(len(members))
        for i, m in enumerate(members):
            rows, total, mx = out.get(i, ([], 0, None))
            m.rows, m.total, m.max_score = rows, total, mx
            m.group_size = len(members)
            m.stats = dict(group.last_stats)
            m.path = m.stats["path"]
            m.gprof = gprof

    def _render(self, searcher, member: _Member, t0: float) -> dict:
        """Per-member response + insight record, on the member's OWN
        thread (so its contextvar insight sink and task attribution
        apply)."""
        from opensearch_tpu.search import insights
        from opensearch_tpu.search.executor import shards_section

        body = member.body or {}
        hits = searcher._hits_from_rows(member.rows or [],
                                        body.get("_source"))
        took_s = time.monotonic() - t0
        resp = {
            "took": int(took_s * 1000),
            "timed_out": False,
            "_shards": shards_section(1),
            "hits": {"total": {"value": int(member.total),
                               "relation": "eq"},
                     "max_score": member.max_score,
                     "hits": hits},
        }
        insights.emit(
            signature=insights.canonical_query(body.get("query")),
            scored=True,
            took_ms=took_s * 1000,
            execution_path=member.path,
            plan_cache="batched",
            pruned=member.stats.get("pruned", 0),
            scanned=member.stats.get("scanned", 0),
            batched=member.group_size,
            queue_wait_ms=member.wait_s * 1000)
        if body.get("profile"):
            # members share the group profiler's phases (that sharing IS
            # the coalescing attribution) plus their OWN queue wait
            from opensearch_tpu.search.profile import QueryProfiler
            mprof = QueryProfiler()
            mprof.phases = dict(member.gprof.phases)
            mprof.counts = dict(member.gprof.counts)
            mprof.attrs = dict(member.gprof.attrs)
            mprof.segments = list(member.gprof.segments)
            mprof._xla0 = member.gprof._xla0
            mprof.add("queue", member.wait_s)
            resp["profile"] = {"shards": [mprof.shard_section(
                searcher.index_name, searcher.shard_id,
                plan_type="TermBagPlan",
                description=(f"continuous batch member of "
                             f"{member.group_size}"),
                total_segments=len(searcher.segments))]}
        return resp

    def stats(self) -> dict:
        m = _metrics()
        with self._cond:
            open_groups = len(self._groups)
            parked = self._parked
        return {
            "enabled": bool(BATCHER_ENABLED),
            "window_ms": (BATCHER_WINDOW_MS if BATCHER_WINDOW_MS > 0
                          else AUTO_WINDOW_MS),
            "max_batch": int(BATCHER_MAX_BATCH),
            "open_groups": open_groups,
            "parked": parked,
            "batched": m.counter("search.batcher.batched").value,
            "bypass": m.counter("search.batcher.bypass").value,
            "window_waits":
                m.counter("search.batcher.window_waits").value,
            "dispatches":
                m.counter("search.batcher.dispatches").value,
        }


class QueryEngine:
    """The unified entry.  Callers hand it a point-in-time
    ``ShardSearcher`` (and, at the REST edge, the owning
    ``IndexService``); backends — mesh collective, continuous batch,
    device kernels and their host recovery — are decisions inside,
    never separately-wired code paths."""

    def __init__(self):
        self.pool = SearchThreadpool()
        self.batcher = ContinuousBatcher()

    def execute(self, searcher, body: Optional[dict] = None, *,
                agg_partials: bool = False, service=None) -> dict:
        """One search body -> one response.  ``service`` (an
        IndexService) enables the service-scoped backends: the mesh
        router and the continuous batcher (both need a stable searcher
        identity across requests, which only the service's cached
        searcher provides — the cluster data-node path builds a fresh
        per-payload searcher and therefore runs the plain pipeline)."""
        body = body or {}
        if service is not None and not agg_partials \
                and service._use_mesh(body):
            return service._mesh_search(body)
        if service is not None and not agg_partials and BATCHER_ENABLED:
            out = self.batcher.execute(searcher, body)
            if out is not None:
                return out
        return searcher.search(body, agg_partials=agg_partials)

    def msearch(self, searcher, bodies: list) -> list[dict]:
        """The multi-search entry: same-shape bodies coalesce into the
        batched kernel, the rest fan out over the engine threadpool
        (see ShardSearcher.msearch for the partitioning)."""
        return searcher.msearch(bodies)

    def count(self, searcher, query: Optional[dict] = None) -> int:
        return searcher.count(query)

    def shutdown(self):
        """Idempotent bounded-join shutdown (Node.stop /
        ClusterNode.stop).  The engine is process-global, so this only
        quiesces worker threads; another live node's next search
        respawns them."""
        self.pool.stop()

    def stats(self) -> dict:
        return {"threadpool": self.pool.stats(),
                "batcher": self.batcher.stats()}


_engine = QueryEngine()


def query_engine() -> QueryEngine:
    return _engine
