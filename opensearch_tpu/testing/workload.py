"""Chaos-soak harness: seeded mixed workload + fault schedule + SLOs.

Analog of the reference's nightly benchmark/disruption runs (the
OpenSearch-benchmark mixed workloads driven against a cluster that
`NetworkDisruption`-style tests are killing underneath) collapsed into
one deterministic in-process subsystem:

- ``MixedWorkload``: a seeded generator of interleaved operation
  classes — zipf BM25 queries (the same query-log shape ``bench.py``
  measures), bulk ingest + refresh, ``date_histogram``/``terms``
  aggregations, scroll-style paged walks, and msearch batches.
- ``FaultSchedule``: a seeded schedule of fault directives pinned to
  operation indices (never wall clock): kill-the-leader + re-election,
  ``slow_search_node``, drop/stall rules, induced duress, and a
  symmetric network ``partition()`` — all via
  ``testing/fault_injection.py`` over the LocalTransport hub.
- ``SoakRunner``: drives a multi-node ``ClusterNode`` cluster through
  the workload while executing the schedule, collects per-op-class
  latency histograms plus rejection/shed/partial/retry accounting from
  the PR-1 metrics registry, and evaluates declarative SLOs: p99 per op
  class, a client-visible-error budget (429s and partial results are
  allowed degradation; unexpected 5xx budget is zero), and a post-fault
  convergence invariant — after the schedule drains, doc count and a
  content checksum must match an uninjected control run.

The same seed replays the same op stream, the same fault schedule, and
the same SLO verdicts — the regression gate ROADMAP item 5 asks for,
enforced in tier-1 via ``tests/test_soak.py`` and recorded as a
``soak`` phase line in ``bench_phases.jsonl`` by ``bench.py``.
"""

from __future__ import annotations

import contextlib
import json
import os
import random
import shutil
import tempfile
import threading
import time
import zlib
from typing import Callable, Optional

import numpy as np

from opensearch_tpu.common.errors import OpenSearchTpuError
from opensearch_tpu.common.telemetry import Histogram, metrics

#: transport failures a real client retries (retryable 503 class);
#: anything else client-visible above 399 that is not a 429 counts
#: against the zero-unexpected-error budget.  ``primary_fenced`` is the
#: replication-safety 503: the write was NOT acked, the slot moved —
#: retry routes to the current primary.  It arrives as a REMOTE type
#: (status 500 on the wire), so the name must be listed here — the
#: status==503 fallback only covers locally-raised fences.
_RETRYABLE_TYPES = ("node_disconnected_exception",
                    "receive_timeout_transport_exception",
                    "no_master_exception", "coordination_exception",
                    "primary_fenced_exception")


def _bump(ctx: dict, key: str, n: int = 1) -> None:
    """Locked counter increment — the full configuration runs ops on a
    worker pool, so the run context's tallies must not race."""
    with ctx["lock"]:
        ctx[key] += n


def zipf_query_log(n_queries: int, vocab_size: int,
                   seed: int = 7, a: float = 1.3) -> list:
    """Seeded zipf query log: ``n_queries`` two-term BM25 queries over a
    ranked vocabulary — the exact sampling ``bench.py`` measures with
    (bench imports THIS function), reused here so soak traffic has the
    same term-frequency shape as the flagship benchmark."""
    rng = np.random.default_rng(seed)
    pairs = []
    for _ in range(n_queries):
        x, y = (rng.zipf(a, size=2) - 1).clip(0, vocab_size - 1)
        pairs.append((int(x), int(y)))
    return pairs


def corpus_doc(seed: int, i: int, vocab_size: int, tags: list) -> dict:
    """Deterministic per-id document: zipf text body, a timestamp
    walking forward one minute per doc (date_histogram fodder), a
    zipf-ish tag (terms-agg fodder), and a sortable long.  Module-level
    so the open-loop harness (``testing/loadgen.py``) seeds its corpus
    with the exact same doc shape the soak exercises; the RNG
    construction and draw order are part of the determinism contract —
    ``MixedWorkload.make_doc`` delegates here and tests pin its
    output."""
    rng = random.Random((seed << 20) ^ i)
    n_terms = rng.randint(4, 10)
    body = " ".join(
        f"t{min(int(rng.paretovariate(1.3)) - 1, vocab_size - 1)}"
        for _ in range(n_terms))
    return {"body": body,
            "ts": 1_700_000_000_000 + i * 60_000,
            "tag": tags[min(int(rng.paretovariate(1.5)) - 1,
                            len(tags) - 1)],
            "v": i}


class SoakConfig:
    """Declarative soak scenario: workload mix, cluster shape, fault
    schedule knobs, and SLOs.  ``smoke()`` is the fixed-seed tier-1
    configuration (small, deterministic, seconds); ``full()`` is the
    production soak marked ``slow`` in the test suite."""

    def __init__(self, *, seed: int = 42, n_ops: int = 48,
                 n_docs: int = 24, bulk_size: int = 3,
                 vocab_size: int = 48, index: str = "soak",
                 shards: int = 2, replicas: int = 1,
                 node_ids: tuple = ("n0", "n1", "n2"),
                 search_replicas: int = 0,
                 searcher_ids: tuple = (),
                 client: str = "n1", concurrency: int = 1,
                 search_rpc_timeout: float = 0.5,
                 max_retries: int = 6,
                 faults_enabled: bool = True,
                 control_run: bool = True,
                 device_faults: bool = False,
                 autoscale: bool = False,
                 schedule: Optional[list] = None,
                 slos: Optional[dict] = None):
        self.seed = int(seed)
        self.n_ops = int(n_ops)
        self.n_docs = int(n_docs)
        self.bulk_size = int(bulk_size)
        self.vocab_size = int(vocab_size)
        self.index = index
        self.shards = int(shards)
        self.replicas = int(replicas)
        self.node_ids = tuple(node_ids)
        # search-only replica tier: ``searcher_ids`` name the
        # search-role nodes (stateless over the shared remote store),
        # ``search_replicas`` the per-shard searcher slots; > 0 enables
        # the tier directive class (kill/add searcher, remote-store
        # stall)
        self.search_replicas = int(search_replicas)
        self.searcher_ids = tuple(searcher_ids)
        if self.search_replicas and not self.searcher_ids:
            raise ValueError(
                "search_replicas > 0 requires searcher_ids")
        # accelerator fault class: the schedule gains the
        # device_oom / device_poison / device_slow / device_mesh_loss /
        # device_heal directives (testing/fault_injection.py
        # DeviceFaultInjector + common/device_health.py breakers)
        self.device_faults = bool(device_faults)
        # elasticity class: the leader gets a SearcherAutoscaler on an
        # injectable clock (advanced only by the scale_up_pressure /
        # scale_down_idle directives, so ticks are deterministic) wired
        # to provision/retire soak searcher nodes
        self.autoscale = bool(autoscale)
        self.client = client
        self.concurrency = int(concurrency)
        self.search_rpc_timeout = float(search_rpc_timeout)
        self.max_retries = int(max_retries)
        self.faults_enabled = bool(faults_enabled)
        self.control_run = bool(control_run)
        # an explicit directive list overrides the seeded generator —
        # focused scenarios (partition-only round-trips, single-fault
        # repros) reuse the whole runner
        self.schedule = schedule
        self.slos = slos if slos is not None else {
            # generous CI-safe p99 bounds: the verdicts must be
            # deterministic across runs/hosts; the OBSERVED p99 is what
            # the bench trajectory tracks run over run
            "p99_ms": {"search": 10_000.0, "msearch": 20_000.0,
                       "bulk": 10_000.0, "agg": 15_000.0,
                       "scroll": 15_000.0},
            "max_rejection_rate": 0.5,
            "max_unexpected_errors": 0,
            "require_convergence": True,
            # replication-safety SLOs (testing/history.py): the
            # post-drain durability audit must find zero lost acked
            # writes / zero stale acks, and every write copy (plus the
            # search tier) must serve an identical per-doc
            # (seq_no, primary_term, version) digest
            "no_lost_acked_writes": True,
            "no_stale_acks": True,
            "require_copy_parity": True,
        }

    @classmethod
    def smoke(cls, **overrides) -> "SoakConfig":
        return cls(**overrides)

    @classmethod
    def full(cls, **overrides) -> "SoakConfig":
        base = {"n_ops": 400, "n_docs": 400, "bulk_size": 10,
                "vocab_size": 2000, "concurrency": 4}
        base.update(overrides)
        return cls(**base)

    @classmethod
    def tier(cls, **overrides) -> "SoakConfig":
        """The search-tier scenario: 3 data nodes + 2 search-only
        replicas per shard over the shared remote store, with the
        searcher directive class (kill/add searcher mid-traffic,
        remote-store stall) in the schedule."""
        base = {"search_replicas": 2, "searcher_ids": ("s0", "s1")}
        base.update(overrides)
        return cls(**base)

    @classmethod
    def autoscale_churn(cls, **overrides) -> "SoakConfig":
        """The elasticity scenario: one seed searcher, the autoscaler
        on the leader (= the client, so admission evidence and
        actuation share a node), and an explicit schedule driving one
        hot window (held admission permits past the dwell) and one idle
        window.  SLOs require >= 1 audited scale-up and >= 1
        drain-complete retirement with the standard p99 / unexpected-
        error / convergence bounds holding across both transitions."""
        base = {"search_replicas": 1, "searcher_ids": ("s0",),
                "client": "n0", "autoscale": True, "n_ops": 32,
                "schedule": [
                    {"step": 8, "fault": "scale_up_pressure"},
                    {"step": 20, "fault": "scale_down_idle"},
                ]}
        base.update(overrides)
        cfg = cls(**base)
        cfg.slos.setdefault("require_scale_up", True)
        cfg.slos.setdefault("require_drain_complete", True)
        return cfg

    @classmethod
    def device(cls, **overrides) -> "SoakConfig":
        """The accelerator-fault scenario: device kernels forced on,
        the device fault directive class in the schedule, and the
        device SLOs — zero unexpected 5xx, convergence vs the
        uninjected control, >= 1 breaker trip visible, breakers
        re-closed after heal (mesh exempt: on a 1-device CPU host the
        mesh stays legitimately demoted), and >= 1 poisoned result
        caught by the sanity guard."""
        base = {"device_faults": True}
        base.update(overrides)
        cfg = cls(**base)
        cfg.slos.setdefault("require_breaker_trip", True)
        cfg.slos.setdefault("require_breaker_reclose", True)
        cfg.slos.setdefault("require_poison_detected", True)
        return cfg


class MixedWorkload:
    """Seeded mixed-operation stream.  Every op is a plain dict (class +
    parameters), so the stream is inspectable, replayable, and identical
    across runs with the same config."""

    CLASSES = ("search", "msearch", "bulk", "agg", "scroll")

    def __init__(self, config: SoakConfig):
        self.config = config
        self._rng = random.Random(config.seed)
        self._doc_seq = config.n_docs          # ids after the seed corpus
        self._queries = zipf_query_log(
            max(64, config.n_ops * 2), config.vocab_size,
            seed=config.seed)
        self._qi = 0
        self.tags = [f"tag{i}" for i in range(8)]

    # -- documents ---------------------------------------------------------

    def make_doc(self, i: int) -> dict:
        """Deterministic per-id document — delegates to the shared
        ``corpus_doc`` so soak and loadgen corpora stay byte-identical
        for the same seed."""
        return corpus_doc(self.config.seed, i, self.config.vocab_size,
                          self.tags)

    def seed_docs(self) -> list:
        return [(str(i), self.make_doc(i)) for i in range(self.config.n_docs)]

    # -- operations --------------------------------------------------------

    def _next_query(self) -> dict:
        a, b = self._queries[self._qi % len(self._queries)]
        self._qi += 1
        return {"query": {"match": {"body": f"t{a} t{b}"}}, "size": 10}

    def _op(self, kind: str) -> dict:
        if kind == "search":
            return {"op": "search", "body": self._next_query()}
        if kind == "msearch":
            return {"op": "msearch",
                    "bodies": [self._next_query() for _ in range(4)]}
        if kind == "bulk":
            docs = []
            for _ in range(self.config.bulk_size):
                i = self._doc_seq
                self._doc_seq += 1
                docs.append((str(i), self.make_doc(i)))
            delete_id = None
            if self._rng.random() < 0.2 and self._doc_seq > 4:
                # delete an early seed doc (deterministic victim), the
                # mixed-workload CRUD shape; convergence tracks it too
                delete_id = str(self._rng.randrange(4))
            return {"op": "bulk", "docs": docs, "delete": delete_id,
                    "refresh": self._rng.random() < 0.5}
        if kind == "agg":
            if self._rng.random() < 0.5:
                aggs = {"per_hour": {"date_histogram": {
                    "field": "ts", "fixed_interval": "1h"}}}
            else:
                aggs = {"tags": {"terms": {"field": "tag", "size": 8}}}
            return {"op": "agg",
                    "body": {"query": {"match_all": {}}, "size": 0,
                             "aggs": aggs}}
        if kind == "scroll":
            return {"op": "scroll", "page_size": 8, "max_pages": 3}
        raise ValueError(kind)

    def ops(self) -> list:
        """The full seeded op stream: weighted mix, search-heavy like
        the reference's default benchmark workloads."""
        weights = {"search": 0.40, "msearch": 0.15, "bulk": 0.20,
                   "agg": 0.15, "scroll": 0.10}
        kinds = list(weights)
        cum = np.cumsum([weights[k] for k in kinds])
        out = []
        for _ in range(self.config.n_ops):
            r = self._rng.random()
            kind = kinds[int(np.searchsorted(cum, r))]
            out.append(self._op(kind))
        return out


class FaultSchedule:
    """Seeded fault directives pinned to op indices.  A directive is a
    dict ``{"step": i, "fault": name, ...params}``; the runner applies
    every directive whose step equals the index of the op about to
    execute, so the interleaving is a pure function of the seed."""

    @staticmethod
    def generate(config: SoakConfig) -> list:
        rng = random.Random(config.seed ^ 0x5EED)
        n = config.n_ops
        client = config.client
        others = [nid for nid in config.node_ids if nid != client]
        slow_victim = rng.choice(others)
        drop_victim = rng.choice(others)
        stall_victim = rng.choice(others)
        # duress on the two non-client nodes: every shard with both
        # copies there becomes sheddable once the coordinator learns
        duress_victims = others[:2]
        # partition isolates a non-client follower; the kill targets the
        # elected leader (re-election is the point)
        part_victim = next(nid for nid in others if nid != "n0") \
            if "n0" in others else rng.choice(others)
        # seeded jitter on each slot (clamped monotone so paired
        # directives — stall/release, induce/clear, unhealthy/heal,
        # partition/heal, kill/restart — keep their order): where a
        # fault lands in the op stream is part of the schedule the seed
        # replays.  Disk faults (corrupt_segment, disk_unhealthy) ride
        # the same schedule — the fault class PRs 2-7 couldn't inject.
        jitter = max(1, n // 24)
        at: list = []
        for f in (0.08, 0.16, 0.24, 0.32, 0.38, 0.46, 0.54,
                  0.60, 0.68, 0.76, 0.84, 0.90, 0.96):
            base = max(1, int(n * f)) + rng.randint(0, jitter)
            at.append(min(max(at[-1] if at else 1, base), n - 1))
        out = [
            {"step": at[0], "fault": "slow_node", "node": slow_victim,
             "seconds": 0.05, "times": 2},
            {"step": at[1], "fault": "drop_write", "node": drop_victim,
             "times": 1},
            {"step": at[2], "fault": "stall_search", "node": stall_victim,
             "times": 2},
            {"step": at[3], "fault": "release_stall"},
            # disk fault 1: a seeded bit-flip in one replica's committed
            # segment file — detection, A_FAIL_COPY, drop + re-recovery
            {"step": at[4], "fault": "corrupt_segment"},
            {"step": at[5], "fault": "induce_duress",
             "nodes": list(duress_victims)},
            {"step": at[6], "fault": "clear_duress",
             "nodes": list(duress_victims)},
            # disk fault 2: a node whose fsync probe starts failing is
            # evicted by the leader (FsHealth piggyback), then healed
            {"step": at[7], "fault": "disk_unhealthy"},
            {"step": at[8], "fault": "disk_heal"},
            {"step": at[9], "fault": "partition", "node": part_victim},
            {"step": at[10], "fault": "heal_partition",
             "node": part_victim},
            {"step": at[11], "fault": "kill_leader"},
            {"step": at[12], "fault": "restart_killed"},
        ]
        if config.search_replicas and config.searcher_ids:
            # searcher-tier directive class: remote-store outage
            # (stall + release), then kill a searcher mid-traffic and
            # add a fresh one — SLOs must hold and doc-count+checksum
            # convergence must survive the fleet rebalancing.  Seeded
            # like the base schedule: paired directives stay ordered
            # under the jitter.
            s_at: list = []
            for f in (0.20, 0.30, 0.44, 0.58):
                base = max(1, int(n * f)) + rng.randint(0, jitter)
                s_at.append(min(max(s_at[-1] if s_at else 1, base),
                                n - 1))
            victim = config.searcher_ids[0]
            out += [
                {"step": s_at[0], "fault": "stall_remote_store"},
                {"step": s_at[1], "fault": "release_remote_store"},
                {"step": s_at[2], "fault": "kill_searcher",
                 "node": victim},
                {"step": s_at[3], "fault": "add_searcher",
                 "node": f"{victim}r"},
            ]
        if config.device_faults:
            # accelerator fault class (the single fault domain the
            # cluster directives above never touch): slow device, then
            # NaN-poisoned top-k (sanity guard + dispatch breaker),
            # heal, then sticky staging OOM over force-evicted
            # segments (restage failures + host fallbacks), mesh
            # member loss probes, final heal with breaker-re-close
            # probes.  Seeded like the rest: paired windows stay
            # ordered under the jitter.
            d_at: list = []
            for f in (0.10, 0.22, 0.34, 0.48, 0.62, 0.76):
                base = max(1, int(n * f)) + rng.randint(0, jitter)
                d_at.append(min(max(d_at[-1] if d_at else 1, base),
                                n - 1))
            out += [
                {"step": d_at[0], "fault": "device_slow",
                 "seconds": 0.02, "times": 3},
                {"step": d_at[1], "fault": "device_poison", "times": 3},
                {"step": d_at[2], "fault": "device_heal"},
                {"step": d_at[3], "fault": "device_oom"},
                {"step": d_at[4], "fault": "device_mesh_loss",
                 "probes": 3},
                {"step": d_at[5], "fault": "device_heal"},
            ]
        # split-brain manufacture (self-contained: partition -> writes
        # -> election -> heal -> fenced writes -> readmit, all inside
        # one directive) runs LAST, after the cluster is whole again —
        # and its rng draw comes after every other directive class's
        # draws, so every pre-existing schedule stays byte-identical
        sb = min(max(at[-1],
                     max(1, int(n * 0.98)) + rng.randint(0, jitter)),
                 n - 1)
        out.append({"step": sb, "fault": "isolate_primary_with_writes",
                    "writes": 2})
        return out


class SoakRunner:
    """Drives the cluster through the workload + schedule, twice when a
    control run is requested: once uninjected (the convergence
    reference) and once under chaos.  ``run()`` returns the full report
    — SLO verdicts included, breaches REPORTED, never swallowed."""

    def __init__(self, data_path: Optional[str] = None,
                 config: Optional[SoakConfig] = None):
        self.config = config or SoakConfig.smoke()
        self._own_dir = data_path is None
        self.data_path = data_path or tempfile.mkdtemp(prefix="soak-")

    # -- cluster plumbing --------------------------------------------------

    def _wait(self, pred: Callable[[], bool], timeout: float = 20.0,
              what: str = "condition") -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:   # deadline
            if pred():
                return
            time.sleep(0.02)                 # deadline
        raise SoakHarnessError(f"soak harness: timed out waiting for {what}")

    def _build_node(self, hub, nid: str, root: str,
                    roles: tuple = ("master", "data")):
        from opensearch_tpu.cluster.node import ClusterNode
        from opensearch_tpu.transport.service import (LocalTransport,
                                                      TransportService)
        svc = TransportService(nid, LocalTransport(hub))
        # with a search tier configured, every node points at the same
        # shared blob store (primaries upload, searchers refill)
        remote = (f"{root}/remote" if self.config.search_replicas
                  else None)
        node = ClusterNode(nid, f"{root}/{nid}", svc,
                           list(self.config.node_ids), roles=roles,
                           remote_store_path=remote)
        # neutralize the real CPU probe: only SCHEDULED duress may fire
        # (a loaded CI host must not leak nondeterminism into verdicts)
        node.search_backpressure.trackers["cpu_usage"].probe = lambda: 0.0
        node.search_rpc_timeout = self.config.search_rpc_timeout
        node.recovery_timeout = max(5.0, self.config.search_rpc_timeout)
        return node

    def _searcher_info(self, nid: str) -> dict:
        return {"name": nid, "roles": ["search"],
                "master_eligible": False}

    def _searchers_ready(self, ctx: dict) -> bool:
        """Every shard's search slots are filled by live searcher nodes
        and every filled slot has reported its remote refill done."""
        nodes = ctx["nodes"]
        state = nodes[ctx["leader"]].coordinator.state()
        routing = state.routing.get(self.config.index, [])
        alive = [nid for nid in ctx["searchers"] if nid in nodes
                 and nid in state.nodes]
        want = min(self.config.search_replicas, len(alive))
        return bool(routing) and all(
            len(e.get("search_replicas") or []) >= want
            and set(e.get("search_replicas") or [])
            == set(e.get("search_in_sync") or []) for e in routing)

    def _searchers_caught_up(self, ctx: dict) -> bool:
        """Post-drain: every ready searcher copy has installed a
        checkpoint at (or past) its primary's current seq — the
        precondition for doc-count/checksum parity with the write
        tier."""
        nodes = ctx["nodes"]
        state = nodes[ctx["leader"]].coordinator.state()
        for s, e in enumerate(state.routing.get(self.config.index, [])):
            primary = e.get("primary")
            if primary not in nodes:
                return False
            engine = nodes[primary].indices[
                self.config.index].engine_for(s)
            for r in e.get("search_replicas") or []:
                if r not in nodes:
                    return False
                if nodes[r].search_installed_seq(
                        self.config.index, s) < engine._seq_no:
                    return False
        return True

    def _in_sync_full(self, nodes, leader: str) -> bool:
        state = nodes[leader].coordinator.state()
        routing = state.routing.get(self.config.index, [])
        want_repl = min(self.config.replicas, len(state.nodes) - 1)
        return bool(routing) and all(
            e.get("primary")
            and set(e["in_sync"]) == {e["primary"], *e["replicas"]}
            and len(e["replicas"]) >= want_repl for e in routing)

    # -- fault directives --------------------------------------------------

    def _apply_fault(self, d: dict, ctx: dict) -> None:
        from opensearch_tpu.cluster.node import A_SEARCH_SHARDS, A_WRITE_SHARD
        faults = ctx["faults"]
        nodes = ctx["nodes"]
        fault = d["fault"]
        ctx["applied"].append(dict(d))
        if fault == "slow_node":
            faults.slow_search_node(d["node"], d["seconds"],
                                    times=d.get("times"))
        elif fault == "drop_write":
            faults.drop(A_WRITE_SHARD, target=d["node"],
                        times=d.get("times", 1))
        elif fault == "stall_search":
            ctx["stall"] = faults.stall(A_SEARCH_SHARDS, target=d["node"],
                                        times=d.get("times"))
        elif fault == "release_stall":
            rule = ctx.pop("stall", None)
            if rule is not None:
                rule.release()
                faults.remove(rule)
        elif fault == "induce_duress":
            for nid in d["nodes"]:
                bp = nodes[nid].search_backpressure
                ctx["saved_breaches"][nid] = bp.num_successive_breaches
                bp.num_successive_breaches = 1
                faults.induce_search_duress(bp, ticks=1_000_000)
                bp.run_once()
        elif fault == "clear_duress":
            client = nodes[ctx["client"]]
            for nid in d["nodes"]:
                bp = nodes[nid].search_backpressure
                bp.force_duress(0)
                bp.run_once()                 # streak resets
                bp.num_successive_breaches = \
                    ctx["saved_breaches"].pop(nid, 3)
                # deterministic flag heal on the coordinator (the
                # record_duress seam) — TTL expiry is wall-clock and the
                # shed path never re-probes a fully-shed shard
                client.response_collector.record_duress(nid, False)
            leader = ctx["leader"]
            if leader in nodes:
                nodes[leader].coordinator.run_checks_once()
            _bump(ctx, "recoveries")
        elif fault == "corrupt_segment":
            self._corrupt_segment(ctx, d)
        elif fault == "disk_unhealthy":
            from opensearch_tpu.common.fshealth import FsHealthService
            from opensearch_tpu.testing.fault_injection import \
                DiskFaultInjector
            victim = d.get("node") or next(
                nid for nid in sorted(nodes)
                if nid not in (ctx["leader"], ctx["client"]))
            disk = DiskFaultInjector(seed=self.config.seed ^ 0xD15C)
            disk.fail_fsync(os.path.join(nodes[victim].data_path,
                                         FsHealthService.PROBE_FILE))
            disk.activate()
            ctx["disk"] = disk
            ctx["disk_victim"] = victim
            ctx["applied"][-1]["node"] = victim
            nodes[victim].fs_health.check()      # probe sees the fault
            # the unhealthy verdict piggybacks on the next follower
            # checks; after the retry budget the leader evicts the node
            # and reroutes its copies (zero client-visible failures)
            self._evict(ctx, victim)
        elif fault == "disk_heal":
            disk = ctx.pop("disk", None)
            if disk is not None:
                disk.deactivate()
            victim = ctx.pop("disk_victim", None)
            if victim is not None and victim in nodes:
                nodes[victim].fs_health.check()  # healthy again
                self._readmit(ctx, victim)
        elif fault == "isolate_primary_with_writes":
            self._isolate_primary_with_writes(ctx, d)
        elif fault == "partition":
            victim = d["node"]
            sides = ([victim],
                     [n for n in nodes if n != victim])
            ctx["partition"] = faults.partition(*sides)
            self._evict(ctx, victim)
        elif fault == "heal_partition":
            rule = ctx.pop("partition", None)
            if rule is not None:
                faults.heal_partition(rule)
            self._readmit(ctx, d["node"])
        elif fault == "kill_leader":
            victim = ctx["leader"]
            ctx["killed"] = victim
            nodes[victim].stop()
            nodes.pop(victim)
            client = ctx["client"]

            # survivors must OBSERVE the leader dead (failed
            # leader-check rounds) before they grant a pre-vote, then
            # the client (never a kill victim) stands for election
            def elected() -> bool:
                for nid, node in nodes.items():
                    retries = \
                        node.coordinator.leader_checker.settings.retries
                    for _ in range(retries + 1):
                        node.coordinator.run_checks_once()
                return nodes[client].coordinator.start_election()
            self._wait(elected, what="re-election after leader kill")
            ctx["leader"] = client
            self._evict(ctx, victim)
            _bump(ctx, "recoveries")
        elif fault == "restart_killed":
            victim = ctx.pop("killed", None)
            if victim is not None:
                hub = ctx["hub"]
                node = self._build_node(hub, victim, ctx["root"])
                ctx["nodes"][victim] = node
                self._readmit(ctx, victim)
        elif fault == "kill_searcher":
            victim = d.get("node") or next(iter(sorted(
                ctx["searchers"])))
            ctx["applied"][-1]["node"] = victim
            if victim in nodes:
                # drain-safe retirement through the ONE sanctioned path
                # (cluster/autoscaler.py): the victim leaves the C3
                # candidate sets and search_in_sync BEFORE it stops, so
                # no late scatter burns a failover attempt on a dead
                # searcher
                from opensearch_tpu.cluster.autoscaler import \
                    retire_searcher
                leader = nodes[ctx["leader"]]
                res = retire_searcher(
                    leader.coordinator, victim,
                    collector=leader.response_collector,
                    node=nodes[victim],
                    drain_timeout_s=d.get("drain_timeout_s", 5.0),
                    audit=leader.qos.record_adaptation,
                    rank=leader.response_collector.rank)
                nodes.pop(victim, None)
                ctx["searchers"].discard(victim)
                ctx["applied"][-1]["drain"] = res
                self._wait(lambda: self._searchers_ready(ctx),
                           timeout=30.0,
                           what="tier rebalance after searcher "
                                "retirement")
                _bump(ctx, "recoveries")
        elif fault == "add_searcher":
            nid = d["node"]
            node = self._build_node(ctx["hub"], nid, ctx["root"],
                                    roles=("search",))
            ctx["nodes"][nid] = node
            ctx["searchers"].add(nid)
            leader = ctx["leader"]
            nodes[leader].coordinator.add_node(
                nid, self._searcher_info(nid))
            # a FRESH searcher recovers purely by cache refill from the
            # remote store — zero primary-directed RPCs (asserted by
            # the acceptance test over transport accounting)
            self._wait(lambda: self._searchers_ready(ctx),
                       timeout=30.0,
                       what=f"remote refill of fresh searcher [{nid}]")
            _bump(ctx, "recoveries")
        elif fault == "scale_up_pressure":
            self._scale_up_pressure(ctx, d)
        elif fault == "scale_down_idle":
            self._scale_down_idle(ctx, d)
        elif fault == "device_slow":
            self._devfaults(ctx).slow_device(d.get("seconds", 0.02),
                                             times=d.get("times"))
        elif fault == "device_poison":
            self._devfaults(ctx).poison_topk(times=d.get("times", 3))
        elif fault == "device_oom":
            from opensearch_tpu.common.device_ledger import device_ledger
            # sticky staging RESOURCE_EXHAUSTED over force-evicted
            # segments: every restage attempt fails, scored term-bags
            # take the byte-identical host fallback, full-scores plans
            # degrade to partial shard failures
            self._devfaults(ctx).oom()
            led = device_ledger()
            led.set_budget(1)
            led.set_budget(0)
        elif fault == "device_mesh_loss":
            from opensearch_tpu.common.telemetry import metrics as _m
            inj = self._devfaults(ctx)
            rule = inj.lose_mesh_member()
            svc = nodes[ctx["client"]].indices.get(self.config.index)
            before_fb = _m().counter("search.mesh.fallback").value
            for _ in range(int(d.get("probes", 3))):
                # drive the mesh entry directly: member loss (or a mesh
                # that cannot build on this host) must demote to the
                # counted host scatter fallback, never raise
                resp = svc._mesh_search(
                    {"query": {"match": {"body": "t0 t1"}}, "size": 5})
                if resp.get("hits") is None:
                    raise SoakHarnessError(
                        "mesh probe returned a malformed response")
            inj.remove(rule)
            ctx["applied"][-1]["mesh_fallbacks"] = int(
                _m().counter("search.mesh.fallback").value - before_fb)
        elif fault == "device_heal":
            from opensearch_tpu.common.device_health import device_health
            inj = ctx.get("devfaults")
            if inj is not None:
                inj.clear()
            # deterministic breaker-re-close probes: a sorted scan
            # restages every evicted segment on the selected copies
            # (staging + dispatch classes), then a scored term-bag runs
            # the device kernel path again
            client = nodes[ctx["client"]]
            self._write_with_retry(ctx, lambda: client.search(
                self.config.index,
                {"query": {"match_all": {}}, "size": 1,
                 "sort": [{"v": "asc"}]}))
            self._write_with_retry(ctx, lambda: client.search(
                self.config.index,
                {"query": {"match": {"body": "t0"}}, "size": 1}))
            ctx["applied"][-1]["breaker_states"] = \
                device_health().breaker_states()
            _bump(ctx, "recoveries")
        elif fault == "stall_remote_store":
            from opensearch_tpu.testing.fault_injection import \
                RemoteStoreFaultInjector
            repos = [n.remote_store for n in nodes.values()
                     if getattr(n, "is_search", False)
                     and n.remote_store is not None]
            inj = RemoteStoreFaultInjector(repos)
            inj.stall()
            ctx["remote_stall"] = inj
        elif fault == "release_remote_store":
            inj = ctx.pop("remote_stall", None)
            if inj is not None:
                inj.release()
        else:
            raise ValueError(f"unknown fault directive [{fault}]")

    def _isolate_primary_with_writes(self, ctx: dict, d: dict) -> None:
        """Split-brain manufacture, end to end inside one directive so
        the interleaving is seed-pure: fully partition one shard's
        primary, drive writes at it (indeterminate outcomes — the
        partition eats them), let the leader evict it and promote a
        replica under a bumped term, HEAL the partition, then drive
        more writes through the deposed primary's stale routing state.
        Every late replication op must be fenced by the promoted
        lineage (``stale_primary_rejections``) and the deposed primary
        must raise the retryable 503 instead of acking — those writes
        are recorded as DEFINITE failures, so if one ever becomes
        visible the durability audit turns ``no_stale_acks`` red.
        Finally the deposed node readmits: its divergent copy rolls
        back above the global checkpoint and re-recovers, leaving the
        final state byte-identical to the control run's."""
        from opensearch_tpu.indices.service import shard_id_for
        cfg = self.config
        nodes = ctx["nodes"]
        hist = ctx["history"]
        faults = ctx["faults"]
        victim = shard = None
        for attempt in range(2):
            state = nodes[ctx["leader"]].coordinator.state()
            routing = state.routing.get(cfg.index, [])
            for s, e in enumerate(routing):
                p = e.get("primary")
                if (p and p not in (ctx["leader"], ctx["client"])
                        and p in nodes and (e.get("replicas") or [])):
                    victim, shard = p, s
                    break
            if victim is not None or attempt > 0:
                break
            # the preceding failover chain tends to park every primary
            # on the survivor-of-everything (the leader/client): force
            # a PLANNED failover through the real deposed-primary path
            # — promote an eligible in-sync replica under a bumped
            # term — then rescan, so the fence is exercised on every
            # seeded schedule, not only topology-lucky ones
            moved = False
            for s, e in enumerate(routing):
                safe = [r for r in (e.get("replicas") or [])
                        if r in (e.get("in_sync") or []) and r in nodes
                        and r not in (ctx["leader"], ctx["client"])]
                if safe and e.get("primary"):
                    nodes[ctx["leader"]]._h_fail_copy({
                        "index": cfg.index, "shard": s,
                        "node": e["primary"], "deposed": True})
                    moved = True
                    break
            if not moved:
                break
            ctx["applied"][-1]["planned_failover"] = True
            self._wait(lambda: self._in_sync_full(nodes,
                                                  ctx["leader"]),
                       timeout=30.0,
                       what="planned failover before split-brain "
                            "directive")
        ctx["applied"][-1].update(node=victim, shard=shard)
        if victim is None:
            # no movable primary either; degrade to a no-op — LOUDLY
            # (the applied record says so), never to a half-run
            ctx["applied"][-1]["skipped"] = "no eligible primary"
            return
        n_shards = len(routing)

        def ids_for(prefix: str, k: int) -> list:
            out, i = [], 0
            while len(out) < k:       # deterministic: murmur3 routing
                did = f"{prefix}{i}"
                if shard_id_for(did, None, n_shards) == shard:
                    out.append(did)
                i += 1
            return out

        writes = int(d.get("writes", 2))
        rule = faults.partition(
            [victim], [n for n in nodes if n != victim])
        # phase A: writes INTO the partition — each fails fast at the
        # cut; the outcome is indeterminate from the client's side
        # (recorded UNKNOWN: absent and present are both legal ends)
        for did in ids_for(f"sb-a-{cfg.seed}-", writes):
            src = {"body": "split brain phase a", "tag": "sb",
                   "ts": 1_700_000_000_000, "v": -1, "nonce": did}
            op_id = hist.invoke("index", did, src)
            try:
                resp = nodes[ctx["client"]].index_doc(cfg.index, did,
                                                      src)
                hist.ok(op_id, resp)
            except OpenSearchTpuError as exc:
                hist.unknown(op_id, f"{type(exc).__name__}: {exc}")
        # the leader evicts the unreachable primary; a surviving
        # in-sync replica is promoted under a bumped primary term
        self._evict(ctx, victim)
        # heal: the deposed primary can reach everyone again but still
        # BELIEVES it holds the primary slot at the old term
        faults.heal_partition(rule)
        fenced = 0
        # phase B: writes through the deposed primary's stale state —
        # its replication ops carry the old term, the promoted
        # lineage's copies fence them, and the 503 (instead of an ack)
        # makes these DEFINITE failures: unique per-attempt content, so
        # any survivor is caught as a stale ack
        for did in ids_for(f"sb-b-{cfg.seed}-", writes):
            src = {"body": "split brain phase b", "tag": "sb",
                   "ts": 1_700_000_000_000, "v": -2, "nonce": did}
            op_id = hist.invoke("index", did, src)
            try:
                resp = nodes[victim].index_doc(cfg.index, did, src)
                # an ack from a deposed primary IS the bug class this
                # directive exists to catch — record it faithfully and
                # let the durability verdict go red
                hist.ok(op_id, resp)
            except OpenSearchTpuError as exc:
                # ONLY the fence (raised instead of an ack, local to
                # the deposed owner) is a definite failure; any other
                # error (disconnect, timeout) leaves the fate open
                from opensearch_tpu.common.errors import \
                    PrimaryFencedError
                if isinstance(exc, PrimaryFencedError):
                    fenced += 1
                    hist.fail(op_id,
                              f"fenced: {type(exc).__name__}: {exc}")
                else:
                    hist.unknown(op_id,
                                 f"{type(exc).__name__}: {exc}")
        ctx["applied"][-1]["fenced_writes"] = fenced
        # readmit: the deposed copy rolls back its divergence above the
        # global checkpoint and peer-recovers under the current term
        self._readmit(ctx, victim)

    def _devfaults(self, ctx: dict):
        """Lazily activate the pass's DeviceFaultInjector (seeded from
        the soak seed, so the whole fault schedule replays)."""
        from opensearch_tpu.testing.fault_injection import \
            DeviceFaultInjector
        inj = ctx.get("devfaults")
        if inj is None:
            inj = DeviceFaultInjector(
                seed=self.config.seed ^ 0xDE7).activate()
            ctx["devfaults"] = inj
        return inj

    def _corrupt_segment(self, ctx: dict, d: dict) -> None:
        """Disk-fault directive: flush one in-sync replica copy, flip a
        seeded byte in one of its committed segment files, then run
        store verification — the copy must detect the damage, fail
        itself via ``A_FAIL_COPY``, drop its local data, and re-recover
        from the primary before the workload proceeds."""
        cfg = self.config
        nodes = ctx["nodes"]
        state = nodes[ctx["leader"]].coordinator.state()
        routing = state.routing.get(cfg.index, [])
        victim = shard = None
        for nid in sorted(nodes):
            if nid == ctx["client"]:
                continue
            for s, e in enumerate(routing):
                if nid in (e.get("replicas") or []) \
                        and nid in (e.get("in_sync") or []):
                    victim, shard = nid, s
                    break
            if victim is not None:
                break
        if victim is None:
            return                        # no in-sync replica to damage
        engine = nodes[victim].indices[cfg.index].engine_for(shard)
        engine.flush()                    # put the copy's files on disk
        seg_dir = os.path.join(engine.data_path, "segments")
        targets = [f for f in sorted(os.listdir(seg_dir))
                   if f.endswith((".npz", ".src", ".json"))]
        if not targets:
            return
        rng = random.Random(cfg.seed ^ 0xB17F11)
        path = os.path.join(seg_dir, rng.choice(targets))
        with open(path, "rb") as f:
            data = bytearray(f.read())
        if not data:
            return
        data[rng.randrange(len(data))] ^= 0xFF
        with open(path, "wb") as f:
            f.write(bytes(data))
        applied = ctx["applied"][-1]
        applied["node"], applied["shard"] = victim, shard
        report = nodes[victim].verify_local_stores(cfg.index)
        applied["detected"] = any(r.get("corrupted") for r in report)
        self._wait(lambda: self._in_sync_full(nodes, ctx["leader"]),
                   timeout=30.0,
                   what=f"re-recovery after corrupting [{victim}]")
        _bump(ctx, "recoveries")

    # -- elasticity directives ---------------------------------------------

    def _wire_autoscaler(self, ctx: dict) -> None:
        """Attach the leader's autoscaler to the harness: fake clock
        (advanced only by the scale directives — ticks from the search
        path see a frozen clock and stay pure evidence updates),
        provisioner/resolver over the soak's in-process node map, and
        bounds pinned per-instance so global knobs stay untouched."""
        nodes = ctx["nodes"]
        asc = nodes[ctx["leader"]].autoscaler
        clock = {"t": 0.0}
        asc.clock = lambda: clock["t"]
        asc.enabled = True
        asc.min_searchers = max(1, len(ctx["searchers"]))
        asc.max_searchers = asc.min_searchers + 2
        asc.dwell_s = 2.0
        asc.cooldown_s = 5.0
        asc.drain_timeout_s = 5.0

        def provision(nid: str) -> dict:
            node = self._build_node(ctx["hub"], nid, ctx["root"],
                                    roles=("search",))
            nodes[nid] = node
            ctx["searchers"].add(nid)
            return self._searcher_info(nid)

        def retired(nid: str) -> None:
            nodes.pop(nid, None)
            ctx["searchers"].discard(nid)

        asc.provision = provision
        asc.resolve = nodes.get
        asc.on_retired = retired
        ctx["scale_clock"] = clock
        ctx["autoscaler"] = asc

    def _scale_up_pressure(self, ctx: dict, d: dict) -> None:
        """Hold admission permits as a hot tenant until occupancy
        evidence crosses the scale-up threshold, advance the fake clock
        past the dwell, and let the autoscaler provision + admit a
        fresh searcher — then wait for its remote refill so SLOs are
        measured THROUGH the transition."""
        import contextlib as _ctl
        nodes = ctx["nodes"]
        asc = ctx["autoscaler"]
        clock = ctx["scale_clock"]
        adm = nodes[ctx["leader"]].search_backpressure.admission
        tenant = d.get("tenant", "tenant-hot")
        permits = int(d.get("permits") or adm.max_concurrent)
        t0 = time.monotonic()
        with _ctl.ExitStack() as stack:
            for _ in range(permits):
                stack.enter_context(
                    adm.acquire("search", tenant=tenant))
            asc.run_once()                      # hot evidence observed
            clock["t"] += asc.dwell_s + 0.001   # dwell passes
            decision = asc.run_once()           # actuation
        if decision.get("action") != "scale_up":
            raise SoakHarnessError(
                f"scale_up_pressure did not scale: {decision}")
        self._wait(lambda: self._searchers_ready(ctx), timeout=30.0,
                   what="fresh autoscaled searcher refill")
        ctx["applied"][-1].update(
            node=decision.get("node"),
            searchers=sorted(ctx["searchers"]),
            time_to_scale_up_s=round(time.monotonic() - t0, 3))
        _bump(ctx, "recoveries")

    def _scale_down_idle(self, ctx: dict, d: dict) -> None:
        """Advance the fake clock past the cooldown with zero admission
        occupancy: the cold dwell (begun by the first post-scale-up
        tick from the traffic path, or by this directive's first
        evaluation) expires and the autoscaler retires the newest
        autoscaled searcher through the drain protocol."""
        asc = ctx["autoscaler"]
        clock = ctx["scale_clock"]
        t0 = time.monotonic()
        clock["t"] += asc.cooldown_s + 0.001
        decision = asc.run_once()
        if decision.get("action") not in ("scale_down", "resume_drain"):
            clock["t"] += asc.dwell_s + 0.001
            decision = asc.run_once()
        if decision.get("action") not in ("scale_down", "resume_drain"):
            raise SoakHarnessError(
                f"scale_down_idle did not drain: {decision}")
        self._wait(lambda: self._searchers_ready(ctx), timeout=30.0,
                   what="tier rebalance after autoscaled drain")
        ctx["applied"][-1].update(
            node=decision.get("node"),
            drain=decision.get("drain"),
            searchers=sorted(ctx["searchers"]),
            drain_s=round(time.monotonic() - t0, 3))
        _bump(ctx, "recoveries")

    def _evict(self, ctx: dict, victim: str) -> None:
        """Drive the leader's fault detection until the victim leaves
        the cluster state and surviving copies are promoted."""
        nodes = ctx["nodes"]
        leader = ctx["leader"]
        retries = nodes[leader].coordinator.follower_checker.settings.retries

        def gone():
            for _ in range(retries + 1):
                nodes[leader].coordinator.run_checks_once()
            return victim not in nodes[leader].coordinator.state().nodes
        self._wait(gone, what=f"eviction of [{victim}]")
        self._wait(lambda: self._in_sync_full(nodes, leader),
                   what=f"promotion after [{victim}] eviction")

    def _readmit(self, ctx: dict, victim: str) -> None:
        """Re-add an evicted/restarted node and wait for peer recovery
        to bring its copies back in sync."""
        nodes = ctx["nodes"]
        leader = ctx["leader"]
        nodes[leader].coordinator.add_node(victim, {"name": victim})
        self._wait(lambda: victim in
                   nodes[ctx["client"]].coordinator.state().nodes,
                   what=f"[{victim}] rejoining")
        self._wait(lambda: self._in_sync_full(nodes, leader),
                   timeout=30.0,
                   what=f"recovery after [{victim}] rejoined")
        _bump(ctx, "recoveries")

    # -- op execution ------------------------------------------------------

    def _execute(self, op: dict, ctx: dict) -> dict:
        client = ctx["nodes"][ctx["client"]]
        index = self.config.index
        kind = op["op"]
        if kind in ("search", "agg"):
            resp = client.search(index, dict(op["body"]))
            return {"partial": resp["_shards"]["failed"] > 0}
        if kind == "msearch":
            out = client.msearch(index,
                                 [dict(b) for b in op["bodies"]])
            partial = False
            for sub in out["responses"]:
                err = sub.get("error")
                if err is not None:
                    status = sub.get("status", 500)
                    if status == 429:
                        _bump(ctx, "rejected")
                    else:
                        raise SoakUnexpectedError(
                            f"msearch sub-request failed: {err}")
                elif sub["_shards"]["failed"] > 0:
                    partial = True
            return {"partial": partial}
        if kind == "bulk":
            for doc_id, source in op["docs"]:
                self._recorded_write(
                    ctx, "index", doc_id, source,
                    lambda d=doc_id, s=source:
                    client.index_doc(index, d, s))
            if op.get("delete"):
                self._recorded_write(
                    ctx, "delete", op["delete"], None,
                    lambda: client.delete_doc(index, op["delete"]))
            if op.get("refresh"):
                self._write_with_retry(
                    ctx, lambda: client.refresh(index))
            return {"partial": False}
        if kind == "scroll":
            from_, partial = 0, False
            for _ in range(op["max_pages"]):
                resp = client.search(index, {
                    "query": {"match_all": {}},
                    "size": op["page_size"], "from": from_,
                    "sort": [{"v": "asc"}]})
                partial = partial or resp["_shards"]["failed"] > 0
                got = len(resp["hits"]["hits"])
                from_ += got
                if got < op["page_size"]:
                    break
            return {"partial": partial}
        raise ValueError(kind)

    def _retryable(self, exc: OpenSearchTpuError) -> bool:
        from opensearch_tpu.common.errors import NodeDisconnectedError
        from opensearch_tpu.transport.service import (ReceiveTimeoutError,
                                                      RemoteTransportError)
        if isinstance(exc, (NodeDisconnectedError, ReceiveTimeoutError)):
            return True
        if isinstance(exc, RemoteTransportError):
            return exc.remote_type in _RETRYABLE_TYPES
        return getattr(exc, "error_type", "") in _RETRYABLE_TYPES \
            or getattr(exc, "status", 0) == 503

    def _write_with_retry(self, ctx: dict, fn: Callable[[], dict]):
        """Client-side bounded write retry (the reference client's
        retry-on-503): a transient transport failure retries after the
        cluster reconverges; exhaustion is an unexpected error."""
        last: Optional[BaseException] = None
        for attempt in range(self.config.max_retries + 1):
            try:
                return fn()
            except OpenSearchTpuError as exc:
                if not self._retryable(exc):
                    raise
                last = exc
                _bump(ctx, "client_retries")
                # reconvergence beat: the leader's checks evict dead
                # copies so the retry routes around them
                leader = ctx["leader"]
                if leader in ctx["nodes"]:
                    ctx["nodes"][leader].coordinator.run_checks_once()
                time.sleep(0.01 * (attempt + 1))   # backoff
        raise SoakUnexpectedError(
            f"write retries exhausted: {type(last).__name__}: {last}")

    def _recorded_write(self, ctx: dict, op: str, doc_id: str,
                        source: Optional[dict], fn: Callable[[], dict]):
        """A ``_write_with_retry`` with its interval recorded in the
        durability history: an ack is OK (with the response's
        ``(seq_no, primary_term, version)``), exhausted retries are
        UNKNOWN (an earlier attempt may have landed), and a
        first-attempt hard rejection is a definite FAIL."""
        hist = ctx["history"]
        op_id = hist.invoke(op, doc_id, source)
        attempts = {"n": 0}

        def counted():
            attempts["n"] += 1
            return fn()
        try:
            resp = self._write_with_retry(ctx, counted)
        except SoakUnexpectedError as exc:
            hist.unknown(op_id, f"retries exhausted: {exc}")
            raise
        except OpenSearchTpuError as exc:
            if attempts["n"] <= 1:
                # rejected outright — the write never applied anywhere
                hist.fail(op_id, f"{type(exc).__name__}: {exc}")
            else:
                # a retried attempt may have landed before this error
                hist.unknown(op_id, f"{type(exc).__name__}: {exc}")
            raise
        hist.ok(op_id, resp if isinstance(resp, dict) else {})
        return resp

    def _run_op(self, i: int, op: dict, ctx: dict) -> None:
        hist = ctx["hists"][op["op"]]
        t0 = time.monotonic()
        try:
            out = self._execute(op, ctx)
            if out.get("partial"):
                _bump(ctx, "partial_results")
        except SoakUnexpectedError as exc:
            ctx["unexpected"].append(f"op {i} [{op['op']}]: {exc}")
        except OpenSearchTpuError as exc:
            if getattr(exc, "status", 0) == 429:
                _bump(ctx, "rejected")
            elif self._retryable(exc) and op["op"] != "bulk":
                # reads fail over internally; a residual transport error
                # after failover is retried ONCE like a real client...
                try:
                    _bump(ctx, "client_retries")
                    out = self._execute(op, ctx)
                    if out.get("partial"):
                        _bump(ctx, "partial_results")
                except OpenSearchTpuError as exc2:
                    ctx["unexpected"].append(
                        f"op {i} [{op['op']}]: "
                        f"{type(exc2).__name__}: {exc2}")
            else:
                ctx["unexpected"].append(
                    f"op {i} [{op['op']}]: {type(exc).__name__}: {exc}")
        finally:
            hist.observe((time.monotonic() - t0) * 1000.0)

    # -- one full pass -----------------------------------------------------

    def _counter_snapshot(self) -> dict:
        return dict(metrics().stats()["counters"])

    def _run_once(self, label: str, inject: bool) -> dict:
        from opensearch_tpu.testing.fault_injection import FaultInjector
        from opensearch_tpu.transport.service import LocalTransport

        cfg = self.config
        root = f"{self.data_path}/{label}"
        hub = LocalTransport.Hub()
        nodes = {nid: self._build_node(hub, nid, root)
                 for nid in cfg.node_ids}
        for sid in cfg.searcher_ids:
            nodes[sid] = self._build_node(hub, sid, root,
                                          roles=("search",))
        from opensearch_tpu.testing.history import HistoryRecorder
        ctx = {
            "lock": threading.Lock(),
            "hub": hub, "nodes": nodes, "root": root,
            "client": cfg.client, "leader": cfg.node_ids[0],
            "searchers": set(cfg.searcher_ids),
            "faults": FaultInjector(hub, seed=cfg.seed),
            # acked-write durability audit (testing/history.py): every
            # CRUD write records an invoke/ok|fail|unknown interval;
            # the post-drain DurabilityChecker replays it against the
            # final state + per-copy digests (both passes record, so
            # the checker is validated on the happy path too)
            "history": HistoryRecorder(),
            "applied": [], "saved_breaches": {},
            "rejected": 0, "partial_results": 0, "client_retries": 0,
            "recoveries": 0, "unexpected": [],
            "hists": {k: Histogram(f"soak.{k}")
                      for k in ("search", "msearch", "bulk", "agg",
                                "scroll")},
        }
        dh_saved = None
        if cfg.device_faults:
            # both passes run on a freshly-reset health service with a
            # snappy breaker: threshold 2, zero cooldown (open ->
            # half-open probe on the next request — wall-clock-free, so
            # verdicts stay deterministic)
            from opensearch_tpu.common.device_health import device_health
            dh = device_health()
            dh_saved = (dh.enabled, dh.failure_threshold,
                        dh.open_interval_s)
            dh.reset()
            dh.set_failure_threshold(2)
            dh.set_open_interval_s(0.0)
        before = self._counter_snapshot()
        workload = MixedWorkload(cfg)
        schedule = ((cfg.schedule if cfg.schedule is not None
                     else FaultSchedule.generate(cfg))
                    if inject else [])
        by_step: dict[int, list] = {}
        for d in schedule:
            by_step.setdefault(d["step"], []).append(d)
        try:
            if not nodes[ctx["leader"]].start_election():
                raise SoakHarnessError("initial election failed")
            self._wait(lambda: all(
                nodes[i].coordinator.state().master_node == ctx["leader"]
                for i in nodes if i not in ctx["searchers"]),
                what="initial leader convergence")
            for sid in sorted(ctx["searchers"]):
                nodes[ctx["leader"]].coordinator.add_node(
                    sid, self._searcher_info(sid))
            settings = {"number_of_shards": cfg.shards,
                        "number_of_replicas": cfg.replicas}
            if cfg.search_replicas:
                settings["number_of_search_replicas"] = \
                    cfg.search_replicas
            nodes[ctx["client"]].create_index(cfg.index, {
                "settings": settings,
                "mappings": {"properties": {
                    "body": {"type": "text"},
                    "ts": {"type": "date"},
                    "tag": {"type": "keyword"},
                    "v": {"type": "long"}}}})
            self._wait(lambda: self._in_sync_full(nodes, ctx["leader"]),
                       what="initial shard allocation")
            if ctx["searchers"]:
                self._wait(lambda: self._searchers_ready(ctx),
                           what="initial searcher refill")
            if cfg.autoscale:
                self._wire_autoscaler(ctx)
            for doc_id, source in workload.seed_docs():
                self._recorded_write(
                    ctx, "index", doc_id, source,
                    lambda d=doc_id, s=source:
                    nodes[ctx["client"]].index_doc(cfg.index, d, s))
            nodes[ctx["client"]].refresh(cfg.index)

            ops = workload.ops()
            if cfg.concurrency <= 1:
                for i, op in enumerate(ops):
                    for d in by_step.get(i, []):
                        self._apply_fault(d, ctx)
                    self._run_op(i, op, ctx)
            else:
                self._run_concurrent(ops, by_step, ctx)

            # drain: lift every remaining fault, restart anything still
            # dead, and wait for full in-sync recovery before measuring
            stall = ctx.pop("stall", None)
            if stall is not None:
                stall.release()
            remote_stall = ctx.pop("remote_stall", None)
            if remote_stall is not None:
                remote_stall.release()
            devfaults = ctx.get("devfaults")
            if devfaults is not None:
                devfaults.clear()       # schedule should have healed;
                #                         the drain lifts stragglers
            ctx["faults"].clear()
            disk = ctx.pop("disk", None)
            if disk is not None:
                disk.deactivate()
            disk_victim = ctx.pop("disk_victim", None)
            if disk_victim is not None and disk_victim in nodes:
                nodes[disk_victim].fs_health.check()
                if disk_victim not in \
                        nodes[ctx["leader"]].coordinator.state().nodes:
                    self._readmit(ctx, disk_victim)
            for nid, bp_breaches in list(ctx["saved_breaches"].items()):
                bp = nodes[nid].search_backpressure
                bp.force_duress(0)
                bp.run_once()
                bp.num_successive_breaches = bp_breaches
                del ctx["saved_breaches"][nid]
            if ctx.get("killed"):
                self._apply_fault({"fault": "restart_killed", "step": -1},
                                  ctx)
            self._wait(lambda: self._in_sync_full(nodes, ctx["leader"]),
                       timeout=30.0, what="post-drain recovery")
            self._write_with_retry(
                ctx, lambda: nodes[ctx["client"]].refresh(cfg.index))
            if ctx["searchers"]:
                # convergence must hold on the SEARCH tier too: every
                # ready searcher installs the final checkpoint before
                # the doc-count/checksum read (re-refreshing re-fires
                # the publish for any copy that missed one mid-churn)
                def tier_converged() -> bool:
                    if not self._searchers_ready(ctx):
                        return False
                    if self._searchers_caught_up(ctx):
                        return True
                    self._write_with_retry(
                        ctx, lambda: nodes[ctx["client"]].refresh(
                            cfg.index))
                    return self._searchers_caught_up(ctx)
                self._wait(tier_converged, timeout=30.0,
                           what="searcher-tier catch-up")
            final = self._final_state(ctx)
            # replication-safety audit, while the cluster is alive:
            # per-copy digest parity, then the acked-write history
            # replayed against the final state + those digests
            parity = self._copy_parity(ctx)
            durability = self._durability_report(
                ctx, parity.pop("copy_digests"))
            device_report = None
            if cfg.device_faults:
                # the breaker-state snapshot AFTER the drain + final
                # convergence search: the re-close SLO reads it (mesh
                # exempt — a 1-device CPU host can never rebuild the
                # mesh, so its breaker legitimately stays open)
                from opensearch_tpu.common.device_health import \
                    device_health
                dh = device_health()
                device_report = {
                    "breaker_states": dh.breaker_states(),
                    "tripped": dh.tripped_kinds(),
                    "poisoned_results": dh.stats()["poisoned_results"],
                }
            # snapshot the client/coordinator node's query-insights
            # section while the cluster is still alive: an SLO breach
            # capture below ships WITH the workload evidence (which
            # query shapes were hot when the SLO went red)
            query_insights = {
                "top_queries": nodes[ctx["client"]].insights.top(
                    by="latency", n=5),
                "coalescability":
                    nodes[ctx["client"]].insights.coalescability(),
                "totals": nodes[ctx["client"]].insights.stats(),
            }
            autoscale_report = None
            if cfg.autoscale and ctx.get("autoscaler") is not None:
                asc = ctx["autoscaler"]
                audit = (nodes[ctx["leader"]].qos.audit(64)
                         if ctx["leader"] in nodes else [])
                scale_audit = [
                    r for r in audit
                    if str(r.get("knob", "")).startswith("autoscale.")]
                autoscale_report = {
                    "scale_ups": asc.scale_ups,
                    "scale_downs": asc.scale_downs,
                    "hard_kills": asc.hard_kills,
                    "abandoned": asc.abandoned,
                    "drains_completed":
                        asc.scale_downs - asc.hard_kills,
                    "decisions_audited": len(scale_audit),
                    "audit": scale_audit[:8],
                    "searchers_final": sorted(ctx["searchers"]),
                }
        finally:
            disk = ctx.pop("disk", None)
            if disk is not None:     # exception path: unpatch open/fsync
                disk.deactivate()
            remote_stall = ctx.pop("remote_stall", None)
            if remote_stall is not None:   # exception path: unpatch reads
                remote_stall.release()
            devfaults = ctx.pop("devfaults", None)
            if devfaults is not None:   # unpatch the device entry points
                devfaults.deactivate()
            if cfg.device_faults:
                from opensearch_tpu.common.device_health import \
                    device_health
                dh = device_health()
                dh.reset()
                if dh_saved is not None:
                    dh.enabled, dh.failure_threshold, \
                        dh.open_interval_s = dh_saved
            for n in list(nodes.values()):
                n.stop()
        after = self._counter_snapshot()

        def delta(name: str) -> int:
            return after.get(name, 0) - before.get(name, 0)
        return {
            "label": label,
            "schedule": [dict(d) for d in schedule],
            "applied": ctx["applied"],
            "ops": len(ops),
            "latency_ms": {k: h.stats()
                           for k, h in ctx["hists"].items()},
            "p99_ms": {k: round(h.percentile(99), 3)
                       for k, h in ctx["hists"].items()},
            "rejected": ctx["rejected"],
            "partial_results": ctx["partial_results"],
            "client_retries": ctx["client_retries"],
            "recoveries": ctx["recoveries"],
            "unexpected_errors": list(ctx["unexpected"]),
            "sheds": delta("search.replica_selection.sheds"),
            "reroutes": delta("search.replica_selection.reroutes"),
            "failovers": delta("search.shard_failover"),
            # search-tier accounting (zeros when no tier configured)
            "searcher_refills": delta("segrep.refills"),
            "searcher_installs": delta("segrep.installs"),
            "remote_bytes_pulled": delta("segrep.bytes_pulled"),
            "internal_retries": sum(
                after.get(k, 0) - before.get(k, 0)
                for k in after if k.startswith("retry.")
                and k.endswith(".retries")),
            # replication-safety accounting: fence activity on both
            # sides (the deposed primary's refused acks, the replicas'
            # stale-op rejections), rollbacks/resyncs, and the
            # post-drain durability + copy-parity audit reports
            "fenced_ops": delta("replication.fenced_ops"),
            "stale_primary_rejections":
                delta("replication.stale_primary_rejections"),
            "replication_rollbacks": delta("replication.rollbacks"),
            "resyncs": delta("replication.resyncs"),
            "durability": durability,
            "copy_parity": parity,
            "final_state": final,
            "query_insights": query_insights,
            # accelerator fault accounting (present only for device
            # soaks): breaker trips/states, sanity-guard discards, and
            # every degradation path's counters
            # elasticity accounting (present only for autoscale soaks)
            **({"autoscale": autoscale_report}
               if cfg.autoscale and autoscale_report is not None
               else {}),
            **({"device": {
                **device_report,
                "breaker_trips": delta("device.breaker.trips"),
                "breaker_closes": delta("device.breaker.closes"),
                "device_errors": delta("device.errors"),
                "poisoned": delta("device.poisoned_results"),
                "restage_failures": delta("device.restage_failures"),
                "host_fallbacks": delta("device.host_fallback"),
                "mesh_fallbacks": delta("search.mesh.fallback"),
                "degraded_searches": delta("device.degraded_searches"),
            }} if device_report is not None else {}),
        }

    def _run_concurrent(self, ops, by_step, ctx) -> None:
        """Full-config mode: ops run on a small worker pool in chunks;
        fault directives still apply at their op index, between chunks
        (coarser interleaving — the smoke config stays sequential for
        bit-exact determinism)."""
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(
                max_workers=self.config.concurrency,
                thread_name_prefix="soak-worker") as pool:
            i = 0
            while i < len(ops):
                chunk = ops[i:i + self.config.concurrency]
                for j in range(i, i + len(chunk)):
                    for d in by_step.get(j, []):
                        self._apply_fault(d, ctx)
                futs = [pool.submit(self._run_op, i + j, op, ctx)
                        for j, op in enumerate(chunk)]
                for f in futs:
                    f.result()
                i += len(chunk)

    def _final_state(self, ctx: dict) -> dict:
        """Post-drain doc count + content checksum via the normal search
        path, all-or-nothing (a shard that cannot answer here is a
        convergence failure, reported as such).  The raw id → source
        map is stashed in ``ctx["final_docs"]`` for the durability
        audit (it replays the write history against exactly this
        client-visible state)."""
        client = ctx["nodes"][ctx["client"]]
        try:
            resp = client.search(self.config.index, {
                "query": {"match_all": {}}, "size": 10_000,
                "allow_partial_search_results": False})
        except OpenSearchTpuError as exc:
            ctx["final_docs"] = None
            return {"error": f"{type(exc).__name__}: {exc}"}
        ctx["final_docs"] = {h["_id"]: h["_source"]
                             for h in resp["hits"]["hits"]}
        docs = sorted(
            (h["_id"], json.dumps(h["_source"], sort_keys=True))
            for h in resp["hits"]["hits"])
        return {"doc_count": resp["hits"]["total"]["value"],
                "checksum": zlib.crc32(
                    json.dumps(docs).encode("utf-8"))}

    def _copy_parity(self, ctx: dict) -> dict:
        """Per-copy convergence: after the drain, the primary, every
        in-sync replica, and every ready searcher of each shard must
        serve the same per-doc ``(seq_no, primary_term, version)``
        digest (``InternalEngine.replication_digest``).  Write copies
        compare the full term-aware digest; the search tier compares
        the termless ``seq_digest`` (its copies are rebuilt from
        segment checkpoints, same seq/version lineage).  Retries
        briefly — replicas install published checkpoints
        asynchronously — then reports the LAST snapshot; a persistent
        mismatch is an SLO breach, not a harness error."""
        cfg = self.config
        nodes = ctx["nodes"]

        def snapshot():
            state = nodes[ctx["leader"]].coordinator.state()
            shards, digests, all_ok = [], [], True
            for s, e in enumerate(state.routing.get(cfg.index, [])):
                primary = e.get("primary")
                copies, searchers = [], []
                try:
                    if primary not in nodes:
                        raise SoakHarnessError(f"primary [{primary}] gone")
                    copies.append((f"{primary}:primary", nodes[
                        primary].indices[cfg.index].engine_for(
                        s).replication_digest()))
                    for r in (e.get("replicas") or []):
                        if r in (e.get("in_sync") or []) and r in nodes:
                            copies.append((f"{r}:replica", nodes[
                                r].indices[cfg.index].engine_for(
                                s).replication_digest()))
                    for r in (e.get("search_in_sync") or []):
                        if r in nodes:
                            searchers.append((f"{r}:search", nodes[
                                r].indices[cfg.index].engine_for(
                                s).replication_digest()))
                except (OpenSearchTpuError, KeyError) as exc:
                    shards.append({"shard": s, "ok": False,
                                   "error": f"{type(exc).__name__}: "
                                            f"{exc}"})
                    all_ok = False
                    continue
                pdig = copies[0][1]
                write_ok = len({d["digest"] for _, d in copies}) == 1
                search_ok = all(d["seq_digest"] == pdig["seq_digest"]
                                for _, d in searchers)
                row = {"shard": s, "ok": write_ok and search_ok,
                       "copies": {lbl: {"digest": d["digest"],
                                        "seq_digest": d["seq_digest"],
                                        "doc_count": d["doc_count"]}
                                  for lbl, d in copies + searchers}}
                if not (write_ok and search_ok):
                    # diagnosable evidence: which doc positions differ
                    base = copies[0][1]["docs"]
                    for lbl, d in copies[1:] + searchers:
                        diff = sorted(
                            k for k in set(base) | set(d["docs"])
                            if base.get(k) != d["docs"].get(k))[:10]
                        if diff:
                            row.setdefault("diverged", {})[lbl] = diff
                shards.append(row)
                all_ok = all_ok and row["ok"]
                digests += [(f"{lbl}/s{s}", d["docs"])
                            for lbl, d in copies + searchers]
            return {"ok": all_ok, "shards": shards,
                    "copy_digests": digests}

        report = snapshot()
        deadline = time.monotonic() + 10.0
        while not report["ok"] and time.monotonic() < deadline:  # deadline
            time.sleep(0.05)                                     # deadline
            report = snapshot()
        return report

    def _durability_report(self, ctx: dict, copy_digests: list) -> dict:
        """Run the ``DurabilityChecker`` over the recorded history,
        the final client-visible state, and the per-copy digests; bump
        the audit counter so ``_nodes/stats`` / ``/_metrics`` show how
        many acked-write promises were actually verified."""
        from opensearch_tpu.testing.history import DurabilityChecker
        hist = ctx["history"]
        hist.settle_open_as_unknown("soak drain")
        final_docs = ctx.get("final_docs")
        if final_docs is None:
            return {"ok": False, "checked_ops": hist.checked_ops,
                    "error": "final state unavailable"}
        report = DurabilityChecker(hist).check(final_docs, copy_digests)
        metrics().counter("replication.durability_checked_ops").inc(
            report["checked_ops"])
        return report

    # -- SLO evaluation ----------------------------------------------------

    def _verdicts(self, chaos: dict, control: Optional[dict]) -> list:
        slos = self.config.slos
        verdicts = []
        for klass, limit in sorted(
                (slos.get("p99_ms") or {}).items()):
            observed = chaos["p99_ms"].get(klass, 0.0)
            verdicts.append({"slo": f"p99_ms.{klass}",
                             "limit": limit, "observed": observed,
                             "ok": observed <= limit})
        total_ops = max(chaos["ops"], 1)
        rate = round(chaos["rejected"] / total_ops, 4)
        max_rate = slos.get("max_rejection_rate", 1.0)
        verdicts.append({"slo": "rejection_rate", "limit": max_rate,
                         "observed": rate, "ok": rate <= max_rate})
        budget = slos.get("max_unexpected_errors", 0)
        verdicts.append({
            "slo": "unexpected_errors", "limit": budget,
            "observed": len(chaos["unexpected_errors"]),
            "ok": len(chaos["unexpected_errors"]) <= budget})
        if slos.get("require_convergence", True) and control is not None:
            ok = (chaos["final_state"] == control["final_state"]
                  and "error" not in chaos["final_state"])
            verdicts.append({
                "slo": "convergence",
                "limit": control["final_state"],
                "observed": chaos["final_state"], "ok": ok})
        dur = chaos.get("durability") or {}
        if slos.get("no_lost_acked_writes"):
            lost = dur.get("lost_acked_writes", [])
            checked = int(dur.get("checked_ops", 0))
            verdicts.append({
                "slo": "no_lost_acked_writes", "limit": 0,
                "observed": {"lost": len(lost),
                             "checked_ops": checked,
                             **({"evidence": lost[:5]} if lost else {})},
                # an audit that checked NOTHING (or errored) is a
                # breach, not a free pass
                "ok": (not lost and checked > 0
                       and "error" not in dur)})
        if slos.get("no_stale_acks"):
            stale = dur.get("stale_acks", [])
            mono = dur.get("monotonicity_violations", [])
            conflicts = dur.get("copy_conflicts", [])
            bad = len(stale) + len(mono) + len(conflicts)
            verdicts.append({
                "slo": "no_stale_acks", "limit": 0,
                "observed": {"stale_acks": len(stale),
                             "monotonicity": len(mono),
                             "copy_conflicts": len(conflicts),
                             **({"evidence":
                                 (stale + mono + conflicts)[:5]}
                                if bad else {})},
                "ok": bad == 0 and "error" not in dur})
        if slos.get("require_copy_parity"):
            par = chaos.get("copy_parity") or {}
            mismatched = [s for s in par.get("shards", [])
                          if not s.get("ok")]
            verdicts.append({
                "slo": "copy_parity", "limit": [],
                "observed": mismatched,
                "ok": par.get("ok", False)})
        dev = chaos.get("device") or {}
        if slos.get("require_breaker_trip"):
            trips = int(dev.get("breaker_trips", 0))
            verdicts.append({"slo": "device_breaker_trip", "limit": 1,
                             "observed": trips, "ok": trips >= 1})
        if slos.get("require_breaker_reclose"):
            # every breaker that tripped must be closed again after the
            # heal — except the mesh, which on a 1-device CPU host can
            # never rebuild and stays legitimately demoted
            states = dev.get("breaker_states") or {}
            stuck = sorted(k for k in dev.get("tripped", [])
                           if k != "mesh"
                           and states.get(k) != "closed")
            verdicts.append({"slo": "device_breaker_reclose",
                             "limit": [], "observed": stuck,
                             "ok": not stuck})
        if slos.get("require_poison_detected"):
            poisoned = int(dev.get("poisoned", 0))
            verdicts.append({"slo": "device_poison_detected",
                             "limit": 1, "observed": poisoned,
                             "ok": poisoned >= 1})
        auto = chaos.get("autoscale") or {}
        if slos.get("require_scale_up"):
            # >= 1 scale-up that ALSO appended to the audit ring — an
            # unaudited fleet mutation fails the SLO even if it scaled
            ups = int(auto.get("scale_ups", 0))
            audited = int(auto.get("decisions_audited", 0))
            verdicts.append({"slo": "autoscale_scale_up_audited",
                             "limit": 1, "observed": min(ups, audited),
                             "ok": ups >= 1 and audited >= 1})
        if slos.get("require_drain_complete"):
            done = int(auto.get("drains_completed", 0))
            verdicts.append({"slo": "autoscale_drain_complete",
                             "limit": 1, "observed": done,
                             "ok": done >= 1})
        return verdicts

    def _capture_breaches(self, verdicts: list, chaos: dict) -> None:
        """Every breached SLO verdict gets a flight-recorder capture
        attached — recent spans + counter snapshot + the breach's own
        limit/observed pair — so a red verdict ships with diagnosable
        evidence, not just a boolean (the captures are also retrievable
        later via ``GET /_nodes/flight_recorder``).  Determinism note:
        the smoke suite compares ``(slo, ok)`` pairs, never the capture
        payloads, which carry timestamps by design."""
        from opensearch_tpu.common.telemetry import flight_recorder
        for v in verdicts:
            if v["ok"]:
                continue
            v["flight_recorder"] = flight_recorder().record(
                "slo_breach",
                f"soak SLO [{v['slo']}] breached",
                detail={"slo": v["slo"], "limit": v["limit"],
                        "observed": v["observed"],
                        "seed": self.config.seed,
                        "applied_faults": [
                            {"step": d.get("step"),
                             "fault": d.get("fault")}
                            for d in chaos.get("applied", [])],
                        "unexpected_errors":
                            list(chaos.get("unexpected_errors", [])),
                        # the top-queries snapshot taken while the
                        # cluster was alive: WHAT was running when the
                        # SLO went red, by plan signature
                        "query_insights":
                            chaos.get("query_insights") or {}})

    def run(self) -> dict:
        """Control pass (when configured) then chaos pass, then SLO
        evaluation.  Always returns the report; ``slo_ok`` is the single
        pass/fail bit and ``verdicts`` carries every breach."""
        try:
            control = (self._run_once("control", inject=False)
                       if self.config.control_run
                       and self.config.faults_enabled else None)
            chaos = self._run_once(
                "chaos", inject=self.config.faults_enabled)
            verdicts = self._verdicts(chaos, control)
            self._capture_breaches(verdicts, chaos)
            return {
                "seed": self.config.seed,
                "config": {"n_ops": self.config.n_ops,
                           "n_docs": self.config.n_docs,
                           "nodes": list(self.config.node_ids),
                           "shards": self.config.shards,
                           "replicas": self.config.replicas,
                           "faults_enabled": self.config.faults_enabled},
                "control": control,
                "chaos": chaos,
                "verdicts": verdicts,
                "slo_ok": all(v["ok"] for v in verdicts),
            }
        finally:
            if self._own_dir:
                shutil.rmtree(self.data_path, ignore_errors=True)


class SoakHarnessError(OpenSearchTpuError):
    """The harness itself failed (timeout waiting on cluster plumbing) —
    distinct from an SLO breach, which is REPORTED in the verdicts."""


class SoakUnexpectedError(OpenSearchTpuError):
    """A client-visible failure outside the allowed degradation classes
    (429 / partial results) — draws against the zero-5xx budget."""


def run_soak(data_path: Optional[str] = None, *,
             full: bool = False, **overrides) -> dict:
    """One-call entry point (bench.py's ``soak`` phase)."""
    cfg = (SoakConfig.full(**overrides) if full
           else SoakConfig.smoke(**overrides))
    return SoakRunner(data_path, cfg).run()


def run_device_soak(data_path: Optional[str] = None,
                    **overrides) -> dict:
    """One-call entry point for the accelerator-fault soak (bench.py's
    ``device_faults`` phase, tests/test_device_faults.py acceptance)."""
    return SoakRunner(data_path, SoakConfig.device(**overrides)).run()


def run_autoscale_soak(data_path: Optional[str] = None,
                       **overrides) -> dict:
    """One-call entry point for the elasticity soak (bench.py's
    ``autoscale`` phase, tests/test_autoscaler.py acceptance): hot-
    tenant pressure scales the fleet up, the idle window drains it
    back, SLOs hold through both transitions."""
    return SoakRunner(
        data_path, SoakConfig.autoscale_churn(**overrides)).run()


# -- noisy-neighbor QoS scenario -------------------------------------------


class NoisyNeighborRunner(SoakRunner):
    """The per-tenant QoS soak: two tenants against one coordinator —
    a well-behaved victim issuing sequential zipf-tail searches, and an
    aggressor flooding the zipf HEAD in concurrent bursts that exceed
    its carved admission share many times over.  Every shard query
    phase is slowed by a seeded delay so the bursts genuinely overlap
    inside the admission window.

    SLOs assert ISOLATION, not absence of overload: the victim's p99
    and 429-rate hold while the aggressor's flood is shed at the
    admission gate (its own 429s), and the adaptive QoS controller —
    ticked deterministically once per op — records at least one
    adaptation (with its triggering evidence) in the audit ring.
    Same-seed runs produce identical verdicts (two-run determinism,
    pinned in tests/test_qos.py)."""

    VICTIM = "tenant-victim"
    AGGRESSOR = "tenant-aggressor"

    def __init__(self, data_path: Optional[str] = None,
                 config: Optional[SoakConfig] = None, *,
                 burst: int = 12, delay_s: float = 0.03,
                 admission_permits: int = 8,
                 victim_share: float = 6.0,
                 aggressor_share: float = 1.0,
                 slos: Optional[dict] = None):
        super().__init__(data_path, config or SoakConfig(
            seed=42, n_ops=16, n_docs=24, control_run=False))
        self.burst = int(burst)
        self.delay_s = float(delay_s)
        self.admission_permits = int(admission_permits)
        self.victim_share = float(victim_share)
        self.aggressor_share = float(aggressor_share)
        self.qos_slos = slos if slos is not None else {
            # generous CI-safe bounds: verdicts must be deterministic
            # across runs/hosts; observed values track the trajectory
            "victim_p99_ms": 10_000.0,
            "victim_max_429_rate": 0.0,
            "aggressor_min_429": 1,
            "min_qos_adaptations": 1,
            "max_unexpected_errors": 0,
        }

    @contextlib.contextmanager
    def _as_tenant(self, node, tenant: str):
        """Run the enclosed client calls under a registered task whose
        X-Opaque-Id names the tenant — the same header threading the
        REST edge performs, so admission, sheds, and insights all
        attribute to the tenant."""
        from opensearch_tpu.common import tasks as taskmod
        task = node.task_manager.register(
            "rest:noisy_neighbor", f"[{tenant}]",
            headers={"X-Opaque-Id": tenant})
        token = taskmod.set_current(task)
        try:
            yield
        finally:
            taskmod.reset_current(token)
            node.task_manager.unregister(task)

    def _flood(self, coord, index: str, body: dict, ctx: dict) -> None:
        """One aggressor burst: ``burst`` concurrent identical
        zipf-head searches released by a barrier, each under the
        aggressor tenant.  The per-tenant admission carve means most of
        the burst 429s while the victim's permits stay untouched."""
        barrier = threading.Barrier(self.burst)

        def one():
            barrier.wait(timeout=10.0)
            t0 = time.monotonic()
            try:
                with self._as_tenant(coord, self.AGGRESSOR):
                    coord.search(index, dict(body))
                _bump(ctx, "aggr_ok")
            except OpenSearchTpuError as exc:
                if getattr(exc, "status", 0) == 429:
                    _bump(ctx, "aggr_429")
                elif self._retryable(exc):
                    _bump(ctx, "client_retries")
                else:
                    with ctx["lock"]:
                        ctx["unexpected"].append(
                            f"aggressor: {type(exc).__name__}: {exc}")
            finally:
                ctx["hists"]["aggressor"].observe(
                    (time.monotonic() - t0) * 1000.0)
        threads = [threading.Thread(target=one,
                                    name=f"noisy-aggr-{i}",
                                    daemon=True)
                   for i in range(self.burst)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)

    def run(self) -> dict:    # noqa: C901 — one linear scenario
        from opensearch_tpu.cluster import response_collector as rc_mod
        from opensearch_tpu.search import engine as engine_mod
        from opensearch_tpu.testing.fault_injection import FaultInjector
        from opensearch_tpu.transport.service import LocalTransport

        cfg = self.config
        root = f"{self.data_path}/noisy"
        hub = LocalTransport.Hub()
        nodes = {nid: self._build_node(hub, nid, root)
                 for nid in cfg.node_ids}
        # the coordinator-only client: no shards, so every shard query
        # phase crosses the transport hub and the seeded delay applies
        coord_id = "c0"
        nodes[coord_id] = self._build_node(hub, coord_id, root,
                                           roles=("master",))
        coord = nodes[coord_id]
        ctx = {
            "lock": threading.Lock(),
            "hists": {"victim": Histogram("noisy.victim"),
                      "aggressor": Histogram("noisy.aggressor")},
            "victim_ok": 0, "victim_429": 0,
            "aggr_ok": 0, "aggr_429": 0,
            "client_retries": 0, "unexpected": [],
        }
        # adaptive knobs are process-global module settings: save and
        # restore so the scenario leaves no trace in the suite
        saved_shed = rc_mod.SHED_OCCUPANCY
        saved_window = engine_mod.AUTO_WINDOW_MS
        faults = FaultInjector(hub, seed=cfg.seed)
        try:
            leader = cfg.node_ids[0]
            if not nodes[leader].start_election():
                raise SoakHarnessError("initial election failed")
            self._wait(lambda: all(
                nodes[i].coordinator.state().master_node == leader
                for i in cfg.node_ids), what="initial leader convergence")
            nodes[leader].coordinator.add_node(
                coord_id, {"name": coord_id, "roles": ["master"],
                           "master_eligible": True})
            self._wait(lambda: coord_id in
                       coord.coordinator.state().nodes,
                       what="coordinator-only node joining")
            nodes[leader].create_index(cfg.index, {
                "settings": {"number_of_shards": cfg.shards,
                             "number_of_replicas": cfg.replicas},
                "mappings": {"properties": {
                    "body": {"type": "text"}, "v": {"type": "long"}}}})
            self._wait(lambda: self._in_sync_full(nodes, leader),
                       what="initial shard allocation")
            workload = MixedWorkload(cfg)
            for doc_id, source in workload.seed_docs():
                nodes[leader].index_doc(cfg.index, doc_id,
                                        {"body": source["body"],
                                         "v": source["v"]})
            nodes[leader].refresh(cfg.index)

            # per-tenant QoS on the coordinator: a small carved budget
            # (aggressor gets ~1 permit), the adaptive controller armed
            # with single-tick hysteresis and a shed threshold it can
            # demonstrably walk down
            adm = coord.search_backpressure.admission
            adm.max_concurrent = self.admission_permits
            adm.set_tenant_shares({self.VICTIM: self.victim_share,
                                   self.AGGRESSOR: self.aggressor_share})
            coord.qos.set_enabled(True)
            coord.qos.hysteresis_ticks = 1
            rc_mod.SHED_OCCUPANCY = 0.5
            # seeded slowdown on every data node's query phase so the
            # aggressor's bursts genuinely overlap in the gate
            for nid in cfg.node_ids:
                faults.slow_search_node(nid, self.delay_s)

            queries = zipf_query_log(max(16, cfg.n_ops), cfg.vocab_size,
                                     seed=cfg.seed)
            head_body = {"query": {"match": {"body": "t0 t1"}},
                         "size": 10}
            qi = 0
            for i in range(cfg.n_ops):
                if i % 4 == 3:
                    self._flood(coord, cfg.index, head_body, ctx)
                else:
                    a, b = queries[qi % len(queries)]
                    qi += 1
                    body = {"query": {"match": {"body": f"t{a} t{b}"}},
                            "size": 10}
                    t0 = time.monotonic()
                    try:
                        with self._as_tenant(coord, self.VICTIM):
                            coord.search(cfg.index, body)
                        _bump(ctx, "victim_ok")
                    except OpenSearchTpuError as exc:
                        if getattr(exc, "status", 0) == 429:
                            _bump(ctx, "victim_429")
                        else:
                            ctx["unexpected"].append(
                                f"victim op {i}: "
                                f"{type(exc).__name__}: {exc}")
                    finally:
                        ctx["hists"]["victim"].observe(
                            (time.monotonic() - t0) * 1000.0)
                # deterministic controller pacing: exactly one
                # evaluation per op, so the adaptation count is a pure
                # function of the op stream's admission evidence
                coord.qos.run_once()

            report = self._qos_report(coord, ctx)
        finally:
            rc_mod.SHED_OCCUPANCY = saved_shed
            engine_mod.AUTO_WINDOW_MS = saved_window
            faults.clear()
            for n in list(nodes.values()):
                n.stop()
            if self._own_dir:
                shutil.rmtree(self.data_path, ignore_errors=True)
        return report

    def _qos_report(self, coord, ctx: dict) -> dict:
        slos = self.qos_slos
        victim_ops = ctx["victim_ok"] + ctx["victim_429"]
        victim_rate = (ctx["victim_429"] / victim_ops
                       if victim_ops else 0.0)
        victim_p99 = ctx["hists"]["victim"].percentile(99)
        qos_stats = coord.qos.stats()
        verdicts = [
            {"slo": "victim_p99_ms", "limit": slos["victim_p99_ms"],
             "observed": round(victim_p99, 3),
             "ok": victim_p99 <= slos["victim_p99_ms"]},
            {"slo": "victim_429_rate",
             "limit": slos["victim_max_429_rate"],
             "observed": round(victim_rate, 4),
             "ok": victim_rate <= slos["victim_max_429_rate"]},
            {"slo": "aggressor_shed",
             "limit": slos["aggressor_min_429"],
             "observed": ctx["aggr_429"],
             "ok": ctx["aggr_429"] >= slos["aggressor_min_429"]},
            {"slo": "qos_adaptations",
             "limit": slos["min_qos_adaptations"],
             "observed": qos_stats["adaptations"],
             "ok": (qos_stats["adaptations"]
                    >= slos["min_qos_adaptations"])},
            {"slo": "unexpected_errors",
             "limit": slos["max_unexpected_errors"],
             "observed": len(ctx["unexpected"]),
             "ok": (len(ctx["unexpected"])
                    <= slos["max_unexpected_errors"])},
        ]
        return {
            "seed": self.config.seed,
            "ops": self.config.n_ops,
            "burst": self.burst,
            "tenants": {
                self.VICTIM: {
                    "ops": victim_ops, "ok": ctx["victim_ok"],
                    "rejected": ctx["victim_429"],
                    "p99_ms": round(victim_p99, 3)},
                self.AGGRESSOR: {
                    "ops": ctx["aggr_ok"] + ctx["aggr_429"],
                    "ok": ctx["aggr_ok"],
                    "rejected": ctx["aggr_429"],
                    "p99_ms": round(
                        ctx["hists"]["aggressor"].percentile(99), 3)},
            },
            "client_retries": ctx["client_retries"],
            "unexpected_errors": list(ctx["unexpected"]),
            "admission": coord.search_backpressure.admission.stats(),
            "insights_tenants": coord.insights.tenants(),
            "qos": qos_stats,
            "verdicts": verdicts,
            "slo_ok": all(v["ok"] for v in verdicts),
        }


def run_noisy_neighbor(data_path: Optional[str] = None,
                       **overrides) -> dict:
    """One-call entry point for the noisy-neighbor QoS scenario
    (bench.py's ``qos`` phase, tests/test_qos.py's acceptance)."""
    cfg_keys = {"seed", "n_ops", "n_docs", "shards", "replicas",
                "vocab_size"}
    cfg_over = {k: v for k, v in overrides.items() if k in cfg_keys}
    run_over = {k: v for k, v in overrides.items() if k not in cfg_keys}
    cfg = SoakConfig(control_run=False,
                     **{"seed": 42, "n_ops": 16, "n_docs": 24,
                        **cfg_over})
    return NoisyNeighborRunner(data_path, cfg, **run_over).run()
