"""Cross-shard search over a device mesh: the scatter-gather phase as XLA
collectives.

Analog of the reference's coordinator fan-out + reduce
(action/search/AbstractSearchAsyncAction.java:223 run/performPhaseOnShard,
SearchPhaseController.sortDocs:175 merge) — but where the reference sends
per-shard RPCs and heap-merges topdocs on one coordinator node, here every
shard is a mesh device, scoring runs data-parallel on all shards at once,
and the merge is an ``all_gather`` of each shard's local top-k followed by
a redundant on-device re-top-k (riding ICI, no host round-trip).

Search-engine parallelism axes (SURVEY §2.3): corpus sharding == data
parallelism over docs ("shards" mesh axis); replica groups for read
throughput would be an outer mesh axis whose devices hold identical arrays
— no TP/PP analog exists because scoring is embarrassingly parallel over
docs.
"""

from __future__ import annotations

import collections
import threading
import time
from functools import partial
from typing import Optional

import numpy as np

import opensearch_tpu.common.jaxenv  # noqa: F401
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from opensearch_tpu.common.telemetry import metrics as _metrics
from opensearch_tpu.common.telemetry import tracer as _tracer
from opensearch_tpu.ops import bm25 as bm25_ops
from opensearch_tpu.search.fetch import filter_source


def make_mesh(n_devices: int, axis: str = "shards") -> Mesh:
    devs = jax.devices()[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def stack_shards(shard_list: list[dict]) -> dict:
    """Stack per-shard array dicts (identical bucketed shapes) along a new
    leading 'shards' axis, ready to place on the mesh."""
    out = {}
    for key in shard_list[0]:
        out[key] = np.stack([np.asarray(s[key]) for s in shard_list])
    return out


def put_on_mesh(stacked: dict, mesh: Mesh, axis: str = "shards") -> dict:
    """Place shard-stacked host arrays on the mesh.  Routed through the
    device ledger so the H2D transfer is byte-accounted (these are
    per-query inputs, not resident state — the resident mesh copies are
    the DeviceSegments MeshSearcher stages per device)."""
    from opensearch_tpu.common.device_ledger import device_ledger

    led = device_ledger()
    sharding = NamedSharding(mesh, P(axis))
    return {k: led.device_put(None, v, sharding, kind="mesh", name=k)
            for k, v in stacked.items()}


def prepare_match_query(segments: list, field: str, terms: list[str]):
    """Host-side prep: per-shard postings staged to COMMON bucketed shapes
    + per-shard term ids + GLOBAL collection stats (idf/avgdl summed over
    shards, so sharded scores match single-shard scores exactly — the
    DFS_QUERY_THEN_FETCH global-stats guarantee, ref search/dfs/DfsPhase.java).

    Ported onto the PR-5 eager impact tables (ROADMAP item 1's mesh
    leftover): instead of staging raw tfs + doc_lens and recomputing the
    BM25 norm per query on every device, each shard stages its
    PRECOMPUTED per-posting impact column (``Segment.impact_table`` at
    the GLOBAL avgdl — bit-identical to what the host scorer and the
    device kernels read), so the mesh query degenerates to the same
    gather + idf-weighted scatter the unified engine lowers everywhere
    else.  Byte-parity with the host path is pinned in
    tests/test_dist_search.py.

    Returns (stacked dict [S, ...], meta dict with n_pad/budget/k-free dims).
    """
    from opensearch_tpu.index.segment import pad_pow2

    n_pad = pad_pow2(max(s.n_docs for s in segments) + 1)
    t_pad = pad_pow2(max((len(s.postings[field].offsets) for s in segments
                          if field in s.postings), default=8))
    p_pad = pad_pow2(max((len(s.postings[field].doc_ids) for s in segments
                          if field in s.postings), default=8))
    q_pad = pad_pow2(len(terms))

    doc_count = sum(s.postings[field].docs_with_field
                    for s in segments if field in s.postings)
    total_len = sum(s.postings[field].total_len
                    for s in segments if field in s.postings)
    avgdl = total_len / doc_count if doc_count else 1.0
    dfs = []
    for t in terms:
        df = 0
        for s in segments:
            pf = s.postings.get(field)
            if pf is not None:
                tid = pf.term_id(t)
                if tid >= 0:
                    df += int(pf.df[tid])
        dfs.append(df)
    idfs = np.zeros(q_pad, np.float32)
    for i, df in enumerate(dfs):
        idfs[i] = bm25_ops.idf(df, doc_count)

    shards = []
    budget = 8
    for s in segments:
        pf = s.postings.get(field)
        sh = {
            "offsets": np.zeros(t_pad, np.int32),
            "doc_ids": np.full(p_pad, n_pad - 1, np.int32),
            "impacts": np.zeros(p_pad, np.float32),
            "tids": np.zeros(q_pad, np.int32),
            "active": np.zeros(q_pad, bool),
            "idfs": idfs,
            "weights": np.where(np.arange(q_pad) < len(terms), 1.0, 0.0
                                ).astype(np.float32),
        }
        if pf is not None:
            # the shard's eager impact table at the GLOBAL avgdl: no
            # per-query norm math ever reaches the mesh kernel
            impacts, _mx = s.impact_table(field, avgdl)
            sh["offsets"][: len(pf.offsets)] = pf.offsets
            sh["offsets"][len(pf.offsets):] = pf.offsets[-1]
            sh["doc_ids"][: len(pf.doc_ids)] = pf.doc_ids
            sh["impacts"][: len(impacts)] = impacts
            local_budget = 0
            for i, t in enumerate(terms):
                tid = pf.term_id(t)
                if tid >= 0:
                    sh["tids"][i] = tid
                    sh["active"][i] = True
                    local_budget += int(pf.df[tid])
            budget = max(budget, pad_pow2(local_budget))
        shards.append(sh)
    return stack_shards(shards), {"n_pad": n_pad, "budget": budget}


class MeshSearcher:
    """Distributed search over shards resident on a device mesh, one
    shard a chip, each staged on its own chip only.

    A scored top-k request runs ``ShardSearcher``'s own lowering: every
    shard compiles the query through its plan cache with its own
    statistics (the reference's default ``query_then_fetch``) and
    prepares each segment through ``ShardSearcher.segment_inputs``.
    Segment index ``g`` of every shard then runs as ONE SPMD program
    over the chips (``run_topk_mesh``): the program key common to the
    shards, the per-query inputs as one array sharded over the mesh,
    the resident columns as one global array (staged to one shape per
    segment index).  One more program (``merge_topk_mesh``) all-gathers
    every shard's per-segment top-k, merges by (score desc, shard asc,
    segment asc, doc asc) and packs the winners; the host reads it once.
    A request whose shards cannot share a program key (unequal segment
    counts, plans or shapes) is served shard by shard on the host path
    and counted (``search.mesh.fallback``).
    """

    def __init__(self, shard_searchers: list, mesh: Optional[Mesh] = None,
                 axis: str = "shards"):
        self.shards = shard_searchers
        self.axis = axis
        self.mesh = mesh if mesh is not None else make_mesh(
            len(shard_searchers), axis)
        self.devices = list(self.mesh.devices.flat)
        if len(self.devices) < len(self.shards):
            raise ValueError(
                f"mesh has {len(self.devices)} devices for "
                f"{len(self.shards)} shards")
        self._lock = threading.RLock()
        # bounded-cache: the compiled metric reduce (mesh_metric_aggs)
        self._merge_cache: dict = {}
        # per-(shard, segment) staging on the shard's own chip
        # (seg.device() would stage on the default device) — kept
        # across refreshes, pruned in update_shards
        self._dsegs: dict = {}
        # bounded-cache: a segment index's stagings, by its segment ids
        self._rows: dict = {}
        # bounded-cache: the global array over the chips of each set of
        # per-chip arrays, by their ids (LRU, _GLOBALS_MAX)
        self._globals: "collections.OrderedDict" = collections.OrderedDict()
        # bounded-cache: a sub-mesh per shard count, a min_score per value
        self._meshes: dict = {}
        self._min_scores: dict = {}
        # reported from the first request on, 0 until one falls back
        _metrics().counter("search.mesh.fallback")

    def update_shards(self, shard_searchers: list):
        """Swap in fresh per-shard searcher snapshots (after a refresh),
        keeping the device staging and compiled-merge caches — only
        segments that no longer exist anywhere are dropped."""
        if len(shard_searchers) > len(self.devices):
            raise ValueError(
                f"mesh has {len(self.devices)} devices for "
                f"{len(shard_searchers)} shards")
        with self._lock:
            self.shards = shard_searchers
            alive = {seg.seg_id for s in shard_searchers
                     for seg in s.segments}
            dsegs = {key: d for key, d in self._dsegs.items()
                     if key[1] in alive}
            if len(dsegs) != len(self._dsegs):
                self._dsegs = dsegs
                self._rows = {key: row for key, row in self._rows.items()
                              if alive.issuperset(key)}
                self._globals.clear()

    def _dseg(self, shard_i: int, seg, pads: Optional[dict] = None):
        from opensearch_tpu.index.segment import DeviceSegment

        with self._lock:
            d = self._dsegs.get((shard_i, seg.seg_id))
            if d is None or (pads is not None and d.pads != pads):
                with jax.default_device(self.devices[shard_i]):
                    d = DeviceSegment(seg, pads)
                self._dsegs[(shard_i, seg.seg_id)] = d
            return d

    def _row(self, g: int) -> list:
        """The stagings of segment index ``g``, one a shard on its chip,
        padded to the lengths the largest of them needs."""
        from opensearch_tpu.index.segment import DeviceSegment

        segs = [s.segments[g] for s in self.shards]
        key = tuple(seg.seg_id for seg in segs)
        row = self._rows.get(key)
        if row is None:
            with self._lock:
                row = self._rows.get(key)
                if row is None:
                    pads: dict = {}
                    for seg in segs:
                        for k, v in DeviceSegment.natural_pads(seg).items():
                            pads[k] = max(pads.get(k, 0), v)
                    row = [self._dseg(si, seg, pads)
                           for si, seg in enumerate(segs)]
                    self._rows[key] = row
        return row

    def _sub_mesh(self, n: int) -> Mesh:
        if n == len(self.devices):
            return self.mesh
        mesh = self._meshes.get(n)
        if mesh is None:
            mesh = self._meshes[n] = Mesh(np.asarray(self.devices[:n]),
                                          (self.axis,))
        return mesh

    def _min_score(self, mesh: Mesh, value) -> jax.Array:
        """``min_score`` (-inf when unset) replicated over the mesh."""
        from opensearch_tpu.common.device_ledger import device_ledger

        v = np.float32(-np.inf if value is None else value)
        key = (len(mesh.devices.flat), float(v))
        out = self._min_scores.get(key)
        if out is None:
            if len(self._min_scores) >= 16:
                self._min_scores.clear()
            out = self._min_scores[key] = device_ledger().stage_input(
                v, NamedSharding(mesh, P()))
        return out

    def _global(self, sharding, arrays: list):
        """One array over the chips from each shard's array on its own
        chip (no copy), or None where one lies elsewhere."""
        key = tuple(id(x) for x in arrays)
        with self._lock:
            hit = self._globals.get(key)
            if hit is not None and all(a is b for a, b in zip(hit[0],
                                                             arrays)):
                self._globals.move_to_end(key)
                return hit[1]
        for si, x in enumerate(arrays):
            if x.devices() != {self.devices[si]}:
                return None
        x = arrays[0]
        out = jax.make_array_from_single_device_arrays(
            (len(arrays) * x.shape[0],) + x.shape[1:], sharding,
            list(arrays))
        with self._lock:
            self._globals[key] = (tuple(arrays), out)
            if len(self._globals) > _GLOBALS_MAX:
                self._globals.popitem(last=False)
        return out

    def _on_mesh(self, sharding, trees: list):
        """The shards' (A, ins) of one segment index as one pytree of
        arrays over the mesh: resident columns glued by ``_global``,
        host inputs stacked and staged in one copy each
        (``stage_input``).  Returns (tree, per-shard leaf shapes), or
        None where the shards' trees differ in structure, shape or
        dtype, or a device array is not on its shard's chip."""
        from opensearch_tpu.common.device_ledger import device_ledger

        flat = [jax.tree_util.tree_flatten(t) for t in trees]
        treedef = flat[0][1]
        if any(f[1] != treedef for f in flat[1:]):
            return None
        leaves, shapes = [], []
        for j in range(len(flat[0][0])):
            col = [f[0][j] if isinstance(f[0][j], jax.Array)
                   else np.asarray(f[0][j]) for f in flat]
            x = col[0]
            if any(type(y) is not type(x) or y.shape != x.shape
                   or y.dtype != x.dtype for y in col[1:]):
                return None
            if isinstance(x, np.ndarray):      # a per-query host input
                host = np.concatenate(col) if x.ndim else np.stack(col)
                out = device_ledger().stage_input(host, sharding)
            elif x.ndim:
                out = self._global(sharding, col)
            else:
                out = None
            if out is None:
                return None
            leaves.append(out)
            shapes.append(tuple(x.shape))
        return (jax.tree_util.tree_unflatten(treedef, leaves),
                tuple(shapes))

    def supports_mesh_aggs(self, aggs_json: dict) -> bool:
        """True when every agg is a single-level numeric metric over a
        NUMERIC field — the family the ICI partial-reduce covers
        (sum/avg/min/max/value_count/stats); keyword value_count and
        friends stay on the host's ordinal path."""
        if not self.shards:
            return False
        ctx = self.shards[0].ctx
        for body in (aggs_json or {}).values():
            if not isinstance(body, dict):
                return False
            types = [k for k in body if k not in ("aggs", "aggregations",
                                                  "meta")]
            if (len(types) != 1 or types[0] not in _MESH_METRICS
                    or body.get("aggs") or body.get("aggregations")
                    or not isinstance(body[types[0]], dict)):
                return False
            field = body[types[0]].get("field")
            if not field:
                return False
            ft = ctx.field_type(field)
            if ft is None or ft.dv_kind not in ("long", "double"):
                return False
        return True

    def mesh_metric_aggs(self, body: dict, aggs_json: dict) -> dict:
        """size:0 metric-agg request fully on the mesh: every shard
        computes its (sum, count, min, max) partial on its own device,
        ONE collective reduces them over ICI, and the host reads back
        5 scalars per agg — no per-shard partial serialization."""
        import time as _time

        from opensearch_tpu.ops import aggs as agg_ops
        from opensearch_tpu.search.aggs import _finish_metric, parse_aggs
        from opensearch_tpu.search.compiler import compile_query
        from opensearch_tpu.search.executor import build_arrays
        from opensearch_tpu.search.query_dsl import parse_query
        from opensearch_tpu.search import plan as planmod

        t0 = _time.monotonic()
        reqs = parse_aggs(aggs_json)
        q = parse_query(body.get("query"))
        S = len(self.shards)
        neg_inf = jnp.asarray(np.float32(-np.inf))  # staging-ok: scalar
        # phase 1: per-shard on-device partials, async-dispatched
        per_agg_parts: dict[str, list] = {r.name: [] for r in reqs}
        for si, shard in enumerate(self.shards):
            dev = self.devices[si]
            with jax.default_device(dev):
                partial_rows = {r.name: [] for r in reqs}
                total = jnp.float64(0)
                if shard.segments:
                    plan, bind = compile_query(q, shard.ctx, scored=False)
                    needed = plan.arrays()
                    for seg in shard.segments:
                        dseg = self._dseg(si, seg)
                        A = build_arrays(dseg, needed, shard.mapper,
                                         live=shard.ctx.live_jnp(seg,
                                                                 dseg))
                        dims, ins = plan.prepare(bind, seg, dseg,
                                                 shard.ctx)
                        _sc, matched = planmod.run_full(plan, dims, A,
                                                        ins, neg_inf)
                        total = total + matched.sum().astype(jnp.float64)
                        for r in reqs:
                            col = dseg.numeric.get(r.params["field"])
                            if col is None:
                                continue
                            s_, c_, mn_, mx_ = agg_ops.masked_metrics(
                                col["values"], col["value_docs"], matched)
                            partial_rows[r.name].append(
                                (s_, c_, mn_, mx_))
                for r in reqs:
                    rows = partial_rows[r.name]
                    if rows:
                        s_ = sum(x[0] for x in rows)
                        c_ = sum(x[1] for x in rows)
                        mn_ = jnp.min(jnp.stack([x[2] for x in rows]))
                        mx_ = jnp.max(jnp.stack([x[3] for x in rows]))
                    else:
                        s_, c_ = jnp.float64(0), jnp.float64(0)
                        mn_ = jnp.float64(np.inf)
                        mx_ = jnp.float64(-np.inf)
                    # float64 partials: epoch-millis longs and >2^24
                    # counts must survive the collective bit-exact
                    per_agg_parts[r.name].append(jnp.stack(
                        [jnp.asarray(s_, jnp.float64),   # staging-ok: on-device scalars
                         jnp.asarray(c_, jnp.float64),   # staging-ok: on-device scalars
                         jnp.asarray(mn_, jnp.float64),  # staging-ok: on-device scalars
                         jnp.asarray(mx_, jnp.float64),  # staging-ok: on-device scalars
                         total]).reshape(1, 5))
        # phase 2: ONE collective per agg over ICI
        sharding = NamedSharding(self.mesh, P(self.axis))
        reduce = self._merge_cache.get("metric_reduce")
        if reduce is None:
            reduce = sharded_metric_reduce(self.mesh, self.axis)
            self._merge_cache["metric_reduce"] = reduce
        out_aggs = {}
        total_docs = 0
        for r in reqs:
            parts = jax.make_array_from_single_device_arrays(
                (S, 5), sharding, per_agg_parts[r.name])
            merged = np.asarray(reduce(parts))
            s_, c_, mn_, mx_, tot = merged
            total_docs = int(tot)
            out_aggs[r.name] = _finish_metric(
                r.type, (float(s_), int(c_),
                         float(mn_) if c_ else np.inf,
                         float(mx_) if c_ else -np.inf))
        return {
            "took": int((_time.monotonic() - t0) * 1000),
            "timed_out": False,
            "_shards": {"total": S, "successful": S, "skipped": 0,
                        "failed": 0},
            "hits": {"total": {"value": total_docs, "relation": "eq"},
                     "max_score": None, "hits": []},
            "aggregations": out_aggs,
        }

    def search(self, body: Optional[dict] = None) -> dict:
        """Scored top-k search (sort/aggs stay on the host path): one
        SPMD program a segment index, one merge program, one read."""
        body = body or {}
        S = len(self.shards)
        G = len(self.shards[0].segments) if S else 0
        _metrics().counter("search.mesh.requests").inc()
        _metrics().counter("search.mesh.shards").inc(S)
        with _tracer().start_span("mesh.search",
                                  {"shards": S, "segments": G}):
            resp = self._search(body, time.monotonic(), S, G)
            return self._scatter(body) if resp is None else resp

    def _search(self, body: dict, t0: float, S: int, G: int):
        """The response, or None where the shards cannot share the
        programs (unequal segment counts, plans, keys or shapes, or
        non-finite scores back from the chips)."""
        from opensearch_tpu.common.device_health import (check_finite,
                                                         device_health)
        from opensearch_tpu.common.device_ledger import device_ledger
        from opensearch_tpu.common.tasks import check_current
        from opensearch_tpu.search import plan as planmod
        from opensearch_tpu.search.profile import NULL_PROFILER, QueryProfiler

        size = int(body.get("size", 10))
        from_ = int(body.get("from", 0))
        k = max(from_ + size, 1)
        if not S or any(len(s.segments) != G for s in self.shards):
            return None
        profs = [QueryProfiler() if body.get("profile") else NULL_PROFILER
                 for _ in self.shards]
        compiled = []
        for si, shard in enumerate(self.shards):
            with jax.default_device(self.devices[si]):
                compiled.append(shard.compiled(
                    body.get("query"), scored=True, with_key=True,
                    prof=profs[si]))
        plan = compiled[0][0][0]
        if any(c[0][0] != plan for c in compiled[1:]):
            return None
        binds = [c[0][1] for c in compiled]
        needed = plan.arrays()
        mesh = self._sub_mesh(S)
        sharding = NamedSharding(mesh, P(self.axis))
        ms = self._min_score(mesh, body.get("min_score"))
        parts, seg_of_part = [], []
        for g in range(G):
            check_current()        # cancellation point per segment index
            with _tracer().start_span("mesh.prepare", {"segment": g}):
                row = self._row(g)
                segs = [s.segments[g] for s in self.shards]
                if not any(plan.can_match(b, seg)
                           for b, seg in zip(binds, segs)):
                    continue       # no staging, no program
                keys, trees = [], []
                with planmod.host_inputs():
                    for si, shard in enumerate(self.shards):
                        with jax.default_device(self.devices[si]):
                            _d, dims, ins, A = shard.segment_inputs(
                                plan, binds[si], segs[si], needed,
                                (_MESH_KEY, compiled[si][1]), profs[si],
                                dseg=row[si])
                        keys.append(dims)
                        trees.append((A, ins))
                key = _common_key(keys)
                if key is None:
                    return None
                placed = self._on_mesh(sharding, trees)
                if placed is None:
                    return None
                (A, ins), shapes = placed
                n_pad = row[0].n_pad
                kk = min(k, n_pad)
                program = planmod.choose(
                    plan, key, n_pad, kk,
                    all(s.ctx.all_live(seg)
                        for s, seg in zip(self.shards, segs)))
            with _tracer().start_span("mesh.launch", {"segment": g}):
                parts.append(run_topk_mesh(
                    mesh, self.axis, plan, key, kk, k, program.sorted_bag,
                    shapes, A, ins, ms))
            planmod.book(program, mesh=True)
            seg_of_part.append(g)

        total, max_score, rows = 0, None, []
        if parts:
            with _tracer().start_span("mesh.launch", {"segment": "merge"}):
                out = merge_topk_mesh(mesh, self.axis, k, tuple(parts))
                out.copy_to_host_async()
            planmod.book(planmod.Program(), mesh=True)
            t_sync = time.monotonic()
            with _tracer().start_span("device.sync", {"site": "mesh"},
                                      cpu=True):
                packed = np.asarray(out)
            device_ledger().record_fetch(packed.nbytes,
                                         time.monotonic() - t_sync)
            vals = packed[:k].view(np.float32)
            bad = check_finite(vals)
            if bad:
                device_health().record_poison(
                    kernel="merge_topk_mesh", segment="-",
                    index=self.shards[0].index_name, shard="mesh", bad=bad)
                return None
            total = int(packed[3 * k])
            per_part = len(parts) * k
            for j in range(k):
                if vals[j] == -np.inf:
                    break
                flat = int(packed[k + j])
                rows.append((float(vals[j]), flat // per_part,
                             seg_of_part[flat % per_part // k],
                             int(packed[2 * k + j])))
            if rows:
                max_score = rows[0][0]
        hits = []
        if size > 0 or from_ > 0:
            source_spec = body.get("_source")
            for score, si, g, local in rows[from_: from_ + size]:
                shard = self.shards[si]
                seg = shard.segments[g]
                hit = {"_index": shard.index_name, "_id": seg.doc_ids[local],
                       "_score": score, "_shard": shard.shard_id}
                src = filter_source(seg.source(local), source_spec)
                if src is not None:
                    hit["_source"] = src
                hits.append(hit)
        else:
            max_score = None   # count-only request, like the host path
        resp = {
            "took": int((time.monotonic() - t0) * 1000),
            "timed_out": False,
            "_shards": {"total": S, "successful": S, "skipped": 0,
                        "failed": 0},
            "hits": {"total": {"value": total, "relation": "eq"},
                     "max_score": max_score, "hits": hits},
        }
        if body.get("profile"):
            from opensearch_tpu.search.profile import describe_plan
            sections = []
            for si, shard in enumerate(self.shards):
                profs[si].set("execution_path", "device")
                sections.append(profs[si].shard_section(
                    shard.index_name, shard.shard_id,
                    plan_type=type(plan).__name__,
                    description=describe_plan(plan, binds[si]),
                    total_segments=G))
            resp["profile"] = {"shards": sections}
        return resp

    def _scatter(self, body: dict) -> dict:
        """The counted fallback: every shard on its own searcher's path,
        merged in the coordinator's order (``executor.scatter_merge``)."""
        from opensearch_tpu.search.executor import scatter_merge

        _metrics().counter("search.mesh.fallback").inc()
        resp = scatter_merge(self.shards, body)
        S = len(self.shards)
        resp["_shards"] = {"total": S, "successful": S, "skipped": 0,
                           "failed": 0}
        return resp


# the prepared-bindings cache key's mark: a mesh prepare leaves its
# inputs on the host, the shard's own path stages them
_MESH_KEY = "mesh"
_GLOBALS_MAX = 4096


def _common_key(keys: list):
    """The one program key of a segment index over the shards: their
    keys where equal, the largest gather bucket of a ``BagDims`` (a
    bucket is a capacity: more lanes than postings give the same
    bits); None where anything else differs."""
    from opensearch_tpu.search import plan as planmod

    first = keys[0]
    if all(d == first for d in keys[1:]):
        return first
    if not isinstance(first, tuple) or any(
            type(d) is not type(first) or len(d) != len(first)
            for d in keys[1:]):
        return None
    if isinstance(first, planmod.BagDims):
        if any(d[:1] + d[2:] != first[:1] + first[2:] for d in keys[1:]):
            return None
        return planmod.BagDims.of(max(d.postings for d in keys),
                                  first[0], *first[2:])
    if type(first) is not tuple:
        return None
    out = []
    for parts in zip(*keys):
        one = _common_key(list(parts))
        if one is None:
            return None
        out.append(one)
    return tuple(out)


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
def run_topk_mesh(mesh: Mesh, axis: str, plan, dims, k: int, k_out: int,
                  sorted_bag: bool, shapes: tuple, A, ins, min_score):
    """One segment index of every shard as ONE program over the mesh:
    each chip runs ``plan._run_topk`` on its shard's segment and packs
    its answer as ``run_topk`` does, padded to ``k_out`` (-inf) so that
    every segment index's answer has one width; the global result is
    ``int32[S * (2 k_out + 2)]``, a shard's block on its chip.
    ``shapes``: each leaf's per-shard shape (a scalar input crosses
    stacked as one element a chip)."""
    from opensearch_tpu.search import plan as planmod

    def local(A, ins, ms):
        leaves, tree = jax.tree_util.tree_flatten((A, ins))
        A, ins = jax.tree_util.tree_unflatten(
            tree, [x.reshape(s) for x, s in zip(leaves, shapes)])
        vals, idx, tot, mx = planmod._run_topk(plan, dims, k, A, ins, ms,
                                               sorted_bag)
        if k_out > k:
            vals = jnp.concatenate(
                [vals, jnp.full(k_out - k, -jnp.inf, vals.dtype)])
            idx = jnp.concatenate([idx, jnp.zeros(k_out - k, idx.dtype)])
        return planmod._pack_topk(vals, idx, tot, mx)

    return shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis), P()),
                     out_specs=P(axis), check_vma=False)(A, ins, min_score)


@partial(jax.jit, static_argnums=(0, 1, 2))
def merge_topk_mesh(mesh: Mesh, axis: str, k: int, parts: tuple):
    """The coordinator's reduce over ICI: each chip all-gathers every
    shard's packed answer of every launched segment index (``parts``,
    ``run_topk_mesh``'s), takes the top ``k`` of the ``S * G * k``
    candidates in shard-major, then segment, then rank order (the
    lower index wins a tie: score desc, shard asc, segment asc, doc
    asc) and packs, replicated: ``int32[3k + 2]`` = the scores' bits,
    the candidates' flat indices, their segment-local doc ids, the
    summed match count and the best score's bits."""

    def local(*blocks):
        allp = lax.all_gather(jnp.stack(blocks), axis)   # [S, G, 2k + 2]
        vals = lax.bitcast_convert_type(allp[:, :, :k],
                                        jnp.float32).reshape(-1)
        docs = allp[:, :, k:2 * k].reshape(-1)
        fv, fi = lax.top_k(vals, k)
        mx = lax.bitcast_convert_type(allp[:, :, 2 * k + 1],
                                      jnp.float32).max()
        return jnp.concatenate([
            lax.bitcast_convert_type(fv, jnp.int32), fi.astype(jnp.int32),
            docs[fi], allp[:, :, 2 * k].sum().astype(jnp.int32)[None],
            lax.bitcast_convert_type(mx, jnp.int32)[None]])

    return shard_map(local, mesh=mesh, in_specs=(P(axis),) * len(parts),
                     out_specs=P(), check_vma=False)(*parts)


def sharded_metric_reduce(mesh: Mesh, axis: str = "shards"):
    """[S, 5] per-shard metric partials (sum, count, min, max, total) ->
    one replicated [5] over ICI — the device-side
    InternalAggregations.reduce for the metric family
    (SearchPhaseController.reducedQueryPhase riding the mesh instead of
    the coordinator's heap).

    ONE all-gather, then every device folds the S rows itself: the
    partials are float64 (see ``mesh_metric_aggs``), which the TPU
    emulates, and its emulated all-reduce implements sum only — a
    ``pmin``/``pmax`` over float64 does not compile there."""

    @partial(shard_map, mesh=mesh, in_specs=P(axis, None), out_specs=P(),
             check_vma=False)
    def reduce(parts):
        rows = lax.all_gather(parts[0], axis)          # [S, 5] everywhere
        return jnp.stack([rows[:, 0].sum(), rows[:, 1].sum(),
                          rows[:, 2].min(), rows[:, 3].max(),
                          rows[:, 4].sum()])

    return reduce


_MESH_METRICS = {"sum", "avg", "min", "max", "value_count", "stats"}


def sharded_impact_topk(mesh: Mesh, *, n_pad: int, budget: int, k: int,
                        axis: str = "shards"):
    """Build the jitted one-step distributed query: every device scores
    its own shard's postings block FROM ITS PRECOMPUTED IMPACT COLUMN
    (no norm recomputation — the port of ROADMAP item 1's mesh
    leftover) and the global top-k is reduced with an all-gather over
    the mesh axis.

    Inputs (per call): the ``prepare_match_query`` shard-stacked arrays
    [S, ...] for offsets/doc_ids/impacts/term_ids/active/idfs/weights.
    Returns (scores[k], global_doc_ids[k]) replicated on all devices;
    global doc id = shard * n_pad + local id, so ties break by
    (score desc, shard asc, local doc asc) — the coordinator merge
    order.  Scores are byte-identical to the host path's (same impact
    table, same accumulation order), pinned in tests/test_dist_search.py.
    """

    def local_step(offsets, doc_ids, impacts, tids, active, idfs,
                   weights):
        # shard_map hands each device a [1, ...] block — drop the axis
        scores = bm25_ops.impact_scores(  # engine-ok: mesh backend lowering of the unified engine
            offsets[0], doc_ids[0], impacts[0], tids[0], active[0],
            idfs[0], weights[0], n_pad=n_pad, budget=budget)
        vals, idx = lax.top_k(scores, k)
        shard = lax.axis_index(axis)
        gids = shard.astype(jnp.int64) * n_pad + idx
        all_vals = lax.all_gather(vals, axis)     # [S, k] on every device
        all_gids = lax.all_gather(gids, axis)
        fv, fi = lax.top_k(all_vals.reshape(-1), k)
        return fv, all_gids.reshape(-1)[fi]

    spec = P(axis)
    # check_vma=False: the outputs ARE replicated (all_gather + identical
    # re-top-k on every device) but the varying-mesh-axes checker cannot
    # infer that statically.
    fn = shard_map(local_step, mesh=mesh,
                   in_specs=(spec,) * 7,
                   out_specs=(P(), P()),
                   check_vma=False)
    return jax.jit(fn)
