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

from functools import partial
from typing import Optional

import numpy as np

import opensearch_tpu.common.jaxenv  # noqa: F401
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from opensearch_tpu.ops import bm25 as bm25_ops


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


def sharded_topk_merge(mesh: Mesh, k: int, axis: str = "shards"):
    """The coordinator reduce as an ICI collective: every device holds its
    shard's local top-k (vals[k] desc, rows already tie-broken locally);
    all-gather + redundant re-top-k yields the global top-k replicated on
    every device — replacing SearchPhaseController.sortDocs:175's host
    heap merge.  Returns (vals[k], flat_idx[k]) where flat_idx indexes the
    shard-major [S*k] concatenation (shard = flat_idx // k), so ties break
    (score desc, shard asc, local rank asc) exactly like the host merge."""

    def local(vals):
        av = lax.all_gather(vals[0], axis)          # [S, k] on every device
        fv, fi = lax.top_k(av.reshape(-1), k)
        return fv, fi

    return jax.jit(shard_map(local, mesh=mesh, in_specs=(P(axis),),
                             out_specs=(P(), P()), check_vma=False))


class MeshSearcher:
    """Distributed search over shards resident on a device mesh: ANY
    compiled plan (bool/range/match/phrase/knn/...) runs per shard on that
    shard's own device, and the cross-shard top-k merge is an all-gather
    collective riding ICI — the device-resident scatter-gather of SURVEY
    §2.3 (scoring stats are per-shard, like the reference's default
    query_then_fetch).

    One mesh device per shard; shards may have heterogeneous sizes and
    segment counts (each compiles its own bucketed program) — only the
    [S, k] merge is a single SPMD program.
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
        # bounded-cache: one compiled merge program per distinct k
        self._merge_cache: dict[int, object] = {}
        # per-(device, segment) staging cache (seg.device() would pin to
        # the default device; mesh copies are staged per device) — kept
        # across refreshes, pruned in update_shards
        self._dsegs: dict = {}

    def update_shards(self, shard_searchers: list):
        """Swap in fresh per-shard searcher snapshots (after a refresh),
        keeping the device staging and compiled-merge caches — only
        segments that no longer exist anywhere are dropped."""
        if len(shard_searchers) > len(self.devices):
            raise ValueError(
                f"mesh has {len(self.devices)} devices for "
                f"{len(shard_searchers)} shards")
        self.shards = shard_searchers
        alive = {seg.seg_id for s in shard_searchers for seg in s.segments}
        self._dsegs = {key: d for key, d in self._dsegs.items()
                       if key[1] in alive}

    def _dseg(self, shard_i: int, seg):
        from opensearch_tpu.index.segment import DeviceSegment

        d = self._dsegs.get((shard_i, seg.seg_id))
        if d is None:
            with jax.default_device(self.devices[shard_i]):
                d = DeviceSegment(seg)
            self._dsegs[(shard_i, seg.seg_id)] = d
        return d

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
        """Scored top-k search (sort/aggs stay on the host path)."""
        import time as _time

        from opensearch_tpu.search.compiler import compile_query
        from opensearch_tpu.search.executor import build_arrays
        from opensearch_tpu.search.fetch import filter_source
        from opensearch_tpu.search.query_dsl import parse_query
        from opensearch_tpu.search import plan as planmod

        body = body or {}
        t0 = _time.monotonic()
        size = int(body.get("size", 10))
        from_ = int(body.get("from", 0))
        k = max(from_ + size, 1)
        q = parse_query(body.get("query"))
        min_score = body.get("min_score")
        ms = np.float32(-np.inf if min_score is None else min_score)

        S = len(self.shards)
        # Phase 1: DISPATCH every shard's program to its device, keeping
        # only jnp handles — no host sync inside the loop, so the S
        # devices execute concurrently (jax async dispatch).
        shard_vals, shard_rows, totals = [], [], []
        for si, shard in enumerate(self.shards):
            dev = self.devices[si]
            with jax.default_device(dev):
                if not shard.segments:
                    shard_vals.append(
                        jnp.full((1, k), -jnp.inf, jnp.float32))
                    shard_rows.append((jnp.zeros(k, jnp.int32),
                                       jnp.zeros(k, jnp.int32)))
                    totals.append(jnp.int32(0))
                    continue
                plan, bind = compile_query(q, shard.ctx, scored=True)
                needed = plan.arrays()
                seg_vals, seg_ids, seg_locals = [], [], []
                total = jnp.int32(0)
                for gi, seg in enumerate(shard.segments):
                    dseg = self._dseg(si, seg)
                    A = build_arrays(dseg, needed, shard.mapper,
                                     live=shard.ctx.live_jnp(seg, dseg))
                    dims, ins = plan.prepare(bind, seg, dseg, shard.ctx)
                    kk = min(k, dseg.n_pad)
                    vals, idx, tot, _mx = planmod.run_topk_parts(
                        plan, dims, kk, A, ins, ms,
                        sorted_bag=plan.sorted_topk(dims, dseg.n_pad, kk)
                        and shard.ctx.all_live(seg))
                    if kk < k:                       # pad to common k
                        pad = k - kk
                        vals = jnp.concatenate(
                            [vals, jnp.full(pad, -jnp.inf, vals.dtype)])
                        idx = jnp.concatenate(
                            [idx, jnp.zeros(pad, idx.dtype)])
                    seg_vals.append(vals)
                    seg_ids.append(jnp.full(k, gi, jnp.int32))
                    seg_locals.append(idx)
                    total = total + tot
                # shard-local merge of per-segment top-k: flat concat is
                # segment-major, so top_k's lowest-index tie-break
                # reproduces the (score desc, seg asc, doc asc) Lucene
                # merge order
                cat_v = jnp.concatenate(seg_vals)
                row_v, pick = lax.top_k(cat_v, k)
                row_s = jnp.concatenate(seg_ids)[pick]
                row_l = jnp.concatenate(seg_locals)[pick]
                shard_vals.append(row_v.reshape(1, k))
                shard_rows.append((row_s, row_l))
                totals.append(total)

        # Phase 2: device-collective merge over the mesh (the flagship
        # reduce riding ICI)
        sharding = NamedSharding(self.mesh, P(self.axis))
        vals_g = jax.make_array_from_single_device_arrays(
            (S, k), sharding, shard_vals)
        merge = self._merge_cache.get(k)
        if merge is None:
            merge = sharded_topk_merge(self.mesh, k, self.axis)
            self._merge_cache[k] = merge
        fv, fi = merge(vals_g)

        # Phase 3: host-side fetch of the k winners (first host sync)
        from opensearch_tpu.common.device_ledger import device_ledger
        t_sync = _time.monotonic()
        fv = np.asarray(fv)
        fi = np.asarray(fi)
        rows_np = [(np.asarray(s_), np.asarray(l_))
                   for s_, l_ in shard_rows]
        total = int(sum(int(t) for t in totals))
        device_ledger().record_fetch(
            fv.nbytes + fi.nbytes
            + sum(s_.nbytes + l_.nbytes for s_, l_ in rows_np),
            _time.monotonic() - t_sync)

        hits = []
        source_spec = body.get("_source")
        max_score = None
        if size > 0 or from_ > 0:
            for val, flat in zip(fv, fi):
                if val == -np.inf:
                    break
                shard_i, pos = divmod(int(flat), k)
                seg_i = int(rows_np[shard_i][0][pos])
                local = int(rows_np[shard_i][1][pos])
                shard = self.shards[shard_i]
                seg = shard.segments[seg_i]
                hit = {"_index": shard.index_name,
                       "_id": seg.doc_ids[local],
                       "_score": float(val), "_shard": shard.shard_id}
                src = filter_source(seg.source(local), source_spec)
                if src is not None:
                    hit["_source"] = src
                hits.append(hit)
            if hits:
                max_score = hits[0]["_score"]
            hits = hits[from_: from_ + size]
        # size=0: count-only request — null max_score, like the host path

        return {
            "took": int((_time.monotonic() - t0) * 1000),
            "timed_out": False,
            "_shards": {"total": S, "successful": S, "skipped": 0,
                        "failed": 0},
            "hits": {"total": {"value": total, "relation": "eq"},
                     "max_score": max_score,
                     "hits": hits},
        }


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
