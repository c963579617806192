"""Per-node index registry: index lifecycle, shard routing, document and
search entry points.

Analog of ``indices/IndicesService.java`` + ``index/IndexService.java`` +
``cluster/routing/OperationRouting.java``: an index is N shard engines;
writes route by murmur3(_id or routing) mod num_shards; node-local search
runs over ALL shards' segments in one ShardSearcher — which makes scoring
stats global (stronger than the reference's per-shard idf under plain
query_then_fetch) and reuses the segment merge path as the shard merge.
The mesh/distributed path (parallel/dist_search.py) is the multi-host
story; this service is the per-node control plane under it.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import threading
import time
import uuid
from typing import Optional

from opensearch_tpu.common.errors import (
    IllegalArgumentError,
    IndexAlreadyExistsError,
    IndexNotFoundError,
    OpenSearchTpuError,
    ResourceAlreadyExistsError,
    ResourceNotFoundError,
    ValidationError,
)
from opensearch_tpu.index.engine import InternalEngine, OpResult
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.search.executor import ShardSearcher


def murmur3_32(data: bytes, seed: int = 0) -> int:
    """murmur3 x86 32-bit (the reference's Murmur3HashFunction routing
    hash family; value compatibility with the JVM impl is not required —
    stability within this engine is)."""
    c1, c2 = 0xCC9E2D51, 0x1B873593
    h = seed
    length = len(data)
    rounded = length & ~3
    for i in range(0, rounded, 4):
        k = int.from_bytes(data[i: i + 4], "little")
        k = (k * c1) & 0xFFFFFFFF
        k = ((k << 15) | (k >> 17)) & 0xFFFFFFFF
        k = (k * c2) & 0xFFFFFFFF
        h ^= k
        h = ((h << 13) | (h >> 19)) & 0xFFFFFFFF
        h = (h * 5 + 0xE6546B64) & 0xFFFFFFFF
    k = 0
    tail = data[rounded:]
    if len(tail) >= 3:
        k ^= tail[2] << 16
    if len(tail) >= 2:
        k ^= tail[1] << 8
    if len(tail) >= 1:
        k ^= tail[0]
        k = (k * c1) & 0xFFFFFFFF
        k = ((k << 15) | (k >> 17)) & 0xFFFFFFFF
        k = (k * c2) & 0xFFFFFFFF
        h ^= k
    h ^= length
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & 0xFFFFFFFF
    h ^= h >> 16
    return h


_INDEX_NAME_FORBIDDEN = set('\\/*?"<>| ,#:')


def deep_merge_doc(base: dict, patch: dict) -> dict:
    """Recursive partial-document merge for _update: nested objects merge
    key-by-key, everything else (incl. arrays) replaces
    (XContentHelper.update / UpdateHelper semantics)."""
    out = dict(base)
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge_doc(out[k], v)
        else:
            out[k] = v
    return out


# cluster-level slowlog threshold defaults, keyed by the full dotted
# setting (e.g. "search.slowlog.threshold.query.warn") — populated by
# the node's _cluster/settings consumers; per-index settings override
# (the reference's index-setting-with-node-default layering)
SLOWLOG_DEFAULTS: dict = {}

# severity order matters: the slowest matching threshold wins, highest
# level first (SearchSlowLog's warn > info > debug > trace)
_SLOWLOG_LEVELS = (("warn", 30), ("info", 20), ("debug", 10),
                   ("trace", 5))


def _parse_millis(v) -> int:
    """Time expression -> ms ("500ms", "1.5s", "1m", "1d", bare
    number=ms); -1 disables (the slow-log convention).  Unparseable
    values log a warning once and disable rather than failing queries."""
    if isinstance(v, (int, float)):
        return int(v)
    s = str(v).strip().lower()
    for suffix, mult in (("ms", 1), ("s", 1000), ("m", 60_000),
                         ("h", 3_600_000), ("d", 86_400_000)):
        if s.endswith(suffix):
            try:
                return int(float(s[: -len(suffix)]) * mult)
            except ValueError:
                break
    try:
        return int(float(s))
    except ValueError:
        import logging
        logging.getLogger("opensearch_tpu.settings").warning(
            "unparseable time value [%s]; threshold disabled", v)
        return -1


def shard_id_for(doc_id: str, routing: Optional[str], num_shards: int) -> int:
    """THE routing decision — every layer (coordinator + data node) must
    agree on it, so it lives in exactly one place."""
    key = (routing if routing is not None else str(doc_id)).encode()
    return murmur3_32(key) % num_shards


class IndexService:
    """One index: mapper + N shard engines + searcher cache."""

    def __init__(self, name: str, data_path: str, settings: dict,
                 mappings: Optional[dict], persist_meta=None,
                 local_shard_ids: Optional[list[int]] = None):
        self.name = name
        self.data_path = data_path
        self.settings = settings
        self._persist_meta = persist_meta
        self.num_shards = int(settings.get("number_of_shards", 1))
        self.num_replicas = int(settings.get("number_of_replicas", 0))
        if self.num_shards < 1:
            raise IllegalArgumentError(
                f"number_of_shards must be >= 1, got {self.num_shards}")
        self.creation_date = int(time.time() * 1000)  # wall-clock: timestamp
        self.uuid = uuid.uuid4().hex[:22]
        self.mapper = DocumentMapper(mappings or {})
        self._durability = settings.get("translog", {}).get("durability",
                                                            "request")
        # index.codec (ref index/codec/CodecService.java:46): default vs
        # best_compression, fixed at index creation like the reference
        self._codec = str(settings.get("codec", "default"))
        from opensearch_tpu.index.store import CODECS
        if self._codec not in CODECS:
            raise IllegalArgumentError(
                f"unknown value for [index.codec]: [{self._codec}] — "
                f"supported: {list(CODECS)}")
        # in cluster mode a node hosts only the shards routed to it
        # (IndicesClusterStateService analog); standalone hosts all
        if local_shard_ids is None:
            local_shard_ids = list(range(self.num_shards))
        self.local_shards: dict[int, InternalEngine] = {
            s: self._open_shard(s) for s in sorted(local_shard_ids)}
        self._lock = threading.RLock()
        self._searcher: Optional[ShardSearcher] = None
        self._mesh_searcher = None
        # search-visibility generation: bumped whenever the searchable
        # segment set may have changed (refresh / checkpoint install /
        # shard set change / mapping change).  The request cache keys on
        # it, so stale entries stop matching the moment anything moves
        # (IndicesRequestCache's reader-generation key).
        self._reader_gen = 0

    def _open_shard(self, shard_id: int) -> InternalEngine:
        return InternalEngine(os.path.join(self.data_path, str(shard_id)),
                              self.mapper, index_name=self.name,
                              shard_id=shard_id,
                              durability=self._durability,
                              codec=self._codec)

    @property
    def shards(self) -> list[InternalEngine]:
        return list(self.local_shards.values())

    def add_local_shard(self, shard_id: int):
        with self._lock:
            if shard_id not in self.local_shards:
                self.local_shards[shard_id] = self._open_shard(shard_id)
                self._searcher = None
                self._mesh_searcher = None

    def remove_local_shard(self, shard_id: int):
        with self._lock:
            engine = self.local_shards.pop(shard_id, None)
            if engine is not None:
                engine.close()
                self._searcher = None
                self._mesh_searcher = None

    def reset_local_shard(self, shard_id: int):
        """Drop a shard copy's on-disk state entirely and reopen empty —
        the corruption-failover primitive: a copy that failed store
        verification is discarded (corruption markers included) and
        re-recovered from the primary (the reference deletes the shard
        directory before re-allocating a failed copy there)."""
        import shutil
        with self._lock:
            engine = self.local_shards.pop(shard_id, None)
            if engine is not None:
                engine.close()
            shutil.rmtree(os.path.join(self.data_path, str(shard_id)),
                          ignore_errors=True)
            self.local_shards[shard_id] = self._open_shard(shard_id)
            self._searcher = None
            self._mesh_searcher = None
            self._reader_gen += 1

    def corrupted_shards(self) -> dict:
        """shard_id -> corruption markers/verdicts for local copies that
        failed store verification (the red-status evidence
        ``_cluster/health`` and ``_cat/indices`` surface)."""
        from opensearch_tpu.index.store import find_corruption_markers
        out = {}
        for sid, engine in sorted(self.local_shards.items()):
            markers = find_corruption_markers(
                os.path.join(engine.data_path, "segments"))
            if engine.corruption is not None and not markers:
                markers = [{"reason": str(engine.corruption)}]
            if markers:
                out[sid] = markers
        return out

    # -- routing ----------------------------------------------------------

    def route_shard(self, doc_id: str, routing: Optional[str] = None) -> int:
        return shard_id_for(doc_id, routing, self.num_shards)

    def engine_for(self, shard_id: int) -> InternalEngine:
        engine = self.local_shards.get(shard_id)
        if engine is None:
            from opensearch_tpu.common.errors import ShardNotFoundError
            raise ShardNotFoundError(
                f"shard [{self.name}][{shard_id}] is not on this node")
        return engine

    def route(self, doc_id: str, routing: Optional[str] = None) -> InternalEngine:
        return self.engine_for(self.route_shard(doc_id, routing))

    # -- document ops -----------------------------------------------------

    def _check_write_block(self):
        if self.settings.get("remote_snapshot"):
            from opensearch_tpu.common.errors import ClusterBlockException
            raise ClusterBlockException(
                f"index [{self.name}] blocked by: [FORBIDDEN/13/remote "
                "index is read-only (searchable snapshot)]")
        blocked = self.settings.get(
            "index.blocks.write",
            (self.settings.get("blocks") or {}).get("write", False))
        if str(blocked).lower() == "true":
            from opensearch_tpu.common.errors import ClusterBlockException
            raise ClusterBlockException(
                f"index [{self.name}] blocked by: [FORBIDDEN/8/index "
                "write (api)]")

    # node-level tracker injected by IndicesService at registration;
    # None = standalone IndexService (tests) with no admission control
    indexing_pressure = None

    def index_doc(self, doc_id: Optional[str], source: dict,
                  routing: Optional[str] = None,
                  op_bytes: Optional[int] = None, **kw) -> OpResult:
        """``op_bytes``: the caller's known wire size (REST passes the
        raw body length so the hot path never re-serializes just to
        measure)."""
        self._check_write_block()
        t0 = time.monotonic()
        if doc_id is None:
            doc_id = uuid.uuid4().hex[:20]
        shard = self.route_shard(str(doc_id), routing)
        engine = self.engine_for(shard)
        if self.indexing_pressure is not None:
            if op_bytes is None:
                op_bytes = len(json.dumps(source, separators=(",", ":")))
            with self.indexing_pressure.coordinating((self.name, shard),
                                                     int(op_bytes)):
                result = engine.index(str(doc_id), source,
                                      routing=routing, **kw)
                engine.ensure_synced()
        else:
            result = engine.index(str(doc_id), source, routing=routing,
                                  **kw)
            engine.ensure_synced()
        self._maybe_indexing_slowlog(
            int((time.monotonic() - t0) * 1000), result.doc_id, source)
        return result

    def delete_doc(self, doc_id: str, routing: Optional[str] = None,
                   **kw) -> OpResult:
        self._check_write_block()
        engine = self.route(doc_id, routing)
        result = engine.delete(str(doc_id), **kw)
        engine.ensure_synced()
        return result

    def get_doc(self, doc_id: str, routing: Optional[str] = None,
                realtime: bool = True) -> Optional[dict]:
        return self.route(doc_id, routing).get(str(doc_id), realtime=realtime)

    def bulk(self, ops: list[tuple]) -> list[dict]:
        """ops: [(action, doc_id, source, params)] — per-item results, errors
        reported per item like TransportShardBulkAction (never aborts the
        batch)."""
        from opensearch_tpu.common.errors import OpenSearchTpuError

        results = []
        touched = set()
        for action, doc_id, source, params in ops:
            try:
                if doc_id == "":
                    raise IllegalArgumentError(
                        "if _id is specified it must not be empty")
                if action in ("index", "create"):
                    if action == "create" and doc_id is not None:
                        existing = self.get_doc(doc_id,
                                                params.get("routing"))
                        if existing is not None:
                            raise ValidationError(
                                f"[{doc_id}]: version conflict, document "
                                "already exists")
                    cas = {k: int(params[k])
                           for k in ("if_seq_no", "if_primary_term")
                           if params.get(k) is not None}
                    r = self.index_doc(doc_id, source,
                                       routing=params.get("routing"),
                                       op_bytes=params.get("op_bytes"),
                                       **cas)
                    results.append({action: {
                        "_index": self.name, "_id": r.doc_id,
                        "_version": r.version, "_seq_no": r.seq_no,
                        "_primary_term": r.primary_term,
                        "result": r.result,
                        "status": 201 if r.result == "created" else 200}})
                elif action == "delete":
                    r = self.delete_doc(doc_id, routing=params.get("routing"))
                    results.append({"delete": {
                        "_index": self.name, "_id": r.doc_id,
                        "_version": r.version, "_seq_no": r.seq_no,
                        "_primary_term": r.primary_term,
                        "result": r.result,
                        "status": 404 if r.result == "not_found" else 200}})
                elif action == "update":
                    cur = self.get_doc(doc_id, params.get("routing"))
                    from opensearch_tpu.common.errors import (
                        VersionConflictError)
                    if params.get("if_seq_no") is not None:
                        cur_seq = cur["_seq_no"] if cur is not None else -1
                        if int(params["if_seq_no"]) != cur_seq:
                            raise VersionConflictError(
                                doc_id, f"seq_no [{params['if_seq_no']}]",
                                f"seq_no [{cur_seq}]")
                    if params.get("if_primary_term") is not None:
                        cur_term = (cur.get("_primary_term", 1)
                                    if cur is not None else 0)
                        if int(params["if_primary_term"]) != cur_term:
                            raise VersionConflictError(
                                doc_id,
                                f"primary_term "
                                f"[{params['if_primary_term']}]",
                                f"primary_term [{cur_term}]")
                    if cur is not None and "_source" not in cur:
                        raise IllegalArgumentError(
                            f"[{doc_id}]: source is missing — partial "
                            "updates require [_source] to be enabled")
                    if cur is None:
                        if "upsert" in source:
                            merged = source["upsert"]
                        else:
                            from opensearch_tpu.common.errors import (
                                DocumentMissingError)
                            raise DocumentMissingError(self.name, doc_id)
                    else:
                        merged = deep_merge_doc(cur["_source"],
                                                source.get("doc", {}))
                    r = self.index_doc(doc_id, merged,
                                       routing=params.get("routing"))
                    src_spec = params.get("_source")
                    if src_spec is None and isinstance(source, dict):
                        src_spec = source.get("_source")
                    if src_spec:
                        from opensearch_tpu.search.fetch import (
                            filter_source)
                        spec = src_spec
                        if spec in ("true", "false"):
                            spec = spec == "true"
                        elif not isinstance(spec, bool):
                            spec = (spec.split(",")
                                    if isinstance(spec, str) else spec)
                        results.append({"update": {
                            "_index": self.name, "_id": r.doc_id,
                            "_version": r.version, "_seq_no": r.seq_no,
                            "result": "updated", "status": 200,
                            "get": {"found": True,
                                    "_source": filter_source(merged,
                                                             spec)}}})
                        continue
                    results.append({"update": {
                        "_index": self.name, "_id": r.doc_id,
                        "_version": r.version, "result": "updated",
                        "status": 200}})
                else:
                    raise ValidationError(f"unknown bulk action [{action}]")
                touched.add(action)
            except OpenSearchTpuError as e:
                results.append({action: {
                    "_index": self.name, "_id": doc_id, "status": e.status,
                    "error": e.to_xcontent()["error"]}})
        return results

    # -- search -----------------------------------------------------------

    def _dirty(self):
        from opensearch_tpu.indices.request_cache import request_cache
        with self._lock:
            self._searcher = None
            self._reader_gen += 1
        # eager cleanup: the generation bump already unreachable-izes the
        # old entries; dropping them keeps memory tracking visibility
        request_cache().invalidate_service(self.uuid)

    def refresh(self):
        for engine in self.shards:
            engine.refresh()
        self._dirty()

    def refresh_doc_shard(self, doc_id: str, routing: Optional[str] = None):
        """?refresh=true on a single-document write refreshes ONLY the
        owning shard (RestActions write-refresh semantics: other shards'
        pending ops stay invisible)."""
        self.route(doc_id, routing).refresh()
        self._dirty()

    def invalidate_searcher(self):
        """Drop the cached node-local searcher (segments changed outside
        the write path, e.g. a replica installed a checkpoint)."""
        self._dirty()

    def save_meta(self):
        """Persist the CURRENT mapping (incl. dynamically-added fields) —
        after a flush the translog can no longer re-derive them on replay."""
        if self._persist_meta is not None:
            self._persist_meta(self.name, self.settings,
                               self.mapper.to_mapping())

    # set by the node when a blob-repository registry exists; consulted
    # at flush time for remote-store mirroring (RemoteStoreRefreshListener
    # analog, at flush granularity).  repo_mutex_fn serializes against
    # the snapshot service's blob GC.
    repo_resolver = None
    repo_mutex_fn = None

    def _remote_repo(self):
        rs = self.settings.get("remote_store") or {}
        enabled = rs.get("enabled") in (True, "true")
        repo_name = rs.get("repository")
        if not enabled or not repo_name or self.repo_resolver is None:
            return None
        try:
            return self.repo_resolver(repo_name)
        except OpenSearchTpuError:
            # a vanished repository must NEVER block local durability —
            # flush proceeds, mirroring resumes when the repo returns
            import logging
            logging.getLogger("opensearch_tpu.remote_store").warning(
                "[%s] remote store repository [%s] unavailable; "
                "flushing locally only", self.name, repo_name)
            return None

    def flush(self):
        # local flush under the index lock (a concurrent flush's
        # merge-GC could delete segment files mid-upload); REMOTE
        # uploads happen after release so slow blob stores never stall
        # searches/shard ops, under the repo mutex so the snapshot GC
        # can't collect just-written blobs.  A per-index flush
        # generation orders uploads: a flush that lost the mutex race to
        # a NEWER flush skips its (stale) manifests entirely instead of
        # rolling the mirror back.
        if self.settings.get("remote_snapshot"):
            return                   # data lives in the repository
        with self._lock:
            self.save_meta()
            self._flush_gen = getattr(self, "_flush_gen", 0) + 1
            my_gen = self._flush_gen
            commits = {sid: engine.flush()
                       for sid, engine in sorted(
                           self.local_shards.items())}
        repo = self._remote_repo()
        if repo is None:
            return
        import logging

        from opensearch_tpu.index.remote_store import upload_shard
        mutex = (self.repo_mutex_fn(repo.name)
                 if self.repo_mutex_fn else None)
        try:
            if mutex is not None:
                mutex.acquire()
            # PER-SHARD generation marks: a shard whose manifest a
            # newer flush already wrote is never overwritten by an
            # older one, even when that newer flush partially failed
            shard_gens = getattr(self, "_uploaded_shard_gens", None)
            if shard_gens is None:
                shard_gens = self._uploaded_shard_gens = {}
            all_ok = True
            for shard_id, commit in commits.items():
                engine = self.local_shards.get(shard_id)
                if engine is None:
                    continue
                if shard_gens.get(shard_id, 0) > my_gen:
                    continue         # newer manifest already mirrored
                try:
                    upload_shard(repo, self.name, shard_id, engine,
                                 commit)
                    shard_gens[shard_id] = my_gen
                except Exception as e:  # noqa: BLE001 — best effort
                    # mirroring is BEST-EFFORT: local durability already
                    # succeeded; the mirror stays at its previous commit
                    all_ok = False
                    logging.getLogger(
                        "opensearch_tpu.remote_store").warning(
                        "[%s][%s] remote upload failed: %s", self.name,
                        shard_id, e)
            if (all_ok and my_gen == self._flush_gen
                    and getattr(self, "_meta_gen", 0) < my_gen):
                # meta only advances WITH the data, and only from the
                # LATEST flush — a stale flush writing current live
                # mappings beside mixed-generation manifests would
                # restore segments under the wrong schema
                import json as _json
                try:
                    repo.store.container(
                        f"remote/{self.name}").write_blob(
                        "_meta.json", _json.dumps({
                            "settings": dict(self.settings),
                            "mappings": self.mapper.to_mapping()
                        }).encode())
                    self._meta_gen = my_gen
                except Exception as e:  # noqa: BLE001 — best effort
                    logging.getLogger(
                        "opensearch_tpu.remote_store").warning(
                        "[%s] remote meta upload failed: %s",
                        self.name, e)
        finally:
            if mutex is not None:
                mutex.release()

    def force_merge(self, max_num_segments: int = 1):
        self._check_write_block()   # would write merged files locally
        for engine in self.shards:
            engine.force_merge(max_num_segments)
        self._dirty()

    def searcher(self) -> ShardSearcher:
        """Node-local search view: every shard's segments under one
        searcher (global stats; segment merge == shard merge).  Cached
        between refreshes — NRT visibility changes only at refresh."""
        with self._lock:
            if self._searcher is None:
                segs = []
                for engine in self.shards:
                    segs.extend(engine.acquire_searcher().segments)
                self._searcher = ShardSearcher(segs, self.mapper,
                                               index_name=self.name)
            return self._searcher

    def update_settings(self, flat: dict):
        """Apply a dynamic settings update; static settings reject
        (IndexScopedSettings.NOT_DYNAMIC check)."""
        for key, value in flat.items():
            bare = key[6:] if key.startswith("index.") else key
            if bare in ("number_of_shards", "routing_partition_size"):
                raise IllegalArgumentError(
                    f"final [{key}] setting: this setting is not "
                    "updateable")
            if bare == "number_of_replicas":
                self.num_replicas = int(value)
            self.settings[f"index.{bare}"] = value
        if self._persist_meta is not None:
            self._persist_meta(self.name, self.settings,
                               self.get_mapping().get("mappings"))

    def index_setting(self, key: str, default):
        """Per-index setting lookup accepting the dotted, bare, and
        nested-object key forms the create body may use."""
        v = self.settings.get(f"index.{key}", self.settings.get(key))
        if v is None:
            for root in (self.settings.get("index"), self.settings):
                node = root
                for part in key.split("."):
                    node = (node.get(part)
                            if isinstance(node, dict) else None)
                    if node is None:
                        break
                if node is not None:
                    v = node
                    break
        return default if v is None else v

    def _check_search_limits(self, body: dict):
        """Per-index request-size guards (IndexSettings.MAX_* family)."""
        mrw = int(self.index_setting("max_result_window", 10000))
        window = int(body.get("from", 0) or 0) + int(
            body.get("size", 10) if body.get("size") is not None else 10)
        if window > mrw:
            raise IllegalArgumentError(
                f"Result window is too large, from + size must be less "
                f"than or equal to: [{mrw}] but was [{window}]. See the "
                "scroll api for a more efficient way to request large "
                "data sets.")
        dvf = body.get("docvalue_fields") or []
        max_dvf = int(self.index_setting("max_docvalue_fields_search", 100))
        if len(dvf) > max_dvf:
            raise IllegalArgumentError(
                f"Trying to retrieve too many docvalue_fields. Must be "
                f"less than or equal to: [{max_dvf}] but was "
                f"[{len(dvf)}]. This limit can be set by changing the "
                "[index.max_docvalue_fields_search] index level setting.")
        sf = body.get("script_fields") or {}
        max_sf = int(self.index_setting("max_script_fields", 32))
        if len(sf) > max_sf:
            raise IllegalArgumentError(
                f"Trying to retrieve too many script_fields. Must be "
                f"less than or equal to: [{max_sf}] but was [{len(sf)}]. "
                "This limit can be set by changing the "
                "[index.max_script_fields] index level setting.")
        max_tc = int(self.index_setting("max_terms_count", 65536))

        def check_terms(node):
            if isinstance(node, dict):
                tq = node.get("terms")
                if isinstance(tq, dict):
                    for f, vals in tq.items():
                        if isinstance(vals, list) and len(vals) > max_tc:
                            raise IllegalArgumentError(
                                f"The number of terms [{len(vals)}] "
                                "used in the Terms Query request has "
                                "exceeded the allowed maximum of "
                                f"[{max_tc}]. This maximum can be set "
                                "by changing the [index.max_terms_count] "
                                "index level setting.")
                for v in node.values():
                    check_terms(v)
            elif isinstance(node, list):
                for v in node:
                    check_terms(v)
        if body.get("query") is not None:
            check_terms(body["query"])
        rescore = body.get("rescore")
        if rescore:
            spec = rescore[0] if isinstance(rescore, list) else rescore
            window = int(spec.get("window_size", 10))
            max_rw = int(self.index_setting("max_rescore_window", 10000))
            if window > max_rw:
                raise IllegalArgumentError(
                    f"Rescore window [{window}] is too large. It must "
                    f"be less than [{max_rw}]. This prevents allocating "
                    "massive heaps for storing the results to be "
                    "rescored. This limit can be set by changing the "
                    "[index.max_rescore_window] index level setting.")

    def search(self, body: Optional[dict] = None, *,
               agg_partials: bool = False) -> dict:
        body = dict(body or {})
        # request-level cache directive (the ?request_cache= param; the
        # REST layer validated it) must not leak into execution or the
        # cache key
        explicit_cache = body.pop("request_cache", None)
        self._check_search_limits(body)
        from opensearch_tpu.search import insights
        if self.should_cache_request(body, explicit_cache, agg_partials):
            from opensearch_tpu.indices.request_cache import request_cache
            resp, hit = request_cache().get_or_compute(
                index=self.name, svc_uuid=self.uuid, shard_key="_local",
                reader_gen=self._reader_gen, body=body,
                compute=lambda: self._execute_search(body, agg_partials))
            if hit:
                # the executor never ran: synthesize the insight record
                # here (the cache hit IS the workload evidence)
                insights.emit(
                    signature=insights.canonical_query(
                        body.get("query")),
                    scored=insights.scored_for_body(body),
                    took_ms=float(resp.get("took", 0)),
                    execution_path="cached", plan_cache="hit",
                    request_cache="hit", index=self.name)
            else:
                insights.annotate_last(request_cache="miss",
                                       index=self.name)
        else:
            resp = self._execute_search(body, agg_partials)
            insights.annotate_last(request_cache="bypass",
                                   index=self.name)
        self._maybe_slowlog(body, resp)
        return resp

    def _execute_search(self, body: dict, agg_partials: bool) -> dict:
        # ONE engine entry for every backend: the mesh router, the
        # continuous batcher, the device kernels and their host recovery
        # are decisions inside QueryEngine.execute, not separately-wired
        # code paths here (search/engine.py; tools/check_execution_paths
        # keeps new paths from bypassing it)
        from opensearch_tpu.common.device_health import \
            DeviceDegradedError
        from opensearch_tpu.search.engine import query_engine
        try:
            resp = query_engine().execute(self.searcher(), body,
                                          agg_partials=agg_partials,
                                          service=self)
        except DeviceDegradedError as exc:
            # an accelerator fault with no byte-identical host fallback
            # degrades to PR-2-style partial results (the same shape a
            # dead shard copy produces) instead of a 500 — unless the
            # client asked for all-or-nothing semantics
            if body.get("allow_partial_search_results") is False:
                raise
            return self._device_degraded_response(body, exc)
        resp["_shards"] = {"total": self.num_shards,
                           "successful": self.num_shards,
                           "skipped": 0, "failed": 0}
        return resp

    def _device_degraded_response(self, body: dict,
                                  exc: BaseException) -> dict:
        """Partial-results response for a device-degraded search: every
        local shard reports the device failure in ``_shards.failures[]``
        (ShardSearchFailure shape), hits are empty, and the insight
        record carries outcome ``device_degraded`` so the workload
        attribution shows WHO was degraded."""
        from opensearch_tpu.common.telemetry import metrics
        from opensearch_tpu.search import insights
        from opensearch_tpu.search.executor import (shard_failure_entry,
                                                    shards_section)
        metrics().counter("device.degraded_searches").inc()
        with self._lock:
            shard_ids = sorted(self.local_shards) or [0]
        failures = [shard_failure_entry(self.name, s, None, exc)
                    for s in shard_ids]
        insights.emit(
            signature=insights.canonical_query(body.get("query")),
            scored=insights.scored_for_body(body),
            took_ms=0.0, execution_path="device",
            plan_cache="miss", outcome="device_degraded")
        return {
            "took": 0,
            "timed_out": False,
            "_shards": shards_section(len(shard_ids), failures=failures),
            "hits": {"total": {"value": 0, "relation": "gte"},
                     "max_score": None, "hits": []},
        }

    def should_cache_request(self, body: dict, explicit,
                             agg_partials: bool = False) -> bool:
        """IndicesRequestCache admission policy (the reference's
        canCache): profile/PIT never cache; an explicit request-level
        ``request_cache`` wins over the ``index.requests.cache.enable``
        index setting; by default only hit-less (size=0) requests cache,
        like the reference."""
        if agg_partials:
            return False         # device partials aren't serializable
        if body.get("profile") or body.get("pit"):
            return False
        if explicit is not None:
            return bool(explicit)
        enabled = str(self.index_setting(
            "requests.cache.enable", True)).lower() != "false"
        size = int(body.get("size", 10)
                   if body.get("size") is not None else 10)
        return enabled and size == 0

    def _slowlog_threshold(self, key: str):
        """Per-index setting (either [index.]-prefixed or bare) over the
        cluster-level default (SLOWLOG_DEFAULTS)."""
        return self.index_setting(key, SLOWLOG_DEFAULTS.get(key))

    def _maybe_slowlog(self, body: dict, resp: dict):
        """index.search.slowlog.threshold.query.{warn,info,debug,trace}
        (ref index/SearchSlowLog.java:61): queries slower than the
        threshold log with the source at the matching level; the most
        severe matching threshold wins.  Dynamic: per-index via
        PUT /{index}/_settings, cluster default via _cluster/settings."""
        import logging
        took = resp.get("took", 0)
        for level, py_level in _SLOWLOG_LEVELS:
            raw = self._slowlog_threshold(
                f"search.slowlog.threshold.query.{level}")
            if raw is None:
                continue
            thr = _parse_millis(raw)
            if thr >= 0 and took >= thr:
                logging.getLogger(
                    "opensearch_tpu.index.search.slowlog").log(
                    py_level, "[%s] took[%dms], timed_out[%s], "
                    "source[%s]", self.name, took,
                    str(bool(resp.get("timed_out"))).lower(),
                    json.dumps(body.get("query") or {})[:256])
                # a tripped slow log is a flight-recorder trigger: the
                # capture carries the query source and — when the slow
                # query ran with profile:true — its phase breakdown,
                # so the slow query is diagnosable after the fact
                from opensearch_tpu.common.telemetry import \
                    flight_recorder
                detail = {"index": self.name, "took_ms": int(took),
                          "level": level,
                          "source": json.dumps(
                              body.get("query") or {})[:256]}
                if resp.get("profile"):
                    detail["profile"] = resp["profile"]
                flight_recorder().record(
                    "slow_log",
                    f"[{self.name}] search took {took}ms >= "
                    f"{level} threshold [{raw}]", detail)
                break

    def _maybe_indexing_slowlog(self, took_ms: int, doc_id: str,
                                source: dict):
        """index.indexing.slowlog.threshold.index.{warn,info,debug,trace}
        (ref index/IndexingSlowLog.java:64): writes slower than the
        threshold log doc id + truncated source."""
        import logging
        for level, py_level in _SLOWLOG_LEVELS:
            raw = self._slowlog_threshold(
                f"indexing.slowlog.threshold.index.{level}")
            if raw is None:
                continue
            thr = _parse_millis(raw)
            if thr >= 0 and took_ms >= thr:
                max_chars = int(self.index_setting(
                    "indexing.slowlog.source", 1000))
                logging.getLogger(
                    "opensearch_tpu.index.indexing.slowlog").log(
                    py_level, "[%s/%s] took[%dms], source[%s]",
                    self.name, doc_id, took_ms,
                    json.dumps(source)[:max_chars])
                break

    # -- device-mesh search path (index.search.mesh: true) ----------------

    def _use_mesh(self, body: dict) -> bool:
        """Route through the device-collective scatter-gather when the
        index opted in, shards fit the mesh, and the request is a scored
        top-k (sort/aggs reduce on the host path for now).  Semantics
        match the multi-node cluster path: per-shard scoring stats
        (query_then_fetch), vs the merged-searcher host path's global
        stats."""
        flag = self.settings.get("search.mesh")
        if flag in (None, False, "false"):
            return False
        if len(self.local_shards) < 2:
            return False
        if body.get("sort") is not None:
            return False
        if body.get("profile"):
            # phase attribution instruments the host pipeline; profiled
            # requests route there (hits are parity-tested identical)
            return False
        q = body.get("query")
        if isinstance(q, dict) and "hybrid" in q:
            return False       # hybrid dispatches inside ShardSearcher
        import jax

        return len(jax.devices()) >= len(self.local_shards)

    def _mesh_degrade(self, body: dict, reason: str) -> dict:
        """Demote a mesh request to the counted host scatter fallback:
        a mesh that cannot be built (member loss / too few devices), an open ``mesh`` circuit breaker, or a
        device error mid-collective all land here — the request
        degrades (same per-shard scoring stats, coordinator-order
        merge), never 500s."""
        from opensearch_tpu.common.telemetry import metrics
        from opensearch_tpu.search import insights
        metrics().counter("search.mesh.fallback").inc()
        with insights.suppressed():
            resp = self._host_scatter_search(body)
        insights.emit(
            signature=insights.canonical_query(body.get("query")),
            scored=True, took_ms=float(resp.get("took", 0)),
            execution_path="mesh_fallback", plan_cache="miss")
        return resp

    def _mesh_search(self, body: dict) -> dict:
        from opensearch_tpu.common.device_health import (device_health,
                                                         is_device_error)
        from opensearch_tpu.search import insights
        from opensearch_tpu.parallel.dist_search import MeshSearcher
        health = device_health()
        if not health.allow("mesh"):
            # open mesh breaker: don't re-attempt a failing collective
            # per request — demote until a half-open probe re-closes it
            return self._mesh_degrade(body, "mesh circuit breaker open")

        try:
            with self._lock:
                shards = [self.local_shards[s].acquire_searcher()
                          for s in sorted(self.local_shards)]
                if (self._mesh_searcher is None
                        or len(self._mesh_searcher.shards)
                        != len(shards)):
                    self._mesh_searcher = MeshSearcher(shards)
                else:
                    # keep the per-device staging + compiled merge
                    # caches across refreshes; only the searcher
                    # snapshots change
                    self._mesh_searcher.update_shards(shards)
                ms = self._mesh_searcher
        except Exception as exc:
            # a mesh that cannot be BUILT (fewer live devices than
            # shards = member loss) is a mesh fault, not a query fault
            with self._lock:
                self._mesh_searcher = None
            health.record_failure("mesh", exc)   # counted: device.errors
            return self._mesh_degrade(
                body, f"mesh construction failed: {exc}")

        def collective(fn):
            """Run one mesh collective; device errors demote to the
            host scatter fallback (counted) instead of raising."""
            try:
                out = fn()
            except Exception as exc:
                if not is_device_error(exc):
                    raise
                health.record_failure("mesh", exc)  # counted: device.errors
                return None
            health.record_success("mesh")
            return out

        aggs_json = body.get("aggs") or body.get("aggregations")
        if not aggs_json and not body.get("suggest"):
            resp = collective(lambda: ms.search(body))
            if resp is None:
                return self._mesh_degrade(body, "mesh collective failed")
            insights.emit(
                signature=insights.canonical_query(body.get("query")),
                scored=True, took_ms=float(resp.get("took", 0)),
                execution_path="mesh", plan_cache="miss")
            return resp
        if (aggs_json and not body.get("suggest")
                and int(body.get("size", 10)) == 0
                and body.get("min_score") is None
                and ms.supports_mesh_aggs(aggs_json)):
            # the metric-agg family reduces ON the mesh (one ICI
            # collective), never serializing per-shard partials
            resp = collective(lambda: ms.mesh_metric_aggs(body,
                                                          aggs_json))
            if resp is None:
                return self._mesh_degrade(body, "mesh collective failed")
            insights.emit(
                signature=insights.canonical_query(body.get("query")),
                scored=False, took_ms=float(resp.get("took", 0)),
                execution_path="mesh", plan_cache="miss")
            return resp
        # device-collective top-k + host-side per-shard partial collect,
        # reduced exactly like the cross-node coordinator (the agg columns
        # are host/default-device resident; the mesh carries the scored
        # merge).  size:0 skips the mesh scored pass entirely — the host
        # collect already produces totals, so running both would execute
        # the query twice for a response whose hits are discarded.
        from opensearch_tpu.search.aggs import reduce_aggs
        from opensearch_tpu.search.suggest import merge_suggest
        collect_body = {"size": 0}
        if aggs_json:
            collect_body["aggs"] = aggs_json
        if body.get("suggest"):
            collect_body["suggest"] = body["suggest"]
        for key in ("query", "min_score"):
            if body.get(key) is not None:
                collect_body[key] = body[key]
        size0 = int(body.get("size", 10)) == 0
        with insights.suppressed():
            # per-shard collect legs of ONE mesh search: the mesh-level
            # record below is the arrival, not its scatter legs
            shard_resps = [s.search(collect_body, agg_partials=True)
                           for s in shards]
        partials = [r.get("aggregation_partials") or {} for r in shard_resps]
        if size0:
            total = sum(r["hits"]["total"]["value"] for r in shard_resps)
            resp = {"took": max((r["took"] for r in shard_resps), default=0),
                    "timed_out": False,
                    "hits": {"total": {"value": total, "relation": "eq"},
                             "max_score": None, "hits": []}}
        else:
            resp = collective(lambda: ms.search(
                {k: v for k, v in body.items()
                 if k not in ("aggs", "aggregations", "suggest")}))
            if resp is None:
                return self._mesh_degrade(body, "mesh collective failed")
        if aggs_json:
            resp["aggregations"] = reduce_aggs(aggs_json, partials)
        if body.get("suggest"):
            resp["suggest"] = merge_suggest(
                [r.get("suggest") for r in shard_resps])
        insights.emit(
            signature=insights.canonical_query(body.get("query")),
            scored=not size0, took_ms=float(resp.get("took", 0)),
            execution_path="mesh", plan_cache="miss")
        return resp

    def _host_scatter_search(self, body: dict) -> dict:
        """Mesh-unavailable fallback: the same scatter-gather the device
        collective performs, on the host — every local shard queries its
        OWN searcher (per-shard scoring stats, query_then_fetch
        semantics identical to the mesh and the multi-node coordinator)
        and the top-k merges with the coordinator's tie-break order."""
        from opensearch_tpu.search.aggs import reduce_aggs
        from opensearch_tpu.search.executor import merge_hit_rows
        from opensearch_tpu.search.suggest import merge_suggest

        t0 = time.monotonic()
        size = int(body.get("size", 10)
                   if body.get("size") is not None else 10)
        from_ = int(body.get("from", 0) or 0)
        aggs_json = body.get("aggs") or body.get("aggregations")
        sub = dict(body)
        sub["from"] = 0
        sub["size"] = from_ + size
        with self._lock:
            searchers = [self.local_shards[s].acquire_searcher()
                         for s in sorted(self.local_shards)]
        shard_resps = [s.search(sub, agg_partials=bool(aggs_json))
                       for s in searchers]
        rows = []
        total = 0
        max_score = None
        for si, r in enumerate(shard_resps):
            for pos, h in enumerate(r["hits"]["hits"]):
                rows.append((h, si, pos))
            total += r["hits"]["total"]["value"]
            ms_ = r["hits"]["max_score"]
            if ms_ is not None and (max_score is None or ms_ > max_score):
                max_score = ms_
        all_hits = merge_hit_rows(rows, body.get("sort"))
        resp = {
            "took": int((time.monotonic() - t0) * 1000),
            "timed_out": any(r.get("timed_out") for r in shard_resps),
            "hits": {"total": {"value": total, "relation": "eq"},
                     "max_score": max_score,
                     "hits": all_hits[from_: from_ + size]},
        }
        if aggs_json:
            resp["aggregations"] = reduce_aggs(
                aggs_json, [r.get("aggregation_partials") or {}
                            for r in shard_resps])
        if body.get("suggest"):
            resp["suggest"] = merge_suggest(
                [r.get("suggest") for r in shard_resps])
        return resp

    def msearch(self, bodies: list) -> list[dict]:
        """Batched multi-search over the node-local searcher (term-bag
        bodies share device programs — search/batch.py), routed through
        the unified engine entry."""
        from opensearch_tpu.search.engine import query_engine
        results = query_engine().msearch(self.searcher(), bodies)
        for r in results:
            r["_shards"] = {"total": self.num_shards,
                            "successful": self.num_shards,
                            "skipped": 0, "failed": 0}
        return results

    def count(self, query: Optional[dict] = None) -> int:
        from opensearch_tpu.search.engine import query_engine
        return query_engine().count(self.searcher(), query)

    def doc_count(self) -> int:
        return sum(e.doc_count() for e in self.shards)

    def stats(self) -> dict:
        from opensearch_tpu.indices.request_cache import request_cache
        return {
            "docs": {"count": self.doc_count()},
            "shards": {"total": self.num_shards},
            "segments": {"count": sum(len(e.segments) for e in self.shards)},
            "request_cache": request_cache().stats_for_index(self.name),
        }

    def put_mapping(self, mapping: dict):
        self._check_write_block()   # schema must match the snapshot
        self.mapper.merge(mapping)
        self.save_meta()
        # a mapping change can alter how cached requests would compile
        self._dirty()

    def get_mapping(self) -> dict:
        return {"mappings": self.mapper.to_mapping()}

    def get_settings(self) -> dict:
        return {"settings": {"index": {
            "number_of_shards": str(self.num_shards),
            "number_of_replicas": str(self.num_replicas),
            "uuid": self.uuid,
            "creation_date": str(self.creation_date),
        }}}

    def close(self):
        from opensearch_tpu.indices.request_cache import request_cache
        for engine in self.shards:
            engine.close()
        request_cache().invalidate_service(self.uuid)


class IndicesService:
    """Node-level registry (IndicesService.java analog) with on-disk
    metadata so indices survive restarts."""

    def __init__(self, data_path: str):
        self.data_path = data_path
        os.makedirs(data_path, exist_ok=True)
        self._lock = threading.RLock()
        self.indices: dict[str, IndexService] = {}
        self._deleting: set[str] = set()   # names mid remote-cleanup
        # alias -> {index_name: {"filter": ..., "is_write_index": bool}}
        # (cluster-state aliases; ref cluster/metadata/AliasMetadata)
        self.aliases: dict[str, dict[str, dict]] = {}
        # composable index templates (ref cluster/metadata/
        # ComposableIndexTemplate): name -> body
        self.templates: dict[str, dict] = {}
        # searchable-snapshot blob cache, a sibling of the index dirs
        # (the reference's node-level FileCache, ref node/Node.java)
        from opensearch_tpu.index.filecache import FileCache
        self.file_cache = FileCache(
            os.path.join(os.path.dirname(data_path) or data_path,
                         "filecache"))
        self._pending_mounts: list[str] = []
        # data streams: name -> {"timestamp_field", "generation",
        # "indices": [backing names]} (cluster/metadata/DataStream)
        self.data_streams: dict[str, dict] = {}
        # node-wide indexing-pressure admission (ShardIndexingPressure)
        from opensearch_tpu.common.indexing_pressure import IndexingPressure
        self.indexing_pressure = IndexingPressure(
            int(os.environ.get("OSTPU_INDEXING_PRESSURE_LIMIT",
                               64 << 20)))
        self._aliases_file = os.path.join(data_path, "_aliases.json")
        self._templates_file = os.path.join(data_path,
                                            "_index_templates.json")
        self._datastreams_file = os.path.join(data_path,
                                              "_data_streams.json")
        for path, attr in ((self._aliases_file, "aliases"),
                           (self._templates_file, "templates"),
                           (self._datastreams_file, "data_streams")):
            if os.path.exists(path):
                with open(path) as f:
                    setattr(self, attr, json.load(f))
        self._load()

    def _meta_path(self, name: str) -> str:
        return os.path.join(self.data_path, name, "index_meta.json")

    def _persist_meta(self, name: str, settings: dict, mappings: dict):
        tmp = self._meta_path(name) + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"settings": settings, "mappings": mappings}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._meta_path(name))

    def set_repo_resolver(self, resolver, mutex_fn=None):
        """Late-bound blob-repository lookup (the node wires it once the
        snapshot service exists); applied to every open index."""
        self._repo_resolver = resolver
        self._repo_mutex_fn = mutex_fn
        for svc in self.indices.values():
            svc.repo_resolver = resolver
            svc.repo_mutex_fn = mutex_fn
        # open mounted (remote_snapshot) indices deferred at boot, best
        # effort: a vanished repository leaves the mount closed rather
        # than failing node startup
        import logging
        pending, self._pending_mounts = self._pending_mounts, []
        for name in pending:
            try:
                with open(self._meta_path(name)) as f:
                    meta = json.load(f)
                with self._lock, \
                        self._mount_materialize(name, meta["settings"]):
                    self.indices[name] = IndexService(
                        name, os.path.join(self.data_path, name),
                        meta["settings"], meta.get("mappings"),
                        persist_meta=self._persist_meta)
            except Exception as e:   # noqa: BLE001 — keep node booting
                logging.getLogger("opensearch_tpu.indices").warning(
                    "could not reopen mounted index [%s]: %s", name, e)

    def _mount_materialize(self, name: str, settings: dict):
        """Context manager: fetch/link a mounted index's segment files
        from its backing repository through the node file cache, and PIN
        the whole blob set until the caller's engines have opened —
        without the pin, materializing shard N under a small cache
        budget evicts shard 1's blobs from under their symlinks before
        the engine reads them."""
        import contextlib

        mount = settings.get("remote_snapshot") or {}
        resolver = getattr(self, "_repo_resolver", None)
        if resolver is None:
            raise ValidationError(
                f"cannot open mounted index [{name}]: no repository "
                "service")
        repo = resolver(mount["repository"])
        index_path = os.path.join(self.data_path, name)
        shard_dirs, blobs = [], set()
        for shard in sorted(os.listdir(index_path)):
            shard_dir = os.path.join(index_path, shard)
            ref_path = os.path.join(shard_dir, "remote_ref.json")
            if os.path.isfile(ref_path):
                with open(ref_path) as f:
                    blobs.update(fm["blob"]
                                 for fm in json.load(f)["files"])
                shard_dirs.append(shard_dir)

        @contextlib.contextmanager
        def mount_ctx():
            with self.file_cache.pin(blobs):
                for sd in shard_dirs:
                    self.file_cache.materialize_shard(sd, repo)
                yield

        return mount_ctx()

    def _load(self):
        for name in sorted(os.listdir(self.data_path)):
            meta_path = self._meta_path(name)
            if os.path.exists(meta_path):
                with open(meta_path) as f:
                    meta = json.load(f)
                if meta.get("settings", {}).get("remote_snapshot"):
                    # mounted indices need the blob repository, wired
                    # later via set_repo_resolver — defer the open
                    self._pending_mounts.append(name)
                    continue
                svc = IndexService(
                    name, os.path.join(self.data_path, name),
                    meta.get("settings", {}), meta.get("mappings"),
                    persist_meta=self._persist_meta)
                svc.indexing_pressure = self.indexing_pressure
                self.indices[name] = svc

    @staticmethod
    def validate_name(name: str):
        """Reference rules (MetadataCreateIndexService.validateIndexName):
        lowercase, no reserved characters, must not start with _ - +,
        not '.'/'..', < 255 bytes.  Any unicode satisfying those is
        legal (e.g. CJK names)."""
        bad = (not name or name != name.lower() or name in (".", "..")
               or name[0] in "_-+"
               or any(c in _INDEX_NAME_FORBIDDEN for c in name)
               or len(name.encode("utf-8")) > 255)
        if bad:
            raise ValidationError(
                f"invalid index name [{name}]: must be lowercase, must "
                "not contain [\\/*?\"<>|, #:] or spaces, and must not "
                "start with [_-+]")

    def _register(self, name: str, settings: dict,
                  mappings: Optional[dict]) -> IndexService:
        """Shared open+persist+register step for create and restore
        (call with the registry lock held)."""
        if name in self.indices:
            raise IndexAlreadyExistsError(name)
        if name in self._deleting:
            raise IllegalArgumentError(
                f"index [{name}] is being deleted — retry shortly")
        self.validate_name(name)
        if "index" in settings:       # accept {"settings": {"index": {...}}}
            inner = settings.pop("index")
            settings.update(inner)
        path = os.path.join(self.data_path, name)
        os.makedirs(path, exist_ok=True)
        import contextlib
        mount_ctx = (self._mount_materialize(name, settings)
                     if settings.get("remote_snapshot")
                     else contextlib.nullcontext())
        with mount_ctx:     # pin blobs until the engines have loaded
            svc = IndexService(name, path, settings, mappings,
                               persist_meta=self._persist_meta)
        svc.repo_resolver = getattr(self, "_repo_resolver", None)
        svc.repo_mutex_fn = getattr(self, "_repo_mutex_fn", None)
        svc.indexing_pressure = self.indexing_pressure
        self._persist_meta(name, settings, mappings or {})
        self.indices[name] = svc
        return svc

    def create(self, name: str, body: Optional[dict] = None) -> IndexService:
        body = body or {}
        with self._lock:
            if name in self.aliases:
                raise IndexAlreadyExistsError(name)
            settings = dict(body.get("settings", {}))
            mappings = body.get("mappings")
            tmpl = self._template_for(name)
            if tmpl is not None:
                # template under, request over (composable V2 merge)
                t = tmpl.get("template") or {}
                settings = {**(t.get("settings") or {}), **settings}
                if t.get("mappings"):
                    merged = dict(t["mappings"].get("properties") or {})
                    merged.update((mappings or {}).get("properties") or {})
                    mappings = {**t["mappings"], **(mappings or {}),
                                "properties": merged}
            svc = self._register(name, settings, mappings)
            tmpl_aliases = ((tmpl or {}).get("template") or {}).get(
                "aliases", {})
            req_aliases = body.get("aliases") or {}
            for alias, meta in {**tmpl_aliases, **req_aliases}.items():
                self.aliases.setdefault(alias, {})[name] = meta or {}
            if tmpl_aliases or req_aliases:
                self._persist_json(self._aliases_file, self.aliases)
            return svc

    def open_restored(self, name: str, settings: dict,
                      mappings: Optional[dict]) -> IndexService:
        """Open an index whose shard directories a snapshot restore just
        materialized (RestoreService's post-copy open)."""
        with self._lock:
            return self._register(name, dict(settings), mappings)

    def get(self, name: str) -> IndexService:
        svc = self.indices.get(name)
        if svc is None:
            svc = self._alias_single(name)
            if svc is None:
                raise IndexNotFoundError(name)
        return svc

    def _alias_single(self, name: str):
        """Resolve an alias for a single-index op (get/mget): one target
        resolves, several is an error (TransportSingleShardAction)."""
        targets = self.aliases.get(name)
        if not targets:
            return None
        if len(targets) > 1:
            raise IllegalArgumentError(
                f"alias [{name}] has more than one index associated with "
                f"it [{', '.join(sorted(targets))}], can't execute a "
                "single index op")
        return self.indices.get(next(iter(targets)))

    auto_create = True          # action.auto_create_index (dynamic)

    def get_or_create(self, name: str) -> IndexService:
        """Auto-create on first write (action.auto_create_index default)."""
        with self._lock:
            if name in self.indices:
                return self.indices[name]
            if not self.auto_create:
                raise IndexNotFoundError(name)
            return self.create(name)

    def exists(self, name: str) -> bool:
        return name in self.indices

    def delete(self, name: str):
        with self._lock:
            svc = self.get(name)
            remote_repo = None
            try:
                remote_repo = svc._remote_repo()
            except Exception:      # noqa: BLE001 — best-effort cleanup
                pass
            svc.close()
            del self.indices[name]
            # drop the index from every alias (empty aliases disappear,
            # like cluster-state alias metadata on index deletion)
            changed = False
            for alias in list(self.aliases):
                if self.aliases[alias].pop(name, None) is not None:
                    changed = True
                    if not self.aliases[alias]:
                        del self.aliases[alias]
            if changed:
                self._persist_json(self._aliases_file, self.aliases)
            if remote_repo is not None:
                # block same-name recreation until the remote cleanup
                # finishes, or the trailing GC would destroy the NEW
                # index's fresh mirror.  EVERY exit path from here on
                # must discard the guard (see the outer try/finally).
                self._deleting.add(name)
            try:
                shutil.rmtree(os.path.join(self.data_path, name),
                              ignore_errors=True)
                # aliases pointing only at the deleted index vanish too
                changed = False
                for alias in list(self.aliases):
                    if name in self.aliases[alias]:
                        del self.aliases[alias][name]
                        if not self.aliases[alias]:
                            del self.aliases[alias]
                        changed = True
                if changed:
                    self._persist_json(self._aliases_file, self.aliases)
            except BaseException:
                self._deleting.discard(name)
                raise
        if remote_repo is not None:
            # OUTSIDE the registry lock (the scan + GC is blob-store
            # I/O), under the repo mutex so snapshot create/delete can't
            # interleave: the mirror dies with the index, blobs nothing
            # references anymore go with it (the GC consults BOTH
            # consumers of the shared space)
            try:
                from opensearch_tpu.snapshots.service import \
                    collect_referenced_blobs
                mutex = (self._repo_mutex_fn(remote_repo.name)
                         if getattr(self, "_repo_mutex_fn", None)
                         else None)
                if mutex is not None:
                    mutex.acquire()
                try:
                    remote_repo.store.container(
                        f"remote/{name}").delete_tree()
                    referenced = collect_referenced_blobs(remote_repo)
                    for blob in list(remote_repo.blobs.list_blobs()):
                        if blob not in referenced:
                            remote_repo.blobs.delete_blob(blob)
                finally:
                    if mutex is not None:
                        mutex.release()
            finally:
                with self._lock:
                    self._deleting.discard(name)

    def resolve(self, expr: str) -> list[IndexService]:
        """Index expression: name, alias, comma list, * / _all wildcards
        (aliases resolve like the reference's IndexNameExpressionResolver)."""
        return [svc for svc, _f in self.resolve_with_filters(expr)]

    def resolve_with_filters(self, expr: str) -> list[tuple]:
        """[(IndexService, alias_filter|None)]: an index reached ONLY
        through filtered aliases carries the (should-of) alias filters;
        any unfiltered route wins (the reference's alias-filter
        application in QueryShardContext)."""
        if expr in ("_all", "*", ""):
            return [(s, None) for s in self.indices.values()]
        acc: dict[str, list] = {}       # name -> [filters] | [None]
        order: list[str] = []

        def add(name, flt):
            if name not in acc:
                acc[name] = [flt]
                order.append(name)
            elif None in acc[name] or flt is None:
                acc[name] = [None]
            else:
                acc[name].append(flt)

        def add_alias(alias):
            for n, meta in self.aliases[alias].items():
                if n in self.indices:
                    add(n, meta.get("filter"))

        for part in expr.split(","):
            if "*" in part:
                rx = re.compile("^" + re.escape(part).replace(r"\*", ".*")
                                + "$")
                for n in self.indices:
                    if rx.match(n):
                        add(n, None)
                for alias in self.aliases:
                    if rx.match(alias):
                        add_alias(alias)
                for ds in self.data_streams:
                    if rx.match(ds):
                        for n in self.data_streams[ds]["indices"]:
                            add(n, None)
            elif part in self.aliases:
                add_alias(part)
            elif part in self.data_streams:
                # a data stream searches all its backing indices
                for n in self.data_streams[part]["indices"]:
                    add(n, None)
            else:
                add(self.get(part).name, None)
        out = []
        for name in order:
            filters = acc[name]
            if None in filters:
                flt = None
            elif len(filters) == 1:
                flt = filters[0]
            else:
                flt = {"bool": {"should": filters,
                                "minimum_should_match": 1}}
            out.append((self.indices[name], flt))
        return out

    # -- aliases -----------------------------------------------------------

    def _persist_json(self, path: str, obj):
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(obj, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def update_aliases(self, actions: list) -> dict:
        """POST /_aliases action list (IndicesAliasesRequest)."""
        with self._lock:
            staged = {a: dict(t) for a, t in self.aliases.items()}
            for entry in actions or []:
                if not isinstance(entry, dict) or len(entry) != 1:
                    raise ValidationError(
                        "alias action must be one of add/remove/"
                        "remove_index")
                ((op, body),) = entry.items()
                if op == "remove_index":
                    raise ValidationError(
                        "[remove_index] is not supported")
                if op not in ("add", "remove"):
                    raise ValidationError(f"unknown alias action [{op}]")
                if not isinstance(body, dict):
                    raise ValidationError(
                        f"alias action [{op}] requires an object body")
                if body.get("routing") is not None:
                    raise ValidationError(
                        "alias [routing] is not supported")
                indices = body.get("indices") or [body.get("index")]
                names = body.get("aliases") or [body.get("alias")]
                if not all(indices) or not all(names):
                    raise ValidationError(
                        f"alias action [{op}] requires [index] and "
                        "[alias]")
                resolved = []
                for ix in indices:
                    resolved.extend(s.name for s in self.resolve(ix)
                                    if s.name in self.indices)
                for alias in names:
                    if alias in self.indices:
                        raise ValidationError(
                            f"an index named [{alias}] already exists")
                    for ix in resolved:
                        if op == "add":
                            meta = {}
                            if body.get("filter") is not None:
                                meta["filter"] = body["filter"]
                            if body.get("is_write_index"):
                                meta["is_write_index"] = True
                            staged.setdefault(alias, {})[ix] = meta
                        else:
                            staged.get(alias, {}).pop(ix, None)
            self.aliases = {a: t for a, t in staged.items() if t}
            self._persist_json(self._aliases_file, self.aliases)
        return {"acknowledged": True}

    def get_aliases(self, index: Optional[str] = None,
                    name: Optional[str] = None) -> dict:
        """GET /_alias family response shape: {index: {aliases: {...}}}."""
        out: dict[str, dict] = {}
        for alias, targets in self.aliases.items():
            if name is not None and not re.match(
                    "^" + re.escape(name).replace(r"\*", ".*") + "$",
                    alias):
                continue
            for ix, meta in targets.items():
                if index is not None and ix != index:
                    continue
                rendered = dict(meta or {})
                # a bare [routing] renders as both index_routing and
                # search_routing (AliasMetadata's xcontent shape)
                routing = rendered.pop("routing", None)
                if routing is not None:
                    rendered.setdefault("index_routing", routing)
                    rendered.setdefault("search_routing", routing)
                out.setdefault(ix, {"aliases": {}})["aliases"][alias] = \
                    rendered
        if name is not None and not out:
            raise ResourceNotFoundError(f"alias [{name}] missing")
        return out

    def write_index_for(self, alias: str) -> "IndexService":
        """Write resolution: an alias works for writes when it points at
        one index or names an explicit write index; a data stream always
        writes to its newest backing index."""
        if alias in self.data_streams:
            return self.data_stream_write_index(alias)
        targets = self.aliases.get(alias)
        if targets is None:
            return self.get_or_create(alias)
        writers = [ix for ix, meta in targets.items()
                   if meta.get("is_write_index")]
        if len(targets) == 1:
            return self.get(next(iter(targets)))
        if len(writers) == 1:
            return self.get(writers[0])
        raise IllegalArgumentError(
            f"no write index is defined for alias [{alias}]. The write "
            "index may be explicitly disabled using is_write_index=false "
            "or the alias points to multiple indices without one being "
            "designated as a write index")

    # -- index templates ---------------------------------------------------

    def put_template(self, name: str, body: dict) -> dict:
        patterns = body.get("index_patterns")
        if not patterns:
            raise ValidationError(
                "index template requires [index_patterns]")
        with self._lock:
            self.templates[name] = body
            self._persist_json(self._templates_file, self.templates)
        return {"acknowledged": True}

    def get_template(self, name: Optional[str] = None) -> dict:
        if name is None:
            items = sorted(self.templates.items())
        else:
            items = [(n, t) for n, t in sorted(self.templates.items())
                     if re.match("^" + re.escape(name)
                                 .replace(r"\*", ".*") + "$", n)]
            if not items and "*" not in name:
                raise ResourceNotFoundError(
                    f"index template matching [{name}] not found")
        return {"index_templates": [
            {"name": n, "index_template": t} for n, t in items]}

    def delete_template(self, name: str) -> dict:
        with self._lock:
            if name not in self.templates:
                raise ResourceNotFoundError(
                    f"index template [{name}] missing")
            del self.templates[name]
            self._persist_json(self._templates_file, self.templates)
        return {"acknowledged": True}

    # -- rollover / resize / data streams ---------------------------------

    @staticmethod
    def _next_rollover_name(name: str) -> str:
        """<base>-000001 -> <base>-000002; no numeric suffix appends one
        (MetadataRolloverService.generateRolloverIndexName)."""
        m = re.match(r"^(.*)-(\d+)$", name)
        if m:
            n = int(m.group(2)) + 1
            return f"{m.group(1)}-{n:0{max(6, len(m.group(2)))}d}"
        return f"{name}-000001"

    def _rollover_conditions_met(self, svc: IndexService,
                                 conditions: dict) -> dict:
        """Evaluate max_docs / max_age / max_size against the write
        index (RolloverRequest conditions)."""
        results = {}
        for cond, want in (conditions or {}).items():
            if cond == "max_docs":
                results["[max_docs: %s]" % want] = \
                    svc.doc_count() >= int(want)
            elif cond == "max_age":
                from opensearch_tpu.common.settings import parse_time
                # creation_date is a wall timestamp, so the age
                # comparison must stay in the same clock domain
                age_s = time.time() - svc.creation_date / 1000.0  # wall-clock
                results["[max_age: %s]" % want] = \
                    age_s >= parse_time(want)
            elif cond == "max_size":
                from opensearch_tpu.common.settings import parse_bytes
                size = sum(
                    sum(len(b) for b in seg.sources)
                    for e in svc.shards
                    for seg in e.acquire_searcher().segments)
                results["[max_size: %s]" % want] = \
                    size >= parse_bytes(want)
            else:
                raise IllegalArgumentError(
                    f"unknown rollover condition [{cond}]")
        return results

    def rollover(self, target: str, body: Optional[dict] = None,
                 dry_run: bool = False) -> dict:
        """Roll a write alias or data stream over to a fresh index
        (action/admin/indices/rollover/MetadataRolloverService)."""
        body = body or {}
        with self._lock:
            if target in self.data_streams:
                return self._rollover_data_stream(target, body, dry_run)
            targets = self.aliases.get(target)
            if not targets:
                raise IllegalArgumentError(
                    f"rollover target [{target}] is not an alias or "
                    "data stream")
            writers = [n for n, m in targets.items()
                       if m.get("is_write_index")]
            if len(targets) == 1:
                old = next(iter(targets))
            elif len(writers) == 1:
                old = writers[0]
            else:
                raise IllegalArgumentError(
                    f"rollover target [{target}] does not point to a "
                    "single write index")
            new = body.get("new_index") or self._next_rollover_name(old)
            conds = self._rollover_conditions_met(
                self.indices[old], body.get("conditions") or {})
            rolled = all(conds.values()) if conds else True
            out = {"acknowledged": rolled and not dry_run,
                   "shards_acknowledged": rolled and not dry_run,
                   "old_index": old, "new_index": new,
                   "rolled_over": rolled and not dry_run,
                   "dry_run": dry_run, "conditions": conds}
            if dry_run or not rolled:
                return out
            self.create(new, {k: v for k, v in body.items()
                              if k in ("settings", "mappings",
                                       "aliases")})
            meta = dict(targets.get(old) or {})
            meta["is_write_index"] = False
            self.aliases[target][old] = meta
            self.aliases[target][new] = {"is_write_index": True}
            self._persist_json(self._aliases_file, self.aliases)
            return out

    def resize(self, source: str, target: str, mode: str,
               body: Optional[dict] = None) -> dict:
        """shrink / split / clone: create ``target`` with the new shard
        count and re-bucket every live doc by the target routing (the
        reference relinks Lucene segments —
        action/admin/indices/shrink/TransportResizeAction; the array
        engine re-routes sources instead, same observable result)."""
        body = body or {}
        with self._lock:
            svc = self.get(source)
            if target in self.indices or target in self.aliases:
                raise IndexAlreadyExistsError(target)
            blocked = svc.index_setting(
                "blocks.write",
                (svc.settings.get("blocks") or {}).get("write", False))
            if str(blocked).lower() != "true":
                raise IllegalArgumentError(
                    f"index [{source}] must block writes to resize "
                    "(set index.blocks.write: true)")
            src_shards = svc.num_shards
            settings = dict(body.get("settings") or {})
            tgt_shards = int(settings.get(
                "number_of_shards",
                settings.get("index.number_of_shards",
                             1 if mode == "shrink" else
                             src_shards * 2 if mode == "split"
                             else src_shards)))
            if mode == "shrink" and src_shards % tgt_shards != 0:
                raise IllegalArgumentError(
                    f"the number of source shards [{src_shards}] must be "
                    f"a multiple of [{tgt_shards}]")
            if mode == "split" and tgt_shards % src_shards != 0:
                raise IllegalArgumentError(
                    f"the number of target shards [{tgt_shards}] must be "
                    f"a multiple of the source shards [{src_shards}]")
            if mode == "clone" and tgt_shards != src_shards:
                raise IllegalArgumentError(
                    "clone must keep the source's number of shards")
            settings["number_of_shards"] = tgt_shards
            settings.pop("index.number_of_shards", None)
            settings.pop("blocks", None)
            new_svc = self.create(target, {
                "settings": settings,
                "mappings": svc.get_mapping().get("mappings"),
                "aliases": body.get("aliases") or {}})
        # copy OUTSIDE the registry lock: doc-by-doc re-route.  Refresh
        # first — the copy reads segments, and unrefreshed hot-buffer
        # docs would silently miss the target otherwise
        svc.refresh()
        copied = 0
        for engine in svc.shards:
            searcher = engine.acquire_searcher()
            for seg in searcher.segments:
                for local in range(seg.n_docs):
                    if not seg.live[local]:
                        continue
                    new_svc.index_doc(seg.doc_ids[local],
                                      seg.source(local),
                                      routing=seg.routings.get(local))
                    copied += 1
        new_svc.refresh()
        return {"acknowledged": True, "shards_acknowledged": True,
                "index": target, "copied_docs": copied}

    # -- data streams ------------------------------------------------------

    def create_data_stream(self, name: str) -> dict:
        """A data stream needs a matching template with a [data_stream]
        section; its first backing index is .ds-<name>-000001
        (MetadataCreateDataStreamService)."""
        with self._lock:
            if name in self.data_streams:
                raise ResourceAlreadyExistsError(
                    f"data_stream [{name}] already exists")
            tmpl = self._template_for(name)
            if tmpl is None or "data_stream" not in tmpl:
                raise IllegalArgumentError(
                    f"no matching index template with a data_stream "
                    f"definition for [{name}]")
            ts_field = ((tmpl.get("data_stream") or {}).get(
                "timestamp_field") or {}).get("name", "@timestamp")
            backing = f".ds-{name}-000001"
            self.create(backing, {
                "mappings": {"properties": {ts_field: {"type": "date"}}}})
            self.data_streams[name] = {"timestamp_field": ts_field,
                                       "generation": 1,
                                       "indices": [backing]}
            self._persist_json(self._datastreams_file, self.data_streams)
            return {"acknowledged": True}

    def _rollover_data_stream(self, name: str, body: dict,
                              dry_run: bool) -> dict:
        ds = self.data_streams[name]
        old = ds["indices"][-1]
        conds = self._rollover_conditions_met(
            self.indices[old], (body or {}).get("conditions") or {})
        rolled = all(conds.values()) if conds else True
        gen = ds["generation"] + 1
        new = f".ds-{name}-{gen:06d}"
        out = {"acknowledged": rolled and not dry_run,
               "old_index": old, "new_index": new,
               "rolled_over": rolled and not dry_run,
               "dry_run": dry_run, "conditions": conds}
        if dry_run or not rolled:
            return out
        self.create(new, {"mappings": {"properties": {
            ds["timestamp_field"]: {"type": "date"}}}})
        ds["generation"] = gen
        ds["indices"].append(new)
        self._persist_json(self._datastreams_file, self.data_streams)
        return out

    def get_data_streams(self, name: Optional[str] = None) -> dict:
        with self._lock:
            items = []
            for n, ds in sorted(self.data_streams.items()):
                if name and name != n and not re.match(
                        "^" + re.escape(name).replace(r"\*", ".*") + "$",
                        n):
                    continue
                items.append({
                    "name": n,
                    "timestamp_field": {"name": ds["timestamp_field"]},
                    "indices": [{"index_name": i} for i in ds["indices"]],
                    "generation": ds["generation"],
                    "status": "GREEN",
                })
            return {"data_streams": items}

    def delete_data_stream(self, name: str) -> dict:
        with self._lock:
            ds = self.data_streams.get(name)
            if ds is None:
                raise ResourceNotFoundError(
                    f"data_stream [{name}] not found")
            for backing in ds["indices"]:
                if backing in self.indices:
                    self.delete(backing)
            del self.data_streams[name]
            self._persist_json(self._datastreams_file, self.data_streams)
            return {"acknowledged": True}

    def data_stream_write_index(self, name: str) -> "IndexService":
        ds = self.data_streams[name]
        return self.get(ds["indices"][-1])

    def _template_for(self, name: str) -> Optional[dict]:
        """Highest-priority template whose pattern matches ``name``."""
        best = None
        best_prio = -1
        for t in self.templates.values():
            for p in t.get("index_patterns") or []:
                if re.match("^" + re.escape(p).replace(r"\*", ".*") + "$",
                            name):
                    prio = int(t.get("priority", 0))
                    if prio > best_prio:
                        best, best_prio = t, prio
        return best

    def close(self):
        for svc in self.indices.values():
            svc.close()
