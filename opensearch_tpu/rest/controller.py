"""REST route table + handlers — the API surface.

Analog of ``rest/RestController.java:250`` (dispatch) and the
``rest/action/**`` handler classes, driven by the same path shapes the
rest-api-spec JSON contract defines.  Transport-agnostic: the HTTP server
calls ``dispatch(method, path, params, body)`` and gets (status, dict).
"""

from __future__ import annotations

import json
import os
import re
import time
from typing import Callable, Optional

from opensearch_tpu.common.errors import (
    DocumentMissingError,
    IllegalArgumentError,
    IndexNotFoundError,
    OpenSearchTpuError,
    ParsingError,
    ResourceNotFoundError,
    ValidationError,
)
from opensearch_tpu.version import __version__ as VERSION


class RestRequest:
    def __init__(self, method: str, path: str, params: dict,
                 body: Optional[bytes], content_type: str = ""):
        self.method = method
        self.path = path
        self.params = params or {}
        self.raw_body = body or b""
        self.content_type = content_type
        self.path_params: dict[str, str] = {}

    def json(self, default=None):
        """Structured body, negotiated by Content-Type (JSON default;
        YAML/CBOR via x-content, ref libs/x-content XContentType)."""
        if not self.raw_body:
            return default
        from opensearch_tpu.common.xcontent import from_bytes
        return from_bytes(self.raw_body, self.content_type)

    def param(self, name: str, default=None):
        return self.params.get(name, self.path_params.get(name, default))

    def int_param(self, name: str):
        """Integer query param, or None when absent — garbage is a
        typed 400 (the reference's number_format_exception), never a
        raw ValueError 500."""
        v = self.param(name)
        if v is None:
            return None
        try:
            return int(v)
        except (TypeError, ValueError):
            from opensearch_tpu.common.errors import IllegalArgumentError
            raise IllegalArgumentError(
                f"[{name}] must be an integer, got [{v}]")

    def flag(self, name: str) -> bool:
        v = self.params.get(name)
        return v is not None and str(v).lower() in ("", "true", "1")


def _os_stats() -> dict:
    """OsProbe analog over stdlib (loadavg + memory via sysconf)."""
    import os as _os

    try:
        la1, la5, la15 = _os.getloadavg()
    except OSError:
        la1 = la5 = la15 = 0.0
    try:
        page = _os.sysconf("SC_PAGE_SIZE")
        total = _os.sysconf("SC_PHYS_PAGES") * page
        free = _os.sysconf("SC_AVPHYS_PAGES") * page
    except (ValueError, OSError):
        total = free = 0
    return {"cpu": {"load_average": {"1m": la1, "5m": la5, "15m": la15}},
            "mem": {"total_in_bytes": total, "free_in_bytes": free}}


def _device_stats() -> dict:
    """The ``device`` section of ``_nodes/stats``: the residency
    ledger's rollups (common/device_ledger.py) — resident bytes per
    index, host↔device transfer counters split stage vs fetch-back,
    budget/eviction/restage accounting, and the per-kernel XLA compile
    registry, next to the jax backend's own ``memory_stats()`` where
    the platform provides it — plus the ``health`` block: the
    per-kernel-class circuit breakers' states, trip/close counters and
    the result-sanity guard's poisoned-result count
    (common/device_health.py)."""
    from opensearch_tpu.common.device_health import device_health
    from opensearch_tpu.common.device_ledger import device_ledger
    return {**device_ledger().stats(), "health": device_health().stats()}


def _query_engine_stats() -> dict:
    """The unified engine's `_nodes/stats` block (continuous batcher +
    search threadpool accounting, search/engine.py)."""
    from opensearch_tpu.search.engine import query_engine
    return query_engine().stats()


def _process_stats() -> dict:
    """ProcessProbe analog: CURRENT rss from /proc statm (linux), peak
    rss from getrusage (kbytes on linux, bytes on darwin)."""
    import resource
    import sys as _sys

    ru = resource.getrusage(resource.RUSAGE_SELF)
    peak = ru.ru_maxrss * (1 if _sys.platform == "darwin" else 1024)
    resident = peak
    try:
        with open("/proc/self/statm") as f:
            import os as _os
            resident = int(f.read().split()[1]) * _os.sysconf(
                "SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        pass
    return {"cpu": {"total_in_millis": int(
        (ru.ru_utime + ru.ru_stime) * 1000)},
        "mem": {"resident_in_bytes": resident,
                "peak_resident_in_bytes": peak},
        "open_file_descriptors": _count_fds()}


def _count_fds() -> int:
    import os as _os

    try:
        return len(_os.listdir("/proc/self/fd"))
    except OSError:
        return -1


def _nest_settings(flat: dict) -> dict:
    """Dotted settings keys -> the nested tree the reference's
    Settings.toXContent(flat_settings=false) renders."""
    out: dict = {}
    for key, v in flat.items():
        node = out
        parts = str(key).split(".")
        for p in parts[:-1]:
            nxt = node.get(p)
            if not isinstance(nxt, dict):
                nxt = node[p] = {}
            node = nxt
        node[parts[-1]] = v
    return out


def _flatten_nulls(d: dict, prefix: str = ""):
    """Yield (dotted_key, None) for nulls nested anywhere in a settings
    body (Settings flattening drops them, but null means RESET)."""
    for k, v in d.items():
        key = f"{prefix}{k}"
        if v is None:
            yield key, None
        elif isinstance(v, dict):
            yield from _flatten_nulls(v, key + ".")


def _total_hits_as_int(resp: dict):
    """?rest_total_hits_as_int=true: render hits.total as the pre-7.0
    integer (RestSearchAction.TOTAL_HITS_AS_INT_PARAM), including per
    sub-response in _msearch."""
    hits = resp.get("hits")
    if isinstance(hits, dict) and isinstance(hits.get("total"), dict):
        hits["total"] = hits["total"].get("value", 0)
    for sub in resp.get("responses") or []:
        if isinstance(sub, dict):
            _total_hits_as_int(sub)


class PlainText:
    """Marker payload: the HTTP layer writes ``text`` verbatim with the
    given content type instead of running x-content negotiation — the
    Prometheus exposition format is text, not JSON."""

    __slots__ = ("text", "content_type")

    def __init__(self, text: str,
                 content_type: str = "text/plain; charset=UTF-8"):
        self.text = text
        self.content_type = content_type


class Route:
    def __init__(self, method: str, pattern: str, handler: Callable):
        self.method = method
        parts = []
        self.names: list[str] = []
        for seg in pattern.strip("/").split("/"):
            if seg.startswith("{"):
                self.names.append(seg[1:-1])
                parts.append(r"([^/]+)")
            else:
                parts.append(re.escape(seg))
        self.rx = re.compile("^/" + "/".join(parts) + "$")
        self.handler = handler


class RestController:
    def __init__(self, node):
        self.node = node
        self.routes: list[Route] = []
        self._register_all()

    def register(self, method: str, pattern: str, handler: Callable):
        self.routes.append(Route(method, pattern, handler))

    # handler-name -> transport-style action name (the reference's task
    # actions; unlisted handlers register as rest:<handler>)
    _ACTIONS = {
        "h_search": "indices:data/read/search",
        "h_msearch": "indices:data/read/msearch",
        "h_scroll_next": "indices:data/read/scroll",
        "h_bulk": "indices:data/write/bulk",
        "h_count": "indices:data/read/count",
        "h_create_snapshot": "cluster:admin/snapshot/create",
        "h_restore_snapshot": "cluster:admin/snapshot/restore",
    }

    def dispatch(self, method: str, path: str, params: dict,
                 body: Optional[bytes], content_type: str = "",
                 authorization: str = "",
                 headers: Optional[dict] = None,
                 response_headers: Optional[dict] = None
                 ) -> tuple[int, dict]:
        """``response_headers``: optional out-channel the HTTP layer
        passes so error mappings can attach headers (Retry-After on
        backpressure rejections) without changing the return shape."""
        import contextlib
        from time import monotonic_ns

        from opensearch_tpu.common import tasks as taskmod
        from opensearch_tpu.common.telemetry import metrics, tracer
        from opensearch_tpu.common.threadpool import RejectedExecutionError

        # under the HTTP front end the edge's own work is split on its
        # span: ``route`` from here to the rest: span, ``after`` from
        # that span's end to the return
        t_entry = monotonic_ns()
        http_span = tracer().current()
        if http_span is not None and http_span.name != "http.request":
            http_span = None
        headers = headers or {}
        # request attribution: X-Opaque-Id threads into the task and all
        # downstream transport requests (Task.java HEADERS_TO_COPY)
        opaque_id = None
        for k, v in headers.items():
            if str(k).lower() == "x-opaque-id":
                opaque_id = v
                break
        req = RestRequest(method, path, params, body, content_type)
        try:
            identity = getattr(self.node, "identity", None)
            principal = (identity.check(method, path, authorization)
                         if identity is not None else None)
            for route in self.routes:
                if route.method != method:
                    continue
                m = route.rx.match(path.rstrip("/") or "/")
                if m:
                    # percent-decode captured segments: /index/_doc/中文
                    # arrives as %E4%B8%AD%E6%96%87 (RestRequest.java
                    # decodes the same way)
                    from urllib.parse import unquote
                    req.path_params = dict(zip(
                        route.names, (unquote(g) for g in m.groups())))
                    # every request runs as a registered, cancellable
                    # task (TaskManager.register analog); device loops
                    # check the contextvar between segment programs
                    handler_name = getattr(route.handler, "__name__", "?")
                    if identity is not None:
                        # authorize on the MATCHED route, not the raw
                        # path — path suffixes are forgeable via ids
                        identity.authorize(principal, method, path,
                                           handler_name)
                    action = self._ACTIONS.get(handler_name,
                                               f"rest:{handler_name}")
                    task_headers = ({"X-Opaque-Id": opaque_id}
                                    if opaque_id else None)
                    task = self.node.task_manager.register(
                        action, f"{method} {path}",
                        headers=task_headers)
                    token = taskmod.set_current(task)
                    attrs = {"http.method": method, "http.path": path,
                             "action": action,
                             "node": getattr(self.node, "node_id",
                                             self.node.name)}
                    if opaque_id:
                        attrs["x_opaque_id"] = opaque_id
                    # search admission: a permit gate at the REST edge —
                    # saturated nodes reject (429 + Retry-After) instead
                    # of queueing unboundedly (the search_backpressure
                    # admission-control half)
                    # the client's X-Opaque-Id doubles as the tenant
                    # key: named tenants draw from their carved
                    # admission share, everyone else from the default
                    # pool (search.qos.tenant_shares)
                    bp = getattr(self.node, "search_backpressure", None)
                    admission = (bp.admission.acquire(handler_name,
                                                      tenant=opaque_id)
                                 if bp is not None and action in (
                                     "indices:data/read/search",
                                     "indices:data/read/msearch")
                                 else contextlib.nullcontext())
                    from opensearch_tpu.search import insights
                    searchish = action in ("indices:data/read/search",
                                           "indices:data/read/msearch")
                    # under the HTTP front end a child of http.request,
                    # which has honoured an incoming W3C traceparent;
                    # called directly, the root that honours it
                    parent = (None if tracer().current() is not None
                              else tracer().extract(headers))
                    rest_span = None    # set once admitted
                    try:
                        with admission, tracer().start_span(
                                f"rest:{action}", attributes=attrs,
                                parent=parent) as span, \
                                insights.collecting() as sink:
                            rest_span = span
                            metrics().counter("rest.requests").inc()
                            status, resp = route.handler(req)
                            span.set_attribute("http.status", status)
                        if searchish and sink:
                            # edge-side insight enrichment: the records
                            # the execution layers emitted gain what
                            # only this layer knows — the client's
                            # X-Opaque-Id, the task's measured CPU/heap,
                            # and the response-level outcome
                            self._record_insights(sink, resp, status,
                                                  task, opaque_id)
                        if searchish:
                            # close the loop: the QoS controller gets a
                            # paced evaluation tick with the freshest
                            # admission/insights evidence (no-op when
                            # search.qos.adaptive is off)
                            qos = getattr(self.node, "qos", None)
                            if qos is not None:
                                qos.maybe_tick()
                        if params.get("rest_total_hits_as_int") == "true" \
                                and isinstance(resp, dict):
                            _total_hits_as_int(resp)
                        return status, resp
                    finally:
                        taskmod.reset_current(token)
                        if searchish:
                            # what the request cost the host: the thread
                            # CPU time the task metered, cumulative
                            metrics().counter("search.cpu_micros").inc(
                                task.cpu_time_nanos // 1000)
                        self.node.task_manager.unregister(task)
                        if rest_span is not None:
                            # the span has ended: its duration is the
                            # histogram's sample, its edges the parts'
                            nanos = rest_span.duration_nanos
                            metrics().histogram("rest.request_ms").observe(
                                nanos / 1e6)
                            if http_span is not None:
                                start = rest_span.start_nanos
                                http_span.add_part("route", start - t_entry)
                                http_span.add_part(
                                    "after", monotonic_ns() - start - nanos)
            # method-mismatch vs not-found distinction
            if any(r.rx.match(path.rstrip("/") or "/") for r in self.routes):
                return 405, {"error": f"Incorrect HTTP method for uri [{path}]"
                                      f" and method [{method}]", "status": 405}
            return 400, {"error": {
                "type": "illegal_argument_exception",
                "reason": f"no handler found for uri [{path}] and method "
                          f"[{method}]"}, "status": 400}
        except OpenSearchTpuError as e:
            # overload rejections (thread-pool RejectedExecutionError,
            # admission/backpressure SearchRejectedError) ship a
            # Retry-After header and count in search.rejected so clients
            # and dashboards see the shed load, not just 429s
            from opensearch_tpu.search.backpressure import \
                SearchRejectedError
            if isinstance(e, (RejectedExecutionError,
                              SearchRejectedError)):
                metrics().counter("search.rejected").inc()
                insights = getattr(self.node, "insights", None)
                if insights is not None:
                    # rejected before any plan existed: counted in the
                    # insights totals (shed load is workload evidence),
                    # never a ring entry — attributed to the tenant
                    insights.record_rejected(opaque_id=opaque_id)
            if getattr(e, "status", None) == 429 \
                    and response_headers is not None:
                # EVERY 429 carries the hint — duress and circuit-
                # breaker rejections are as retryable as admission
                # ones, and a hintless 429 leaves clients guessing
                response_headers["Retry-After"] = str(
                    int(getattr(e, "retry_after_seconds", 1)))
            # transport-layer failures (NodeDisconnectedError /
            # ReceiveTimeoutError / NoMasterError) carry status 503 on
            # the class: the condition is retryable and the serialized
            # body keeps the precise error.type for clients
            return e.status, e.to_xcontent()
        except (TimeoutError, ConnectionError) as e:
            # stdlib-level transport failures get the same 503 treatment
            return 503, {"error": {"type": "node_disconnected_exception",
                                   "reason": f"{type(e).__name__}: {e}"},
                         "status": 503}
        except Exception as e:  # noqa: BLE001 — the REST boundary
            return 500, {"error": {"type": "internal_server_error",
                                   "reason": f"{type(e).__name__}: {e}"},
                         "status": 500}

    def _record_insights(self, sink: list, resp, status: int, task,
                         opaque_id) -> None:
        """Drain one request's emitted insight records into the node's
        QueryInsightsService, enriched with edge-only attribution."""
        service = getattr(self.node, "insights", None)
        if service is None or not service.enabled:
            return
        # fold un-checkpointed CPU into the task before reading it
        task.record_checkpoint()
        rs = task.resource_stats()
        cpu = int(rs.get("cpu_time_in_nanos", 0))
        heap = int(rs.get("peak_heap_size_in_bytes", 0))
        outcome = None
        if isinstance(resp, dict):
            shards = resp.get("_shards") or {}
            if status >= 500:
                outcome = "error"
            elif status == 429:
                outcome = "429"
            elif resp.get("timed_out"):
                outcome = "timeout"
            elif shards.get("failed"):
                failures = shards.get("failures") or []
                types = {(f.get("reason") or {}).get("type")
                         for f in failures}
                # duress sheds and device degradation get their own
                # outcome classes (workload attribution must show WHO
                # the breaker/shed degraded, not a generic "partial")
                outcome = ("shed" if "node_duress_exception" in types
                           else "device_degraded"
                           if "device_degraded_exception" in types
                           else "partial")
        n = len(sink) or 1
        for rec in sink:
            service.record(rec, opaque_id=opaque_id,
                           cpu_nanos=cpu // n, heap_bytes=heap,
                           outcome=outcome)

    # ------------------------------------------------------------------

    def _register_all(self):
        r = self.register
        r("GET", "/", self.h_root)
        r("GET", "/_cluster/health", self.h_cluster_health)
        r("GET", "/_cluster/state", self.h_cluster_state)
        r("GET", "/_cluster/stats", self.h_cluster_stats)
        r("GET", "/_nodes", self.h_nodes_info)
        r("GET", "/_nodes/stats", self.h_nodes_stats)
        r("GET", "/_nodes/trace", self.h_nodes_trace)
        r("GET", "/_nodes/hot_threads", self.h_hot_threads)
        r("GET", "/_nodes/flight_recorder", self.h_flight_recorder)
        r("GET", "/_insights/top_queries", self.h_insights_top_queries)
        r("GET", "/_metrics", self.h_metrics)
        r("GET", "/_cluster/settings", self.h_cluster_get_settings)
        r("PUT", "/_cluster/settings", self.h_cluster_put_settings)
        r("GET", "/_cat/indices", self.h_cat_indices)
        r("GET", "/_cat/health", self.h_cat_health)
        r("GET", "/_cat/count", self.h_cat_count)
        r("GET", "/_cat/count/{index}", self.h_cat_count)
        r("GET", "/_cat/shards", self.h_cat_shards)
        r("GET", "/_cat/nodes", self.h_cat_nodes)
        r("GET", "/_cat/aliases", self.h_cat_aliases)
        r("GET", "/_cat/templates", self.h_cat_templates)
        r("GET", "/_cat/segments", self.h_cat_segments)
        r("GET", "/_cat/recovery", self.h_cat_recovery)
        r("GET", "/_cat/recovery/{index}", self.h_cat_recovery)
        r("GET", "/_cat/repositories", self.h_cat_repositories)
        r("GET", "/_cat/snapshots/{repo}", self.h_cat_snapshots)
        r("GET", "/_cat/tasks", self.h_cat_tasks)
        r("GET", "/_cat/thread_pool", self.h_cat_thread_pool)
        r("GET", "/_cat/pending_tasks", self.h_cat_pending_tasks)
        r("GET", "/_cat/plugins", self.h_cat_plugins)
        r("GET", "/_cat/cluster_manager", self.h_cat_cluster_manager)
        r("GET", "/_cat/master", self.h_cat_cluster_manager)
        r("GET", "/_cat/nodeattrs", self.h_cat_nodeattrs)
        r("GET", "/_cat/allocation", self.h_cat_allocation)
        r("GET", "/_cat/fielddata", self.h_cat_fielddata)
        r("POST", "/_aliases", self.h_update_aliases)
        r("GET", "/_alias", self.h_get_alias)
        r("GET", "/_alias/{name}", self.h_get_alias)
        r("HEAD", "/_alias/{name}", self.h_alias_exists)
        r("GET", "/{index}/_alias", self.h_get_alias)
        r("PUT", "/{index}/_alias/{name}", self.h_put_alias)
        r("POST", "/{index}/_alias/{name}", self.h_put_alias)
        r("DELETE", "/{index}/_alias/{name}", self.h_delete_alias)
        r("POST", "/{index}/_rollover", self.h_rollover)
        r("POST", "/{index}/_rollover/{target}", self.h_rollover)
        r("PUT", "/{index}/_shrink/{target}", self.h_resize_shrink)
        r("POST", "/{index}/_shrink/{target}", self.h_resize_shrink)
        r("PUT", "/{index}/_split/{target}", self.h_resize_split)
        r("POST", "/{index}/_split/{target}", self.h_resize_split)
        r("PUT", "/{index}/_clone/{target}", self.h_resize_clone)
        r("POST", "/{index}/_clone/{target}", self.h_resize_clone)
        r("GET", "/{index}/_recovery", self.h_recovery)
        r("GET", "/_recovery", self.h_recovery)
        r("PUT", "/_data_stream/{name}", self.h_create_data_stream)
        r("GET", "/_data_stream", self.h_get_data_stream)
        r("GET", "/_data_stream/{name}", self.h_get_data_stream)
        r("DELETE", "/_data_stream/{name}", self.h_delete_data_stream)
        r("POST", "/_cluster/reroute", self.h_reroute)
        r("PUT", "/_index_template/{name}", self.h_put_template)
        r("POST", "/_index_template/{name}", self.h_put_template)
        r("GET", "/_index_template", self.h_get_template)
        r("GET", "/_index_template/{name}", self.h_get_template)
        r("DELETE", "/_index_template/{name}", self.h_delete_template)
        r("GET", "/_rank_eval", self.h_rank_eval)
        r("POST", "/_rank_eval", self.h_rank_eval)
        r("GET", "/{index}/_rank_eval", self.h_rank_eval)
        r("POST", "/{index}/_rank_eval", self.h_rank_eval)
        r("POST", "/_reindex", self.h_reindex)
        r("POST", "/{index}/_update_by_query", self.h_update_by_query)
        r("POST", "/{index}/_delete_by_query", self.h_delete_by_query)
        r("GET", "/_field_caps", self.h_field_caps)
        r("POST", "/_field_caps", self.h_field_caps)
        r("GET", "/{index}/_field_caps", self.h_field_caps)
        r("POST", "/{index}/_field_caps", self.h_field_caps)
        r("GET", "/{index}/_termvectors/{id}", self.h_termvectors)
        r("POST", "/{index}/_termvectors/{id}", self.h_termvectors)
        r("PUT", "/_ingest/pipeline/{id}", self.h_put_ingest)
        r("GET", "/_ingest/pipeline", self.h_get_ingest)
        r("GET", "/_ingest/pipeline/{id}", self.h_get_ingest)
        r("DELETE", "/_ingest/pipeline/{id}", self.h_delete_ingest)
        r("POST", "/_ingest/pipeline/{id}/_simulate",
          self.h_simulate_ingest)
        r("POST", "/_ingest/pipeline/_simulate", self.h_simulate_ingest)
        r("GET", "/_analyze", self.h_analyze)
        r("POST", "/_analyze", self.h_analyze)
        r("GET", "/{index}/_analyze", self.h_analyze)
        r("POST", "/{index}/_analyze", self.h_analyze)
        r("POST", "/_bulk", self.h_bulk)
        r("PUT", "/_bulk", self.h_bulk)
        r("POST", "/{index}/_bulk", self.h_bulk)
        r("PUT", "/{index}/_bulk", self.h_bulk)
        r("GET", "/_search", self.h_search)
        r("POST", "/_search", self.h_search)
        r("GET", "/_msearch", self.h_msearch)
        r("POST", "/_msearch", self.h_msearch)
        r("GET", "/_search/scroll", self.h_scroll_next)
        r("POST", "/_search/scroll", self.h_scroll_next)
        r("GET", "/_search/scroll/{scroll_id}", self.h_scroll_next)
        r("POST", "/_search/scroll/{scroll_id}", self.h_scroll_next)
        r("DELETE", "/_search/scroll/_all", self.h_scroll_clear_all)
        r("DELETE", "/_search/scroll", self.h_scroll_clear)
        r("DELETE", "/_search/scroll/{scroll_id}", self.h_scroll_clear)
        r("DELETE", "/_search/point_in_time", self.h_pit_close)
        r("GET", "/_search/pipeline", self.h_get_pipelines)
        r("GET", "/_search/pipeline/{id}", self.h_get_pipeline)
        r("PUT", "/_search/pipeline/{id}", self.h_put_pipeline)
        r("DELETE", "/_search/pipeline/{id}", self.h_delete_pipeline)
        r("GET", "/_count", self.h_count)
        r("POST", "/_count", self.h_count)
        r("GET", "/_mapping", self.h_get_mapping_all)
        r("GET", "/_refresh", self.h_refresh)
        r("POST", "/_refresh", self.h_refresh)
        r("GET", "/_security/user", self.h_security_list_users)
        r("PUT", "/_security/user/{username}", self.h_security_put_user)
        r("DELETE", "/_security/user/{username}",
          self.h_security_delete_user)
        r("GET", "/_tasks", self.h_tasks_list)
        r("GET", "/_persistent_tasks", self.h_persistent_tasks_list)
        r("GET", "/_tasks/{task_id}", self.h_task_get)
        r("POST", "/_tasks/{task_id}/_cancel", self.h_task_cancel)
        r("POST", "/_tasks/_cancel", self.h_tasks_cancel_all)
        r("POST", "/_remotestore/_restore", self.h_remotestore_restore)
        r("GET", "/_snapshot", self.h_get_repos)
        r("PUT", "/_snapshot/{repo}", self.h_put_repo)
        r("POST", "/_snapshot/{repo}", self.h_put_repo)
        r("GET", "/_snapshot/{repo}", self.h_get_repo)
        r("DELETE", "/_snapshot/{repo}", self.h_delete_repo)
        r("PUT", "/_snapshot/{repo}/{snapshot}", self.h_create_snapshot)
        r("POST", "/_snapshot/{repo}/{snapshot}", self.h_create_snapshot)
        r("GET", "/_snapshot/{repo}/{snapshot}", self.h_get_snapshot)
        r("DELETE", "/_snapshot/{repo}/{snapshot}", self.h_delete_snapshot)
        r("POST", "/_snapshot/{repo}/{snapshot}/_restore",
          self.h_restore_snapshot)

        r("PUT", "/{index}", self.h_create_index)
        r("DELETE", "/{index}", self.h_delete_index)
        r("GET", "/{index}", self.h_get_index)
        r("HEAD", "/{index}", self.h_index_exists)
        r("GET", "/{index}/_mapping", self.h_get_mapping)
        r("PUT", "/{index}/_mapping", self.h_put_mapping)
        r("GET", "/{index}/_settings", self.h_get_settings)
        r("PUT", "/{index}/_settings", self.h_put_index_settings)
        r("GET", "/{index}/_stats", self.h_index_stats)
        r("POST", "/{index}/_refresh", self.h_refresh)
        r("GET", "/{index}/_refresh", self.h_refresh)
        r("POST", "/_cache/clear", self.h_cache_clear)
        r("POST", "/{index}/_cache/clear", self.h_cache_clear)
        r("POST", "/{index}/_flush", self.h_flush)
        r("POST", "/{index}/_forcemerge", self.h_forcemerge)
        r("GET", "/{index}/_count", self.h_count)
        r("POST", "/{index}/_count", self.h_count)
        r("GET", "/{index}/_search", self.h_search)
        r("POST", "/{index}/_search", self.h_search)
        r("GET", "/{index}/_msearch", self.h_msearch)
        r("POST", "/{index}/_msearch", self.h_msearch)
        r("POST", "/{index}/_search/point_in_time", self.h_pit_open)
        r("POST", "/{index}/_doc", self.h_index_doc_auto)
        r("PUT", "/{index}/_doc/{id}", self.h_index_doc)
        r("POST", "/{index}/_doc/{id}", self.h_index_doc)
        r("GET", "/{index}/_doc/{id}", self.h_get_doc)
        r("HEAD", "/{index}/_doc/{id}", self.h_doc_exists)
        r("DELETE", "/{index}/_doc/{id}", self.h_delete_doc)
        r("GET", "/{index}/_source/{id}", self.h_get_source)
        r("PUT", "/{index}/_create/{id}", self.h_create_doc)
        r("POST", "/{index}/_create/{id}", self.h_create_doc)
        r("POST", "/{index}/_update/{id}", self.h_update_doc)
        r("POST", "/_mget", self.h_mget)
        r("POST", "/{index}/_mget", self.h_mget)
        r("GET", "/{index}/_mget", self.h_mget)

    # -- info / cluster ----------------------------------------------------

    def h_root(self, req):
        return 200, {
            "name": self.node.name,
            "cluster_name": self.node.cluster_name,
            "cluster_uuid": self.node.cluster_uuid,
            "version": {"number": VERSION,
                        "distribution": "opensearch-tpu"},
            "tagline": "The OpenSearch Project: https://opensearch.org/",
        }

    def h_cluster_health(self, req):
        indices = self.node.indices.indices
        unassigned = sum(s.num_replicas * s.num_shards
                         for s in indices.values())
        active = sum(s.num_shards for s in indices.values())
        status = "yellow" if unassigned else "green"
        # a shard copy that failed store verification (corruption
        # marker on disk) makes the cluster red — Store.verify /
        # CorruptedFileException surfaced the way the reference fails
        # the shard
        corrupted = {name: sorted(svc.corrupted_shards())
                     for name, svc in indices.items()
                     if svc.corrupted_shards()}
        if corrupted:
            status = "red"
        extra = ({"corrupted_shards": sum(len(v)
                                          for v in corrupted.values())}
                 if corrupted else {})
        return 200, {
            **extra,
            "cluster_name": self.node.cluster_name,
            "status": status,
            "timed_out": False,
            "discovered_master": True,
            "discovered_cluster_manager": True,
            "number_of_nodes": 1,
            "number_of_data_nodes": 1,
            "active_primary_shards": active,
            "active_shards": active,
            "relocating_shards": 0,
            "initializing_shards": 0,
            "unassigned_shards": unassigned,
            "delayed_unassigned_shards": 0,
            "number_of_pending_tasks": 0,
            "number_of_in_flight_fetch": 0,
            "task_max_waiting_in_queue_millis": 0,
            "active_shards_percent_as_number": 100.0,
            **self._health_indices_level(req, indices),
        }

    def _health_indices_level(self, req, indices) -> dict:
        """?level=indices|shards adds the per-index (and per-shard)
        breakdown (ClusterHealthResponse levels)."""
        level = req.param("level", "cluster")
        if level not in ("indices", "shards"):
            return {}
        out = {}
        for name, svc in indices.items():
            st = "yellow" if svc.num_replicas else "green"
            entry = {
                "status": st,
                "number_of_shards": svc.num_shards,
                "number_of_replicas": svc.num_replicas,
                "active_primary_shards": svc.num_shards,
                "active_shards": svc.num_shards,
                "relocating_shards": 0,
                "initializing_shards": 0,
                "unassigned_shards": svc.num_replicas * svc.num_shards,
            }
            if level == "shards":
                entry["shards"] = {
                    str(i): {"status": st, "primary_active": True,
                             "active_shards": 1, "relocating_shards": 0,
                             "initializing_shards": 0,
                             "unassigned_shards": svc.num_replicas}
                    for i in range(svc.num_shards)}
            out[name] = entry
        return {"indices": out}

    def h_cluster_state(self, req):
        return 200, {
            "cluster_name": self.node.cluster_name,
            "cluster_uuid": self.node.cluster_uuid,
            "metadata": {"indices": {
                name: {**svc.get_settings(), **svc.get_mapping()}
                for name, svc in self.node.indices.indices.items()}},
        }

    def h_cluster_stats(self, req):
        from opensearch_tpu.common.device_health import device_health
        from opensearch_tpu.common.device_ledger import device_ledger
        indices = self.node.indices.indices
        dev = device_ledger().stats()
        health = device_health().stats()
        return 200, {
            "cluster_name": self.node.cluster_name,
            "indices": {"count": len(indices),
                        "docs": {"count": sum(s.doc_count()
                                              for s in indices.values())}},
            "nodes": {"count": {"total": 1, "data": 1}},
            # compact device-residency + fault-tolerance rollup (full
            # detail per node in _nodes/stats `device`)
            "device": {
                "resident_bytes": dev["resident_bytes"],
                "resident_segments": dev["resident_segments"],
                "budget_bytes": dev["budget"]["budget_bytes"],
                "evictions": dev["budget"]["evictions"],
                "breaker_trips": sum(
                    b["trips"] for b in health["breakers"].values()),
                "breakers_open": sum(
                    1 for b in health["breakers"].values()
                    if b["state"] != "closed"),
                "poisoned_results": health["poisoned_results"],
            },
        }

    def h_nodes_info(self, req):
        return 200, {"cluster_name": self.node.cluster_name, "nodes": {
            self.node.node_id: {"name": self.node.name,
                                "version": VERSION,
                                "roles": ["cluster_manager", "data"]}}}

    def h_nodes_stats(self, req):
        from opensearch_tpu.common.breakers import breaker_service
        from opensearch_tpu.common.telemetry import (gc_timer, metrics,
                                                     tracer)
        from opensearch_tpu.indices.request_cache import request_cache
        # probe on read: stats reflect CURRENT disk health, not boot-time
        self.node.fs_health.check()
        indices = self.node.indices.indices
        return 200, {"cluster_name": self.node.cluster_name, "nodes": {
            self.node.node_id: {
                "name": self.node.name,
                "indices": {"docs": {"count": sum(
                    s.doc_count() for s in indices.values())},
                    "request_cache": request_cache().stats(),
                    # query-hot-path observability: compiled-plan reuse
                    # and block-max segment pruning (PR-1 registry
                    # counters fed by ShardSearcher)
                    "search": {
                        "plan_cache": {
                            "hits": metrics().counter(
                                "search.plan_cache.hits").value,
                            "misses": metrics().counter(
                                "search.plan_cache.misses").value},
                        "segments_pruned": metrics().counter(
                            "search.segments_pruned").value}},
                "breakers": breaker_service().stats(),
                "tasks": {"count": len(self.node.task_manager.list())},
                "thread_pool": self.node.thread_pool.stats(),
                "fs": {"health": self.node.fs_health.stats()},
                "file_cache": self.node.indices.file_cache.stats(),
                "indexing_pressure":
                    self.node.indices.indexing_pressure.stats(),
                # overload-protection observability: duress trackers,
                # cancellation accounting, admission gate occupancy
                "search_backpressure":
                    self.node.search_backpressure.stats(),
                # coordinator-side adaptive replica selection: per-node
                # EWMAs, C3 ranks, duress verdicts, and the reroute/shed
                # counters (ResponseCollectorService / the reference's
                # AdaptiveSelectionStats in _nodes/stats)
                "adaptive_selection": {
                    "nodes": self.node.response_collector.stats(),
                    "reroutes": metrics().counter(
                        "search.replica_selection.reroutes").value,
                    "sheds": metrics().counter(
                        "search.replica_selection.sheds").value,
                    # the unified overload budget: edge 429s and
                    # coordinator duress sheds draw from ONE admission
                    # gate, so its occupancy/rejection ledger shows up
                    # here too (same numbers as search_backpressure's
                    # admission_control block, by construction)
                    "budget":
                        self.node.search_backpressure.admission.stats(),
                },
                # always-on workload attribution: record totals, rollup
                # cardinality, and the coalescability fraction (full
                # detail at GET /_insights/top_queries)
                "query_insights": self.node.insights.stats(),
                # per-tenant attribution (who sent what, at what cost,
                # how often degraded) + the adaptive QoS controller's
                # state: current knob values and the bounded audit ring
                # of every adaptation with its triggering evidence
                "tenants": self.node.insights.tenants(),
                "qos": self.node.qos.stats(),
                # the unified query engine: continuous-batcher
                # accounting (members batched / bypasses / window
                # waits / shared dispatches) + the bounded search
                # threadpool (search/engine.py)
                "search_engine": _query_engine_stats(),
                # device residency + transfer observability: ledger
                # rollups per index, stage/fetch transfer counters, the
                # device.memory.budget_bytes eviction accounting, the
                # per-kernel compile registry, and the backend's own
                # memory_stats() where the platform provides it
                "device": _device_stats(),
                # recovery observability: the recovery.* metric family
                # (incl. PR 8's corrupt-blob re-requests) + per-shard
                # store state, the JSON face of GET /_cat/recovery
                "recovery": self._recovery_stats(),
                # replication safety: per-shard (term, checkpoint)
                # positions + the fencing / rollback / resync counter
                # family (the write-path durability ledger)
                "replication": self._replication_stats(),
                "os": _os_stats(),
                "process": _process_stats(),
                # the interpreter's collector: collections run and the
                # time they stopped every thread for (cumulative)
                "runtime": {"gc": gc_timer().stats()},
                # counters + latency histograms with p50/p90/p99 readout
                # (the telemetry SPI's MetricsRegistry surface), and the
                # tracer's totals: what every finished span of a name
                # adds up to (wall, thread CPU, off-CPU, parts), kept
                # where a span ends and so whole whatever the ring of
                # GET /_nodes/trace still holds
                "telemetry": {**metrics().stats(),
                              "spans": tracer().totals(),
                              "tracer": tracer().stats()},
            }}}

    def _recovery_stats(self) -> dict:
        from opensearch_tpu.common.telemetry import metrics

        m = metrics()
        shards = []
        for svc in sorted(self.node.indices.indices.values(),
                          key=lambda s: s.name):
            corrupted = svc.corrupted_shards()
            for shard_id in sorted(svc.local_shards):
                row = {"index": svc.name, "shard": shard_id,
                       "type": "store",
                       "stage": ("corrupted"
                                 if shard_id in corrupted else "done")}
                if shard_id in corrupted:
                    row["corruption"] = corrupted[shard_id]
                shards.append(row)
        return {
            "corrupt_blobs": m.counter("recovery.corrupt_blobs").value,
            "retries": {
                name: {
                    # metric-name-ok: bounded recovery action names
                    "attempts": m.counter(
                        f"retry.recovery.{name}.attempts").value,
                    # metric-name-ok: bounded recovery action names
                    "retries": m.counter(
                        f"retry.recovery.{name}.retries").value,
                    # metric-name-ok: bounded recovery action names
                    "exhausted": m.counter(
                        f"retry.recovery.{name}.exhausted").value,
                } for name in ("start", "report", "fetch")},
            # search-replica tier: remote-store segment replication
            # accounting (publishes, searcher installs/refills, CRC
            # re-fetches, bytes pulled through the FileCache)
            "segment_replication": {
                # metric-name-ok: bounded segrep counter family
                name: m.counter(f"segrep.{name}").value
                for name in ("publishes", "publish_failures",
                             "installs", "install_failures", "fetches",
                             "bytes_pulled", "corrupt_blobs",
                             "refills", "refill_failures")},
            "shards": shards,
        }

    def _replication_stats(self) -> dict:
        """Single-node face of the cluster nodes' ``replication_stats()``
        block: every local shard is its own primary, so the interesting
        signal here is the (term, local/global checkpoint) positions
        plus the process-wide replication.* counters (which a cluster
        test sharing the process also feeds)."""
        from opensearch_tpu.common.telemetry import metrics

        m = metrics()
        shards = []
        for svc in sorted(self.node.indices.indices.values(),
                          key=lambda s: s.name):
            for shard_id, engine in sorted(svc.local_shards.items()):
                shards.append({
                    "index": svc.name, "shard": shard_id,
                    "primary_term": engine.primary_term,
                    "max_seq_no": engine._seq_no,
                    "local_checkpoint": engine.local_checkpoint,
                    "global_checkpoint": engine.global_checkpoint,
                })
        return {
            "shards": shards,
            # metric-name-ok: bounded replication counter family
            "counters": {name: m.counter(f"replication.{name}").value
                         for name in ("fenced_ops",
                                      "stale_primary_rejections",
                                      "rollbacks", "resyncs",
                                      "resync_failures",
                                      "durability_checked_ops")},
        }

    def h_nodes_trace(self, req):
        """Recent finished spans from the bounded in-memory exporter —
        a debug surface over the tracing SPI (the reference exports via
        OTLP; this engine keeps a ring buffer readable over REST)."""
        from opensearch_tpu.common.telemetry import tracer
        limit = int(req.param("size", 100))
        spans = tracer().recent(limit, trace_id=req.param("trace_id"))
        out = {"name": self.node.name, **tracer().stats(), "spans": spans}
        if spans:
            # monotonic, like every span's start_time_in_nanos: a window
            # that began before it lies (in part) outside what was read,
            # which is not the same as a window in which nothing ran
            out["oldest_start_time_in_nanos"] = min(
                s["start_time_in_nanos"] for s in spans)
        return 200, {"cluster_name": self.node.cluster_name,
                     "nodes": {self.node.node_id: out}}

    def h_metrics(self, req):
        """Prometheus text exposition of the full MetricsRegistry —
        counters as ``*_total``, latency histograms as cumulative
        ``_bucket{le=...}`` + ``_sum``/``_count`` (milliseconds) — plus
        the query-insights per-signature series (signature is always a
        LABEL drawn from the bounded top-N path, never a metric name).
        The same underlying data ``_nodes/stats`` serves as JSON."""
        from opensearch_tpu.common.device_ledger import device_ledger
        from opensearch_tpu.common.telemetry import metrics, tracer
        text = metrics().prometheus_text() + tracer().prometheus_text()
        insights = getattr(self.node, "insights", None)
        if insights is not None:
            text += insights.prometheus_text()
        # device residency gauges (transfer/eviction counters already
        # flow through the MetricsRegistry exposition above)
        text += device_ledger().prometheus_text()
        # device breaker-state gauges (trip/close/poison counters flow
        # through the MetricsRegistry exposition above)
        from opensearch_tpu.common.device_health import device_health
        text += device_health().prometheus_text()
        return 200, PlainText(
            text,
            content_type="text/plain; version=0.0.4; charset=utf-8")

    def h_insights_top_queries(self, req):
        """Always-on top-N query attribution + per-plan-signature
        workload stats (``GET /_insights/top_queries``): ranked by
        ``?by=latency|cpu|heap``, with the per-signature rollups and
        the coalescability report the continuous batcher sizes from.
        Single-node deployments serve their local section in the same
        fan-in shape the cluster coordinator's merge produces."""
        from opensearch_tpu.search.insights import merge_sections
        by = req.param("by", "latency")
        n = req.param("size") or req.param("n")
        n = int(n) if n is not None else self.node.insights.top_n
        section = self.node.insights.section(by=by, n=n)
        merged = merge_sections({self.node.node_id: section},
                                by=by, n=n)
        merged["cluster_name"] = self.node.cluster_name
        return 200, merged

    def h_flight_recorder(self, req):
        """Recent flight-recorder captures (slow-log trips, soak SLO
        breaches): spans + counters snapshotted at trigger time."""
        from opensearch_tpu.common.telemetry import flight_recorder
        limit = int(req.param("size", 32))
        return 200, {"cluster_name": self.node.cluster_name,
                     "nodes": {self.node.node_id: {
                         "name": self.node.name,
                         "captures":
                             flight_recorder().captures(limit)}}}

    def h_hot_threads(self, req):
        """Per-thread stack dump (RestNodesHotThreadsAction analog over
        sys._current_frames — the busiest diagnostic when a query
        wedges host-side)."""
        import sys
        import threading as _threading
        import traceback

        names = {t.ident: t.name for t in _threading.enumerate()}
        lines = [f"::: {{{self.node.name}}}{{{self.node.node_id}}}"]
        for ident, frame in sorted(sys._current_frames().items()):
            lines.append(
                f"\n   thread [{names.get(ident, '?')}] id [{ident}]:")
            lines.extend(
                "     " + ln.rstrip() for ln in
                traceback.format_stack(frame))
        return 200, {"nodes": {self.node.node_id: {
            "name": self.node.name,
            "hot_threads": "\n".join(lines)}}}

    def h_cat_indices(self, req):
        rows = []
        for name, svc in sorted(self.node.indices.indices.items()):
            health = "red" if svc.corrupted_shards() else "green"
            rows.append({"health": health, "status": "open", "index": name,
                         "uuid": svc.uuid, "pri": str(svc.num_shards),
                         "rep": str(svc.num_replicas),
                         "docs.count": str(svc.doc_count())})
        return 200, rows

    def h_cat_health(self, req):
        h = self.h_cluster_health(req)[1]
        return 200, [{"cluster": h["cluster_name"], "status": h["status"],
                      "node.total": "1", "shards": str(h["active_shards"])}]

    def h_cat_count(self, req):
        targets = (self._target_indices(req)
                   if req.path_params.get("index")
                   else self.node.indices.indices.values())
        total = sum(s.doc_count() for s in targets)
        now = time.time()   # wall-clock: epoch/timestamp columns
        return 200, [{"epoch": str(int(now)),
                      "timestamp": time.strftime("%H:%M:%S",
                                                 time.gmtime(now)),
                      "count": str(total)}]

    def h_cat_shards(self, req):
        rows = []
        for name, svc in sorted(self.node.indices.indices.items()):
            for engine in svc.shards:
                rows.append({"index": name, "shard": str(engine.shard_id),
                             "prirep": "p", "state": "STARTED",
                             "docs": str(engine.doc_count())})
        return 200, rows

    # -- index admin -------------------------------------------------------

    def h_create_index(self, req):
        name = req.path_params["index"]
        self.node.indices.create(name, req.json({}))
        return 200, {"acknowledged": True, "shards_acknowledged": True,
                     "index": name}

    def h_delete_index(self, req):
        for svc in self.node.indices.resolve(req.path_params["index"]):
            self.node.indices.delete(svc.name)
        return 200, {"acknowledged": True}

    def h_get_index(self, req):
        svc = self.node.indices.get(req.path_params["index"])
        aliases = (self.node.indices.get_aliases(index=svc.name)
                   .get(svc.name, {}).get("aliases", {}))
        return 200, {svc.name: {"aliases": aliases, **svc.get_mapping(),
                                **svc.get_settings()}}

    def h_index_exists(self, req):
        if self.node.indices.exists(req.path_params["index"]):
            return 200, {}
        return 404, {}

    def h_get_mapping(self, req):
        svc = self.node.indices.get(req.path_params["index"])
        return 200, {svc.name: svc.get_mapping()}

    def h_get_mapping_all(self, req):
        return 200, {name: svc.get_mapping()
                     for name, svc in self.node.indices.indices.items()}

    def h_put_mapping(self, req):
        svc = self.node.indices.get(req.path_params["index"])
        svc.put_mapping(req.json({}))
        return 200, {"acknowledged": True}

    def h_get_settings(self, req):
        svc = self.node.indices.get(req.path_params["index"])
        return 200, {svc.name: svc.get_settings()}

    def h_index_stats(self, req):
        svc = self.node.indices.get(req.path_params["index"])
        stats = svc.stats()
        return 200, {"_all": {"primaries": stats, "total": stats},
                     "indices": {svc.name: {"primaries": stats,
                                            "total": stats}}}

    def h_refresh(self, req):
        services = self._target_indices(req)
        for svc in services:
            svc.refresh()
        n = sum(s.num_shards for s in services)
        return 200, {"_shards": {"total": n, "successful": n, "failed": 0}}

    def h_cache_clear(self, req):
        """POST [/{index}]/_cache/clear (RestClearIndicesCacheAction):
        ``?request=false`` skips the request cache — the only cache type
        with a clear hook here; fielddata/query params are accepted and
        ignored like unsupported cache types in the reference."""
        from opensearch_tpu.indices.request_cache import request_cache
        expr = req.path_params.get("index")
        services = (self.node.indices.resolve(expr) if expr
                    else list(self.node.indices.indices.values()))
        clear_request = (req.param("request") is None
                         or req.flag("request"))
        if clear_request:
            for svc in services:
                request_cache().clear(index=svc.name)
        n = sum(s.num_shards for s in services)
        return 200, {"_shards": {"total": n, "successful": n,
                                 "failed": 0}}

    def h_flush(self, req):
        svc = self.node.indices.get(req.path_params["index"])
        svc.flush()
        return 200, {"_shards": {"total": svc.num_shards,
                                 "successful": svc.num_shards, "failed": 0}}

    def h_forcemerge(self, req):
        svc = self.node.indices.get(req.path_params["index"])
        svc.force_merge(int(req.param("max_num_segments", 1)))
        return 200, {"_shards": {"total": svc.num_shards,
                                 "successful": svc.num_shards, "failed": 0}}

    def h_rank_eval(self, req):
        from opensearch_tpu.search.rank_eval import run_rank_eval

        body = req.json({}) or {}
        default_index = req.path_params.get("index")

        def search_fn(index_expr, search_body):
            if default_index and index_expr == "_all":
                index_expr = default_index
            targets = self.node.indices.resolve_with_filters(index_expr)
            if len(targets) == 1:
                svc, flt = targets[0]
                return svc.search(self._apply_alias_filter(search_body,
                                                           flt))
            return self._multi_index_search(targets, search_body)
        return 200, run_rank_eval(body, search_fn)

    # -- reindex family (scroll-read + bulk-write; modules/reindex) --------

    def _scan_all(self, svc, query):
        """Every matching (engine, _id, source) via the scroll
        materialization path, PER SHARD ENGINE — write-backs go straight
        to the owning engine, so custom-routed docs are never mis-routed
        through id-based rerouting."""
        for engine in svc.shards:
            searcher = engine.acquire_searcher()
            rows, _total = searcher.scan_rows({"query": query})
            for row in rows:
                seg = searcher.segments[row["seg"]]
                local = row["local"]
                yield engine, seg.doc_ids[local], seg.source(local)

    def _validate_reindex(self, body) -> None:
        """Cheap request checks shared by both modes — a malformed async
        request must 400 at submit time, not become a persisted failed
        task."""
        src = body.get("source") or {}
        dest = body.get("dest") or {}
        if not src.get("index") or not dest.get("index"):
            raise ValidationError(
                "[reindex] requires source.index and dest.index")
        services = self.node.indices.resolve(src["index"])
        dest_svc = self.node.indices.write_index_for(dest["index"])
        if any(svc.name == dest_svc.name for svc in services):
            raise ValidationError(
                "reindex cannot write into its own source index")

    def h_reindex(self, req):
        body = req.json({}) or {}
        self._validate_reindex(body)
        if str(req.param("wait_for_completion",
                         "true")).lower() == "false":
            # runs as a PERSISTENT task: durably recorded, resumed on
            # restart (ref persistent/PersistentTasksService.java:47;
            # reindex is idempotent — doc ids overwrite)
            task_id = self.node.persistent_tasks.submit(
                "indices:data/write/reindex", body)
            return 200, {"task": task_id}
        return 200, self._do_reindex(body)

    def _do_reindex(self, body):
        src = body.get("source") or {}
        dest = body.get("dest") or {}
        if not src.get("index") or not dest.get("index"):
            raise ValidationError(
                "[reindex] requires source.index and dest.index")
        services = self.node.indices.resolve(src["index"])
        dest_svc = self.node.indices.write_index_for(dest["index"])
        # validate BEFORE any copy: a partial write then a 400 would lie
        if any(svc.name == dest_svc.name for svc in services):
            raise ValidationError(
                "reindex cannot write into its own source index")
        pid = dest.get("pipeline")
        created = updated = total = 0
        t0 = time.monotonic()
        for svc in services:
            for _eng, doc_id, source in self._scan_all(svc,
                                                       src.get("query")):
                total += 1
                if pid:
                    source = self.node.ingest.process(pid, source)
                    if source is None:
                        continue
                r = dest_svc.index_doc(doc_id, source)
                if r.result == "created":
                    created += 1
                else:
                    updated += 1
        dest_svc.refresh()
        return {"took": int((time.monotonic() - t0) * 1000),
                "total": total, "created": created,
                "updated": updated, "deleted": 0, "failures": []}

    def h_update_by_query(self, req):
        body = req.json({}) or {}
        services = self._target_indices(req)
        if body.get("script") is not None:
            # painless update scripts mutate via ctx._source assignments
            # — unsupported; full-document transforms go through ingest
            raise ValidationError(
                "[update_by_query] with [script] is not supported — use "
                "an ingest [pipeline] instead")
        pid = req.param("pipeline")
        total = updated = 0
        t0 = time.monotonic()
        for svc in services:
            for engine, doc_id, source in self._scan_all(
                    svc, body.get("query")):
                total += 1
                if pid:
                    source = self.node.ingest.process(pid, source)
                    if source is None:
                        continue
                engine.index(doc_id, source)    # owning shard directly
                updated += 1
            for engine in svc.shards:
                engine.ensure_synced()          # durable BEFORE the ack
            svc.invalidate_searcher()
            svc.refresh()
        return 200, {"took": int((time.monotonic() - t0) * 1000),
                     "total": total, "updated": updated,
                     "failures": []}

    def h_delete_by_query(self, req):
        body = req.json({}) or {}
        if body.get("query") is None:
            raise ValidationError("[delete_by_query] requires [query]")
        services = self._target_indices(req)
        total = deleted = 0
        t0 = time.monotonic()
        for svc in services:
            for engine, doc_id, _source in self._scan_all(
                    svc, body["query"]):
                total += 1
                r = engine.delete(doc_id)   # owning shard directly
                if r.result == "deleted":
                    deleted += 1
            for engine in svc.shards:
                engine.ensure_synced()          # durable BEFORE the ack
            svc.invalidate_searcher()
            svc.refresh()
        return 200, {"took": int((time.monotonic() - t0) * 1000),
                     "total": total, "deleted": deleted,
                     "failures": []}

    # -- field_caps / termvectors ------------------------------------------

    def h_field_caps(self, req):
        body = req.json({}) or {}
        fields = req.param("fields") or body.get("fields")
        if not fields:
            raise ValidationError("[_field_caps] requires [fields]")
        if isinstance(fields, str):
            fields = [f.strip() for f in fields.split(",") if f.strip()]
        import fnmatch as _fn
        services = self._target_indices(req)
        caps: dict[str, dict] = {}
        for svc in services:
            for path, ft in svc.mapper.field_types().items():
                if not any(_fn.fnmatchcase(path, p) for p in fields):
                    continue
                entry = caps.setdefault(path, {})
                entry.setdefault(ft.type_name, {
                    "type": ft.type_name,
                    "searchable": bool(ft.index_enabled
                                       or ft.dv_kind != "none"),
                    "aggregatable": ft.dv_kind != "none",
                })
        return 200, {"indices": sorted(s.name for s in services),
                     "fields": caps}

    def h_termvectors(self, req):
        name = req.path_params["index"]
        svc = self._single_index(name)
        doc = svc.get_doc(req.path_params["id"])
        if doc is None:
            return 404, {"_index": name, "_id": req.path_params["id"],
                         "found": False}
        body = req.json({}) or {}
        wanted = body.get("fields") or req.param("fields")
        if isinstance(wanted, str):
            wanted = [f.strip() for f in wanted.split(",")]
        source = doc.get("_source") or {}
        term_vectors = {}
        for field, ft in svc.mapper.field_types().items():
            if wanted and field not in wanted:
                continue
            if not hasattr(ft, "search_terms"):
                continue
            from opensearch_tpu.ingest.service import path_get
            value = path_get(source, field)
            if value is None:
                continue
            analyzer = svc.mapper.analyzers.get(
                getattr(ft, "analyzer_name", "standard"))
            terms: dict[str, dict] = {}
            values = value if isinstance(value, list) else [value]
            pos_base = 0
            for v in values:             # arrays analyze per element
                for tok in analyzer.analyze(str(v)):
                    t = terms.setdefault(tok.term, {"term_freq": 0,
                                                    "tokens": []})
                    t["term_freq"] += 1
                    t["tokens"].append({
                        "position": pos_base + tok.position,
                        "start_offset": tok.start_offset,
                        "end_offset": tok.end_offset})
                pos_base += 100          # position_increment_gap analog
            if terms:
                term_vectors[field] = {"terms": terms}
        return 200, {"_index": name, "_id": req.path_params["id"],
                     "found": True, "term_vectors": term_vectors}

    # -- ingest pipelines --------------------------------------------------

    def h_put_ingest(self, req):
        return 200, self.node.ingest.put(req.path_params["id"],
                                         req.json({}) or {})

    def h_get_ingest(self, req):
        return 200, self.node.ingest.get(req.path_params.get("id"))

    def h_delete_ingest(self, req):
        return 200, self.node.ingest.delete(req.path_params["id"])

    def h_simulate_ingest(self, req):
        body = req.json({}) or {}
        pid = req.path_params.get("id")
        pipeline = (self.node.ingest.get(pid)[pid] if pid
                    else body.get("pipeline") or {})
        return 200, self.node.ingest.simulate(pipeline,
                                              body.get("docs") or [])

    def _ingest_pipeline_for(self, req, svc) -> Optional[str]:
        """?pipeline= param, else the index's default_pipeline setting
        (IndexSettings.DEFAULT_PIPELINE)."""
        pid = req.param("pipeline")
        if pid:
            return None if pid == "_none" else pid
        default = svc.settings.get("default_pipeline")
        return default if default and default != "_none" else None

    # -- documents ---------------------------------------------------------

    @staticmethod
    def _bulk_source_param(req):
        """URL-level _source/_source_includes/_source_excludes default
        for bulk update items."""
        if req.param("_source") is not None:
            return req.param("_source")
        inc = req.param("_source_includes")
        exc = req.param("_source_excludes")
        if inc or exc:
            spec = {}
            if inc:
                spec["includes"] = inc.split(",")
            if exc:
                spec["excludes"] = exc.split(",")
            return spec
        return None

    def _maybe_refresh(self, svc, req, doc_id=None) -> bool:
        refresh = req.param("refresh")
        if refresh is not None and str(refresh).lower() in ("", "true",
                                                            "wait_for"):
            if doc_id is not None:
                # a single-doc write refreshes only its owning shard
                svc.refresh_doc_shard(str(doc_id), req.param("routing"))
            else:
                svc.refresh()
            # wait_for reports forced_refresh=false (the write merely
            # waited); an explicit refresh reports true
            return str(refresh).lower() != "wait_for"
        return False

    def h_index_doc(self, req, doc_id=None, op_type=None):
        name = req.path_params["index"]
        svc = self.node.indices.write_index_for(name)
        doc_id = doc_id or req.path_params.get("id")
        if doc_id is not None and len(str(doc_id).encode("utf-8")) > 512:
            raise ValidationError(
                f"id is too long, must be no longer than 512 bytes but "
                f"was: {len(str(doc_id).encode('utf-8'))}")
        source = req.json()
        if not isinstance(source, dict):
            raise ParsingError("request body is required and must be a JSON "
                               "object")
        pid = self._ingest_pipeline_for(req, svc)
        if pid is not None:
            source = self.node.ingest.process(pid, source)
            if source is None:             # drop processor
                return 200, {"_index": name, "_id": doc_id,
                             "result": "noop"}
        kw = {}
        if req.param("if_seq_no") is not None:
            kw["if_seq_no"] = req.int_param("if_seq_no")
        if req.param("if_primary_term") is not None:
            kw["if_primary_term"] = req.int_param("if_primary_term")
        if req.param("version") is not None:
            kw["version"] = req.int_param("version")
            kw["version_type"] = req.param("version_type", "internal")
        if ((op_type or req.param("op_type")) == "create"
                and kw.get("version_type", "internal") != "internal"):
            raise ValidationError(
                "Validation Failed: 1: create operations only support "
                "internal versioning. use index instead;")
        if (op_type or req.param("op_type")) == "create" and doc_id is not None:
            if svc.get_doc(doc_id, req.param("routing")) is not None:
                from opensearch_tpu.common.errors import VersionConflictError
                raise VersionConflictError(doc_id, "document to be absent",
                                           "exists")
        r = svc.index_doc(doc_id, source, routing=req.param("routing"),
                          op_bytes=len(req.raw_body or b""), **kw)
        forced = self._maybe_refresh(svc, req, doc_id=r.doc_id)
        status = 201 if r.result == "created" else 200
        out = {"_index": svc.name, "_id": r.doc_id,
               "_version": r.version, "_seq_no": r.seq_no,
               # the engine's REAL primary term (bumped on promotion),
               # not a hardcoded 1 — fencing is observable to clients
               "_primary_term": r.primary_term, "result": r.result,
               "_shards": {"total": 1, "successful": 1, "failed": 0}}
        if forced:
            out["forced_refresh"] = True
        return status, out

    def h_index_doc_auto(self, req):
        return self.h_index_doc(req, doc_id=None)

    def h_create_doc(self, req):
        return self.h_index_doc(req, op_type="create")

    def h_get_doc(self, req):
        name = req.path_params["index"]
        svc = self._single_index(name)
        doc = svc.get_doc(req.path_params["id"], req.param("routing"),
                          realtime=req.param("realtime", "true") != "false")
        if doc is None:
            return 404, {"_index": name, "_id": req.path_params["id"],
                         "found": False}
        if req.param("version") is not None \
                and req.int_param("version") != doc["_version"]:
            from opensearch_tpu.common.errors import VersionConflictError
            raise VersionConflictError(req.path_params["id"],
                                       req.param("version"),
                                       doc["_version"])
        return 200, {"_index": name, **doc}

    def h_doc_exists(self, req):
        svc = self._single_index(req.path_params["index"])
        doc = svc.get_doc(req.path_params["id"], req.param("routing"))
        return (200, {}) if doc is not None else (404, {})

    def h_get_source(self, req):
        name = req.path_params["index"]
        svc = self._single_index(name)
        doc = svc.get_doc(req.path_params["id"], req.param("routing"))
        if doc is None:
            raise DocumentMissingError(name, req.path_params["id"])
        if "_source" not in doc:
            from opensearch_tpu.common.errors import ResourceNotFoundError
            raise ResourceNotFoundError(
                f"document source missing for [{name}]/"
                f"[{req.path_params['id']}]")
        return 200, doc["_source"]

    def h_delete_doc(self, req):
        name = req.path_params["index"]
        svc = self._single_index(name)
        kw = {}
        if req.param("if_seq_no") is not None:
            kw["if_seq_no"] = req.int_param("if_seq_no")
        if req.param("if_primary_term") is not None:
            kw["if_primary_term"] = req.int_param("if_primary_term")
        if req.param("version") is not None:
            kw["version"] = req.int_param("version")
            kw["version_type"] = req.param("version_type", "internal")
        r = svc.delete_doc(req.path_params["id"],
                           routing=req.param("routing"), **kw)
        forced = self._maybe_refresh(svc, req, doc_id=r.doc_id)
        if r.result == "not_found":
            return 404, {"_index": name, "_id": r.doc_id,
                         "result": "not_found",
                         "_shards": {"total": 1, "successful": 1,
                                     "failed": 0}}
        out = {"_index": name, "_id": r.doc_id, "_version": r.version,
               "_seq_no": r.seq_no, "_primary_term": r.primary_term,
               "result": "deleted",
               "_shards": {"total": 1, "successful": 1, "failed": 0}}
        if forced:
            out["forced_refresh"] = True
        return 200, out

    def h_update_doc(self, req):
        from opensearch_tpu.indices.service import deep_merge_doc

        name = req.path_params["index"]
        svc = self.node.indices.write_index_for(name)
        body = req.json({})
        doc_id = req.path_params["id"]
        cur = svc.get_doc(doc_id, req.param("routing"))
        created = cur is None
        kw = {}
        if req.param("if_seq_no") is not None:
            kw["if_seq_no"] = req.int_param("if_seq_no")
        if req.param("if_primary_term") is not None:
            kw["if_primary_term"] = req.int_param("if_primary_term")
        if kw and cur is None and "upsert" not in body \
                and not body.get("doc_as_upsert"):
            # CAS on a missing doc is document_missing, not a conflict
            raise DocumentMissingError(name, doc_id)
        if kw and cur is not None:
            # CAS params check against the CURRENT doc before any noop
            # short-circuit (UpdateHelper applies them to the write)
            from opensearch_tpu.common.errors import VersionConflictError
            cur_seq = cur["_seq_no"] if cur is not None else -1
            cur_term = cur.get("_primary_term", 1) if cur is not None else 0
            if kw.get("if_seq_no") is not None \
                    and kw["if_seq_no"] != cur_seq:
                raise VersionConflictError(
                    doc_id, f"seq_no [{kw['if_seq_no']}]",
                    f"seq_no [{cur_seq}]")
            if kw.get("if_primary_term") is not None \
                    and kw["if_primary_term"] != cur_term:
                raise VersionConflictError(
                    doc_id, f"primary_term [{kw['if_primary_term']}]",
                    f"primary_term [{cur_term}]")
        if cur is None:
            if "upsert" in body:
                merged = body["upsert"]
            elif body.get("doc_as_upsert") and "doc" in body:
                merged = body["doc"]
            else:
                raise DocumentMissingError(name, doc_id)
        else:
            if "doc" not in body:
                raise ValidationError("[_update] requires a [doc] or "
                                      "[upsert] section")
            if "_source" not in cur:
                raise ValidationError(
                    f"[{name}][{doc_id}]: source is missing — partial "
                    "updates require [_source] to be enabled")
            merged = deep_merge_doc(cur["_source"], body["doc"])
            # detect_noop (default true): an update that changes nothing
            # neither bumps the version nor writes (UpdateHelper.java)
            if merged == cur["_source"] and body.get("detect_noop", True):
                out = {"_index": name, "_id": doc_id,
                       "_version": cur["_version"],
                       "_seq_no": cur["_seq_no"],
                       "result": "noop",
                       "_shards": {"total": 0, "successful": 0,
                                   "failed": 0}}
                self._update_get_section(req, out, cur)
                return 200, out
        r = svc.index_doc(doc_id, merged, routing=req.param("routing"), **kw)
        forced = self._maybe_refresh(svc, req, doc_id=r.doc_id)
        out = {"_index": name, "_id": r.doc_id, "_version": r.version,
               "_seq_no": r.seq_no, "_primary_term": r.primary_term,
               "result": "created" if created else "updated",
               "_shards": {"total": 1, "successful": 1, "failed": 0}}
        if forced:
            out["forced_refresh"] = True
        self._update_get_section(
            req, out, svc.get_doc(doc_id, req.param("routing")))
        return 200, out

    @staticmethod
    def _update_get_section(req, out, doc):
        """?_source=... on _update returns the post-update doc inline
        (UpdateResponse.getGetResult)."""
        spec = req.param("_source")
        if spec is None or doc is None:
            return
        from opensearch_tpu.search.fetch import filter_source
        if spec in ("", "true", "false"):
            spec = spec != "false"
        else:
            spec = spec.split(",")
        src = filter_source(doc.get("_source"), spec)
        get = {"found": True, "_seq_no": doc["_seq_no"],
               "_primary_term": doc.get("_primary_term", 1)}
        if src is not None:
            get["_source"] = src
        out["get"] = get

    def h_mget(self, req):
        body = req.json({})
        default_index = req.path_params.get("index")
        docs_out = []
        specs = body.get("docs", []) or [
            {"_id": i} for i in body.get("ids", [])]
        if not specs:
            raise ValidationError(
                "Validation Failed: 1: no documents to get;")
        missing = [i + 1 for i, s in enumerate(specs) if "_id" not in s]
        if missing:
            raise ValidationError("Validation Failed: " + "".join(
                f"{i}: id is missing;" for i in missing))
        no_index = [i + 1 for i, s in enumerate(specs)
                    if s.get("_index", default_index) is None]
        if no_index:
            raise ValidationError("Validation Failed: " + "".join(
                f"{i}: index is missing;" for i in no_index))
        for spec in specs:
            name = spec.get("_index", default_index)
            doc_id = str(spec["_id"])        # ids are strings on the wire
            routing = spec.get("routing")
            try:
                svc = self.node.indices.get(name)
            except IllegalArgumentError as e:
                # e.g. an alias over multiple indices: a per-doc error,
                # not a request failure (TransportMultiGetAction)
                docs_out.append({"_index": name, "_id": doc_id, "error": {
                    "root_cause": [{"type": e.error_type,
                                    "reason": e.reason}],
                    "type": e.error_type, "reason": e.reason}})
                continue
            except OpenSearchTpuError:
                docs_out.append({"_index": name, "_id": doc_id,
                                 "found": False})
                continue
            try:
                doc = svc.get_doc(doc_id, None if routing is None
                                  else str(routing))
            except OpenSearchTpuError:
                doc = None
            if doc is None:
                docs_out.append({"_index": name, "_id": doc_id,
                                 "found": False})
            else:
                docs_out.append({"_index": name, **doc})
        return 200, {"docs": docs_out}

    # -- bulk --------------------------------------------------------------

    def h_bulk(self, req):
        default_index = req.path_params.get("index")
        lines = req.raw_body.split(b"\n")
        ops_by_index: dict[str, list] = {}
        order: list[tuple[str, int]] = []
        i = 0
        while i < len(lines):
            line = lines[i].strip()
            i += 1
            if not line:
                continue
            try:
                action_line = json.loads(line)
            except json.JSONDecodeError as e:
                raise ParsingError(f"malformed action/metadata line: {e}")
            if len(action_line) != 1:
                raise ParsingError("action/metadata line must contain a "
                                   "single action")
            action, meta = next(iter(action_line.items()))
            if action not in ("index", "create", "delete", "update"):
                raise ParsingError(f"unknown bulk action [{action}]")
            if action == "index" and meta.get("op_type") == "create":
                action = "create"    # renders as a create item, with
                # create's already-exists conflict semantics
            name = meta.get("_index", default_index)
            if name is None:
                raise ValidationError("bulk item requires _index")
            source = None
            if action != "delete":
                if i >= len(lines):
                    raise ParsingError("bulk request ends with an action "
                                       "line and no source")
                try:
                    source = json.loads(lines[i])
                except json.JSONDecodeError as e:
                    raise ParsingError(f"malformed bulk source line: {e}")
                i += 1
            require_alias = meta.get(
                "require_alias", req.param("require_alias") == "true")
            if require_alias and name not in self.node.indices.aliases:
                bucket = ops_by_index.setdefault("\x00err", [])
                order.append(("\x00err", len(bucket)))
                bucket.append({action: {
                    "_index": name, "_id": meta.get("_id"), "status": 404,
                    "error": {"type": "index_not_found_exception",
                              "reason": f"no such index [{name}] and "
                                        "[require_alias] request flag is "
                                        f"[true] and [{name}] is not an "
                                        "alias"}}})
                continue
            bucket = ops_by_index.setdefault(name, [])
            order.append((name, len(bucket)))
            bucket.append((action, meta.get("_id"), source,
                           {"routing": meta.get("routing",
                                                meta.get("_routing")),
                            "if_seq_no": meta.get("if_seq_no"),
                            "if_primary_term": meta.get(
                                "if_primary_term"),
                            "pipeline": meta.get("pipeline"),
                            "op_bytes": len(lines[i - 1])
                            if source is not None else None,
                            "_source": meta.get(
                                "_source", self._bulk_source_param(req))}))
        results_by_index = {}
        t0 = time.monotonic()
        for name, ops in ops_by_index.items():
            if name == "\x00err":     # pre-cooked require_alias failures
                results_by_index[name] = ops
                continue
            try:
                svc = self.node.indices.write_index_for(name)
            except OpenSearchTpuError as e:
                # unresolvable write target (e.g. alias without a write
                # index): item-level errors, never a request failure
                results_by_index[name] = [{action: {
                    "_index": name, "_id": doc_id, "status": 400,
                    "error": {"type": "illegal_argument_exception",
                              "reason": e.reason}}}
                    for action, doc_id, _s, _kw in ops]
                continue
            req_pid = self._ingest_pipeline_for(req, svc)
            cooked = []
            precooked = {}      # i -> ready response (drop/error)
            for i, (action, doc_id, source, kw) in enumerate(ops):
                # pipelines transform only index/create sources; an
                # update's {"doc": ...} wrapper passes through
                # untouched (IngestService skips updates too).  A
                # per-item [pipeline] in the action metadata overrides
                # the request-level one.
                pid = kw.get("pipeline") or req_pid
                if pid is not None and action in ("index", "create") \
                        and source is not None:
                    try:
                        source = self.node.ingest.process(pid, source)
                    except ResourceNotFoundError as e:
                        # a missing pipeline is a CLIENT error per item
                        # (TransportBulkAction: illegal_argument, 400)
                        precooked[i] = {action: {
                            "_index": name, "_id": doc_id, "status": 400,
                            "error": {"type": "illegal_argument_exception",
                                      "reason": e.reason}}}
                        continue
                    except OpenSearchTpuError as e:
                        # per-ITEM failure: bulk never aborts
                        precooked[i] = {action: {
                            "_index": name, "_id": doc_id,
                            "status": e.status,
                            "error": {"type": e.error_type,
                                      "reason": e.reason}}}
                        continue
                    if source is None:      # dropped
                        precooked[i] = {action: {
                            "_index": name, "_id": doc_id,
                            "result": "noop", "status": 200}}
                        continue
                cooked.append((action, doc_id, source, kw))
            results = svc.bulk(cooked)
            merged, ri = [], 0
            for i in range(len(ops)):
                if i in precooked:
                    merged.append(precooked[i])
                else:
                    merged.append(results[ri])
                    ri += 1
            results_by_index[name] = merged
            if req.param("refresh") in ("", "true", "wait_for"):
                svc.refresh()
        items = [results_by_index[name][j] for name, j in order]
        errors = any(next(iter(it.values())).get("error") for it in items)
        took = int((time.monotonic() - t0) * 1000)
        from opensearch_tpu.common.telemetry import metrics
        metrics().counter("bulk.items").inc(len(items))
        metrics().histogram("bulk.request_ms").observe(float(took))
        return 200, {"took": took, "errors": errors, "items": items}

    # -- search ------------------------------------------------------------

    def _target_indices(self, req) -> list:
        expr = req.path_params.get("index")
        if expr is None:
            return list(self.node.indices.indices.values())
        return self.node.indices.resolve(expr)

    def _target_indices_filtered(self, req) -> list:
        """[(svc, alias_filter|None)] for search-style requests."""
        expr = req.path_params.get("index")
        if expr is None:
            return [(s, None)
                    for s in self.node.indices.indices.values()]
        return self.node.indices.resolve_with_filters(expr)

    @staticmethod
    def _apply_alias_filter(body: dict, flt) -> dict:
        """AND an alias filter into the request query (the reference
        applies alias filters inside QueryShardContext)."""
        if flt is None:
            return body
        out = dict(body)
        q = body.get("query")
        out["query"] = {"bool": {"must": [q] if q else [],
                                 "filter": [flt]}}
        return out

    def _single_index(self, name: str):
        """Exactly-one-index resolution for doc-level APIs (GET/DELETE/
        UPDATE through an alias work when it targets one index)."""
        svcs = self.node.indices.resolve(name)
        if len(svcs) != 1:
            raise ValidationError(
                f"[{name}] resolves to {len(svcs)} indices — doc "
                "operations require exactly one")
        return svcs[0]

    def h_msearch(self, req):
        """NDJSON multi-search (RestMultiSearchAction analog): alternating
        header/body lines; header may name an index, else the URL index
        applies.  Same-index runs batch through ShardSearcher.msearch (one
        device program per query group — see search/batch.py)."""
        lines = [ln for ln in req.raw_body.split(b"\n") if ln.strip()]
        if len(lines) % 2 != 0:
            raise ValidationError(
                "_msearch body must be alternating header/body NDJSON lines")
        default_index = req.path_params.get("index")
        requests = []            # (index_name, body)
        for i in range(0, len(lines), 2):
            try:
                header = json.loads(lines[i])
                body = json.loads(lines[i + 1])
            except json.JSONDecodeError as e:
                raise ParsingError(f"invalid _msearch NDJSON: {e}") from e
            index = header.get("index") or default_index
            if index is None:
                raise ValidationError(
                    "_msearch header must name an [index] when the URL "
                    "does not")
            requests.append((index, body))
        # group per index expression so same-index bursts batch; errors
        # are PER sub-request (the _msearch contract: one bad body never
        # fails its neighbours)
        responses: list = [None] * len(requests)
        by_index: dict[str, list[int]] = {}
        for pos, (index, _b) in enumerate(requests):
            by_index.setdefault(index, []).append(pos)

        def err_of(e):
            err = {"error": {"type": e.error_type, "reason": e.reason},
                   "status": e.status}
            if e.status == 429:
                # sub-responses can't carry headers (the envelope is
                # 200), so the Retry-After hint rides in the body
                err["error"]["retry_after_seconds"] = int(
                    getattr(e, "retry_after_seconds", 1))
            return err

        for index, positions in by_index.items():
            try:
                svcs = self.node.indices.resolve(index)
                if not svcs:
                    raise IndexNotFoundError(index)
            except OpenSearchTpuError as e:
                for p in positions:
                    responses[p] = err_of(e)
                continue
            bodies = [requests[p][1] for p in positions]
            results = None
            if len(svcs) == 1:
                try:
                    results = svcs[0].msearch(bodies)
                except OpenSearchTpuError:
                    results = None       # retry body-by-body below
            if results is not None:
                for p, r in zip(positions, results):
                    r["status"] = 200
                    responses[p] = r
                continue
            for p, body in zip(positions, bodies):
                try:
                    r = (svcs[0].search(body) if len(svcs) == 1
                         else self._multi_index_search(
                             [(s, None) for s in svcs], body))
                    r["status"] = 200
                    responses[p] = r
                except OpenSearchTpuError as e:
                    responses[p] = err_of(e)
        return 200, {"took": max((r.get("took", 0) for r in responses),
                                 default=0),
                     "responses": responses}

    # -- scroll / PIT ------------------------------------------------------

    def _scroll_response(self, ctx, scroll_id):
        from opensearch_tpu.search.executor import ShardSearcher  # noqa: F401
        page = ctx.next_page()
        hits = ctx.searcher._hits_from_rows(page, ctx.source_spec)
        for h in hits:
            h["_index"] = ctx.index_name
        return {"_scroll_id": scroll_id, "took": 0, "timed_out": False,
                "_shards": {"total": 1, "successful": 1, "skipped": 0,
                            "failed": 0},
                "hits": {"total": {"value": ctx.total, "relation": "eq"},
                         "max_score": None, "hits": hits}}

    def h_scroll_next(self, req):
        from opensearch_tpu.search.contexts import (ScrollContext,
                                                    parse_keepalive)
        body = req.json({}) or {}
        scroll_id = (body.get("scroll_id") or req.param("scroll_id")
                     or req.path_params.get("scroll_id"))
        if not scroll_id:
            raise ValidationError("scroll_id is required")
        # only an EXPLICIT scroll param replaces the stored keepalive; a
        # bare fetch keeps the lease the client asked for at open
        raw_ka = body.get("scroll") or req.param("scroll")
        ka = parse_keepalive(raw_ka) if raw_ka else None
        ctx = self.node.contexts.get(scroll_id, ka)
        if not isinstance(ctx, ScrollContext):
            raise ValidationError(
                f"id [{scroll_id}] is a point-in-time, not a scroll")
        self._close_context_on_cancel(scroll_id)
        return 200, self._scroll_response(ctx, scroll_id)

    def _close_context_on_cancel(self, context_id: str) -> None:
        """Cancelling the task that owns a scroll/PIT page closes the
        live reader context at once — releasing its breaker reservation
        — instead of waiting for keep-alive reaping (the reference frees
        the reader context when the scroll task is cancelled)."""
        from opensearch_tpu.common import tasks as taskmod
        task = taskmod.current()
        if task is not None:
            task.add_cancellation_listener(
                lambda: self.node.contexts.close(context_id))

    def h_scroll_clear(self, req):
        body = req.json({}) or {}
        ids = (body.get("scroll_id")
               or req.path_params.get("scroll_id") or [])
        if isinstance(ids, str):
            ids = ids.split(",")
        freed = sum(1 for i in ids if self.node.contexts.close(i))
        if ids and freed == 0:
            return 404, {"succeeded": False, "num_freed": 0}
        return 200, {"succeeded": True, "num_freed": freed}

    def h_scroll_clear_all(self, req):
        return 200, {"succeeded": True,
                     "num_freed": self.node.contexts.close_all()}

    def h_pit_open(self, req):
        from opensearch_tpu.search.contexts import (PitContext,
                                                    parse_keepalive)
        services = self._target_indices(req)
        if len(services) != 1:
            raise ValidationError(
                "point-in-time requires exactly one target index")
        svc = services[0]
        # no explicit keep_alive -> the dynamic search.default_keep_alive
        ka = parse_keepalive(
            req.param("keep_alive"),
            default_ms=int(self.node.contexts.default_keep_alive_s
                           * 1000))
        ctx = PitContext(svc.searcher(), svc.name)
        pit_id = self.node.contexts.open(ctx, ka)
        return 200, {"pit_id": pit_id,
                     "_shards": {"total": svc.num_shards,
                                 "successful": svc.num_shards,
                                 "skipped": 0, "failed": 0}}

    def h_pit_close(self, req):
        body = req.json({}) or {}
        ids = body.get("pit_id") or []
        if isinstance(ids, str):
            ids = [ids]
        freed = sum(1 for i in ids if self.node.contexts.close(i))
        return 200, {"succeeded": True, "num_freed": freed}

    _SEARCH_BODY_KEYS = frozenset({
        "query", "size", "from", "sort", "aggs", "aggregations",
        "_source", "min_score", "search_after", "highlight", "explain",
        "docvalue_fields", "fields", "script_fields", "rescore",
        "collapse", "suggest", "profile", "track_total_hits",
        "track_scores", "scroll", "slice", "pit", "timeout",
        "terminate_after", "version", "seq_no_primary_term",
        "indices_boost", "stored_fields", "post_filter",
        "_hybrid_pipeline", "allow_partial_search_results"})

    def h_search(self, req):
        body = req.json({}) or {}
        unknown = set(body) - self._SEARCH_BODY_KEYS
        if unknown:
            # the reference 400s on unknown top-level search keys
            # (SearchSourceBuilder's strict parser)
            raise ParsingError(
                f"unknown key for a search request: "
                f"[{sorted(unknown)[0]}]")
        # URI-search support: ?q= runs through query_string with its df/
        # operator/lenient params (RestSearchAction.parseSearchSource)
        q = req.param("q")
        if q:
            qs = {"query": q}
            if req.param("df"):
                qs["default_field"] = req.param("df")
            if req.param("default_operator"):
                qs["default_operator"] = req.param("default_operator")
            if req.param("analyze_wildcard") is not None:
                qs["analyze_wildcard"] = (req.param("analyze_wildcard")
                                          == "true")
            if req.param("lenient") is not None:
                qs["lenient"] = req.param("lenient") == "true"
            body.setdefault("query", {"query_string": qs})
        if req.param("size") is not None:
            body["size"] = int(req.param("size"))
        if req.param("from") is not None:
            body["from"] = int(req.param("from"))
        if req.param("allow_partial_search_results") is not None:
            # request param wins over the dynamic cluster default
            # (search.default_allow_partial_search_results); consumed by
            # the cluster coordinator's scatter phase
            body["allow_partial_search_results"] = \
                str(req.param("allow_partial_search_results")).lower() \
                != "false"
        src_spec = self._bulk_source_param(req)
        if src_spec is not None:
            body["_source"] = src_spec     # URL params override the body
        if body.get("query") is not None:
            self._resolve_terms_lookup(body["query"])
        if req.param("track_total_hits") is not None \
                and "track_total_hits" not in body:
            raw_tth = req.param("track_total_hits")
            body["track_total_hits"] = (int(raw_tth)
                                        if raw_tth.lstrip("-").isdigit()
                                        else raw_tth != "false")
        if req.param("docvalue_fields") and "docvalue_fields" not in body:
            body["docvalue_fields"] = \
                req.param("docvalue_fields").split(",")
        tth0 = body.get("track_total_hits")
        if (isinstance(tth0, int) and not isinstance(tth0, bool)
                and tth0 <= 0 and tth0 != -1):
            raise IllegalArgumentError(
                "[track_total_hits] parameter must be positive or "
                f"equals to -1, got {tth0}")
        if (req.param("rest_total_hits_as_int") == "true"
                and isinstance(tth0, int)
                and not isinstance(tth0, bool)):
            raise IllegalArgumentError(
                "[rest_total_hits_as_int] cannot be used if the tracking "
                f"of total hits is not accurate, got {tth0}")
        resp_status, resp = self._h_search_inner(req, body)
        tth = body.get("track_total_hits")
        if isinstance(resp, dict):
            hits = resp.get("hits")
            if tth is False and isinstance(hits, dict):
                if req.param("rest_total_hits_as_int") == "true":
                    # the int rendering of an untracked total is -1
                    hits["total"] = {"value": -1, "relation": "eq"}
                else:
                    hits.pop("total", None)
            elif (isinstance(tth, int) and not isinstance(tth, bool)
                  and isinstance(hits, dict)
                  and isinstance(hits.get("total"), dict)
                  and hits["total"]["value"] > tth):
                # tracking cap: report the cap with relation gte
                hits["total"] = {"value": tth, "relation": "gte"}
        return resp_status, resp

    def _resolve_terms_lookup(self, node):
        """terms lookup ({"terms": {field: {index, id, path}}}) resolves
        to the referenced doc's values at the COORDINATOR, like
        TermsQueryBuilder's fetch phase."""
        if isinstance(node, dict):
            tq = node.get("terms")
            if isinstance(tq, dict):
                for f, spec in list(tq.items()):
                    if f in ("boost", "_name") or not isinstance(spec,
                                                                 dict):
                        continue
                    if "index" not in spec or "id" not in spec:
                        continue
                    svc = self.node.indices.get(spec["index"])
                    doc = svc.get_doc(str(spec["id"]),
                                      spec.get("routing"))
                    vals = []
                    if doc is not None:
                        src = doc.get("_source") or {}
                        for part in str(spec.get("path", "")).split("."):
                            src = (src.get(part)
                                   if isinstance(src, dict) else None)
                            if src is None:
                                break
                        if src is not None:
                            vals = src if isinstance(src, list) else [src]
                    tq[f] = vals
            for v in node.values():
                self._resolve_terms_lookup(v)
        elif isinstance(node, list):
            for v in node:
                self._resolve_terms_lookup(v)

    def _h_search_inner(self, req, body):
        # search pipeline: resolve the normalization-processor config the
        # hybrid combination should use (neural-search's hook)
        pid = req.param("search_pipeline")
        if pid:
            conf = self.node.search_pipelines.hybrid_conf(pid)
            if conf is not None:
                body["_hybrid_pipeline"] = conf
        # request-cache directive: strict boolean (a typo like
        # request_cache=tru must 400, not silently disable caching —
        # RestRequest.paramAsBoolean semantics)
        rc = req.param("request_cache")
        if rc is not None:
            if str(rc).lower() not in ("true", "false"):
                raise IllegalArgumentError(
                    f"Failed to parse value [{rc}] of parameter "
                    "[request_cache] as only [true] or [false] are "
                    "allowed.")
            body["request_cache"] = str(rc).lower() == "true"
        if "request_cache" in body and \
                not isinstance(body["request_cache"], bool):
            raise IllegalArgumentError(
                "[request_cache] must be a boolean")
        # PIT search: the body names a held reader; no index in the path
        if body.get("pit"):
            return 200, self._pit_search(body)
        expr = req.path_params.get("index")
        scroll = req.param("scroll") or body.get("scroll")
        if expr and ":" in expr:
            if scroll:
                raise ValidationError(
                    "scroll is not supported with cross-cluster index "
                    "expressions")
            return 200, self._ccs_search(expr, body)
        if scroll:
            if body.get("size") == 0:
                raise IllegalArgumentError(
                    "[size] cannot be [0] in a scroll context")
            if body.get("request_cache"):
                raise IllegalArgumentError(
                    "[request_cache] cannot be used in a scroll context")
            body.pop("request_cache", None)
            if int(body.get("from", 0) or 0) > 0:
                raise IllegalArgumentError(
                    "`from` parameter must be set to 0 when `scroll` is "
                    "used")
            batch = int(body.get("size", 10)
                        if body.get("size") is not None else 10)
            if batch > 10000:
                raise IllegalArgumentError(
                    f"Batch size is too large, size must be less than or "
                    f"equal to: [10000] but was [{batch}]. Scroll batch "
                    "sizes cost as much memory as result windows so they "
                    "are controlled by the [index.max_result_window] "
                    "index level setting.")
            return 200, self._open_scroll(req, body, scroll)
        from_ = int(body.get("from", 0) or 0)
        size_ = int(body.get("size", 10)
                    if body.get("size") is not None else 10)
        if from_ < 0:
            raise IllegalArgumentError(f"[from] parameter cannot be "
                                       f"negative, found [{from_}]")
        if size_ < 0:
            raise IllegalArgumentError(f"[size] parameter cannot be "
                                       f"negative, found [{size_}]")
        # per-index window/field-count limits apply in IndexService.search
        # (index.max_result_window et al are index-level settings)
        targets = self._target_indices_filtered(req)
        if not targets:
            # allow_no_indices=true default: empty result, not an error
            return 200, {"took": 0, "timed_out": False,
                         "_shards": {"total": 0, "successful": 0,
                                     "skipped": 0, "failed": 0},
                         "hits": {"total": {"value": 0, "relation": "eq"},
                                  "max_score": None, "hits": []}}
        if len(targets) == 1:
            svc, flt = targets[0]
            return 200, svc.search(self._apply_alias_filter(body, flt))
        return 200, self._multi_index_search(targets, body)

    def _ccs_search(self, expr: str, body: dict) -> dict:
        """Cross-cluster search: 'alias:expr' parts fan out to configured
        remotes over HTTP, local parts run here, hits merge like the
        multi-index coordinator (TransportSearchAction's CCS split;
        scoring is per-cluster).  Aggregations/suggest don't reduce
        across clusters yet — rejected loudly."""
        from opensearch_tpu.transport.remote import RemoteClusterService

        if (body.get("aggs") or body.get("aggregations")
                or body.get("suggest")):
            raise ValidationError(
                "cross-cluster [aggs]/[suggest] reduce is not supported "
                "— target a single cluster")
        local_exprs, remote_map = RemoteClusterService.split_indices(expr)
        size = int(body.get("size", 10))
        from_ = int(body.get("from", 0))
        sub = dict(body)
        sub["from"] = 0
        sub["size"] = from_ + size
        responses = []
        # remotes fan out CONCURRENTLY (each seed attempt can block on
        # its timeout; latency must be the slowest cluster, not the sum)
        remote_items = sorted(remote_map.items())
        remote_resps = []
        if remote_items:
            pool = self.node.thread_pool.executor("search")
            futures = [(alias, rexpr, pool.submit(
                self.node.remotes.search, alias, rexpr, sub))
                for alias, rexpr in remote_items]
            for alias, rexpr, fut in futures:
                r = fut.result()
                for h in r["hits"]["hits"]:
                    h["_index"] = f"{alias}:{h.get('_index', rexpr)}"
                remote_resps.append(r)
        if local_exprs:
            targets = self.node.indices.resolve_with_filters(
                ",".join(local_exprs))
            responses.extend(
                svc.search(self._apply_alias_filter(sub, flt))
                for svc, flt in targets)
        responses.extend(remote_resps)
        n_clusters = len(remote_map) + (1 if local_exprs else 0)
        out = self._merge_responses(responses, body, from_, size)
        out["_clusters"] = {"total": n_clusters,
                            "successful": n_clusters, "skipped": 0}
        return out

    def _merge_responses(self, responses, body, from_, size) -> dict:
        """Shared coordinator merge (SearchPhaseController.merge analog)
        used by the multi-index and cross-cluster paths."""
        rows = []
        for resp_idx, resp in enumerate(responses):
            for pos, h in enumerate(resp["hits"]["hits"]):
                rows.append((h, resp_idx, pos))
        from opensearch_tpu.common.telemetry import tracer
        from opensearch_tpu.search.executor import merge_hit_rows

        profiling = bool(body.get("profile"))
        t_reduce = time.monotonic() if profiling else 0.0
        with tracer().start_span("coordinator.reduce",
                                 {"sources": len(responses),
                                  "rows": len(rows)}):
            all_hits = merge_hit_rows(rows, body.get("sort"))
        total = sum(r["hits"]["total"]["value"] for r in responses)
        scores = [r["hits"]["max_score"] for r in responses
                  if r["hits"]["max_score"] is not None]
        shards = sum(r.get("_shards", {}).get("total", 1)
                     for r in responses)
        out = {
            "took": max((r["took"] for r in responses), default=0),
            # partial-results flag survives the coordinator reduce: one
            # shard running out of budget marks the whole response
            "timed_out": any(r.get("timed_out") for r in responses),
            "_shards": {"total": shards, "successful": shards,
                        "skipped": 0, "failed": 0},
            "hits": {"total": {"value": total, "relation": "eq"},
                     "max_score": max(scores) if scores else None,
                     "hits": all_hits[from_: from_ + size]},
        }
        if profiling:
            # profile merge: per-source shard sections concatenate (each
            # already carries its engine attribution), the coordinator
            # block adds the merge cost only this layer can measure
            sections = []
            for r in responses:
                sections.extend((r.get("profile") or {})
                                .get("shards") or [])
            out["profile"] = {
                "shards": sections,
                "coordinator": {
                    "sources": len(responses),
                    "reduce_time_in_nanos": int(
                        (time.monotonic() - t_reduce) * 1e9)}}
        return out

    def _open_scroll(self, req, body, scroll):
        """First scroll page: pin a searcher snapshot, materialize the
        full sorted match list, serve page one (reader-context creation;
        SearchService.createContext + scroll keepalive analog)."""
        from opensearch_tpu.search.contexts import (ScrollContext,
                                                    parse_keepalive)
        services = self._target_indices(req)
        if len(services) != 1:
            raise ValidationError(
                "scroll requires exactly one target index")
        svc = services[0]
        flt = dict(self.node.indices.resolve_with_filters(
            req.path_params["index"])).get(svc) \
            if req.path_params.get("index") else None
        body = self._apply_alias_filter(body, flt)
        # keep-alive parses BEFORE any breaker reservation: a malformed
        # value must not leak the context's request-breaker charge
        keepalive_ms = parse_keepalive(scroll)
        searcher = svc.searcher()
        rows, total = searcher.scan_rows(
            {k: v for k, v in body.items() if k != "slice"},
            slice_spec=body.get("slice"))
        ctx = ScrollContext(searcher, rows, total,
                            page_size=int(body.get("size", 10)),
                            source_spec=body.get("_source"),
                            index_name=svc.name)
        try:
            scroll_id = self.node.contexts.open(ctx, keepalive_ms)
        except OpenSearchTpuError:
            ctx.release()
            raise
        self._close_context_on_cancel(scroll_id)
        return self._scroll_response(ctx, scroll_id)

    def _pit_search(self, body):
        from opensearch_tpu.search.contexts import (PitContext,
                                                    parse_keepalive)
        pit = body["pit"]
        pit_id = pit.get("id")
        if not pit_id:
            raise ValidationError("[pit] requires an [id]")
        ka = (parse_keepalive(pit["keep_alive"])
              if pit.get("keep_alive") else None)
        ctx = self.node.contexts.get(pit_id, ka)
        if not isinstance(ctx, PitContext):
            raise ValidationError(
                f"id [{pit_id}] is a scroll, not a point-in-time")
        self._close_context_on_cancel(pit_id)
        sub = {k: v for k, v in body.items() if k != "pit"}
        resp = ctx.searcher.search(sub)
        resp["pit_id"] = pit_id
        return resp

    def _multi_index_search(self, services, body):
        """Coordinator merge over several indices (scores are per-index,
        like cross-index query_then_fetch in the reference)."""
        size = int(body.get("size", 10))
        from_ = int(body.get("from", 0))
        aggs_json = body.get("aggs") or body.get("aggregations")
        sub = dict(body)
        sub["from"] = 0
        sub["size"] = from_ + size
        responses = [svc.search(self._apply_alias_filter(sub, flt),
                                agg_partials=bool(aggs_json))
                     for svc, flt in services]
        out = self._merge_responses(responses, body, from_, size)
        if aggs_json:
            from opensearch_tpu.search.aggs import reduce_aggs
            out["aggregations"] = reduce_aggs(
                aggs_json, [r.get("aggregation_partials") or {}
                            for r in responses])
        if body.get("suggest"):
            from opensearch_tpu.search.suggest import merge_suggest
            out["suggest"] = merge_suggest(
                [r.get("suggest") for r in responses])
        return out

    # -- cluster settings / aliases / templates / analyze ------------------

    def h_cluster_get_settings(self, req):
        buckets = getattr(self.node, "settings_buckets", None) or {
            "persistent": self.node.cluster_settings.settings.as_dict(),
            "transient": {}}
        out = {"persistent": _nest_settings(buckets["persistent"]),
               "transient": _nest_settings(buckets["transient"])}
        if req.flag("include_defaults"):
            out["defaults"] = {
                k: s.default(self.node.cluster_settings.settings)
                for k, s in
                self.node.cluster_settings._registered.items()}
        return 200, out

    def h_cluster_put_settings(self, req):
        body = req.json({}) or {}
        from opensearch_tpu.common.settings import Settings

        def flat(d):
            # flatten nested keys; preserve explicit nulls (= reset)
            out = Settings(d or {}).as_dict()
            for k, v in _flatten_nulls(d or {}):
                out[k] = v
            return out

        persistent = flat(body.get("persistent"))
        transient = flat(body.get("transient"))
        if not persistent and not transient:
            raise ValidationError(
                "no settings to update: provide [persistent] or "
                "[transient]")
        out = self.node.update_cluster_settings(
            persistent=persistent, transient=transient)
        out["persistent"] = _nest_settings(out["persistent"])
        out["transient"] = _nest_settings(out["transient"])
        return 200, out

    def h_put_index_settings(self, req):
        """Dynamic per-index settings update (RestUpdateSettingsAction);
        static settings like number_of_shards are rejected."""
        body = req.json({}) or {}
        updates = body.get("settings", body) or {}
        from opensearch_tpu.common.settings import Settings
        flat = Settings(updates).as_dict()
        for svc in self.node.indices.resolve(req.path_params["index"]):
            svc.update_settings(flat)
        return 200, {"acknowledged": True}

    def h_rollover(self, req):
        body = req.json({}) or {}
        if req.path_params.get("target"):
            body["new_index"] = req.path_params["target"]
        return 200, self.node.indices.rollover(
            req.path_params["index"], body,
            dry_run=req.flag("dry_run"))

    def _h_resize(self, req, mode):
        return 200, self.node.indices.resize(
            req.path_params["index"], req.path_params["target"], mode,
            req.json({}) or {})

    def h_resize_shrink(self, req):
        return self._h_resize(req, "shrink")

    def h_resize_split(self, req):
        return self._h_resize(req, "split")

    def h_resize_clone(self, req):
        return self._h_resize(req, "clone")

    def h_recovery(self, req):
        """Per-shard recovery report (indices/recovery/RecoveryState):
        the array engine recovers locally from commit + translog, so
        every started shard reports a DONE store recovery."""
        out = {}
        targets = (self.node.indices.resolve(req.path_params["index"])
                   if req.path_params.get("index")
                   else self.node.indices.indices.values())
        for svc in targets:
            shards = []
            for engine in svc.shards:
                shards.append({
                    "id": engine.shard_id,
                    "type": "STORE",
                    "stage": "DONE",
                    "primary": True,
                    "source": {},
                    "target": {"id": self.node.node_id,
                               "name": self.node.name},
                    "index": {"size": {}, "files": {}},
                    "translog": {"recovered": 0, "total": 0,
                                 "percent": "100.0%"},
                })
            out[svc.name] = {"shards": shards}
        return 200, out

    def h_create_data_stream(self, req):
        return 200, self.node.indices.create_data_stream(
            req.path_params["name"])

    def h_get_data_stream(self, req):
        return 200, self.node.indices.get_data_streams(
            req.path_params.get("name"))

    def h_delete_data_stream(self, req):
        return 200, self.node.indices.delete_data_stream(
            req.path_params["name"])

    def h_reroute(self, req):
        """Single-node reroute: validates command names; allocation
        decisions are a no-op with one node (the decider chain lives in
        cluster/state.allocate_shards for the multi-node path)."""
        body = req.json({}) or {}
        known = {"move", "cancel", "allocate_replica",
                 "allocate_stale_primary", "allocate_empty_primary"}
        for cmd in body.get("commands") or []:
            ((name, _args),) = cmd.items()
            if name not in known:
                raise IllegalArgumentError(
                    f"unknown reroute command [{name}]")
        return 200, {"acknowledged": True,
                     "state": {"cluster_name": self.node.cluster_name}}

    def h_update_aliases(self, req):
        body = req.json({}) or {}
        return 200, self.node.indices.update_aliases(
            body.get("actions") or [])

    def h_get_alias(self, req):
        return 200, self.node.indices.get_aliases(
            index=req.path_params.get("index"),
            name=req.path_params.get("name"))

    def h_alias_exists(self, req):
        try:
            self.node.indices.get_aliases(name=req.path_params["name"])
            return 200, {}
        except ResourceNotFoundError:
            return 404, {}

    def h_put_alias(self, req):
        body = req.json({}) or {}
        action = {"index": req.path_params["index"],
                  "alias": req.path_params["name"]}
        for k in ("filter", "is_write_index", "routing"):
            if body.get(k) is not None:
                action[k] = body[k]
        return 200, self.node.indices.update_aliases([{"add": action}])

    def h_delete_alias(self, req):
        self.node.indices.get_aliases(name=req.path_params["name"])
        return 200, self.node.indices.update_aliases([{"remove": {
            "index": req.path_params["index"],
            "alias": req.path_params["name"]}}])

    def h_put_template(self, req):
        return 200, self.node.indices.put_template(
            req.path_params["name"], req.json({}) or {})

    def h_get_template(self, req):
        return 200, self.node.indices.get_template(
            req.path_params.get("name"))

    def h_delete_template(self, req):
        return 200, self.node.indices.delete_template(
            req.path_params["name"])

    def h_analyze(self, req):
        body = req.json({}) or {}
        text = body.get("text")
        if text is None:
            raise ValidationError("[_analyze] requires [text]")
        texts = text if isinstance(text, list) else [text]
        analyzer_name = body.get("analyzer")
        index = req.path_params.get("index")
        mapper = None
        if index is not None:
            mapper = self.node.indices.get(index).mapper
        if analyzer_name is None and body.get("field") and mapper:
            ft = mapper.field_type(body["field"])
            analyzer_name = getattr(ft, "analyzer_name", "standard")
        analyzers = (mapper.analyzers if mapper is not None
                     else self._default_analyzers())
        analyzer = analyzers.get(analyzer_name or "standard")
        tokens = []
        offset = 0
        pos_base = 0
        for t in texts:
            for tok in analyzer.analyze(str(t)):
                tokens.append({
                    "token": tok.term,
                    "start_offset": offset + tok.start_offset,
                    "end_offset": offset + tok.end_offset,
                    "type": "<ALPHANUM>",
                    "position": pos_base + tok.position})
            offset += len(str(t)) + 1
            pos_base += 100      # position_increment_gap analog
        return 200, {"tokens": tokens}

    @staticmethod
    def _default_analyzers():
        from opensearch_tpu.analysis.registry import AnalysisRegistry
        return AnalysisRegistry()

    def h_cat_nodes(self, req):
        """One row per known node; ``search.rank``/``search.duress``
        expose which copies this coordinator currently prefers (lowest
        rank wins — the _cat operator view of adaptive_selection)."""
        ars = self.node.response_collector.stats()

        def row(name, stats, master="-"):
            rank = (stats or {}).get("rank")
            return {"name": name, "node.role": "dimr", "master": master,
                    "ip": "127.0.0.1",
                    "search.rank": "-" if rank is None else f"{rank:.3f}",
                    "search.duress":
                        str(bool((stats or {}).get("in_duress"))).lower()}
        rows = [row(self.node.name, ars.get(self.node.name), master="*")]
        rows.extend(row(n, s) for n, s in sorted(ars.items())
                    if n != self.node.name)
        return 200, rows

    def h_cat_aliases(self, req):
        rows = []
        for alias, targets in sorted(self.node.indices.aliases.items()):
            for ix, meta in sorted(targets.items()):
                rows.append({"alias": alias, "index": ix,
                             "filter": "*" if meta.get("filter") else "-",
                             "is_write_index":
                                 str(bool(meta.get("is_write_index")))
                                 .lower()})
        return 200, rows

    def h_cat_templates(self, req):
        return 200, [{"name": n,
                      "index_patterns": str(t.get("index_patterns")),
                      "order": str(t.get("priority", 0))}
                     for n, t in sorted(self.node.indices.templates.items())]

    def h_cat_segments(self, req):
        """Per-segment rows with HOST and DEVICE footprints: ``size``
        is the host-side array footprint (device_ledger.host_footprint,
        the one source of truth) and ``size.device`` the bytes the
        residency ledger currently holds staged for the segment (0 when
        it is host-only or was budget-evicted)."""
        from opensearch_tpu.common.device_ledger import (device_ledger,
                                                         host_footprint)
        led = device_ledger()
        rows = []
        for name, svc in sorted(self.node.indices.indices.items()):
            for shard_id, engine in sorted(svc.local_shards.items()):
                for seg in engine.segments:
                    rows.append({"index": name, "shard": str(shard_id),
                                 "segment": seg.seg_id,
                                 "docs.count": str(seg.live_count()),
                                 "docs.deleted": str(
                                     seg.n_docs - seg.live_count()),
                                 "size": str(host_footprint(seg)),
                                 "size.device": str(
                                     led.device_footprint(seg))})
        return 200, rows

    def h_cat_recovery(self, req):
        """Per-shard recovery state + the recovery.* metric family
        (corrupt-blob re-requests, retry accounting) — the _cat face of
        the ``recovery`` section in _nodes/stats."""
        from opensearch_tpu.common.telemetry import metrics
        m = metrics()
        corrupt_blobs = str(m.counter("recovery.corrupt_blobs").value)
        retries = str(
            m.counter("retry.recovery.start.retries").value
            + m.counter("retry.recovery.report.retries").value)
        rows = []
        targets = (self.node.indices.resolve(req.path_params["index"])
                   if req.path_params.get("index")
                   else self.node.indices.indices.values())
        for svc in sorted(targets, key=lambda s: s.name):
            corrupted = svc.corrupted_shards()
            for shard_id, _engine in sorted(svc.local_shards.items()):
                stage = "corrupted" if shard_id in corrupted else "done"
                rows.append({"index": svc.name, "shard": str(shard_id),
                             "type": "store", "stage": stage,
                             "source_node": "-",
                             "target_node": self.node.name,
                             "files_percent": "100.0%",
                             "bytes_percent": "100.0%",
                             "corrupt_blobs": corrupt_blobs,
                             "retries": retries})
        return 200, rows

    def h_cat_repositories(self, req):
        return 200, [{"id": name, "type": meta["type"]}
                     for name, meta in sorted(
                         self.node.snapshots.get_repository().items())]

    def h_cat_snapshots(self, req):
        repo = req.path_params["repo"]
        out = self.node.snapshots.get_snapshot(repo, "_all")
        return 200, [{"id": s["snapshot"], "status": s.get("state", ""),
                      "indices": str(len(s.get("indices", [])))}
                     for s in out.get("snapshots", [])]

    def h_cat_tasks(self, req):
        return 200, [{"action": t.action,
                      "task_id": f"{self.node.node_id}:{t.id}",
                      "type": "transport",
                      "x_opaque_id": t.headers.get("X-Opaque-Id", "-")}
                     for t in sorted(self.node.task_manager.list(),
                                     key=lambda t: t.id)]

    def h_cat_thread_pool(self, req):
        rows = []
        for name, stats in sorted(self.node.thread_pool.stats().items()):
            rows.append({"node_name": self.node.name, "name": name,
                         "active": str(stats.get("active", 0)),
                         "queue": str(stats.get("queue", 0)),
                         "rejected": str(stats.get("rejected", 0))})
        return 200, rows

    def h_cat_pending_tasks(self, req):
        return 200, []               # single node: no pending state tasks

    def h_cat_plugins(self, req):
        # built-in module set (the reference lists installed plugins)
        return 200, [{"name": self.node.name, "component": c,
                      "version": VERSION}
                     for c in ("analysis-common", "ingest-common",
                               "parent-join", "percolator", "rank-eval",
                               "reindex", "search-pipeline-common")]

    def h_cat_cluster_manager(self, req):
        return 200, [{"id": self.node.node_id, "host": self.node.host,
                      "ip": self.node.host, "node": self.node.name}]

    def h_cat_nodeattrs(self, req):
        return 200, [{"node": self.node.name, "host": self.node.host,
                      "attr": "accelerator", "value": "tpu"}]

    def h_cat_allocation(self, req):
        shards = sum(s.num_shards
                     for s in self.node.indices.indices.values())
        return 200, [{"shards": str(shards), "node": self.node.name,
                      "host": self.node.host, "ip": self.node.host}]

    def h_cat_fielddata(self, req):
        """Per-field doc-value footprint from the ONE footprint source
        of truth (device_ledger.host_footprint) instead of ad-hoc
        ``nbytes`` math picking an arbitrary subset of the arrays."""
        from opensearch_tpu.common.device_ledger import host_footprint
        rows = []
        for name, svc in sorted(self.node.indices.indices.items()):
            for engine in svc.shards:
                for seg in engine.segments:
                    per = host_footprint(seg, per_field=True)
                    for (kind, field), nbytes in sorted(per.items()):
                        if kind != "ordinal":
                            continue
                        rows.append({
                            "node": self.node.name, "field": field,
                            "size": str(nbytes)})
        return 200, rows

    # -- task management ---------------------------------------------------

    def _task_payload(self, tasks):
        return {"nodes": {self.node.node_id: {
            "name": self.node.name,
            "tasks": {f"{self.node.node_id}:{t.id}": t.info()
                      for t in tasks}}}}

    def h_security_list_users(self, req):
        return 200, self.node.identity.list_users()

    def h_security_put_user(self, req):
        body = req.json({}) or {}
        # path_params directly: req.param() would let a ?username= query
        # parameter retarget the operation at a different account
        name = req.path_params["username"]
        created = self.node.identity.put_user(
            name, body.get("password") or "",
            body.get("roles"))   # None preserves roles (rotation)
        return 200, {"user": name, "created": created}

    def h_security_delete_user(self, req):
        name = req.path_params["username"]
        if not self.node.identity.delete_user(name):
            from opensearch_tpu.common.errors import \
                ResourceNotFoundError
            raise ResourceNotFoundError(f"user [{name}] not found")
        return 200, {"user": name, "deleted": True}

    def h_tasks_list(self, req):
        return 200, self._task_payload(
            self.node.task_manager.list(req.param("actions")))

    @staticmethod
    def _parse_task_id(raw: str) -> int:
        # accepts bare ids and the node_id:task_id composite form
        try:
            return int(raw.rsplit(":", 1)[-1])
        except ValueError:
            raise ValidationError(f"invalid task id [{raw}]") from None

    def h_task_get(self, req):
        raw = req.path_params["task_id"]
        # persistent tasks (reindex?wait_for_completion=false) answer
        # here too, like the reference's GET _tasks/<id> for reindex
        pt = self.node.persistent_tasks.get_or_none(raw)
        if pt is not None:
            done = pt["state"] in ("completed", "failed")
            return 200, {"completed": done,
                         "task": {"id": raw, "action": pt["action"],
                                  "state": pt["state"]},
                         **({"response": pt.get("result")}
                            if pt.get("result") else {}),
                         **({"error": pt["error"]}
                            if pt.get("error") else {})}
        tid = self._parse_task_id(raw)
        t = self.node.task_manager.get(tid)
        if t is None:
            raise ResourceNotFoundError(f"task [{tid}] isn't running")
        return 200, {"completed": False, "task": t.info()}

    def h_persistent_tasks_list(self, req):
        return 200, {"tasks": self.node.persistent_tasks.list()}

    def h_task_cancel(self, req):
        tid = self._parse_task_id(req.path_params["task_id"])
        cancelled = self.node.task_manager.cancel(task_id=tid)
        if not cancelled:
            raise ResourceNotFoundError(f"task [{tid}] isn't running")
        return 200, self._task_payload(cancelled)

    def h_tasks_cancel_all(self, req):
        return 200, self._task_payload(self.node.task_manager.cancel(
            actions=req.param("actions") or "*"))

    # -- search pipelines --------------------------------------------------

    def h_get_pipelines(self, req):
        return 200, self.node.search_pipelines.get()

    def h_get_pipeline(self, req):
        return 200, self.node.search_pipelines.get(req.path_params["id"])

    def h_put_pipeline(self, req):
        return 200, self.node.search_pipelines.put(
            req.path_params["id"], req.json({}) or {})

    def h_delete_pipeline(self, req):
        return 200, self.node.search_pipelines.delete(
            req.path_params["id"])

    def h_remotestore_restore(self, req):
        """Restore lost indices from their remote store mirrors (the
        remotestore restore action).  The index must not be open locally
        — remote store is the survivor copy after total local loss."""
        import json as _json

        from opensearch_tpu.common.blobstore import NoSuchBlobError
        from opensearch_tpu.index import remote_store as rs

        body = req.json({}) or {}
        names = body.get("indices")
        if not names:
            raise ValidationError(
                "[_remotestore/_restore] requires [indices]")
        if isinstance(names, str):
            names = [n.strip() for n in names.split(",") if n.strip()]
        restored = []
        for name in names:
            if self.node.indices.exists(name):
                raise ValidationError(
                    f"cannot restore [{name}]: an open index with that "
                    "name exists — delete it first")
            # find which repository mirrors it
            found = None
            for repo_name in self.node.snapshots.get_repository():
                repo = self.node.snapshots._repo(repo_name)
                try:
                    meta = _json.loads(repo.store.container(
                        f"remote/{name}").read_blob("_meta.json"))
                except NoSuchBlobError:
                    continue
                found = (repo, meta)
                break
            if found is None:
                raise ResourceNotFoundError(
                    f"no remote store data for index [{name}]")
            repo, meta = found
            settings = dict(meta.get("settings") or {})
            n_shards = int(settings.get("number_of_shards", 1))
            # every shard manifest must exist BEFORE any file lands:
            # a partial restore would leave resurrectable orphan dirs
            missing = [sid for sid in range(n_shards)
                       if rs.read_manifest(repo, name, sid) is None]
            if missing:
                raise ResourceNotFoundError(
                    f"remote store for [{name}] is incomplete — "
                    f"missing shard manifests {missing}")
            index_path = os.path.join(self.node.indices.data_path, name)
            try:
                for shard_id in range(n_shards):
                    rs.restore_shard(
                        repo, name, shard_id,
                        os.path.join(index_path, str(shard_id)))
                self.node.indices.open_restored(name, settings,
                                                meta.get("mappings"))
            except Exception:
                import shutil as _shutil
                _shutil.rmtree(index_path, ignore_errors=True)
                raise
            restored.append(name)
        return 200, {"remote_store": {"indices": restored},
                     "acknowledged": True}

    # -- snapshots ---------------------------------------------------------

    def h_get_repos(self, req):
        return 200, self.node.snapshots.get_repository()

    def h_put_repo(self, req):
        return 200, self.node.snapshots.put_repository(
            req.path_params["repo"], req.json({}) or {})

    def h_get_repo(self, req):
        return 200, self.node.snapshots.get_repository(
            req.path_params["repo"])

    def h_delete_repo(self, req):
        return 200, self.node.snapshots.delete_repository(
            req.path_params["repo"])

    def h_create_snapshot(self, req):
        return 200, self.node.snapshots.create_snapshot(
            req.path_params["repo"], req.path_params["snapshot"],
            req.json({}) or {})

    def h_get_snapshot(self, req):
        return 200, self.node.snapshots.get_snapshot(
            req.path_params["repo"], req.path_params["snapshot"])

    def h_delete_snapshot(self, req):
        return 200, self.node.snapshots.delete_snapshot(
            req.path_params["repo"], req.path_params["snapshot"])

    def h_restore_snapshot(self, req):
        return 200, self.node.snapshots.restore_snapshot(
            req.path_params["repo"], req.path_params["snapshot"],
            req.json({}) or {})

    def h_count(self, req):
        body = req.json({}) or {}
        unknown = set(body) - {"query"}
        if unknown:
            raise ParsingError(
                f"request does not support {sorted(unknown)}")
        q = req.param("q")
        if q and "query" not in body:
            qs = {"query": q}
            if req.param("df"):
                qs["default_field"] = req.param("df")
            if req.param("analyze_wildcard") is not None:
                qs["analyze_wildcard"] = (req.param("analyze_wildcard")
                                          == "true")
            if req.param("lenient") is not None:
                qs["lenient"] = req.param("lenient") == "true"
            if req.param("default_operator"):
                qs["default_operator"] = req.param("default_operator")
            body["query"] = {"query_string": qs}
        services = self._target_indices_filtered(req)
        total = sum(
            svc.count(self._apply_alias_filter(
                {"query": body.get("query")}, flt)["query"])
            for svc, flt in services)
        n_shards = sum(svc.num_shards for svc, _f in services)
        return 200, {"count": total,
                     "_shards": {"total": n_shards,
                                 "successful": n_shards, "skipped": 0,
                                 "failed": 0}}
