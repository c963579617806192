"""Node: service wiring + lifecycle + CLI entry point.

Analog of ``node/Node.java`` (ctor wiring at :400, start at :1249) and
``bootstrap/OpenSearch.main`` — at single-node scope: settings, indices
service, REST controller, HTTP transport.

Run: ``python -m opensearch_tpu.node --port 9200 --data-path ./data``
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import uuid

from opensearch_tpu.indices.service import IndicesService
from opensearch_tpu.rest.controller import RestController
from opensearch_tpu.rest.http_server import HttpServer


class Node:
    def __init__(self, data_path: str, name: str = "node-1",
                 cluster_name: str = "opensearch-tpu",
                 host: str = "127.0.0.1", port: int = 9200,
                 path_repo: "list[str] | None" = None):
        self.name = name
        self.host = host
        self.cluster_name = cluster_name
        self.node_id = uuid.uuid4().hex[:22]
        self.cluster_uuid = uuid.uuid4().hex[:22]
        self.data_path = data_path
        os.makedirs(data_path, exist_ok=True)
        self.indices = IndicesService(os.path.join(data_path, "indices"))
        from opensearch_tpu.snapshots.service import SnapshotsService
        from opensearch_tpu.search.contexts import ReaderContextRegistry
        from opensearch_tpu.search.pipeline import SearchPipelineService
        from opensearch_tpu.common.tasks import TaskManager
        from opensearch_tpu.common.fshealth import FsHealthService
        from opensearch_tpu.common.threadpool import ThreadPool
        self.thread_pool = ThreadPool()
        from opensearch_tpu.ingest.service import IngestService
        self.fs_health = FsHealthService(data_path)
        self.fs_health.check()
        self.ingest = IngestService(data_path)
        self.snapshots = SnapshotsService(self.indices, data_path,
                                          path_repo=path_repo)
        # remote-store mirroring resolves repositories late-bound
        self.indices.set_repo_resolver(self.snapshots._repo,
                                       self.snapshots.repo_mutex)
        self.contexts = ReaderContextRegistry()
        self.search_pipelines = SearchPipelineService(data_path)
        self.task_manager = TaskManager(name)
        from opensearch_tpu.search.backpressure import \
            SearchBackpressureService
        self.search_backpressure = SearchBackpressureService(
            self.task_manager, self.thread_pool)
        from opensearch_tpu.security.identity import IdentityService
        self.identity = IdentityService(data_path)
        # adaptive-selection stats surface (_nodes/stats, _cat/nodes);
        # populated by the cluster coordinator's scatter path — a
        # single-node deployment exposes an empty (but present) block
        from opensearch_tpu.cluster.response_collector import \
            ResponseCollectorService
        self.response_collector = ResponseCollectorService()
        # always-on top-N query attribution + per-plan-signature
        # workload stats (GET /_insights/top_queries, _nodes/stats
        # query_insights, /_metrics labeled series)
        from opensearch_tpu.search.insights import QueryInsightsService
        self.insights = QueryInsightsService(node_id=self.node_id)
        # per-tenant QoS + adaptive overload control (search/qos.py):
        # the AIMD controller connecting the admission ledger / flight
        # recorder / insights measurements to the shed-occupancy,
        # batcher-window, and tenant-share knobs
        from opensearch_tpu.search.qos import QosController
        self.qos = QosController(
            admission=self.search_backpressure.admission,
            insights=self.insights,
            backpressure=self.search_backpressure)
        self._init_cluster_settings()
        from opensearch_tpu.common.persistent_tasks import \
            PersistentTasksService
        self.persistent_tasks = PersistentTasksService(data_path)
        self.rest = RestController(self)
        self.persistent_tasks.register_executor(
            "indices:data/write/reindex", self.rest._do_reindex)
        self.http = HttpServer(self.rest, host=host, port=port)

    # actuator-ok (knob writes replay operator-set settings at boot)
    def _init_cluster_settings(self):
        """Dynamic cluster-settings registry + persistence
        (ClusterSettings / the _cluster/settings update API; consumers
        wire live like SearchService.java:360)."""
        import json as _json

        from opensearch_tpu.common.settings import (Setting, Settings,
                                                    SettingsRegistry)
        from opensearch_tpu.search import aggs as aggs_mod

        self._settings_file = os.path.join(self.data_path,
                                           "cluster_settings.json")
        stored = {}
        if os.path.exists(self._settings_file):
            with open(self._settings_file) as f:
                stored = _json.load(f)
        # transient settings live in memory only; persistent survive boot
        self.settings_buckets = {"persistent": dict(stored),
                                 "transient": {}}
        max_buckets = Setting.int_setting(
            "search.max_buckets", 65536, min_value=1, dynamic=True)
        auto_create = Setting.bool_setting(
            "action.auto_create_index", True, dynamic=True)
        max_scroll = Setting.int_setting(
            "search.max_open_scroll_context", 500, min_value=0,
            dynamic=True)
        cache_size = Setting.int_setting(
            "node.searchable_snapshot.cache.size", 256 << 20,
            min_value=0, dynamic=True)
        identity_enabled = Setting.bool_setting(
            "identity.enabled", False, dynamic=True)
        allow_partial = Setting.bool_setting(
            "search.default_allow_partial_search_results", True,
            dynamic=True)
        # compat-only: accepted and validated for client parity;
        # single-node allocation has no routing decisions to gate
        # knob-ok (tools/check_dead_settings.py)
        alloc_enable = Setting.str_setting(
            "cluster.routing.allocation.enable", "all", dynamic=True,
            choices=("all", "primaries", "new_primaries", "none"))
        from opensearch_tpu.common.errors import IllegalArgumentError

        def _bp_mode_check(v: str):
            if v not in ("monitor_only", "enforced", "disabled"):
                raise IllegalArgumentError(
                    f"Invalid SearchBackpressureMode: {v}")
        backpressure_mode = Setting(
            "search_backpressure.mode", "monitor_only", str,
            validator=_bp_mode_check, dynamic=True)
        bp_cpu = Setting.float_setting(
            "search_backpressure.node_duress.cpu_threshold", 0.9,
            min_value=0.0, dynamic=True)
        bp_heap = Setting.float_setting(
            "search_backpressure.node_duress.heap_threshold", 0.85,
            min_value=0.0, dynamic=True)
        bp_queue = Setting.int_setting(
            "search_backpressure.node_duress.search_queue_threshold",
            500, min_value=1, dynamic=True)
        bp_streak = Setting.int_setting(
            "search_backpressure.node_duress.num_successive_breaches",
            3, min_value=1, dynamic=True)
        bp_max_cc = Setting.int_setting(
            "search_backpressure.max_concurrent_searches", 256,
            min_value=1, dynamic=True)
        ars_enabled = Setting.bool_setting(
            "search.replica_selection.adaptive", True, dynamic=True)
        ars_shed = Setting.bool_setting(
            "search.replica_selection.shed_on_duress", True, dynamic=True)
        ars_spill = Setting.int_setting(
            "search.replica_selection.spill_outstanding", 8,
            min_value=0, dynamic=True)
        ars_shed_occ = Setting.float_setting(
            "search.replica_selection.shed_occupancy", 0.0,
            min_value=0.0, dynamic=True)
        # search-replica tier: checkpoint lag (ops behind the last
        # published checkpoint) past which a searcher is deranked by
        # the C3 selector like a duress node
        search_max_lag = Setting.int_setting(
            "search.replication.max_lag", 8, min_value=0, dynamic=True)
        max_keep_alive = Setting.time_setting(
            "search.max_keep_alive", 24 * 3600.0, dynamic=True)
        default_keep_alive = Setting.time_setting(
            "search.default_keep_alive", 300.0, dynamic=True)
        ins_enabled = Setting.bool_setting(
            "search.insights.enabled", True, dynamic=True)
        ins_top_n = Setting.int_setting(
            "search.insights.top_n", 10, min_value=1, dynamic=True)
        ins_window = Setting.time_setting(
            "search.insights.window", 300.0, dynamic=True)
        ins_coalesce = Setting.float_setting(
            "search.insights.coalesce_window_ms", 10.0,
            min_value=0.0, dynamic=True)
        # continuous batcher at the REST edge (search/engine.py):
        # window_ms 0 = auto-size from the measured insights coalesce
        # window (the PR-10 coalescability report's Δt)
        batcher_enabled = Setting.bool_setting(
            "search.batcher.enabled", True, dynamic=True)
        batcher_window = Setting.float_setting(
            "search.batcher.window_ms", 0.0, min_value=0.0,
            dynamic=True)
        batcher_max = Setting.int_setting(
            "search.batcher.max_batch", 64, min_value=2, dynamic=True)
        # per-tenant QoS (search/qos.py): weighted admission shares per
        # X-Opaque-Id ("tenantA:4,tenantB:1"; empty = one legacy pool),
        # the default pool's weight for unlabeled traffic, and the
        # adaptive AIMD controller's enable/pacing knobs
        from opensearch_tpu.search.qos import parse_tenant_shares

        def _shares_check(v: str):
            parse_tenant_shares(v)
        qos_shares = Setting(
            "search.qos.tenant_shares", "", str,
            validator=_shares_check, dynamic=True)
        qos_default_share = Setting.float_setting(
            "search.qos.default_share", 1.0, min_value=0.0,
            dynamic=True)
        qos_adaptive = Setting.bool_setting(
            "search.qos.adaptive", False, dynamic=True)
        qos_interval = Setting.float_setting(
            "search.qos.interval_s", 1.0, min_value=0.01, dynamic=True)
        # measured device-memory budget: 0 = unlimited; exceeding it
        # unstages least-recently-dispatched segments (ROADMAP item 5's
        # host↔device paging seed, common/device_ledger.py)
        device_budget = Setting.byte_size_setting(
            "device.memory.budget_bytes", 0, dynamic=True)
        # circuit-breaker limits (common/breakers.py): the fielddata
        # breaker charges twice a segment's host footprint before it is
        # staged, so a chip filled past a quarter needs more than the
        # dev-host defaults; 0 = the limit the service was built with
        breaker_fielddata = Setting.byte_size_setting(
            "breaker.fielddata.limit", 0, dynamic=True)
        breaker_total = Setting.byte_size_setting(
            "breaker.total.limit", 0, dynamic=True)
        # paged quantized index (index/codec.py + the device pager):
        # page accounting granularity, and the per-segment lowering
        # policy ("auto" quantizes segments >= QUANTIZED_MIN_DOCS)
        pager_page_bytes = Setting.byte_size_setting(
            "device.pager.page_bytes", 0, dynamic=True)
        quantized_mode = Setting.str_setting(
            "index.device.quantized", "auto", dynamic=True,
            choices=("auto", "on", "off"))
        # accelerator fault tolerance (common/device_health.py): the
        # per-kernel-class circuit breakers' trip threshold and the
        # open-state cooldown before a half-open probe is allowed
        dh_enabled = Setting.bool_setting(
            "device.health.enabled", True, dynamic=True)
        dh_threshold = Setting.int_setting(
            "device.health.failure_threshold", 3, min_value=1,
            dynamic=True)
        dh_interval = Setting.float_setting(
            "device.health.open_interval_s", 30.0, min_value=0.0,
            dynamic=True)
        from opensearch_tpu.indices.request_cache import (
            DEFAULT_MAX_BYTES, request_cache)
        req_cache_size = Setting.byte_size_setting(
            "indices.requests.cache.size", DEFAULT_MAX_BYTES,
            dynamic=True)
        # QoS-driven searcher elasticity (cluster/autoscaler.py): the
        # leader's control loop from admission/Retry-After evidence to
        # fleet mutation — enable gate, fleet bounds, the dwell window
        # hot/cold evidence must persist before an actuation, the
        # anti-flap cooldown between scale events, and the drain
        # deadline past which retirement escalates to hard-kill
        as_enabled = Setting.bool_setting(
            "cluster.autoscale.enabled", False, dynamic=True)
        as_min = Setting.int_setting(
            "cluster.autoscale.min_searchers", 1, min_value=0,
            dynamic=True)
        as_max = Setting.int_setting(
            "cluster.autoscale.max_searchers", 4, min_value=0,
            dynamic=True)
        as_dwell = Setting.float_setting(
            "cluster.autoscale.dwell_s", 3.0, min_value=0.0,
            dynamic=True)
        as_cooldown = Setting.float_setting(
            "cluster.autoscale.cooldown_s", 10.0, min_value=0.0,
            dynamic=True)
        as_drain_timeout = Setting.float_setting(
            "cluster.autoscale.drain_timeout_s", 5.0, min_value=0.0,
            dynamic=True)
        self.cluster_settings = SettingsRegistry(
            Settings(stored),
            [max_buckets, auto_create, max_scroll, cache_size,
             identity_enabled, alloc_enable, backpressure_mode,
             bp_cpu, bp_heap, bp_queue, bp_streak, bp_max_cc,
             ars_enabled, ars_shed, ars_spill, ars_shed_occ,
             search_max_lag,
             max_keep_alive, default_keep_alive, allow_partial,
             req_cache_size, ins_enabled, ins_top_n, ins_window,
             ins_coalesce, device_budget, breaker_fielddata,
             breaker_total, pager_page_bytes, quantized_mode,
             dh_enabled, dh_threshold, dh_interval, batcher_enabled,
             batcher_window, batcher_max, qos_shares,
             qos_default_share, qos_adaptive, qos_interval,
             as_enabled, as_min, as_max, as_dwell, as_cooldown,
             as_drain_timeout])
        # per-tenant QoS knobs reach the live admission gate and the
        # controller immediately; persisted values replay at boot
        adm = self.search_backpressure.admission
        for setting, consumer in (
                (qos_shares,
                 lambda v: adm.set_tenant_shares(
                     parse_tenant_shares(v))),
                (qos_default_share, adm.set_default_share),
                (qos_adaptive, self.qos.set_enabled),
                (qos_interval, self.qos.set_interval_s)):
            self.cluster_settings.add_settings_update_consumer(
                setting, consumer)
            consumer(self.cluster_settings.get(setting))
        # continuous-batcher knobs land on engine module globals (the
        # DEFAULT_ALLOW_PARTIAL_RESULTS idiom); the insights coalesce
        # window doubles as the batcher's auto window so the Δt always
        # tracks the measured workload knob
        from opensearch_tpu.search import engine as engine_mod
        for setting, attr, conv in (
                (batcher_enabled, "BATCHER_ENABLED", bool),
                (batcher_window, "BATCHER_WINDOW_MS", float),
                (batcher_max, "BATCHER_MAX_BATCH", int),
                (ins_coalesce, "AUTO_WINDOW_MS", float)):
            def _apply_eng(v, attr=attr, conv=conv):
                setattr(engine_mod, attr, conv(v))
            self.cluster_settings.add_settings_update_consumer(
                setting, _apply_eng)
            _apply_eng(self.cluster_settings.get(setting))
        # autoscale knobs land on the autoscaler module globals: every
        # SearcherAutoscaler instance without a pinned override reads
        # them at tick time, so dynamic updates apply live
        from opensearch_tpu.cluster import autoscaler as asc_mod  # actuator-ok (operator-set knobs; the autoscaler audits its own decisions)
        for setting, attr, conv in (
                (as_enabled, "AUTOSCALE_ENABLED", bool),
                (as_min, "MIN_SEARCHERS", int),
                (as_max, "MAX_SEARCHERS", int),
                (as_dwell, "DWELL_S", float),
                (as_cooldown, "COOLDOWN_S", float),
                (as_drain_timeout, "DRAIN_TIMEOUT_S", float)):
            def _apply_asc(v, attr=attr, conv=conv):
                setattr(asc_mod, attr, conv(v))
            self.cluster_settings.add_settings_update_consumer(
                setting, _apply_asc)
            _apply_asc(self.cluster_settings.get(setting))
        # device-memory budget reaches the residency ledger immediately
        # (and persisted values replay at boot)
        from opensearch_tpu.common.device_ledger import (device_ledger,
                                                         device_pager)
        self.cluster_settings.add_settings_update_consumer(
            device_budget,
            lambda v: device_ledger().set_budget(int(v or 0)))
        device_ledger().set_budget(
            int(self.cluster_settings.get(device_budget) or 0))
        from opensearch_tpu.common.breakers import breaker_service
        for setting, name in ((breaker_fielddata, "fielddata"),
                              (breaker_total, "total")):
            def _apply_limit(v, name=name):
                breaker_service().set_limit(name, int(v or 0))
            self.cluster_settings.add_settings_update_consumer(
                setting, _apply_limit)
            _apply_limit(self.cluster_settings.get(setting))
        # pager page size reaches the process-global pager immediately;
        # the quantized-mode knob lands on the codec module global (the
        # DEFAULT_ALLOW_PARTIAL_RESULTS idiom) so the lowering decision
        # and the host parity fallback read one source of truth
        from opensearch_tpu.index import codec as codec_mod
        self.cluster_settings.add_settings_update_consumer(
            pager_page_bytes,
            lambda v: device_pager().set_page_bytes(int(v or 0)))
        device_pager().set_page_bytes(
            int(self.cluster_settings.get(pager_page_bytes) or 0))
        self.cluster_settings.add_settings_update_consumer(
            quantized_mode,
            lambda v: setattr(codec_mod, "QUANTIZED_MODE", str(v)))
        codec_mod.QUANTIZED_MODE = str(
            self.cluster_settings.get(quantized_mode))
        # device-health breaker knobs reach the process-global service
        # immediately (and persisted values replay at boot)
        from opensearch_tpu.common.device_health import device_health
        dh = device_health()
        for setting, consumer in (
                (dh_enabled, dh.set_enabled),
                (dh_threshold, dh.set_failure_threshold),
                (dh_interval, dh.set_open_interval_s)):
            self.cluster_settings.add_settings_update_consumer(
                setting, consumer)
            consumer(self.cluster_settings.get(setting))
        # query-insights knobs reach the live service immediately and
        # persisted values replay at boot
        ins = self.insights
        for setting, consumer in (
                (ins_enabled, ins.set_enabled),
                (ins_top_n, ins.set_top_n),
                (ins_window, ins.set_window_s),
                (ins_coalesce, ins.set_coalesce_window_ms)):
            self.cluster_settings.add_settings_update_consumer(
                setting, consumer)
            consumer(self.cluster_settings.get(setting))
        # search backpressure: the mode setting was validated-but-dead
        # before this PR — now every flip (and the node_duress knobs)
        # reaches the live service immediately, and persisted values
        # replay at boot (SearchBackpressureSettings' consumers)
        bp = self.search_backpressure
        for setting, consumer in (
                (backpressure_mode, bp.set_mode),
                (bp_cpu, bp.set_cpu_threshold),
                (bp_heap, bp.set_heap_threshold),
                (bp_queue, bp.set_queue_threshold),
                (bp_streak, bp.set_num_successive_breaches),
                (bp_max_cc, bp.set_max_concurrent_searches)):
            self.cluster_settings.add_settings_update_consumer(
                setting, consumer)
            consumer(self.cluster_settings.get(setting))
        # adaptive replica selection knobs land on module globals the
        # cluster coordinator reads per search (same idiom as
        # DEFAULT_ALLOW_PARTIAL_RESULTS below)
        from opensearch_tpu.cluster import response_collector as rc_mod
        self.cluster_settings.add_settings_update_consumer(
            ars_enabled,
            lambda v: setattr(rc_mod, "ADAPTIVE_ENABLED", bool(v)))
        self.cluster_settings.add_settings_update_consumer(
            ars_shed,
            lambda v: setattr(rc_mod, "SHED_ON_DURESS", bool(v)))
        self.cluster_settings.add_settings_update_consumer(
            ars_spill,
            lambda v: setattr(rc_mod, "SPILL_OUTSTANDING", int(v)))
        self.cluster_settings.add_settings_update_consumer(
            ars_shed_occ,
            lambda v: setattr(rc_mod, "SHED_OCCUPANCY", float(v)))
        self.cluster_settings.add_settings_update_consumer(
            search_max_lag,
            lambda v: setattr(rc_mod, "SEARCH_MAX_LAG", int(v)))
        rc_mod.SEARCH_MAX_LAG = int(
            self.cluster_settings.get(search_max_lag))
        rc_mod.ADAPTIVE_ENABLED = bool(
            self.cluster_settings.get(ars_enabled))
        rc_mod.SHED_ON_DURESS = bool(self.cluster_settings.get(ars_shed))
        rc_mod.SPILL_OUTSTANDING = int(
            self.cluster_settings.get(ars_spill))
        rc_mod.SHED_OCCUPANCY = float(
            self.cluster_settings.get(ars_shed_occ))
        self.cluster_settings.add_settings_update_consumer(
            req_cache_size,
            lambda v: request_cache().set_max_bytes(int(v)))
        request_cache().set_max_bytes(
            int(self.cluster_settings.get(req_cache_size)))
        from opensearch_tpu.search import executor as executor_mod
        self.cluster_settings.add_settings_update_consumer(
            allow_partial,
            lambda v: setattr(executor_mod,
                              "DEFAULT_ALLOW_PARTIAL_RESULTS", bool(v)))
        executor_mod.DEFAULT_ALLOW_PARTIAL_RESULTS = bool(
            self.cluster_settings.get(allow_partial))
        self.cluster_settings.add_settings_update_consumer(
            max_keep_alive,
            lambda v: setattr(self.contexts, "max_keep_alive_s", v))
        # search.default_keep_alive was registered-but-dead before this
        # PR (tools/check_dead_settings.py caught it): it now sets the
        # keepalive a PIT opened without an explicit keep_alive gets
        self.cluster_settings.add_settings_update_consumer(
            default_keep_alive,
            lambda v: setattr(self.contexts, "default_keep_alive_s",
                              float(v)))
        self.contexts.default_keep_alive_s = float(
            self.cluster_settings.get(default_keep_alive))
        # cluster-level slowlog threshold DEFAULTS (per-index settings
        # override; the reference layers index settings over node ones)
        from opensearch_tpu.indices import service as indices_mod
        for prefix in ("search.slowlog.threshold.query",
                       "indexing.slowlog.threshold.index"):
            for level in ("warn", "info", "debug", "trace"):
                key = f"{prefix}.{level}"
                s = Setting(key, None, lambda x: x, dynamic=True)
                self.cluster_settings.register(s)

                def _apply(v, key=key):
                    if v is None:
                        indices_mod.SLOWLOG_DEFAULTS.pop(key, None)
                    else:
                        indices_mod.SLOWLOG_DEFAULTS[key] = v
                self.cluster_settings.add_settings_update_consumer(
                    s, _apply)
                _apply(self.cluster_settings.get(s))   # replay persisted
        # remote clusters configure via affix keys (RemoteClusterService)
        self.cluster_settings.register_prefix("cluster.remote")
        from opensearch_tpu.transport.remote import RemoteClusterService
        self.remotes = RemoteClusterService(
            lambda: self.cluster_settings.settings.as_dict())
        self.cluster_settings.add_settings_update_consumer(
            max_buckets, lambda v: setattr(aggs_mod, "MAX_BUCKETS", v))
        self.cluster_settings.add_settings_update_consumer(
            auto_create, lambda v: setattr(self.indices, "auto_create", v))
        self.cluster_settings.add_settings_update_consumer(
            max_scroll, lambda v: setattr(self.contexts, "_max_open", v))
        self.cluster_settings.add_settings_update_consumer(
            cache_size, lambda v: self.indices.file_cache.set_max_bytes(v))
        self.cluster_settings.add_settings_update_consumer(
            identity_enabled,
            lambda v: setattr(self.identity, "enabled", v))
        # replay persisted values into the consumers at boot
        aggs_mod.MAX_BUCKETS = self.cluster_settings.get(max_buckets)
        self.indices.auto_create = self.cluster_settings.get(auto_create)
        self.contexts._max_open = self.cluster_settings.get(max_scroll)
        self.indices.file_cache.set_max_bytes(
            self.cluster_settings.get(cache_size))
        self.identity.enabled = self.cluster_settings.get(
            identity_enabled)

    def update_cluster_settings(self, persistent: dict | None = None,
                                transient: dict | None = None) -> dict:
        """Two-bucket cluster settings (ClusterUpdateSettingsRequest):
        null values reset; transient overrides persistent; only the
        persistent bucket survives restart."""
        import json as _json

        touched = set(persistent or {}) | set(transient or {})
        # validate BEFORE mutating the buckets (a rejected update must
        # leave them unchanged)
        self.cluster_settings.validate(
            {k: v for k, v in {**(persistent or {}),
                               **(transient or {})}.items()
             if v is not None})
        for bucket, ups in (("persistent", persistent),
                            ("transient", transient)):
            d = self.settings_buckets[bucket]
            for k, v in (ups or {}).items():
                if v is None:
                    d.pop(k, None)
                else:
                    d[k] = v
        # the EFFECTIVE value of a touched key is transient over
        # persistent over default — never just this request's value
        # (ClusterSettings precedence)
        effective = {**self.settings_buckets["persistent"],
                     **self.settings_buckets["transient"]}
        self.cluster_settings.apply_update(
            {k: effective.get(k) for k in touched})
        tmp = self._settings_file + ".tmp"
        with open(tmp, "w") as f:
            _json.dump(self.settings_buckets["persistent"], f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._settings_file)
        return {"acknowledged": True,
                "persistent": dict(self.settings_buckets["persistent"]),
                "transient": dict(self.settings_buckets["transient"])}

    @property
    def port(self) -> int:
        return self.http.port

    def start(self):
        from opensearch_tpu.bootstrap import (default_checks,
                                              run_bootstrap_checks)
        # the reference enforces once the node publishes beyond
        # loopback (BootstrapChecks.enforceLimits); dev mode only warns
        enforce = (self.host not in ("127.0.0.1", "localhost", "::1")
                   or os.environ.get("OSTPU_ENFORCE_BOOTSTRAP") == "1")
        run_bootstrap_checks(default_checks(self.data_path),
                             enforce=enforce)
        if self.identity.enabled and self.host not in ("127.0.0.1",
                                                       "localhost", "::1"):
            import logging
            logging.getLogger("opensearch_tpu.security").warning(
                "identity.enabled is set with a non-loopback bind [%s] "
                "and no TLS: basic-auth credentials travel in cleartext "
                "(the reference's security plugin requires TLS here)",
                self.host)
        # collector pauses into _nodes/stats runtime.gc, from the first
        # request on (one hook a process, shared by its nodes)
        from opensearch_tpu.common.telemetry import gc_timer
        gc_timer().install()
        self.http.start()
        # overload monitor: evaluates node duress on a cadence even when
        # no new searches arrive to tick it (SearchBackpressureService's
        # scheduled run)
        self.search_backpressure.start_monitor()
        # periodic disk probe (FsHealthService.monitorFSHealth's schedule):
        # health was previously only refreshed when _nodes/stats was read —
        # a dead disk between reads went unnoticed
        self.fs_health.start_probe(
            float(os.environ.get("OSTPU_FSHEALTH_INTERVAL", "5.0")),
            name=f"fshealth-{self.name}")
        # re-run persistent tasks that never completed (crash between
        # submit and completion); executors are idempotent
        self.persistent_tasks.resume_incomplete()
        return self

    def stop(self):
        # idempotent (and safe when start() never ran): double-stop in a
        # test teardown must not re-close engines or hang on the HTTP
        # server's shutdown handshake
        if getattr(self, "_stopped", False):
            return
        self._stopped = True
        self.search_backpressure.stop_monitor()
        self.fs_health.stop_probe()
        self.http.stop()
        self.indices.close()
        # bounded-join the (process-global) query-engine workers; safe
        # when never started, idempotent on double-stop
        from opensearch_tpu.search.engine import query_engine
        query_engine().shutdown()
        self.thread_pool.shutdown()


def main(argv=None):
    ap = argparse.ArgumentParser(prog="opensearch-tpu")
    ap.add_argument("--port", type=int, default=9200)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--data-path", default="./data")
    ap.add_argument("--name", default="node-1")
    ap.add_argument("--cluster-name", default="opensearch-tpu")
    args = ap.parse_args(argv)

    node = Node(args.data_path, name=args.name,
                cluster_name=args.cluster_name, host=args.host,
                port=args.port).start()
    from opensearch_tpu.common.device_ledger import backend_info
    dev = backend_info()
    print(f"[{args.name}] listening on http://{args.host}:{node.port} "
          f"(data: {args.data_path}; platform: {dev['platform']}, "
          f"device_kind: {dev['device_kind']}, "
          f"devices: {dev['device_count']})", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    try:
        stop.wait()
    finally:
        node.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
