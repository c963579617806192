"""Device-resident memory & transfer observability: the staging ledger.

Until this module, nothing in the system could answer "what is on the
device, how many bytes, who staged it, and when was it last used" — the
fielddata breaker counted an *estimate* at segment-staging time and the
rest was assertion.  ROADMAP items 1 (continuous batching) and 5
(quantized device-resident indices at 10-100x corpus scale) both need a
measured device-memory budget line; GPUSparse (arxiv 2606.26441) treats
accelerator-resident index layout and transfer cost as first-class
engineering quantities.  This ledger makes them measurable here:

- **Residency ledger** — ALL device staging flows through it: every
  ``DeviceSegment`` array family (postings, impacts, doc values, live
  masks, nested blocks, ANN structures), the batched-msearch group
  arrays, and the mesh path's ``jax.device_put``.  Each entry records
  its owner (index/shard/segment/field/kind), exact staged nbytes, the
  staging tick, and per-owner dispatch count + last-dispatch tick.
  ``tools/check_device_staging.py`` (tier-1) rejects raw staging calls
  outside this module in ``index/``/``search/``/``parallel/``/``ops/``.
- **Transfer accounting** — host→device (stage) and device→host
  (fetch-back) byte/op/time counters, fed into the MetricsRegistry so
  ``/_metrics`` scrapes them and ``_nodes/stats`` reports them.
- **Compile registry** — per-kernel XLA program counts behind a
  version-tolerant ``_cache_size`` shim (jit's private introspection
  moved across jax versions; a missing attribute degrades to a counted
  ``unavailable`` instead of breaking the profiler).
- **Budget enforcement** — the first consumer: a dynamic
  ``device.memory.budget_bytes`` setting; when resident bytes exceed
  it, the least-recently-dispatched sealed segment stagings are
  unstaged (counted evictions, fielddata-breaker release).  Evicted
  scored term-bags degrade byte-identically to the host impact-table
  path (``TermBagPlan.host_topk`` — the PR-5 parity invariant); other
  plans restage on demand (counted restages).  This is the seed of
  ROADMAP item 5's host↔device paging.

The ledger is process-global (like the breaker service and the metrics
registry): in-process multi-node tests share one ledger, which is the
honest model — they also share one device.  Tests reset it via
``device_ledger().reset()``.
"""

from __future__ import annotations

import itertools
import threading
import time
import weakref
from typing import Callable, Optional

from opensearch_tpu.common.telemetry import metrics as _metrics

# entry kinds a DeviceSegment stages (the "array families" of the
# tentpole); batch/mesh/other producers add their own kinds
SEGMENT_KINDS = ("postings", "numeric", "ordinal", "vector", "geo",
                 "impacts", "live", "nested", "ann")


def host_footprint(seg, per_field: bool = False):
    """Host-side footprint of one ``Segment`` in bytes — THE source of
    truth for "how big is this segment" (replaces the hand-rolled
    estimate ``DeviceSegment`` used for its breaker charge and the
    ad-hoc doc-values math ``GET /_cat/fielddata`` did inline).

    Returns total bytes, or ``{(kind, field): bytes}`` with
    ``per_field=True``.  Pure numpy accounting; never touches jax.
    """
    out: dict[tuple, int] = {}

    def put(kind, field, *arrays):
        n = sum(int(getattr(a, "nbytes", 0)) for a in arrays
                if a is not None)
        if n:
            out[(kind, field)] = out.get((kind, field), 0) + n

    for name, pf in seg.postings.items():
        put("postings", name, pf.offsets, pf.doc_ids, pf.tfs,
            pf.pos_offsets, pf.positions, pf.doc_lens, pf.df, pf.present)
    for name, dv in seg.numeric_dv.items():
        put("numeric", name, dv.offsets, dv.values, dv.value_docs,
            dv.minv, dv.maxv, dv.exists)
    for name, dv in seg.ordinal_dv.items():
        put("ordinal", name, dv.offsets, dv.ords, dv.value_docs,
            dv.min_ord, dv.max_ord, dv.exists)
    for name, dv in seg.vector_dv.items():
        put("vector", name, dv.values, dv.exists)
    for name, dv in seg.geo_dv.items():
        put("geo", name, dv.offsets, dv.lats, dv.lons, dv.value_docs,
            dv.exists)
    if per_field:
        return out
    return sum(out.values())


class KernelCompileRegistry:
    """Per-kernel XLA compile/retrace registry: every jit entry point of
    the query path registers here, and ``counts()`` reads each one's
    live compiled-program count through a version-tolerant shim around
    jit's private ``_cache_size`` — generalizing the profiler's one-off
    delta so a jax upgrade that drops the introspection degrades the
    metric (counted ``unavailable``) instead of breaking the Profile
    API."""

    # default query-path kernels, resolved lazily (import cycles during
    # bootstrap are the same reason profile.py resolved them lazily)
    _DEFAULTS = (
        ("plan.run_topk", "opensearch_tpu.search.plan", "run_topk"),
        ("plan.run_topk_parts", "opensearch_tpu.search.plan",
         "run_topk_parts"),
        ("plan.run_full", "opensearch_tpu.search.plan", "run_full"),
        ("plan.topk_from_scores", "opensearch_tpu.search.plan",
         "topk_from_scores"),
        ("batch.batch_impact_union_topk", "opensearch_tpu.search.batch",
         "batch_impact_union_topk"),
        ("knn.knn_scores", "opensearch_tpu.ops.knn", "knn_scores"),
        ("knn.knn_topk", "opensearch_tpu.ops.knn", "knn_topk"),
        ("knn.knn_topk_batch", "opensearch_tpu.ops.knn", "knn_topk_batch"),
    )

    def __init__(self):
        self._lock = threading.Lock()
        self._kernels: dict[str, object] = {}
        self._defaults_loaded = False

    def register(self, name: str, fn) -> None:
        with self._lock:
            self._kernels[name] = fn

    def _ensure_defaults(self) -> None:
        if self._defaults_loaded:
            return
        import importlib
        loaded = {}
        for name, mod, attr in self._DEFAULTS:
            try:
                fn = getattr(importlib.import_module(mod), attr)
            except Exception:      # partial import cycle during bootstrap
                return             # retry on the next read
            loaded[name] = fn
        with self._lock:
            for name, fn in loaded.items():
                self._kernels.setdefault(name, fn)
            self._defaults_loaded = True

    @staticmethod
    def _cache_size_of(fn) -> Optional[int]:
        """The version-tolerant ``_cache_size`` shim: None when this jax
        doesn't expose compiled-program introspection for ``fn``."""
        size = getattr(fn, "_cache_size", None)
        if size is None:
            return None
        try:
            return int(size())
        except Exception:          # introspection changed shape again
            return None

    def counts(self) -> dict:
        """{"kernels": {name: programs}, "unavailable": n, "total": n}
        — kernels whose introspection is gone are listed under
        ``unavailable`` (counted, never raising)."""
        self._ensure_defaults()
        with self._lock:
            kernels = dict(self._kernels)
        out: dict[str, int] = {}
        unavailable = 0
        for name in sorted(kernels):
            n = self._cache_size_of(kernels[name])
            if n is None:
                unavailable += 1
            else:
                out[name] = n
        return {"kernels": out, "unavailable": unavailable,
                "total": sum(out.values())}

    def program_count(self) -> int:
        """Total live compiled programs across registered kernels (the
        profiler's ``xla_compiles`` delta source)."""
        return self.counts()["total"]


class _Group:
    """One staging owner's ledger entries — normally one DeviceSegment's
    whole array family set; also one batch-prep group or one mesh
    placement.  The group is the eviction unit: "unstage the
    least-recently-dispatched segment" means closing its group."""

    __slots__ = ("index", "shard", "segment", "entries", "staged_tick",
                 "dispatches", "last_dispatch_tick", "sealed",
                 "evict_cb", "evict_class", "_gid", "__weakref__")

    def __init__(self, index: str, shard, segment: str,
                 evict_cb: Optional[Callable] = None,
                 evict_class: str = "segment"):
        self.index = index
        self.shard = shard
        self.segment = segment
        self.entries: dict[tuple, int] = {}   # (kind, field, name) -> nbytes
        self.staged_tick = 0
        self.dispatches = 0
        self.last_dispatch_tick = 0
        self.sealed = False                   # unsealed groups never evict
        self.evict_cb = evict_cb              # None -> not evictable
        self.evict_class = evict_class        # "page" evicts before "segment"

    def nbytes(self) -> int:
        return sum(self.entries.values())

    def to_dict(self) -> dict:
        by_kind: dict[str, int] = {}
        for (kind, _f, _n), b in self.entries.items():
            by_kind[kind] = by_kind.get(kind, 0) + b
        return {"index": self.index, "shard": self.shard,
                "segment": self.segment, "bytes": self.nbytes(),
                "entries": len(self.entries),
                "by_kind": dict(sorted(by_kind.items())),
                "staged_tick": self.staged_tick,
                "dispatches": self.dispatches,
                "last_dispatch_tick": self.last_dispatch_tick,
                "evictable": self.evict_cb is not None and self.sealed}


class GroupCloser:
    """Keep one of these inside a cache entry that owns a staging group
    (dicts are not weakref-able, so ``tether`` can't watch them): when
    the entry is evicted or garbage collected, the sentinel closes the
    group and its ledger entries disappear with the staged arrays."""

    __slots__ = ("_ledger", "_group")

    def __init__(self, ledger: "DeviceResidencyLedger", group: "_Group"):
        self._ledger = ledger
        self._group = group

    def __del__(self):
        try:
            self._ledger.close_group(self._group)
        except Exception:
            pass


class DeviceResidencyLedger:
    """The device residency + transfer ledger (module docstring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._groups: "dict[int, _Group]" = {}
        self._next_id = itertools.count(1)
        self._tick = itertools.count(1)
        self.budget_bytes: Optional[int] = None
        self.evictions = 0
        self.restages = 0
        self.host_fallbacks = 0
        self.slice_gather_programs = 0
        self.block_topk_programs = 0
        self.sorted_bag_programs = 0
        self.phrase_programs = 0
        self._evicted_bytes = 0
        self._transfers = {
            "stage": {"bytes": 0, "ops": 0, "seconds": 0.0},
            "fetch": {"bytes": 0, "ops": 0, "arrays": 0, "seconds": 0.0},
            "input": {"bytes": 0, "arrays": 0}}

    # -- group lifecycle ---------------------------------------------------

    def open_group(self, *, index: str = "-", shard=0, segment: str = "-",
                   evict: Optional[Callable] = None,
                   evict_class: str = "segment") -> _Group:
        """New (unsealed) staging group.  ``evict`` is the unstage
        callback the budget enforcer may call; groups without one are
        accounted but never evicted (batch/mesh stagings whose lifetime
        is owned by their caches).  ``evict_class="page"`` marks a
        cheap-to-restage group (the pager's quantized tables rebuild
        from host codec tables, not from a full segment restage) —
        budget enforcement spends pages before whole segments."""
        g = _Group(index, shard, segment, evict_cb=evict,
                   evict_class=evict_class)
        g.staged_tick = next(self._tick)
        gid = next(self._next_id)
        with self._lock:
            self._groups[gid] = g
        g._gid = gid  # type: ignore[attr-defined]
        return g

    def tether(self, owner, group: _Group) -> None:
        """Close ``group`` automatically when ``owner`` (a weakref-able
        object — e.g. a DeviceSegment) is garbage collected, so a
        refreshed-away staging cannot leak ledger entries."""
        weakref.finalize(owner, self._forget,
                         getattr(group, "_gid", -1))

    def _forget(self, gid: int) -> None:
        with self._lock:
            self._groups.pop(gid, None)

    def seal(self, group: _Group) -> None:
        """Mark the group fully staged — only sealed groups are eviction
        candidates (never unstage a segment mid-construction)."""
        group.sealed = True
        self._enforce(protect=group)

    def close_group(self, group: _Group) -> None:
        """Explicit removal (eviction or owner teardown)."""
        self._forget(getattr(group, "_gid", -1))

    # -- staging (H2D) -----------------------------------------------------

    def stage(self, group: Optional[_Group], host_array, *, kind: str,
              field: str = "", name: str = ""):
        """THE sanctioned host→device staging call: performs the
        transfer (``jnp.asarray``), times it, and records the entry
        under ``group`` with the exact staged nbytes.  Returns the
        device array."""
        import jax.numpy as jnp

        t0 = time.monotonic()
        out = jnp.asarray(host_array)      # staging-ok: the ledger itself
        dt = time.monotonic() - t0
        self._record(group, (kind, field, name),
                     int(getattr(host_array, "nbytes", None)
                         or out.nbytes), dt)
        return out

    def stage_input(self, host_array):
        """Transfer-only staging of ONE per-query input (term ids, a
        query vector, a scalar): the ``jnp.asarray`` and a count under
        ``transfers.input``, the outbound mirror of ``record_fetch``'s
        ``arrays``.  Nothing becomes resident here and no group owns
        it: the prepared-bindings cache holds the result for as long as
        the query may come again.  Untimed: the copy is asynchronous and
        two clock reads would cost what a small one does."""
        import jax.numpy as jnp

        out = jnp.asarray(host_array)      # staging-ok: the ledger itself
        with self._lock:
            t = self._transfers["input"]
            t["bytes"] += int(getattr(host_array, "nbytes", None)
                              or out.nbytes)
            t["arrays"] += 1
        return out

    def device_put(self, group: Optional[_Group], value, sharding=None,
                   *, kind: str = "mesh", field: str = "",
                   name: str = ""):
        """Sanctioned ``jax.device_put`` (the mesh placement path)."""
        import jax

        t0 = time.monotonic()
        out = jax.device_put(value, sharding)  # staging-ok: the ledger itself
        dt = time.monotonic() - t0
        self._record(group, (kind, field, name),
                     int(getattr(value, "nbytes", None) or 0), dt)
        return out

    def adopt(self, group: _Group, arrays, *, kind: str,
              field: str = "", name: str = "") -> None:
        """Account already-staged device arrays (ANN structures staged
        by their own builders) without re-performing the transfer."""
        total = 0
        stackk = [arrays]
        while stackk:
            v = stackk.pop()
            nb = getattr(v, "nbytes", None)
            if nb is not None:
                total += int(nb)
            elif isinstance(v, (tuple, list)):
                stackk.extend(v)
            elif isinstance(v, dict):
                stackk.extend(v.values())
        self._record(group, (kind, field, name), total, 0.0)

    def _record(self, group: Optional[_Group], key: tuple, nbytes: int,
                seconds: float) -> None:
        prev = 0
        with self._lock:
            if group is not None:
                prev = group.entries.get(key)
                group.entries[key] = nbytes
            t = self._transfers["stage"]
            t["bytes"] += nbytes
            t["ops"] += 1
            t["seconds"] += seconds
        _metrics().counter("device.transfer.stage.bytes").inc(nbytes)
        _metrics().counter("device.transfer.stage.ops").inc()
        if group is not None and prev is None and group.sealed:
            # post-seal additions (impacts/live staged lazily) can push
            # past the budget too
            self._enforce(protect=group)

    def drop(self, group: _Group, *, kind: str, field: str = "",
             name: str = "") -> None:
        """Remove one entry (its device array was dropped by the owning
        cache — e.g. a live-mask snapshot LRU'ing out)."""
        with self._lock:
            group.entries.pop((kind, field, name), None)

    # -- dispatch + fetch-back accounting ----------------------------------

    def record_dispatch(self, group: Optional[_Group], *,
                        slice_gather: bool = False,
                        block_topk: bool = False,
                        sorted_bag: bool = False,
                        phrase: bool = False) -> None:
        """One device program consumed this group's arrays — the LRU
        signal budget eviction orders by.  ``slice_gather``: the
        program's static shape took ``gather_postings``'s contiguous-
        slice lowering (``ops/bm25.py::slice_lowering``, asked by the
        caller as the kernel asks it).  ``block_topk``: its top-k took
        the two stages of ``ops/topk.py`` (``block_size`` of the
        lanes of the program's key and ``k``, asked the same way).
        ``sorted_bag``: a scored term bag's top-k came from its sorted
        postings, without the dense accumulator (``Plan.sorted_topk``,
        i.e. ``ops/bm25.py::sorted_bag``, over a segment with no
        deleted doc).  ``phrase``: the program holds a ``PhrasePlan``
        somewhere in its plan (``plan.phrase_dims`` of its key)."""
        with self._lock:
            self.slice_gather_programs += bool(slice_gather)
            self.block_topk_programs += bool(block_topk)
            self.sorted_bag_programs += bool(sorted_bag)
            self.phrase_programs += bool(phrase)
            if group is not None:
                group.dispatches += 1
                group.last_dispatch_tick = next(self._tick)

    def record_fetch(self, nbytes: int, seconds: float, *,
                     arrays: int = 1) -> None:
        """Device→host result readback: one sync region of the query
        path or the mesh merge (``ops``), in which ``arrays`` device
        arrays were read, each a round trip of its own unless its copy
        was started at launch."""
        with self._lock:
            t = self._transfers["fetch"]
            t["bytes"] += int(nbytes)
            t["ops"] += 1
            t["arrays"] += int(arrays)
            t["seconds"] += seconds
        _metrics().counter("device.transfer.fetch.bytes").inc(int(nbytes))
        _metrics().counter("device.transfer.fetch.ops").inc()

    def record_restage(self) -> None:
        """A previously evicted segment was staged again (demand
        paging's fault counter)."""
        with self._lock:
            self.restages += 1
        _metrics().counter("device.restages").inc()

    def record_host_fallback(self) -> None:
        """An evicted segment scored on the host impact tables instead
        of restaging (the byte-identical degradation path)."""
        with self._lock:
            self.host_fallbacks += 1
        _metrics().counter("device.host_fallback").inc()

    # -- budget enforcement ------------------------------------------------

    def set_budget(self, budget_bytes: Optional[int]) -> None:
        """Dynamic ``device.memory.budget_bytes`` consumer; 0/None =
        unlimited.  Applies immediately."""
        b = int(budget_bytes) if budget_bytes else 0
        self.budget_bytes = b if b > 0 else None
        self._enforce()

    def _enforce(self, protect: Optional[_Group] = None) -> None:
        """Unstage least-recently-dispatched sealed groups until
        resident bytes fit the budget.  ``protect`` (the group just
        staged) is never evicted — evicting the staging you are in the
        middle of serving would livelock demand paging."""
        budget = self.budget_bytes
        if budget is None:
            return
        while True:
            with self._lock:
                resident = sum(g.nbytes() for g in self._groups.values())
                if resident <= budget:
                    return
                victims = [g for g in self._groups.values()
                           if g.sealed and g.evict_cb is not None
                           and g is not protect]
                if not victims:
                    return          # nothing evictable: stay over budget
                # cheap-to-restage pages go before whole segments
                # (a page rebuilds from host codec tables; a segment
                # eviction forces host fallback or a full restage);
                # within a class, least-recently-dispatched first
                victim = min(victims,
                             key=lambda g: (g.evict_class != "page",
                                            g.last_dispatch_tick,
                                            g.staged_tick))
                freed = victim.nbytes()
                self.evictions += 1
                self._evicted_bytes += freed
                cb = victim.evict_cb
                victim.evict_cb = None    # never evict twice
            _metrics().counter("device.evictions").inc()
            _metrics().counter("device.evicted.bytes").inc(freed)
            try:
                cb()                      # releases the breaker charge
            finally:
                self.close_group(victim)

    # -- readout -----------------------------------------------------------

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(g.nbytes() for g in self._groups.values())

    def transfer_snapshot(self) -> tuple[int, int]:
        """(stage_bytes, fetch_bytes) monotonic totals — per-query
        attribution takes deltas (the insights transfer_bytes field)."""
        with self._lock:
            return (self._transfers["stage"]["bytes"],
                    self._transfers["fetch"]["bytes"])

    def device_footprint(self, seg) -> int:
        """Currently staged bytes of one ``Segment`` (0 when it is not
        device-resident)."""
        dseg = getattr(seg, "_device", None)
        group = getattr(dseg, "_ledger_group", None)
        if group is None:
            return 0
        with self._lock:
            return group.nbytes()

    def stats(self) -> dict:
        """The ``_nodes/stats`` ``device`` section body: residency
        rollups per index, transfer counters, budget/eviction
        accounting, and the per-kernel compile registry."""
        with self._lock:
            groups = list(self._groups.values())
            transfers = {
                side: ({**{k: v for k, v in t.items() if k != "seconds"},
                        "time_ms": round(t["seconds"] * 1000.0, 3)}
                       if "seconds" in t else dict(t))
                for side, t in self._transfers.items()}
            budget = self.budget_bytes
            ev, evb = self.evictions, self._evicted_bytes
            rs, hf = self.restages, self.host_fallbacks
            slice_gathers = self.slice_gather_programs
            block_topks = self.block_topk_programs
            sorted_bags = self.sorted_bag_programs
            phrases = self.phrase_programs
        per_index: dict[str, dict] = {}
        resident = 0
        dispatches = 0
        for g in groups:
            b = g.nbytes()
            resident += b
            dispatches += g.dispatches
            ix = per_index.setdefault(
                g.index, {"bytes": 0, "segments": 0, "dispatches": 0})
            ix["bytes"] += b
            ix["segments"] += 1
            ix["dispatches"] += g.dispatches
        return {
            "resident_bytes": resident,
            "resident_segments": len(groups),
            "dispatches": dispatches,
            "slice_gather_programs": slice_gathers,
            "block_topk_programs": block_topks,
            "sorted_bag_programs": sorted_bags,
            "phrase_programs": phrases,
            "budget": {
                "budget_bytes": budget or 0,
                "evictions": ev,
                "evicted_bytes": evb,
                "restages": rs,
                "host_fallbacks": hf,
            },
            "transfers": transfers,
            "pager": device_pager().stats(),
            "indices": dict(sorted(per_index.items())),
            "compile_registry": kernel_registry().counts(),
            "backend": _backend_memory_stats(),
        }

    def segments(self) -> list[dict]:
        """Per-group detail rows (debug surface; `_cat/segments` reads
        footprints through ``device_footprint`` instead)."""
        with self._lock:
            groups = sorted(self._groups.values(),
                            key=lambda g: (g.index, str(g.shard),
                                           g.segment))
        return [g.to_dict() for g in groups]

    def prometheus_text(self) -> str:
        """Gauge exposition for the scrape surface (counters already
        flow through the MetricsRegistry)."""
        s = self.stats()
        lines = [
            "# HELP opensearch_tpu_device_resident_bytes "
            "Device-resident ledger bytes",
            "# TYPE opensearch_tpu_device_resident_bytes gauge",
            f"opensearch_tpu_device_resident_bytes {s['resident_bytes']}",
            "# HELP opensearch_tpu_device_budget_bytes "
            "Configured device memory budget (0 = unlimited)",
            "# TYPE opensearch_tpu_device_budget_bytes gauge",
            "opensearch_tpu_device_budget_bytes "
            f"{s['budget']['budget_bytes']}",
            "# HELP opensearch_tpu_device_resident_segments "
            "Device-resident staging groups",
            "# TYPE opensearch_tpu_device_resident_segments gauge",
            "opensearch_tpu_device_resident_segments "
            f"{s['resident_segments']}",
            "# HELP opensearch_tpu_device_pager_resident_pages "
            "Quantized-index pager resident pages",
            "# TYPE opensearch_tpu_device_pager_resident_pages gauge",
            "opensearch_tpu_device_pager_resident_pages "
            f"{s['pager']['resident_pages']}",
            "# HELP opensearch_tpu_device_pager_capacity_pages "
            "Quantized-index pager page capacity (-1 = unlimited)",
            "# TYPE opensearch_tpu_device_pager_capacity_pages gauge",
            "opensearch_tpu_device_pager_capacity_pages "
            f"{s['pager']['capacity_pages'] if s['pager']['capacity_pages'] is not None else -1}",
        ]
        lines.append(
            "# HELP opensearch_tpu_device_index_resident_bytes "
            "Device-resident bytes per index")
        lines.append(
            "# TYPE opensearch_tpu_device_index_resident_bytes gauge")
        for ix, row in s["indices"].items():
            ixv = (str(ix).replace("\\", "\\\\").replace('"', '\\"')
                   .replace("\n", "\\n"))
            lines.append(
                f'opensearch_tpu_device_index_resident_bytes'
                f'{{index="{ixv}"}} {row["bytes"]}')  # label-ok: bounded by index count
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        """Test hook: forget all groups and zero the counters (the
        staged arrays themselves stay owned by their segments)."""
        with self._lock:
            self._groups.clear()
            self.budget_bytes = None
            self.evictions = self.restages = self.host_fallbacks = 0
            self.slice_gather_programs = self.block_topk_programs = 0
            self.sorted_bag_programs = self.phrase_programs = 0
            self._evicted_bytes = 0
            for t in self._transfers.values():
                for key in t:
                    t[key] = 0.0 if key == "seconds" else 0
        device_pager().reset()


class _PageEntry:
    """One pager residency unit: the staged device arrays of one
    quantized (segment, field, avgdl) table set, accounted in fixed-size
    pages."""

    __slots__ = ("key", "arrays", "group", "nbytes", "pages",
                 "last_use_tick")

    def __init__(self, key, arrays, group, nbytes, pages, tick):
        self.key = key
        self.arrays = arrays
        self.group = group
        self.nbytes = nbytes
        self.pages = pages
        self.last_use_tick = tick


class DevicePager:
    """Host↔device pager for quantized segment groups (ROADMAP item 2's
    paging half).

    Quantized table sets (index/codec.py) are staged as fixed-size
    *pages* under the same ``device.memory.budget_bytes`` the ledger
    enforces: capacity is ``budget_bytes // page_bytes``; an ``acquire``
    that doesn't fit evicts the least-recently-used resident entry
    first (pager-level LRU — finer-grained and cheaper to restage than
    whole-segment ledger eviction, because a quantized page rebuilds
    from the host codec tables, not from a full segment restage).
    ``prefetch`` stages ahead of the dispatch loop but only into FREE
    pages — the prefetch oracle (per-term block-max score bounds, see
    ``TermBagPlan.prefetch_quantized``) ranks what is worth staging; it
    never thrashes demand-paged residents.

    Every staging flows through the owning ledger, so pager pages also
    show up in residency/transfer accounting, and the ledger's own
    budget enforcement can evict a pager group like any other sealed
    group (the pager is told via the evict callback and keeps its book
    straight).  Miss/evict/prefetch counters feed ``_nodes/stats``
    ``device.pager`` and ``/_metrics``.
    """

    DEFAULT_PAGE_BYTES = 1 << 20

    def __init__(self, ledger: DeviceResidencyLedger):
        self._led = ledger
        self._lock = threading.Lock()
        self.page_bytes = self.DEFAULT_PAGE_BYTES
        self._entries: dict[tuple, _PageEntry] = {}
        self._tick = itertools.count(1)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.evicted_pages = 0
        self.prefetches = 0

    def set_page_bytes(self, n) -> None:
        """Dynamic ``device.pager.page_bytes`` consumer (0/None keeps
        the default)."""
        n = int(n) if n else 0
        self.page_bytes = n if n > 0 else self.DEFAULT_PAGE_BYTES

    def capacity_pages(self):
        """None = unlimited (no device budget configured)."""
        budget = self._led.budget_bytes
        if budget is None:
            return None
        return max(1, budget // self.page_bytes)

    def resident_pages(self) -> int:
        with self._lock:
            return sum(e.pages for e in self._entries.values())

    def _pages_of(self, nbytes: int) -> int:
        return max(1, -(-int(nbytes) // self.page_bytes))

    def acquire(self, key, loader, *, index: str = "-", shard=0,
                segment: str = "-"):
        """Resident arrays for ``key``, staging (and evicting LRU pages
        to fit) on miss.  ``loader()`` returns the host payload as a
        list of ``(name, kind, np_array)``; the staged dict is keyed by
        name."""
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                self.hits += 1
                e.last_use_tick = next(self._tick)
                group = e.group
                arrays = e.arrays
        if e is not None:
            self._led.record_dispatch(group)
            _metrics().counter("device.pager.hits").inc()
            return arrays
        with self._lock:
            self.misses += 1
        _metrics().counter("device.pager.misses").inc()
        return self._stage(key, loader(), index=index, shard=shard,
                           segment=segment, prefetched=False)

    def prefetch(self, key, loader, nbytes_hint: int, *,
                 index: str = "-", shard=0, segment: str = "-") -> bool:
        """Stage ``key`` ahead of demand IF it fits in free pages —
        prefetch never evicts a resident entry, so a bad oracle ranking
        costs nothing but spare capacity.  Returns True when staged."""
        cap = self.capacity_pages()
        need = self._pages_of(nbytes_hint)
        with self._lock:
            if key in self._entries:
                return False
            if cap is not None:
                free = cap - sum(e.pages for e in self._entries.values())
                if free < need:
                    return False
        self._stage(key, loader(), index=index, shard=shard,
                    segment=segment, prefetched=True)
        return True

    def _stage(self, key, items, *, index, shard, segment, prefetched):
        field = key[3] if len(key) > 3 else ""
        cb = lambda: self._on_ledger_evict(key)  # noqa: E731
        group = self._led.open_group(index=index, shard=shard,
                                     segment=segment, evict=cb,
                                     evict_class="page")
        arrays = {}
        nbytes = 0
        for name, kind, arr in items:
            arrays[name] = self._led.stage(group, arr, kind=kind,
                                           field=field, name=name)
            nbytes += int(getattr(arr, "nbytes", 0))
        pages = self._pages_of(nbytes)
        entry = _PageEntry(key, arrays, group, nbytes, pages,
                           next(self._tick))
        evict_keys = []
        with self._lock:
            prior = self._entries.get(key)   # benign load race: keep ours
            self._entries[key] = entry
            cap = self.capacity_pages()
            if cap is not None:
                while sum(e.pages
                          for e in self._entries.values()) > cap:
                    victims = [e for e in self._entries.values()
                               if e is not entry]
                    if not victims:
                        break                # one entry over capacity
                    v = min(victims, key=lambda e: e.last_use_tick)
                    del self._entries[v.key]
                    self.evictions += 1
                    self.evicted_pages += v.pages
                    evict_keys.append(v)
            if prefetched:
                self.prefetches += 1
        if prior is not None:
            self._led.close_group(prior.group)
        for v in evict_keys:
            _metrics().counter("device.pager.evictions").inc()
            self._led.close_group(v.group)
        if prefetched:
            _metrics().counter("device.pager.prefetches").inc()
        # seal AFTER the pager's own eviction pass so ledger budget
        # enforcement sees the post-eviction footprint
        self._led.seal(group)
        return arrays

    def _on_ledger_evict(self, key) -> None:
        """The owning ledger's budget enforcement chose this pager group
        as its LRU victim — drop the entry and count it here too."""
        with self._lock:
            e = self._entries.pop(key, None)
            if e is None:
                return
            self.evictions += 1
            self.evicted_pages += e.pages
        _metrics().counter("device.pager.evictions").inc()

    def invalidate(self, key) -> None:
        """Owner teardown (segment merged away / GC'd)."""
        with self._lock:
            e = self._entries.pop(key, None)
        if e is not None:
            self._led.close_group(e.group)

    def stats(self) -> dict:
        with self._lock:
            resident = sum(e.pages for e in self._entries.values())
            resident_bytes = sum(e.nbytes
                                 for e in self._entries.values())
            out = {
                "page_bytes": self.page_bytes,
                "capacity_pages": self.capacity_pages(),
                "resident_pages": resident,
                "resident_entries": len(self._entries),
                "resident_bytes": resident_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "evicted_pages": self.evicted_pages,
                "prefetches": self.prefetches,
            }
        return out

    def reset(self) -> None:
        with self._lock:
            entries = list(self._entries.values())
            self._entries.clear()
            self.hits = self.misses = self.evictions = 0
            self.evicted_pages = self.prefetches = 0
            self.page_bytes = self.DEFAULT_PAGE_BYTES
        for e in entries:
            self._led.close_group(e.group)


def backend_info() -> dict:
    """What jax is actually running on: ``platform``, ``device_kind``
    and ``device_count`` of the default backend.  Initializes the
    backend on first call (and raises if it cannot)."""
    import opensearch_tpu.common.jaxenv  # noqa: F401
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _backend_memory_stats() -> dict:
    """``jax`` device ``memory_stats()`` where the backend provides it
    (TPU/GPU do; CPU returns None) — the allocator's own view next to
    the ledger's."""
    try:
        import jax
        info = backend_info()
        raw = jax.devices()[0].memory_stats()
        if not raw:
            return {"available": False, **info}
        keep = {k: int(v) for k, v in raw.items()
                if isinstance(v, (int, float)) and (
                    "bytes" in k or "allocs" in k)}
        return {"available": True, **info, **keep}
    except Exception:
        return {"available": False}


_ledger = DeviceResidencyLedger()
_registry = KernelCompileRegistry()
_pager = DevicePager(_ledger)


def device_ledger() -> DeviceResidencyLedger:
    return _ledger


def device_pager() -> DevicePager:
    return _pager


def kernel_registry() -> KernelCompileRegistry:
    return _registry
