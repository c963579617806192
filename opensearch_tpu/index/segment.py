"""Immutable, array-oriented index segments — the TPU-native analog of a
Lucene segment.

Where Lucene stores postings as compressed blocks decoded doc-at-a-time
inside ``Weight.bulkScorer`` (ref server/src/main/java/org/opensearch/
search/internal/ContextIndexSearcher.java:318), a TPU segment is a set of
flat device-stageable arrays:

- per indexed field, CSR postings ``[term_offsets, doc_ids, tfs]`` plus a
  positions CSR (for phrase queries) and per-doc field lengths (BM25 norms
  — ref index/similarity/, Lucene BM25Similarity);
- per doc-value field, a multi-valued CSR column (SortedNumericDocValues /
  SortedSetDocValues analog — ref index/fielddata/) with an expanded
  ``value_docs`` row-id array so range masks and aggregations are single
  scatter ops on device, plus dense min/max columns for sorting;
- dense vectors as a ``[n_docs, dim]`` matrix (KnnVectorField analog);
- stored ``_source`` bytes host-side (ref index/mapper/SourceFieldMapper);
- a mutable live-docs bitmap for deletes (Lucene liveDocs analog).

All device arrays are padded to power-of-two sizes so XLA compile caches
are shared across segments of similar size (static shapes; see
/opt/skills/guides/pallas_guide.md on shape bucketing).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field as dc_field
from typing import Optional

import numpy as np

from opensearch_tpu.mapping.mapper import ParsedDocument

# Sentinels for missing values in dense sort columns.
LONG_MISSING_MAX = np.iinfo(np.int64).max
LONG_MISSING_MIN = np.iinfo(np.int64).min


def pad_pow2(n: int, minimum: int = 8) -> int:
    """Next power of two >= max(n, minimum)."""
    m = max(int(n), minimum)
    return 1 << (m - 1).bit_length()


def pad_bucket(n: int, minimum: int = 4096) -> int:
    """Coarse size bucket: ``minimum * 4^k``.  Used for per-query gather
    budgets, where every distinct value is a separate XLA compile, so 4x
    steps (vs pow2) trade a few wasted gather lanes for ~half the
    program count."""
    m = max(int(n), minimum)
    b = int(minimum)
    while b < m:
        b <<= 2
    return b


def per_term_max(values: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """float32 [T]: the largest of each term's run ``offsets[t] :
    offsets[t + 1]`` of a per-posting column, 0 for an empty run."""
    if not len(values):
        return np.zeros(len(offsets) - 1, dtype=np.float32)
    starts = np.minimum(offsets[:-1], len(values) - 1)
    return np.where(np.diff(offsets) > 0,
                    np.maximum.reduceat(values, starts), np.float32(0.0))


@dataclass
class PostingsField:
    """CSR inverted index for one field.

    ``offsets[t]:offsets[t+1]`` is term t's posting range in ``doc_ids`` /
    ``tfs``; ``pos_offsets[p]:pos_offsets[p+1]`` is posting entry p's range
    in ``positions``.  ``doc_lens`` is the per-doc token count (1.0 for
    fields without norms, like Lucene omitNorms keyword fields).
    """

    terms: dict[str, int]            # term -> term id (sorted order)
    df: np.ndarray                   # int32 [T] doc freq
    offsets: np.ndarray              # int32 [T+1]
    doc_ids: np.ndarray              # int32 [P]
    tfs: np.ndarray                  # float32 [P]
    pos_offsets: np.ndarray          # int32 [P+1]
    positions: np.ndarray            # int32 [sum positions]
    doc_lens: np.ndarray             # float32 [n_docs]
    total_len: float                 # sum of doc_lens over docs with field
    docs_with_field: int             # docs with >=1 term (Lucene docCount)
    has_norms: bool
    # docs where the field was present at all — a zero-token text value
    # still writes a "norm entry" (Lucene FieldExistsQuery over norms
    # matches it even though docCount does not count it).
    present: np.ndarray = None       # bool [n_docs]
    # a ``rank_features`` field: ``tfs`` holds each posting's stored
    # feature weight (Lucene FeatureField's 9-bit float), scored as it
    # stands; no norms, no positions (``pos_offsets`` int32 [1],
    # ``positions`` empty), so two columns a posting on the device
    features: bool = False

    def term_id(self, term: str) -> int:
        return self.terms.get(term, -1)

    def max_values(self) -> np.ndarray:
        """float32 [T]: each term's largest value-column entry (a feature
        field's block-max table, what ``impact_table``'s ``max`` is to
        BM25).  Computed once: a segment's postings never change."""
        mx = getattr(self, "_max_values", None)
        if mx is None:
            mx = self._max_values = per_term_max(self.tfs, self.offsets)
        return mx


@dataclass
class NumericDV:
    """Multi-valued numeric doc-value column (SortedNumericDocValues)."""

    kind: str                        # "long" | "double"
    offsets: np.ndarray              # int32 [n_docs+1]
    values: np.ndarray               # int64 | float64 [V], sorted per doc
    value_docs: np.ndarray           # int32 [V] owning doc per value
    minv: np.ndarray                 # dense per-doc min (sentinel if missing)
    maxv: np.ndarray                 # dense per-doc max
    exists: np.ndarray               # bool [n_docs]


@dataclass
class OrdinalDV:
    """Multi-valued ordinal column (SortedSetDocValues analog).  Ordinals
    are per-segment, assigned in sorted term order so ordinal comparisons
    are term-order comparisons."""

    ord_terms: list[str]             # ordinal -> term
    term_to_ord: dict[str, int]
    offsets: np.ndarray              # int32 [n_docs+1]
    ords: np.ndarray                 # int32 [V], sorted per doc
    value_docs: np.ndarray           # int32 [V]
    min_ord: np.ndarray              # int32 [n_docs] (-1 if missing)
    max_ord: np.ndarray              # int32 [n_docs]
    exists: np.ndarray               # bool [n_docs]


@dataclass
class VectorDV:
    values: np.ndarray               # float32 [n_docs, dim]
    exists: np.ndarray               # bool [n_docs]
    dim: int
    similarity: str                  # l2_norm | cosine | dot_product


@dataclass
class NestedBlock:
    """One nested path's objects, stored OBJECT-major: columns key by
    object id, ``obj_to_doc`` maps objects back to parents (the TPU
    formulation of Lucene's adjacent nested documents — ref
    index/mapper/ nested handling, join/ToParentBlockJoinQuery)."""

    obj_to_doc: np.ndarray               # int32 [n_obj]
    # child full path -> (values f64 [V], value_objs i32 [V])
    numeric: dict[str, tuple] = dc_field(default_factory=dict)
    # child full path -> (ord_terms list, ords i32 [V], value_objs i32)
    ordinal: dict[str, tuple] = dc_field(default_factory=dict)

    @property
    def n_objs(self) -> int:
        return len(self.obj_to_doc)


@dataclass
class GeoDV:
    offsets: np.ndarray              # int32 [n_docs+1]
    lats: np.ndarray                 # float32 [V]
    lons: np.ndarray                 # float32 [V]
    value_docs: np.ndarray           # int32 [V]
    exists: np.ndarray               # bool [n_docs]


class Segment:
    """One immutable segment.  Mutable pieces: ``live`` (deletes) only."""

    def __init__(self, seg_id: str, n_docs: int):
        self.seg_id = seg_id
        self.n_docs = n_docs
        self.doc_ids: list[str] = []
        self.id_to_local: dict[str, int] = {}
        self.sources: list[bytes] = []
        self.seq_nos = np.zeros(n_docs, dtype=np.int64)
        self.versions = np.ones(n_docs, dtype=np.int64)
        # local -> custom routing value (only docs indexed with one; the
        # reference stores _routing as a stored field)
        self.routings: dict[int, str] = {}
        # completion field -> {(local, input): weight} — per-INPUT
        # suggestion weights (CompletionFieldMapper stores weight per
        # entry in the FST)
        self.completion_weights: dict[str, dict] = {}
        self.postings: dict[str, PostingsField] = {}
        self.numeric_dv: dict[str, NumericDV] = {}
        self.ordinal_dv: dict[str, OrdinalDV] = {}
        self.vector_dv: dict[str, VectorDV] = {}
        self.geo_dv: dict[str, GeoDV] = {}
        self.nested: dict[str, NestedBlock] = {}
        self.live = np.ones(n_docs, dtype=bool)
        self._device: Optional["DeviceSegment"] = None
        # set True when the device-memory budget unstaged this segment
        # (common/device_ledger.py): scored term-bags then score on the
        # host impact tables byte-identically; anything else restages
        # on demand (counted in device.restages)
        self._device_evicted = False
        # ledger-owner attribution, tagged by the owning engine when a
        # searcher is acquired (bench/tests may leave the defaults)
        self.index_name = "-"
        self.shard_id = 0
        # trained ANN structures, lazily built per (field, method) — the
        # segment is immutable so one training pass serves every query
        # (the k-NN plugin trains at graph-build/flush time; ref
        # plugins/SearchPlugin.java:151 SPI)
        self._ann: dict[tuple, object] = {}

    def ann_index(self, field: str, method: dict):
        """Build-or-fetch the trained IVF/IVF-PQ structure for ``field``.

        Keyed by the method signature so a changed mapping retrains; the
        padded cluster-major layout is what the device search kernels
        consume (ops/ivf.py)."""
        from opensearch_tpu.ops.ivf import IvfIndex, IvfPqIndex

        dv = self.vector_dv.get(field)
        if dv is None or not dv.exists.any():
            return None
        name = method.get("name", "ivf")
        # default nlist ~ sqrt(n) (FAISS guidance), clamped to >=1
        nlist = int(method.get("nlist")
                    or max(1, int(np.sqrt(max(int(dv.exists.sum()), 1)))))
        m = int(method.get("m", 8))
        key = (field, name, nlist, m)
        idx = self._ann.get(key)
        if idx is None:
            if name == "ivf_pq":
                idx = IvfPqIndex.build(dv.values, dv.exists, nlist, m=m)
            else:
                idx = IvfIndex.build(dv.values, dv.exists, nlist)
            self._ann[key] = idx
        return idx

    # -- stats used for cross-segment collection statistics ---------------

    def live_count(self) -> int:
        return int(self.live.sum())

    def delete_local(self, local_id: int):
        self.apply_deletes([local_id])

    def apply_deletes(self, local_ids):
        """Copy-on-write: searchers that snapshotted the previous ``live``
        array keep their point-in-time view (Lucene reader semantics)."""
        live = self.live.copy()
        live[np.asarray(local_ids, dtype=np.int64)] = False
        self.live = live

    def source(self, local_id: int) -> dict:
        return json.loads(self.sources[local_id])

    def impact_table(self, field: str, avgdl: float,
                     k1: float = 1.2, b: float = 0.75):
        """Host-side per-posting BM25 impacts + per-term BLOCK-MAX
        metadata for ``field``, as ``(impacts f32 [P], max f32 [T])``.

        ``impacts[p] = tf/(tf + k1*(1-b + b*dl/avgdl))`` — the eager
        BM25S precompute; the float32 operation order matches
        ``ops/bm25.py::compute_impacts`` bit-for-bit so the host and
        device scoring paths produce identical scores.  ``max[t]`` is
        the segment-block maximum per term (the BMW/MaxScore
        upper-bound table of the reference's ``ImpactsEnum``, ref
        org.apache.lucene.index.Impacts), consumed by
        ``plan.max_score_bound`` to skip segments that provably cannot
        beat a min_score / running top-k threshold.

        Keyed by (field, avgdl): a refresh/merge changes the shard
        avgdl through the reader-generation bump, so stale tables stop
        being requested and LRU out."""
        pf = self.postings.get(field)
        if pf is None:
            return None
        from opensearch_tpu.common.cache import attached_cache
        cache = attached_cache(self, "_impact_table_cache",
                               name="segment.impact_table",
                               max_weight=256 << 20, breaker="fielddata")
        key = (field, float(np.float32(avgdl)), k1, b)
        out = cache.get(key)
        if out is None:
            imp = np.zeros(0, dtype=np.float32)
            if len(pf.tfs):
                dl = pf.doc_lens[pf.doc_ids]
                norm = np.float32(k1) * (np.float32(1.0 - b)
                                         + np.float32(b) * dl
                                         / np.float32(avgdl))
                imp = (pf.tfs / (pf.tfs + norm)).astype(np.float32)
            out = (imp, per_term_max(imp, pf.offsets))
            cache.put(key, out)
        return out

    def max_impacts(self, field: str, avgdl: float,
                    k1: float = 1.2, b: float = 0.75):
        """Per-term block-max impacts (see ``impact_table``)."""
        table = self.impact_table(field, avgdl, k1, b)
        return None if table is None else table[1]

    def quantized_table(self, field: str, avgdl: float):
        """Quantized + bit-packed tables for ``field`` at this avgdl
        (``index/codec.py``), host-side and cached like
        ``impact_table``.  When ``self.quant_dir`` is set (the store
        attaches it on load), the persisted ``.quant`` sidecar is tried
        first — a CRC mismatch degrades to recompute-and-rewrite, never
        a failed search — and fresh builds are written back so the next
        process skips the quantization pass."""
        pf = self.postings.get(field)
        if pf is None:
            return None
        from opensearch_tpu.common.cache import attached_cache
        cache = attached_cache(self, "_quant_table_cache",
                               name="segment.quantized_table",
                               max_weight=256 << 20,
                               breaker="fielddata")
        key = (field, float(np.float32(avgdl)))
        qt = cache.get(key)
        if qt is None:
            from opensearch_tpu.index import codec as codec_mod
            qdir = getattr(self, "quant_dir", None)
            if qdir is not None:
                from opensearch_tpu.index import store as store_mod
                try:
                    qt = store_mod.load_quantized_tables(
                        qdir, self.seg_id, field, avgdl=key[1])
                except store_mod.CorruptIndexError:
                    qt = None       # degrade: recompute + rewrite
            if qt is None:
                imp, mx = self.impact_table(field, avgdl)
                qt = codec_mod.quantize_postings(pf, imp, mx, avgdl)
                if qdir is not None:
                    try:
                        store_mod.save_quantized_tables(
                            qdir, self.seg_id, field, qt)
                    except OSError:
                        pass        # sidecar is a cache, not a commit
            qt._offsets = pf.offsets
            cache.put(key, qt)
        return qt

    def device(self) -> "DeviceSegment":
        if self._device is None:
            was_evicted = self._device_evicted
            try:
                self._device = DeviceSegment(self)
            except Exception as exc:
                from opensearch_tpu.common.device_health import (
                    device_health, is_device_error)
                if not is_device_error(exc):
                    raise
                # staging failed (device OOM et al.): the segment is
                # treated as budget-evicted — scored term-bags take the
                # byte-identical host impact-table fallback instead of
                # failing the search; plans that truly need the device
                # degrade via their own dispatch-site handlers
                self._device = None
                self._device_evicted = True
                from opensearch_tpu.common.telemetry import metrics
                metrics().counter("device.restage_failures").inc()
                device_health().record_failure("staging", exc)
                raise
            if was_evicted:
                # demand paging's fault path: a budget-evicted segment
                # was staged again (a plan without a host fallback
                # needed the device arrays back)
                from opensearch_tpu.common.device_ledger import \
                    device_ledger
                device_ledger().record_restage()
                self._device_evicted = False
            from opensearch_tpu.common.device_health import device_health
            device_health().record_success("staging")
        return self._device


class DeviceSegment:
    """jnp-staged view of a Segment, padded to power-of-two shapes.

    Padding scheme: ``n_pad >= n_docs + 1`` so slot ``n_docs`` is a dead
    scatter target for padded postings/value entries; ``live`` is False on
    all padding slots so they can never reach the top-k.
    """

    def __init__(self, seg: Segment):
        import opensearch_tpu.common.jaxenv  # noqa: F401

        self.seg = seg
        self.n_docs = seg.n_docs
        self.n_pad = pad_pow2(seg.n_docs + 1)
        n_pad = self.n_pad
        # HBM budget: the breaker estimate comes from the ONE footprint
        # source of truth (device_ledger.host_footprint; padding roughly
        # doubles worst-case, x2 covers it), charged BEFORE any device
        # allocation — an oversized staging is rejected as 429, not an
        # OOM (FileCache/fielddata-breaker analog)
        from opensearch_tpu.common.breakers import breaker_service
        from opensearch_tpu.common.device_ledger import (device_ledger,
                                                         host_footprint)
        self._breaker_bytes = host_footprint(seg) * 2
        breaker = breaker_service().fielddata
        breaker.add_estimate(self._breaker_bytes,
                             label=f"segment [{seg.seg_id}] staging")
        import weakref
        # idempotent release handle: fires on GC, or EARLY when the
        # device-memory budget unstages this segment (finalize runs once)
        self._breaker_fin = weakref.finalize(self, breaker.release,
                                             self._breaker_bytes)
        # residency ledger: every staged array below is recorded under
        # this group (owner = index/shard/segment); the evict callback
        # is how `device.memory.budget_bytes` unstages us — the Segment
        # flips to its host fallback and the breaker charge releases
        led = self._ledger = device_ledger()
        seg_ref = weakref.ref(seg)
        dseg_ref = weakref.ref(self)

        def _unstage():
            s = seg_ref()
            d = dseg_ref()
            if s is not None and (d is None or s._device is d):
                s._device = None
                s._device_evicted = True
            if d is not None:
                d._breaker_fin()

        group = self._ledger_group = led.open_group(
            index=getattr(seg, "index_name", "-"),
            shard=getattr(seg, "shard_id", 0),
            segment=seg.seg_id, evict=_unstage)
        led.tether(self, group)

        def pad1(a: np.ndarray, size: int, fill) -> np.ndarray:
            out = np.full(size, fill, dtype=a.dtype)
            out[: len(a)] = a
            return out

        def stage(arr, kind, field, name):
            return led.stage(group, arr, kind=kind, field=field,
                             name=name)

        # Lowering decision (index/codec.py): quantized segments stage
        # only offsets/doc_lens/field_exists eagerly — the heavy
        # per-posting columns either flow through the pager in
        # compressed form (scored term-bags) or stage lazily on first
        # demand (``ensure_postings``, for phrase/span/filter plans the
        # quantized kernels don't cover).
        from opensearch_tpu.index import codec as codec_mod
        self.quantized_mode = codec_mod.use_quantized(seg)
        self.postings: dict[str, dict] = {}
        for name, pf in seg.postings.items():
            # offsets padded by repeating the final cumulative value so
            # padded term ids decode as empty ranges and the array shape
            # stays bucketed (compile-cache sharing across segments).
            t_pad = pad_pow2(len(pf.offsets))
            self.postings[name] = {
                "offsets": stage(pad1(pf.offsets, t_pad, pf.offsets[-1]),
                                 "postings", name, "offsets"),
                "doc_lens": stage(pad1(pf.doc_lens, n_pad, 1.0),
                                  "postings", name, "doc_lens"),
                "field_exists": stage(pad1(pf.present, n_pad, False),
                                      "postings", name, "field_exists"),
            }
            if not self.quantized_mode:
                self.ensure_postings(name)
        self.numeric: dict[str, dict] = {}
        for name, dv in seg.numeric_dv.items():
            v_pad = pad_pow2(len(dv.values))
            vals = dv.values
            self.numeric[name] = {
                "values": stage(pad1(vals, v_pad, 0),
                                "numeric", name, "values"),
                "value_docs": stage(
                    pad1(dv.value_docs, v_pad, self.n_docs),
                    "numeric", name, "value_docs"),
                "minv": stage(
                    pad1(dv.minv, n_pad,
                         LONG_MISSING_MAX if dv.kind == "long"
                         else np.inf),
                    "numeric", name, "minv"),
                "maxv": stage(
                    pad1(dv.maxv, n_pad,
                         LONG_MISSING_MIN if dv.kind == "long"
                         else -np.inf),
                    "numeric", name, "maxv"),
                "exists": stage(pad1(dv.exists, n_pad, False),
                                "numeric", name, "exists"),
            }
        self.ordinal: dict[str, dict] = {}
        for name, dv in seg.ordinal_dv.items():
            v_pad = pad_pow2(len(dv.ords))
            self.ordinal[name] = {
                "ords": stage(pad1(dv.ords, v_pad, -1),
                              "ordinal", name, "ords"),
                "value_docs": stage(
                    pad1(dv.value_docs, v_pad, self.n_docs),
                    "ordinal", name, "value_docs"),
                "min_ord": stage(pad1(dv.min_ord, n_pad, -1),
                                 "ordinal", name, "min_ord"),
                "max_ord": stage(pad1(dv.max_ord, n_pad, -1),
                                 "ordinal", name, "max_ord"),
                "exists": stage(pad1(dv.exists, n_pad, False),
                                "ordinal", name, "exists"),
                "n_ords": len(dv.ord_terms),
            }
        self.vector: dict[str, dict] = {}
        for name, dv in seg.vector_dv.items():
            vals = np.zeros((n_pad, dv.dim), dtype=np.float32)
            vals[: len(dv.values)] = dv.values
            self.vector[name] = {
                "values": stage(vals, "vector", name, "values"),
                "exists": stage(pad1(dv.exists, n_pad, False),
                                "vector", name, "exists"),
            }
        self.geo: dict[str, dict] = {}
        for name, dv in seg.geo_dv.items():
            v_pad = pad_pow2(len(dv.lats))
            self.geo[name] = {
                "lats": stage(pad1(dv.lats, v_pad, 0.0),
                              "geo", name, "lats"),
                "lons": stage(pad1(dv.lons, v_pad, 0.0),
                              "geo", name, "lons"),
                "value_docs": stage(
                    pad1(dv.value_docs, v_pad, self.n_docs),
                    "geo", name, "value_docs"),
                "exists": stage(pad1(dv.exists, n_pad, False),
                                "geo", name, "exists"),
            }
        # bounded-cache: one staged copy per live-bitmap version, freed
        self._live_cache: dict[int, object] = {}  # with its PIT searcher
        self._ann_staged: dict[int, tuple] = {}
        self.live = self.live_jnp(seg.live)
        # fully staged: from here on the group is a budget-eviction
        # candidate (lazily staged impacts/live/nested entries keep
        # accruing into it)
        led.seal(group)

    def ensure_postings(self, field: str) -> Optional[dict]:
        """Full per-posting device arrays (doc_ids/tfs/positions) for
        ``field``, staged on demand.

        On quantized segments these are skipped at construction — that
        skip IS the footprint win — but plans outside the quantized
        lowering (phrase, span, filter-context term bags, the batched
        union kernel) still need them; they stage here on first use and
        join the segment's ledger group like any eager array."""
        p = self.postings.get(field)
        if p is None or "doc_ids" in p:
            return p
        pf = self.seg.postings[field]
        p_pad = pad_pow2(len(pf.doc_ids))
        pos_pad = pad_pow2(len(pf.positions))
        led = self._ledger
        group = self._ledger_group

        def pad1(a: np.ndarray, size: int, fill) -> np.ndarray:
            out = np.full(size, fill, dtype=a.dtype)
            out[: len(a)] = a
            return out

        def stage(arr, name):
            return led.stage(group, arr, kind="postings", field=field,
                             name=name)

        p["doc_ids"] = stage(pad1(pf.doc_ids, p_pad, self.n_docs),
                             "doc_ids")
        p["tfs"] = stage(pad1(pf.tfs, p_pad, 0.0), "tfs")
        # positions CSR for phrase matching (pos_offsets is per posting
        # entry, so a term's positions are one contiguous slice of
        # ``positions``).
        p["pos_offsets"] = stage(
            pad1(pf.pos_offsets, pad_pow2(len(pf.pos_offsets)),
                 pf.pos_offsets[-1] if len(pf.pos_offsets) else 0),
            "pos_offsets")
        p["positions"] = stage(pad1(pf.positions, pos_pad, 0),
                               "positions")
        if self.quantized_mode:
            # diagnostic: how often the compressed layout had to pull
            # the full f32 arrays in anyway (plan mix dependent)
            from opensearch_tpu.common.telemetry import metrics
            metrics().counter("device.quantized.full_postings").inc()
        return p

    def quantized(self, field: str, avgdl: float):
        """Quantized device arrays for ``field`` (index/codec.py),
        staged through the device pager under the page budget.

        Returns the staged dict (qvals/scales/exact_vals/exact_offsets/
        packed/base) or None if the field has no postings.  Pager
        entries are keyed by (index, shard, segment, field, avgdl) and
        deliberately OUTLIVE this DeviceSegment: a budget eviction of
        the segment group doesn't drop the compressed pages, so the
        restage path only re-stages the cheap eager arrays."""
        if self.postings.get(field) is None:
            return None
        seg = self.seg
        key = _quant_key(seg, field, avgdl)
        from opensearch_tpu.common.device_ledger import device_pager
        _register_pager_invalidation(seg, key)
        return device_pager().acquire(
            key, lambda: _quant_items(seg, field, avgdl),
            index=getattr(seg, "index_name", "-"),
            shard=getattr(seg, "shard_id", 0),
            segment=seg.seg_id)

    def impacts(self, field: str, avgdl: float):
        """Staged per-posting BM25 impact column for ``field``, indexed
        exactly like ``postings[field]["tfs"]`` (padded slots are 0).

        Staged from the HOST impact table (``Segment.impact_table``) so
        the device scoring path and its host recovery (``host_topk``) read
        bit-identical impacts, and cached per (field, avgdl).  avgdl is
        the only query-time input: a refresh/merge that changes it does
        so through the reader-generation bump (new searcher, new
        ShardContext stats), so the old keys stop being requested and
        LRU out — staleness is structurally impossible."""
        p = self.postings.get(field)
        from opensearch_tpu.common.cache import attached_cache
        cache = attached_cache(self, "_impact_cache",
                               name="segment.impacts",
                               max_weight=256 << 20, breaker="fielddata")
        key = (field, float(np.float32(avgdl)))
        imp = cache.get(key)
        if imp is None:
            import jax.numpy as jnp
            if p is None:
                imp = jnp.zeros(8, jnp.float32)
            else:
                host_imp, _mx = self.seg.impact_table(field, avgdl)
                # padded like doc_ids/tfs even when those are lazily
                # staged (quantized segments): same bucketed shape
                p_pad = pad_pow2(len(self.seg.postings[field].doc_ids))
                padded = np.zeros(p_pad, np.float32)
                padded[: len(host_imp)] = host_imp
                imp = self._ledger.stage(       # quantize-ok
                    self._ledger_group, padded, kind="impacts",
                    field=field, name=f"avgdl={key[1]:.6g}")
            cache.put(key, imp)
        return imp

    def nested_staged(self, path: str) -> Optional[dict]:
        """Padded device arrays for one nested block (lazy, cached)."""
        cache = getattr(self, "_nested_cache", None)
        if cache is None:
            # bounded-cache: at most one entry per nested mapping path
            cache = self._nested_cache = {}
        if path in cache:
            return cache[path]
        block = self.seg.nested.get(path)
        if block is None or block.n_objs == 0:
            cache[path] = None
            return None

        def pad1(a, size, fill, name=""):
            out = np.full(size, fill, dtype=a.dtype)
            out[: len(a)] = a
            return self._ledger.stage(self._ledger_group, out,
                                      kind="nested", field=path,
                                      name=name)

        n_obj_pad = pad_pow2(block.n_objs + 1)
        staged = {
            "n_obj_pad": n_obj_pad,
            # padding objects belong to the parent dead slot
            "obj_to_doc": pad1(block.obj_to_doc, n_obj_pad,
                               self.n_pad - 1, "obj_to_doc"),
            "obj_valid": pad1(np.ones(block.n_objs, bool), n_obj_pad,
                              False, "obj_valid"),
            "numeric": {}, "ordinal": {},
        }
        for f, (values, value_objs) in block.numeric.items():
            v_pad = pad_pow2(len(values))
            staged["numeric"][f] = {
                "values": pad1(values, v_pad, 0.0, f"{f}/values"),
                "value_objs": pad1(value_objs, v_pad, n_obj_pad - 1,
                                   f"{f}/value_objs"),
                "v_pad": v_pad,
            }
        for f, (ord_terms, ords, value_objs) in block.ordinal.items():
            v_pad = pad_pow2(len(ords))
            staged["ordinal"][f] = {
                "ords": pad1(ords, v_pad, -1, f"{f}/ords"),
                "value_objs": pad1(value_objs, v_pad, n_obj_pad - 1,
                                   f"{f}/value_objs"),
                "v_pad": v_pad,
            }
        cache[path] = staged
        return staged

    def ann_staged(self, idx) -> tuple:
        """Device-staged arrays for a trained ANN index (strong-keyed by
        the host object so a retrain restages)."""
        key = id(idx)
        cached = self._ann_staged.get(key)
        if cached is None or cached[0] is not idx:
            cached = (idx, idx.device())
            if len(self._ann_staged) >= 4:
                old = next(iter(self._ann_staged))
                self._ann_staged.pop(old)
                self._ledger.drop(self._ledger_group, kind="ann",
                                  name=str(old))
            self._ann_staged[key] = cached
            # ANN builders stage their own arrays (ops/ivf.py); the
            # ledger adopts the accounting so residency stays exact
            self._ledger.adopt(self._ledger_group, cached[1],
                               kind="ann", name=str(key))
        return cached[1]

    def live_jnp(self, live_np: np.ndarray):
        """Staged live mask for a SNAPSHOT of the live bitmap (keyed by
        array identity — apply_deletes replaces the array, so old
        snapshots keep resolving to their own staged copy).  The cache
        holds a strong reference to the keyed numpy array: id() keys are
        only valid while the object is alive."""
        key = id(live_np)
        cached = self._live_cache.get(key)
        if cached is None or cached[0] is not live_np:
            padded = np.zeros(self.n_pad, dtype=bool)
            padded[: len(live_np)] = live_np
            cached = (live_np,
                      self._ledger.stage(self._ledger_group, padded,
                                         kind="live", name=str(key)))
            if len(self._live_cache) >= 4:
                old = next(iter(self._live_cache))
                self._live_cache.pop(old)
                self._ledger.drop(self._ledger_group, kind="live",
                                  name=str(old))
            self._live_cache[key] = cached
        return cached[1]


def _quant_key(seg: Segment, field: str, avgdl: float) -> tuple:
    """Pager key for one quantized table set — stable across
    DeviceSegment restages so compressed pages survive segment-group
    eviction."""
    return (getattr(seg, "index_name", "-"),
            getattr(seg, "shard_id", 0),
            seg.seg_id, field, float(np.float32(avgdl)))


def _quant_items(seg: Segment, field: str, avgdl: float) -> list:
    """Pager loader: one quantized table set as padded host arrays,
    shape-bucketed exactly like the eager staging so XLA programs are
    shared across same-bucket segments."""
    qt = seg.quantized_table(field, avgdl)
    pf = seg.postings[field]
    t_pad = pad_pow2(len(pf.offsets))

    def pad1(a: np.ndarray, size: int, fill) -> np.ndarray:
        out = np.full(size, fill, dtype=a.dtype)
        out[: len(a)] = a
        return out

    return [
        ("qvals", "impacts_q",
         pad1(qt.qvals, pad_pow2(len(qt.qvals)), 0)),
        # padded term slots are inactive in every gather; scale 1 keeps
        # a stray read finite
        ("scales", "impacts_q", pad1(qt.scales, t_pad, 1.0)),
        ("exact_vals", "impacts_q",
         pad1(qt.exact_vals, pad_pow2(len(qt.exact_vals)), 0.0)),
        ("exact_offsets", "impacts_q",
         pad1(qt.exact_offsets, t_pad,
              qt.exact_offsets[-1] if len(qt.exact_offsets) else 0)),
        # packed keeps its own guard word; zero padding beyond it is
        # never addressed (w+1 <= word count of the real payload)
        ("packed", "postings_q",
         pad1(qt.packed, pad_pow2(len(qt.packed)), 0)),
        ("base", "postings_q", pad1(qt.base, t_pad, 0)),
    ]


def _pager_invalidate(key: tuple) -> None:
    from opensearch_tpu.common.device_ledger import device_pager
    device_pager().invalidate(key)


def _register_pager_invalidation(seg: Segment, key: tuple) -> None:
    """One finalizer per (segment, pager key): a merged-away/GC'd
    segment drops its compressed pages instead of squatting in the
    budget until LRU."""
    import weakref
    reg = getattr(seg, "_quant_pager_keys", None)
    if reg is None:
        reg = seg._quant_pager_keys = set()
    if key not in reg:
        reg.add(key)
        weakref.finalize(seg, _pager_invalidate, key)


def prefetch_quantized(seg: Segment, field: str, avgdl: float) -> bool:
    """Prefetch-oracle entry point: stage a segment's quantized tables
    into FREE pager pages ahead of the dispatch loop (never evicts —
    see ``DevicePager.prefetch``).  The footprint hint is an estimate
    so a skipped prefetch costs no quantization work."""
    pf = seg.postings.get(field)
    if pf is None:
        return False
    key = _quant_key(seg, field, avgdl)
    # ~1B/posting quantized impacts + <=4B/posting packed ids + per-term
    # scale/base/offset columns; close enough for page-granular fit
    hint = (len(pf.doc_ids) * 5
            + len(pf.offsets) * 12 + 4096)
    from opensearch_tpu.common.device_ledger import device_pager
    _register_pager_invalidation(seg, key)
    return device_pager().prefetch(
        key, lambda: _quant_items(seg, field, avgdl), hint,
        index=getattr(seg, "index_name", "-"),
        shard=getattr(seg, "shard_id", 0),
        segment=seg.seg_id)


class SegmentWriter:
    """Builds an immutable Segment from a batch of ParsedDocuments — the
    invert step Lucene does inside IndexWriter.addDocuments (ref
    index/engine/InternalEngine.java:1186), done columnar in one pass."""

    def build(self, docs: list[ParsedDocument], seg_id: str,
              norms_fields: Optional[dict[str, bool]] = None,
              vector_meta: Optional[dict[str, dict]] = None) -> Segment:
        n = len(docs)
        seg = Segment(seg_id, n)
        norms_fields = norms_fields or {}
        vector_meta = vector_meta or {}

        # term -> list index accumulation per field
        inv: dict[str, dict[str, list[tuple[int, int, list[int]]]]] = {}
        field_doc_lens: dict[str, np.ndarray] = {}
        longs: dict[str, list[list[int]]] = {}
        doubles: dict[str, list[list[float]]] = {}
        ordinals: dict[str, list[list[str]]] = {}
        vectors: dict[str, dict[int, list[float]]] = {}
        geos: dict[str, list[list[tuple[float, float]]]] = {}
        # rank_features field -> feature -> [(doc, stored weight)]
        feature_inv: dict[str, dict[str, list[tuple[int, float]]]] = {}

        for i, doc in enumerate(docs):
            seg.doc_ids.append(doc.doc_id)
            seg.id_to_local[doc.doc_id] = i
            seg.sources.append(json.dumps(doc.source, separators=(",", ":")).encode())
            seg.seq_nos[i] = doc.seq_no
            seg.versions[i] = doc.version
            if doc.routing is not None:
                seg.routings[i] = doc.routing
            for cfield, entries in doc.completions.items():
                wmap = seg.completion_weights.setdefault(cfield, {})
                for text, weight in entries:
                    key = (i, text)
                    # an explicit weight of 0 must round-trip (it ranks
                    # LAST, not as the implicit 1)
                    if key not in wmap or weight > wmap[key]:
                        wmap[key] = weight
            for fname, toks in doc.tokens.items():
                per_term: dict[str, tuple[int, list[int]]] = {}
                for term, pos in toks:
                    if term in per_term:
                        tf, plist = per_term[term]
                        per_term[term] = (tf + 1, plist)
                        plist.append(pos)
                    else:
                        per_term[term] = (1, [pos])
                finv = inv.setdefault(fname, {})
                for term, (tf, plist) in per_term.items():
                    finv.setdefault(term, []).append((i, tf, plist))
            # each per-field column is built once, on the field's first
            # doc (setdefault would evaluate its O(n) default per doc)
            for fname, length in doc.field_lengths.items():
                if fname not in field_doc_lens:
                    field_doc_lens[fname] = np.zeros(n, dtype=np.float32)
                field_doc_lens[fname][i] = length
            for column, per_field in ((longs, doc.longs),
                                      (doubles, doc.doubles),
                                      (ordinals, doc.ordinals),
                                      (geos, doc.geo_points)):
                for fname, vals in per_field.items():
                    if fname not in column:
                        column[fname] = [[] for _ in range(n)]
                    column[fname][i].extend(vals)
            for fname, vec in doc.vectors.items():
                vectors.setdefault(fname, {})[i] = vec
            for fname, feats in doc.features.items():
                finv = feature_inv.setdefault(fname, {})
                for feature, weight in feats.items():
                    finv.setdefault(feature, []).append((i, weight))

        field_present: dict[str, np.ndarray] = {}
        for i, doc in enumerate(docs):
            for fname in doc.field_lengths:
                if fname not in field_present:
                    field_present[fname] = np.zeros(n, dtype=bool)
                field_present[fname][i] = True

        for fname in set(inv) | set(field_present):
            seg.postings[fname] = self._build_postings(
                fname, inv.get(fname, {}), n, field_doc_lens.get(fname),
                has_norms=norms_fields.get(fname, fname in field_doc_lens),
                present=field_present.get(fname))

        for fname, finv in feature_inv.items():
            seg.postings[fname] = self._build_features(finv, n)

        for fname, per_doc in longs.items():
            seg.numeric_dv[fname] = self._build_numeric(per_doc, n, "long")
        for fname, per_doc in doubles.items():
            seg.numeric_dv[fname] = self._build_numeric(per_doc, n, "double")
        for fname, per_doc in ordinals.items():
            seg.ordinal_dv[fname] = self._build_ordinal(per_doc, n)
        for fname, per_doc in vectors.items():
            meta = vector_meta.get(fname, {})
            dim = meta.get("dims") or len(next(iter(per_doc.values())))
            vals = np.zeros((n, dim), dtype=np.float32)
            exists = np.zeros(n, dtype=bool)
            for i, vec in per_doc.items():
                vals[i] = np.asarray(vec, dtype=np.float32)
                exists[i] = True
            seg.vector_dv[fname] = VectorDV(
                values=vals, exists=exists, dim=dim,
                similarity=meta.get("similarity", "l2_norm"))
        for fname, per_doc in geos.items():
            seg.geo_dv[fname] = self._build_geo(per_doc, n)
        self._build_nested(docs, seg)
        return seg

    @staticmethod
    def _build_nested(docs: list[ParsedDocument], seg: Segment):
        """Object-major nested blocks: objects append in doc order, child
        columns key by object id (see NestedBlock)."""
        paths = sorted({p for d in docs for p in d.nested})
        for path in paths:
            obj_to_doc: list[int] = []
            num_cols: dict[str, tuple[list, list]] = {}
            ord_raw: dict[str, tuple[list, list]] = {}   # terms, objs
            for i, doc in enumerate(docs):
                for obj in doc.nested.get(path, []):
                    oid = len(obj_to_doc)
                    obj_to_doc.append(i)
                    for child, (kind, values) in obj.items():
                        if kind == "num":
                            vals, objs = num_cols.setdefault(child,
                                                             ([], []))
                        else:
                            vals, objs = ord_raw.setdefault(child,
                                                            ([], []))
                        for v in values:
                            vals.append(v)
                            objs.append(oid)
            if not obj_to_doc:
                continue
            block = NestedBlock(
                obj_to_doc=np.asarray(obj_to_doc, np.int32))
            for child, (vals, objs) in num_cols.items():
                block.numeric[child] = (
                    np.asarray(vals, np.float64),
                    np.asarray(objs, np.int32))
            for child, (terms, objs) in ord_raw.items():
                ord_terms = sorted(set(terms))
                term_to_ord = {t: o for o, t in enumerate(ord_terms)}
                block.ordinal[child] = (
                    ord_terms,
                    np.asarray([term_to_ord[t] for t in terms],
                               np.int32),
                    np.asarray(objs, np.int32))
            seg.nested[path] = block

    @staticmethod
    def _build_postings(fname, finv, n_docs, doc_lens, has_norms,
                        present=None) -> PostingsField:
        terms_sorted = sorted(finv)
        term_ids = {t: i for i, t in enumerate(terms_sorted)}
        T = len(terms_sorted)
        df = np.zeros(T, dtype=np.int32)
        offsets = np.zeros(T + 1, dtype=np.int32)
        has_terms = np.zeros(n_docs, dtype=bool)
        doc_list, tf_list, pos_off, pos_all = [], [], [0], []
        for t_idx, term in enumerate(terms_sorted):
            entries = finv[term]  # already ascending doc id (insert order)
            df[t_idx] = len(entries)
            for d, tf, plist in entries:
                doc_list.append(d)
                tf_list.append(tf)
                pos_all.extend(plist)
                pos_off.append(len(pos_all))
                has_terms[d] = True
            offsets[t_idx + 1] = len(doc_list)
        if doc_lens is None:
            doc_lens = np.ones(n_docs, dtype=np.float32)
        docs_with = int((doc_lens > 0).sum()) if has_norms else n_docs
        if not has_norms:
            doc_lens = np.ones(n_docs, dtype=np.float32)
        if present is None:
            present = has_terms
        return PostingsField(
            terms=term_ids, df=df, offsets=offsets,
            doc_ids=np.asarray(doc_list, dtype=np.int32),
            tfs=np.asarray(tf_list, dtype=np.float32),
            pos_offsets=np.asarray(pos_off, dtype=np.int32),
            positions=np.asarray(pos_all, dtype=np.int32),
            doc_lens=doc_lens.astype(np.float32),
            total_len=float(doc_lens[doc_lens > 0].sum()) if has_norms else float(n_docs),
            docs_with_field=docs_with, has_norms=has_norms,
            present=present)

    @staticmethod
    def _build_features(finv, n_docs: int) -> PostingsField:
        """A ``rank_features`` field's postings: terms are the features in
        sorted order, ``tfs`` the stored weights (see ``PostingsField.
        features``)."""
        terms_sorted = sorted(finv)
        df = np.asarray([len(finv[t]) for t in terms_sorted], dtype=np.int32)
        offsets = np.zeros(len(terms_sorted) + 1, dtype=np.int32)
        np.cumsum(df, out=offsets[1:])
        entries = [e for t in terms_sorted for e in finv[t]]
        doc_ids = np.asarray([d for d, _w in entries], dtype=np.int32)
        present = np.zeros(n_docs, dtype=bool)
        present[doc_ids] = True
        return PostingsField(
            terms={t: i for i, t in enumerate(terms_sorted)}, df=df,
            offsets=offsets, doc_ids=doc_ids,
            tfs=np.asarray([w for _d, w in entries], dtype=np.float32),
            pos_offsets=np.zeros(1, dtype=np.int32),
            positions=np.zeros(0, dtype=np.int32),
            doc_lens=np.ones(n_docs, dtype=np.float32),
            total_len=float(n_docs), docs_with_field=int(present.sum()),
            has_norms=False, present=present, features=True)

    @staticmethod
    def _build_numeric(per_doc: list[list], n_docs: int, kind: str) -> NumericDV:
        dtype = np.int64 if kind == "long" else np.float64
        miss_min = LONG_MISSING_MAX if kind == "long" else np.inf
        miss_max = LONG_MISSING_MIN if kind == "long" else -np.inf
        offsets = np.zeros(n_docs + 1, dtype=np.int32)
        values, value_docs = [], []
        minv = np.full(n_docs, miss_min, dtype=dtype)
        maxv = np.full(n_docs, miss_max, dtype=dtype)
        exists = np.zeros(n_docs, dtype=bool)
        for i, vals in enumerate(per_doc):
            vals = sorted(vals)
            values.extend(vals)
            value_docs.extend([i] * len(vals))
            offsets[i + 1] = len(values)
            if vals:
                minv[i], maxv[i] = vals[0], vals[-1]
                exists[i] = True
        return NumericDV(kind=kind, offsets=offsets,
                         values=np.asarray(values, dtype=dtype),
                         value_docs=np.asarray(value_docs, dtype=np.int32),
                         minv=minv, maxv=maxv, exists=exists)

    @staticmethod
    def _build_ordinal(per_doc: list[list[str]], n_docs: int) -> OrdinalDV:
        uniq = sorted({t for vals in per_doc for t in vals})
        term_to_ord = {t: i for i, t in enumerate(uniq)}
        offsets = np.zeros(n_docs + 1, dtype=np.int32)
        ords, value_docs = [], []
        min_ord = np.full(n_docs, -1, dtype=np.int32)
        max_ord = np.full(n_docs, -1, dtype=np.int32)
        exists = np.zeros(n_docs, dtype=bool)
        for i, vals in enumerate(per_doc):
            # SortedSetDocValues semantics: per-doc ordinals are DEDUPED
            # (unlike SortedNumeric, which keeps duplicate values)
            o = sorted({term_to_ord[t] for t in vals})
            ords.extend(o)
            value_docs.extend([i] * len(o))
            offsets[i + 1] = len(ords)
            if o:
                min_ord[i], max_ord[i] = o[0], o[-1]
                exists[i] = True
        return OrdinalDV(ord_terms=uniq, term_to_ord=term_to_ord,
                         offsets=offsets,
                         ords=np.asarray(ords, dtype=np.int32),
                         value_docs=np.asarray(value_docs, dtype=np.int32),
                         min_ord=min_ord, max_ord=max_ord, exists=exists)

    @staticmethod
    def _build_geo(per_doc, n_docs) -> GeoDV:
        offsets = np.zeros(n_docs + 1, dtype=np.int32)
        lats, lons, value_docs = [], [], []
        exists = np.zeros(n_docs, dtype=bool)
        for i, pts in enumerate(per_doc):
            for lat, lon in pts:
                lats.append(lat)
                lons.append(lon)
                value_docs.append(i)
            offsets[i + 1] = len(lats)
            exists[i] = bool(pts)
        return GeoDV(offsets=offsets,
                     lats=np.asarray(lats, dtype=np.float32),
                     lons=np.asarray(lons, dtype=np.float32),
                     value_docs=np.asarray(value_docs, dtype=np.int32),
                     exists=exists)
