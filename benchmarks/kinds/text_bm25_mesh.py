"""Configuration kind ``text_bm25_mesh``: ``text_bm25``'s seeded passages,
queries and program space, cut into ``shards`` primary shards of equal
segments, one shard a chip, installed into one index that the mesh serves
(``search.mesh``).

The corpus is ``text_bm25.generate``'s: ``segments`` counts the segments of
every shard together, and shard ``s`` holds segments ``s * per`` to
``(s + 1) * per - 1`` (doc numbers stay global, so ids are unique across
shards).  A shard scores with its own statistics (OpenSearch's default
``query_then_fetch``), so the plain reference beside the configuration does
too.  The mesh runs segment index ``g`` of every shard as one program whose
gather bucket is the largest of the shards' (``signature``).
"""

from __future__ import annotations

import numpy as np

from benchmarks.kinds import text_bm25
from benchmarks.kinds.text_bm25 import (FIELD, body, generate,  # noqa: F401
                                        program_space, queries,
                                        warmup_queries, work_bytes,
                                        work_flops)


def per_shard(cfg: dict) -> int:
    """Segments a shard."""
    if cfg["segments"] % cfg["shards"]:
        raise ValueError("shards must divide segments: the mesh runs one "
                         "segment index of every shard as one program")
    return cfg["segments"] // cfg["shards"]


def shard_segments(cfg: dict, data, shard: int) -> list:
    per = per_shard(cfg)
    return data.segments[shard * per:(shard + 1) * per]


def index_body(cfg: dict) -> dict:
    return {"settings": {"number_of_shards": cfg["shards"],
                         "number_of_replicas": 0, "search.mesh": True},
            "mappings": {"properties": {FIELD: {"type": "text"}}}}


def install(node, index: str, cfg: dict, data) -> None:
    """Shard ``s``'s segments adopted as a checkpoint of its own engine
    (``engine_for(s).install_remote_checkpoint``, a replica's segment-copy
    path), as ``text_bm25.install`` adopts one shard's."""
    from opensearch_tpu.index.segment import PostingsField, Segment

    service = node.indices.get(index)
    per = per_shard(cfg)
    for shard in range(cfg["shards"]):
        segments, live = {}, {}
        for i, sd in enumerate(shard_segments(cfg, data, shard)):
            seg = Segment(f"bench_{shard * per + i}", sd.n_docs)
            seg.doc_ids = [str(d) for d in range(sd.lo, sd.lo + sd.n_docs)]
            seg.id_to_local = {d: j for j, d in enumerate(seg.doc_ids)}
            seg.sources = [b"{}"] * sd.n_docs
            lens = sd.lens.astype(np.float32)
            seg.postings[FIELD] = PostingsField(
                terms={f"t{t}": t for t in np.flatnonzero(sd.df).tolist()},
                df=sd.df, offsets=sd.offsets, doc_ids=sd.doc_ids,
                tfs=sd.tfs,
                pos_offsets=np.zeros(len(sd.doc_ids) + 1, dtype=np.int32),
                positions=np.zeros(0, dtype=np.int32), doc_lens=lens,
                total_len=float(lens.sum()), docs_with_field=sd.n_docs,
                has_norms=True, present=np.ones(sd.n_docs, dtype=bool))
            segments[seg.seg_id] = seg
            live[seg.seg_id] = np.ones(sd.n_docs, dtype=bool).tobytes()
        n = sum(seg.n_docs for seg in segments.values())
        ckpt = {"segments": list(segments), "live": live,
                "max_seq_no": n - 1, "primary_term": 1}
        service.engine_for(shard).install_remote_checkpoint(ckpt, segments)


def signature(cfg: dict, data, terms: list, g: int):
    """(t_pad, bucket) of ``terms`` in segment index ``g``: the program of
    the mesh takes the largest of the shards' budgets; None where no term
    occurs in any of them."""
    per = per_shard(cfg)
    budget = max(int(data.segments[s * per + g].df[terms].sum())
                 for s in range(cfg["shards"]))
    return ((text_bm25.t_pad(len(terms)), text_bm25.bucket(budget))
            if budget else None)
