"""Configuration kind ``knn_exact``: seeded vectors, the installer, the
query maker and the warm-up.  Numpy only above ``install``.

Values lie on a 1/64 grid over 0-255 (``chip_smoke.py``): exact in
float32 and in short decimal JSON, but 14 bits wide, so that a bf16 pass
over them moves the neighbours (whole numbers would hide it).
"""

from __future__ import annotations

import dataclasses

import numpy as np

FIELD = "vec"
GRID = 64


@dataclasses.dataclass
class VectorData:
    n_docs: int
    dim: int
    vectors: np.ndarray          # float32 [n_docs, dim]


def _draw(rng, shape) -> np.ndarray:
    raw = rng.integers(0, 255 * GRID, size=shape, dtype=np.uint16)
    return raw.astype(np.float32) / np.float32(GRID)


def generate(cfg: dict, seed: int) -> VectorData:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    return VectorData(cfg["n_docs"], cfg["dim"],
                      _draw(rng, (cfg["n_docs"], cfg["dim"])))


def index_body(cfg: dict) -> dict:
    return {"settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"properties": {FIELD: {
                "type": "knn_vector", "dimension": cfg["dim"],
                "method": {"name": "exact", "space_type": "l2"}}}}}


def install(node, index: str, cfg: dict, data: VectorData) -> None:
    """One force-merged segment, adopted through the engine's
    segment-copy path (see kinds/text_bm25.py)."""
    from opensearch_tpu.index.segment import Segment, VectorDV

    n = data.n_docs
    seg = Segment("bench_0", n)
    seg.doc_ids = [str(i) for i in range(n)]
    seg.id_to_local = {d: i for i, d in enumerate(seg.doc_ids)}
    seg.sources = [b"{}"] * n
    seg.vector_dv[FIELD] = VectorDV(
        values=data.vectors, exists=np.ones(n, dtype=bool), dim=data.dim,
        similarity="l2_norm")
    ckpt = {"segments": [seg.seg_id],
            "live": {seg.seg_id: np.ones(n, dtype=bool).tobytes()},
            "max_seq_no": n - 1, "primary_term": 1}
    node.indices.get(index).engine_for(0).install_remote_checkpoint(
        ckpt, {seg.seg_id: seg})


def queries(cfg: dict, data: VectorData, seed: int) -> list:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    return list(_draw(rng, (cfg["n_queries"], cfg["dim"])))


def body(cfg: dict, q: np.ndarray) -> dict:
    return {"query": {"knn": {FIELD: {"vector": q.tolist(),
                                      "k": cfg["k"]}}},
            "size": cfg["k"], "_source": False}


def program_space(cfg: dict) -> list:
    return [("knn_topk", cfg["k"])]        # one shape: one segment, one k


def warmup_queries(cfg: dict, data: VectorData) -> list:
    q = np.full(cfg["dim"], 127.5, dtype=np.float32)
    return [(program_space(cfg)[0], q)]


def signature(cfg: dict, data: VectorData, q, si: int):
    return program_space(cfg)[0]


def work_bytes(cfg: dict, data: VectorData, q) -> float:
    """An exact scan reads every vector once."""
    return float(cfg["n_docs"]) * cfg["dim"] * 4.0


def work_flops(cfg: dict, data: VectorData, q) -> float:
    return 2.0 * cfg["n_docs"] * cfg["dim"]
