"""k-NN distance kernels: brute-force exact search as batched matmuls.

The reference ecosystem's FAISS/nmslib C++ engines plug in via the k-NN
plugin SPI (ref server/src/main/java/org/opensearch/plugins/
SearchPlugin.java:151); on TPU the exact path IS the friendly one — a
[n_docs, dim] x [dim] (or [dim, q]) matmul feeds the MXU directly, and
an exact top-k over the scores replaces the heap: ``ops/topk.py``'s block
maxima and k winning blocks for one query (a whole-segment ``lax.top_k``
is a sort of every row on the TPU), ``lax.top_k`` a row for a batch of
queries.  Score translations match the opensearch-knn
plugin's space definitions so scores are drop-in comparable:

- l2:            1 / (1 + ||v - q||^2)
- cosinesimil:   (2 - (1 - cos)) / 2  == (1 + cos) / 2
- innerproduct:  d >= 0 ? d + 1 : 1 / (1 - d)
"""

from __future__ import annotations

from functools import partial

import opensearch_tpu.common.jaxenv  # noqa: F401
import jax
import jax.numpy as jnp
from jax import lax

from opensearch_tpu.ops.topk import topk_exact

SPACES = ("l2", "cosinesimil", "innerproduct")

# This is the EXACT search path: a TPU's default matmul precision is a
# single bf16 pass, whose error the l2 form ``v2 - 2*dots + q2`` then
# amplifies by cancellation until neighbours swap.  HIGHEST keeps the
# dots float32-accurate (ops/ivf.py is approximate by contract and
# keeps the default).
_EXACT = lax.Precision.HIGHEST


@partial(jax.jit, static_argnames=("space",))
def knn_scores(vectors, valid, query, *, space: str):
    """Per-doc similarity scores [n_pad]; invalid rows score -inf.

    ``vectors`` [n_pad, d] float32, ``valid`` bool [n_pad] (exists & live),
    ``query`` [d].
    """
    q = query.astype(jnp.float32)
    dots = jnp.matmul(vectors, q, precision=_EXACT)       # MXU
    if space == "l2":
        v2 = jnp.sum(vectors * vectors, axis=1)
        d2 = jnp.maximum(v2 - 2.0 * dots + jnp.sum(q * q), 0.0)
        scores = 1.0 / (1.0 + d2)
    elif space == "cosinesimil":
        norms = jnp.sqrt(jnp.sum(vectors * vectors, axis=1))
        qn = jnp.sqrt(jnp.sum(q * q))
        cos = dots / jnp.maximum(norms * qn, 1e-30)
        scores = (1.0 + cos) / 2.0
    elif space == "innerproduct":
        scores = jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))
    else:
        raise ValueError(f"unknown space [{space}]")
    return jnp.where(valid, scores, -jnp.inf)


@partial(jax.jit, static_argnames=("space", "k"))
def knn_topk(vectors, valid, query, *, space: str, k: int):
    scores = knn_scores(vectors, valid, query, space=space)
    return topk_exact(scores, k)


def knn_topk_auto(vectors, valid, query, *, space: str, k: int):
    """Exact top-k via the hand-written pallas kernel when opted in
    (OSTPU_PALLAS=1, see ops/pallas_knn.py) and the layout qualifies;
    the XLA-fused jnp path otherwise.  Identical results either way."""
    import os
    if os.environ.get("OSTPU_PALLAS") == "1":
        # pallas import deferred so the default path never loads it
        from opensearch_tpu.ops.pallas_knn import TILE, knn_scores_pallas
        if vectors.shape[0] % TILE == 0:
            # the kernel is compiled everywhere but on the CPU backend,
            # which has no pallas lowering and runs the interpreter
            interpret = jax.default_backend() == "cpu"
            scores = knn_scores_pallas(vectors, valid, query, space=space,
                                       interpret=interpret)
            return topk_exact(scores, k)
    return knn_topk(vectors, valid, query, space=space, k=k)


@partial(jax.jit, static_argnames=("space", "k"))
def knn_topk_batch(vectors, valid, queries, *, space: str, k: int):
    """Batched queries [Q, d] -> (scores [Q, k], ids [Q, k]).  One
    [n, d] x [d, Q] matmul for the whole batch — the throughput path."""
    q = queries.astype(jnp.float32)
    dots = jnp.matmul(vectors, q.T, precision=_EXACT)     # [n, Q]
    if space == "l2":
        v2 = jnp.sum(vectors * vectors, axis=1)[:, None]
        q2 = jnp.sum(q * q, axis=1)[None, :]
        d2 = jnp.maximum(v2 - 2.0 * dots + q2, 0.0)
        scores = 1.0 / (1.0 + d2)
    elif space == "cosinesimil":
        norms = jnp.sqrt(jnp.sum(vectors * vectors, axis=1))[:, None]
        qn = jnp.sqrt(jnp.sum(q * q, axis=1))[None, :]
        cos = dots / jnp.maximum(norms * qn, 1e-30)
        scores = (1.0 + cos) / 2.0
    elif space == "innerproduct":
        scores = jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))
    else:
        raise ValueError(f"unknown space [{space}]")
    scores = jnp.where(valid[:, None], scores, -jnp.inf)
    return lax.top_k(scores.T, k)
