"""An exact top-k that does not sort the segment.

``lax.top_k`` over a whole ``[n_pad]`` key lowers on the TPU to a full
sort of (value, index) pairs: 1.15 ms for 1,048,576 lanes to find ten
rows.  ``topk_exact`` finds the same rows in two stages:

  key [n] -> contiguous blocks [n / B, B] -> each block's maximum
          -> ``lax.top_k`` of the n / B maxima: the k winning blocks
          -> those blocks, in ascending order, gathered [k, B]
          -> ``lax.top_k`` of the k * B candidates -> global indices

Exact, ties included.  ``lax.top_k`` breaks a tie by the lower index
(Lucene's ascending doc id), so among equal block maxima the lower
block wins.  Had a row of the true top-k lain in a block that was not
chosen, each of the k chosen blocks would hold a row that beats it
(greater, or equal at a lower index: a lower block lies at lower
indices), so it was not in the top-k.  The blocks are gathered in
ascending order, so a candidate's position orders as its global index
does and the second ``lax.top_k`` breaks ties as the first would have.
The blocks have to be contiguous: over a strided view two columns may
tie on their maxima while the winning row sits in the later column.

What a caller may rely on: for every returned entry whose value is above
``-inf``, the value, the index and the position ``lax.top_k(key, k)``
gives.  An entry at ``-inf`` may name any row (every reader drops
those).  A NaN orders first in ``lax.top_k`` and is a block's maximum
(``max`` propagates it), so a poisoned key still surfaces in the values.
"""

from __future__ import annotations

from functools import partial

import opensearch_tpu.common.jaxenv  # noqa: F401
import jax
import jax.numpy as jnp
from jax import lax

# Which way a top-k goes, from its static shape alone.  Measured on one
# v5e (PR 34, ``tools/topk_bench.py``, table in PERF.md section 3), in us
# a top-k inside one program, ``lax.top_k`` / two stages at B = 128:
#   (1,048,576, k 10) 1,260 / 125    (1,048,576, 1000) 1,265 / 249
#   (262,144, 100)      307 /  87    (262,144, 10)       310 /  91
#   (131,072, 10)       171 /  75    (131,072, 1000)     169 / 200
#   (65,536, 10)        102 /  68    (32,768, 10)         75 /  67
#   (16,384, 10)         64 /  73    (8,192, 10)          60 /  66
# Blocks of 128 lanes are the widest that reshape for free (one row of a
# tile) and were the fastest or within 7 us of it at every shape; wider
# blocks only grow the second sort (1,048,576 x 1000: 388 us at 256, 716
# at 512).  The three small sorts, the reduce and the gather cost 65-75 us
# whatever ``n``, which is what ``lax.top_k`` costs up to 32,768 lanes.
_BLOCK = 128
_MIN_LANES = 65536
# the two stages sort n / B maxima and k * B candidates where ``lax.top_k``
# sorts n: taken while those are at most 1 / _MIN_SHRINK of n
_MIN_SHRINK = 2


def block_size(n: int, k: int) -> int:
    """The block size ``topk_exact`` takes for a key of ``n`` lanes and
    ``k`` results, or 0 where it is plain ``lax.top_k``: a short key,
    one that is no whole number of blocks or has fewer than ``k`` of
    them, or too little saved (a ``size: 10000`` window over a
    131,072-lane segment would sort 1,280,000 candidates).  The kernel
    and the ``device.block_topk_programs`` counter both ask here, so they
    cannot disagree."""
    b = _BLOCK
    if n < _MIN_LANES or n % b or not 1 <= k <= n // b:
        return 0
    if (n // b + k * b) * _MIN_SHRINK > n:
        return 0
    return b


def _two_stage(key, k: int, b: int):
    """The two stages over contiguous blocks of ``b`` lanes."""
    n = key.shape[0]
    blocks = key.reshape(n // b, b)
    maxima = jnp.max(blocks, axis=1)
    _, best = lax.top_k(maxima, k)
    best = jnp.sort(best)
    vals, pos = lax.top_k(blocks[best].reshape(k * b), k)
    idx = best[pos // b] * b + pos % b
    return vals, idx, jnp.max(maxima)


def topk_and_max(key, k: int):
    """(values[k], indices[k], max(key)) of a float32 ``key`` [n]; the
    first two as ``lax.top_k(key, k)`` (module doc).  The maximum is that
    of the block maxima, so it costs no second pass over ``key``."""
    b = block_size(key.shape[0], k)
    if b:
        return _two_stage(key, k, b)
    vals, idx = lax.top_k(key, k)
    return vals, idx, jnp.max(key)


@partial(jax.jit, static_argnums=1)
def topk_exact(key, k: int):
    """``lax.top_k(key, k)`` without the sort of the whole key; one
    program where it is called outside one."""
    vals, idx, _ = topk_and_max(key, k)
    return vals, idx
