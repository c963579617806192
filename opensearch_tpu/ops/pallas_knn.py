"""Pallas TPU kernel for the exact k-NN scoring hot op.

The jnp formulation in ops/knn.py already lands on the MXU via XLA; this
kernel is the hand-scheduled variant per SURVEY §7's pallas mandate: the
vector matrix streams HBM -> VMEM one doc-tile at a time (grid over
tiles), each tile does one [1, d] x [TILE, d]^T MXU contraction plus the
VPU score translation, writing its slice of the dense score vector — no
intermediate [n, d] temporaries, explicit control of the tile size.

Layout (what Mosaic compiles): every ref is 2-D and scores live on the
LANE axis.  Contracting the query row against the tile on the MXU yields
a lane-dense ``[1, TILE]`` row directly, where a VPU ``sum(v * q,
axis=1)`` would leave a ``[TILE]`` column needing a sublane->lane
relayout; the squared norms come from the same contraction with a row of
ones.  The query rides as 8 identical sublanes (one f32 tile) and the
valid mask as int32, since sub-tile operands and bool refs are things
the compiler is strict about.  Contractions run at float32 precision —
this is the EXACT search path (see ops/knn.py).

Same formulas and masking as ``ops.knn.knn_scores``; validated against
it in interpreter mode on CPU (tests/test_pallas.py) and compiled
against a float32 numpy reference on the chip (chip_smoke.py).  Serving
uses it behind ``OSTPU_PALLAS=1``.  Tile size 256 keeps a
(256, d<=1024) f32 block well under VMEM.
"""

from __future__ import annotations

import functools

import numpy as np

import opensearch_tpu.common.jaxenv  # noqa: F401
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

TILE = 256
_SUBLANES = 8                            # one f32 tile of query rows
_NT = (((1,), (1,)), ((), ()))           # [m, d] x [n, d]^T -> [m, n]
# block indices must be 32-bit: under the engine's global x64 a literal 0
# in an index map is an int64, which Mosaic does not legalize
_ZERO = np.int32(0)


def _row_dot(lhs, v):
    """``lhs`` [8, d] (identical rows) against the tile ``v`` [TILE, d]
    on the MXU at float32 precision -> the lane-dense [1, TILE] row."""
    out = lax.dot_general(lhs, v, _NT, precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return out[0:1, :]


def _score_kernel_l2(v_ref, q_ref, valid_ref, out_ref):
    v = v_ref[...]                       # [TILE, d] f32 (VMEM)
    q = q_ref[...]                       # [8, d], rows identical
    dots = _row_dot(q, v)
    v2 = _row_dot(jnp.ones_like(q), v * v)
    q2 = jnp.sum(q[0:1, :] * q[0:1, :], axis=1, keepdims=True)
    d2 = jnp.maximum(v2 - 2.0 * dots + q2, 0.0)
    scores = 1.0 / (1.0 + d2)
    out_ref[...] = jnp.where(valid_ref[...] != 0, scores, -jnp.inf)


def _score_kernel_cosine(v_ref, q_ref, valid_ref, out_ref):
    v = v_ref[...]
    q = q_ref[...]
    dots = _row_dot(q, v)
    norms = jnp.sqrt(_row_dot(jnp.ones_like(q), v * v))
    qn = jnp.sqrt(jnp.sum(q[0:1, :] * q[0:1, :], axis=1, keepdims=True))
    cos = dots / jnp.maximum(norms * qn, 1e-30)
    out_ref[...] = jnp.where(valid_ref[...] != 0, (1.0 + cos) / 2.0,
                             -jnp.inf)


def _score_kernel_ip(v_ref, q_ref, valid_ref, out_ref):
    dots = _row_dot(q_ref[...], v_ref[...])
    scores = jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))
    out_ref[...] = jnp.where(valid_ref[...] != 0, scores, -jnp.inf)


_KERNELS = {"l2": _score_kernel_l2, "cosinesimil": _score_kernel_cosine,
            "innerproduct": _score_kernel_ip}


@functools.partial(jax.jit, static_argnames=("space", "interpret"))
def knn_scores_pallas(vectors, valid, query, *, space: str = "l2",
                      interpret: bool = False):
    """Drop-in pallas replacement for ``ops.knn.knn_scores``.

    ``vectors`` [n_pad, d] f32 with n_pad % TILE == 0 (the segment
    staging pads to pow2 >= 8, so any n_pad >= TILE qualifies; smaller
    inputs should use the jnp path).
    """
    kernel = _KERNELS.get(space)
    if kernel is None:
        raise ValueError(f"unknown space [{space}]")
    n_pad, d = vectors.shape
    assert n_pad % TILE == 0, n_pad
    q8 = jnp.broadcast_to(query.astype(jnp.float32).reshape(1, d),
                          (_SUBLANES, d))
    scores = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        grid=(n_pad // TILE,),
        in_specs=[
            pl.BlockSpec((TILE, d), lambda i: (i, _ZERO)),
            pl.BlockSpec((_SUBLANES, d), lambda i: (_ZERO, _ZERO)),
            pl.BlockSpec((1, TILE), lambda i: (_ZERO, i)),
        ],
        out_specs=pl.BlockSpec((1, TILE), lambda i: (_ZERO, i)),
        interpret=interpret,
    )(vectors.astype(jnp.float32), q8,
      valid.astype(jnp.int32).reshape(1, n_pad))
    return scores.reshape(n_pad)
