"""Quantized-impact scoring kernels (the device half of index/codec.py).

Same composition as the f32 impact kernels in ops/bm25.py — CSR gather,
weighted scatter-add, the exact top-k of ops/topk.py downstream — but
the gather decodes
bit-packed doc-id deltas in-lane and the impact column dequantizes
int8/int16 codes against per-term scales, with an in-kernel override
for terms the exact-rank-parity guard stored as sparse f32
(``exact_vals``/``exact_offsets``).

Parity contract: every contribution is ``weights[slot] * (idfs[slot] *
imp)`` where ``imp = q.astype(f32) * scales[term]`` — float32, the
same multiply order as ``QuantizedPostings.dequantized()`` feeding
``TermBagPlan.host_topk``, so budget eviction and breaker-open
degradation stay byte-identical on quantized segments (the PR-5/11
invariant, extended to the compressed layout).

All functions are pure jnp and shape-static; ``width`` and ``budget``
are static so the executor's bucketed dims share XLA programs.
"""

from __future__ import annotations

import opensearch_tpu.common.jaxenv  # noqa: F401

import jax.numpy as jnp

from opensearch_tpu.ops.bm25 import gather_postings_packed


def _dequant(idx, slot, valid, offsets, term_ids, qvals, scales,
             exact_vals, exact_offsets):
    """Per-lane impact reconstruction: quantized code * per-term scale,
    overridden by the exact f32 block where the parity guard demanded
    one.  ``idx - starts`` is the in-list position, which indexes the
    exact CSR directly (same order as the postings CSR)."""
    tid = term_ids[slot]
    imp_q = qvals[idx].astype(jnp.float32) * scales[tid]
    pos = idx - offsets[term_ids][slot]
    e0 = exact_offsets[tid]
    has_exact = exact_offsets[tid + 1] > e0
    ei = jnp.clip(e0 + pos, 0, exact_vals.shape[0] - 1)
    imp = jnp.where(has_exact, exact_vals[ei], imp_q)
    return jnp.where(valid, imp, 0.0)


def quantized_impact_scores(offsets, packed, base, qvals, scales,
                            exact_vals, exact_offsets, term_ids,
                            term_active, idfs, weights, *, width: int,
                            n_pad: int, budget: int):
    """Quantized mirror of ``bm25.impact_scores`` (the required<=1
    positive-weight fast path: score > 0 iff matched, no count
    scatter).  The floor-of-1 quantization in index/codec.py is what
    keeps that equivalence: a matched posting never decodes to 0."""
    d, idx, slot, valid = gather_postings_packed(
        offsets, packed, base, term_ids, term_active,
        width=width, budget=budget, pad_doc=n_pad - 1)
    imp = _dequant(idx, slot, valid, offsets, term_ids, qvals, scales,
                   exact_vals, exact_offsets)
    base_score = idfs[slot] * imp
    contrib = jnp.where(valid, weights[slot] * base_score, 0.0)
    return jnp.zeros(n_pad, jnp.float32).at[d].add(contrib)


def quantized_impact_score_count(offsets, packed, base, qvals, scales,
                                 exact_vals, exact_offsets, term_ids,
                                 term_active, idfs, weights, *,
                                 width: int, n_pad: int, budget: int,
                                 scored: bool):
    """Quantized mirror of ``bm25.impact_score_count``: one gather,
    score scatter + matched-slot count scatter (AND /
    minimum_should_match semantics)."""
    d, idx, slot, valid = gather_postings_packed(
        offsets, packed, base, term_ids, term_active,
        width=width, budget=budget, pad_doc=n_pad - 1)
    count = jnp.zeros(n_pad, jnp.int32).at[d].add(valid.astype(jnp.int32))
    if not scored:
        return jnp.zeros(n_pad, jnp.float32), count
    imp = _dequant(idx, slot, valid, offsets, term_ids, qvals, scales,
                   exact_vals, exact_offsets)
    base_score = idfs[slot] * imp
    contrib = jnp.where(valid, weights[slot] * base_score, 0.0)
    scores = jnp.zeros(n_pad, jnp.float32).at[d].add(contrib)
    return scores, count
