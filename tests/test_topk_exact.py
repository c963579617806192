"""``ops/topk.py``: an exact top-k that does not sort the segment.

``topk_exact`` (block maxima, then ``lax.top_k`` over the k winning
blocks) against ``lax.top_k`` on XLA:CPU: the same values everywhere, the
same indices wherever the value is above ``-inf``, ties broken by the
lower index; the rule that chooses between the two from ``(n, k)`` on
both of its sides, and that the program traced is the one the rule names
(the ``device.block_topk_programs`` counter asks the same rule)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from opensearch_tpu.ops import topk

B = 128
KEYS = ("uniform", "four_ints", "all_equal", "all_neg_inf", "few_finite",
        "one_nan", "tie_across_block_edge", "best_in_last_lane")


def make_key(kind: str, n: int, k: int) -> np.ndarray:
    rng = np.random.default_rng(n * 31 + k)
    if kind == "uniform":
        return rng.random(n, dtype=np.float32)
    if kind == "four_ints":          # ties inside and across blocks
        return rng.integers(0, 4, n).astype(np.float32)
    if kind == "all_equal":
        return np.full(n, 2.5, np.float32)
    key = np.full(n, -np.inf, np.float32)
    if kind == "all_neg_inf":
        return key
    if kind == "few_finite":         # fewer than k above -inf
        few = max(1, min(k, n) // 2)
        key[rng.choice(n, few, replace=False)] = rng.integers(0, 3, few)
        return key
    if kind == "one_nan":
        key = rng.random(n, dtype=np.float32)
        key[n // 3] = np.nan
        return key
    if kind == "tie_across_block_edge":
        # a block's last lane and the next block's first, and a later row
        key = np.zeros(n, np.float32)
        key[[B - 1, B, n - 1]] = 5.0
        key[min(300, n - 2)] = 7.0
        return key
    assert kind == "best_in_last_lane"
    key = rng.random(n, dtype=np.float32)
    key[n - 1] = 9.0
    return key


def assert_same_topk(got, want) -> None:
    gv, gi = (np.asarray(a) for a in got[:2])
    wv, wi = (np.asarray(a) for a in want)
    assert gv.dtype == wv.dtype == np.float32
    assert gi.dtype == wi.dtype == np.int32
    np.testing.assert_array_equal(gv, wv)          # NaN equals NaN here
    above = ~(wv == -np.inf)
    np.testing.assert_array_equal(gi[above], wi[above])


def _two_stage_cases():
    for n in (1024, 4096, 16384, 131072):
        for k in (1, 3, 10, 100):
            if k <= n // B and k * B < n:
                yield n, k


@pytest.mark.parametrize("kind", KEYS)
@pytest.mark.parametrize("n,k", list(_two_stage_cases()))
def test_two_stages_match_lax_top_k(n, k, kind):
    """The two stages themselves at every size, below ``block_size``'s
    threshold too (the rule is about speed, not about where they hold)."""
    key = jnp.asarray(make_key(kind, n, k))
    got = jax.jit(topk._two_stage, static_argnums=(1, 2))(key, k, B)
    assert_same_topk(got, lax.top_k(key, k))
    want_max = np.asarray(jnp.max(key))
    np.testing.assert_array_equal(np.asarray(got[2]), want_max)


# the public entry: both sides of the rule, a key that is no whole number
# of blocks, and k = n (every row asked for)
ENTRY_N = (1024, 65536, 65600, 131072)


@pytest.mark.parametrize("kind", KEYS)
@pytest.mark.parametrize("k", [1, 3, 10, 100, "n"])
@pytest.mark.parametrize("n", ENTRY_N)
def test_topk_exact_matches_lax_top_k(n, k, kind):
    k = n if k == "n" else k
    key = jnp.asarray(make_key(kind, n, k))
    want = lax.top_k(key, k)
    assert_same_topk(topk.topk_exact(key, k), want)
    vals, idx, mx = jax.jit(topk.topk_and_max, static_argnums=1)(key, k)
    assert_same_topk((vals, idx), want)
    np.testing.assert_array_equal(np.asarray(mx), np.asarray(jnp.max(key)))
    if kind == "one_nan":            # what ``check_finite`` has to see
        assert np.isnan(np.asarray(vals)).any() and np.isnan(np.asarray(mx))


def test_contiguous_blocks_where_a_strided_view_errs():
    """Columns ``[1, 5]`` / ``[5, 0]`` of a strided view tie on their
    maxima; the first column wins the tie and names index 2 where
    ``lax.top_k`` names 1.  Contiguous blocks of two: ``[1, 5]`` holds
    the winner and is the lower block."""
    key = jnp.asarray([1.0, 5.0, 5.0, 0.0], jnp.float32)
    want = lax.top_k(key, 1)
    assert int(want[1][0]) == 1
    assert_same_topk(topk._two_stage(key, 1, 2), want)
    strided = key.reshape(2, 2).max(axis=0)        # columns {0, 2}, {1, 3}
    assert int(jnp.argmax(strided)) == 0           # ... which holds index 2


RULE = [
    # the cells' shapes (PERF.md section 3)
    (1048576, 10, B), (262144, 100, B), (262144, 10, B), (131072, 10, B),
    # a size window that leaves too little to save, on both sides
    (131072, 10000, 0), (131072, 1000, 0), (131072, 504, B), (131072, 505, 0),
    (65536, 252, B), (65536, 253, 0), (1048576, 1000, B),
    # a short key, on both sides
    (65536, 10, B), (32768, 10, 0), (1024, 1, 0),
    # no whole number of blocks; more results than blocks; k = n
    (65600, 10, 0), (65536 + B, 10, B), (65536, 513, 0), (65536, 65536, 0),
]


@pytest.mark.parametrize("n,k,want", RULE)
def test_block_size_rule(n, k, want):
    assert topk.block_size(n, k) == want


def _top_k_operand_sizes(jaxpr) -> list:
    sizes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "top_k":
            sizes.append(int(np.prod(eqn.invars[0].aval.shape)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            sizes.extend(_top_k_operand_sizes(sub))
    return sizes


@pytest.mark.parametrize("n,k", [(n, k) for n, k, _ in RULE
                                 if n <= 131072 and k <= 1000])
def test_the_program_is_the_one_the_rule_names(n, k):
    """What the counter is told and what was traced: with a block size,
    no ``top_k`` over the whole key is left (the maxima and the
    candidates only); without one, exactly the one ``lax.top_k``."""
    key = jax.ShapeDtypeStruct((n,), jnp.float32)
    jaxpr = jax.make_jaxpr(topk.topk_and_max, static_argnums=1)(key, k)
    sizes = sorted(_top_k_operand_sizes(jaxpr.jaxpr))
    b = topk.block_size(n, k)
    if b:
        assert sizes == sorted([n // b, k * b]) and max(sizes) * 2 <= n
    else:
        assert sizes == [n]
