"""``ops/bm25.py::gather_postings`` lane for lane against a numpy
reference of the CSR lay-out, on both lowerings (contiguous slices up to
``slice_lowering``'s threshold, the element gather beyond it).

The columns carry values that name their own position, so a lane that
read from beyond its term's run shows as a wrong number, not a wrong
score some layers up."""

import numpy as np
import pytest

import opensearch_tpu.common.jaxenv  # noqa: F401
import jax
import jax.numpy as jnp

from opensearch_tpu.ops import bm25

PAD_DOC = 999_999

# runs of the ten terms of the test column, in posting slots; term 9 ends
# at the column's last posting, term 4 is empty
LENS = np.array([5, 1, 40, 7, 0, 300, 2, 64, 68, 80], dtype=np.int32)


def column(pad_to: int | None = None):
    offsets = np.zeros(len(LENS) + 1, dtype=np.int32)
    np.cumsum(LENS, out=offsets[1:])
    n = int(offsets[-1])
    size = n if pad_to is None else pad_to
    doc_ids = np.full(size, -7, dtype=np.int32)
    tfs = np.full(size, -7.0, dtype=np.float32)
    doc_ids[:n] = 1000 + np.arange(n)
    tfs[:n] = 0.5 + np.arange(n)
    return offsets, doc_ids, tfs


def reference(offsets, doc_ids, tfs, term_ids, active, budget):
    d = np.full(budget, PAD_DOC, dtype=np.int32)
    tf = np.zeros(budget, dtype=np.float32)
    slot = np.full(budget, len(term_ids) - 1, dtype=np.int32)
    at = 0
    bounds = []
    for tid, on in zip(term_ids, active):
        n = int(offsets[tid + 1] - offsets[tid]) if on else 0
        lo, hi = min(at, budget), min(at + n, budget)
        d[lo:hi] = doc_ids[offsets[tid]: offsets[tid] + hi - lo]
        tf[lo:hi] = tfs[offsets[tid]: offsets[tid] + hi - lo]
        at += n
        bounds.append(at)
    # lane i belongs to the first slot whose cumulative end is past i
    for i in range(budget):
        s = int(np.searchsorted(bounds, i, side="right"))
        slot[i] = min(s, len(term_ids) - 1)
    return d, tf, slot, np.arange(budget) < at


def gather(offsets, doc_ids, tfs, term_ids, active, budget):
    # a fresh function every call: jit's cache must not carry one
    # lowering over to a test that has switched to the other
    out = jax.jit(lambda *args: bm25.gather_postings(
        *args, budget=budget, pad_doc=PAD_DOC))(
        jnp.asarray(offsets), jnp.asarray(doc_ids), jnp.asarray(tfs),
        jnp.asarray(np.asarray(term_ids, dtype=np.int32)),
        jnp.asarray(np.asarray(active, dtype=bool)))
    return [np.asarray(x) for x in out]


def padded(term_ids, active, t_pad):
    term_ids = list(term_ids) + [0] * (t_pad - len(term_ids))
    active = list(active) + [False] * (t_pad - len(active))
    return term_ids, active


# name: (term ids, active, t_pad, budget, column padded to, slices?)
CASES = {
    # term 9's run ends at the column's last posting, so its window's
    # start is clamped; term 0's window is not
    "run_ends_at_last_posting":
        ([9, 0, 7], [True, True, True], 4, 512, None, True),
    "last_posting_first_and_alone":
        ([9], [True], 1, 512, None, True),
    # the window is the whole column and every term but the first is
    # shifted inside it
    "budget_larger_than_column":
        ([2, 5, 8], [True, True, True], 4, 1024, None, True),
    "budget_larger_than_padded_column":
        ([5, 9, 1], [True, True, True], 4, 2048, 1024, True),
    "inactive_slot_between_active":
        ([3, 5, 7], [True, False, True], 4, 512, 4096, True),
    "zero_length_term":
        ([2, 4, 6], [True, True, True], 4, 512, 4096, True),
    "same_term_twice":
        ([7, 7, 3], [True, True, True], 4, 512, 4096, True),
    "nothing_active":
        ([1, 2], [False, False], 2, 512, 4096, True),
    "budget_filled_to_the_last_lane":
        ([5, 9, 7, 8], [True] * 4, 4, 512, 4096, True),
    # a caller that broke the contract: lanes past ``budget`` are dropped
    "total_past_budget":
        ([5, 5, 9, 7], [True] * 4, 4, 512, 600, True),
    "elements_total_past_budget":
        ([5, 5, 9, 7], [True] * 4, 4, 256, 600, False),
    # the same runs on the element gather's side of the threshold
    "elements_run_ends_at_last_posting":
        ([9, 0, 7], [True, True, True], 4, 256, None, False),
    "elements_inactive_and_repeated":
        ([7, 5, 7, 4], [True, False, True, True], 4, 256, 4096, False),
    "elements_many_slots":
        (list(range(10)) * 6, [True, False, True] * 20, 64, 4096, 4096,
         False),
    "slices_many_slots":
        (list(range(10)) * 3, [True, False, True] * 10, 32, 4096, 4096,
         True),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_gather_postings_matches_csr_layout(name):
    term_ids, active, t_pad, budget, pad_to, slices = CASES[name]
    assert bm25.slice_lowering(t_pad, budget) is slices
    offsets, doc_ids, tfs = column(pad_to)
    term_ids, active = padded(term_ids, active, t_pad)
    want = reference(offsets, doc_ids, tfs, term_ids, active, budget)
    got = gather(offsets, doc_ids, tfs, term_ids, active, budget)
    for what, g, w in zip(("docs", "tfs", "slot", "valid"), got, want):
        np.testing.assert_array_equal(g, w, err_msg=f"{name}: {what}")
    # every lane past the total is padding, whatever the window held
    total = int(want[3].sum())
    assert (got[0][total:] == PAD_DOC).all()
    assert (got[1][total:] == 0.0).all()


@pytest.mark.parametrize("t_pad,budget", [(4, 512), (8, 4096), (32, 4096),
                                          (64, 4096), (128, 4096),
                                          (512, 65536)])
def test_both_lowerings_agree(monkeypatch, t_pad, budget):
    """The same random terms through both lowerings: the threshold picks
    a price, never a result."""
    rng = np.random.default_rng(t_pad * 31 + budget)
    offsets, doc_ids, tfs = column(4096)
    term_ids = rng.integers(0, len(LENS), t_pad)
    active = rng.random(t_pad) < 0.7
    # keep the contract: at most ``budget`` postings
    while LENS[term_ids][active].sum() > budget:
        active[np.flatnonzero(active)[-1]] = False
    outs = []
    for slices in (True, False):
        monkeypatch.setattr(bm25, "slice_lowering",
                            lambda t, b, s=slices: s)
        outs.append(gather(offsets, doc_ids, tfs, term_ids, active, budget))
    for g, w in zip(*outs):
        np.testing.assert_array_equal(g, w)


def test_slice_lowering_is_chosen_from_the_static_shape():
    """The cell's shapes (t_pad 4 or 8, every bucket) copy slices; an
    expansion of hundreds of terms over a small bucket gathers."""
    for t_pad in (1, 2, 4, 8):
        for budget in (4096, 16384, 65536, 262144, 1048576):
            assert bm25.slice_lowering(t_pad, budget)
    assert bm25.slice_lowering(32, 4096)
    assert not bm25.slice_lowering(128, 4096)
    assert bm25.slice_lowering(128, 65536)
    assert bm25.slice_lowering(512, 262144)
    assert not bm25.slice_lowering(512, 16384)
    assert not bm25.slice_lowering(4096, 1048576)


def test_slice_lowering_traces_no_element_gather_of_a_column():
    """What the lowering is for: the program holds no gather whose
    operand is a postings column (the offsets look-ups stay)."""
    offsets, doc_ids, tfs = column(4096)

    def column_gathers(t_pad, budget):
        jaxpr = jax.make_jaxpr(
            lambda o, d, t, ti, a: bm25.gather_postings(
                o, d, t, ti, a, budget=budget, pad_doc=PAD_DOC))(
            offsets, doc_ids, tfs, np.zeros(t_pad, np.int32),
            np.ones(t_pad, bool))
        return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "gather"
                and e.invars[0].aval.shape == (4096,)]

    assert column_gathers(8, 4096) == []
    assert len(column_gathers(128, 4096)) == 2
