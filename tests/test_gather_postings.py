"""``ops/bm25.py::gather_postings`` lane for lane against a numpy
reference of the CSR lay-out, on both lowerings (contiguous slices up to
``slice_lowering``'s threshold, the element gather beyond it).

The columns carry values that name their own position, so a lane that
read from beyond its term's run shows as a wrong number, not a wrong
score some layers up.  The slice copy moves a run in chunks of
``copy_chunk`` lanes, the whole budget at these sizes; the ``chunk_``
cases ask for a small chunk so that a run of the test column is several."""

import math
from typing import NamedTuple

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


def cell_lens():
    """Forty runs as a SPLADE segment's query tokens have them: 330,000
    postings, the longest some 100,000."""
    w = np.random.default_rng(38).lognormal(0.0, 1.2, 40)
    return np.maximum(w / w.sum() * 330_000, 1).astype(np.int32)


def column(pad_to: int | None = None, lens=LENS):
    offsets = np.zeros(len(lens) + 1, dtype=np.int32)
    np.cumsum(lens, out=offsets[1:])
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
    slot[:] = np.minimum(
        np.searchsorted(bounds, np.arange(budget), side="right"),
        len(term_ids) - 1)
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


class Case(NamedTuple):
    term_ids: list
    active: list
    t_pad: int
    budget: int
    pad_to: int | None      # the column's length, None: its last posting
    slices: bool            # the lowering the shape takes
    chunk: int | None = None    # None: what ``copy_chunk`` says
    lens: np.ndarray = LENS


# name: (term ids, active, t_pad, budget, column padded to, slices?
#        [, lanes a chunk [, the column's runs]])
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
    # the chunked copy: term 7 has 64 postings, term 9 has 80 and ends at
    # the column's last, term 5 has 300
    "chunk_run_of_exactly_one_chunk":
        ([7, 0, 2], [True] * 3, 4, 512, 4096, True, 64),
    "chunk_run_a_whole_multiple":
        ([7, 9, 2], [True] * 3, 4, 512, 4096, True, 16),
    "chunk_run_a_multiple_plus_one":
        ([5, 0, 7], [True] * 3, 4, 512, 4096, True, 13),
    # the third window of term 9 would pass the column's end: clamped,
    # its lanes ``shift``ed
    "chunk_long_run_ends_at_last_posting":
        ([9, 0, 7], [True] * 3, 4, 512, None, True, 32),
    "chunk_last_posting_first_and_alone":
        ([9], [True], 1, 512, None, True, 32),
    "chunk_column_shorter_than_a_chunk":
        ([5, 9, 1], [True] * 3, 4, 2048, None, True, 1024),
    "chunk_nothing_active":
        ([1, 2], [False, False], 2, 512, 4096, True, 16),
    "chunk_inactive_and_empty_slots":
        ([3, 5, 4, 7], [True, False, True, True], 4, 512, 4096, True, 4),
    "chunk_total_equal_to_budget":
        ([5, 9, 7, 8], [True] * 4, 4, 512, 4096, True, 32),
    "chunk_same_term_twice":
        ([7, 7, 3], [True] * 3, 4, 512, 4096, True, 16),
    "chunk_of_one_lane":
        ([2, 9], [True, True], 2, 512, None, True, 1),
    # a caller that broke the contract, a run across the budget's end
    "chunk_total_past_budget":
        ([5, 5, 9, 7], [True] * 4, 4, 512, 600, True, 32),
    "chunk_many_slots":
        (list(range(10)) * 3, [True, False, True] * 10, 32, 4096, 4096,
         True, 8),
    # a cell's shape: ``splade_sparse_*``'s most frequent program, 24 of
    # 32 slots active, chunks of ``copy_chunk``'s own choosing over a
    # column shorter than the budget
    "cell_shape_32_by_1048576":
        (list(range(24)) + [0] * 8, [True] * 24 + [False] * 8, 32,
         1048576, 1 << 19, True, None, cell_lens()),
}
CASES = {name: Case(*case) for name, case in CASES.items()}


@pytest.mark.parametrize("name", sorted(CASES))
def test_gather_postings_matches_csr_layout(monkeypatch, name):
    term_ids, active, t_pad, budget, pad_to, slices, chunk, lens = CASES[name]
    assert bm25.slice_lowering(t_pad, budget) is slices
    if chunk is not None:
        monkeypatch.setattr(bm25, "copy_chunk", lambda t, b: chunk)
    offsets, doc_ids, tfs = column(pad_to, lens)
    term_ids, active = padded(term_ids, active, t_pad)
    want = reference(offsets, doc_ids, tfs, term_ids, active, budget)
    got = gather(offsets, doc_ids, tfs, term_ids, active, budget)
    for what, g, w in zip(("docs", "tfs", "slot", "valid"), got, want):
        np.testing.assert_array_equal(g, w, err_msg=f"{name}: {what}")
    # every lane past the total is padding, whatever the window held
    total = int(want[3].sum())
    assert (got[0][total:] == PAD_DOC).all()
    assert (got[1][total:] == 0.0).all()


@pytest.mark.parametrize("t_pad,budget,chunk", [
    (4, 512, None), (8, 4096, None), (32, 4096, None), (64, 4096, None),
    (128, 4096, None), (512, 65536, None), (8, 4096, 7), (64, 4096, 64),
    (128, 4096, 256)])
def test_both_lowerings_agree(monkeypatch, t_pad, budget, chunk):
    """The same random terms through both lowerings: the threshold picks
    a price, never a result, and the chunk only how the slices move."""
    if chunk is not None:
        monkeypatch.setattr(bm25, "copy_chunk", lambda t, b: chunk)
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


def test_copy_chunk_is_chosen_from_the_static_shape():
    """One window of the whole budget at one slot; else the budget's
    share of a slot, a power of two, never under the floor unless the
    budget is, and a full budget in at most ``2 * t_pad`` chunks."""
    floor = bm25._CHUNK_FLOOR
    assert floor & (floor - 1) == 0
    buckets = [4096 * 4 ** k for k in range(7)]
    for budget in buckets:
        assert bm25.copy_chunk(1, budget) == budget
        for t_pad in (1, 2, 4, 8, 16, 32, 64, 128, 512, 4096):
            chunk = bm25.copy_chunk(t_pad, budget)
            assert 1 <= chunk <= budget
            assert chunk & (chunk - 1) == 0
            assert chunk >= min(floor, budget)
            assert math.ceil(budget / chunk) <= 2 * t_pad
    # the cells' shapes
    assert bm25.copy_chunk(32, 1048576) == max(floor, 32768)
    assert bm25.copy_chunk(64, 1048576) == max(floor, 16384)
    assert bm25.copy_chunk(2, 1048576) == 524288
    assert bm25.copy_chunk(8, 4096) == 4096
    # any shape a caller may bring: no power of two, more slots than lanes
    assert bm25.copy_chunk(3, 600) == 600
    assert bm25.copy_chunk(7, 10 * floor) == floor
    assert bm25.copy_chunk(4096, 512) == 512


@pytest.mark.parametrize("term_ids,lens,chunk,trips", [
    ([7, 4, 0, 5], [64, 0, 5, 300], 16, 4 + 0 + 1 + 19),
    ([7, 4, 0, 5], [64, 0, 5, 300], 512, 3),
    ([7, 4, 0, 5], [0, 0, 0, 0], 16, 0),         # every slot inactive
    ([5, 5, 9, 7], [300, 300, 80, 64], 32, 10 + 7),  # cut at the budget:
                                                     # 300 + 212 lanes
])
def test_the_loop_runs_once_a_chunk(monkeypatch, term_ids, lens, chunk,
                                    trips):
    """The trip count comes from the data: as many window writes as the
    runs have chunks, none for an empty or inactive slot."""
    offsets, doc_ids, tfs = column(4096)
    lens = np.array(lens, np.int32)
    starts = offsets[term_ids]
    writes = []
    real = jax.lax.dynamic_update_slice
    monkeypatch.setattr(
        bm25.lax, "dynamic_update_slice",
        lambda *a, **kw: writes.append(1) or real(*a, **kw))
    with jax.disable_jit():
        d, tf = bm25._copy_runs(
            jnp.asarray(doc_ids), jnp.asarray(tfs), jnp.asarray(starts),
            jnp.asarray(lens), jnp.asarray(np.cumsum(lens) - lens),
            budget=512, pad_doc=PAD_DOC, chunk=chunk)
    assert len(writes) == 2 * trips      # one a column
    total = min(int(lens.sum()), 512)
    assert (np.asarray(d)[total:] == PAD_DOC).all()
    assert (np.asarray(tf)[total:] == 0.0).all()
    assert (np.asarray(tf)[:total] > 0.0).all()


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
