"""What keeps a new seed from compiling: the warm-up is an enumeration of
the configuration's program space, not of whatever one seed's queries
hit.  Checked from the benchmark's mirror of the plan signature and,
against the program itself, as a count of compiled programs."""

import pytest

from benchmarks import harness
from benchmarks.kinds import text_bm25
from bench_tiny import SEEDS, assert_bucket_rule, tiny_cell


def test_program_space_of_the_committed_configuration():
    cell = harness.load_cell("msmarco_closed")
    space = text_bm25.program_space(cell.cfg)
    # t_pad 4 (3-4 terms) and 8 (5-8 terms) x the bucket rule up to what
    # their terms can hold in a segment (no df passes the segment's docs)
    hi = cell.cfg["query_terms"][1]
    per_seg = cell.cfg["n_docs"] // cell.cfg["segments"]
    assert [t for t, _b in space] == sorted(t for t, _b in space)
    assert {t for t, _b in space} == {4, 8}
    for tp in (4, 8):
        assert_bucket_rule([b for t, b in space if t == tp],
                           min(tp, hi) * per_seg)
    assert [text_bm25.t_pad(n) for n in range(3, 9)] == [4, 4, 8, 8, 8, 8]
    assert [text_bm25.bucket(b) for b in (1, 4096, 4097, 16385, 10 ** 6)] \
        == [4096, 4096, 16384, 65536, 1048576]


@pytest.mark.parametrize("seed", SEEDS)
def test_warmup_covers_every_signature_the_query_maker_produces(seed):
    cell = tiny_cell("msmarco_closed")
    cfg = cell.cfg
    data = text_bm25.generate(cfg, seed)
    crafted = text_bm25.warmup_queries(cfg, data)
    warmed = set()
    for sig, terms in crafted:
        # the crafted query lands where it was aimed, in every segment
        assert {text_bm25.signature(cfg, data, terms, si)
                for si in range(cfg["segments"])} == {sig}
        assert cfg["query_terms"][0] <= len(terms) <= cfg["query_terms"][1]
        warmed.add(sig)
    produced = {text_bm25.signature(cfg, data, q, si)
                for q in text_bm25.queries(cfg, data, seed)
                for si in range(cfg["segments"])} - {None}
    assert produced <= warmed
    assert warmed <= set(text_bm25.program_space(cfg))


def test_query_lengths_are_one_multiset_for_every_seed():
    cell = tiny_cell("msmarco_closed")
    lens = []
    for seed in SEEDS:
        data = text_bm25.generate(cell.cfg, seed)
        qs = text_bm25.queries(cell.cfg, data, seed)
        lens.append(sorted(len(q) for q in qs))
        assert all(len(set(q)) == len(q) for q in qs)
    assert lens[0] == lens[1] == lens[2]
    assert sum(lens[0]) / len(lens[0]) == pytest.approx(6.0, abs=0.05)
    assert min(lens[0]) == 3 and max(lens[0]) == 8


def test_postings_match_a_count_from_the_tokens():
    """The seeded CSR that the installer and the reference both read,
    against a plain count."""
    cell = tiny_cell("msmarco_closed")
    data = text_bm25.generate(cell.cfg, 5)
    sd = data.segments[1]
    for doc in (0, 17, sd.n_docs - 1):
        toks = sd.tokens[sd.starts[doc]: sd.starts[doc + 1]].tolist()
        assert len(toks) == sd.lens[doc] == data.lens[sd.lo + doc]
        for t in set(toks):
            a, b = sd.offsets[t], sd.offsets[t + 1]
            where = sd.doc_ids[a:b].tolist().index(doc)
            assert sd.tfs[a + where] == toks.count(t)
            assert sd.df[t] == b - a
    assert int(sd.df.sum()) == len(sd.doc_ids) == sd.offsets[-1]


@pytest.mark.parametrize("name", ["msmarco_closed", "sift_paced"])
def test_a_new_seed_compiles_nothing_after_the_warm_up(cpu_kernels, name):
    """Against the program: after set-up, every query of the seed's list
    runs without one more executable (jax's own count)."""
    import jax

    # an earlier test of this process may have compiled these shapes:
    # set-up has to get its programs again, so that it counts them
    jax.clear_caches()
    cell = tiny_cell(name)
    session = harness.Session(cell, SEEDS[1], harness.device_info())
    try:
        before = session.counter.programs
        assert before == session.programs_setup > 0
        for qi in range(len(session.queries)):
            session.send(qi)
        assert session.counter.programs == before
        registry = session.served.stats()["device"]["compile_registry"]
        if name == "msmarco_closed":
            # the jit cache is the process's: other tests add to it
            assert registry["kernels"]["plan.run_topk"] >= len(
                text_bm25.warmup_queries(cell.cfg, session.data))
    finally:
        session.close()
