"""The configuration ``msmarco-v2-passage-bm25-4shard`` and its cell
``msmarco_v2_mesh4`` at a size a test run can hold, on four of the CPU's
virtual devices: the committed file, the program space the mesh's keys
fall in, the plain reference and its two controls, and the cell run end
to end through the harness (one program a segment index of every shard,
one merge, one read a request)."""

import dataclasses

import pytest

from benchmarks import compare, harness
from benchmarks.kinds import text_bm25, text_bm25_mesh
from bench_tiny import LATE, SEEDS, last_line_ok, run_tiny

NAME = "msmarco_v2_mesh4"
# four shards of two segments of 1,024 passages
TINY = dict(n_docs=8192, segments=8, vocab=6000, n_queries=240,
            compare_max=48)


def tiny_mesh(**mix) -> harness.Cell:
    cell = harness.load_cell(NAME)
    return dataclasses.replace(cell, cfg={**cell.cfg, **TINY},
                               mix={**cell.mix, "warmup_s": 0.3, **mix})


def test_the_committed_configuration():
    cell = harness.load_cell(NAME)
    cfg = cell.cfg
    assert cell.chips == 4 and cfg["kind"] == "text_bm25_mesh"
    assert cfg["shards"] == 4 and text_bm25_mesh.per_shard(cfg) == 8
    assert cfg["n_docs"] // cfg["segments"] == 250_000
    assert list(cfg["reduced"]) == ["n_docs"]
    settings = text_bm25_mesh.index_body(cfg)["settings"]
    assert settings == {"number_of_shards": 4, "number_of_replicas": 0,
                        "search.mesh": True}
    # the program space is the one-chip kind's at the segment's size
    assert text_bm25_mesh.program_space(cfg) == text_bm25.program_space(cfg)
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"mesh_prepare_ms.lat", "mesh_launch_ms.lat",
            "mesh_programs_per_query.lat", "mesh_fallbacks_per_query.lat",
            "mesh_topk_roofline", "dispatches_per_query.lat"} <= names
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "latency_p50_ms", "setup_s"]


@pytest.mark.parametrize("seed", SEEDS)
def test_warmup_covers_every_key_the_mesh_takes(seed):
    """The key of segment index g is (t_pad, the largest of the shards'
    buckets): the crafted queries land where they were aimed in every
    segment index, and every query of the seed lands among them."""
    cfg = tiny_mesh().cfg
    data = text_bm25_mesh.generate(cfg, seed)
    per = text_bm25_mesh.per_shard(cfg)
    warmed = set()
    for sig, terms in text_bm25_mesh.warmup_queries(cfg, data):
        assert {text_bm25_mesh.signature(cfg, data, terms, g)
                for g in range(per)} == {sig}
        warmed.add(sig)
    produced = {text_bm25_mesh.signature(cfg, data, q, g)
                for q in text_bm25_mesh.queries(cfg, data, seed)
                for g in range(per)} - {None}
    assert produced <= warmed <= set(text_bm25_mesh.program_space(cfg))


def _numbers(cell, seed, precision):
    cfg = cell.cfg
    data = cell.kind.generate(cfg, seed)
    queries = cell.kind.queries(cfg, data, seed)[:48]
    exact = cell.reference.Reference(cfg, data)
    served = cell.reference.Reference(cfg, data, precision)
    rows = [[(str(i), s) for i, s in r] for r in served.topk_many(queries)]
    numbers = compare.compare(exact, queries, rows, cfg["k"])
    numbers.update(failed=0, device_faults=0)
    return numbers, cfg["limits"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_controls_are_rejected_and_the_reference_is_not(seed):
    cell = tiny_mesh()
    numbers, limits = _numbers(cell, seed, "float64")
    assert compare.verdict(numbers, limits)[0], numbers
    for control in ("bfloat16", "global_stats"):
        numbers, limits = _numbers(cell, seed, control)
        correct, lines = compare.verdict(numbers, limits)
        assert not correct, (control, lines)
        assert numbers["score_err"] > 20 * limits["score_err"], control


def test_the_reference_scores_a_shard_with_its_own_statistics():
    """A passage's score reads its own shard's idf and avgdl: equal
    passages in two shards score apart, and global statistics move
    both."""
    cell = tiny_mesh()
    data = cell.kind.generate(cell.cfg, SEEDS[0])
    terms = text_bm25_mesh.queries(cell.cfg, data, SEEDS[0])[1]
    own = cell.reference.Reference(cell.cfg, data).scores(terms)
    pooled = cell.reference.Reference(cell.cfg, data,
                                      "global_stats").scores(terms)
    hit = own > 0
    assert hit.any() and (own[hit] != pooled[hit]).all()


def test_a_tiny_cell_runs_on_four_devices(cpu_kernels):
    cell = tiny_mesh()
    per = text_bm25_mesh.per_shard(cell.cfg)
    result = run_tiny(cell, traced=True)
    last_line_ok(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] >= cell.chips
    m = {name: v["value"] for name, v in result["metrics"].items()}
    for name in ("mesh_programs_per_query.lat", "dispatches_per_query.lat"):
        assert per + 1 <= m[name] <= (per + 1) * LATE, (name, m[name])
    assert 1 <= m["d2h_reads_per_query.lat"] <= LATE
    assert 1 <= m["d2h_arrays_per_query.lat"] <= LATE
    assert per <= m["h2d_arrays_per_query.lat"] <= per * LATE
    assert m["mesh_fallbacks_per_query.lat"] == 0
    assert m["mesh_prepare_ms.lat"] > 0 and m["mesh_launch_ms.lat"] > 0
    assert m["compiles_in_window.lat"] == 0


def test_a_new_seed_compiles_nothing_after_the_warm_up(cpu_kernels,
                                                       monkeypatch):
    """Against the program: every key the mesh launches is in the
    configuration's program space and is the kind's signature of its
    segment index, and after set-up no query of a new seed gets one more
    executable."""
    import jax

    from opensearch_tpu.parallel import dist_search

    jax.clear_caches()
    launched = []
    real = dist_search.run_topk_mesh

    def run(mesh, axis, plan, dims, *rest):
        launched.append((dims[0], dims[1]))
        return real(mesh, axis, plan, dims, *rest)

    monkeypatch.setattr(dist_search, "run_topk_mesh", run)
    cell = tiny_mesh()
    cfg = cell.cfg
    session = harness.Session(cell, SEEDS[1], harness.device_info())
    try:
        before = session.counter.programs
        assert before == session.programs_setup > 0
        sent = []
        for qi in range(len(session.queries)):
            session.send(qi)
            sent.append(session.queries[qi])
        assert session.counter.programs == before
    finally:
        session.close()
    per = text_bm25_mesh.per_shard(cfg)
    data = session.data
    crafted = [terms for _sig, terms in
               text_bm25_mesh.warmup_queries(cfg, data)]
    tail = session.queries[len(session.head):]
    want = []
    for terms in crafted + tail + sent:
        for g in range(per):
            # a segment index no term occurs in runs at the least bucket
            want.append(text_bm25_mesh.signature(cfg, data, terms, g)
                        or (text_bm25.t_pad(len(terms)),
                            text_bm25.BUCKET_MIN))
    assert sorted(set(launched)) == sorted(set(want))
    assert set(launched) <= set(text_bm25_mesh.program_space(cfg))
