"""Configuration kind ``text_positions``: one shard of full-text articles
whose ``text`` field is indexed with positions, asked exact phrases
(``match_phrase``, slop 0) alone, under required keywords, or as a boost
beside them.  Numpy only above ``install``.

An article is thousands of tokens long (a lognormal draw, cut) over a
Zipf-like vocabulary (``p(r) ~ 1 / r`` by the continuous law, inverted:
the head word is 4.8% of all tokens and lies in every article) and, as
real articles do, repeats its own words and its own phrases: each article
first draws a *pool text* from the vocabulary, ``pool_share`` of its
length, and is then written as snippets of that text (a snippet goes on
with probability ``continue_share`` a token, else jumps to a uniform place
of the pool) with a ``fresh_share`` of its tokens drawn anew.  The seed's
word is the document-major token list (``tokens``, ``starts``: what the
plain reference reads); it is inverted here into the term-major columns
the index holds (postings with a frequency each, and the positions of
every posting: what the program reads).

A query is a phrase of two or three consecutive tokens of one article, so
it has an answer: three in five of words outside the ``plain_from`` most
frequent terms, two in five with one word among the ``head_ranks`` most
frequent beside such words ("quality of life").  By turns the phrase is
sent alone, under a ``filter`` of further required keywords of the
article, or as a ``should`` beside a ``must`` of required keywords.
"""

from __future__ import annotations

import dataclasses
import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.kinds.text_bm25 import BUCKET_MIN, BUCKET_STEP, bucket, t_pad

FIELD = "body"
INDEX_BITS = 27                  # a segment's token index inside a sort key
# the phrase program's key (``plan.PhraseDims``), mirrored as the bag's is:
# the padded slot count and the anchor's ``1024 * 4^k`` bucket
SLOT_PAD_MIN, ANCHOR_BUCKET_MIN = 4, 1024
# (phrase words, one of them a head word): the share of every 20 queries
PHRASES = (((2, False), 7), ((2, True), 5), ((3, False), 5), ((3, True), 3))
SHAPES = ("phrase", "phrase", "phrase_filtered", "keywords_boosted")
GENERATE_THREADS = 5             # ~2 GB of sort in flight a segment
COMPILE_THREADS = 8              # crafted requests compiling side by side


def term_name(term: int) -> str:
    return f"t{int(term)}"


@dataclasses.dataclass
class SegmentText:
    lo: int                      # first shard-wide article number
    n_docs: int
    # document-major: what the seed says, and what the reference reads
    lens: np.ndarray             # int64 [n_docs]
    starts: np.ndarray           # int64 [n_docs + 1], into tokens
    tokens: np.ndarray           # int32 [tokens]
    # term-major: what the index holds
    df: np.ndarray               # int32 [vocab]
    offsets: np.ndarray          # int32 [vocab + 1], into doc_ids
    doc_ids: np.ndarray          # int32 [postings], segment-local
    tfs: np.ndarray              # float32 [postings]
    pos_offsets: np.ndarray      # int32 [postings + 1], into positions
    positions: np.ndarray        # int32 [tokens]

    def occurrences(self, terms) -> np.ndarray:
        """int64 [len(terms)]: the positions each term holds here."""
        terms = np.asarray(terms, dtype=np.int64)
        return (self.pos_offsets[self.offsets[terms + 1]].astype(np.int64)
                - self.pos_offsets[self.offsets[terms]])


@dataclasses.dataclass
class TextData:
    n_docs: int
    vocab: int
    segments: list
    lens: np.ndarray             # int64 [n_docs], shard-wide
    df: np.ndarray               # int64 [vocab], shard-wide

    def article(self, doc: int) -> np.ndarray:
        """The tokens of shard-wide article ``doc``."""
        sd = self.segments[doc // self.segments[0].n_docs]
        a, b = sd.starts[doc - sd.lo: doc - sd.lo + 2]
        return sd.tokens[a:b]


def _zipf(rng, n: int, vocab: int) -> np.ndarray:
    """``n`` int32 terms: the continuous 1 / x law on [1, vocab + 1),
    inverted and floored; term 0 is the most frequent."""
    u = rng.random(n, dtype=np.float32)
    u *= np.float32(math.log(vocab + 1))
    np.exp(u, out=u)
    return np.clip(u.astype(np.int32) - 1, 0, vocab - 1)


def _write(rng, cfg: dict, lens: np.ndarray, starts: np.ndarray,
           doc_of: np.ndarray) -> np.ndarray:
    """The segment's tokens, article after article (module docstring)."""
    n_tok, vocab = int(starts[-1]), cfg["vocab"]
    pool_len = np.maximum(
        np.ceil(lens * cfg["pool_share"]), 1).astype(np.int32)
    pool_starts = np.zeros(len(lens) + 1, dtype=np.int32)
    np.cumsum(pool_len, out=pool_starts[1:])
    pool = _zipf(rng, int(pool_starts[-1]), vocab)
    at = np.arange(n_tok, dtype=np.int32)
    jumps = rng.random(n_tok, dtype=np.float32) >= cfg["continue_share"]
    jumps[starts[:-1]] = True
    run_start = np.maximum.accumulate(np.where(jumps, at, 0))
    del jumps
    place = rng.integers(0, 1 << 30, size=n_tok, dtype=np.int32)[run_start]
    size = pool_len[doc_of]
    place %= size
    place += at - run_start
    place %= size
    place += pool_starts[:-1][doc_of]
    del at, run_start, size
    tokens = pool[place]
    del place
    fresh = np.flatnonzero(rng.random(n_tok, dtype=np.float32)
                           < cfg["fresh_share"])
    tokens[fresh] = _zipf(rng, len(fresh), vocab)
    return tokens


def _segment(seed_seq, lo: int, n: int, cfg: dict) -> SegmentText:
    rng = np.random.default_rng(seed_seq)
    vocab = cfg["vocab"]
    len_lo, len_hi = cfg["article_tokens"]
    sigma = cfg["length_sigma"]
    mu = math.log(cfg["length_mean"]) - sigma ** 2 / 2
    lens = np.clip(np.rint(rng.lognormal(mu, sigma, size=n)),
                   len_lo, len_hi).astype(np.int64)
    starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=starts[1:])
    n_tok = int(starts[-1])
    if n_tok >= 1 << INDEX_BITS:
        raise ValueError(f"{n_tok} tokens in a segment: the sort key "
                         f"holds {INDEX_BITS} bits of index")
    doc_of = np.repeat(np.arange(n, dtype=np.int32), lens)
    tokens = _write(rng, cfg, lens, starts, doc_of)
    # term-major: one sort of (term, index) turns the list; an index
    # ascends by article and by position inside it
    key = tokens.astype(np.int64)
    key <<= INDEX_BITS
    key |= np.arange(n_tok, dtype=np.int64)
    key.sort()
    at = (key & ((1 << INDEX_BITS) - 1)).astype(np.int32)
    key >>= INDEX_BITS
    term = key.astype(np.int32)
    del key
    doc = doc_of[at]
    del doc_of
    at -= starts[:-1].astype(np.int32)[doc]              # now the position
    first = np.empty(n_tok, dtype=bool)
    first[0] = True
    np.not_equal(term[1:], term[:-1], out=first[1:])
    first[1:] |= doc[1:] != doc[:-1]
    first = np.flatnonzero(first)
    pos_offsets = np.empty(len(first) + 1, dtype=np.int32)
    pos_offsets[:-1] = first
    pos_offsets[-1] = n_tok
    df = np.bincount(term[first], minlength=vocab).astype(np.int32)
    offsets = np.zeros(vocab + 1, dtype=np.int32)
    np.cumsum(df, out=offsets[1:])
    return SegmentText(
        lo=lo, n_docs=n, lens=lens, starts=starts, tokens=tokens, df=df,
        offsets=offsets, doc_ids=doc[first],
        tfs=np.diff(pos_offsets).astype(np.float32),
        pos_offsets=pos_offsets, positions=at)


def generate(cfg: dict, seed: int) -> TextData:
    n_docs, n_seg = cfg["n_docs"], cfg["segments"]
    if n_docs % n_seg:
        raise ValueError("segments must divide n_docs: equal segments "
                         "share one set of compiled programs")
    per = n_docs // n_seg
    seqs = np.random.SeedSequence([int(seed), 1]).spawn(n_seg)
    with ThreadPoolExecutor(max_workers=min(n_seg,
                                            GENERATE_THREADS)) as pool:
        segs = list(pool.map(
            lambda i: _segment(seqs[i], i * per, per, cfg), range(n_seg)))
    return TextData(
        n_docs=n_docs, vocab=cfg["vocab"], segments=segs,
        lens=np.concatenate([s.lens for s in segs]),
        df=np.sum([s.df.astype(np.int64) for s in segs], axis=0))


def index_body(cfg: dict) -> dict:
    return {"settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"properties": {FIELD: {"type": "text"}}}}


def install(node, index: str, cfg: dict, data: TextData) -> None:
    """Each segment's term-major columns, the positions among them, as a
    ``Segment`` adopted through the engine's segment-copy path (``_bulk``
    analyses ~600 documents a second); then the configuration's programs
    compiled side by side."""
    from opensearch_tpu.index.segment import PostingsField, Segment

    names = [term_name(t) for t in range(data.vocab)]
    segments, live = {}, {}
    for si, sd in enumerate(data.segments):
        n = sd.n_docs
        seg = Segment(f"bench_{si}", n)
        seg.doc_ids = [str(i) for i in range(sd.lo, sd.lo + n)]
        seg.id_to_local = {d: i for i, d in enumerate(seg.doc_ids)}
        seg.sources = [b"{}"] * n
        lens = sd.lens.astype(np.float32)
        seg.postings[FIELD] = PostingsField(
            terms={names[t]: t for t in np.flatnonzero(sd.df).tolist()},
            df=sd.df, offsets=sd.offsets, doc_ids=sd.doc_ids, tfs=sd.tfs,
            pos_offsets=sd.pos_offsets, positions=sd.positions,
            doc_lens=lens, total_len=float(sd.lens.sum()),
            docs_with_field=n, has_norms=True,
            present=np.ones(n, dtype=bool))
        segments[seg.seg_id] = seg
        live[seg.seg_id] = np.ones(n, dtype=bool).tobytes()
    ckpt = {"segments": list(segments), "live": live,
            "max_seq_no": data.n_docs - 1, "primary_term": 1}
    engine = node.indices.get(index).engine_for(0)
    engine.install_remote_checkpoint(ckpt, segments)
    check_program_key(engine.acquire_searcher(), cfg, data)
    compile_side_by_side(node, index, cfg, data)


def check_program_key(searcher, cfg: dict, data: TextData) -> None:
    """Before anything is staged or warmed: the program has to key a
    phrase as ``phrase_signature`` mirrors it, the padded slot count and
    one bucket, the rarest slot's.  A program that keys a bucket a slot
    and gathers every slot whole (until PR 39) would spend warm-up on the
    head words' millions of positions and compile inside the window."""
    from opensearch_tpu.search import compiler, query_dsl

    q = PhraseQuery("phrase", tuple(int(t) for t in data.article(0)[:2]))
    plan, bind = compiler.compile_query(
        query_dsl.parse_query(body(cfg, q)["query"]), searcher.ctx,
        scored=True)
    seg = next(s for s in searcher.segments if s.seg_id == "bench_0")
    dims, _ins = plan.prepare(bind, seg, None, searcher.ctx)
    want = phrase_signature(data.segments[0], q.phrase)
    if tuple(dims) != want:
        raise RuntimeError(
            f"the program keys the phrase {q.phrase} by {tuple(dims)}, "
            f"this configuration's warm-up enumerates {want}: it needs "
            f"search/plan.py::PhraseDims (PR 39)")


def compile_side_by_side(node, index: str, cfg: dict,
                         data: TextData) -> None:
    """``sparse_features``' ordering, for its reasons: the first crafted
    request alone (the first request to reach a segment stages it, and
    nothing keeps two first requests from each staging a copy), then the
    others at the same time, so that their programs compile side by
    side.  The harness's own pass then finds them compiled."""
    from opensearch_tpu.client import OpenSearch

    client = OpenSearch([f"http://127.0.0.1:{node.port}"], timeout=900.0)
    client.indices.refresh(index)
    crafted = [q for _sig, q in warmup_queries(cfg, data)]
    # first a request that needs every staged column: the positions and
    # (a scored bag's) the impacts
    crafted.sort(key=lambda q: q.shape != "keywords_boosted")
    bodies = [body(cfg, q) for q in crafted]

    def send(b: dict) -> None:
        resp = client.search(index=index, body=b)
        if resp.get("_shards", {}).get("failed", 1) or resp.get("timed_out"):
            raise RuntimeError(f"set-up request degraded: {resp}")

    send(bodies[0])
    if len(bodies) > 1:
        with ThreadPoolExecutor(max_workers=min(len(bodies) - 1,
                                                COMPILE_THREADS)) as pool:
            list(pool.map(send, bodies[1:]))


# -- queries ----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PhraseQuery:
    shape: str                   # one of SHAPES
    phrase: tuple                # terms, in the article's order
    keywords: tuple = ()         # the bag's terms, ascending

    def terms(self) -> tuple:
        return self.phrase + self.keywords


def _turns() -> list:
    """``PHRASES``' twenty kinds in a fixed order that spreads each
    kind evenly over the twenty turns."""
    at = [((j + 0.5) / share, i, kind)
          for i, (kind, share) in enumerate(PHRASES) for j in range(share)]
    return [kind for _at, _i, kind in sorted(at)]


def query_kinds(n: int, first=(0, 0, 0, 0)) -> list:
    """(shape, phrase words, head word) of ``n`` requests: request ``i``
    has shape ``SHAPES[i % 4]``, and the requests of one shape take
    ``PHRASES``' kinds in turn, from turn ``first[i % 4]``: every eighty
    requests in a row hold the same multiset, whatever the seed."""
    turns = _turns()
    return [(SHAPES[i % 4],) + turns[(i // 4 + first[i % 4]) % len(turns)]
            for i in range(n)]


def _find_phrase(rng, cfg: dict, article: np.ndarray, words: int,
                 head: bool):
    """A start in ``article`` of ``words`` consecutive tokens outside the
    ``plain_from`` most frequent terms, but for exactly one among the
    ``head_ranks`` most frequent when ``head``; None where it has none."""
    plain = (article >= cfg["plain_from"]).astype(np.int8)
    heads = (article < cfg["head_ranks"]).astype(np.int8)
    n = len(article) - words + 1
    n_plain = sum(plain[j: n + j] for j in range(words))
    n_head = sum(heads[j: n + j] for j in range(words))
    ok = np.flatnonzero((n_plain == words - head) & (n_head == int(head)))
    return int(rng.choice(ok)) if len(ok) else None


def queries(cfg: dict, data: TextData, seed: int) -> list:
    """``n_queries`` ``PhraseQuery``: the same multiset of kinds every
    seed (and every eighty requests in a row: a window's median does not
    follow the mix it drew), each shape's turns begun at another place."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    kinds = query_kinds(cfg["n_queries"],
                        rng.integers(0, 20, size=len(SHAPES)).tolist())
    out, seen = [], set()
    while len(out) < len(kinds):
        shape, words, head = kinds[len(out)]
        article = data.article(int(rng.integers(data.n_docs)))
        start = _find_phrase(rng, cfg, article, words, head)
        if start is None:
            continue
        phrase = tuple(int(t) for t in article[start: start + words])
        keywords = ()
        if shape != "phrase":
            # further required words of the article, a reader's choice:
            # none of the plain_from most frequent
            lo, hi = cfg["filter_words" if shape == "phrase_filtered"
                         else "must_words"]
            own = set(phrase) if shape == "keywords_boosted" else set()
            want = int(rng.integers(lo, hi + 1)) - len(own)
            more = np.setdiff1d(article[article >= cfg["plain_from"]],
                                list(phrase))
            if len(more) < want:
                continue
            keywords = tuple(sorted(own | {int(t) for t in rng.choice(
                more, size=max(want, 0), replace=False)}))
        q = PhraseQuery(shape, phrase, keywords)
        if q not in seen:                        # no window sends one twice
            seen.add(q)
            out.append(q)
    return out


def body(cfg: dict, q: PhraseQuery) -> dict:
    phrase = {"match_phrase": {FIELD: " ".join(map(term_name, q.phrase))}}
    bag = {"match": {FIELD: {
        "query": " ".join(map(term_name, q.keywords)), "operator": "and"}}}
    if q.shape == "phrase":
        query = phrase
    elif q.shape == "phrase_filtered":
        query = {"bool": {"must": [phrase], "filter": [bag]}}
    else:
        query = {"bool": {"must": [bag], "should": [phrase]}}
    return {"query": query, "size": cfg["k"], "_source": False}


# -- the programs a cell can need -------------------------------------------

def anchor_bucket(positions: int) -> int:
    b = ANCHOR_BUCKET_MIN
    while b < positions:
        b *= BUCKET_STEP
    return b


def phrase_signature(sd: SegmentText, phrase: tuple):
    """``PhraseDims`` as ``PhrasePlan.prepare`` keys a phrase in this
    segment: the padded slot count and the bucket of the slot with the
    fewest positions here (0 positions where a term is missing)."""
    slots = max(SLOT_PAD_MIN, t_pad(len(phrase)))
    held = sd.occurrences(phrase)
    return (slots, anchor_bucket(int(held.min()) if held.all() else 0))


def signature(cfg: dict, data: TextData, q: PhraseQuery, si: int):
    """The program key of ``q`` in segment ``si`` as (shape, phrase key,
    bag key), or None where the program prunes the segment: a term of the
    phrase missing (it is a ``must``, or the root), or a required keyword
    missing."""
    sd = data.segments[si]
    if q.shape != "keywords_boosted" and not sd.df[list(q.phrase)].all():
        return None
    if q.keywords and not sd.df[list(q.keywords)].all():
        return None
    bag = ((t_pad(len(q.keywords)),
            bucket(int(sd.df[list(q.keywords)].sum())))
           if q.keywords else None)
    return (q.shape, phrase_signature(sd, q.phrase), bag)


def _anchor_buckets(cfg: dict) -> list:
    """Buckets the rarest slot of a phrase can key: it is a word outside
    the ``plain_from`` most frequent, so it holds at most what the
    ``plain_from``-th word holds, reckoned at twice the law's mean."""
    per_seg = cfg["n_docs"] // cfg["segments"]
    most = (2 * per_seg * cfg["length_mean"]
            * math.log1p(1 / (cfg["plain_from"] + 1))
            / math.log(cfg["vocab"] + 1))
    out, b = [], ANCHOR_BUCKET_MIN
    while not out or out[-1] < most:
        out.append(b)
        b *= BUCKET_STEP
    return out


def _bag_buckets(cfg: dict, words: int) -> list:
    per_seg = cfg["n_docs"] // cfg["segments"]       # a df cannot pass it
    out, b = [], BUCKET_MIN
    while not out or out[-1] < words * per_seg:
        out.append(b)
        b *= BUCKET_STEP
    return out


def program_space(cfg: dict) -> list:
    """Every (shape, phrase key, bag key) a query of this configuration
    can produce: a function of the file, never of the seed."""
    lo, hi = cfg["phrase_words"]
    phrases = [(slots, b)
               for slots in sorted({max(SLOT_PAD_MIN, t_pad(w))
                                    for w in range(lo, hi + 1)})
               for b in _anchor_buckets(cfg)]
    out = [("phrase", p, None) for p in phrases]
    for shape, key in (("phrase_filtered", "filter_words"),
                       ("keywords_boosted", "must_words")):
        lo, hi = cfg[key]
        for tp in sorted({t_pad(n) for n in range(lo, hi + 1)}):
            for b in _bag_buckets(cfg, min(tp, hi)):
                out += [(shape, p, (tp, b)) for p in phrases]
    return out


class _Crafter:
    """Phrases and bags that land in a wanted bucket in *every* segment
    (equal segments then share the one program)."""

    ARTICLES = 400               # of segment 0, searched for a phrase

    def __init__(self, data: TextData):
        self.data = data
        self.held = np.stack([s.occurrences(np.arange(data.vocab))
                              for s in data.segments])
        self.dfs = np.stack([s.df.astype(np.int64) for s in data.segments])
        order = np.argsort(-self.dfs[0], kind="stable")  # head terms first
        self.order = order[(self.dfs[:, order] > 0).all(axis=0)]
        sd = data.segments[0]
        n = min(sd.n_docs, self.ARTICLES)
        self.tokens = sd.tokens[: int(sd.starts[n])]
        self.inner = np.ones(len(self.tokens) - 1, dtype=bool)
        self.inner[sd.starts[1:n] - 1] = False   # not across two articles
        self.taken = set()

    def phrases(self, slots: int, b: int, df_most: int = 0):
        """Pairs of consecutive tokens of an article whose rarer word
        keys bucket ``b``; with ``df_most``, whose summed df stays under
        it (a bag that holds them has to reach its own bucket)."""
        floor = 0 if b == ANCHOR_BUCKET_MIN else b // BUCKET_STEP
        fits = ((self.held > floor) & (self.held <= b)).all(axis=0)
        first, second = self.tokens[:-1], self.tokens[1:]
        here = (self.held > 0).all(axis=0)       # no segment prunes it
        ok = (self.inner & (fits[first] | fits[second])
              & here[first] & here[second])
        if df_most:
            ok &= self.dfs[0][first] + self.dfs[0][second] <= df_most
        for i in np.flatnonzero(ok)[:512]:
            phrase = (int(first[i]), int(second[i]))
            if phrase not in self.taken and all(
                    phrase_signature(s, phrase) == (slots, b)
                    for s in self.data.segments):
                yield phrase

    def bag(self, n: int, b: int, own: tuple = ()):
        """``n`` terms, ``own`` among them, whose summed df lands in
        bucket ``b``: ``own`` and neighbours in document-frequency
        order."""
        own = tuple(dict.fromkeys(own))
        floor = 0 if b == BUCKET_MIN else b // BUCKET_STEP
        base = self.dfs[:, list(own)].sum(axis=1, keepdims=True)
        n -= len(own)
        if n <= 0:
            ok = ((base > floor) & (base <= b)).all() and n == 0
            return tuple(sorted(own)) if ok else None
        order = self.order[~np.isin(self.order, list(own))]
        csum = np.concatenate(
            [np.zeros((len(self.dfs), 1), dtype=np.int64),
             np.cumsum(self.dfs[:, order], axis=1)], axis=1)
        sums = base + csum[:, n:] - csum[:, :-n]
        ok = np.flatnonzero(((sums > floor) & (sums <= b)).all(axis=0))
        if not len(ok):
            return None
        at = ok[np.argmin(np.abs(sums[0, ok] - (floor + b) // 2))]
        return tuple(sorted(own + tuple(int(t)
                                        for t in order[at: at + n])))


def warmup_queries(cfg: dict, data: TextData) -> list:
    """One crafted query per program of ``program_space``; a program
    that no phrase and bag of this corpus reach together is left out."""
    craft, out = _Crafter(data), []
    for shape, (slots, b), bag in program_space(cfg):
        boosted = shape == "keywords_boosted"
        for phrase in craft.phrases(slots, b, bag[1] if boosted else 0):
            keywords = ()
            if bag is not None:
                lo, hi = cfg["must_words" if boosted else "filter_words"]
                for n in range(min(bag[0], hi), max(bag[0] // 2, lo - 1),
                               -1):
                    keywords = craft.bag(n, bag[1],
                                         phrase if boosted else ())
                    if keywords is not None:
                        break
            if keywords is not None:
                craft.taken.add(phrase)
                out.append(((shape, (slots, b), bag),
                            PhraseQuery(shape, phrase, keywords)))
                break
    return out


# -- the work the algorithm needs (roofline denominators) ------------------

def work_bytes(cfg: dict, data: TextData, q: PhraseQuery) -> float:
    """Bytes an exact-phrase top-k over this shard has to move for one
    query, never what the kernel reads: in each segment searched the
    rarest slot's positions (4 B each) and posting entries (a doc id and
    a run start, 8 B) once and one 4 B probe a further slot an
    occurrence of the rarest; the keywords' bag by ``text_bm25``'s count
    (8 B a posting); and a write and a read of each searched segment's
    accumulator."""
    per_seg = cfg["n_docs"] // cfg["segments"]
    total = 0.0
    for si, sd in enumerate(data.segments):
        if signature(cfg, data, q, si) is None:
            continue
        total += per_seg * 4.0 * 2
        if q.keywords:
            total += float(sd.df[list(q.keywords)].sum()) * 8.0
        held = sd.occurrences(q.phrase)
        if held.all():
            j = int(np.argmin(held))
            total += (float(held[j]) * 4.0 * len(q.phrase)
                      + float(sd.df[q.phrase[j]]) * 8.0)
    return total


def work_flops(cfg: dict, data: TextData, q: PhraseQuery) -> float:
    """A compare a probe and an add a keyword's posting."""
    total = 0.0
    for sd in data.segments:
        held = sd.occurrences(q.phrase)
        if held.all():
            total += float(held.min()) * (len(q.phrase) - 1)
        if q.keywords:
            total += float(sd.df[list(q.keywords)].sum())
    return total
