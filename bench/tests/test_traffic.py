"""The generator: one seed gives one schedule, every seed the same work,
and the mixes share prefixes as their cells say."""
import json
from pathlib import Path

import numpy as np

from bench import generator as G

SPEC = json.loads((Path(G.__file__).resolve().parents[1]
                   / "BENCHMARK.json").read_text())
SECONDS = float(SPEC["run_seconds"])
VOCAB = 152064


def _schedule(mix, seed):
    t = G.build(mix, VOCAB, seed, SECONDS)
    return t, [(r.arrival_s, r.max_new_tokens, tuple(r.prompt))
               for r in t.requests]


def test_one_seed_one_schedule():
    for name in ("docqa", "chat"):
        mix = G.load_mix(name)
        assert _schedule(mix, 2 ** 33 + 9)[1] == _schedule(mix, 2 ** 33 + 9)[1]
        assert _schedule(mix, 1)[1] != _schedule(mix, 2)[1]


def test_every_seed_the_same_work():
    """Seeds differ in token ids only: arrivals, lengths and the document
    each request asks about are one schedule."""
    for name in ("docqa", "chat"):
        mix = G.load_mix(name)
        a = G.build(mix, VOCAB, 3, SECONDS)
        b = G.build(mix, VOCAB, 2 ** 31 + 17, SECONDS)

        def shape(t):
            return [(r.arrival_s, r.max_new_tokens, len(r.prompt), r.doc)
                    for r in t.requests], [len(d) for d in t.documents]
        assert shape(a) == shape(b)
        assert [tuple(r.prompt) for r in a.requests] != \
            [tuple(r.prompt) for r in b.requests]
        assert len(a.requests) == round(mix["arrival"]["rate_rps"] * SECONDS)
        assert max(r.arrival_s for r in a.requests) < SECONDS


def _block_hits(prompts, cached, block=16):
    """Prompt tokens a block-granular prefix cache would serve, each
    prompt looked up against ``cached`` prompts and those before it."""
    seen = set()
    for p in cached:
        for i in range(block, len(p) + 1, block):
            seen.add(tuple(p[:i]))
    hits = []
    for p in prompts:
        h = 0
        for i in range(block, len(p), block):
            if tuple(p[:i]) in seen:
                h = i
        hits.append(h)
        for i in range(block, len(p) + 1, block):
            seen.add(tuple(p[:i]))
    return hits


def test_chat_shares_no_prefix():
    t = G.build(G.load_mix("chat"), VOCAB, 7, SECONDS)
    prompts = [list(r.prompt) for r in t.requests]
    hits = _block_hits(prompts, [])
    assert sum(hits) / sum(map(len, prompts)) < 1e-3


def test_docqa_requests_hit_a_whole_document():
    mix = G.load_mix("docqa")
    t = G.build(mix, VOCAB, 7, SECONDS)
    docs = [list(d) for d in t.documents]
    assert all(len(d) % mix["prefill_chunk"] == 0 for d in docs)
    for r in t.requests:
        assert list(r.prompt[:len(docs[r.doc])]) == docs[r.doc]
    hits = _block_hits([list(r.prompt) for r in t.requests], docs)
    assert all(h >= len(docs[r.doc]) for h, r in zip(hits, t.requests))
    asked = np.bincount([r.doc for r in t.requests], minlength=len(docs))
    assert asked.max() - asked.min() <= 1
    # a document is asked again after its first restore, 3-5 times a window
    assert asked.min() >= 3


def test_buckets_cover_every_request():
    for name in ("docqa", "chat"):
        mix = G.load_mix(name)
        got = set(G.buckets(mix))
        t = G.build(mix, VOCAB, 11, SECONDS)
        need = {G.bucket(len(r.prompt) + r.max_new_tokens)
                for r in t.requests}
        need |= {G.bucket(len(d) + 1) for d in t.documents}
        assert need <= got
