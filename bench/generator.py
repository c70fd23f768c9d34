"""One general generator for every traffic mix (``bench/traffic/<mix>.json``).

A mix is data: an open-loop arrival rate, length distributions of prompts
(or of questions about shared documents) and of answers, the documents
themselves, KV budgets and the service limits. Every seed gets the same
work in the same order: lengths are the quantiles of the mix's distribution
at (i + 1/2) / n and inter-arrival gaps the quantiles of the exponential
distribution, shuffled once by a fixed seed, so that runs with different
seeds differ in token ids and weights, not in what there is to do. Arrivals
are host seconds from the start of the measured window; every request is
due inside it.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
SCHEDULE_SEED = 0x5C4E


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths: quantiles of a lognormal (``median``, ``sigma``)
    clipped to [``min``, ``max``], rounded to ``multiple`` (default 1)."""
    mult = spec.get("multiple", 1)
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    v = np.exp(math.log(spec["median"]) + spec["sigma"] * np.asarray(z))
    v = np.clip(v, spec["min"], spec["max"])
    return (np.round(v / mult) * mult).astype(np.int64)


def arrival_times(rate_rps: float, seconds: float, rng) -> np.ndarray:
    """``round(rate * seconds)`` arrivals in [0, seconds): exponential
    quantile gaps in ``rng``'s order, scaled so the mean rate is exact.
    The gap nearest the mean closes the window after the last arrival, so
    the arrivals span the same time for every seed."""
    n = max(int(round(rate_rps * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    tail = int(np.argmin(np.abs(gaps - gaps.mean())))
    gaps = np.append(rng.permutation(np.delete(gaps, tail)), gaps[tail])
    t = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    span = t[-1] + gaps[-1]
    return t * (seconds / span)


@dataclasses.dataclass
class Request:
    rid: int
    arrival_s: float
    prompt: np.ndarray          # token ids (int32)
    max_new_tokens: int
    doc: Optional[int] = None   # index of the document the prompt opens with


@dataclasses.dataclass
class Traffic:
    requests: List[Request]
    documents: List[np.ndarray]     # prefilled into the prefix cache first


def build(mix: dict, vocab: int, seed: int, seconds: float) -> Traffic:
    """The seed's requests for one window of ``seconds``.

    The seed draws the token ids (and the documents'). The order of the
    arrival gaps, the lengths and the asks is one fixed order for every
    seed: which request queues behind which decides the tails, and runs
    whose order differed by seed spread several times wider than two runs
    of one seed."""
    rng = np.random.default_rng([int(seed), 0x7E57])
    order = np.random.default_rng(SCHEDULE_SEED)
    arrivals = arrival_times(mix["arrival"]["rate_rps"], seconds, order)
    n = len(arrivals)
    docs: List[np.ndarray] = []
    doc_of = [None] * n
    if "documents" in mix:
        dspec = mix["documents"]
        count = dspec["count"]
        # (length, times asked) pairs are the same for every seed: the
        # spare asks go to the longest documents; the order decides which
        # document is which and the order of the asks
        dl = lengths(dspec["tokens"], count)
        asks = np.full(count, n // count)
        asks[count - n % count:] += 1 if n % count else 0
        perm = order.permutation(count)
        dl, asks = dl[perm], asks[perm]
        docs = [rng.integers(0, vocab, int(m)).astype(np.int32) for m in dl]
        doc_of = [int(d) for d in order.permutation(
            np.repeat(np.arange(count), asks))]
    plens = order.permutation(lengths(mix["prompt_tokens"], n))
    alens = order.permutation(lengths(mix["answer_tokens"], n))
    reqs = []
    for i in range(n):
        own = rng.integers(0, vocab, int(plens[i])).astype(np.int32)
        prompt = own if doc_of[i] is None else np.concatenate(
            [docs[doc_of[i]], own])
        reqs.append(Request(rid=i, arrival_s=float(arrivals[i]),
                            prompt=prompt, max_new_tokens=int(alens[i]),
                            doc=doc_of[i]))
    return Traffic(requests=reqs, documents=docs)


def bucket(ntokens: int) -> int:
    """The served path's sequence bucket for a request of ``ntokens``
    prompt + answer tokens: the next power of two of ntokens + 1, >= 32."""
    return max(1 << int(ntokens).bit_length(), 32)


def buckets(mix: dict) -> List[int]:
    """Every bucket the mix's length ranges can reach, documents included
    (prefilled with a one-token answer)."""
    p, a = mix["prompt_tokens"], mix["answer_tokens"]
    lo, hi = p["min"] + a["min"], p["max"] + a["max"]
    if "documents" in mix:
        d = mix["documents"]["tokens"]
        lo, hi = lo + d["min"], hi + d["max"]
        out = {bucket(n) for n in range(d["min"] + 1, d["max"] + 2)}
    else:
        out = set()
    out |= {bucket(n) for n in range(lo, hi + 1)}
    return sorted(out)
