"""The one traffic generator: documents and arrivals from a mix's parameters.

A traffic mix is a JSON file under ``bench/traffic/`` (see its keys in
``PERF.md``).  Everything here is a function of the mix and ``--seed``.
Across seeds the *work* stays the same: document lengths and arrival gaps
are one fixed multiset per mix, drawn from a generator keyed by the mix
alone, and the seed only permutes them and draws the tokens.  So two
seeds differ in order and content, not in how much there is to do.

The corpus imitates the generator of the repo's synthetic imdb stream
(``data/streams.py``): log-normal lengths, a Zipf background over the
first 25k ids and class keywords at a per-token rate.
"""
from __future__ import annotations

import zlib

import numpy as np


def _fixed_rng(mix: dict, what: str) -> np.random.Generator:
    return np.random.default_rng(zlib.crc32(f"{mix['name']}:{what}".encode()))


def corpus(mix: dict, seed: int):
    """``(docs, classes)``: ``mix['corpus']['n_docs']`` int32 token arrays
    and the class each was written for."""
    c = mix["corpus"]
    n = int(c["n_docs"])
    lengths = np.clip(
        _fixed_rng(mix, "lengths").lognormal(np.log(c["mean_len"]),
                                             c["len_sigma"], n),
        c["min_len"], c["max_len"]).astype(np.int64)
    rng = np.random.default_rng(seed)
    lengths = lengths[rng.permutation(n)]
    classes = rng.choice(c["n_classes"], size=n, p=np.asarray(c["class_probs"]))
    total = int(lengths.sum())
    ranks = np.arange(1, c["background"] + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks)
    cdf /= cdf[-1]
    toks = np.searchsorted(cdf, rng.random(total)).astype(np.int32)
    owner = np.repeat(classes, lengths)
    kw = rng.random(total) < c["keyword_prob"]
    per = c["keywords_per_class"]
    toks[kw] = (c["vocab"] - 5000 + owner[kw] * per
                + rng.integers(0, per, int(kw.sum()))).astype(np.int32)
    docs = np.split(toks, np.cumsum(lengths)[:-1])
    return docs, classes.astype(np.int32)
