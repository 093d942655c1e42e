"""Data pipeline: dedup-by-correlation-clustering quality and the token
stream it keeps (the paper's first-class integration point)."""

import numpy as np
import pytest

from repro.data.dedup import dedup_corpus, dedup_quality, minhash_signatures, similarity_edges
from repro.data.synthetic import synthetic_corpus, token_stream


def test_minhash_similarity_reflects_jaccard():
    corpus = synthetic_corpus(n_docs=40, dup_fraction=0.5, mutate_p=0.05,
                              seed=1)
    sigs = minhash_signatures(corpus.docs, num_hashes=64)
    dup_pairs = [(i, int(corpus.duplicate_of[i]))
                 for i in range(len(corpus.docs))
                 if corpus.duplicate_of[i] >= 0]
    dup_sims = [np.mean(sigs[i] == sigs[j]) for i, j in dup_pairs]
    rng = np.random.default_rng(0)
    rand_sims = []
    orig = np.flatnonzero(corpus.duplicate_of < 0)
    for _ in range(50):
        i, j = rng.choice(orig, 2, replace=False)
        rand_sims.append(np.mean(sigs[i] == sigs[j]))
    assert np.mean(dup_sims) > 0.5 > np.mean(rand_sims) + 0.2


def test_dedup_end_to_end_quality():
    corpus = synthetic_corpus(n_docs=120, dup_fraction=0.4, mutate_p=0.05,
                              seed=2)
    res = dedup_corpus(corpus, threshold=0.45)
    q = dedup_quality(res, corpus)
    assert q["pairs_recall"] > 0.7, q
    assert q["pairs_precision"] > 0.9, q
    assert q["kept_fraction"] < 0.85, q


def test_dedup_distributed_matches_local():
    corpus = synthetic_corpus(n_docs=80, dup_fraction=0.4, seed=3)
    a = dedup_corpus(corpus, threshold=0.45, distributed=False, seed=5)
    b = dedup_corpus(corpus, threshold=0.45, distributed=True, seed=5)
    assert (a.labels == b.labels).all()


def test_similarity_graph_is_sparse():
    corpus = synthetic_corpus(n_docs=100, dup_fraction=0.3, seed=4)
    sigs = minhash_signatures(corpus.docs)
    edges = similarity_edges(sigs, threshold=0.45)
    n = len(corpus.docs)
    assert len(edges) < 0.1 * n * (n - 1) / 2, "graph should be sparse"


def test_token_stream_respects_keep_mask():
    corpus = synthetic_corpus(n_docs=20, dup_fraction=0.5, seed=5)
    keep = np.zeros(20, dtype=bool)
    keep[:5] = True
    s = token_stream(corpus, keep=keep)
    expect_len = sum(len(corpus.docs[i]) + 1 for i in range(5))
    assert len(s) == expect_len
