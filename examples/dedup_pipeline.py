"""Data-pipeline integration: near-dedup a corpus with Algorithm 4 + PIVOT.

    PYTHONPATH=src python examples/dedup_pipeline.py
"""

from repro.data.dedup import dedup_corpus, dedup_quality
from repro.data.synthetic import synthetic_corpus, token_stream
from repro.util import enable_compile_cache


def main():
    enable_compile_cache()
    corpus = synthetic_corpus(n_docs=200, dup_fraction=0.4, mutate_p=0.05,
                              seed=0)
    res = dedup_corpus(corpus, threshold=0.45)
    q = dedup_quality(res, corpus)
    print(f"similarity graph edges: {res.n_edges}")
    print(f"clusters: {q['clusters']}  kept: {q['kept_fraction']:.1%} of docs")
    print(f"pairs precision {q['pairs_precision']:.3f} / "
          f"recall {q['pairs_recall']:.3f}")
    stream = token_stream(corpus, keep=res.keep)
    print(f"training stream: {len(stream)} tokens after dedup "
          f"(vs {len(token_stream(corpus))} raw)")


if __name__ == "__main__":
    main()
