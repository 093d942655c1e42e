"""Serving example: continuous batching with prefill + KV-cache decode.

    PYTHONPATH=src python examples/serve_decode.py
"""

from repro.launch.serve import run
from repro.util import enable_compile_cache


def main():
    enable_compile_cache()
    reqs, stats = run("smollm-135m", smoke=True, n_requests=8, max_new=16,
                      max_slots=4, cache_len=96)
    print(f"prefills={stats.prefills} decode_steps={stats.decode_steps} "
          f"tokens={stats.emitted_tokens}")
    for r in reqs[:3]:
        print(f"  req {r.uid}: prompt[:6]={r.prompt[:6].tolist()} → "
              f"out={r.out_tokens}")


if __name__ == "__main__":
    main()
