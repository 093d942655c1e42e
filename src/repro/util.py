"""Small shared utilities used across core / serve / kernels.

Kept free of import-time dependencies beyond the stdlib and JAX so every
layer can import it without cycles — ``core.batch`` packs device tensors
with it, the serving layer uses it for slot accounting, and every layer
names its host spans with :func:`span`.
"""

from __future__ import annotations

import os
from pathlib import Path

from jax.profiler import TraceAnnotation

# <repo>/.jax_cache: src/repro/util.py sits two levels below the repo root.
_DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def next_pow2(x: int) -> int:
    """Smallest power of two >= max(1, x) (``next_pow2(0) == 1``).

    The single source of truth for every power-of-two padding decision in
    the batch engine and the serving layer: bucket rows/width, batch-axis
    sub-batches, and the pad accounting derived from them. Keeping one
    helper means the packer and the schedulers can never round differently.
    """
    return 1 << max(0, int(x) - 1).bit_length()


class VirtualClock:
    """Deterministic engine clock for tests, simulators and benchmarks.

    Injected as ``ClusterBatcher(clock=...)`` (the engine clock is the only
    time source scheduling decisions see), so deadline/steal behaviour can
    be driven in virtual time and traces replay exactly. One definition for
    every call site — tests and benchmarks must not fork their own copies
    that could drift.
    """

    __slots__ = ("t",)

    def __init__(self, start: float = 0.0):
        self.t = start

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def span(name: str, **args) -> TraceAnnotation:
    """Host span ``repro.<name>`` for a ``with`` block, with scalar ``args``.

    It is a :class:`jax.profiler.TraceAnnotation`, so it lands in the same
    profiler session as the device trace, on its clock, nested under the
    spans open on the same thread, with ``args`` as the event's stats. With
    no profiler session active it records nothing and costs about a
    microsecond.
    """
    return TraceAnnotation("repro." + name, **args)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    For entry points (``chip_smoke.py``, ``benchmarks/``, ``examples/``),
    called before their first compile — never at library import. Uses
    ``JAX_COMPILATION_CACHE_DIR`` when it is set, else the fixed
    ``<repo>/.jax_cache``: the directory is part of what a later run must
    find again, so it is never derived from a pid, a temporary name or the
    time. Every program is written, however fast it compiled, because a
    serving warmup compiles hundreds of sub-second bucket programs.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        _DEFAULT_COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


__all__ = ["next_pow2", "VirtualClock", "span", "enable_compile_cache"]
