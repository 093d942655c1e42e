"""Measurement-driven block-shape autotuner for the batched Pallas kernels.

The paper's per-round budget is the frame: Theorem 26 bounds each
PIVOT/MIS round by work proportional to the capped adjacency width
(``W <= 12*lambda`` after the degree cap), so the engine's whole round cost
lives in two batched ELL sweeps — ``neighbor_min_ell_batch`` and
``label_agree_ell_batch``. Every bucket program the method/objective
registry composes (:mod:`repro.core.programs`) is built from these same
two kernels: the ``'pivot'`` MIS while-loop and the ``'precluster'``
constant-round propagation both run ``neighbor_min``; the ``'disagree'``
*and* ``'minmax'`` cost passes both reduce over ``label_agree`` counts.
Tuning is therefore keyed by kernel × shape, never by method or
objective — one warmup sweep's winners are baked into every registered
program at that bucket shape, and registering a new method can never
leave it running untuned blocks. The one free knob in those sweeps is
``block_rows``: the row-tile each Pallas grid step pipelines through
VMEM. Whether a 64-row or a 512-row tile meets the
per-round budget "as fast as the hardware allows" depends on ``(R, W,
batch tier, backend)`` — none of which is known at authoring time — so
this module measures instead of assuming: sweep a small candidate set over
*real packed bucket tensors* at warmup, keep the winner, and bake it into
the compiled bucket program. Block shape may change timing, never
labels/costs/picked — the bit-exactness contract is asserted for every
candidate in ``tests/test_autotune.py``.

Persistence: :class:`TuningCache` maps ``(backend, kernel, R, W,
batch_tier)`` → winning ``block_rows`` and serializes to JSON (explicit
path or the ``REPRO_TUNING_CACHE`` env var) so tuned shapes survive across
processes — a second process warms up with zero sweep timings (hit
counters prove it). Entries are *invalidated, never trusted*: a cached
winner is honoured only when its recorded backend and ``jax.__version__``
match the running process; stale entries count in ``stale`` and fall back
to a fresh sweep.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.neighbor_min import lane_tile
from repro.util import next_pow2

#: The hand-picked constant the kernels shipped with — the sweep baseline.
DEFAULT_BLOCK_ROWS = 256
#: Candidate row tiles (resolved to lane tiles per bucket before sweeping).
CANDIDATE_BLOCK_ROWS = (128, 256, 512)
#: The two batched kernels on the bucket program's hot path.
KERNELS = ("neighbor_min", "label_agree")
#: Tier cap: batch axes beyond this share one tuning entry.
MAX_BATCH_TIER = 1024

_CACHE_ENV = "REPRO_TUNING_CACHE"
_FORMAT_VERSION = 1


def batch_tier(b: int) -> int:
    """Pow2 tier of a packed batch axis ``B = G_pad * k`` (capped).

    Buckets are swept and cached per tier, not per exact B: the packed
    batch axis is already pow2-padded by the executors, so tiers are what
    actually reaches the device.
    """
    return min(MAX_BATCH_TIER, next_pow2(max(1, int(b))))


def candidate_blocks(r: int,
                     candidates: Optional[Sequence[int]] = None
                     ) -> Tuple[int, ...]:
    """Candidate ``block_rows`` for a bucket of R rows: the sweep set
    resolved to the kernels' lane tiles (:func:`~repro.kernels.
    neighbor_min.lane_tile`), deduplicated order-preserving, always
    containing the default's tile so "tuned vs default" is measured, never
    inferred. Sizes with one tile are one program and are timed once."""
    cands = CANDIDATE_BLOCK_ROWS if candidates is None else tuple(candidates)
    out: List[int] = []
    for c in (*cands, DEFAULT_BLOCK_ROWS):
        c = lane_tile(int(c), int(r))
        if c not in out:
            out.append(c)
    return tuple(out)


def lane_tiles(r: int, block_rows) -> Optional[Tuple[int, int]]:
    """The ``(neighbor_min, label_agree)`` lane tiles a block-row pair
    compiles to at R rows, or None when both are the default's tile (the
    untuned program key, so an equivalent pair never compiles twice)."""
    tiles = tuple(lane_tile(int(b), int(r)) for b in block_rows)
    default = lane_tile(DEFAULT_BLOCK_ROWS, int(r))
    return None if tiles == (default, default) else tiles


class TuningCache:
    """Persistent ``(backend, kernel, R, W, batch_tier) -> block_rows`` map.

    File format (versioned JSON)::

        {"version": 1,
         "entries": {
            "cpu/neighbor_min/128x16/b64": {
                "block_rows": 128,
                "backend": "cpu",
                "jax_version": "0.4.37",
                "timings_ms": {"64": 1.9, "128": 1.4},
                "speedup_vs_default": 1.36}}}

    Invalidation rule: an entry is honoured only when its ``backend`` and
    ``jax_version`` match the running process — anything else is counted
    as ``stale`` and treated as a miss (stale entries are ignored, never
    trusted). Counters (``hits``/``misses``/``stale``/``sweeps``) are
    process-local telemetry, not persisted.
    """

    def __init__(self, path: Optional[str] = None, autoload: bool = True):
        if path is None:
            path = os.environ.get(_CACHE_ENV) or None
        self.path = path
        self._entries: Dict[str, dict] = {}
        self.hits = 0
        self.misses = 0
        self.stale = 0
        self.sweeps = 0          # completed kernel sweeps this process
        self.sweep_log: List[dict] = []
        if self.path and autoload:
            self.load()

    @staticmethod
    def _key(backend: str, kernel: str, r: int, w: int, tier: int) -> str:
        return f"{backend}/{kernel}/{int(r)}x{int(w)}/b{int(tier)}"

    def get(self, kernel: str, r: int, w: int, tier: int,
            backend: Optional[str] = None, count: bool = True
            ) -> Optional[int]:
        """Winning ``block_rows`` or None (miss / stale). ``count=False``
        keeps hot-path resolution out of the warmup hit/miss counters."""
        backend = backend or jax.default_backend()
        entry = self._entries.get(self._key(backend, kernel, r, w, tier))
        if entry is None:
            if count:
                self.misses += 1
            return None
        if (entry.get("backend") != backend
                or entry.get("jax_version") != jax.__version__):
            if count:
                self.stale += 1
                self.misses += 1
            return None
        if count:
            self.hits += 1
        return int(entry["block_rows"])

    def put(self, kernel: str, r: int, w: int, tier: int, block_rows: int,
            backend: Optional[str] = None,
            meta: Optional[dict] = None) -> None:
        backend = backend or jax.default_backend()
        entry = {"block_rows": int(block_rows), "backend": backend,
                 "jax_version": jax.__version__}
        if meta:
            entry.update(meta)
        self._entries[self._key(backend, kernel, r, w, tier)] = entry

    def load(self) -> int:
        """Merge entries from ``path`` (missing/corrupt files are treated
        as empty — a tuning cache is an optimization, never a hard dep)."""
        if not self.path or not os.path.exists(self.path):
            return 0
        try:
            with open(self.path) as f:
                blob = json.load(f)
        except (OSError, ValueError):
            return 0
        if not isinstance(blob, dict) or blob.get("version") != _FORMAT_VERSION:
            return 0
        entries = blob.get("entries")
        if not isinstance(entries, dict):
            return 0
        loaded = 0
        for key, entry in entries.items():
            if isinstance(entry, dict) and "block_rows" in entry:
                self._entries[key] = entry
                loaded += 1
        return loaded

    def save(self) -> None:
        if not self.path:
            return
        blob = {"version": _FORMAT_VERSION, "entries": self._entries}
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(blob, f, indent=2, sort_keys=True)
        os.replace(tmp, self.path)

    def info(self) -> dict:
        """Engine-side telemetry block (serialization-safe)."""
        return {
            "path": self.path,
            "entries": len(self._entries),
            "hits": self.hits,
            "misses": self.misses,
            "stale": self.stale,
            "sweeps": self.sweeps,
            "backend": jax.default_backend(),
            "jax_version": jax.__version__,
        }


# Process-default cache (lazy): the executor's hot-path resolution and the
# serving warmup must read the same winners or program keys would lie.
_cache: Optional[TuningCache] = None


def tuning_cache() -> TuningCache:
    global _cache
    if _cache is None:
        _cache = TuningCache()
    return _cache


def set_tuning_cache(cache: Optional[TuningCache]) -> Optional[TuningCache]:
    """Swap the process-default cache (tests / explicit paths); returns the
    previous one. ``None`` resets to lazy env-var resolution."""
    global _cache
    prev = _cache
    _cache = cache
    return prev


def tuning_info() -> dict:
    """Default-cache counters + sweep log — the engine-side telemetry."""
    cache = tuning_cache()
    out = cache.info()
    out["sweep_log"] = list(cache.sweep_log)
    return out


def resolve_block_rows(shape) -> Optional[Tuple[int, int]]:
    """Tuned ``(neighbor_min, label_agree)`` lane tiles for a packed
    ``(B, R, W)`` shape, or None when the bucket tier is untuned or tuned
    to the default's tile (the program key then stays on the legacy
    default and the kernels use ``DEFAULT_BLOCK_ROWS``). Pure dict reads —
    safe on the hot path."""
    b, r, w = (int(s) for s in shape)
    tier = batch_tier(b)
    cache = tuning_cache()
    nm = cache.get("neighbor_min", r, w, tier, count=False)
    la = cache.get("label_agree", r, w, tier, count=False)
    if nm is None and la is None:
        return None
    return lane_tiles(r, (nm if nm is not None else DEFAULT_BLOCK_ROWS,
                          la if la is not None else DEFAULT_BLOCK_ROWS))


def sweep_bucket(ell, ranks_p, elig_p,
                 cache: Optional[TuningCache] = None,
                 candidates: Optional[Sequence[int]] = None,
                 repeats: int = 3) -> List[dict]:
    """Time both batched kernels over real packed bucket tensors across the
    clamped candidate set; record winners (and timings) in the cache.

    The measurement inputs are the *actual* packed ELL/state tensors a
    flush of this bucket would run, not synthetic shapes — sparsity
    patterns and pad rows are part of what the sweep prices. Each
    candidate is compiled (first call, untimed) then timed best-of-
    ``repeats`` with ``block_until_ready``. Returns one sweep record per
    kernel; also appended to ``cache.sweep_log``.

    One sweep serves every registered bucket program at this shape: the
    ``neighbor_min`` timing covers both the MIS loop and the precluster
    propagation (same kernel, same tensors, different trip counts), and
    the ``label_agree`` timing covers both registered cost passes — the
    ``'minmax'`` objective consumes the same per-vertex agreement counts
    the ``'disagree'`` reduction does, so its hot kernel is tuned by this
    sweep without a separate pass.
    """
    from repro.kernels import ops as _kops
    from repro.kernels.neighbor_min import prepare_ell

    cache = cache if cache is not None else tuning_cache()
    ell = jnp.asarray(ell)
    ranks_p = jnp.asarray(ranks_p)
    active_p = jnp.asarray(elig_p)
    b, r, w = (int(s) for s in ell.shape)
    tier = batch_tier(b)
    cands = candidate_blocks(r, candidates)
    default_br = lane_tile(DEFAULT_BLOCK_ROWS, r)
    # Labels for the cost-pass kernel: contents don't affect timing (the
    # memory/grid shape does), so any valid labeling with the -1 pad
    # sentinel works.
    labels_p = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(r, dtype=jnp.int32), (b, r)),
         jnp.full((b, 1), -1, jnp.int32)], axis=1)
    # Prepared once, as the bucket programs do, so the timings are the
    # kernels' alone.
    layout = prepare_ell(ell)
    runs = {
        "neighbor_min": lambda br: _kops.neighbor_min_ell_batch(
            layout, ranks_p, active_p, block_rows=br),
        "label_agree": lambda br: _kops.label_agree_ell_batch(
            layout, labels_p, block_rows=br),
    }
    records: List[dict] = []
    for kernel in KERNELS:
        fn = runs[kernel]
        timings: Dict[int, float] = {}
        for br in cands:
            jax.block_until_ready(fn(br))        # compile outside the timing
            best = float("inf")
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(br))
                best = min(best, time.perf_counter() - t0)
            timings[br] = best
        winner = min(cands, key=timings.__getitem__)
        speedup = timings[default_br] / max(timings[winner], 1e-12)
        record = {
            "kernel": kernel, "R": r, "W": w, "batch": b, "tier": tier,
            "candidates": list(cands),
            "timings_ms": {str(br): t * 1e3 for br, t in timings.items()},
            "winner": winner,
            "default_block_rows": default_br,
            "default_ms": timings[default_br] * 1e3,
            "winner_ms": timings[winner] * 1e3,
            "speedup_vs_default": speedup,
        }
        cache.put(kernel, r, w, tier, winner,
                  meta={"timings_ms": record["timings_ms"],
                        "speedup_vs_default": speedup})
        cache.sweeps += 1
        cache.sweep_log.append(record)
        records.append(record)
    cache.save()
    return records


def host_provenance() -> dict:
    """Host/runtime metadata stamped into benchmark JSONs so the perf
    trajectory is comparable across machines, plus the tuning-cache state
    (the invalidation key — backend + jax version — lives here too)."""
    import platform

    dev = jax.devices()[0]
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "device_count": jax.device_count(),
        "cpu_count": os.cpu_count(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "tuning_cache": tuning_cache().info(),
    }


__all__ = [
    "DEFAULT_BLOCK_ROWS",
    "CANDIDATE_BLOCK_ROWS",
    "KERNELS",
    "TuningCache",
    "batch_tier",
    "candidate_blocks",
    "lane_tiles",
    "tuning_cache",
    "set_tuning_cache",
    "tuning_info",
    "resolve_block_rows",
    "sweep_bucket",
    "host_provenance",
]
