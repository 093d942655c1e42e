"""Device-side execution layer of the batch engine: programs + executors.

The "how it runs" half of the plan/executor split (packing and bucketing
live in :mod:`repro.core.plan`). Three pieces:

**The fused bucket programs** — one jit program per ``(B, R, W)`` bucket
shape × registered ``(method, objective)`` combination, composed from the
method registry in :mod:`repro.core.programs`: the method's rounds body
(MIS ``lax.while_loop`` for ``'pivot'``, straight-line constant-round
agreement for ``'precluster'``), the objective's cost pass
(``'disagree'`` / ``'minmax'``) and the shared best-of-k argmin run
entirely on device, so only winning labels / costs / sample indices cross
back to the host. Every batch entry is independent of every other, which
is what makes both async overlap and data-parallel sharding
semantics-preserving.

**The compiled-program cache** — :func:`run_bucket_program` resolves each
``(shape, k, kernel, donation, mesh, method, objective)`` request through
a bounded LRU of jit instances. Methods sharing one *program family*
(``'pivot'`` / ``'pivot_raw'``) share compiled programs. Long-lived
servers seeing many bucket shapes hold at most
:func:`program_cache_capacity` compiled programs; evictions and
compiles are counted (:func:`program_cache_info`) instead of growing
memory without limit. The LRU takes *hints* from layers that know more
than the access order: :func:`program_cache_contains` is a non-mutating
probe (the serving cost model prices the compile a candidate flush shape
would pay), :func:`program_cache_touch` refreshes a bucket shape's recency
and :func:`program_cache_pin` / :func:`program_cache_unpin` protect a hot
bucket shape's programs from eviction while cold shapes churn through the
cache (the scheduler's ``on_retire`` heat tracking drives these). Pins are
preferences, not leaks: capacity stays a hard bound — when every resident
program is pinned the LRU victim is evicted anyway.

**Bucket executors** — the :class:`BucketExecutor` protocol decouples the
serving layer from *how* a packed bucket reaches the device:

* :class:`SyncExecutor` — the classic path: dispatch, block, fetch. One
  bucket at a time, results available the moment ``submit`` returns.
* :class:`AsyncExecutor` — non-blocking dispatch returning
  :class:`InFlightBucket` handles; the caller packs/flushes the next
  bucket while the previous one computes and transfers (JAX async
  dispatch). ``retire()`` harvests completed handles without blocking;
  ``drain()`` blocks for everything outstanding.
* :class:`ShardedExecutor` — data-parallel ``shard_map`` over the pow2
  group axis across the local device mesh
  (:func:`repro.core.dist.pow2_device_mesh`), so one flush spans all local
  devices: the MPC "more machines" axis. Group padding is raised to the
  device count so the batch axis splits evenly; padded entries are inert.

All three executors satisfy the same bit-exactness contract as the
per-graph engine — for matching keys, labels / costs / picked sample
indices are identical — because the program they run is the same per-entry
computation (asserted for every executor in ``tests/test_executor.py``).
"""

from __future__ import annotations

import os
import time
import warnings
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from functools import partial, update_wrapper
from typing import Any, Callable, Deque, List, Optional, Protocol, Tuple, \
    runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.util import next_pow2, span

from .programs import IN_MIS, REMOVED, UNDECIDED, bucket_impl, \
    method_spec, objective_spec

# ---------------------------------------------------------------------------
# Fused device programs: rounds body + cost pass + best-of-k argmin, composed
# from the method/objective registries in repro.core.programs.
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Bounded LRU of compiled bucket programs.
# ---------------------------------------------------------------------------

_DEFAULT_CACHE_CAPACITY = 256

_program_cache: "OrderedDict[tuple, Callable]" = OrderedDict()
_program_cache_capacity = _DEFAULT_CACHE_CAPACITY
_program_cache_evictions = 0
_program_cache_compiles = 0
# Pinned (R, W) bucket shapes → pin count. Refcounted because pins are
# process-global while pinners (engines' heat trackers) are not: two
# engines sharing a hot shape must not have one engine's teardown strip
# the other's eviction protection.
_program_cache_pins: dict = {}


def _mesh_cache_key(mesh: Optional[Mesh]):
    return None if mesh is None else tuple(d.id for d in mesh.devices.flat)


def _program_key(shape, k: int, use_kernel: bool, donate: bool,
                 mesh: Optional[Mesh],
                 block_rows: Optional[Tuple[int, int]] = None,
                 program: str = "pivot",
                 objective: str = "disagree") -> tuple:
    """The cache key for one compiled bucket program — single definition so
    :func:`run_bucket_program` and the :func:`program_cache_contains` probe
    can never disagree about identity. ``block_rows`` is the *resolved*
    kernel lane-tile pair (None on the jnp path and at the default
    tiles), so a tuning-cache update yields a new program at the new
    shape instead of mutating a compiled one. ``program`` is the method's
    *program family* (``method_spec(m).program``, so ``'pivot'`` and
    ``'pivot_raw'`` share compiled programs) and ``objective`` its cost
    pass; every key carries both."""
    return (tuple(int(s) for s in shape), k, use_kernel, donate,
            _mesh_cache_key(mesh), block_rows, program, objective)


def _resolve_block_rows(shape, use_kernel: bool,
                        block_rows=None) -> Optional[Tuple[int, int]]:
    """Static kernel lane tiles a bucket program of ``shape`` will bake
    in: the caller's explicit pair, else the tuning-cache winners, each
    resolved to its lane tile; None at the default tiles (so untuned
    buckets share one program) and when the kernels are not in play at
    all."""
    if not use_kernel:
        return None
    from repro.kernels.autotune import lane_tiles, resolve_block_rows

    if block_rows is None:
        return resolve_block_rows(shape)
    if not isinstance(block_rows, (tuple, list)):
        block_rows = (block_rows, block_rows)
    return lane_tiles(shape[1], block_rows)


def _key_bucket(key: tuple) -> Tuple[int, int]:
    """(R, W) bucket shape of a cache key's packed (B, R, W) shape."""
    shape = key[0]
    return (shape[1], shape[2])


def _bucket_and_tiles(ell, ranks_p, elig_p, m_edges, **static):
    """:func:`bucket_impl`'s outputs and, per batch entry, the ELL tiles
    the kernels sweep and all of them (``kernels.neighbor_min.
    tile_counts``), from the one layout the program prepares."""
    from repro.kernels.neighbor_min import prepare_ell, tile_counts

    layout = prepare_ell(ell) if static["use_kernel"] else None
    outs = bucket_impl(ell, ranks_p, elig_p, m_edges, layout=layout,
                       **static)
    return (*outs, tile_counts(ell, layout))


def _build_program(k: int, use_kernel: bool, donate: bool,
                   mesh: Optional[Mesh],
                   block_rows: Optional[Tuple[int, int]] = None,
                   program: str = "pivot",
                   objective: str = "disagree") -> Callable:
    # The partial takes the bucket program's name, so XLA names the
    # program ``jit_bucket_impl`` (a bare partial lowers as
    # ``jit__unknown``).
    impl = update_wrapper(partial(_bucket_and_tiles, k=k,
                                  use_kernel=use_kernel,
                                  block_rows=block_rows, program=program,
                                  objective=objective), bucket_impl)
    if mesh is not None:
        axis = mesh.axis_names[0]
        spec = P(axis)
        # Every entry is independent, so the outputs shard like the
        # inputs; nothing is replicated for the checker to verify.
        impl = jax.shard_map(impl, mesh=mesh,
                             in_specs=(spec, spec, spec, spec),
                             out_specs=(spec,) * 5,
                             check_vma=False)
    return jax.jit(impl, donate_argnums=(0, 1, 2, 3) if donate else ())


def _evict_to_capacity() -> None:
    global _program_cache_evictions
    while len(_program_cache) > _program_cache_capacity:
        # LRU order, skipping pinned bucket shapes; capacity is a hard
        # bound, so when everything left is pinned the LRU loses anyway.
        victim = next((key for key in _program_cache
                       if _key_bucket(key) not in _program_cache_pins),
                      None)
        if victim is None:
            victim = next(iter(_program_cache))
        fn = _program_cache.pop(victim)
        _program_cache_evictions += 1
        clear = getattr(fn, "clear_cache", None)
        if clear is not None:       # drop the compiled executable eagerly
            clear()


def program_cache_size() -> int:
    """Number of compiled bucket programs resident (benchmark: O(#buckets))."""
    return len(_program_cache)


def program_cache_capacity() -> int:
    return _program_cache_capacity


def set_program_cache_capacity(capacity: int) -> int:
    """Bound the compiled-program LRU; returns the previous capacity.

    Long-lived servers seeing many bucket shapes would otherwise accumulate
    one compiled executable per ``(B, R, W, k, kernel, donation, mesh)``
    combination forever. The default (256) is generous — a workload that
    legitimately cycles through more shapes than this pays recompiles on
    the evicted ones (correctness is unaffected; tested).
    """
    global _program_cache_capacity
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    prev = _program_cache_capacity
    _program_cache_capacity = capacity
    _evict_to_capacity()
    return prev


def program_cache_contains(shape, k: int, use_kernel: bool = False,
                           donate: bool = False,
                           mesh: Optional[Mesh] = None,
                           block_rows=None,
                           method: str = "pivot",
                           objective: str = "disagree") -> bool:
    """Non-mutating probe: is this exact bucket program compiled?

    Unlike a real run this never touches the LRU order, so the serving
    cost model can price the compile a candidate (coalesced) flush shape
    would pay without distorting the recency the eviction decision reads.
    ``block_rows`` resolves exactly as :func:`run_bucket_program` does
    (explicit pair > tuning-cache winners > None), and ``method``
    resolves through the registry to its program family, so probe and run
    can never disagree about which program a flush would use.
    """
    resolved = _resolve_block_rows(shape, use_kernel, block_rows)
    return _program_key(shape, k, use_kernel, donate, mesh, resolved,
                        program=method_spec(method).program,
                        objective=objective) in _program_cache


def program_cache_touch(bucket: Tuple[int, int]) -> int:
    """Refresh the LRU recency of every program of one ``(R, W)`` bucket
    shape; returns how many were touched.

    The cache's own order only updates when a program *runs* — the
    scheduler, which sees the request stream, can know a shape is about to
    be hot again before the next run does.
    """
    touched = 0
    for key in [key for key in _program_cache if _key_bucket(key) == bucket]:
        _program_cache.move_to_end(key)
        touched += 1
    return touched


def program_cache_pin(bucket: Tuple[int, int]) -> int:
    """Protect a bucket shape's programs from eviction (scheduler heat
    hint); returns the number currently resident. Pinning is durable —
    programs of this shape compiled later are protected too — and is a
    preference, not a leak: capacity remains a hard bound (see
    :func:`set_program_cache_capacity`). Pins are *refcounted*: each
    ``pin`` needs a matching ``unpin``, so one engine releasing its pins
    never strips a shape another live engine still pins."""
    bucket = (int(bucket[0]), int(bucket[1]))
    _program_cache_pins[bucket] = _program_cache_pins.get(bucket, 0) + 1
    return sum(1 for key in _program_cache if _key_bucket(key) == bucket)


def program_cache_unpin(bucket: Tuple[int, int]) -> bool:
    """Drop one reference to a bucket shape's eviction protection; True if
    the shape was pinned (it stays protected while other pinners remain)."""
    bucket = (int(bucket[0]), int(bucket[1]))
    count = _program_cache_pins.get(bucket, 0)
    if count <= 0:
        return False
    if count == 1:
        del _program_cache_pins[bucket]
    else:
        _program_cache_pins[bucket] = count - 1
    return True


def program_cache_info() -> dict:
    """Cache observability for serving stats / benchmarks."""
    resident = {_key_bucket(key) for key in _program_cache}
    return {
        "size": len(_program_cache),
        "capacity": _program_cache_capacity,
        "evictions": _program_cache_evictions,
        "compiles": _program_cache_compiles,
        "pinned": sorted(_program_cache_pins),
        # Learned compile walls per resident (R, W) shape — the measured
        # priors the serving cost model's compile_charge consumes.
        "compile_wall_ewma_ms": {
            f"{r}x{w}": _compile_walls[(r, w)] * 1e3
            for (r, w) in sorted(resident) if (r, w) in _compile_walls},
    }


# Observed compile walls per (R, W) bucket shape: EWMA over every program
# compiled at that shape (any B/k/kernel variant — the serving cost model
# prices per bucket shape, so that is the learning granularity too).
_compile_walls: dict = {}
_COMPILE_EWMA_ALPHA = 0.3
_last_compile_wall: Optional[float] = None


def consume_compile_wall() -> Optional[float]:
    """Compile wall (seconds) paid by the immediately preceding
    :func:`run_bucket_program` call, or None when it hit a resident
    program. Reading clears the stamp — executors consume it onto the
    in-flight handle so the serving telemetry sees each compile once."""
    global _last_compile_wall
    wall, _last_compile_wall = _last_compile_wall, None
    return wall


def run_bucket_program(ell, ranks_p, elig_p, m_edges, k: int,
                       use_kernel: bool = False, donate: bool = False,
                       mesh: Optional[Mesh] = None, block_rows=None,
                       method: str = "pivot",
                       objective: str = "disagree"):
    """Invoke one fused bucket program through the bounded program cache.

    The single entry point for every executor and the serving-layer warmup,
    so the donation policy and its warning handling live in one place: the
    selection outputs are group-shaped, so XLA cannot alias the
    entry-shaped inputs into them on every backend — donation still
    releases the inputs eagerly instead of holding two generations live,
    and the "not usable" warning is expected, not actionable.

    ``method`` / ``objective`` select the registered rounds body and cost
    pass (:mod:`repro.core.programs`); the method resolves to its program
    family before keying the cache, so family-sharing methods reuse one
    compiled program per shape.

    ``block_rows`` picks the kernel row tiles baked into the program: an
    explicit ``(neighbor_min, label_agree)`` pair, or (default) the tuning
    cache's winners for this packed shape (:mod:`repro.kernels.autotune`),
    or the kernel defaults when untuned. The resolved pair extends the
    program key, so re-tuning compiles a fresh program rather than
    repurposing an old one.

    On a cache miss the first invocation is timed: jit's first call blocks
    through trace + compile, so its wall is the compile wall. The sample
    feeds a per-bucket-shape EWMA (surfaced via ``program_cache_info`` and
    :func:`consume_compile_wall`) that the serving cost model learns
    ``compile_cost_s`` from.

    With JAX's async dispatch this returns device arrays that may still be
    computing; callers that need the values block via ``np.asarray`` (which
    is what :class:`InFlightBucket` does on harvest).
    """
    global _last_compile_wall
    _last_compile_wall = None
    ell = jnp.asarray(ell)
    key, fn, fresh = _cached_program(ell.shape, k, use_kernel, donate, mesh,
                                     block_rows, method, objective)
    args = (ell, jnp.asarray(ranks_p), jnp.asarray(elig_p),
            jnp.asarray(m_edges))

    def _invoke():
        with _donation_quiet(donate):
            return fn(*args)

    if not fresh:
        return _invoke()
    t0 = time.perf_counter()
    with span("compile", R=ell.shape[1], W=ell.shape[2]):
        out = _invoke()
    _last_compile_wall = time.perf_counter() - t0
    _record_compile_wall(key, _last_compile_wall)
    return out


def _cached_program(shape, k: int, use_kernel: bool, donate: bool,
                    mesh: Optional[Mesh], block_rows, method: str,
                    objective: str) -> Tuple[tuple, Callable, bool]:
    """The program cache's entry for one bucket program, built on a miss:
    ``(key, jitted program, built_now)``."""
    global _program_cache_compiles
    program = method_spec(method).program
    objective_spec(objective)            # fail fast on unknown objectives
    if use_kernel:
        # First import must happen OUTSIDE any trace: the kernels modules
        # create module-level jnp constants, and a first import from inside
        # the traced while-loop body would stage those constants as tracers
        # that leak into every later (untraced) kernel call.
        from repro.kernels import ops  # noqa: F401

    resolved = _resolve_block_rows(shape, use_kernel, block_rows)
    key = _program_key(shape, k, use_kernel, donate, mesh, resolved,
                       program=program, objective=objective)
    fn = _program_cache.get(key)
    if fn is not None:
        _program_cache.move_to_end(key)
        return key, fn, False
    _program_cache_compiles += 1
    fn = _build_program(k, use_kernel, donate, mesh, resolved,
                        program=program, objective=objective)
    _program_cache[key] = fn
    _evict_to_capacity()
    return key, fn, True


def _record_compile_wall(key: tuple, wall: float) -> None:
    bucket = _key_bucket(key)
    prev = _compile_walls.get(bucket)
    _compile_walls[bucket] = wall if prev is None else (
        _COMPILE_EWMA_ALPHA * wall + (1.0 - _COMPILE_EWMA_ALPHA) * prev)


def compile_bucket_programs(shapes, k: int, use_kernel: bool = False,
                            donate: bool = False,
                            mesh: Optional[Mesh] = None,
                            method: str = "pivot",
                            objective: str = "disagree") -> int:
    """Compile the bucket programs of several packed ``(B, R, W)`` shapes
    before their first run, side by side; returns how many were compiled.

    XLA compiles release the GIL, so a serving warmup — hundreds of
    programs on a cold TPU host — compiles on a pool of threads instead of
    one program at a time. The programs enter the cache here, and jit
    reuses the ahead-of-time executable on the first
    :func:`run_bucket_program` of each shape. Each compile wall feeds the
    same per-shape EWMA a first run would have.
    """
    fresh = []
    for shape in shapes:
        key, fn, built = _cached_program(shape, k, use_kernel, donate, mesh,
                                         None, method, objective)
        if built:
            fresh.append((key, fn))

    def compile_one(entry):
        key, fn = entry
        b, r, w = key[0]
        t0 = time.perf_counter()
        with span("compile", R=r, W=w):
            fn.lower(jax.ShapeDtypeStruct((b, r, w), jnp.int32),
                     jax.ShapeDtypeStruct((b, r + 1), jnp.int32),
                     jax.ShapeDtypeStruct((b, r + 1), jnp.bool_),
                     jax.ShapeDtypeStruct((b,), jnp.int32)).compile()
        return key, time.perf_counter() - t0

    if fresh:
        # One thread per CPU this process may run on (not per CPU of the
        # host): each compile holds its own host memory.
        workers = min(len(fresh), len(os.sched_getaffinity(0)))
        with _donation_quiet(donate), ThreadPoolExecutor(workers) as pool:
            for key, wall in pool.map(compile_one, fresh):
                _record_compile_wall(key, wall)
    return len(fresh)


@contextmanager
def _donation_quiet(donate: bool):
    """Silence the expected "donated buffers were not usable" warning."""
    with warnings.catch_warnings():
        if donate:
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
        yield


# ---------------------------------------------------------------------------
# Executors.
# ---------------------------------------------------------------------------


class InFlightBucket:
    """Handle for one dispatched bucket program.

    Holds the (possibly still computing) device outputs, the submitter's
    ``payload`` context, and the staging lease pinning the host buffers
    that fed the program. ``result()`` blocks for the outputs, converts
    them to numpy, and only then releases the lease — the invariant that
    keeps overlapped flushes from refilling a buffer still in flight.

    Per-flush latency telemetry rides on the handle: ``shape`` is the
    packed ``(B, R, W)``, ``assemble_seconds`` the host bucket-assembly
    time (stamped by :func:`pack_and_submit`; the per-request row *build*
    happens at admission and is accounted there), ``submitted_at`` the
    dispatch wall-clock stamp, and ``wall_seconds`` the submit→fetch wall
    time, filled in when the outputs are first fetched. The serving layer
    feeds these into its :class:`~repro.serve.scheduler.FlushTelemetry`
    so scheduling policies can adapt to observed flush latency.
    ``flush`` is the submitter's ordinal of the flush (set by
    :func:`pack_and_submit`), which names it on the harvest's span.
    ``ell_tiles`` is, once fetched, ``(swept, full)``: the ELL tiles the
    program's kernels swept per call and all of them, summed over the
    batch entries.
    """

    __slots__ = ("payload", "_outputs", "_fetched", "_lease",
                 "shape", "assemble_seconds", "submitted_at",
                 "wall_seconds", "inflight_at_submit", "compile_seconds",
                 "method", "objective", "flush")

    def __init__(self, outputs, payload: Any = None, lease=None,
                 shape: Optional[Tuple[int, ...]] = None,
                 assemble_seconds: float = 0.0,
                 submitted_at: Optional[float] = None,
                 inflight_at_submit: int = 1,
                 compile_seconds: Optional[float] = None,
                 method: str = "pivot", objective: str = "disagree"):
        self._outputs = outputs
        self._fetched: Optional[Tuple[np.ndarray, ...]] = None
        self.payload = payload
        self._lease = lease
        self.shape = shape
        self.assemble_seconds = assemble_seconds
        self.submitted_at = submitted_at
        self.wall_seconds: Optional[float] = None
        # Which registered program produced this flush — the serving
        # harvest keys its per-bucket telemetry by (method, R, W).
        self.method = method
        self.objective = objective
        # In-flight depth counting this flush — wall time includes queueing
        # behind the depth−1 earlier flushes, so telemetry divides by this
        # to estimate per-flush service time.
        self.inflight_at_submit = inflight_at_submit
        # Compile wall this flush paid (None on program-cache hits) — the
        # serving layer feeds these into the learned compile-cost stream.
        self.compile_seconds = compile_seconds
        self.flush: Optional[int] = None

    @property
    def harvested(self) -> bool:
        return self._fetched is not None

    def ready(self) -> bool:
        """True once the device program has finished (never blocks).

        Also true after a *failed* fetch (``_outputs`` cleared): there is
        nothing left to wait for, and ``result()`` reports the failure.
        """
        if self._fetched is not None or self._outputs is None:
            return True
        return all(o.is_ready() for o in self._outputs)

    @property
    def ell_tiles(self) -> Optional[Tuple[int, int]]:
        if self._fetched is None:
            return None
        swept, full = self._fetched[4].sum(axis=0)
        return int(swept), int(full)

    def result(self) -> Tuple[np.ndarray, ...]:
        """(labels, costs, picked, rounds) as numpy; blocks if needed.

        The staging lease is released whether the fetch succeeds or the
        device program surfaces a runtime error here — either way the
        program is finished with its input buffers.
        """
        if self._fetched is None:
            outputs, self._outputs = self._outputs, None
            if outputs is None:
                raise RuntimeError(
                    "bucket program outputs unavailable (an earlier fetch "
                    "of this handle failed)")
            try:
                self._fetched = tuple(np.asarray(o) for o in outputs)
                if self.submitted_at is not None:
                    self.wall_seconds = time.perf_counter() - self.submitted_at
            finally:
                if self._lease is not None:
                    self._lease.release()
                    self._lease = None
        return self._fetched[:4]


@runtime_checkable
class BucketExecutor(Protocol):
    """Structural protocol the serving layer schedules bucket flushes by."""

    name: str
    mesh: Optional[Mesh]

    def group_pad(self, n_groups: int) -> int:
        """Padded group count for a bucket of ``n_groups`` graphs."""
        ...

    def submit(self, ell, ranks_p, elig_p, m_edges, k: int,
               use_kernel: bool = False, donate: bool = False,
               payload: Any = None, lease=None,
               track: bool = True,
               assemble_seconds: float = 0.0,
               method: str = "pivot",
               objective: str = "disagree") -> InFlightBucket:
        """Dispatch one packed bucket; returns its in-flight handle.

        ``track=True`` (serving layers) enqueues the handle for delivery
        through ``retire``/``drain``; ``track=False`` (one-shot callers
        that keep their own handle list and harvest via ``result()``)
        leaves queue bookkeeping to the submitter. ``assemble_seconds`` is
        the host bucket-assembly time the submitter measured; it is
        carried on the handle for latency telemetry. ``method`` /
        ``objective`` select the registered bucket program.
        """
        ...

    def retire(self) -> List[InFlightBucket]:
        """Harvest completed handles without blocking."""
        ...

    def drain(self) -> List[InFlightBucket]:
        """Hand back every outstanding handle (callers block via result)."""
        ...

    @property
    def in_flight(self) -> int:
        """Submitted-but-unharvested bucket count (backpressure signal)."""
        ...


class _QueueExecutor:
    """Shared submit/retire bookkeeping for the concrete executors."""

    name = "base"
    mesh: Optional[Mesh] = None

    def __init__(self):
        self._pending: Deque[InFlightBucket] = deque()

    def group_pad(self, n_groups: int) -> int:
        return next_pow2(max(1, n_groups))

    def submit(self, ell, ranks_p, elig_p, m_edges, k: int,
               use_kernel: bool = False, donate: bool = False,
               payload: Any = None, lease=None,
               track: bool = True,
               assemble_seconds: float = 0.0,
               method: str = "pivot",
               objective: str = "disagree") -> InFlightBucket:
        shape = tuple(int(s) for s in np.shape(ell))
        nbytes = sum(a.nbytes for a in (ell, ranks_p, elig_p, m_edges))
        submitted_at = time.perf_counter()
        # Dispatch, starting the input transfer (which may finish after
        # the span); ``bytes`` is what crosses to the device.
        with span("submit", bytes=nbytes):
            outputs = run_bucket_program(
                ell, ranks_p, elig_p, m_edges, k=k, use_kernel=use_kernel,
                donate=donate, mesh=self.mesh, method=method,
                objective=objective)
        handle = InFlightBucket(outputs, payload=payload, lease=lease,
                                shape=shape,
                                assemble_seconds=assemble_seconds,
                                submitted_at=submitted_at,
                                inflight_at_submit=len(self._pending) + 1,
                                compile_seconds=consume_compile_wall(),
                                method=method, objective=objective)
        self._post_submit(handle)
        if track:
            self._pending.append(handle)
        return handle

    def _post_submit(self, handle: InFlightBucket) -> None:
        pass

    @property
    def in_flight(self) -> int:
        return len(self._pending)

    def retire(self) -> List[InFlightBucket]:
        done: List[InFlightBucket] = []
        still: Deque[InFlightBucket] = deque()
        while self._pending:
            h = self._pending.popleft()
            if h.ready():
                done.append(h)
            else:
                still.append(h)
        self._pending = still
        return done

    def drain(self) -> List[InFlightBucket]:
        out = list(self._pending)
        self._pending.clear()
        return out


class SyncExecutor(_QueueExecutor):
    """The classic path: dispatch, block, fetch — one bucket at a time.

    ``submit`` returns only after the program has completed and its outputs
    (and staging lease) have been harvested into the handle, so ``retire``
    always finds every submitted handle ready and ``in_flight`` never
    exceeds the unharvested-handle count of the current caller.
    """

    name = "sync"

    def _post_submit(self, handle: InFlightBucket) -> None:
        handle.result()


class AsyncExecutor(_QueueExecutor):
    """Pipelined path: non-blocking dispatch, handles harvested later.

    JAX dispatch is asynchronous — ``submit`` returns as soon as the
    program is enqueued, so the caller overlaps host-side packing of the
    next bucket with device execution and device→host transfer of the
    previous ones. ``retire()`` harvests whatever has finished;
    ``drain()`` hands back everything (harvest order = submission order,
    so results block at most once per handle).
    """

    name = "async"


class ShardedExecutor(AsyncExecutor):
    """Data-parallel path: one flush spans every local device.

    The packed batch axis is split across a 1-D mesh with ``shard_map``
    (the same MPC ⇒ mesh mapping as :mod:`repro.core.dist`, reusing its
    mesh utilities): each device runs the fused program on ``B/D`` entries
    with zero collectives, because batch entries are mutually independent.
    ``group_pad`` raises the group padding to the device count so the pow2
    group axis splits evenly and best-of-k replicas never straddle a shard
    boundary. Dispatch stays asynchronous, so sharding and pipelining
    compose.
    """

    name = "sharded"

    def __init__(self, num_devices: Optional[int] = None,
                 mesh: Optional[Mesh] = None):
        super().__init__()
        if mesh is None:
            from .dist import pow2_device_mesh

            mesh = pow2_device_mesh(num_devices)
        self.mesh = mesh
        self.num_devices = int(mesh.devices.size)
        if self.num_devices & (self.num_devices - 1):
            raise ValueError(
                f"ShardedExecutor needs a power-of-two device count to "
                f"split the pow2 group axis evenly, got mesh of "
                f"{self.num_devices} (use pow2_device_mesh)")

    def group_pad(self, n_groups: int) -> int:
        return max(self.num_devices, next_pow2(max(1, n_groups)))


def pack_and_submit(plans, k: int, executor: "BucketExecutor",
                    pool=None, use_kernel: bool = False, payload: Any = None,
                    track: bool = True, objective: str = "disagree",
                    flush: int = 0):
    """Pack one bucket and dispatch it through an executor.

    The single lease → ``pack_bucket`` → ``submit`` sequence shared by
    ``correlation_cluster_batch`` and the serving-layer flush, so group
    padding, donation policy and pad accounting cannot drift between the
    two paths. Every plan carries its :class:`~repro.core.plan.
    PackedRows`, so the pack only expands rows; its measured host time is
    stamped on the handle as ``assemble_seconds``. Returns ``(handle,
    stats)`` where ``stats`` is this one flush's :class:`~repro.core.
    plan.PackStats` — the single source every caller's pad accounting
    merges from. If packing or dispatch raises, the staging lease is
    released before re-raising — nothing was dispatched, so the buffers
    are genuinely free.

    The clustering method rides on the plans themselves
    (``GraphPlan.method``): one flush is one method by construction, so a
    mixed-method plan list is rejected here — the last line of defence
    behind the scheduler's cross-method steal refusal.

    ``flush`` is the caller's ordinal of this flush: it rides on the
    handle and names the flush's ``assemble`` span.
    """
    from .plan import estimate_pack_stats, pack_bucket

    R, W = plans[0].bucket
    method = getattr(plans[0], "method", "pivot")
    for p in plans[1:]:
        if getattr(p, "method", "pivot") != method:
            raise ValueError(
                f"cannot pack methods {method!r} and "
                f"{getattr(p, 'method', 'pivot')!r} into one bucket flush: "
                "a bucket program runs exactly one registered method — "
                "cross-method coalescing/stealing is refused")
    g_pad = executor.group_pad(len(plans))
    b_pad = g_pad * k
    lease = pool.acquire(b_pad, R, W) if pool is not None else None
    try:
        t_pack = time.perf_counter()
        with span("assemble", flush=flush):
            ell, ranks, elig, m_edges, _ = pack_bucket(
                plans, k=k, g_pad=g_pad,
                staging=lease.arrays if lease is not None else None)
        assemble_seconds = time.perf_counter() - t_pack
        handle = executor.submit(
            ell, ranks, elig, m_edges, k=k, use_kernel=use_kernel,
            donate=pool is not None and pool.donate,
            payload=payload, lease=lease, track=track,
            assemble_seconds=assemble_seconds,
            method=method, objective=objective)
    except BaseException:
        if lease is not None:
            lease.release()
        raise
    handle.flush = flush
    # The same pure formula the serving cost model prices candidate
    # flushes with, so priced pads and reported pads can never drift.
    stats = estimate_pack_stats(plans, k, g_pad=g_pad)
    return handle, stats


_EXECUTORS = {
    "sync": SyncExecutor,
    "async": AsyncExecutor,
    "sharded": ShardedExecutor,
}


def make_executor(spec=None) -> BucketExecutor:
    """Resolve an executor argument: name, instance, or None (→ sync)."""
    if spec is None:
        return SyncExecutor()
    if isinstance(spec, str):
        try:
            return _EXECUTORS[spec]()
        except KeyError:
            raise ValueError(
                f"unknown executor {spec!r}; expected one of "
                f"{sorted(_EXECUTORS)}") from None
    if isinstance(spec, BucketExecutor):
        return spec
    raise TypeError(f"executor must be a name or BucketExecutor, "
                    f"got {type(spec).__name__}")


__all__ = [
    "UNDECIDED",
    "IN_MIS",
    "REMOVED",
    "InFlightBucket",
    "BucketExecutor",
    "SyncExecutor",
    "AsyncExecutor",
    "ShardedExecutor",
    "make_executor",
    "pack_and_submit",
    "run_bucket_program",
    "consume_compile_wall",
    "program_cache_size",
    "program_cache_capacity",
    "set_program_cache_capacity",
    "program_cache_info",
    "program_cache_contains",
    "program_cache_touch",
    "program_cache_pin",
    "program_cache_unpin",
]
