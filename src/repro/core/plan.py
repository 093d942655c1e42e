"""Host-side planning layer of the batch engine: bucketing, packing, staging.

This is the "what runs" half of the plan/executor split (the "how it runs"
half is :mod:`repro.core.executor`). Everything here is pure numpy on the
host:

* :func:`plan_graph` resolves one graph's degree cap and its ``(R, W)``
  shape bucket (``R`` = vertex count rounded to a power of two, ``W`` = max
  *eligible-induced* degree rounded to a power of two — the Theorem 26 cap
  is what keeps ``W ≤ 12λ`` and makes ELL padding cheap). It also
  canonicalises the eligible-induced edge list (lexsorted) exactly once;
  :func:`graph_fingerprint` and the row builder both read
  ``GraphPlan.canonical_edges`` instead of re-deriving it.
* :func:`build_packed_rows` turns one plan into a :class:`PackedRows`
  artifact — the graph's finished ``(R, W)`` ELL rows, rank rows, and
  eligibility row. It is the one row builder: serving calls it once per
  request at admission and ``correlation_cluster_batch`` once per graph
  after planning, so the argsort/bincount/scatter work never runs on the
  flush path.
* :func:`pack_bucket` lays one bucket's rows (× k best-of-k samples)
  into the ``(B, R, W)`` ELL tensor plus ``(B, R+1)`` rank/eligibility
  state the device program consumes, with the group axis padded to a power
  of two (callers may request extra group padding, e.g. to a device-count
  multiple for the sharded executor). It only copies rows.
* :class:`PackStats` is the packer's own padding accounting — the single
  source serving stats are derived from, so they cannot drift from what was
  actually padded onto the device. :func:`estimate_pack_stats` is the pure
  formula behind it, shared with the serving cost model so candidate
  flushes are priced with exactly the math the real pack will report.
* :class:`BucketBufferPool` owns the persistent host staging arrays.
  Staging is handed out as **leases**: an acquired buffer is not eligible
  for reuse until its lease is released, which the executor layer does only
  after the bucket's device program has completed and its outputs have been
  fetched. That is the invariant that makes async (overlapped) flushes
  safe — a buffer feeding an in-flight program is never refilled.

The bit-exactness contract lives at this layer too: ranks come from the
same ``random_permutation_ranks(n_i, key_i)`` as the per-graph engine, so
for matching keys any grouping of graphs into buckets — full flushes,
partial deadline flushes, sharded flushes — yields identical results.
"""

from __future__ import annotations

import dataclasses
import hashlib
import struct
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.util import next_pow2, span

from .arboricity import Peel, arboricity_bounds, degeneracy_peel
from .degree_cap import degree_threshold
from .graph import Graph
from .mis import random_permutation_ranks_batch

MIN_ROWS = 8     # smallest R bucket
MIN_WIDTH = 4    # smallest W bucket

# Largest supported bucket shapes. R is bounded so the int32 pair count
# R·(R−1)/2 of the device cost pass cannot overflow (jax x64 is disabled in
# this deployment); W is bounded because an eligible-induced degree that
# large means the degree cap is effectively off for a dense graph — the
# per-graph engine is the right tool there.
MAX_ROWS = 1 << 15
MAX_WIDTH = 1 << 12

_INT32_MAX = np.iinfo(np.int32).max


@dataclasses.dataclass
class GraphPlan:
    """Per-graph packing plan: bucket key + degree-cap metadata."""

    g: Graph
    n: int
    lam: Optional[int]          # resolved arboricity bound (None for raw)
    threshold: Optional[float]  # degree-cap threshold (None for raw)
    eligible: np.ndarray        # (n,) bool — vertices the inner PIVOT sees
    wreq: int                   # max eligible-induced degree
    R: int                      # row bucket (pow2)
    W: int                      # width bucket (pow2)
    # Eligible-induced undirected edge list in canonical (lexsorted (u, v))
    # order, int64 C-contiguous. Built once by plan_graph; both
    # graph_fingerprint and build_packed_rows consume it, so the
    # keep-mask/sort happens exactly once per request and the two can
    # never diverge.
    canonical_edges: Optional[np.ndarray] = None
    # The graph's device rows (build_packed_rows); pack_bucket needs them.
    rows: Optional["PackedRows"] = None
    # Registered clustering method this plan was resolved for. Part of the
    # serving-layer queue key: one flush runs one method's bucket program.
    method: str = "pivot"
    # How the exact degeneracy was peeled, when plan_graph peeled it (None
    # for a given lam, an uncapped method or the doubling bound).
    peel: Optional[Peel] = None

    @property
    def bucket(self) -> Tuple[int, int]:
        """Shape bucket (R, W) — the packing/promotion identity."""
        return (self.R, self.W)

    @property
    def queue_key(self) -> Tuple[str, int, int]:
        """Serving-layer bucket key (method, R, W): requests coalesce into
        one flush only when they share both the packed shape and the
        registered bucket program."""
        return (self.method, self.R, self.W)


def plan_graph(g: Graph, method: str = "pivot", eps: float = 2.0,
               lam: Optional[int] = None) -> GraphPlan:
    """Resolve the degree cap and the (R, W) shape bucket for one graph.

    ``method`` must be registered in :mod:`repro.core.programs`; its spec
    drives planning. Degree-capped methods mirror the per-graph api
    exactly: ``lam`` defaults to the degeneracy upper bound, eligibility
    is ``deg <= 8(1+ε)/ε·λ`` (Theorem 26). Uncapped methods
    (``'pivot_raw'``) mark every vertex eligible.

    Raises ``ValueError`` for an unregistered method, or when the graph
    exceeds the largest supported bucket (``MAX_ROWS`` vertices /
    eligible-induced degree ``MAX_WIDTH``).
    """
    from .programs import method_spec

    spec = method_spec(method)     # ValueError lists registered methods
    n = g.n
    peel = None
    if spec.degree_cap:
        if lam is None:
            with span("degeneracy", n=n) as sp:
                if n <= 200_000:
                    peel = degeneracy_peel(g)
                    sp.set_metadata(peel_rounds=peel.rounds,
                                    peel_single=peel.single)
                    lam = max(1, peel.d)
                else:
                    _, lam = arboricity_bounds(g, exact=False)
        threshold = degree_threshold(lam, eps)
        eligible = ~(np.asarray(g.deg) > threshold)
    else:
        lam, threshold = None, None
        eligible = np.ones(n, dtype=bool)

    und = g.undirected_edges()
    if len(und):
        keep = eligible[und[:, 0]] & eligible[und[:, 1]]
        kept = und[keep]
    else:
        kept = np.zeros((0, 2), dtype=np.int64)
    if len(kept):
        # Canonical order: lexsorted by (u, v). This is the byte order the
        # fingerprint hashes and the edge order build_packed_rows scatters
        # from.
        kept = kept[np.lexsort((kept[:, 1], kept[:, 0]))]
        wreq = int(np.bincount(kept.ravel(), minlength=n).max())
    else:
        wreq = 0
    kept = np.ascontiguousarray(kept, dtype=np.int64)

    R = max(MIN_ROWS, next_pow2(max(1, n)))
    W = max(MIN_WIDTH, next_pow2(max(1, wreq)))
    if R > MAX_ROWS:
        raise ValueError(
            f"graph with n={n} needs row bucket R={R} > MAX_ROWS={MAX_ROWS}; "
            "the batch engine targets many small graphs — cluster this one "
            "through correlation_cluster (per-graph engine) instead")
    if W > MAX_WIDTH:
        raise ValueError(
            f"graph needs ELL width W={W} > MAX_WIDTH={MAX_WIDTH} (max "
            f"eligible-induced degree {wreq}); with method='pivot' the "
            "Theorem 26 degree cap bounds this by 12λ — a width this large "
            "means the graph is too dense for the bucketed ELL layout; use "
            "the per-graph engine")
    return GraphPlan(g=g, n=n, lam=lam, threshold=threshold,
                     eligible=eligible, wreq=wreq, R=R, W=W,
                     canonical_edges=kept, method=method, peel=peel)


def plan_canonical_edges(plan: GraphPlan) -> np.ndarray:
    """The plan's canonical (lexsorted) eligible-induced edge list.

    ``plan_graph`` always attaches it; plans constructed by hand get it
    derived (and memoised) here so the fingerprint and the row builder
    keep one source of truth either way.
    """
    if plan.canonical_edges is None:
        und = plan.g.undirected_edges()
        if len(und):
            keep = plan.eligible[und[:, 0]] & plan.eligible[und[:, 1]]
            kept = und[keep]
            if len(kept):
                kept = kept[np.lexsort((kept[:, 1], kept[:, 0]))]
        else:
            kept = np.zeros((0, 2), dtype=np.int64)
        plan.canonical_edges = np.ascontiguousarray(kept, dtype=np.int64)
    return plan.canonical_edges


class PackedRows:
    """The device rows of one planned graph (see :func:`build_packed_rows`).

    Everything the device program reads of this graph, finished once
    before any flush: the ``(R, W)`` int32 ELL adjacency rows
    (pad id ``R``), the ``(k, R+1)`` rank rows for the request's best-of-k
    sample keys (``INT32_MAX`` beyond ``n``), the ``(R+1,)`` eligibility
    row (slot ``R`` False), and the full edge count ``m`` the cost
    identity reads. Flush-time assembly then reduces to row copies into
    the leased staging arrays.

    The rank permutations are dispatched to the device when the artifact
    is built (one fused async call) and materialised into the padded
    numpy layout lazily on first access — by flush time they have long
    finished, so the host work after the build overlaps them.
    """

    __slots__ = ("R", "W", "n", "m", "k", "ell", "elig",
                 "_ranks", "_ranks_dev")

    def __init__(self, R: int, W: int, n: int, m: int, k: int,
                 ell: np.ndarray, elig: np.ndarray,
                 ranks: Optional[np.ndarray] = None, ranks_dev=None):
        self.R = R
        self.W = W
        self.n = n
        self.m = m
        self.k = k
        self.ell = ell
        self.elig = elig
        self._ranks = ranks
        self._ranks_dev = ranks_dev

    @property
    def bucket(self) -> Tuple[int, int]:
        return (self.R, self.W)

    @property
    def ranks(self) -> np.ndarray:
        """``(k, R+1)`` int32 rank rows (materialises the device batch)."""
        if self._ranks is None:
            out = np.full((self.k, self.R + 1), _INT32_MAX, dtype=np.int32)
            if self._ranks_dev is not None:
                # Blocks until the rank program ran: on one device it
                # queues behind every program dispatched before it.
                with span("rank_wait"):
                    out[:, : self.n] = np.asarray(self._ranks_dev)
                self._ranks_dev = None
            self._ranks = out
        return self._ranks

    def promote(self, R: int, W: int) -> "PackedRows":
        """Pad-copy relayout into a larger ``(R, W)`` bucket (coalescing).

        Bit-exact for the same reason :func:`promote_plan` is: promoted
        rows ``n..R`` carry INF rank and are ineligible, extra width slots
        hold the new pad id ``R``. Raises ``ValueError`` for a target that
        cannot hold these rows.
        """
        if (R, W) == (self.R, self.W):
            return self
        if R < self.R or W < self.W:
            raise ValueError(
                f"cannot promote packed rows {self.bucket} into ({R}, {W}):"
                " the target must be at least as large in both dimensions")
        ell = np.full((R, W), R, dtype=np.int32)
        if self.n:
            # Real entries only live in rows < n; re-stamp the pad id.
            sub = self.ell[: self.n]
            ell[: self.n, : self.W] = np.where(sub == self.R, R, sub)
        elig = np.zeros(R + 1, dtype=bool)
        elig[: self.n] = self.elig[: self.n]
        ranks = np.full((self.k, R + 1), _INT32_MAX, dtype=np.int32)
        ranks[:, : self.n] = self.ranks[:, : self.n]
        return PackedRows(R=R, W=W, n=self.n, m=self.m, k=self.k,
                          ell=ell, elig=elig, ranks=ranks)


def build_packed_rows(plan: GraphPlan,
                      keys: Sequence[jax.Array]) -> PackedRows:
    """Build one graph's :class:`PackedRows` at its native bucket.

    ``keys`` are the request's best-of-k sample keys; the rank batch is
    dispatched here (async) and harvested lazily. The ELL rows scatter
    straight from the plan's canonical edge list — the same array the
    fingerprint hashes — so the sort/bincount of packing happens exactly
    once per request, at admission.
    """
    n = plan.n
    R, W = plan.bucket
    ell = np.full((R, W), R, dtype=np.int32)
    e = plan_canonical_edges(plan)
    if len(e):
        src = np.concatenate([e[:, 0], e[:, 1]])
        dst = np.concatenate([e[:, 1], e[:, 0]])
        order = np.argsort(src, kind="stable")
        src, dst = src[order], dst[order]
        deg = np.bincount(src, minlength=n)
        starts = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(deg, out=starts[1:])
        slot = np.arange(len(src)) - starts[src]
        ell[src, slot] = dst
    elig = np.zeros(R + 1, dtype=bool)
    if n:
        elig[:n] = plan.eligible
    ranks_dev = random_permutation_ranks_batch(n, keys) if n else None
    return PackedRows(R=R, W=W, n=n, m=int(plan.g.m), k=len(keys),
                      ell=ell, elig=elig, ranks_dev=ranks_dev)


def promote_plan(plan: GraphPlan, R: int, W: int) -> GraphPlan:
    """Re-target a plan at a larger ``(R, W)`` shape bucket (coalescing).

    The scheduler's work-stealing policy packs a starving bucket's
    requests into a compatible hot bucket's flush; this is the shape
    promotion that makes the packed tensors line up. It is bit-exact by
    construction: ranks/eligibility are a function of ``(n, key)`` only,
    promoted rows ``n..R`` carry INF rank and are ineligible (removed
    before the first MIS round, singleton labels sliced off by
    ``result_for_plan``), extra ELL width slots hold the pad id ``R``
    whose gathered rank is INF / label is −1, and the cost identity sums
    zero over both. Asserted against the per-graph engine in
    ``tests/test_scheduler.py``.

    Raises ``ValueError`` if the target shape cannot hold the plan
    (``R < plan.R`` or ``W < plan.W``) or exceeds the largest supported
    bucket.
    """
    if R < plan.R or W < plan.W:
        raise ValueError(
            f"cannot promote bucket {plan.bucket} into ({R}, {W}): the "
            "target must be at least as large in both dimensions")
    if R > MAX_ROWS or W > MAX_WIDTH:
        raise ValueError(
            f"promotion target ({R}, {W}) exceeds the largest supported "
            f"bucket ({MAX_ROWS}, {MAX_WIDTH})")
    if (R, W) == plan.bucket:
        return plan
    # Prebuilt rows relayout with the plan (cheap pad-copies), so a
    # coalesced flush at the promoted shape still assembles by row copies.
    rows = plan.rows.promote(R, W) if plan.rows is not None else None
    return dataclasses.replace(plan, R=R, W=W, rows=rows)


@dataclasses.dataclass(frozen=True)
class GraphFingerprint:
    """Content address of one planned clustering request.

    ``digest`` is a 128-bit blake2b over ``payload``, the canonical byte
    encoding of everything that determines the device result bit-for-bit
    (see :func:`graph_fingerprint`). The payload rides along so a cache
    keyed by ``digest`` can *verify* equality on every hit instead of
    trusting the hash — a digest collision is detected, counted, and
    treated as a miss rather than silently serving another graph's labels.
    """

    digest: str
    payload: bytes

    @property
    def nbytes(self) -> int:
        """Size of the retained canonical payload (cache byte accounting)."""
        return len(self.payload)


def _key_payload(key: jax.Array) -> bytes:
    """Canonical bytes of a PRNG key — dtype, size, and raw key data.

    Handles both legacy ``uint32`` key arrays and new-style typed key
    arrays (``jax.random.key``); the encoding distinguishes them, which is
    correct — they can drive different bit streams.
    """
    try:
        arr = np.asarray(key)
    except TypeError:
        # Typed key arrays refuse np.asarray; unwrap to the raw key data.
        arr = np.asarray(jax.random.key_data(key))
    arr = np.ascontiguousarray(arr)
    return (str(arr.dtype).encode("utf-8") + b"\0"
            + struct.pack("<q", arr.size) + arr.tobytes())


def graph_fingerprint(plan: GraphPlan, key: jax.Array, *,
                      method: str = "pivot", num_samples: int = 1,
                      eps: float = 2.0,
                      objective: str = "disagree") -> GraphFingerprint:
    """Canonical, collision-checked content hash of one planned request.

    Two requests with equal fingerprints produce bit-identical device
    inputs, hence bit-identical ``(labels, cost, picked)`` — the invariant
    the serving-layer result cache and single-flight coalescing rest on.
    The payload canonicalises exactly what :func:`pack_bucket` puts on
    the device for this graph at its native bucket (bucket-shape-stable:
    promotion to a larger flush shape is bit-exact, so it does not enter
    the fingerprint):

    * the eligible-induced edge set in a canonical (lexsorted) order, the
      eligibility mask, ``n``, and ``m`` (the cost identity reads the full
      edge count) — together these determine the ELL rows and the
      eligibility state;
    * the **exact PRNG key bytes** plus ``num_samples`` — ranks are a
      function of ``(n, key)`` only, and best-of-k sample keys are derived
      by ``fold_in`` from the base key, so key + k pins every permutation.
      Caching is keyed on the exact key precisely because the contract is
      bit-exactness *per key*, not statistical equivalence;
    * ``method`` / ``objective`` / ``eps`` / the resolved ``lam`` — method
      and objective select the registered bucket program (different
      methods or objectives on identical inputs produce different labels
      or different best-of-k winners, so their cache entries must never
      alias), and ``eps``/``lam`` resolve the degree cap (eligibility,
      threshold) and the result's info schema.

    Only post-selection winners (the argmin-of-k labels/cost/picked the
    engine returns) are cached against this fingerprint: intermediate
    per-sample outputs never leave the device program, so the cached value
    is exactly what a cold flush would have returned.
    """
    g = plan.g
    # The canonical lexsorted edge list is built once by plan_graph and
    # shared with the row builder — hashing here re-derives nothing.
    kept = plan_canonical_edges(plan)
    elig = np.ascontiguousarray(np.asarray(plan.eligible, dtype=bool))
    payload = b"".join([
        b"cc-graph-fp2\0",
        method.encode("utf-8") + b"\0",
        objective.encode("utf-8") + b"\0",
        struct.pack("<d", float(eps)),
        struct.pack("<q", -1 if plan.lam is None else int(plan.lam)),
        struct.pack("<qqq", max(1, int(num_samples)), int(plan.n), int(g.m)),
        _key_payload(key),
        np.packbits(elig).tobytes() if plan.n else b"",
        kept.tobytes(),
    ])
    return GraphFingerprint(
        digest=hashlib.blake2b(payload, digest_size=16).hexdigest(),
        payload=payload)


@dataclasses.dataclass
class PackStats:
    """Packing/padding accounting for one ``correlation_cluster_batch`` call.

    Returned by the packer itself (``with_stats=True``) so serving-layer
    stats can never drift from what was actually padded onto the device.
    """

    n_graphs: int = 0
    n_entries: int = 0        # real device entries = graphs × num_samples
    padded_entries: int = 0   # empty entries added for pow2 group padding
    pad_vertex_waste: int = 0  # Σ (R − n) over real graphs
    bucket_shapes: List[Tuple[int, int, int]] = dataclasses.field(
        default_factory=list)  # (R, W, B) per bucket actually run

    def merge(self, other: "PackStats") -> None:
        """Accumulate another flush's packing accounting into this one."""
        self.n_graphs += other.n_graphs
        self.n_entries += other.n_entries
        self.padded_entries += other.padded_entries
        self.pad_vertex_waste += other.pad_vertex_waste
        self.bucket_shapes.extend(other.bucket_shapes)


def estimate_pack_stats(plans: Sequence[GraphPlan], k: int,
                        g_pad: Optional[int] = None) -> PackStats:
    """Price a prospective flush's padding without packing it.

    A pure function over :class:`GraphPlan`\\ s — the single
    :class:`PackStats` formula. ``pack_and_submit`` builds its real
    accounting from it, and the serving cost model
    (:mod:`repro.serve.costmodel`) prices *candidate* coalesced flushes
    with it before committing, so a priced decision and the pad stats the
    flush later reports are the same numbers by construction. For a
    promoted (coalesced) pack, pass plans already run through
    :func:`promote_plan` — every plan must share one bucket shape.

    ``g_pad`` is the padded group count (defaults to the plain pow2
    padding; executors may require more, e.g. a device-count floor).
    """
    if not plans:
        raise ValueError("estimate_pack_stats needs at least one plan")
    R, W = plans[0].bucket
    if any(p.bucket != (R, W) for p in plans):
        raise ValueError("plans must share one (R, W) bucket shape — "
                         "promote them first")
    if g_pad is None:
        g_pad = next_pow2(len(plans))
    elif g_pad < len(plans):
        raise ValueError(f"g_pad={g_pad} < {len(plans)} graphs in bucket")
    return PackStats(
        n_graphs=len(plans),
        n_entries=len(plans) * k,
        padded_entries=(g_pad - len(plans)) * k,
        pad_vertex_waste=sum(R - p.n for p in plans),
        bucket_shapes=[(R, W, g_pad * k)],
    )


def pack_bucket(plans: Sequence[GraphPlan], k: int,
                staging: Optional[dict] = None,
                g_pad: Optional[int] = None):
    """Assemble one bucket's graphs (× k samples each) into device tensors.

    Returns ``(ell, ranks, elig, m_edges, pad_groups)`` with batch axis
    ``B = g_pad · k`` where ``g_pad`` defaults to ``next_pow2(len(plans))``
    — executors may request more group padding (e.g. the sharded executor
    pads to at least its device count so the batch axis splits evenly).
    The ``k`` sample replicas of a graph occupy contiguous entries so the
    device argmin can reduce over a simple ``(G, k)`` reshape. ``staging``
    (a lease from :class:`BucketBufferPool`) reuses host arrays across
    flushes instead of reallocating.

    Every plan carries its :class:`PackedRows` (built by
    :func:`build_packed_rows`, promoted with its plan for coalesced
    flushes), so assembly is row copies only: each real entry is wholly
    overwritten by its copy, and only the group-padding tail is (re)stamped
    with the pad pattern. Raises ``ValueError`` for a plan without rows or
    with rows at another bucket or sample count.
    """
    R, W = plans[0].bucket
    if g_pad is None:
        g_pad = next_pow2(len(plans))
    elif g_pad < len(plans):
        raise ValueError(f"g_pad={g_pad} < {len(plans)} graphs in bucket")
    b_pad = g_pad * k
    for plan in plans:
        pr = plan.rows
        if pr is None:
            raise ValueError(
                "plan has no rows: build them with build_packed_rows(plan, "
                "sample_keys(key, k)) before packing")
        if pr.bucket != (R, W) or pr.k != k:
            raise ValueError(
                f"prebuilt rows at bucket {pr.bucket} with k={pr.k} cannot "
                f"assemble into a ({R}, {W}) flush with k={k}; promote the "
                "plan first (promote_plan relays its PackedRows)")
    n_real = len(plans) * k
    if staging is None:
        ell = np.empty((b_pad, R, W), dtype=np.int32)
        ranks = np.empty((b_pad, R + 1), dtype=np.int32)
        elig = np.empty((b_pad, R + 1), dtype=bool)
        m_edges = np.empty((b_pad,), dtype=np.int32)
    else:
        ell, ranks, elig, m_edges = (staging["ell"], staging["ranks"],
                                     staging["elig"], staging["m_edges"])
    ell[n_real:] = R
    ranks[n_real:] = _INT32_MAX
    elig[n_real:] = False
    m_edges[n_real:] = 0
    for gi, plan in enumerate(plans):
        pr = plan.rows
        base = gi * k
        ell[base: base + k] = pr.ell
        ranks[base: base + k] = pr.ranks
        elig[base: base + k] = pr.elig
        m_edges[base: base + k] = pr.m
    return ell, ranks, elig, m_edges, g_pad - len(plans)


def result_for_plan(plan: GraphPlan, labels_row: np.ndarray, cost: int,
                    picked: int, rounds: int, k: int, method: str):
    """Build one :class:`~repro.core.api.ClusterResult` from device outputs.

    Shared by ``correlation_cluster_batch`` and the serving-layer harvest so
    the result/info schema cannot diverge between the one-shot and the
    streaming paths.
    """
    from .api import ClusterResult  # deferred: api imports the batch layer

    info = {
        "bucket": plan.bucket,
        "depth": rounds,
        "engine": "batch",
    }
    if plan.threshold is not None:
        info.update(threshold=plan.threshold,
                    high_degree=int((~plan.eligible).sum()),
                    lambda_bound=plan.lam)
    if k > 1:
        info.update(num_samples=k, picked_sample=picked)
    return ClusterResult(labels=labels_row[: plan.n].astype(np.int32),
                         cost=cost, method=method, info=info)


class StagingLease:
    """One checked-out host staging buffer set (see :class:`BucketBufferPool`).

    ``arrays`` maps ``ell``/``ranks``/``elig``/``m_edges`` to the numpy
    staging arrays a flush packs into. The lease must be released (once)
    after the device program consuming the buffers has completed; the
    executor layer does this when a flush's outputs are fetched.
    """

    __slots__ = ("pool", "key", "arrays", "released")

    def __init__(self, pool: "BucketBufferPool", key: Tuple[int, int, int],
                 arrays: dict):
        self.pool = pool
        self.key = key
        self.arrays = arrays
        self.released = False

    def release(self) -> None:
        if not self.released:
            self.released = True
            self.pool._release(self)


class BucketBufferPool:
    """Persistent per-bucket-shape buffers for steady-state serving.

    Two halves, both keyed by the packed shape ``(B, R, W)``:

    * **Host staging** — the numpy ``ell``/``ranks``/``eligible``/``m``
      arrays a flush packs into are allocated once per shape and refilled
      in place on later flushes. Buffers are handed out as
      :class:`StagingLease` objects: a leased buffer is **never** handed
      out again until released, so an async executor overlapping flushes of
      the same bucket shape gets a second buffer generation instead of
      corrupting the one still feeding an in-flight program (regression
      tested in ``tests/test_executor.py``). Synchronous serving releases
      each lease before the next flush, holding O(#buckets) buffers;
      pipelined serving holds O(#buckets · in-flight).
    * **Device donation** — flushes routed through a pool run the
      ``donate_argnums`` jit variant, so the device input buffers are
      recycled into the outputs instead of surviving alongside them.

    Results are bit-identical with or without the pool (asserted in
    ``tests/test_engine.py``); the pool only changes allocation behaviour.
    """

    def __init__(self, donate: bool = True):
        self.donate = donate
        self._free: Dict[Tuple[int, int, int], List[dict]] = {}
        self._allocated = 0
        self._leased = 0

    def _new_buffers(self, b: int, r: int, w: int) -> dict:
        return {
            "ell": np.empty((b, r, w), dtype=np.int32),
            "ranks": np.empty((b, r + 1), dtype=np.int32),
            "elig": np.empty((b, r + 1), dtype=bool),
            "m_edges": np.empty((b,), dtype=np.int32),
        }

    def acquire(self, b: int, r: int, w: int) -> StagingLease:
        """Check out a staging buffer set for shape ``(b, r, w)``.

        Reuses a free buffer when one exists; otherwise allocates — a
        buffer whose lease is outstanding is never returned.
        """
        key = (b, r, w)
        free = self._free.get(key)
        if free:
            arrays = free.pop()
        else:
            arrays = self._new_buffers(b, r, w)
            self._allocated += 1
        self._leased += 1
        return StagingLease(self, key, arrays)

    def _release(self, lease: StagingLease) -> None:
        self._leased -= 1
        self._free.setdefault(lease.key, []).append(lease.arrays)

    @property
    def n_buffers(self) -> int:
        """Total staging buffer sets allocated (free + leased)."""
        return self._allocated

    @property
    def leased(self) -> int:
        """Buffer sets currently checked out to in-flight flushes."""
        return self._leased


__all__ = [
    "GraphPlan",
    "GraphFingerprint",
    "graph_fingerprint",
    "PackStats",
    "PackedRows",
    "StagingLease",
    "BucketBufferPool",
    "plan_graph",
    "plan_canonical_edges",
    "promote_plan",
    "build_packed_rows",
    "pack_bucket",
    "estimate_pack_stats",
    "result_for_plan",
    "MIN_ROWS",
    "MIN_WIDTH",
    "MAX_ROWS",
    "MAX_WIDTH",
]
