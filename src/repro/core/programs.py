"""Method registry of fused bucket programs + pluggable cost objectives.

The device half of the batch engine used to be a single hardcoded
MIS+PIVOT pipeline. This module is the seam that replaced it: every
clustering *method* the batch engine can run is a :class:`BucketProgramSpec`
registered here, and every *objective* it can optimise is an
:class:`ObjectiveSpec`. The executor (:mod:`repro.core.executor`) composes
``rounds_body × cost_pass`` into one jit program per
``(shape, k, kernel, donation, mesh, block_rows, program, objective)`` key
and never needs to know what the method computes.

**The BucketProgramSpec contract** — what a new method must provide, and
what it inherits for free:

A method provides exactly one traced function, ``rounds_body``::

    rounds_body(ell, ranks_p, elig_p, *, use_kernel, nm_rows, layout)
        -> (labels (B, R) int32, rounds (B,) int32)

over the shared packed tensors: ``ell`` the (B, R, W) int32 ELL adjacency
(pad id ``R``), ``ranks_p`` the (B, R+1) int32 rank rows (slot R = INF),
``elig_p`` the (B, R+1) bool eligibility rows (slot R False); on the
kernel path ``layout`` is ``ell`` prepared once for the ragged kernels
(:func:`repro.kernels.neighbor_min.prepare_ell`), else None. It must
label ineligible and padded vertices with their own index (singletons) so
the cost identity and result slicing hold, and report a per-entry
``rounds`` counter (its notion of parallel depth). Everything else is
inherited: the host-side ELL pack and bucketing, admission-time row
prebuilds, best-of-k replica plumbing and the on-device argmin harvest,
both kernel paths (``nm_rows`` is the tuned ``neighbor_min`` row tile —
both registered methods reduce over neighbourhoods with the same
:func:`repro.kernels.ops.neighbor_min_ell_batch` kernel, so autotuned
winners apply to every method at that bucket shape), the compiled-program
LRU, staging leases, donation, sharding, and the whole serving layer.

An objective provides one traced function, ``cost_pass``::

    cost_pass(ell, labels, m_edges, *, use_kernel, la_rows, layout)
        -> costs (B,) int32

scored per batch entry *before* best-of-k selection, so the argmin picks
the best sample under the configured objective. ``la_rows`` is the tuned
``label_agree`` row tile (again shared across objectives — both registered
cost passes consume the same per-vertex same-label neighbour counts).

Registered methods:

* ``'pivot'`` / ``'pivot_raw'`` — the paper's MIS+PIVOT rounds loop
  (``lax.while_loop`` until no vertex is undecided). The two share one
  *program family* (``program='pivot'``): they differ only in host-side
  eligibility planning, so they must keep sharing compiled programs.
* ``'precluster'`` — constant-round pre-clustering by neighbourhood
  agreement (Cohen-Addad et al., arXiv 2106.08448): vertices whose closed
  neighbourhoods differ by less than a constant fraction agree; labels are
  the minimum rank reached over :data:`PRECLUSTER_ROUNDS` static hops of
  the agreement graph. One straight-line device program — O(1)
  rounds-loop trips instead of the MIS while-loop.

Registered objectives:

* ``'disagree'`` — total disagreement count (the paper's objective).
* ``'minmax'`` — worst-vertex disagreement (min-max correlation
  clustering, arXiv 2502.12519), computed over the same packed tensors.
  Caveat (stated honestly): it is evaluated on the *eligible-induced*
  (degree-capped) subgraph — a cap-dropped edge's disagreement is not
  attributed to its endpoints, so under ``method='pivot'`` with capping
  active the device value is exact only for graphs where nothing is
  dropped. The host oracle :func:`minmax_cost_host` scores the full graph.

Numpy host oracles (:func:`precluster_host`, :func:`minmax_cost_host`)
replicate the device semantics exactly — integer-only agreement math, no
float thresholds — and back the per-graph reference path plus the
bit-exactness suites.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .mis import INF_RANK

UNDECIDED = 0
IN_MIS = 1
REMOVED = 2

# Constant round budget of the precluster min-rank propagation (static:
# baked into the straight-line device program).
PRECLUSTER_ROUNDS = 3

# Agreement threshold β = BETA_NUM/BETA_DEN: neighbours u, v agree when
# |N[u] Δ N[v]| < β·max(|N[u]|, |N[v]|) over closed neighbourhoods. Kept
# rational so the device (int32) and host (int64) comparisons are the same
# integer predicate — no float32-vs-float64 drift can break bit-exactness.
BETA_NUM = 2
BETA_DEN = 5


def _gather_rows(table: jnp.ndarray, ell: jnp.ndarray) -> jnp.ndarray:
    """(B, R+1) per-graph state gathered through (B, R, W) neighbour ids."""
    return jax.vmap(lambda t, e: t[e])(table, ell)


# ---------------------------------------------------------------------------
# Rounds bodies.
# ---------------------------------------------------------------------------


def _pivot_rounds_body(ell, ranks_p, elig_p, *, use_kernel: bool,
                       nm_rows: Optional[int], layout=None):
    """MIS rounds (``lax.while_loop``) + PIVOT capture — the paper's method.

    On the kernel path every round's two calls and the capture sweep the
    one ``layout`` prepared before the loop.
    """
    B, R, W = ell.shape
    ranks = ranks_p[:, :R]
    elig = elig_p[:, :R]
    # Rank gather is loop-invariant on the jnp path — hoisted out of the
    # while body; only the activity gather changes per round.
    nbr_ranks = None if use_kernel else _gather_rows(ranks_p, ell)

    def nbr_min(active: jnp.ndarray) -> jnp.ndarray:
        active_p = jnp.concatenate(
            [active, jnp.zeros((B, 1), active.dtype)], axis=1)
        if use_kernel:
            from repro.kernels import ops as _kops  # kernels stay optional

            if nm_rows is not None:
                return _kops.neighbor_min_ell_batch(layout, ranks_p,
                                                    active_p,
                                                    block_rows=nm_rows)
            return _kops.neighbor_min_ell_batch(layout, ranks_p, active_p)
        act = _gather_rows(active_p, ell)
        return jnp.min(jnp.where(act, nbr_ranks, INF_RANK), axis=2)

    def cond(carry):
        status, _ = carry
        return jnp.any(status == UNDECIDED)

    def body(carry):
        status, rounds = carry
        und = status == UNDECIDED            # UNDECIDED ⊆ eligible
        nmin = nbr_min(und)
        winners = und & (ranks < nmin)
        wmin = nbr_min(winners)
        hit = und & (~winners) & (wmin < INF_RANK)
        status = jnp.where(winners, IN_MIS, status)
        status = jnp.where(hit, REMOVED, status)
        # Per-entry done mask: finished entries stop accumulating rounds.
        rounds = rounds + jnp.any(und, axis=1).astype(jnp.int32)
        return status, rounds

    status0 = jnp.where(elig, UNDECIDED, REMOVED).astype(jnp.int32)
    status, rounds = jax.lax.while_loop(
        cond, body, (status0, jnp.zeros((B,), jnp.int32)))

    # PIVOT capture pass: min-rank MIS neighbour, one batched convergecast.
    in_mis = status == IN_MIS
    wmin = nbr_min(in_mis)
    arange_r = jnp.arange(R, dtype=jnp.int32)
    rank_to_v = jax.vmap(
        lambda rk: jnp.zeros((R + 1,), jnp.int32).at[
            jnp.clip(rk, 0, R)].set(arange_r)
    )(ranks)
    piv = jnp.take_along_axis(rank_to_v, jnp.minimum(wmin, R), axis=1)
    own = jnp.broadcast_to(arange_r[None, :], (B, R))
    labels = jnp.where(in_mis, own,
                       jnp.where(wmin < INF_RANK, piv, own))
    labels = jnp.where(elig, labels, own)
    return labels, rounds


def _precluster_rounds_body(ell, ranks_p, elig_p, *, use_kernel: bool,
                            nm_rows: Optional[int], layout=None):
    """Constant-round pre-clustering by neighbourhood agreement.

    Three straight-line stages, no data-dependent loop:

    1. **Agreement pass** — for every kept edge (u, v), count the common
       neighbours |N(u) ∩ N(v)| by looking each of v's ELL entries up in
       u's sorted ELL row (O(B·R·W²·log W) compare work, O(B·R·W²)
       intermediate memory — bounded because the Theorem 26 cap keeps
       W ≤ 12λ). The edge *agrees* when the closed neighbourhoods differ
       by less than β = BETA_NUM/BETA_DEN of the larger one:
       ``BETA_DEN·(deg(u)+deg(v)−2·common−2) < BETA_NUM·max(deg(u)+1,
       deg(v)+1)`` — symmetric in (u, v) and integer-only, so the filtered
       agreement graph is undirected by construction.
    2. **Min-rank propagation** — :data:`PRECLUSTER_ROUNDS` static hops of
       per-vertex min over the agreement neighbourhood, seeded with each
       vertex's own rank. This is where the key (hence best-of-k)
       enters: different permutations elect different cluster centres.
    3. **Label capture** — the reached minimum rank maps back to its
       vertex through the same rank→vertex table PIVOT capture uses.

    The per-entry ``rounds`` counter reports how many of the static hops
    still changed some vertex (realized propagation depth ≤ constant).
    """
    B, R, W = ell.shape
    ranks = ranks_p[:, :R]
    elig = elig_p[:, :R]
    real = ell != R                                     # (B, R, W)
    deg = jnp.sum(real, axis=2).astype(jnp.int32)       # (B, R)

    # Common-neighbour counts via sorted-row membership tests. Pad ids (R)
    # sort to the end of each row and are excluded from matching.
    ell_sorted = jnp.sort(ell, axis=2)
    ell_rows_p = jnp.concatenate(
        [ell, jnp.full((B, 1, W), R, jnp.int32)], axis=1)   # (B, R+1, W)
    nbr_lists = jax.vmap(lambda rows, e: rows[e])(ell_rows_p, ell)

    def row_common(sorted_row, cand):
        # sorted_row (W,), cand (W, W): cand[w] = ELL row of neighbour w.
        idx = jnp.searchsorted(sorted_row, cand)
        got = sorted_row[jnp.minimum(idx, W - 1)]
        member = (got == cand) & (cand != R)
        return jnp.sum(member, axis=1).astype(jnp.int32)

    common = jax.vmap(jax.vmap(row_common))(ell_sorted, nbr_lists)

    deg_p = jnp.concatenate(
        [deg, jnp.zeros((B, 1), jnp.int32)], axis=1)
    nbr_deg = _gather_rows(deg_p, ell)                  # (B, R, W)
    du = deg[:, :, None]
    dv = nbr_deg
    sym_diff = du + dv - 2 * common - 2     # closed nbhds: u∈N[v], v∈N[u]
    agree = real & (BETA_DEN * sym_diff
                    < BETA_NUM * (jnp.maximum(du, dv) + 1))
    agree_ell = jnp.where(agree, ell, R)
    if use_kernel:
        from repro.kernels import ops as _kops  # kernels stay optional
        from repro.kernels.neighbor_min import prepare_ell

        agree_layout = prepare_ell(agree_ell)

    def agree_min(state: jnp.ndarray) -> jnp.ndarray:
        state_p = jnp.concatenate(
            [state, jnp.full((B, 1), INF_RANK, jnp.int32)], axis=1)
        if use_kernel:
            if nm_rows is not None:
                return _kops.neighbor_min_ell_batch(agree_layout, state_p,
                                                    elig_p,
                                                    block_rows=nm_rows)
            return _kops.neighbor_min_ell_batch(agree_layout, state_p,
                                                elig_p)
        act = _gather_rows(elig_p, agree_ell)
        vals = _gather_rows(state_p, agree_ell)
        return jnp.min(jnp.where(act, vals, INF_RANK), axis=2)

    state = jnp.where(elig, ranks, INF_RANK)
    rounds = jnp.zeros((B,), jnp.int32)
    for _ in range(PRECLUSTER_ROUNDS):
        nxt = jnp.minimum(state, agree_min(state))
        rounds = rounds + jnp.any(nxt != state, axis=1).astype(jnp.int32)
        state = nxt

    arange_r = jnp.arange(R, dtype=jnp.int32)
    rank_to_v = jax.vmap(
        lambda rk: jnp.zeros((R + 1,), jnp.int32).at[
            jnp.clip(rk, 0, R)].set(arange_r)
    )(ranks)
    lab = jnp.take_along_axis(rank_to_v, jnp.minimum(state, R), axis=1)
    own = jnp.broadcast_to(arange_r[None, :], (B, R))
    labels = jnp.where(state < INF_RANK, lab, own)
    labels = jnp.where(elig, labels, own)
    return labels, rounds


# ---------------------------------------------------------------------------
# Cost passes.
# ---------------------------------------------------------------------------


def _label_agree_counts(ell, labels, *, use_kernel: bool,
                        la_rows: Optional[int], layout) -> jnp.ndarray:
    """(B, R) per-vertex same-label neighbour counts over the packed ELL
    (its prepared ``layout`` on the kernel path)."""
    B, R, W = ell.shape
    labels_p = jnp.concatenate(
        [labels, jnp.full((B, 1), -1, jnp.int32)], axis=1)
    if use_kernel:
        from repro.kernels import ops as _kops

        if la_rows is not None:
            return _kops.label_agree_ell_batch(layout, labels_p,
                                               block_rows=la_rows)
        return _kops.label_agree_ell_batch(layout, labels_p)
    nbr_lab = _gather_rows(labels_p, ell)
    return jnp.sum((nbr_lab == labels[:, :, None]).astype(jnp.int32), axis=2)


def _cluster_sizes(labels: jnp.ndarray) -> jnp.ndarray:
    B, R = labels.shape
    return jax.vmap(
        lambda lab: jnp.zeros((R,), jnp.int32).at[lab].add(1))(labels)


def _disagree_cost_pass(ell, labels, m_edges, *, use_kernel: bool,
                        la_rows: Optional[int], layout=None) -> jnp.ndarray:
    """Total disagreement count — the paper's objective.

    Every kept (eligible-induced) undirected edge appears twice in the
    ELL, so the same-label neighbour count sums to 2·intra_pos;
    cap-dropped edges are always cut (their ineligible endpoint is a
    singleton) so m_edges accounts for them exactly:
      cost = (m − intra_pos) + (intra_pairs − intra_pos).
    """
    agree = _label_agree_counts(ell, labels, use_kernel=use_kernel,
                                la_rows=la_rows, layout=layout)
    intra_pos2 = jnp.sum(agree, axis=1)
    sizes = _cluster_sizes(labels)
    intra_pairs = jnp.sum(sizes * (sizes - 1) // 2, axis=1)
    return m_edges - intra_pos2 + intra_pairs


def _minmax_cost_pass(ell, labels, m_edges, *, use_kernel: bool,
                      la_rows: Optional[int], layout=None) -> jnp.ndarray:
    """Worst-vertex disagreement (min-max objective, arXiv 2502.12519).

    Per vertex v: cut positive edges (deg(v) − samelabel(v)) plus missing
    intra-cluster edges (|C(v)| − 1 − samelabel(v)); the entry's cost is
    the maximum over its vertices. Evaluated on the eligible-induced
    (degree-capped) subgraph the packed tensors carry — cap-dropped edges
    are not attributed to their endpoints (see the module caveat);
    :func:`minmax_cost_host` is the full-graph oracle.
    """
    B, R, W = ell.shape
    agree = _label_agree_counts(ell, labels, use_kernel=use_kernel,
                                la_rows=la_rows, layout=layout)
    deg = jnp.sum(ell != R, axis=2).astype(jnp.int32)
    sizes = _cluster_sizes(labels)
    size_of = jnp.take_along_axis(sizes, labels, axis=1)
    per_vertex = (deg - agree) + (size_of - 1 - agree)
    return jnp.max(per_vertex, axis=1)


# ---------------------------------------------------------------------------
# Registries.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketProgramSpec:
    """One registered clustering method of the batch engine.

    ``program`` is the *program family* — the compiled-program cache
    identity. Methods that run the same device computation and differ only
    in host-side planning (``'pivot'`` vs ``'pivot_raw'``) share one
    family, so the resident program cache never fragments across them.
    ``degree_cap`` drives planning: whether :func:`repro.core.plan.
    plan_graph` resolves the Theorem 26 threshold (capped eligibility) or
    marks every vertex eligible. ``constant_rounds`` is advisory metadata:
    True for straight-line programs with a static round budget.
    """

    method: str
    program: str
    rounds_body: Callable
    degree_cap: bool
    constant_rounds: bool
    description: str


@dataclasses.dataclass(frozen=True)
class ObjectiveSpec:
    """One registered cost objective, selectable orthogonally to method."""

    objective: str
    cost_pass: Callable
    description: str


_METHODS: Dict[str, BucketProgramSpec] = {}
_OBJECTIVES: Dict[str, ObjectiveSpec] = {}


def register_method(spec: BucketProgramSpec) -> BucketProgramSpec:
    if spec.method in _METHODS:
        raise ValueError(f"method {spec.method!r} already registered")
    _METHODS[spec.method] = spec
    return spec


def register_objective(spec: ObjectiveSpec) -> ObjectiveSpec:
    if spec.objective in _OBJECTIVES:
        raise ValueError(f"objective {spec.objective!r} already registered")
    _OBJECTIVES[spec.objective] = spec
    return spec


def registered_methods() -> Tuple[str, ...]:
    """Batch-engine method names, sorted — the single source user-facing
    docs and error messages list methods from."""
    return tuple(sorted(_METHODS))


def registered_objectives() -> Tuple[str, ...]:
    return tuple(sorted(_OBJECTIVES))


def method_spec(method: str) -> BucketProgramSpec:
    try:
        return _METHODS[method]
    except KeyError:
        raise ValueError(
            f"batch engine supports methods {registered_methods()}, "
            f"got {method!r}") from None


def objective_spec(objective: str) -> ObjectiveSpec:
    try:
        return _OBJECTIVES[objective]
    except KeyError:
        raise ValueError(
            f"batch engine supports objectives {registered_objectives()}, "
            f"got {objective!r}") from None


register_method(BucketProgramSpec(
    method="pivot", program="pivot", rounds_body=_pivot_rounds_body,
    degree_cap=True, constant_rounds=False,
    description="degree-capped MIS+PIVOT (Corollary 28, the paper's "
                "headline algorithm)"))
register_method(BucketProgramSpec(
    method="pivot_raw", program="pivot", rounds_body=_pivot_rounds_body,
    degree_cap=False, constant_rounds=False,
    description="PIVOT without the degree cap (baseline comparator)"))
register_method(BucketProgramSpec(
    method="precluster", program="precluster",
    rounds_body=_precluster_rounds_body,
    degree_cap=True, constant_rounds=True,
    description="constant-round neighbourhood-agreement pre-clustering "
                "(arXiv 2106.08448)"))

register_objective(ObjectiveSpec(
    objective="disagree", cost_pass=_disagree_cost_pass,
    description="total disagreement count (the paper's objective)"))
register_objective(ObjectiveSpec(
    objective="minmax", cost_pass=_minmax_cost_pass,
    description="worst-vertex disagreement (min-max objective, arXiv "
                "2502.12519; scored on the eligible-induced subgraph)"))


# ---------------------------------------------------------------------------
# Composed bucket implementation (what the executor jit-compiles).
# ---------------------------------------------------------------------------


def bucket_impl(ell, ranks_p, elig_p, m_edges, k: int, use_kernel: bool,
                block_rows: Optional[Tuple[int, int]],
                program: str, objective: str, layout=None):
    """Cluster + cost + select every graph of one shape bucket on device.

    ``rounds_body × cost_pass`` composed with the shared best-of-k argmin
    harvest: the first cost minimum wins (``jnp.argmin`` tie-break), the
    same rule as the host loop's strict ``<`` — only winners cross back to
    the host. ``program`` is a program *family* name; resolution through
    the method registry happens in the executor so two methods of one
    family compile (and cache) identical programs. On the kernel path the
    ELL is prepared for the ragged kernels once, here, unless the caller
    passes its ``layout``.
    """
    spec = _METHODS[program]
    obj = _OBJECTIVES[objective]
    B, R, W = ell.shape
    nm_rows, la_rows = block_rows if block_rows is not None else (None, None)
    if use_kernel and layout is None:
        from repro.kernels.neighbor_min import prepare_ell

        layout = prepare_ell(ell)
    labels, rounds = spec.rounds_body(ell, ranks_p, elig_p,
                                      use_kernel=use_kernel, nm_rows=nm_rows,
                                      layout=layout)
    costs = obj.cost_pass(ell, labels, m_edges, use_kernel=use_kernel,
                          la_rows=la_rows, layout=layout)
    G = B // k
    cost_g = costs.reshape(G, k)
    picked = jnp.argmin(cost_g, axis=1).astype(jnp.int32)
    labels_win = jnp.take_along_axis(
        labels.reshape(G, k, R), picked[:, None, None], axis=1)[:, 0]
    costs_win = jnp.take_along_axis(cost_g, picked[:, None], axis=1)[:, 0]
    rounds_win = jnp.take_along_axis(
        rounds.reshape(G, k), picked[:, None], axis=1)[:, 0]
    return labels_win, costs_win, picked, rounds_win


# ---------------------------------------------------------------------------
# Numpy host oracles.
# ---------------------------------------------------------------------------

_INT32_INF = np.int32(2**31 - 1)


def _host_adjacency(n: int, edges: np.ndarray):
    adj = [[] for _ in range(n)]
    for u, v in np.asarray(edges, dtype=np.int64):
        adj[int(u)].append(int(v))
        adj[int(v)].append(int(u))
    return [sorted(a) for a in adj]


def precluster_host(n: int, edges: np.ndarray, eligible: np.ndarray,
                    ranks: np.ndarray):
    """Numpy reference of the precluster device program for one graph.

    ``edges`` is the *eligible-induced* kept undirected edge list (what
    the ELL pack carries), ``ranks`` the full permutation ranks. Returns
    ``(labels (n,) int32, rounds int)`` bit-identical to the device
    program's per-entry outputs — same integer agreement predicate, same
    synchronous min-rank propagation over :data:`PRECLUSTER_ROUNDS` hops,
    same rank→vertex capture.
    """
    eligible = np.asarray(eligible, dtype=bool)
    ranks = np.asarray(ranks, dtype=np.int64)
    adj = _host_adjacency(n, edges)
    deg = np.array([len(a) for a in adj], dtype=np.int64)
    sets = [set(a) for a in adj]

    agree_adj = [[] for _ in range(n)]
    for u, v in np.asarray(edges, dtype=np.int64):
        u, v = int(u), int(v)
        common = len(sets[u] & sets[v])
        sym_diff = deg[u] + deg[v] - 2 * common - 2
        if BETA_DEN * sym_diff < BETA_NUM * (max(deg[u], deg[v]) + 1):
            agree_adj[u].append(v)
            agree_adj[v].append(u)

    state = np.where(eligible, ranks, np.int64(_INT32_INF))
    rounds = 0
    for _ in range(PRECLUSTER_ROUNDS):
        nxt = state.copy()
        for u in range(n):
            for v in agree_adj[u]:
                if state[v] < nxt[u]:
                    nxt[u] = state[v]
        if np.any(nxt != state):
            rounds += 1
        state = nxt

    v_of_rank = np.empty(n, dtype=np.int64)
    v_of_rank[ranks] = np.arange(n)
    own = np.arange(n, dtype=np.int64)
    labels = np.where(state < _INT32_INF, v_of_rank[np.minimum(state, n - 1)],
                      own)
    labels = np.where(eligible, labels, own)
    return labels.astype(np.int32), rounds


def minmax_cost_host(n: int, edges: np.ndarray,
                     labels: np.ndarray) -> int:
    """Numpy min-max oracle: worst-vertex disagreement over ``edges``.

    Pass the full undirected positive edge list for the true objective, or
    the eligible-induced kept list to mirror the device cost pass exactly.
    """
    labels = np.asarray(labels, dtype=np.int64)
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    deg = np.zeros(n, dtype=np.int64)
    same = np.zeros(n, dtype=np.int64)
    if len(edges):
        np.add.at(deg, edges[:, 0], 1)
        np.add.at(deg, edges[:, 1], 1)
        agree = labels[edges[:, 0]] == labels[edges[:, 1]]
        np.add.at(same, edges[agree][:, 0], 1)
        np.add.at(same, edges[agree][:, 1], 1)
    sizes = np.bincount(labels, minlength=n if n else 1)
    size_of = sizes[labels] if n else np.zeros(0, dtype=np.int64)
    per_vertex = (deg - same) + (size_of - 1 - same)
    return int(per_vertex.max(initial=0))


__all__ = [
    "UNDECIDED",
    "IN_MIS",
    "REMOVED",
    "PRECLUSTER_ROUNDS",
    "BETA_NUM",
    "BETA_DEN",
    "BucketProgramSpec",
    "ObjectiveSpec",
    "register_method",
    "register_objective",
    "registered_methods",
    "registered_objectives",
    "method_spec",
    "objective_spec",
    "bucket_impl",
    "precluster_host",
    "minmax_cost_host",
    "_gather_rows",
]
