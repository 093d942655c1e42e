"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (deliverable (c))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import build_graph, random_permutation_ranks
from repro.core.graph import random_arboric, star
from repro.core.mis import neighbor_min_ranks
from repro.kernels import ops, ref
from repro.kernels.neighbor_min import ell_from_graph, neighbor_min_ell, pad_state


# --- neighbor_min ----------------------------------------------------------

@pytest.mark.parametrize("n,lam", [(17, 1), (64, 2), (257, 3), (1000, 5)])
def test_neighbor_min_matches_oracle(n, lam, rng):
    edges, _ = random_arboric(n, lam, rng)
    g = build_graph(n, edges)
    key = jax.random.PRNGKey(n)
    ranks = random_permutation_ranks(n, key)
    active = jax.random.bernoulli(key, 0.6, (n,))
    oracle = neighbor_min_ranks(g, ranks, active)
    kern = ops.neighbor_min(g, ranks, active)
    assert (np.asarray(oracle) == np.asarray(kern)).all()


@pytest.mark.parametrize("block_rows", [32, 128, 512])
def test_neighbor_min_block_sweep(block_rows, rng):
    edges, _ = random_arboric(300, 4, rng)
    g = build_graph(300, edges)
    ranks = random_permutation_ranks(300, jax.random.PRNGKey(0))
    active = jnp.ones((300,), bool)
    ell = ell_from_graph(g)
    rp, ap = pad_state(ranks, active)
    out = neighbor_min_ell(ell, rp, ap, block_rows=block_rows)
    expect = ref.neighbor_min_ref(ell, rp, ap)
    assert (np.asarray(out) == np.asarray(expect)).all()


def test_neighbor_min_star_highdeg(rng):
    """Width = n−1 row (hub) exercises the wide-ELL path."""
    g = build_graph(64, star(64))
    ranks = random_permutation_ranks(64, jax.random.PRNGKey(1))
    active = jnp.ones((64,), bool)
    oracle = neighbor_min_ranks(g, ranks, active)
    kern = ops.neighbor_min(g, ranks, active)
    assert (np.asarray(oracle) == np.asarray(kern)).all()


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 60), seed=st.integers(0, 50),
       frac=st.floats(0.0, 1.0))
def test_neighbor_min_property(n, seed, frac):
    rng = np.random.default_rng(seed)
    edges, _ = random_arboric(n, 2, rng)
    g = build_graph(n, edges)
    key = jax.random.PRNGKey(seed)
    ranks = random_permutation_ranks(n, key)
    active = jax.random.bernoulli(key, frac, (n,))
    oracle = neighbor_min_ranks(g, ranks, active)
    kern = ops.neighbor_min(g, ranks, active)
    assert (np.asarray(oracle) == np.asarray(kern)).all()


def test_ell_truncation_raises(rng):
    """Regression: width < max degree used to silently drop neighbours,
    corrupting the MIS; it must raise unless explicitly allowed."""
    g = build_graph(32, star(32))                 # hub degree 31
    with pytest.raises(ValueError, match="width"):
        ell_from_graph(g, width=4)
    # explicit opt-in still works (rows beyond width are truncated)
    ell = ell_from_graph(g, width=4, allow_truncate=True)
    assert ell.shape == (32, 4)
    # and a safe width is unchanged behaviour
    assert ell_from_graph(g, width=31).shape == (32, 31)


def test_neighbor_min_batch_matches_single(rng):
    """Batched (batch, row_block) grid ≡ per-graph kernel on each slice."""
    B, n = 5, 64
    ells, rps, aps = [], [], []
    for i in range(B):
        edges, _ = random_arboric(n, 3, rng)
        g = build_graph(n, edges)
        key = jax.random.PRNGKey(i)
        ranks = random_permutation_ranks(n, key)
        active = jax.random.bernoulli(key, 0.5, (n,))
        ell = ell_from_graph(g, width=16, allow_truncate=g.max_degree() > 16)
        rp, ap = pad_state(ranks, active)
        ells.append(ell), rps.append(rp), aps.append(ap)
    w = max(e.shape[1] for e in ells)
    ells = [jnp.pad(e, ((0, 0), (0, w - e.shape[1])), constant_values=n)
            for e in ells]
    batch_out = ops.neighbor_min_ell_batch(
        jnp.stack(ells), jnp.stack(rps), jnp.stack(aps))
    for i in range(B):
        single = ops.neighbor_min_ell(ells[i], rps[i], aps[i])
        assert (np.asarray(batch_out[i]) == np.asarray(single)).all()


@pytest.mark.parametrize("block_rows", [16, 64, 256])
def test_neighbor_min_batch_block_sweep(block_rows, rng):
    edges, _ = random_arboric(100, 2, rng)
    g = build_graph(100, edges)
    ranks = random_permutation_ranks(100, jax.random.PRNGKey(2))
    active = jnp.ones((100,), bool)
    ell = ell_from_graph(g)
    rp, ap = pad_state(ranks, active)
    out = ops.neighbor_min_ell_batch(ell[None], rp[None], ap[None],
                                     block_rows=block_rows)
    expect = ref.neighbor_min_ref(ell, rp, ap)
    assert (np.asarray(out[0]) == np.asarray(expect)).all()


def _packed_batch(n, B, rng, width=None):
    """B random (ell, ranks_p, active_p) slices of one n-vertex bucket."""
    ells, rps, aps = [], [], []
    for i in range(B):
        edges, _ = random_arboric(n, 3, rng)
        g = build_graph(n, edges)
        key = jax.random.PRNGKey(1000 + i)
        ranks = random_permutation_ranks(n, key)
        active = jax.random.bernoulli(key, 0.5, (n,))
        ells.append(ell_from_graph(g))
        rp, ap = pad_state(ranks, active)
        rps.append(rp), aps.append(ap)
    w = max(e.shape[1] for e in ells)
    ells = [jnp.pad(e, ((0, 0), (0, w - e.shape[1])), constant_values=n)
            for e in ells]
    return jnp.stack(ells), jnp.stack(rps), jnp.stack(aps)


@pytest.mark.parametrize("block_rows", [48, 512])
def test_neighbor_min_batch_block_edge_cases(block_rows, rng):
    """block_rows > n_rows (512 on R=128) and a non-dividing tile (48 on
    R=128: 2 full blocks + a 32-row remainder) — bit-identical to the
    oracle either way."""
    n = 128
    ell, rp, ap = _packed_batch(n, 3, rng)
    out = ops.neighbor_min_ell_batch(ell, rp, ap, block_rows=block_rows)
    for i in range(3):
        expect = ref.neighbor_min_ref(ell[i], rp[i], ap[i])
        assert (np.asarray(out[i]) == np.asarray(expect)).all()


@pytest.mark.parametrize("block_rows", [48, 512])
def test_label_agree_batch_block_edge_cases(block_rows, rng):
    """Same edge tiles for the cost-pass kernel, vs its numpy-style
    oracle (label_agree_ref)."""
    n = 128
    ell, _rp, _ap = _packed_batch(n, 3, rng)
    labels = jnp.asarray(rng.integers(0, n, size=(3, n)), jnp.int32)
    labels_p = jnp.concatenate(
        [labels, jnp.full((3, 1), -1, jnp.int32)], axis=1)
    out = ops.label_agree_ell_batch(ell, labels_p, block_rows=block_rows)
    for i in range(3):
        expect = ref.label_agree_ref(ell[i], labels_p[i])
        assert (np.asarray(out[i]) == np.asarray(expect)).all()


def test_label_agree_batch_default_matches_ref(rng):
    """Default block path of the cost-pass kernel vs the oracle (the other
    batch tests route through the fused program, not the kernel alone)."""
    ell, _rp, _ap = _packed_batch(64, 2, rng)
    labels = jnp.asarray(rng.integers(0, 64, size=(2, 64)), jnp.int32)
    labels_p = jnp.concatenate(
        [labels, jnp.full((2, 1), -1, jnp.int32)], axis=1)
    out = ops.label_agree_ell_batch(ell, labels_p)
    for i in range(2):
        expect = ref.label_agree_ref(ell[i], labels_p[i])
        assert (np.asarray(out[i]) == np.asarray(expect)).all()


def test_interpret_mode_resolved_once():
    """Satellite: the wrappers read one import-time interpret flag — a
    mid-process backend probe can no longer flip the jit static arg."""
    assert isinstance(ops.interpret_mode(), bool)
    prev = ops.set_interpret_mode(True)
    try:
        assert ops.interpret_mode() is True
        # Wrappers still honour the contract under an explicit override.
        ell = jnp.full((1, 8, 4), 8, jnp.int32)
        rp = jnp.full((1, 9), 2**31 - 1, jnp.int32)
        ap = jnp.zeros((1, 9), bool)
        out = ops.neighbor_min_ell_batch(ell, rp, ap)
        assert (np.asarray(out) == 2**31 - 1).all()
    finally:
        ops.set_interpret_mode(prev)
    # None re-resolves from the live backend.
    ops.set_interpret_mode(None)
    assert ops.interpret_mode() == (jax.default_backend() != "tpu")


# --- ragged, degree-ordered sweep -------------------------------------------

def _ell_of_degrees(degs, r, w, rng):
    """(R, W) ELL whose row v holds ``degs[v]`` random distinct ids < R in
    its first slots, pad id R after them."""
    ell = np.full((r, w), r, np.int32)
    for v, d in enumerate(degs):
        ell[v, :d] = rng.choice(r, size=d, replace=False)
    return ell


def _power_law(r, w, rng, hubs=8):
    """Zipf-like degrees with a few hubs at random rows (permuted labels)."""
    degs = np.minimum(rng.zipf(1.8, size=r) - 1, w)
    degs[rng.choice(r, size=hubs, replace=False)] = rng.integers(
        w // 2, w + 1, size=hubs)
    return degs


def _ragged_case(case, rng):
    """(B, R, W) ELL of one named degree profile for the ragged kernels."""
    if case == "skewed_permuted":
        r, w = 1024, 128
        return np.stack([_ell_of_degrees(_power_law(r, w, rng), r, w, rng)])
    if case == "empty_rows":
        r, w = 256, 16
        degs = np.where(rng.random(r) < 0.7, 0, rng.integers(1, w + 1, r))
        return np.stack([_ell_of_degrees(degs, r, w, rng)])
    if case == "mid_row_pads":
        r, w = 256, 32
        ells = [_ell_of_degrees(_power_law(r, w, rng), r, w, rng)
                for _ in range(2)]
        ell = np.stack(ells)
        return np.where(rng.random(ell.shape) < 0.5, ell, r).astype(np.int32)
    if case == "r_200":
        r, w = 200, 16
        return np.stack([_ell_of_degrees(_power_law(r, w, rng), r, w, rng)
                         for _ in range(2)])
    if case == "batch_profiles":
        r, w = 384, 64
        return np.stack([_ell_of_degrees(np.full(r, w), r, w, rng),
                         _ell_of_degrees(_power_law(r, w, rng), r, w, rng),
                         _ell_of_degrees(np.zeros(r, int), r, w, rng)])
    if case == "promoted_w":
        r, w, wreq = 256, 64, 20
        return np.stack([_ell_of_degrees(_power_law(r, wreq, rng), r, w,
                                         rng)])
    if case == "row_of_degree_w":
        r, w = 256, 32
        degs = rng.integers(0, 4, size=r)
        degs[rng.integers(r)] = w
        return np.stack([_ell_of_degrees(degs, r, w, rng)])
    raise ValueError(case)


RAGGED_CASES = ["skewed_permuted", "empty_rows", "mid_row_pads", "r_200",
                "batch_profiles", "promoted_w", "row_of_degree_w"]


@pytest.mark.parametrize("block_rows", [128, 256])
@pytest.mark.parametrize("case", RAGGED_CASES)
def test_ragged_kernels_match_oracle_and_jnp(case, block_rows, rng):
    """Both ragged kernels, from the raw ELL and from a prepared layout,
    equal ``kernels.ref``'s oracles and the bucket programs' jnp path."""
    from repro.core.programs import _gather_rows, _label_agree_counts
    from repro.kernels.neighbor_min import prepare_ell

    ell = jnp.asarray(_ragged_case(case, rng))
    b, r, _ = ell.shape
    ranks = np.stack([rng.permutation(r) for _ in range(b)])
    ranks_p = jnp.asarray(np.concatenate(
        [ranks, np.full((b, 1), 2**31 - 1)], axis=1), jnp.int32)
    active_p = jnp.asarray(np.concatenate(
        [rng.random((b, r)) < 0.6, np.zeros((b, 1), bool)], axis=1))
    labels = jnp.asarray(rng.integers(0, max(1, r // 8), size=(b, r)),
                         jnp.int32)
    labels_p = jnp.concatenate([labels, jnp.full((b, 1), -1, jnp.int32)],
                               axis=1)
    layout = prepare_ell(ell)

    nm_jnp = jnp.min(jnp.where(_gather_rows(active_p, ell),
                               _gather_rows(ranks_p, ell), 2**31 - 1), axis=2)
    la_jnp = _label_agree_counts(ell, labels, use_kernel=False, la_rows=None,
                                 layout=None)
    for src in (ell, layout):
        nm = np.asarray(ops.neighbor_min_ell_batch(src, ranks_p, active_p,
                                                   block_rows=block_rows))
        la = np.asarray(ops.label_agree_ell_batch(src, labels_p,
                                                  block_rows=block_rows))
        assert (nm == np.asarray(nm_jnp)).all()
        assert (la == np.asarray(la_jnp)).all()
        for i in range(b):
            assert (nm[i] == np.asarray(ref.neighbor_min_ref(
                ell[i], ranks_p[i], active_p[i]))).all()
            assert (la[i] == np.asarray(ref.label_agree_ref(
                ell[i], labels_p[i]))).all()


def _numpy_tiles(ell):
    """Σ over 128-lane groups of ceil(widest row / 8) once each entry's
    rows are ordered by width, widest first; and R_lanes/128 · W/8."""
    b, r, w = ell.shape
    slot = np.arange(1, w + 1)
    width = np.where(ell < r, slot, 0).max(axis=2)
    lanes = -(-r // 128) * 128
    swept = []
    for row in width:
        ordered = np.sort(row)[::-1]
        swept.append(sum(-(-int(ordered[g]) // 8)
                         for g in range(0, r, 128)))
    return np.array(swept), (lanes // 128) * (w // 8)


@pytest.mark.parametrize("case", RAGGED_CASES)
def test_prepare_ell_orders_rows_and_counts_tiles(case, rng):
    """The layout holds every row once, widest first (stable), lane-major;
    its tile counts are the numpy count and cover every real id."""
    from repro.kernels.neighbor_min import prepare_ell, tile_counts

    ell = _ragged_case(case, rng)
    b, r, w = ell.shape
    layout = prepare_ell(jnp.asarray(ell))
    order = np.asarray(layout.order)
    lm = np.asarray(layout.ell)
    width = np.where(ell < r, np.arange(1, w + 1), 0).max(axis=2)
    for i in range(b):
        assert (order[i] == np.argsort(-width[i], kind="stable")).all()
        assert (lm[i, :, :r].T == ell[i][order[i]]).all()
        assert (lm[i, :, r:] == r).all()
        tiles = np.asarray(layout.tiles[i])
        for g, t in enumerate(tiles):      # no real id past a group's tiles
            assert (lm[i, 8 * t:, 128 * g:128 * (g + 1)] >= r).all()
    swept, full = _numpy_tiles(ell)
    counts = np.asarray(tile_counts(jnp.asarray(ell), layout))
    assert (counts[:, 0] == swept).all() and (counts[:, 1] == full).all()
    assert (np.asarray(tile_counts(jnp.asarray(ell)))[:, 0] == full).all()


# --- flash attention --------------------------------------------------------

SHAPES = [
    (1, 4, 4, 128, 128, 64, True, jnp.float32),
    (2, 4, 2, 128, 128, 64, True, jnp.float32),     # GQA
    (1, 8, 1, 256, 256, 64, True, jnp.bfloat16),    # MQA bf16
    (2, 4, 4, 128, 384, 64, True, jnp.float32),     # kv longer (decode-ish)
    (1, 2, 2, 192, 192, 32, False, jnp.float32),    # non-causal, ragged
    (1, 9, 3, 130, 130, 64, True, jnp.float32),     # odd sizes (padding)
    (1, 4, 4, 64, 64, 128, True, jnp.bfloat16),     # big head dim
]


@pytest.mark.parametrize("b,h,kh,sq,sk,d,causal,dtype", SHAPES)
def test_flash_attention_matches_ref(b, h, kh, sq, sk, d, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, kh, sk, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, kh, sk, d), jnp.float32).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=causal)
    expect = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - expect.astype(jnp.float32))))
    assert err < tol, (err, tol)


def test_flash_attention_block_sweep():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64))
    k = jax.random.normal(ks[1], (1, 4, 256, 64))
    v = jax.random.normal(ks[2], (1, 4, 256, 64))
    expect = ref.attention_ref(q, k, v, causal=True)
    for bq, bk in [(64, 64), (128, 256), (256, 128)]:
        out = ops.flash_attention(q, k, v, causal=True, block_q=bq,
                                  block_k=bk)
        assert float(jnp.max(jnp.abs(out - expect))) < 2e-5


def test_chunked_xla_attention_matches_ref():
    """The pure-XLA blocked softmax (production CPU/dry-run path) — same
    contract as the kernel."""
    from repro.models.attention import _chunked_attention, _naive_attention
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    b, sq, kh, g, hd, sk = 2, 200, 2, 2, 32, 200
    q = jax.random.normal(ks[0], (b, sq, kh, g, hd))
    k = jax.random.normal(ks[1], (b, sk, kh, hd))
    v = jax.random.normal(ks[2], (b, sk, kh, hd))
    for causal in (True, False):
        a = _chunked_attention(q, k, v, causal, q_chunk=64, kv_chunk=96)
        e = _naive_attention(q, k, v, causal)
        assert float(jnp.max(jnp.abs(a - e))) < 2e-5
