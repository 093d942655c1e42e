"""MLP blocks: SwiGLU dense and Mixture-of-Experts.

MoE ships two dispatch implementations with identical semantics:

* ``moe_impl='einsum'`` — classic one-hot dispatch/combine einsums
  (ParallelPIVOT-era MapReduce style: dense masks of shape (T, E, C)).
  Simple, GSPMD-friendly — but the dispatch matmuls cost O(T·E·C·d) MXU
  FLOPs, which for olmoe (64 experts) *exceeds* the expert FLOPs ~2.7×.
* ``moe_impl='sort'``  — gather/scatter dispatch: assignments are sorted by
  expert, tokens are *gathered* into (E, C, d) expert batches and results
  scatter-added back. Only the expert matmuls hit the MXU; dispatch is
  pure data movement. This is the beyond-paper optimization measured in
  EXPERIMENTS.md §Perf (compute-term drop on the MoE cells).

Both respect capacity ``C = ceil(T/E · k · capacity_factor)`` with dropped
overflow tokens (standard; combine weights renormalized over kept experts).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .common import Pm, constrain, dense_init, linear


def init_mlp(cfg: ModelConfig, kg, dtype, plan, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wi": Pm(dense_init(kg(), (d, f), dtype), plan.P("embed", "ff")),
        "wg": Pm(dense_init(kg(), (d, f), dtype), plan.P("embed", "ff")),
        "wo": Pm(dense_init(kg(), (f, d), dtype), plan.P("ff", "embed")),
    }


def mlp(params, x):
    h = jax.nn.silu(linear(x, params["wg"])) * linear(x, params["wi"])
    return linear(h, params["wo"])


def init_moe(cfg: ModelConfig, kg, dtype, plan):
    d = cfg.d_model
    e = cfg.num_experts
    f = cfg.moe_d_ff or cfg.d_ff
    return {
        "router": Pm(dense_init(kg(), (d, e), jnp.float32),
                     plan.P("embed", None)),
        "wi": Pm(dense_init(kg(), (e, d, f), dtype),
                 plan.P("experts", "expert_embed", "expert_ff")),
        "wg": Pm(dense_init(kg(), (e, d, f), dtype),
                 plan.P("experts", "expert_embed", "expert_ff")),
        "wo": Pm(dense_init(kg(), (e, f, d), dtype),
                 plan.P("experts", "expert_ff", "expert_embed")),
    }


def _router(params, x, cfg: ModelConfig):
    """Top-k routing. x (T, d) → gates (T, k), experts (T, k)."""
    logits = linear(x.astype(jnp.float32), params["router"])  # (T, E)
    k = cfg.experts_per_tok
    gates, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(gates, axis=-1)
    return gates, idx


def _capacity(t: int, cfg: ModelConfig, factor: float) -> int:
    c = int(t * cfg.experts_per_tok * factor / cfg.num_experts) + 1
    c = max(4, min(t, c))
    return ((c + 31) // 32) * 32  # divisible by any batch-shard span


def _experts_ffn(params, xin):
    """xin (E, C, d) → (E, C, d), batched expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, params["wg"])) * (
        jnp.einsum("ecd,edf->ecf", xin, params["wi"]))
    return jnp.einsum("ecf,efd->ecd", h, params["wo"])


def moe_einsum(params, x, cfg: ModelConfig, capacity_factor: float = 1.25,
               plan=None):
    """One-hot dispatch/combine MoE. x (T, d)."""
    t, d = x.shape
    e = cfg.num_experts
    c = _capacity(t, cfg, capacity_factor)
    gates, idx = _router(params, x, cfg)                  # (T, k)

    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)    # (T, k, E)
    # Position of each (token, expert) assignment in the expert queue.
    pos = jnp.cumsum(onehot.reshape(t * cfg.experts_per_tok, e), axis=0
                     ).reshape(t, cfg.experts_per_tok, e) - 1.0
    keep = (pos < c) & (onehot > 0)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), c, dtype=jnp.float32)
    dispatch = jnp.einsum("tke,tkec->tec", onehot * keep, pos_oh)  # (T,E,C)
    combine = jnp.einsum("tk,tke,tkec->tec", gates, onehot * keep, pos_oh)

    xin = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    if plan is not None and plan.axes.get("moe_c") is not None:
        xin = constrain(xin, plan, "experts", "moe_c", None)
    out = _experts_ffn(params, xin)
    return jnp.einsum("tec,ecd->td", combine.astype(out.dtype), out)


def moe_sort(params, x, cfg: ModelConfig, capacity_factor: float = 1.25,
             plan=None):
    """Gather/scatter dispatch MoE (no one-hot matmuls). x (T, d)."""
    t, d = x.shape
    e = cfg.num_experts
    k = cfg.experts_per_tok
    c = _capacity(t, cfg, capacity_factor)
    gates, idx = _router(params, x, cfg)                  # (T, k)

    flat_e = idx.reshape(-1)                              # (T*k,)
    flat_g = gates.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    tok_sorted = flat_tok[order]
    g_sorted = flat_g[order]
    # Rank within expert: global position − start offset of that expert.
    counts = jnp.zeros((e,), jnp.int32).at[e_sorted].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(t * k) - starts[e_sorted]
    valid = rank < c
    slot = jnp.where(valid, rank, 0)

    # Gather tokens into expert batches (scatter into (E, C, d)).
    xin = jnp.zeros((e, c, d), x.dtype)
    xin = xin.at[e_sorted, slot].add(
        jnp.where(valid[:, None], x[tok_sorted], 0).astype(x.dtype))
    # Optional (off by default — measured WORSE): forcing the expert batch
    # onto (experts, data-sharded capacity) makes the token scatter itself
    # cross-shard and quadrupled collective bytes on grok-1 (§Perf H2
    # iter 3, refuted hypothesis). Enable via plan axes["moe_c"].
    if plan is not None and plan.axes.get("moe_c") is not None:
        xin = constrain(xin, plan, "experts", "moe_c", None)
    out = _experts_ffn(params, xin)                       # (E, C, d)
    if plan is not None and plan.axes.get("moe_c") is not None:
        out = constrain(out, plan, "experts", "moe_c", None)

    # Scatter-combine back to tokens.
    vals = out[e_sorted, slot] * (g_sorted * valid)[:, None].astype(out.dtype)
    y = jnp.zeros((t, d), out.dtype).at[tok_sorted].add(vals)
    return y


def moe(params, x, cfg: ModelConfig, impl: str = "sort",
        capacity_factor: float = 1.25, token_chunk: int = 65_536,
        plan=None, mesh=None):
    """x (B, S, d) → (B, S, d).

    ``impl``: 'sort' (gather/scatter dispatch), 'einsum' (one-hot masks),
    'ep_local' (shard_map expert parallelism — see moe_ep_local).

    Long-sequence batches are scanned through the expert layer in
    ``token_chunk`` slices: the dispatch buffers scale with the chunk, not
    the full (batch × seq) token count — without this, olmoe's 64-expert
    dispatch at 32k-prefill materializes ~43 GB of (E, C, d) buffers.
    """
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    t = xt.shape[0]
    if impl == "ep_local":
        if mesh is None or plan is None or plan.axes.get("experts") is None:
            fn = moe_sort          # graceful fallback (smoke/1-device)
        else:
            y = moe_ep_local(params, xt, cfg, capacity_factor, plan, mesh)
            return y.reshape(b, s, d).astype(x.dtype)
    if impl == "einsum":
        fn = moe_einsum
    else:
        fn = moe_sort
    if t <= token_chunk:
        y = fn(params, xt, cfg, capacity_factor, plan=plan)
    else:
        pad = (-t) % token_chunk
        if pad:
            xt = jnp.pad(xt, ((0, pad), (0, 0)))
        nc = (t + pad) // token_chunk
        xc = xt.reshape(nc, token_chunk, d)

        @jax.checkpoint
        def step(_, xi):
            return None, fn(params, xi, cfg, capacity_factor, plan=plan)

        _, yc = jax.lax.scan(step, None, xc)
        y = yc.reshape(-1, d)[:t]
    return y.reshape(b, s, d).astype(x.dtype)


__all__ = ["init_mlp", "mlp", "init_moe", "moe", "moe_einsum", "moe_sort"]


# ---------------------------------------------------------------------------
# ep_local: shard_map expert parallelism without cross-shard dispatch.
# ---------------------------------------------------------------------------


def moe_ep_local(params, x, cfg: ModelConfig, capacity_factor: float,
                 plan, mesh):  # noqa: D401
    """Expert parallelism with *local* dispatch + one psum combine.

    Layout: activations are replicated over 'model' (standard TP layout), so
    every model column of a data row already holds the tokens — no token
    movement is needed at all. Each model shard owns E/|model| experts,
    gathers its assigned tokens from the local activation slab, runs its
    experts, and contributes a partial (T_loc, d) output; one bf16 psum over
    'model' completes the combine. GSPMD never sees the dispatch (it is
    shard-local jnp), eliminating the partial-activation all-reduces that
    dominate the capacity-dispatch path (§Perf H1/H2: 11.5 TiB → ~0.4 TiB
    on olmoe train_4k).

    Requirements: plan.axes['experts'] is a mesh axis dividing E, and
    x's token dim divides the batch axes. Per-(data-shard × expert)
    capacity = T_loc·k·cf/E (drop semantics are per data shard).
    """
    from jax.sharding import PartitionSpec as P

    t, d = x.shape
    e = cfg.num_experts
    k = cfg.experts_per_tok
    model_ax = plan.axes.get("experts")
    batch_ax = plan.axes.get("batch")
    assert model_ax is not None, "ep_local needs expert-parallel plan"
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    msize = axis_sizes[model_ax]
    e_loc = e // msize
    batch_axes = ((batch_ax,) if isinstance(batch_ax, str)
                  else tuple(batch_ax or ()))

    def _dispatch_chunk(x_loc, router, wi, wg, wo, m):
        t_loc = x_loc.shape[0]
        c = max(4, int(t_loc * k * capacity_factor / e) + 1)
        logits = jax.lax.dot_general(
            x_loc.astype(jnp.float32), router,
            (((1,), (0,)), ((), ())))
        gates, idx = jax.lax.top_k(logits, k)
        gates = jax.nn.softmax(gates, axis=-1)
        # Assignments owned by this shard: experts [m·e_loc, (m+1)·e_loc).
        flat_e = idx.reshape(-1) - m * e_loc
        flat_g = gates.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(t_loc), k)
        mine = (flat_e >= 0) & (flat_e < e_loc)
        e_mine = jnp.where(mine, flat_e, e_loc)       # spill row e_loc
        order = jnp.argsort(e_mine, stable=True)
        e_sorted = e_mine[order]
        tok_sorted = flat_tok[order]
        g_sorted = flat_g[order]
        counts = jnp.zeros((e_loc + 1,), jnp.int32).at[e_sorted].add(1)
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(counts)[:-1]])
        rank = jnp.arange(t_loc * k) - starts[e_sorted]
        valid = (e_sorted < e_loc) & (rank < c)
        slot = jnp.where(valid, rank, 0)
        row = jnp.where(valid, e_sorted, e_loc)
        xin = jnp.zeros((e_loc + 1, c, d), x_loc.dtype)
        xin = xin.at[row, slot].add(
            jnp.where(valid[:, None], x_loc[tok_sorted], 0
                      ).astype(x_loc.dtype))
        xin = xin[:e_loc]
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, wg)) * (
            jnp.einsum("ecd,edf->ecf", xin, wi))
        out = jnp.einsum("ecf,efd->ecd", h, wo)        # (E_loc, C, d)
        out = jnp.concatenate(
            [out, jnp.zeros((1, c, d), out.dtype)], axis=0)
        vals = out[row, slot] * (g_sorted * valid)[:, None].astype(out.dtype)
        return jnp.zeros((t_loc, d), out.dtype).at[tok_sorted].add(vals)

    def body(x_loc, router, wi, wg, wo):
        m = jax.lax.axis_index(model_ax)
        t_loc = x_loc.shape[0]
        chunk = min(8192, t_loc)
        if t_loc % chunk:
            chunk = t_loc
        if t_loc == chunk:
            y_part = _dispatch_chunk(x_loc, router, wi, wg, wo, m)
        else:
            xc = x_loc.reshape(t_loc // chunk, chunk, d)

            @jax.checkpoint
            def step(_, xi):
                return None, _dispatch_chunk(xi, router, wi, wg, wo, m)

            _, yc = jax.lax.scan(step, None, xc)
            y_part = yc.reshape(t_loc, d)
        return jax.lax.psum(y_part, model_ax)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_ax, None), P(None, None),
                  P(model_ax, None, None), P(model_ax, None, None),
                  P(model_ax, None, None)),
        out_specs=P(batch_ax, None),
        check_vma=False,
    )(x, params["router"], params["wi"], params["wg"], params["wo"])
