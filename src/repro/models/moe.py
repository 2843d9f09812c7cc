"""Mixture-of-Experts layer with sort-based capacity dispatch and
shard_map expert parallelism (EP over the "model" mesh axis).

Dispatch: token->expert pairs are sorted by expert id and packed into a
per-expert capacity buffer (E_local, C, d) — static shapes, no host-side
ragged ops; overflow beyond capacity C = ceil(T*k*cf/E) is dropped
(standard capacity-factor semantics).  Under EP each device computes only
its local expert shard against (replicated-over-model) tokens; the
combine is a psum over the model axis.  This maps VTA's "explicit memory
arbitration" philosophy onto the MoE layer: the dispatch buffer is an
explicitly-managed scratchpad with a hard capacity, not an implicit cache.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.meshctx import get_mesh

from .layers import linear_apply, linear_init, mlp_apply, mlp_init

Params = Dict[str, Any]


def moe_init(key, cfg) -> Params:
    d = cfg.d_model
    E = cfg.moe_experts
    f = cfg.moe_d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p: Params = {
        "router": {"w": (jax.random.uniform(ks[0], (d, E), jnp.float32,
                                            -scale, scale)).astype(jnp.float32)},
        "wi": (jax.random.uniform(ks[1], (E, d, f), jnp.float32, -scale, scale)
               ).astype(dt),
        "wg": (jax.random.uniform(ks[2], (E, d, f), jnp.float32, -scale, scale)
               ).astype(dt),
        "wo": (jax.random.uniform(ks[3], (E, f, d), jnp.float32,
                                  -1 / math.sqrt(f), 1 / math.sqrt(f))).astype(dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], cfg, d,
                               cfg.n_shared_experts * (cfg.moe_d_ff or cfg.d_ff))
    return p


def _capacity(T: int, k: int, E: int, cf: float) -> int:
    c = int(math.ceil(T * k * cf / E))
    return max(8, -(-c // 8) * 8)  # round up to 8 for lane friendliness


def _expert_ffn(buf: jax.Array, wi: jax.Array, wg: jax.Array,
                wo: jax.Array) -> jax.Array:
    """buf: (E, C, d) -> (E, C, d), swiglu per expert."""
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
         * jnp.einsum("ecd,edf->ecf", buf, wi))
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _dispatch_compute_combine(xt: jax.Array, flat_e: jax.Array,
                              flat_g: jax.Array, k: int, n_local: int,
                              e_offset, C: int, wi, wg, wo,
                              expert_ffn=None) -> jax.Array:
    """Core dispatch for a token shard against a local expert shard.

    xt: (T, d); flat_e/flat_g: (T*k,) global expert ids / gate weights.
    `expert_ffn` overrides the per-expert FFN (2-D sharded serving path).
    Returns this expert-shard's contribution: (T, d).
    """
    T, d = xt.shape
    Tk = T * k
    flat_t = jnp.arange(Tk, dtype=jnp.int32) // k
    e_local = flat_e - e_offset
    is_local = (e_local >= 0) & (e_local < n_local)
    sort_key = jnp.where(is_local, e_local, n_local)     # non-local -> end
    order = jnp.argsort(sort_key, stable=True)
    sid = sort_key[order]                                # sorted local ids
    # position within each expert segment (cummax-of-starts trick)
    idx = jnp.arange(Tk, dtype=jnp.int32)
    is_new = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    starts = jax.lax.associative_scan(jnp.maximum,
                                      jnp.where(is_new, idx, 0))
    pos = idx - starts
    keep = (sid < n_local) & (pos < C)
    dest = jnp.where(keep, sid * C + pos, n_local * C)   # overflow slot
    gathered = jnp.take(xt, flat_t[order], axis=0)       # (Tk, d)
    buf = jnp.zeros((n_local * C + 1, d), xt.dtype).at[dest].set(gathered)
    ffn = expert_ffn or (lambda b: _expert_ffn(b, wi, wg, wo))
    out_buf = ffn(buf[:n_local * C].reshape(n_local, C, d))
    out_pad = jnp.concatenate(
        [out_buf.reshape(n_local * C, d),
         jnp.zeros((1, d), xt.dtype)], axis=0)
    contrib = jnp.take(out_pad, dest, axis=0) * flat_g[order][:, None].astype(xt.dtype)
    y = jnp.zeros((T, d), xt.dtype).at[flat_t[order]].add(contrib)
    return y


def _route(cfg, xt: jax.Array, router_w: jax.Array
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing with renormalized gates + load-balancing aux loss."""
    logits = xt.astype(jnp.float32) @ router_w           # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_g, top_i = jax.lax.top_k(probs, cfg.moe_top_k)
    top_g = top_g / jnp.maximum(jnp.sum(top_g, -1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * sum_e fraction_e * prob_e
    E = cfg.moe_experts
    onehot = jax.nn.one_hot(top_i[..., 0], E, dtype=jnp.float32)
    frac = jnp.mean(onehot, axis=0)
    prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * prob)
    return top_i.astype(jnp.int32), top_g, aux


def _route_local(cfg, xt: jax.Array, router_w: jax.Array):
    """Routing math shared by the outside path and the fused-EP path.
    Returns (flat_e, flat_g, (count_sum, prob_sum)) with flat arrays of
    length T*k and per-expert partial sums for the aux loss."""
    k, E = cfg.moe_top_k, cfg.moe_experts
    logits = xt.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    top_g, top_i = jax.lax.top_k(probs, k)
    top_g = top_g / jnp.maximum(jnp.sum(top_g, -1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(top_i[..., 0], E, dtype=jnp.float32)
    return (top_i.astype(jnp.int32).reshape(-1), top_g.reshape(-1),
            (jnp.sum(onehot, axis=0), jnp.sum(probs, axis=0)))


def _shared_partial(cfg, xt: jax.Array, sh: Params) -> jax.Array:
    """Shared-expert contribution from a model-rank's f-slice (partial sum
    completed by the EP combine psum)."""
    h = (jax.nn.silu(xt @ sh["wg"]["w"].astype(xt.dtype))
         * (xt @ sh["wi"]["w"].astype(xt.dtype)))
    return h @ sh["wo"]["w"].astype(xt.dtype)


def moe_apply(p: Params, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss)."""
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    k, E = cfg.moe_top_k, cfg.moe_experts

    mesh = get_mesh()
    sc = cfg.sharding
    if sc.enabled and mesh is not None and sc.model_axis in mesh.axis_names:
        tp = mesh.shape[sc.model_axis]
    else:
        tp = 1

    if tp > 1 and E % tp == 0 and cfg.moe_fused_ep:
        dp_axes_ = tuple(a for a in sc.data_axes
                         if a in mesh.axis_names and a != sc.model_axis)
        dp_size_ = 1
        for a in dp_axes_:
            dp_size_ *= mesh.shape[a]
        if T % (dp_size_ * tp) == 0:   # decode batches may be too small
            return _moe_fused_ep(p, cfg, xt, mesh, tp, B, S)

    top_i, top_g, aux = _route(cfg, xt, p["router"]["w"])
    flat_e = top_i.reshape(-1)
    flat_g = top_g.reshape(-1)

    if tp > 1 and E % tp == 0:
        n_local = E // tp
        dp_axes = tuple(a for a in sc.data_axes if a in mesh.axis_names)
        # tokens sharded over data axes, replicated over model;
        # experts sharded over model axis; combine = psum over model.
        dp_size = 1
        for a in dp_axes:
            dp_size *= mesh.shape[a]
        # 2-D resident experts (serving): weights enter the shard_map in
        # their stored (E:model, d:data) layout — zero weight collectives;
        # tokens must then be REPLICATED across data ranks (each rank
        # holds a d-slice of every token's contraction).
        expert_2d = (cfg.moe_expert_2d and len(dp_axes) > 0
                     and d % dp_size == 0)
        # capacity per expert, sized from the *local* token shard
        C = _capacity(max(1, T if expert_2d else T // dp_size),
                      k, E, cfg.moe_capacity_factor)

        combine = cfg.moe_combine
        if combine == "reduce_scatter" and (T // max(1, dp_size)) % tp != 0:
            combine = "psum"   # decode batches too small to scatter
        token_gather = (not expert_2d and cfg.moe_token_gather
                        and T % (dp_size * tp) == 0)

        def local_fn(xt_l, fe_l, fg_l, wi_l, wg_l, wo_l):
            if token_gather:
                xt_l = jax.lax.all_gather(xt_l, sc.model_axis, axis=0,
                                          tiled=True)
            e_off = jax.lax.axis_index(sc.model_axis) * n_local
            ffn2d = None
            if expert_2d:
                ds = d // dp_size
                dpi = jnp.int32(0)
                mult = 1
                for a in reversed(dp_axes):
                    dpi = dpi + jax.lax.axis_index(a) * mult
                    mult *= mesh.shape[a]

                def ffn2d(buf, wi_l=wi_l, wg_l=wg_l, wo_l=wo_l, dpi=dpi):
                    # buf: (E_l, C, d) full-d; weights: (E_l, d/dp, f),
                    # (E_l, f, d/dp) — slice buf to this rank's d-shard
                    buf_l = jax.lax.dynamic_slice_in_dim(
                        buf, dpi * ds, ds, axis=2)
                    hg = jax.lax.psum(
                        jnp.einsum("ecd,edf->ecf", buf_l, wg_l), dp_axes)
                    hi = jax.lax.psum(
                        jnp.einsum("ecd,edf->ecf", buf_l, wi_l), dp_axes)
                    h = jax.nn.silu(hg) * hi
                    y_part = jnp.einsum("ecf,efd->ecd", h, wo_l)
                    return jax.lax.all_gather(
                        y_part, dp_axes, axis=2, tiled=True)

            y = _dispatch_compute_combine(xt_l, fe_l, fg_l, k, n_local,
                                          e_off, C, wi_l, wg_l, wo_l,
                                          expert_ffn=ffn2d)
            if combine == "psum_bf16":
                return jax.lax.psum(y.astype(jnp.bfloat16),
                                    sc.model_axis).astype(xt_l.dtype)
            if combine == "reduce_scatter":
                # half the wire bytes of an all-reduce; output arrives
                # token-sharded over model — pairs with seq-parallel
                # residuals which keep it sharded between layers
                return jax.lax.psum_scatter(
                    y.astype(jnp.bfloat16), sc.model_axis,
                    scatter_dimension=0, tiled=True).astype(xt_l.dtype)
            return jax.lax.psum(y, sc.model_axis)

        dp = dp_axes if dp_axes else None
        if combine == "reduce_scatter":
            axes0 = (tuple(dp_axes) + (sc.model_axis,)) if dp_axes \
                else (sc.model_axis,)
            out_spec = P(axes0, None)
        else:
            out_spec = P(dp, None)
        xt_spec = (P((tuple(dp_axes) + (sc.model_axis,)) if dp_axes
                     else sc.model_axis, None)
                   if token_gather else P(dp, None))
        if expert_2d:
            # weights consumed in their stored 2-D layout, no resharding;
            # tokens/gates replicated across data ranks; output identical
            # on every data rank (the replication checker can't prove it
            # — disabled via check_vma)
            wi_spec = P(sc.model_axis, dp, None)
            wo_spec = P(sc.model_axis, None, dp)
            xt_spec = P(None, None)
            fe_spec = fg_spec = P(None)
            out_spec = P(None, None)
        else:
            wi_spec = P(sc.model_axis, None, None)
            wo_spec = P(sc.model_axis, None, None)
            fe_spec = fg_spec = P(dp)
        y = jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(xt_spec, fe_spec, fg_spec,
                      wi_spec, wi_spec, wo_spec),
            out_specs=out_spec,
            check_vma=not expert_2d,
        )(xt, flat_e, flat_g, p["wi"], p["wg"], p["wo"])
    else:
        C = _capacity(T, k, E, cfg.moe_capacity_factor)
        y = _dispatch_compute_combine(xt, flat_e, flat_g, k, E, 0, C,
                                      p["wi"], p["wg"], p["wo"])

    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], xt, cfg)
    return y.reshape(B, S, d), aux


def _moe_fused_ep(p: Params, cfg, xt: jax.Array, mesh, tp: int,
                  B: int, S: int) -> Tuple[jax.Array, jax.Array]:
    """Fully fused expert parallelism: tokens enter model-sharded and are
    all-gathered in bf16 inside the shard_map; routing, dispatch, expert
    FFN, the shared expert (f-sliced per rank) and the aux-loss partials
    all happen per device; ONE psum over "model" combines everything.

    Removes (measured on kimi-k2): the router-probs all-gather, the
    unsharded shared-expert activation gather, and the f32 replicated-
    input backward psum — the three largest collective line items of the
    baseline MoE layer."""
    T, d = xt.shape
    k, E = cfg.moe_top_k, cfg.moe_experts
    sc = cfg.sharding
    n_local = E // tp
    dp_axes = tuple(a for a in sc.data_axes
                    if a in mesh.axis_names and a != sc.model_axis)
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    C = _capacity(max(1, T // dp_size), k, E, cfg.moe_capacity_factor)
    has_shared = bool(cfg.n_shared_experts)
    combine = cfg.moe_combine
    if combine == "reduce_scatter" and (T // max(1, dp_size)) % tp != 0:
        combine = "psum"   # decode batches too small to scatter

    def local_fn(xt_l, router_w, wi_l, wg_l, wo_l, *shared):
        xt_full = jax.lax.all_gather(xt_l, sc.model_axis, axis=0, tiled=True)
        fe, fg, (cnt, psum_probs) = _route_local(cfg, xt_full, router_w)
        e_off = jax.lax.axis_index(sc.model_axis) * n_local
        y = _dispatch_compute_combine(xt_full, fe, fg, k, n_local,
                                      e_off, C, wi_l, wg_l, wo_l)
        if has_shared:
            y = y + _shared_partial(cfg, xt_full,
                                    {"wg": {"w": shared[0]},
                                     "wi": {"w": shared[1]},
                                     "wo": {"w": shared[2]}})
        if combine == "reduce_scatter":
            y = jax.lax.psum_scatter(y.astype(jnp.bfloat16), sc.model_axis,
                                     scatter_dimension=0,
                                     tiled=True).astype(xt_l.dtype)
        else:
            y = jax.lax.psum(y, sc.model_axis)
        # aux-loss partials: identical across model ranks (computed from
        # the gathered tokens), so psum over model + /tp both replicates
        # them for the VMA checker and leaves the value unchanged
        red_axes = tuple(dp_axes) + (sc.model_axis,)
        cnt = jax.lax.psum(cnt, red_axes) / tp
        psum_probs = jax.lax.psum(psum_probs, red_axes) / tp
        return y, cnt, psum_probs

    tok_axes = (tuple(dp_axes) + (sc.model_axis,)) if dp_axes \
        else (sc.model_axis,)
    y_spec = (P(tok_axes, None) if combine == "reduce_scatter"
              else P(dp_axes if dp_axes else None, None))
    args = [xt, p["router"]["w"], p["wi"], p["wg"], p["wo"]]
    in_specs = [P(tok_axes, None), P(None, None),
                P(sc.model_axis, None, None),
                P(sc.model_axis, None, None),
                P(sc.model_axis, None, None)]
    if has_shared:
        args += [p["shared"]["wg"]["w"], p["shared"]["wi"]["w"],
                 p["shared"]["wo"]["w"]]
        in_specs += [P(None, sc.model_axis), P(None, sc.model_axis),
                     P(sc.model_axis, None)]
    y, cnt, prob_sum = jax.shard_map(
        local_fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(y_spec, P(), P()))(*args)
    frac = cnt / jnp.maximum(jnp.sum(cnt), 1.0)
    prob = prob_sum / jnp.maximum(jnp.sum(cnt), 1.0)
    aux = E * jnp.sum(frac * prob)
    return y.reshape(B, S, d), aux
