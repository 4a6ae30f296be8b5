"""MoE dispatch with explicit all-to-all (shard_map), bypassing GSPMD.

Why this exists (EXPERIMENTS.md §Perf cell 3): GSPMD lowers the
token(data)->expert(model) `jnp.take` as mask + ALL-REDUCE of the full
(E*cap, D) expert buffer (~21 GB/layer/microbatch at qwen3-30B train_4k,
227 s of ICI time per step). The classic Switch decomposition moves only
the routed tokens: each device routes its local tokens, buckets them by
destination model-rank, and a single `all_to_all` over the model axis
delivers them to the experts' owners (payload ~= T*K*D/chips).

Manual collectives over BOTH mesh axes; expert weights arrive sharded
over the model axis (E_loc = E/mp experts per rank; fsdp on the weight
D/F dims is all-gathered locally, mirroring the GSPMD FSDP pattern).
Differentiable end-to-end (all_to_all / all_gather are linear).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.base import ArchConfig
from repro.parallel import sharding as shd


def _bucket_by_dest(ids, gates, xt, *, n_dest, cap, e_loc):
    """Group routed (token, expert) pairs into per-destination buckets.
    ids/gates: (T*K,), xt: (T, D). Returns send buffers:
      xs   (n_dest, cap, D)   token vectors
      meta (n_dest, cap, 3)   [local_expert, gate, src_row] (-1 pad)
    """
    TK = ids.shape[0]
    T, D = xt.shape
    dest = ids // e_loc                                   # (TK,)
    order = jnp.argsort(dest, stable=True)
    d_s, ids_s = dest[order], ids[order]
    gates_s = gates[order]
    src_s = (jnp.arange(TK, dtype=jnp.int32) // (TK // T))[order]

    pos = jnp.arange(TK, dtype=jnp.int32)
    seg_start = jnp.searchsorted(d_s, jnp.arange(n_dest, dtype=d_s.dtype),
                                 side="left")
    pos_in_dest = pos - seg_start[d_s]
    keep = pos_in_dest < cap
    slot = jnp.where(keep, d_s.astype(jnp.int32) * cap + pos_in_dest,
                     n_dest * cap)

    xs = jnp.zeros((n_dest * cap + 1, D), xt.dtype).at[slot].set(
        jnp.take(xt, src_s, axis=0), mode="drop")[:-1]
    rows3 = jnp.stack([(ids_s % e_loc).astype(jnp.float32), gates_s,
                       src_s.astype(jnp.float32)], axis=-1)     # (TK, 3)
    meta = jnp.full((n_dest * cap + 1, 3), -1.0, jnp.float32).at[slot].set(
        rows3, mode="drop")[:-1]
    return xs.reshape(n_dest, cap, D), meta.reshape(n_dest, cap, 3)


def moe_shardmap(cfg: ArchConfig, p: dict, x: jnp.ndarray,
                 *, capacity_factor: float = 1.25):
    """Drop-in for layers.moe.moe() when a mesh with (data, model) axes is
    active. x: (B, S, D) batch-sharded over data. Returns (out, aux)."""
    mesh = shd.active_mesh()
    assert mesh is not None and "model" in mesh.shape
    mp = mesh.shape["model"]
    E, K, D = cfg.n_experts, cfg.experts_per_token, cfg.d_model
    e_loc = E // mp

    def body(xb, rw, wi, wg, wo):
        # xb (B_loc, S, D) replicated over model; weights (E_loc, D, F)
        B_loc, S, _ = xb.shape
        midx = jax.lax.axis_index("model")
        T_all = B_loc * S
        T_loc = T_all // mp
        xt_all = xb.reshape(T_all, D)
        xt = jax.lax.dynamic_slice_in_dim(xt_all, midx * T_loc, T_loc)

        # local routing
        logits = xt.astype(jnp.float32) @ rw.astype(jnp.float32)   # (T_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, K)
        if cfg.moe_norm_topk:
            gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        counts = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0)
        lb = E * jnp.sum(me * (counts / T_loc))
        zl = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        lb = jax.lax.pmean(jax.lax.pmean(lb, "model"), "data")
        zl = jax.lax.pmean(jax.lax.pmean(zl, "model"), "data")

        cap = int(max(1, capacity_factor * T_loc * K / mp))
        xs, meta = _bucket_by_dest(
            expert_ids.reshape(-1), gate_vals.reshape(-1).astype(jnp.float32),
            xt, n_dest=mp, cap=cap, e_loc=e_loc)

        # the all-to-all: tokens travel to their experts' owners
        xr = jax.lax.all_to_all(xs, "model", split_axis=0, concat_axis=0,
                                tiled=False)
        mr = jax.lax.all_to_all(meta, "model", split_axis=0, concat_axis=0,
                                tiled=False)
        # xr: (mp, cap, D) rows from each source rank; local experts only
        xr_f = xr.reshape(mp * cap, D)
        le = mr.reshape(mp * cap, 3)[:, 0]                # local expert or -1
        valid = le >= 0

        # bucket received rows by local expert (same trick, local)
        le_key = jnp.where(valid, le, float(e_loc)).astype(jnp.int32)
        le_s, order = jax.lax.sort(
            (le_key, jnp.arange(le_key.shape[0], dtype=jnp.int32)), num_keys=1)
        rows_s = jnp.take(xr_f, order, axis=0)
        # per-local-expert capacity: mean + 2x imbalance headroom
        cap_e = int(max(1, 2 * mp * cap // e_loc))
        pos = jnp.arange(mp * cap, dtype=jnp.int32)
        seg = jnp.searchsorted(le_s, jnp.arange(e_loc, dtype=jnp.int32),
                               side="left")
        pie = pos - seg[jnp.clip(le_s, 0, e_loc - 1)]
        slot = jnp.where(le_s < e_loc, le_s * cap_e + pie, e_loc * cap_e)
        xe = jnp.zeros((e_loc * cap_e + 1, D), xr_f.dtype).at[slot].set(
            rows_s, mode="drop")[:-1].reshape(e_loc, cap_e, D)

        # expert FFN (swiglu)
        dt = xb.dtype
        h = jnp.einsum("ecd,edf->ecf", xe, wi.astype(dt))
        g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(dt))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * h
        ye = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))  # (e_loc, cap_e, D)

        # un-bucket: back to received-row order, then all_to_all home
        ye_f = ye.reshape(e_loc * cap_e, D)
        take = jnp.where(slot < e_loc * cap_e, slot, 0)
        back = jnp.where((valid[order] & (slot < e_loc * cap_e))[:, None],
                         jnp.take(ye_f, take, axis=0), 0.0).astype(dt)
        # invert the sort permutation
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        y_recv_order = jnp.take(back, inv, axis=0).reshape(mp, cap, D)
        y_home = jax.lax.all_to_all(y_recv_order, "model", split_axis=0,
                                    concat_axis=0, tiled=False)
        # combine at the source: weighted scatter-add by original token row
        y_home_f = y_home.reshape(mp * cap, D)
        meta_home = meta.reshape(mp * cap, 3)
        src = meta_home[:, 2].astype(jnp.int32)
        gts = meta_home[:, 1]
        ok = meta_home[:, 0] >= 0
        out_my = jnp.zeros((T_loc, D), dt).at[jnp.where(ok, src, 0)].add(
            jnp.where(ok[:, None], y_home_f * gts[:, None].astype(dt), 0.0),
            mode="drop")

        # reassemble the full local-batch tokens across model ranks
        out_all = jax.lax.all_gather(out_my, "model", axis=0, tiled=True)
        return out_all.reshape(B_loc, S, D), lb, zl

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    xspec = P(data_axes if len(data_axes) > 1 else data_axes[0], None, None)
    in_specs = (xspec, P(None, None), P("model", None, None),
                P("model", None, None), P("model", None, None))
    out_specs = (xspec, P(), P())
    mapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    out, lb, zl = mapped(x, p["router"].astype(jnp.float32),
                         p["wi"], p["wg"], p["wo"])
    return out, {"lb_loss": lb, "z_loss": zl}
