"""Mixture-of-experts FFN: top-k routing, capacity-bounded sort-based
dispatch (no dense (T, E, C) dispatch tensors — scales to 32k sequences),
expert-parallel over the 'model' mesh axis.

Dispatch: flatten (token, k) assignments, sort by expert id, take the first
C = ceil(T*k/E * capacity_factor) slots per expert (tokens beyond capacity
are dropped — standard Switch/Mixtral-style), run the per-expert FFN as one
batched einsum over stacked expert weights, and scatter-add weighted outputs
back.  Sorting gives O(Tk log Tk) routing and O(E*C*D) activation memory,
and the E dimension shards cleanly over 'model' (GSPMD inserts the
all-to-all at the dispatch boundary).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.parallel.sharding import constrain
from . import layers


def init_moe(key, cfg) -> dict:
    m = cfg.moe
    ks = jax.random.split(key, 4)
    e, d, f = m.n_experts, cfg.d_model, m.expert_ff

    def ex(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(
            jnp.bfloat16
        )

    return {
        "router": layers.init_linear(ks[0], d, e),
        "w_gate": ex(ks[1], (e, d, f), d),
        "w_up": ex(ks[2], (e, d, f), d),
        "w_down": ex(ks[3], (e, f, d), f),
    }


def moe_ffn(p: dict, x: jax.Array, cfg) -> jax.Array:
    """x: (B, S, D) -> (B, S, D)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)

    logits = layers.linear(p["router"], xf).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, m.top_k)  # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # Flatten (token, k) assignments and sort by expert.
    tk = t * m.top_k
    eid = idx.reshape(tk)
    tok = jnp.repeat(jnp.arange(t), m.top_k)
    gw = gate.reshape(tk)
    order = jnp.argsort(eid)
    eid_s, tok_s, gw_s = eid[order], tok[order], gw[order]
    # Position within the expert's segment (first-occurrence trick).
    first = jnp.searchsorted(eid_s, eid_s, side="left")
    pos = jnp.arange(tk) - first
    # capacity floor of 4 keeps tiny decode batches effectively dropless
    cap = min(tk, max(int(t * m.top_k / m.n_experts * m.capacity_factor), 4))
    keep = pos < cap

    # Dispatch: (E, C, D) buffer — E shards over 'model' (EP), C over the DP
    # axes (each data shard's tokens land in its capacity slice after the
    # GSPMD all-to-all), D unsharded.  Without the C sharding every data
    # shard would replicate all expert FLOPs (16x waste — caught by the
    # dry-run roofline, see EXPERIMENTS.md §Perf).
    pos_c = jnp.where(keep, pos, cap)  # dropped -> OOB row (scatter-drop)
    buf = jnp.zeros((m.n_experts, cap, d), x.dtype)
    xe = buf.at[eid_s, pos_c].set(xf[tok_s], mode="drop")
    xe = constrain(xe, "experts", "expert_capacity", None)

    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = constrain(h, "experts", "expert_capacity", "ffn")
    oe = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))
    oe = constrain(oe, "experts", "expert_capacity", None)

    # Combine: gather each kept assignment's output, weight, scatter-add.
    contrib = oe[eid_s, jnp.minimum(pos, cap - 1)]
    contrib = contrib * (gw_s * keep)[:, None].astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[tok_s].add(contrib)
    return out.reshape(b, s, d)


def _local_dispatch(xf, logits, n_experts, top_k, cap, dtype):
    """Shared routing math on a (local) token slab: returns the dispatch
    buffer (E, cap, D) plus the combine metadata."""
    t, d = xf.shape
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, top_k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    tk = t * top_k
    eid = idx.reshape(tk)
    tok = jnp.repeat(jnp.arange(t), top_k)
    gw = gate.reshape(tk)
    order = jnp.argsort(eid)
    eid_s, tok_s, gw_s = eid[order], tok[order], gw[order]
    first = jnp.searchsorted(eid_s, eid_s, side="left")
    pos = jnp.arange(tk) - first
    keep = pos < cap
    pos_c = jnp.where(keep, pos, cap)
    buf = jnp.zeros((n_experts, cap, d), dtype)
    xe = buf.at[eid_s, pos_c].set(xf[tok_s], mode="drop")
    return xe, (eid_s, pos, tok_s, gw_s, keep)


def _local_combine(oe, meta, t, cap, dtype):
    eid_s, pos, tok_s, gw_s, keep = meta
    d = oe.shape[-1]
    contrib = oe[eid_s, jnp.minimum(pos, cap - 1)]
    contrib = contrib * (gw_s * keep)[:, None].astype(dtype)
    return jnp.zeros((t, d), dtype).at[tok_s].add(contrib)


def moe_ffn_ep(p: dict, x: jax.Array, cfg) -> jax.Array:
    """Expert parallelism with an EXPLICIT all-to-all (shard_map over the
    full mesh).  Each (data, seq) shard routes its local tokens, packs an
    (M, E_loc, C, D) send buffer (M = |model| expert shards), all-to-alls
    over 'model', runs its local experts, and all-to-alls back.

    Replaces the GSPMD-partitioned scatter dispatch, whose data-dependent
    indices force token replication (olmoe train_4k baseline: 243 s
    collective term vs 0.4 s compute — EXPERIMENTS.md §Perf iteration 1).
    """
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import current_mesh, spec_for

    mesh = current_mesh()
    m = cfg.moe
    b, s, d = x.shape
    if mesh is None:
        return moe_ffn(p, x, cfg)
    msize = mesh.shape.get("model", 1)

    # token layout from the ACTIVE rule set: default = (batch->dp, seq->model)
    # [SP], ep_dp = (batch->all axes, seq unsharded) [DeepSpeed-MoE style].
    x_spec = spec_for(("batch", "seq", None), x.shape)

    def _size(entry):
        if entry is None:
            return 1
        axes = entry if isinstance(entry, tuple) else (entry,)
        out = 1
        for a in axes:
            out *= mesh.shape[a]
        return out

    b_fac, s_fac = _size(x_spec[0]), _size(x_spec[1])
    if (msize == 1 or m.n_experts % msize or b % b_fac or s % s_fac
            or cfg.quant.mode != "none"):
        return moe_ffn(p, x, cfg)  # fall back to the GSPMD path

    e_loc = m.n_experts // msize
    t_loc = (b // b_fac) * (s // s_fac)
    cap = min(t_loc * m.top_k,
              max(int(t_loc * m.top_k / m.n_experts * m.capacity_factor), 4))

    w_spec = P("model", None, None)

    def body(xs, router_w, wg, wu, wd):
        bl, sl, _ = xs.shape
        xf = xs.reshape(bl * sl, d)
        logits = (xf @ router_w.astype(jnp.float32))
        xe, meta = _local_dispatch(xf, logits, m.n_experts, m.top_k, cap, xs.dtype)
        # (E, C, D) -> (M, E_loc, C, D): expert e = m'*E_loc + j lives on m'
        send = xe.reshape(msize, e_loc, cap, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                                  tiled=False)
        # recv: (M, E_loc, C, D) — slabs from every source shard
        xcat = recv.transpose(1, 0, 2, 3).reshape(e_loc, msize * cap, d)
        g = jnp.einsum("ecd,edf->ecf", xcat, wg.astype(xs.dtype))
        u = jnp.einsum("ecd,edf->ecf", xcat, wu.astype(xs.dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xs.dtype) * u
        oe = jnp.einsum("ecf,efd->ecd", h, wd.astype(xs.dtype))
        back = oe.reshape(e_loc, msize, cap, d).transpose(1, 0, 2, 3)
        ret = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0,
                                 tiled=False)
        oe_local = ret.reshape(m.n_experts, cap, d)
        y = _local_combine(oe_local, meta, bl * sl, cap, xs.dtype)
        return y.reshape(bl, sl, d)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    return fn(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])


def load_balance_loss(p: dict, x: jax.Array, cfg) -> jax.Array:
    """Auxiliary load-balancing loss (Switch-style: E * sum(f_e * P_e))."""
    m = cfg.moe
    xf = x.reshape(-1, x.shape[-1])
    logits = layers.linear(p["router"], xf).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, m.n_experts), axis=0)
    pmean = jnp.mean(probs, axis=0)
    return m.n_experts * jnp.sum(f * pmean)
