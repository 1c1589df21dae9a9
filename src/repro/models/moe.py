"""Mixture-of-Experts with group-local capacity dispatch.

Tokens are reshaped into G groups (sharded over the data axis); each
group dispatches its own tokens into a per-group [E, C, D] buffer via
sort + scatter, so no cross-shard cumsum serializes, and expert FLOPs
are proportional to *active* parameters (top-k), which keeps the
roofline honest. Capacity overflow drops tokens (residual keeps them).

Supports Mixtral-style (8 routed, top-2, renormalized) and
DeepSeekMoE-style (64 fine-grained routed top-6 + shared experts that
every token visits, implemented as one fused dense FFN of width
n_shared * d_ff).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.sharding import constrain, current_mesh, current_rules


def moe_init(key, cfg, dtype):
    E = cfg.num_experts
    F = cfg.moe_d_ff or cfg.d_ff
    D = cfg.d_model
    ks = jax.random.split(key, 6)
    scale = D ** -0.5

    def stack(k, a, b):
        w = jax.random.normal(k, (E, a, b), dtype=jnp.float32) * (a ** -0.5)
        return w.astype(dtype)

    p = {
        "router": {"kernel": (jax.random.normal(ks[0], (D, E),
                              dtype=jnp.float32) * scale)},
        "experts": {
            "w_gate": stack(ks[1], D, F),
            "w_up": stack(ks[2], D, F),
            "w_down": stack(ks[3], F, D),
        },
    }
    if cfg.num_shared_experts:
        p["shared"] = L.mlp_init(ks[4], D, cfg.num_shared_experts * F,
                                 "swiglu", dtype)
    return p


def _pick_groups(total_tokens: int, batch: int) -> int:
    """Groups must divide total tokens; prefer ~>=256 tokens per group so
    capacity quantization stays small, while keeping G a multiple that
    the data axis can shard."""
    if total_tokens <= 256:
        return 1
    g = batch
    while g > 1 and total_tokens // g < 256:
        g //= 2
    return max(g, 1)


def _dispatch(xg, top_idx, E, C):
    """Group-batched dispatch, G-major so the group dim stays visible to
    the partitioner (a vmapped formulation loses the sharding of the
    internal scatter buffers and GSPMD reconstructs them with
    full-replica all-reduces -- see EXPERIMENTS.md section Perf iter 2).

    xg: [G, T, D]; top_idx: [G, T, k].
    Returns (buf [G, E, C, D], dest [G, T*k], keep, src, order).
    """
    G, T, D = xg.shape
    k = top_idx.shape[-1]
    flat_e = top_idx.reshape(G, T * k)
    order = jnp.argsort(flat_e, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    # position within expert run: index - index of run start (cummax of
    # run-start positions replaces a per-row searchsorted)
    idx = jnp.broadcast_to(jnp.arange(T * k)[None], (G, T * k))
    starts = jnp.concatenate(
        [jnp.ones((G, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]],
        axis=1)
    run_start = jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)
    pos = idx - run_start
    keep = pos < C
    dest = jnp.where(keep, sorted_e * C + pos, E * C)   # E*C = drop slot
    src = order // k
    g_idx = jnp.arange(G)[:, None]
    vals = jnp.take_along_axis(xg, src[..., None], axis=1) \
        * keep[..., None].astype(xg.dtype)
    buf = jnp.zeros((G, E * C + 1, D), dtype=xg.dtype)
    buf = buf.at[g_idx, dest].add(vals)
    buf = constrain(buf, "group", None, None)
    return (buf[:, :-1, :].reshape(G, E, C, D), dest, keep, src, order,
            g_idx)


def _ep_axis(E):
    """Return (mesh, expert_axis_name, n_shards) when explicit expert
    parallelism applies (rules map 'expert' to a mesh axis dividing E)."""
    mesh = current_mesh()
    if mesh is None:
        return None, None, 0
    ax = current_rules().to_mesh_axes("expert")
    if not isinstance(ax, str) or ax not in mesh.axis_names:
        return None, None, 0
    n = mesh.shape[ax]
    if n <= 1 or E % n:
        return None, None, 0
    return mesh, ax, n


def _moe_expert_compute_ep(params, xg, ig, wg, cfg, E, C, mesh, axis, n):
    """Explicit expert parallelism (shard_map over the expert axis):
    every chip holds E/n full experts, dispatches only the slots bound
    for ITS experts, runs dense local matmuls, and contributes a
    partial per-token output -- ONE bf16 psum of [G,T,D] per layer is
    the only cross-chip traffic (vs. full [G,E,C,D] buffer psums under
    plain GSPMD; EXPERIMENTS.md section Perf iter 4)."""
    G, Tg, D = xg.shape
    k = ig.shape[-1]
    rules = current_rules()
    batch_axes = rules.to_mesh_axes("group")
    if not isinstance(batch_axes, (tuple, list)):
        batch_axes = (batch_axes,) if batch_axes else ()
    kept, prod = [], 1
    for a in batch_axes:
        if a in mesh.axis_names and a != axis \
                and G % (prod * mesh.shape[a]) == 0:
            kept.append(a)
            prod *= mesh.shape[a]
    batch_axes = tuple(kept) if kept else None

    def local_fn(x_l, i_l, w_l, wg_l, wu_l, wd_l):
        # x_l: [G_l, Tg, D]; i_l/w_l: [G_l, Tg, k];
        # wg_l/wu_l: [E_l, D, F]; wd_l: [E_l, F, D]
        Gl = x_l.shape[0]
        E_l = wg_l.shape[0]
        me = jax.lax.axis_index(axis)
        lo = me * E_l
        flat_e = i_l.reshape(Gl, Tg * k)
        order = jnp.argsort(flat_e, axis=1, stable=True)
        sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
        idx = jnp.broadcast_to(jnp.arange(Tg * k)[None], (Gl, Tg * k))
        starts = jnp.concatenate(
            [jnp.ones((Gl, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]],
            axis=1)
        run_start = jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)
        pos = idx - run_start
        mine = (sorted_e >= lo) & (sorted_e < lo + E_l) & (pos < C)
        local_dest = jnp.where(mine, (sorted_e - lo) * C + pos, E_l * C)
        src = order // k
        g_idx = jnp.arange(Gl)[:, None]
        vals = jnp.take_along_axis(x_l, src[..., None], axis=1) \
            * mine[..., None].astype(x_l.dtype)
        buf = jnp.zeros((Gl, E_l * C + 1, D), x_l.dtype)
        buf = buf.at[g_idx, local_dest].add(vals)
        buf = buf[:, :-1, :].reshape(Gl, E_l, C, D)
        h = jnp.einsum("gecd,edf->gecf", buf, wg_l)
        u = jnp.einsum("gecd,edf->gecf", buf, wu_l)
        out = jnp.einsum("gecf,efd->gecd", jax.nn.silu(h) * u, wd_l)
        out_flat = jnp.concatenate(
            [out.reshape(Gl, E_l * C, D), jnp.zeros((Gl, 1, D),
                                                    out.dtype)], axis=1)
        slot = jnp.take_along_axis(out_flat, local_dest[..., None], axis=1)
        w_sorted = jnp.take_along_axis(w_l.reshape(Gl, Tg * k), order,
                                       axis=1)
        y = jnp.zeros((Gl, Tg, D), x_l.dtype)
        y = y.at[g_idx, src].add(
            slot * (w_sorted * mine.astype(w_sorted.dtype))[..., None])
        return jax.lax.psum(y, axis)

    bspec = P(batch_axes, None, None)
    espec = P(axis, None, None)
    y = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(bspec, bspec, bspec, espec, espec, espec),
        out_specs=bspec, check_vma=False)(
            xg, ig, wg, params["experts"]["w_gate"],
            params["experts"]["w_up"], params["experts"]["w_down"])
    return y


def moe_apply(params, x, cfg):
    """x: [B, S, D] -> (y [B, S, D], aux_loss scalar)."""
    B, S, D = x.shape
    E = cfg.num_experts
    k = cfg.num_experts_per_tok
    F = cfg.moe_d_ff or cfg.d_ff
    T = B * S
    xf = x.reshape(T, D)

    logits = (xf.astype(jnp.float32) @ params["router"]["kernel"])  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)                            # [E]
    one_hot = jax.nn.one_hot(top_idx, E, dtype=jnp.float32) # [T,k,E]
    ce = jnp.mean(one_hot.sum(1), axis=0)                   # frac routed
    aux = cfg.router_aux_weight * E * jnp.sum(me * ce) / k

    G = _pick_groups(T, B)
    Tg = T // G
    C = max(1, int(cfg.expert_capacity_factor * k * Tg / E))
    C = min(C, Tg * k)

    xg = constrain(xf.reshape(G, Tg, D), "group", None, None)
    ig = top_idx.reshape(G, Tg, k)
    wg = top_w.reshape(G, Tg, k).astype(x.dtype)

    mesh, ep_ax, ep_n = _ep_axis(E)
    if mesh is not None:
        y = _moe_expert_compute_ep(params, xg, ig, wg, cfg, E, C, mesh,
                                   ep_ax, ep_n).reshape(B, S, D)
        if "shared" in params:
            y = y + L.mlp_apply(params["shared"], x, "swiglu")
        return y, aux

    buf, dest, keep, src, order, g_idx = _dispatch(xg, ig, E, C)
    buf = constrain(buf, "group", "expert", None, None)
    h = jnp.einsum("gecd,edf->gecf", buf, params["experts"]["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", buf, params["experts"]["w_up"])
    h = jax.nn.silu(h) * u
    h = constrain(h, "group", "expert", None, "expert_mlp")
    out = jnp.einsum("gecf,efd->gecd", h, params["experts"]["w_down"])
    out = constrain(out, "group", "expert", None, None)
    out_flat = jnp.concatenate(
        [out.reshape(G, E * C, D), jnp.zeros((G, 1, D), out.dtype)],
        axis=1)
    slot_out = jnp.take_along_axis(out_flat, dest[..., None], axis=1) \
        * keep[..., None].astype(out.dtype)               # [G, Tg*k, D]
    w_sorted = jnp.take_along_axis(wg.reshape(G, Tg * k), order, axis=1)
    y = jnp.zeros((G, Tg, D), dtype=x.dtype)
    y = y.at[g_idx, src].add(slot_out * w_sorted[..., None])
    y = constrain(y, "group", None, None).reshape(B, S, D)

    if "shared" in params:
        y = y + L.mlp_apply(params["shared"], x, "swiglu")
    return y, aux
