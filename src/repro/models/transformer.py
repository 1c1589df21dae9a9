"""Composable decoder / encoder-decoder stacks over heterogeneous layer
kinds (attention, Mamba, RWKV6, dense FFN, MoE), assembled from a
ModelConfig.

Layer stacks are decomposed into (prefix, periodic-group) form and the
periodic part is lax.scan'ed over stacked params so HLO size is O(period)
not O(num_layers) -- essential for compiling 56-layer models for a
512-device mesh on one CPU. Each scan body is rematerialized.

The De-VertiFL input block (vertical feature partitioning + Hidden
OutputExchange) lives in embed_input()/exchange_features(): with a mesh,
the embedding's d_model dim is sharded over the client axis and the
exchange reconstitutes full hidden features either by the paper's
zero-pad + psum (Algorithm 2) or the optimized all-gather (see
DESIGN.md §2).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
from jax.sharding import PartitionSpec as P

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.sharding import constrain, current_mesh, current_rules


# ---------------------------------------------------------------------------
# layer-kind schedule
# ---------------------------------------------------------------------------
def layer_kinds(cfg):
    kinds = []
    for l in range(cfg.num_layers):
        if cfg.ssm_type == "rwkv6":
            mixer = "rwkv"
        elif cfg.ssm_type == "mamba" and (
                cfg.attn_layer_period == 0
                or l % cfg.attn_layer_period != cfg.attn_layer_offset):
            mixer = "mamba"
        else:
            mixer = "attn"
        window = A.layer_window_for(cfg, l) if mixer == "attn" else None
        if mixer == "rwkv":
            ffn = "rwkv_cm"
        elif l == 0 and cfg.first_layer_dense_ff:
            ffn = "dense0"
        elif cfg.num_experts and (l % cfg.moe_every) == cfg.moe_offset:
            ffn = "moe"
        else:
            ffn = "dense"
        kinds.append({
            "mixer": mixer, "ffn": ffn, "window": window,
            "cross": cfg.is_encoder_decoder, "causal": True,
        })
    return kinds


def encoder_kinds(cfg):
    return [{"mixer": "attn", "ffn": "dense", "window": None,
             "cross": False, "causal": False}
            for _ in range(cfg.num_encoder_layers)]


def periodic_split(kinds):
    """Return (prefix_len, period) decomposing kinds into an irregular
    prefix followed by a periodic tail."""
    n = len(kinds)
    for prefix in (0, 1, 2):
        rest = kinds[prefix:]
        if not rest:
            continue
        for period in range(1, min(16, len(rest)) + 1):
            if len(rest) % period:
                continue
            if all(rest[i] == rest[i % period] for i in range(len(rest))):
                return prefix, period
    return n, 1


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------
def block_init(key, cfg, kind, dtype):
    ks = jax.random.split(key, 6)
    D = cfg.d_model
    p = {"pre_norm": L.norm_init(D, cfg.norm_type)}
    if kind["mixer"] == "attn":
        p["attn"] = A.attn_init(ks[0], cfg, dtype)
    elif kind["mixer"] == "mamba":
        p.update(S.mamba_init(ks[0], cfg, dtype))
    elif kind["mixer"] == "rwkv":
        p.update(S.rwkv_init(ks[0], cfg, dtype))
    if kind["cross"]:
        p["cross_norm"] = L.norm_init(D, cfg.norm_type)
        p["cross"] = A.attn_init(ks[1], cfg, dtype)
    p["ffn_norm"] = L.norm_init(D, cfg.norm_type)
    if kind["ffn"] == "moe":
        p["moe"] = M.moe_init(ks[2], cfg, dtype)
    elif kind["ffn"] == "dense0":
        p["ffn"] = L.mlp_init(ks[2], D, cfg.first_layer_dense_ff, cfg.act,
                              dtype)
    elif kind["ffn"] == "dense":
        p["ffn"] = L.mlp_init(ks[2], D, cfg.d_ff, cfg.act, dtype)
    return p


def block_apply(p, x, positions, cfg, kind, enc=None):
    """Full-sequence block. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    name = _checkpoint_name
    h = L.apply_norm(p["pre_norm"], x, cfg.norm_type)
    if kind["mixer"] == "attn":
        y = A.attn_apply(p["attn"], h, positions, cfg,
                         layer_window=kind["window"],
                         causal=kind.get("causal", True))
    elif kind["mixer"] == "mamba":
        y = S.mamba_apply(p, h, cfg)
    else:
        y = S.rwkv_time_mix(p, h, cfg)
    x = x + name(y, "mixer_out")
    if kind["cross"] and enc is not None:
        hc = L.apply_norm(p["cross_norm"], x, cfg.norm_type)
        x = x + A.attn_apply(p["cross"], hc, positions, cfg, causal=False,
                             kv_override=enc)
    h2 = L.apply_norm(p["ffn_norm"], x, cfg.norm_type)
    if kind["ffn"] == "rwkv_cm":
        x = x + name(S.rwkv_channel_mix(p, h2, cfg), "ffn_out")
        return x, aux
    if kind["ffn"] == "moe":
        y, aux = M.moe_apply(p["moe"], h2, cfg)
        x = x + name(y, "ffn_out")
    else:
        x = x + name(L.mlp_apply(p["ffn"], h2, cfg.act), "ffn_out")
    return x, aux


def block_prefill(p, x, positions, cfg, kind, batch, cache_len, dtype,
                  enc=None):
    """Full-sequence forward that also emits the decode cache for this
    block (forward-only: the inference-prefill path)."""
    h = L.apply_norm(p["pre_norm"], x, cfg.norm_type)
    cache = {}
    if kind["mixer"] == "attn":
        y, (k, v) = A.attn_apply(p["attn"], h, positions, cfg,
                                 layer_window=kind["window"],
                                 causal=kind.get("causal", True),
                                 return_kv=True)
        x = x + y
        empty = A.init_cache(cfg, batch,
                             min(cache_len, kind["window"])
                             if kind["window"] else cache_len,
                             kind["window"], dtype)
        cache["attn"] = A.fill_cache_from_prefill(empty, k, v, positions,
                                                  batch)
    elif kind["mixer"] == "mamba":
        y, st = S.mamba_apply(p, h, cfg, return_state=True)
        x = x + y
        cache["mamba"] = st
    else:
        y, tm = S.rwkv_time_mix(p, h, cfg, return_state=True)
        x = x + y
        cache["rwkv"] = {"wkv": tm["wkv"], "x_prev_tm": h[:, -1, :]}
    if kind["cross"] and enc is not None:
        hc = L.apply_norm(p["cross_norm"], x, cfg.norm_type)
        x = x + A.attn_apply(p["cross"], hc, positions, cfg, causal=False,
                             kv_override=enc)
    h2 = L.apply_norm(p["ffn_norm"], x, cfg.norm_type)
    if kind["ffn"] == "rwkv_cm":
        y, cm_prev = S.rwkv_channel_mix(p, h2, cfg, return_state=True)
        x = x + y
        cache["rwkv"]["x_prev_cm"] = h2[:, -1, :]
    elif kind["ffn"] == "moe":
        y, _ = M.moe_apply(p["moe"], h2, cfg)
        x = x + y
    else:
        x = x + L.mlp_apply(p["ffn"], h2, cfg.act)
    return x, cache


def block_init_cache(cfg, kind, batch, seq_len, dtype):
    if kind["mixer"] == "attn":
        c = {"attn": A.init_cache(cfg, batch, seq_len, kind["window"], dtype)}
    elif kind["mixer"] == "mamba":
        c = {"mamba": S.mamba_init_state(cfg, batch, dtype)}
    else:
        c = {"rwkv": S.rwkv_init_state(cfg, batch, dtype)}
    return c


def block_decode(p, x, position, cfg, kind, cache, enc=None):
    """One-token decode. Returns (x, new_cache)."""
    h = L.apply_norm(p["pre_norm"], x, cfg.norm_type)
    new_cache = dict(cache)
    if kind["mixer"] == "attn":
        y, new_cache["attn"] = A.attn_decode(
            p["attn"], h, position, cache["attn"], cfg,
            layer_window=kind["window"])
        x = x + y
    elif kind["mixer"] == "mamba":
        y, new_cache["mamba"] = S.mamba_decode(p, h, cache["mamba"], cfg)
        x = x + y
    else:
        st = cache["rwkv"]
        y, tm_state = S.rwkv_time_mix(
            p, h, cfg, x_prev=st["x_prev_tm"], state=st["wkv"],
            return_state=True)
        x = x + y
        new_st = dict(st)
        new_st["wkv"] = tm_state["wkv"]
        new_st["x_prev_tm"] = h[:, -1, :]
        new_cache["rwkv"] = new_st
    if kind["cross"] and enc is not None:
        hc = L.apply_norm(p["cross_norm"], x, cfg.norm_type)
        x = x + A.attn_apply(p["cross"], hc, position[:, None], cfg,
                             causal=False, kv_override=enc)
    if kind["ffn"] == "rwkv_cm":
        st = new_cache["rwkv"]
        h2 = L.apply_norm(p["ffn_norm"], x, cfg.norm_type)
        y, cm_prev = S.rwkv_channel_mix(p, h2, cfg,
                                        x_prev=st["x_prev_cm"],
                                        return_state=True)
        x = x + y
        st2 = dict(st)
        st2["x_prev_cm"] = h2[:, -1, :]
        new_cache["rwkv"] = st2
        return x, new_cache
    h2 = L.apply_norm(p["ffn_norm"], x, cfg.norm_type)
    if kind["ffn"] == "moe":
        y, _ = M.moe_apply(p["moe"], h2, cfg)
        x = x + y
    else:
        x = x + L.mlp_apply(p["ffn"], h2, cfg.act)
    return x, new_cache


# ---------------------------------------------------------------------------
# stacks (prefix + scanned periodic groups)
# ---------------------------------------------------------------------------
class StackLayout:
    def __init__(self, cfg, kinds):
        self.kinds = kinds
        if cfg.scan_layers:
            self.prefix, self.period = periodic_split(kinds)
        else:
            self.prefix, self.period = len(kinds), 1
        self.n_groups = (len(kinds) - self.prefix) // self.period \
            if self.prefix < len(kinds) else 0
        self.group_kinds = kinds[self.prefix:self.prefix + self.period] \
            if self.n_groups else []


def stack_init(key, cfg, kinds, dtype):
    layout = StackLayout(cfg, kinds)
    ks = jax.random.split(key, layout.prefix + 1)
    params = {}
    for i in range(layout.prefix):
        params[f"layer_{i}"] = block_init(ks[i], cfg, kinds[i], dtype)
    if layout.n_groups:
        def ginit(k):
            gks = jax.random.split(k, layout.period)
            return {f"sub_{j}": block_init(gks[j], cfg,
                                           layout.group_kinds[j], dtype)
                    for j in range(layout.period)}
        gkeys = jax.random.split(ks[-1], layout.n_groups)
        params["scanned"] = jax.vmap(ginit)(gkeys)
    return params


def stack_apply(params, x, positions, cfg, kinds, enc=None):
    layout = StackLayout(cfg, kinds)
    aux = jnp.zeros((), jnp.float32)

    policy = None
    if cfg.remat_policy == "save_mixer_ffn":
        policy = jax.checkpoint_policies.save_only_these_names(
            "mixer_out", "ffn_out")

    for i in range(layout.prefix):
        fn = block_apply
        if cfg.remat:
            fn = jax.remat(fn, static_argnums=(3, 4), policy=policy)
        x, a = fn(params[f"layer_{i}"], x, positions, cfg, kinds[i], enc)
        aux = aux + a

    if layout.n_groups:
        def body(carry, gparams):
            xc, auxc = carry
            for j, kind in enumerate(layout.group_kinds):
                xc, a = block_apply(gparams[f"sub_{j}"], xc, positions, cfg,
                                    kind, enc)
                auxc = auxc + a
            return (xc, auxc), None
        if cfg.remat:
            body = jax.remat(body, policy=policy)
        (x, aux), _ = jax.lax.scan(body, (x, aux), params["scanned"])
    return x, aux


def stack_init_cache(cfg, kinds, batch, seq_len, dtype):
    layout = StackLayout(cfg, kinds)
    cache = {}
    for i in range(layout.prefix):
        cache[f"layer_{i}"] = block_init_cache(cfg, kinds[i], batch, seq_len,
                                               dtype)
    if layout.n_groups:
        def one_group(_):
            return {f"sub_{j}": block_init_cache(cfg, layout.group_kinds[j],
                                                 batch, seq_len, dtype)
                    for j in range(layout.period)}
        groups = [one_group(g) for g in range(layout.n_groups)]
        cache["scanned"] = jax.tree.map(lambda *xs: jnp.stack(xs), *groups)
    return cache


def stack_prefill(params, x, positions, cfg, kinds, batch, cache_len,
                  dtype, enc=None):
    layout = StackLayout(cfg, kinds)
    cache = {}
    for i in range(layout.prefix):
        x, cache[f"layer_{i}"] = block_prefill(
            params[f"layer_{i}"], x, positions, cfg, kinds[i], batch,
            cache_len, dtype, enc)

    if layout.n_groups:
        def body(xc, gparams):
            newc = {}
            for j, kind in enumerate(layout.group_kinds):
                xc, newc[f"sub_{j}"] = block_prefill(
                    gparams[f"sub_{j}"], xc, positions, cfg, kind, batch,
                    cache_len, dtype, enc)
            return xc, newc
        if cfg.remat:
            body = jax.remat(body)
        x, cache["scanned"] = jax.lax.scan(body, x, params["scanned"])
    return x, cache


def stack_decode(params, x, position, cfg, kinds, cache, enc=None):
    layout = StackLayout(cfg, kinds)
    new_cache = {}
    for i in range(layout.prefix):
        x, new_cache[f"layer_{i}"] = block_decode(
            params[f"layer_{i}"], x, position, cfg, kinds[i],
            cache[f"layer_{i}"], enc)

    if layout.n_groups:
        def body(xc, inp):
            gparams, gcache = inp
            newc = {}
            for j, kind in enumerate(layout.group_kinds):
                xc, newc[f"sub_{j}"] = block_decode(
                    gparams[f"sub_{j}"], xc, position, cfg, kind,
                    gcache[f"sub_{j}"], enc)
            return xc, newc
        x, new_cache["scanned"] = jax.lax.scan(
            body, x, (params["scanned"], cache["scanned"]))
    return x, new_cache


# ---------------------------------------------------------------------------
# De-VertiFL input block
# ---------------------------------------------------------------------------
def _client_axis():
    mesh = current_mesh()
    if mesh is None:
        return None, 0
    ax = current_rules().to_mesh_axes("client")
    if ax is None or ax not in mesh.axis_names or mesh.shape[ax] == 1:
        return None, 0
    return ax, mesh.shape[ax]


def exchange_features(x_local, axis, n, mode, batch_axes):
    """HiddenOutputExchange over client-sharded features.

    x_local (inside shard_map): [B_local, S, D/n] -- this client's slice.
    mode 'zeropad_psum': paper Algorithm 2 -- zero-pad to full width and
        sum across clients (each client transmits the full-width tensor).
    mode 'allgather': exchange only owned slices (1/n bytes).
    """
    if mode == "zeropad_psum":
        d_local = x_local.shape[-1]
        idx = jax.lax.axis_index(axis)
        full = jnp.zeros(x_local.shape[:-1] + (d_local * n,), x_local.dtype)
        full = jax.lax.dynamic_update_slice_in_dim(
            full, x_local, idx * d_local, axis=x_local.ndim - 1)
        return jax.lax.psum(full, axis)          # the exchange
    return jax.lax.all_gather(x_local, axis, axis=x_local.ndim - 1,
                              tiled=True)


def embed_input(params, ids, cfg, prefix_emb=None):
    """Token embedding with optional De-VertiFL vertical input block.
    Returns full-width features [B, S_total, D]."""
    axis, n = _client_axis()
    emb_scale = cfg.d_model ** 0.5 if cfg.final_logit_softcap else 1.0
    key = "vfl_embedding" if cfg.vfl.enabled else "embedding"
    table = params[key]["table"]
    if not cfg.vfl.enabled or axis is None:
        h = L.embed(params[key], ids)
        if prefix_emb is not None:
            h = jnp.concatenate([prefix_emb.astype(h.dtype), h], axis=1)
        return h * jnp.asarray(emb_scale, h.dtype)

    mesh = current_mesh()
    rules = current_rules()
    batch_axes = rules.to_mesh_axes("batch")
    if not isinstance(batch_axes, (tuple, list)):
        batch_axes = (batch_axes,) if batch_axes else ()
    # keep only axes that exist in this mesh AND divide the batch evenly
    kept, prod = [], 1
    for a in batch_axes:
        if a in mesh.axis_names and ids.shape[0] % (prod * mesh.shape[a]) == 0:
            kept.append(a)
            prod *= mesh.shape[a]
    batch_axes = tuple(kept) if kept else None
    mode = cfg.vfl.exchange

    bspec = P(batch_axes, None)
    out_spec = P(batch_axes, None, None)

    if prefix_emb is None:
        def local_fn(table_local, ids_local):
            # table_local: [V, D/n] -- this client's vertical feature slice
            emb = jnp.take(table_local, ids_local, axis=0)  # [B_l,S,D/n]
            return exchange_features(emb, axis, n, mode, batch_axes)
        h = jax.shard_map(local_fn, mesh=mesh,
                          in_specs=(P(None, axis), bspec),
                          out_specs=out_spec, check_vma=False)(table, ids)
    else:
        def local_fn(table_local, ids_local, prefix_local):
            emb = jnp.take(table_local, ids_local, axis=0)
            emb = jnp.concatenate(
                [prefix_local.astype(emb.dtype), emb], axis=1)
            return exchange_features(emb, axis, n, mode, batch_axes)
        h = jax.shard_map(local_fn, mesh=mesh,
                          in_specs=(P(None, axis), bspec,
                                    P(batch_axes, None, axis)),
                          out_specs=out_spec, check_vma=False)(
                              table, ids, prefix_emb)
    return h * jnp.asarray(emb_scale, h.dtype)


@jax.custom_vjp
def _tied_logits(h, table):
    return h @ table.T


def _tied_logits_fwd(h, table):
    return _tied_logits(h, table), (h, table)


def _tied_logits_bwd(res, dlogits):
    """The table is D-sharded (VFL client slices) while logits are
    vocab-sharded; without this VJP, GSPMD computes dtable by
    ALL-GATHERING the [B,S,V] activation grads over the model axis
    (37 GB/step for qwen1.5-0.5b). Instead: contract locally in the
    vocab-sharded layout, then reshard the [V, D] weight grad (~0.6 GB)
    -- EXPERIMENTS.md section Perf iter 5."""
    h, table = res
    dh = dlogits @ table                                  # psum over model
    dtable = jnp.einsum("bsv,bsd->vd", dlogits, h)
    dtable = constrain(dtable, "vocab", None)             # compute sharded
    dtable = constrain(dtable, None, "client")            # reshard to param
    return dh, dtable.astype(table.dtype)


_tied_logits.defvjp(_tied_logits_fwd, _tied_logits_bwd)


def logits_from_hidden(params, h, cfg):
    key = "vfl_embedding" if cfg.vfl.enabled else "embedding"
    if cfg.tie_embeddings:
        logits = _tied_logits(h, params[key]["table"])
    else:
        logits = L.dense(params["lm_head"], h)
    logits = L.softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return constrain(logits, "batch", None, "vocab")
