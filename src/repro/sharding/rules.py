"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Model code tags tensors with *logical* axis names via ``constrain``;
the launcher installs a mesh + rules context; rules resolve logical
names to mesh axes. Without a mesh everything is a no-op so the same
model code runs single-device (smoke tests) and multi-pod (dry-run).

Logical axes used by the substrate:
  batch      activation batch dim            -> (pod, data)
  seq        sequence dim (ctx-parallel KV)   -> data for huge caches
  embed      param d_model dim (FSDP)         -> data
  heads      flattened q/kv head dim          -> model
  mlp        ffn hidden dim                   -> model
  vocab      vocabulary dim                   -> model
  expert     MoE expert dim                   -> None (or data for EP)
  group      MoE dispatch group dim           -> (pod, data)
  client     De-VertiFL client axis           -> model (input block)
  layers     scanned-layer leading dim        -> None
  sweep_lane sweep (seed x client-count) lane -> (pod, data): every
             lane is an independent federation, so the sweep engine
             shard_maps the lane axis over the data-parallel devices
             with no cross-lane collectives
"""
from __future__ import annotations

import contextlib
import re
from dataclasses import dataclass, field, replace
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


@dataclass
class AxisRules:
    rules: dict = field(default_factory=dict)

    def to_mesh_axes(self, logical: Optional[str]):
        if logical is None:
            return None
        return self.rules.get(logical, None)

    def spec(self, *logical) -> P:
        return P(*[self.to_mesh_axes(a) for a in logical])

    def with_overrides(self, **kw) -> "AxisRules":
        r = dict(self.rules)
        r.update(kw)
        return AxisRules(r)


DEFAULT_RULES = AxisRules({
    "batch": ("pod", "data"),
    "seq": None,
    # long-context decode: shard the KV cache on seq over every axis not
    # already consumed by batch (the dedup in _fix_spec drops reused
    # axes per-tensor, so decode_32k shards B over (pod,data) and S over
    # model, while long_500k's B=1 leaves all axes free for S)
    "kv_seq": ("pod", "data", "model"),
    "embed": ("pod", "data"),    # FSDP over params' d_model dim
    "heads": "model",
    "mlp": "model",
    "vocab": "model",
    "expert": None,          # EP mode: 'model' (experts spread over TP)
    "expert_mlp": "model",   # EP mode: None (each chip holds full experts)
    "group": ("pod", "data"),
    "client": "model",
    "layers": None,
    "sweep_lane": ("pod", "data"),
    "act_embed": None,           # activations replicated on d_model
    "ssm_inner": "model",
})

# Federated (De-VertiFL) production mode: the pod axis is the federated
# axis -- params are REPLICATED across pods (each "super-client" holds
# full weights, FedAvg pmean syncs them at round boundaries), FSDP only
# within a pod.
FEDERATED_RULES = DEFAULT_RULES.with_overrides(
    embed="data",
    kv_seq="data",
)

# Expert-parallel MoE (beyond-paper perf mode, see EXPERIMENTS.md §Perf):
# experts are spread over the model axis (each chip holds full experts
# with MXU-friendly [D, F] matmuls) instead of slicing every expert's
# hidden dim; kills the per-layer expert-weight all-gather.
EP_RULES = DEFAULT_RULES.with_overrides(
    expert="model",
    expert_mlp=None,
)


class _Ctx:
    mesh: Optional[Mesh] = None
    rules: AxisRules = DEFAULT_RULES


_ctx = _Ctx()


def auto_axes(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """The same devices and axis names with every axis Auto.  The rules
    are GSPMD hints (``with_sharding_constraint`` and jit shardings),
    which refer to Auto axes only, while ``jax.make_mesh`` builds
    Explicit axes by default."""
    if mesh is None or all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def set_context(mesh: Optional[Mesh], rules: Optional[AxisRules] = None):
    _ctx.mesh = auto_axes(mesh)
    if rules is not None:
        _ctx.rules = rules


@contextlib.contextmanager
def use_context(mesh: Optional[Mesh], rules: Optional[AxisRules] = None):
    old = (_ctx.mesh, _ctx.rules)
    set_context(mesh, rules or _ctx.rules)
    try:
        yield
    finally:
        _ctx.mesh, _ctx.rules = old


def current_mesh() -> Optional[Mesh]:
    return _ctx.mesh


def current_rules() -> AxisRules:
    return _ctx.rules


def _filter_spec_for_mesh(spec: P, mesh: Mesh) -> P:
    """Drop mesh axes the current mesh does not have (e.g. 'pod' on the
    single-pod mesh) and axes that do not divide -- GSPMD supports uneven
    sharding but shard_map and some in_shardings paths do not, so we play
    safe for explicit constraints."""
    names = set(mesh.axis_names)
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in names else None)
    return P(*out)


def logical_spec(*logical) -> P:
    spec = _ctx.rules.spec(*logical)
    if _ctx.mesh is not None:
        spec = _filter_spec_for_mesh(spec, _ctx.mesh)
    return spec


def _mesh_axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def _fix_spec(shape, spec, mesh):
    """Make a spec legal for a concrete shape: drop mesh axes that do
    not divide the dim, and axes already used by an earlier dim
    (earlier dims take priority -- e.g. batch wins over kv_seq and the
    cache seq dim picks up whatever remains)."""
    used = set()
    fixed = []
    for dim, entry in zip(shape, spec):
        axes = () if entry is None else (
            tuple(entry) if isinstance(entry, (tuple, list)) else (entry,))
        kept = []
        for a in axes:
            if a in used:
                continue
            n = mesh.shape[a]
            if dim % (n * int(np_prod([mesh.shape[x] for x in kept]))) != 0:
                continue
            kept.append(a)
        used.update(kept)
        fixed.append(tuple(kept) if len(kept) > 1 else
                     (kept[0] if kept else None))
    return P(*fixed)


def np_prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


def constrain(x, *logical):
    """with_sharding_constraint on logical axes; no-op without a mesh.
    Axes that don't divide the dim evenly are dropped (GSPMD would pad,
    but we prefer deterministic layouts). If NO logical axis maps to a
    mesh axis the call is a no-op -- an all-None spec would FORCE
    replication (inserting all-gathers) rather than leave layout to the
    partitioner, which is never what a hint should do."""
    mesh = _ctx.mesh
    if mesh is None:
        return x
    spec = _fix_spec(x.shape, logical_spec(*logical), mesh)
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter specs from path-based rules
# ---------------------------------------------------------------------------
# Patterns are matched against '/'-joined param tree paths. First match
# wins; value is the tuple of logical axes for the trailing dims (a
# leading 'layers' axis is added automatically for scanned params whose
# rank exceeds the pattern).
_PARAM_PATTERNS = [
    (r"embedding/table",        ("vocab", "embed")),
    (r"vfl_embedding/table",    ("vocab", "client")),   # VFL input block
    (r"lm_head/kernel",         ("embed", "vocab")),
    (r"(wq|wk|wv)/kernel",      ("embed", "heads")),
    (r"(wq|wk|wv)/bias",        ("heads",)),
    (r"wo/kernel",              ("heads", "embed")),
    (r"wo/bias",                (None,)),
    (r"experts/(w_gate|w_up)",  ("expert", "embed", "expert_mlp")),
    (r"experts/w_down",         ("expert", "expert_mlp", "embed")),
    (r"router/kernel",          ("embed", None)),
    (r"(w_gate|w_up|wi)/kernel", ("embed", "mlp")),
    (r"(w_down|wo_mlp)/kernel", ("mlp", "embed")),
    (r"(w_gate|w_up|wi|w_down|wo_mlp)/bias", (None,)),
    # mamba
    (r"mamba/in_proj",          ("embed", "ssm_inner")),
    (r"mamba/conv",             (None, "ssm_inner")),
    (r"mamba/(x_proj|dt_proj)", ("ssm_inner", None)),
    (r"mamba/dt_bias",          ("ssm_inner",)),
    (r"mamba/(A_log|D)",        ("ssm_inner", None)),
    (r"mamba/out_proj",         ("ssm_inner", "embed")),
    # rwkv6
    (r"rwkv/(wr|wk|wv|wg)/kernel", ("embed", "heads")),
    (r"rwkv/wo/kernel",         ("heads", "embed")),
    (r"rwkv/(decay_lora_a|gate_lora_a)", ("embed", None)),
    (r"rwkv/(decay_lora_b|gate_lora_b)", (None, "heads")),
    (r"rwkv/(mu|decay_base|bonus)", (None,)),
    (r"rwkv/cm_(wk)/kernel",    ("embed", "mlp")),
    (r"rwkv/cm_(wv)/kernel",    ("mlp", "embed")),
    (r"rwkv/cm_wr/kernel",      ("embed", "act_embed")),
    (r"norm|scale|bias",        (None,)),
]


# decode-state (KV cache / recurrent state) patterns
_STATE_PATTERNS = [
    (r"attn/(k|v)$",            ("batch", "kv_seq", "heads", None)),
    (r"attn/pos$",              ("batch", "kv_seq")),
    (r"mamba/h$",               ("batch", "ssm_inner", None)),
    (r"mamba/conv$",            ("batch", None, "ssm_inner")),
    (r"rwkv/wkv$|(^|/)wkv$",    ("batch", "heads", None, None)),
    (r"x_prev",                 ("batch", None)),
    (r"(^|/)position$",         ("batch",)),
    (r"(^|/)enc$",              ("batch", None, None)),
]

# training-batch patterns
_BATCH_PATTERNS = [
    (r"tokens|labels",          ("batch", None)),
    (r"prefix_emb",             ("batch", None, "client")),
]


def _logical_for_path(path: str, ndim: int, scanned: bool, patterns):
    for pat, axes in patterns:
        if re.search(pat, path):
            axes = tuple(axes)
            if scanned and ndim == len(axes) + 1:
                axes = ("layers",) + axes
            if len(axes) != ndim:
                axes = tuple([None] * (ndim - len(axes))) + axes \
                    if ndim > len(axes) else axes[-ndim:]
            return axes
    return tuple([None] * ndim)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _specs_for_tree(tree_shape, patterns, scanned: bool = True):
    def one(path, leaf):
        p = _path_str(path)
        axes = _logical_for_path(p, len(leaf.shape), scanned, patterns)
        spec = logical_spec(*axes)
        mesh = current_mesh()
        if mesh is not None:
            spec = _fix_spec(leaf.shape, spec, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(one, tree_shape)


def param_specs(params_shape, scanned: bool = True):
    """Pytree of PartitionSpec matching a (possibly abstract) params tree."""
    return _specs_for_tree(params_shape, _PARAM_PATTERNS, scanned)


def state_specs(state_shape, scanned: bool = True):
    """Specs for decode state (KV caches, SSM states, positions)."""
    return _specs_for_tree(state_shape, _STATE_PATTERNS, scanned)


def batch_specs(batch_shape):
    """Specs for a training/serving input batch dict."""
    return _specs_for_tree(batch_shape, _BATCH_PATTERNS, scanned=False)


def named_sharding_tree(specs, mesh: Mesh):
    mesh = auto_axes(mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
