"""Partition-rule engine and sharding helpers.

Sharding strategy (see DESIGN.md §4):

* weights: FSDP-style 2-D — tensor-parallel dims (heads*d_head, d_ff,
  experts) on ``model``; d_model on ``data``. Replicated across ``pod``
  (pure DP over DCN between pods).
* activations: batch on ``(pod, data)``; head / feature dims on ``model``.
* optimizer state inherits the param specs (ZeRO-1).

Rules are (regex, PartitionSpec-template) pairs matched against the
"/"-joined param path; templates use axis *roles* ("B", "D", "M", None)
resolved against the active mesh (so the same rules serve the single-pod
(data, model) and multi-pod (pod, data, model) meshes).
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE_MESH: contextvars.ContextVar[Optional[Mesh]] = \
    contextvars.ContextVar("repro_active_mesh", default=None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Activate a mesh for `constrain` hints inside model code."""
    tok = _ACTIVE_MESH.set(mesh)
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _ACTIVE_MESH.reset(tok)


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH.get()


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: the bodies are
    Pallas kernels and hand-placed collectives it cannot see through."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _resolve_role(role, mesh: Mesh):
    """Map an axis role to concrete mesh axis name(s). Training meshes name
    the tensor-parallel axis ``model``; serving meshes (launch/mesh.py
    ``make_tp_mesh``) name it ``tp`` — the same "M" role resolves to either,
    so one set of rules serves both worlds."""
    names = mesh.axis_names
    if role is None:
        return None
    if role == "B":                      # batch: all pure-data axes
        return ("pod", "data") if "pod" in names else "data"
    if role == "D":                      # fsdp: data axis only
        return "data"
    if role == "M":                      # tensor parallel
        return "tp" if "tp" in names else "model"
    return role


def spec(*roles) -> Tuple[Any, ...]:
    return tuple(roles)


def to_pspec(roles: Sequence[Any], mesh: Mesh) -> P:
    return P(*[_resolve_role(r, mesh) for r in roles])


def constrain(x: jax.Array, *roles) -> jax.Array:
    """Sharding hint; no-op when no mesh is active (CPU tests). Axes that
    don't divide their mesh extent are dropped to replicated (same
    ``roles_pspec`` rule as the cache layout) — otherwise a hint on e.g. a
    2-kv-head cache at tp=4 would force GSPMD pad-shard/reshard cycles
    against the replicated pool every decode step."""
    mesh = _ACTIVE_MESH.get()
    if mesh is None or mesh.size == 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, roles_pspec(roles, x.shape, mesh)))


# ---------------------------------------------------------------------------
# Parameter partition rules
# ---------------------------------------------------------------------------

# (regex over "/".join(path), role template). First match wins. Templates are
# aligned to the *trailing* dims of the array (leading dims — e.g. the stacked
# layer axis from scan — are unsharded).
DEFAULT_RULES: Tuple[Tuple[str, Tuple[Any, ...]], ...] = (
    # embeddings: vocab on model, d_model on data
    (r"(^|/)embed(/w)?$", ("M", "D")),
    (r"(^|/)(lm_)?head(/w)?$", ("D", "M")),
    (r"pos_embed", (None, "D")),
    # attention
    (r"attn/wqkv$", ("D", "M")),
    (r"attn/bqkv$", ("M",)),
    (r"attn/wo$", ("M", "D")),
    # dense / residual MLP
    (r"mlp/w_(gate|up)$", ("D", "M")),
    (r"mlp/w_down$", ("M", "D")),
    # MoE: experts on model, then (d_in, d_out) on (data, -)
    (r"moe/w_(gate|up)$", ("M", "D", None)),
    (r"moe/w_down$", ("M", None, "D")),
    (r"moe/router$", ("D", None)),
    # mamba
    (r"mamba/w_in$", ("D", "M")),
    (r"mamba/w_out$", ("M", "D")),
    (r"mamba/(w_x|conv_w|A_log|D|dt_)", ("M",)),
    # xlstm
    (r"xlstm/w_(qkv|if|o)$", ("D", "M")),
    (r"xlstm/w_proj$", ("M", "D")),
    # norms / scalars: replicated
    (r".*", ()),
)


def serve_rules() -> Tuple[Tuple[str, Tuple[Any, ...]], ...]:
    """Inference partition rules: TP-only (weights replicated across the
    data/pod axes). FSDP ("D"-role) sharding is a *training* memory
    optimization; at decode it forces a per-token all-gather of every
    weight (see EXPERIMENTS.md §Perf, jamba decode iteration)."""
    return tuple((rx, tuple(None if r == "D" else r for r in roles))
                 for rx, roles in DEFAULT_RULES)


def _drop_indivisible(full: Sequence[Any], shape: Tuple[int, ...],
                      mesh: Mesh) -> P:
    """Drop shardings that don't divide (GSPMD would pad; for params and
    cache leaves we prefer exact or replicated on that dim)."""
    fixed = []
    for dim, ax in zip(shape, full):
        if ax is None:
            fixed.append(None)
            continue
        size = np.prod([mesh.shape[a] for a in
                        (ax if isinstance(ax, tuple) else (ax,))])
        fixed.append(ax if dim % int(size) == 0 else None)
    return P(*fixed)


def rules_pspec(path: str, shape: Tuple[int, ...], mesh: Mesh,
                rules=DEFAULT_RULES) -> P:
    # int-resident (prequantized) {w_int | w_packed, w_scale, colsum}
    # leaves: w_int/w_packed shard exactly like their fp parent weight (the
    # rules match the parent path), the (N,)-shaped colsum follows the
    # parent's OUTPUT axis (it is a per-output-column reduction — the
    # zero-point correction must stay local to the shard that owns those
    # columns), and the scalar/group w_scale replicates. For w_packed the
    # contracting axis holds K/2 nibble-pair rows: under the serve rules
    # that axis is unsharded anyway ("D" roles nulled), and under training
    # rules a packed K/2 that no longer divides the mesh axis is dropped to
    # replicated by _drop_indivisible — divisibility is handled, never
    # silently padded.
    path = re.sub(r"/w_(int|packed)$", "", path)
    if path.endswith("/w_scale"):
        return P()
    mcol = re.match(r"^(.*)/colsum$", path)
    if mcol:
        for rx, roles in rules:
            if re.search(rx, mcol.group(1)):
                out_role = roles[-1] if roles else None
                full = (None,) * (len(shape) - 1) \
                    + (_resolve_role(out_role, mesh),)
                return _drop_indivisible(full, shape, mesh)
        return P()
    for rx, roles in rules:
        if re.search(rx, path):
            pads = (None,) * (len(shape) - len(roles))
            full = pads + tuple(_resolve_role(r, mesh) for r in roles)
            return _drop_indivisible(full, shape, mesh)
    return P()


def roles_pspec(roles: Sequence[Any], shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Resolve a role template aligned to the *leading* dims of `shape`
    (cache-leaf convention; trailing dims replicated), dropping axes that
    don't divide — e.g. a KV-heads axis narrower than the tp width falls
    back to replicated instead of GSPMD padding."""
    full = tuple(_resolve_role(r, mesh) for r in roles)
    full = full + (None,) * (len(shape) - len(full))
    return _drop_indivisible(full, shape, mesh)


def cache_shardings(roles: Any, cache: Any, mesh: Mesh) -> Any:
    """NamedShardings for a serving-cache pytree from a family's
    ``cache_roles`` template (models/*.cache_roles: leaf name -> role
    tuple; xlstm nests its state dicts). Leaves without a template entry
    are replicated (tiny scales / cushion blocks / untemplated families)."""
    if isinstance(cache, dict):
        rd = roles if isinstance(roles, dict) else {}
        return {key: cache_shardings(rd.get(key, ()), leaf, mesh)
                for key, leaf in cache.items()}
    rt = roles if isinstance(roles, (tuple, list)) else ()
    return NamedSharding(mesh, roles_pspec(rt, cache.shape, mesh))


def tree_paths(tree: Any) -> Any:
    """Pytree of "/"-joined key paths, same structure as `tree`."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)

    def keystr(kp):
        parts = []
        for k in kp:
            if isinstance(k, jax.tree_util.DictKey):
                parts.append(str(k.key))
            elif isinstance(k, jax.tree_util.SequenceKey):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        return "/".join(parts)
    return jax.tree_util.tree_unflatten(treedef, [keystr(kp) for kp, _ in flat])


def params_shardings(params_shape: Any, mesh: Mesh, rules=DEFAULT_RULES) -> Any:
    """NamedShardings for a (possibly abstract) param pytree."""
    paths = tree_paths(params_shape)
    return jax.tree_util.tree_map(
        lambda p, x: NamedSharding(mesh, rules_pspec(p, x.shape, mesh, rules)),
        paths, params_shape)


def batch_sharding(mesh: Mesh, ndim: int, batch_divisible: bool = True) -> NamedSharding:
    """Leading-axis batch sharding for data batches."""
    roles = ("B",) + (None,) * (ndim - 1)
    if not batch_divisible:
        roles = (None,) * ndim
    return NamedSharding(mesh, to_pspec(roles, mesh))
