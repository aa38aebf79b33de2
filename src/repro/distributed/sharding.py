"""Logical-axis sharding with divisibility fallback.

Every parameter/activation/cache tensor in ``repro.models`` carries a tuple
of *logical* axis names. This module resolves those names against a concrete
mesh via priority rules:

    RULES:  logical name -> tuple of mesh-axis candidates, tried in order.
            A candidate may itself be a tuple (joint sharding, e.g. batch
            over ("pod", "data")).

A candidate is accepted only if (a) all its mesh axes exist, (b) their size
product divides the tensor dim, and (c) none of them is already used by
another dim of the same tensor. Otherwise the next candidate (ultimately
``None`` = replicate) is tried. This is what keeps every (arch × mesh) cell
compilable without per-arch hand-tuning: 8 KV heads on a 16-way model axis
fall back to replicated KV while Q stays sharded; 60 experts fall back to
tensor-parallel expert FFNs; and so on (DESIGN.md §6).

``constrain`` applies ``with_sharding_constraint`` inside model code using
the ambient mesh + rules (no-op outside a mesh/rules context, so smoke tests
on one device run the same code).
"""
from __future__ import annotations

import contextlib
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Candidate = tuple[str, ...] | str | None

DEFAULT_RULES: dict[str, tuple[Candidate, ...]] = {
    "batch": (("pod", "data"), "data", None),
    "vocab": ("model", None),
    "embed": (None,),
    "heads": ("model", None),
    "kv_heads": ("model", None),
    "head_dim": (None,),
    "mlp": ("model", None),
    "experts": ("model", None),
    "expert_mlp": ("model", None),
    "lora": ("model", None),
    "layers": (None,),
    # KV-cache length: prefer whatever axes the tensor has not used yet —
    # decode_32k gets T/model (batch took data); long_500k's batch=1 falls
    # back to replicated so T takes (data, model) jointly (500K × d fits)
    "cache_len": (("pod", "data", "model"), ("data", "model"), "model", None),
    "length": (None,),
}

# Sequence-parallel variant: long-context caches shard their length dim over
# the data axis (each data shard owns a slice of the KV timeline). Used by
# the decode_32k / long_500k serve cells and as a §Perf lever.
SEQUENCE_RULES = dict(
    DEFAULT_RULES,
    cache_len=(("pod", "data"), "data", None),
    batch=(None,),
)

# DP-heavy variant (§Perf lever): batch shards over BOTH mesh axes, params
# keep their model shardings (ZeRO/FSDP-style weight gathers). The right
# config for architectures whose head counts don't divide the model axis
# (musicgen 24H, minicpm3 40H): uniform rules would replicate their
# attention compute 16× across the model axis; here every FLOP is
# data-parallel and the wire cost is one weight gather per layer.
DP_RULES = dict(
    DEFAULT_RULES,
    batch=(("pod", "data", "model"), ("data", "model"), ("pod", "data"),
           "data", None),
)

RULE_SETS = {
    "default": DEFAULT_RULES,
    "sequence": SEQUENCE_RULES,
    "dp": DP_RULES,
}


class _Ctx(threading.local):
    def __init__(self):
        self.rules: dict[str, tuple[Candidate, ...]] | None = None
        self.mesh: Mesh | None = None


_CTX = _Ctx()


@contextlib.contextmanager
def logical_axis_rules(rules: dict[str, tuple[Candidate, ...]] | None = None,
                       mesh: Mesh | None = None):
    prev = (_CTX.rules, _CTX.mesh)
    _CTX.rules = DEFAULT_RULES if rules is None else rules
    _CTX.mesh = mesh
    try:
        yield
    finally:
        _CTX.rules, _CTX.mesh = prev


def _ambient_mesh() -> Mesh | None:
    if _CTX.mesh is not None:
        return _CTX.mesh
    try:
        mesh = jax.interpreters.pxla.thread_resources.env.physical_mesh
        if mesh and not mesh.empty:
            return mesh
    except Exception:
        pass
    return None


def _axis_sizes(mesh) -> dict[str, int]:
    """Axis name -> size; works for Mesh and AbstractMesh."""
    sizes = getattr(mesh, "axis_sizes", None)
    if sizes is not None:
        return dict(zip(mesh.axis_names, sizes))
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def abstract_mesh(axis_sizes: tuple[int, ...], axis_names: tuple[str, ...]):
    """A device-free mesh of the given shape, for resolving specs."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def resolve_spec(
    names: tuple[str | None, ...], shape: tuple[int, ...], mesh: Mesh,
    rules: dict[str, tuple[Candidate, ...]] | None = None,
) -> P:
    """Resolve logical names for one tensor into a PartitionSpec."""
    rules = rules or DEFAULT_RULES
    sizes = _axis_sizes(mesh)
    used: set[str] = set()
    out: list = []
    for dim, name in zip(shape, names):
        if name is None or name not in rules:
            out.append(None)
            continue
        chosen = None
        for cand in rules[name]:
            if cand is None:
                break
            axes = (cand,) if isinstance(cand, str) else tuple(cand)
            if not all(a in sizes for a in axes):
                continue
            prod = int(np.prod([sizes[a] for a in axes]))
            if dim % prod != 0:
                continue
            if any(a in used for a in axes):
                continue
            chosen = axes if len(axes) > 1 else axes[0]
            used.update(axes)
            break
        out.append(chosen)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def tree_specs(spec_tree, shape_tree, mesh: Mesh, rules=None):
    """Resolve a whole tree of logical-name tuples to PartitionSpecs."""
    is_names = lambda t: isinstance(t, tuple) and all(
        isinstance(x, str) or x is None for x in t
    )
    return jax.tree_util.tree_map(
        lambda names, leaf: resolve_spec(names, leaf.shape, mesh, rules),
        spec_tree,
        shape_tree,
        is_leaf=is_names,
    )


def tree_shardings(spec_tree, shape_tree, mesh: Mesh, rules=None):
    specs = tree_specs(spec_tree, shape_tree, mesh, rules)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda t: isinstance(t, P),
    )


def constrain(x, *names):
    """with_sharding_constraint via the ambient mesh+rules; no-op outside."""
    rules = _CTX.rules
    mesh = _ambient_mesh()
    if rules is None or mesh is None:
        return x
    spec = resolve_spec(tuple(names), x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ------------------------------------------------------------------ ZeRO-1
def zero1_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Additionally shard one replicated dim over the data(+pod) axes.

    Optimizer moments are element-wise state: any consistent placement
    works, so we cut their footprint by the data-parallel degree (ZeRO-1).
    XLA inserts the reduce-scatter/all-gather pair around the update.
    """
    sizes = _axis_sizes(mesh)
    cands = [a for a in ("pod", "data") if a in sizes]
    if not cands:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used = set()
    for e in entries:
        if e is None:
            continue
        used.update((e,) if isinstance(e, str) else e)
    avail = [a for a in cands if a not in used]
    if not avail:
        return spec
    prod = int(np.prod([sizes[a] for a in avail]))
    best, best_dim = None, 0
    for i, (e, dim) in enumerate(zip(entries, shape)):
        if e is None and dim % prod == 0 and dim > best_dim:
            best, best_dim = i, dim
    if best is None:
        # try single-axis fallback
        for a in avail:
            for i, (e, dim) in enumerate(zip(entries, shape)):
                if e is None and dim % sizes[a] == 0 and dim > best_dim:
                    best, best_dim = i, dim
            if best is not None:
                entries[best] = a
                return P(*entries)
        return spec
    entries[best] = tuple(avail) if len(avail) > 1 else avail[0]
    return P(*entries)


def zero1_tree(specs_tree, shape_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s, leaf: zero1_spec(s, leaf.shape, mesh),
        specs_tree,
        shape_tree,
        is_leaf=lambda t: isinstance(t, P),
    )
