"""Logical-axis sharding rules (GSPMD annotations).

Model code annotates tensors with *logical* axis names; a ``Rules`` table maps
those to physical mesh axes.  Annotations degrade gracefully: axes that do not
exist on the current mesh, or that do not divide the dimension size, are
dropped — so the same model code runs on a single CPU device, a 16x16 pod and
a 2x16x16 multi-pod mesh.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

# logical axis -> tuple of mesh axes (tried in order, kept if they divide)
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),        # data parallel over pod+data
    "cache_batch": ("pod", "data"),  # decode KV/state cache batch dim
    "clients": ("data",),            # federated client shards (PackedClients)
    "seq": (),                       # unsharded by default
    "kv_seq": ("model",),            # decode KV cache: sequence over model axis
    "embed": (),                     # activations replicated over model (TP)
    "heads": ("model",),             # attention head parallelism
    "kv_heads": ("model",),          # GQA kv heads (dropped when not divisible)
    "head_dim": (),
    "ff": ("model",),                # column-parallel ffn
    "vocab": ("model",),             # column-parallel logits
    "experts": ("model",),           # expert parallelism
    "expert_ff": (),                 # per-expert hidden dim
    "expert_cap": (),
    "ssm_inner": ("model",),         # mamba d_inner parallelism
    "ssm_state": (),
    "fsdp": ("data",),               # parameter sharding for FSDP variants
    "none": (),
}


class Rules:
    def __init__(self, table: Optional[Dict[str, Tuple[str, ...]]] = None,
                 fsdp: bool = False):
        self.table = dict(DEFAULT_RULES)
        if table:
            self.table.update(table)
        self.fsdp = fsdp

    def mesh_axes(self, logical: Optional[str]) -> Tuple[str, ...]:
        if logical is None:
            return ()
        return self.table.get(logical, ())


_state = threading.local()


def current_rules() -> Rules:
    r = getattr(_state, "rules", None)
    if r is None:
        r = Rules()
        _state.rules = r
    return r


@contextlib.contextmanager
def use_rules(rules: Rules):
    prev = getattr(_state, "rules", None)
    _state.rules = rules
    try:
        yield rules
    finally:
        _state.rules = prev


def _abstract_mesh():
    m = jax.sharding.get_abstract_mesh()
    if not m.axis_names:
        return None
    return m


def logical_spec(shape: Sequence[int], axes: Sequence[Optional[str]],
                 rules: Optional[Rules] = None, mesh=None) -> P:
    """Build a PartitionSpec from logical axis names, dropping non-divisible
    or absent mesh axes.

    ``mesh`` may be a concrete ``jax.sharding.Mesh`` (same ``axis_names`` /
    ``shape`` interface as the abstract mesh) for callers outside a
    ``jax.set_mesh`` context; without one the ambient mesh is used."""
    rules = rules or current_rules()
    mesh = mesh if mesh is not None else _abstract_mesh()
    if mesh is None:
        return P()
    entries = []
    used: set = set()
    for dim, name in zip(shape, axes):
        chosen = []
        size = 1
        for ax in rules.mesh_axes(name):
            if ax in used or ax not in mesh.axis_names:
                continue
            ax_size = mesh.shape[ax]
            if dim % (size * ax_size) != 0:
                continue
            chosen.append(ax)
            size *= ax_size
        for ax in chosen:
            used.add(ax)
        if not chosen:
            entries.append(None)
        elif len(chosen) == 1:
            entries.append(chosen[0])
        else:
            entries.append(tuple(chosen))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def shard(x: jax.Array, *axes: Optional[str]):
    """with_sharding_constraint by logical axis names; no-op off-mesh."""
    mesh = _abstract_mesh()
    if mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"shard(): {len(axes)} axes for rank-{x.ndim} tensor")
    spec = logical_spec(x.shape, axes)
    return jax.lax.with_sharding_constraint(x, spec)
