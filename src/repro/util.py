"""Small shared utilities: mesh-aware sharding constraints, dtypes, trees."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def constrain(x: jnp.ndarray, spec: P) -> jnp.ndarray:
    """``with_sharding_constraint`` that no-ops when no mesh is active.

    Models call this on large intermediates (MoE dispatch buffers, SSM
    channel states). Under an active mesh (``jax.set_mesh``) the
    constraint binds; in
    single-device unit tests it silently disappears. Axis names
    not present in the active mesh are dropped from the spec.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))

    def filt(entry, dim):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(e for e in entry if e in sizes)
            total = 1
            for e in kept:
                total *= sizes[e]
            return kept if (kept and dim % total == 0) else None
        if entry not in sizes or dim % sizes[entry] != 0:
            return None
        return entry

    entries = list(spec) + [None] * (x.ndim - len(spec))
    new_spec = P(*(filt(e, d) for e, d in zip(entries, x.shape)))
    return jax.lax.with_sharding_constraint(x, new_spec)


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def tree_size(tree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def split_like(key, tree):
    """One PRNG key per leaf, mirroring the tree structure."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, list(keys))


def count_params(params) -> int:
    return tree_size(params)
