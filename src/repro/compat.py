"""Single import point for the JAX symbols the codebase re-exports.

The repo targets one installed JAX (see requirements-ci.txt). Call sites
import these names from here rather than from `jax` directly, so a future
rename is a one-line fix in this file:

  * `shard_map`  — `jax.shard_map`.
  * `make_mesh`  — `jax.make_mesh`.
  * `tree_map` / `tree_leaves` / `tree_flatten` / `tree_unflatten` /
    `tree_structure` — the `jax.tree_util` spellings.

Policy (see docs/montecarlo.md): production modules and tests import these
from `repro.compat`; no module probes the JAX version.
"""
from __future__ import annotations

import jax

__all__ = [
    "shard_map",
    "make_mesh",
    "tree_map",
    "tree_leaves",
    "tree_flatten",
    "tree_unflatten",
    "tree_structure",
]

shard_map = jax.shard_map
make_mesh = jax.make_mesh

tree_map = jax.tree_util.tree_map
tree_leaves = jax.tree_util.tree_leaves
tree_flatten = jax.tree_util.tree_flatten
tree_unflatten = jax.tree_util.tree_unflatten
tree_structure = jax.tree_util.tree_structure
