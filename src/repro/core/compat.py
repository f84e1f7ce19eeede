"""The JAX API surface this repo is written against (JAX 0.9).

Every module imports these names from here, never from jax directly, so a
version bump is a one-file change:

  - ``shard_map`` is ``jax.shard_map`` (replication check: ``check_vma``);
  - ``make_mesh`` builds a mesh whose axes are all ``AxisType.Auto``;
  - ``set_mesh`` is ``jax.set_mesh``, the ambient-mesh context manager;
  - ``cost_analysis`` is ``Compiled.cost_analysis()``, a flat dict.
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map
set_mesh = jax.set_mesh


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def cost_analysis(compiled) -> dict:
    return compiled.cost_analysis()
