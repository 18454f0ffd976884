"""The one home of JAX-API choices the rest of the codebase builds on.

* ``shard_map`` is ``jax.shard_map``.
* ``make_mesh`` builds meshes whose axes are all ``AxisType.Auto``:
  ``jax.make_mesh`` defaults to Explicit axes, under which the sharding
  constraints and gathers of the model code are refused.
* ``cost_analysis`` returns ``Compiled.cost_analysis()`` as a flat dict.
* ``setup_compile_cache`` places JAX's persistent compilation cache.

Everything that touches these APIs goes through this module so the rest
of the codebase is written against a single surface.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["shard_map", "make_mesh", "cost_analysis", "setup_compile_cache",
           "CHECKOUT_CACHE_DIR"]

shard_map = jax.shard_map

#: The compile cache's fixed in-checkout home (``<checkout>/.jax_cache``):
#: no temp name, pid or time, so a later run finds what an earlier stored.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def cost_analysis(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a dict (empty when XLA gives none)."""
    return dict(compiled.cost_analysis() or {})


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache and say where it is.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing is set here.  Otherwise the cache goes to the fixed
    :data:`CHECKOUT_CACHE_DIR`.  Returns a one-line description of the
    choice in effect."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return f"compile cache: {env} (from JAX_COMPILATION_CACHE_DIR)"
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return f"compile cache: {CHECKOUT_CACHE_DIR} (in-checkout default)"
