"""Inputs and weights of a run, made from ``--seed``.

The program's network, offloading plans and bucket layouts follow the
cell's fixed ``structure_seed``; ``--seed`` gives the weights and which
sample sits in which row.  So every seed runs the same sizes and the
same arrivals, on other values.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import convnet


def weights(config, seed: int, stream: int = 0):
    """Reference-layout weights ``[{"b", "w"}, ...]`` made on the device
    in one jitted call from ``(seed, stream)``."""
    seed = int(seed)
    make = jax.jit(lambda lo, hi, s: convnet.init_params(
        config, jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(lo), hi), s)))
    return make(np.uint32(seed & 0xFFFFFFFF),
                np.uint32((seed >> 32) & 0xFFFFFFFF), np.uint32(stream))


def to_program(ref_params, like):
    """The reference-layout weights in the program's pytree ``like``: the
    leaves of both, in order, are (bias, weight) per layer."""
    leaves, treedef = jax.tree_util.tree_flatten(like)
    mine = jax.tree_util.tree_leaves(ref_params)
    shapes = [a.shape for a in leaves], [a.shape for a in mine]
    if shapes[0] != shapes[1]:
        raise ValueError(f"the config's layers do not match the program's "
                         f"model: {shapes[1]} vs {shapes[0]}")
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.array(a, copy=True) for a in mine])


def shuffle_rows(rng: np.random.Generator, x, y):
    """The same rows in another order (inputs and labels together)."""
    order = rng.permutation(len(x))
    return np.asarray(x)[order], np.asarray(y)[order]
