"""Pallas TPU kernel: fused lambda-weighted multi-client aggregation.

eq. (13) is a pure HBM-bandwidth operation executed over every parameter
each round: out[p] = sum_c w[c] * x[c, p]. The kernel streams 128x128-
aligned VMEM tiles of the flattened parameter axis, so each parameter
byte is read exactly once.  The client axis is a second, innermost grid
dimension of ``CLIENT_TILE``-row tiles that accumulate into the resident
f32 output tile, so the VMEM footprint does not grow with the cohort:
a round's whole padded cohort (C up to 1024 and beyond) compiles within
the default scoped VMEM limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 16384       # flattened f32 elements per tile (64 KiB per client row)
CLIENT_TILE = 32    # clients per reduction step (2 MiB f32 input tile)


def _agg_kernel(w_ref, x_ref, o_ref):
    # w_ref: (TC, 1); x_ref: (TC, BLOCK) VMEM tile; o_ref: (1, BLOCK) f32,
    # resident across the client grid axis (same block for every k)
    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[...].astype(jnp.float32)            # (TC, 1)
    x = x_ref[...].astype(jnp.float32)            # (TC, BLOCK)
    o_ref[...] += jnp.sum(w * x, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def weighted_aggregate(stacked: jnp.ndarray, weights: jnp.ndarray,
                       interpret: bool = False) -> jnp.ndarray:
    """Pallas path: stacked (C, ...) -> (...,) weighted sum over clients."""
    c = stacked.shape[0]
    out_shape = stacked.shape[1:]
    flat = stacked.reshape(c, -1)
    p = flat.shape[1]
    w2 = weights.reshape(c, 1).astype(jnp.float32)
    # a cohort of at most CLIENT_TILE clients is one full-height tile;
    # a larger one is cut into CLIENT_TILE-row tiles, its tail padded
    # with zero-weight clients
    tc = min(c, CLIENT_TILE)
    c_pad = (-c) % tc
    p_pad = (-p) % BLOCK
    if c_pad or p_pad:
        flat = jnp.pad(flat, ((0, c_pad), (0, p_pad)))
    if c_pad:
        w2 = jnp.pad(w2, ((0, c_pad), (0, 0)))
    n_blocks = flat.shape[1] // BLOCK
    n_client_tiles = flat.shape[0] // tc
    # inside shard_map the sum varies over the mesh axes its inputs do
    vma = jax.typeof(flat).vma | jax.typeof(w2).vma

    out = pl.pallas_call(
        _agg_kernel,
        grid=(n_blocks, n_client_tiles),
        in_specs=[
            pl.BlockSpec((tc, 1), lambda i, k: (k, 0)),
            pl.BlockSpec((tc, BLOCK), lambda i, k: (k, i)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, flat.shape[1]), jnp.float32,
                                       vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(w2, flat)
    return out[0, :p].astype(stacked.dtype).reshape(out_shape)
