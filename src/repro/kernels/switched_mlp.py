"""Pallas TPU kernel: multi-approximator "weight switch" (paper §III-D).

The NPU switches approximators by shipping a weight set from on-chip cache
to the PE weight buffers.  The TPU-native equivalent: rows are pre-sorted by
the classifier's class (ops.py), every grid tile is single-class, and a
SCALAR-PREFETCHED per-tile class index drives the weight BlockSpec index_map
— so the correct approximator's weights are DMA'd HBM->VMEM while the
previous tile computes.  Switching cost is therefore hidden behind compute
(the paper's "within a cycle" claim, Case 3), and when all approximators fit
VMEM the pipeline degenerates to Case 1 (no reload: consecutive tiles with
the same class reuse the same block).

Grid: one step per row-tile.  tile_cls (num_tiles,) int32 is the scalar
prefetch operand; weight index_maps select block ``tile_cls[i]`` of the
stacked (n_approx, ...) weight tensors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.scopes import SWITCH_KERNEL


def _switched_kernel(tile_cls_ref, x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref):
    del tile_cls_ref  # consumed by the index_maps only
    x = x_ref[...]
    h = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
    h = jnp.tanh(h + b1_ref[0].astype(jnp.float32))
    y = jnp.dot(h.astype(x.dtype), w2_ref[0], preferred_element_type=jnp.float32)
    o_ref[...] = (y + b2_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def switched_mlp(x: jax.Array, tile_cls: jax.Array, w1: jax.Array,
                 b1: jax.Array, w2: jax.Array, b2: jax.Array, *,
                 block_t: int = 256, interpret: bool = False) -> jax.Array:
    """Grouped MLP forward over class-sorted rows.

    x: (T, d_in) with T % block_t == 0 and every tile single-class;
    tile_cls: (T // block_t,) int32 — class of each tile;
    w1: (n, d_in, d_h); b1: (n, 1, d_h); w2: (n, d_h, d_out); b2: (n, 1, d_out).
    """
    t, d_in = x.shape
    n, _, d_h = w1.shape
    d_out = w2.shape[2]
    assert t % block_t == 0, (t, block_t)
    num_tiles = t // block_t

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((block_t, d_in), lambda i, tc: (i, 0)),
            pl.BlockSpec((1, d_in, d_h), lambda i, tc: (tc[i], 0, 0)),
            pl.BlockSpec((1, 1, d_h), lambda i, tc: (tc[i], 0, 0)),
            pl.BlockSpec((1, d_h, d_out), lambda i, tc: (tc[i], 0, 0)),
            pl.BlockSpec((1, 1, d_out), lambda i, tc: (tc[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, d_out), lambda i, tc: (i, 0)),
    )
    return pl.pallas_call(
        _switched_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d_out), x.dtype),
        interpret=interpret,
        name=SWITCH_KERNEL,
    )(tile_cls, x, w1, b1, w2, b2)
