"""Pallas TPU kernel: FUSED dispatch — gather/compute/scatter in one pass.

The unfused engine path (ops.switched_apply) moves every activation row
three times per layer: scatter into class-sorted order, the weight-switch
kernel, gather back to original order — each a standalone XLA op crossing
HBM.  The paper's NPU gets its switch "within a cycle" precisely because
only synapse weights move while activations stay put (§III-D); this kernel
is the TPU-native equivalent of that claim.

Mechanics: the ``DispatchPlan``'s class-sort is folded into a single
(t_pad,) int32 ROW-INDEX vector (``fused_row_index``: padded position p
holds the original row that sorts there, or the trash id T for padding),
scalar-prefetched alongside ``tile_cls``.  The activations ride into the
kernel ONCE as a VMEM-resident block; each grid step reads its tile's row
ids from SMEM, gathers those rows VMEM-locally, computes the tile under
the weight block ``tile_cls[i]`` selects (the same scalar-prefetched
weight switch as switched_mlp.py), and the epilogue scatters the results
straight to their ORIGINAL row of the output block — which is flushed to
HBM once when the grid finishes.  Net: one HBM pass over activations per
layer and zero standalone gather/scatter ops in the surrounding program.

Padding rows (positions with row id T) gather a clamped real row, compute
garbage under that tile's weights, and scatter into the trash row T of the
(T + 1)-row output — sliced off afterwards — so they never touch a real
row.  Exact / over-capacity / masked rows ride the zero-weight
pseudo-class exactly as in the unfused kernel and come out exactly zero.

When fusion is sound: the whole activation block (T, d_in) and the
(T + 1, d_out) output must fit VMEM simultaneously with one weight block
(the compiled branch also holds an f32 copy of each) — decode-tick
batches do comfortably (a 1024×512 f32 block is 2 MiB against ~16 MiB
VMEM on v5e); past that, fall back to the unfused
``backend="pallas"`` path whose tiles stream.  The kernel keeps two
I/O strategies behind the static ``vector_io`` flag:

  * ``vector_io=True`` (default under ``interpret``): value-level
    vectorized gather/scatter inside the kernel body.  In interpret mode
    these lower to plain XLA gathers on the VMEM-resident block values —
    the CI-measurable form — and XLA keeps the revisited full-array
    blocks in place across grid steps.
  * ``vector_io=False`` (default compiled, the only branch Mosaic
    lowers): per-row ref-to-ref copies (``fori_loop`` over SMEM row ids,
    ``pl.ds`` row slices), staged through f32 VMEM scratch because Mosaic
    moves a single row at a dynamic sublane offset only for 32-bit data.
    The output is assembled in f32 scratch and written once, cast, at the
    last grid step.  Both branches are bit-identical (pinned in
    tests/test_fused_dispatch.py, f32 and bf16); the compute between them
    is shared and shape-identical to _switched_kernel, so results match
    the unfused kernel bit-for-bit.  tests/test_tpu_compile.py compiles
    this branch for a described TPU v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.scopes import SWITCH_KERNEL_FUSED


def _pad_rows(n: int) -> int:
    """Round a scratch row count up to whole (8, 128) f32 tiles."""
    return pl.cdiv(n, 8) * 8


def fused_row_index(order: jax.Array, pos: jax.Array, t: int,
                    t_pad: int) -> jax.Array:
    """Fold a class-sort permutation into the kernel's row-index vector.

    ``(order, pos)`` come from ops.class_sort_plan (original row
    ``order[k]`` lands at padded position ``pos[k]``).  Returns a (t_pad,)
    int32 vector mapping each padded position to its ORIGINAL row — both
    the gather source on load and the scatter destination on store —
    with padding positions holding the trash id ``t``.
    """
    return jnp.full((t_pad,), t, jnp.int32).at[pos].set(
        order.astype(jnp.int32))


def _fused_kernel(t, block_t, d_in, vector_io,
                  rows_ref, tile_cls_ref, x_ref, w1_ref, b1_ref, w2_ref,
                  b2_ref, o_ref, *stage):
    del tile_cls_ref  # consumed by the weight index_maps only
    i = pl.program_id(0)
    base = i * block_t
    d_in_p = w1_ref.shape[1]

    # ---- gather-on-load: this tile's rows, VMEM-locally ------------------
    if vector_io:
        idx = jax.lax.dynamic_slice(rows_ref[...], (base,), (block_t,))
        src = jnp.minimum(idx, t - 1)          # padding rows read a real row
        xs = x_ref[...][src]
        if d_in_p > d_in:
            xs = jnp.pad(xs, ((0, 0), (0, d_in_p - d_in)))
    else:
        # Mosaic moves single rows at a dynamic sublane offset only for
        # 32-bit data, so rows are staged through f32 scratch: the whole
        # activation block once (zero lane padding included), then one
        # row copy per tile row.  bf16 -> f32 -> bf16 is exact, so the
        # compute below sees the same bits as the unfused kernel.
        x32_ref, xs32_ref, ys32_ref, o32_ref = stage

        @pl.when(i == 0)
        def _stage_activations():
            if d_in_p > d_in:
                x32_ref[:, d_in:] = jnp.zeros(
                    (x32_ref.shape[0], d_in_p - d_in), jnp.float32)
            x32_ref[:t, :d_in] = x_ref[...].astype(jnp.float32)

        def gather_body(k, carry):
            r = jnp.minimum(rows_ref[base + k], t - 1)
            xs32_ref[pl.ds(k, 1), :] = x32_ref[pl.ds(r, 1), :]
            return carry
        jax.lax.fori_loop(0, block_t, gather_body, 0)
        xs = xs32_ref[...].astype(x_ref.dtype)

    # ---- compute: identical shapes/ops to _switched_kernel ---------------
    h = jnp.dot(xs, w1_ref[0], preferred_element_type=jnp.float32)
    h = jnp.tanh(h + b1_ref[0].astype(jnp.float32))
    y = jnp.dot(h.astype(xs.dtype), w2_ref[0],
                preferred_element_type=jnp.float32)
    y = (y + b2_ref[0].astype(jnp.float32)).astype(o_ref.dtype)

    # ---- scatter-on-store: straight to original rows (trash row t for
    # padding positions; every real row is written exactly once) ----------
    if vector_io:
        o_ref[...] = o_ref[...].at[idx].set(y)
    else:
        ys32_ref[...] = y.astype(jnp.float32)

        def scatter_body(k, carry):
            o32_ref[pl.ds(rows_ref[base + k], 1), :] = ys32_ref[pl.ds(k, 1), :]
            return carry
        jax.lax.fori_loop(0, block_t, scatter_body, 0)

        @pl.when(i == pl.num_programs(0) - 1)
        def _flush():
            o_ref[...] = o32_ref[:t + 1, :].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "interpret", "vector_io"))
def switched_mlp_fused(x: jax.Array, rows: jax.Array, tile_cls: jax.Array,
                       w1: jax.Array, b1: jax.Array, w2: jax.Array,
                       b2: jax.Array, *, block_t: int = 256,
                       interpret: bool = False,
                       vector_io: bool | None = None) -> jax.Array:
    """Fused grouped MLP over UNSORTED rows via a prefetched row index.

    x: (T, d_in) in ORIGINAL row order; rows: (t_pad,) int32 row index
    from ``fused_row_index`` (t_pad % block_t == 0, every block_t tile
    single-class); tile_cls: (t_pad // block_t,) int32 per-tile class;
    w1: (n, d_in_p, d_h_p); b1: (n, 1, d_h_p); w2: (n, d_h_p, d_out_p);
    b2: (n, 1, d_out_p) — feature dims may exceed x's (lane padding).

    Returns (T + 1, d_out_p): row r of the input's result at row r, the
    trash row last — callers slice ``[:T, :d_out]``.
    """
    t, d_in = x.shape
    assert t >= 1, "fused dispatch needs at least one row"
    d_in_p, d_h_p = w1.shape[1], w1.shape[2]
    d_out_p = w2.shape[2]
    assert d_in <= d_in_p, (d_in, d_in_p)
    t_pad = rows.shape[0]
    assert t_pad % block_t == 0, (t_pad, block_t)
    num_tiles = t_pad // block_t
    if vector_io is None:
        vector_io = bool(interpret)

    # Named index maps (arity = grid rank 1 + num_scalar_prefetch 2): the
    # activation/output blocks are whole-array VMEM residents (constant
    # block index -> fetched once, flushed once); only the weight blocks
    # switch per tile, driven by the prefetched tile_cls exactly as in the
    # unfused kernel.
    def _resident(i, rows_s, tile_cls_s):
        return (0, 0)

    def _weight(i, rows_s, tile_cls_s):
        return (tile_cls_s[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((t, d_in), _resident),
            pl.BlockSpec((1, d_in_p, d_h_p), _weight),
            pl.BlockSpec((1, 1, d_h_p), _weight),
            pl.BlockSpec((1, d_h_p, d_out_p), _weight),
            pl.BlockSpec((1, 1, d_out_p), _weight),
        ],
        out_specs=pl.BlockSpec((t + 1, d_out_p), _resident),
        scratch_shapes=[] if vector_io else [
            pltpu.VMEM((_pad_rows(t), d_in_p), jnp.float32),
            pltpu.VMEM((block_t, d_in_p), jnp.float32),
            pltpu.VMEM((block_t, d_out_p), jnp.float32),
            pltpu.VMEM((_pad_rows(t + 1), d_out_p), jnp.float32)],
    )
    kernel = functools.partial(_fused_kernel, t, block_t, d_in, vector_io)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t + 1, d_out_p), x.dtype),
        interpret=interpret,
        name=SWITCH_KERNEL_FUSED,
    )(rows, tile_cls, x, w1, b1, w2, b2)
