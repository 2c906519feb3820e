"""Pallas TPU kernel: one decode step's attention over the paged KV stack,
read in place.

A paged decode cache keeps K and V as one stack per model, ``(L, N, P,
R, W)`` over its attention layers: ``N`` pool pages of ``P`` tokens per
layer, a token's KV heads as ``R`` rows of ``W`` features (one head a
row, or heads narrower than 128 lanes packed to a row, as granite-4.0-h
packs two of 64), and one page of one layer is a contiguous ``P * R *
W`` block.  This
kernel reads each slot's held pages of layer ``layer`` straight from that
stack in HBM, so the step never slices a layer's pool out of the stack,
never gathers a ``(B, max_len)`` window and never writes a pool back.

Mechanics.  Three scalar-prefetch operands: the layer index, the flattened
``(B, n_pp)`` block table and each slot's length (tokens to attend, 0 for
a slot with nothing held).  The grid runs over slots, in order; each grid
step loops over blocks of ``pages_per_block`` pages up to its slot's
length, so pages past the length are never fetched.  Each page is one
DMA per K and V (all ``Kh`` heads at once) into a VMEM double buffer: the
next block's pages — or, at a slot's last block, the next slot's first
block — are in flight while the current block computes.

Compute.  A block's keys are viewed as ``(T * Kh, hd)`` rows (token-major,
head-minor) and every query head is scored against every row; a
precomputed head mask keeps only the rows of the head's own KV group
(GQA without repeating heads), and a token mask keeps rows below the
slot's length.  The softmax is online, in f32; the value contraction
takes the probabilities in the cache's dtype with f32 accumulation.  A
slot whose length is 0 returns zeros.  Scoring all ``Kh`` heads costs
``Kh`` times the scores of a per-head contraction, which is noise at
decode sizes: the kernel is bound by the pages it reads.  With packed
rows, each query goes into its KV head's lane block of a zero row and
the head mask picks the row holding that head, so a row's other heads
score nothing; the output keeps the head's lane block.

A slot's length must not exceed its leading run of held pages (the
block table holds ``-1`` past it): ``held_lengths`` clips ``pos + 1`` to
that run, so a hole is never fetched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.scopes import PAGED_ATTENTION

BLOCK_TOKENS = 128      # tokens per compute block (pages_per_block * P)
_MASK = -1e30


def held_lengths(pos: jax.Array, block_table: jax.Array,
                 page_size: int) -> jax.Array:
    """Tokens each slot attends at a decode step: ``pos + 1`` (the step's
    own token included), clipped to the slot's leading run of held pages
    (0 for a slot that holds none)."""
    held = jnp.sum(jnp.cumprod(block_table >= 0, axis=1), axis=1)
    return jnp.minimum(pos + 1, held * page_size).astype(jnp.int32)


def _kernel(ppb, page, n_pp, scale, layer_ref, bt_ref, len_ref, q_ref,
            grp_ref, tok_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem,
            first_ref):
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]
    blk_tok = ppb * page

    def held_pages(slot, blk):
        """Pages of block ``blk`` of ``slot`` below its length."""
        left = len_ref[slot] - blk * blk_tok
        return jnp.clip(pl.cdiv(left, page), 0, ppb)

    def dma(slot, blk, buf, n, act):
        """``act`` (start or wait) on the K and V copies of the first
        ``n`` pages of block ``blk`` of ``slot`` into buffer ``buf``."""
        def one(j, carry):
            pg = bt_ref[slot * n_pp + jnp.minimum(blk * ppb + j, n_pp - 1)]
            for hbm, vmem in ((k_hbm, k_buf), (v_hbm, v_buf)):
                act(pltpu.make_async_copy(hbm.at[layer, pg], vmem.at[buf, j],
                                          sem.at[buf]))
            return carry
        jax.lax.fori_loop(0, n, one, 0)

    def start(slot, blk, buf, n):
        dma(slot, blk, buf, n, lambda c: c.start())

    @pl.when(b == 0)
    def _first_slot():
        # pages a block does not fetch keep what the buffer held: zero it
        # once so that stale rows are finite (0 * garbage would be NaN)
        k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        first_ref[0] = 0
        start(0, 0, 0, held_pages(0, 0))

    length = len_ref[b]
    n_blk = pl.cdiv(length, blk_tok)
    first = first_ref[0]
    # the slot after this one, clamped: past the last slot it fetches
    # nothing
    after = jnp.minimum(b + 1, n_slots - 1)
    after_pages = jnp.where(b + 1 < n_slots, held_pages(after, 0), 0)
    q = q_ref[0]                                        # (H, hd)
    grp = grp_ref[...] != 0                             # (H, T * Kh)
    tok = tok_ref[...]                                  # (1, T * Kh)

    def body(blk, carry):
        m, l, acc = carry
        cur = jax.lax.rem(first + blk, 2)
        last = blk + 1 == n_blk
        # prefetch this slot's next block, or after its last block the
        # next slot's first, into the other buffer
        start(jnp.where(last, after, b), jnp.where(last, 0, blk + 1),
              1 - cur, jnp.where(last, after_pages, held_pages(b, blk + 1)))
        dma(b, blk, cur, held_pages(b, blk), lambda c: c.wait())
        k = k_buf[cur]
        k = k.reshape(-1, k.shape[-1])                  # (T * Kh, hd)
        v = v_buf[cur]
        v = v.reshape(-1, v.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = grp & (tok + blk * blk_tok < length)
        s = jnp.where(ok, s, _MASK)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_new, alpha * acc + pv

    h, hd = q.shape
    m0 = jnp.full((h, 1), _MASK, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    a0 = jnp.zeros((h, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blk, body, (m0, l0, a0))

    @pl.when(n_blk == 0)
    def _idle_slot():
        start(after, 0, first, after_pages)

    # the next slot's first block went to the buffer after this slot's
    # last (to ``first`` itself when this slot had no block)
    first_ref[0] = jax.lax.rem(first + n_blk, 2)
    o_ref[0] = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0),
                         0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(q: jax.Array, k_stack: jax.Array, v_stack: jax.Array,
                    layer: jax.Array, block_table: jax.Array,
                    lengths: jax.Array, *, scale: float | None = None,
                    interpret: bool = False) -> jax.Array:
    """Decode attention of layer ``layer`` over the held pages, in place.

    q: (B, H, hd), one query per slot; k_stack, v_stack: (L, N, P, R, W),
    a token's K/V heads as R rows of W features (``layers.pool_rows``):
    one head a row, or W // hd heads packed in a row of W lanes; layer:
    int32 scalar; block_table: (B, n_pp) int32 (-1 = hole); lengths:
    (B,) int32 from ``held_lengths``.  Returns (B, H, hd) in q's dtype:
    softmax(scale * q k^T) v over each slot's first ``lengths`` tokens,
    zeros where the length is 0; ``scale`` defaults to ``hd ** -0.5``.
    """
    b, h, hd = q.shape
    _, _, page, kh, width = k_stack.shape
    ppb = min(block_table.shape[1], pl.cdiv(BLOCK_TOKENS, page))
    # query head i reads KV head g = i // rep, which sits in row g // pack
    # at lane block g % pack (pack = 1: one head a row, block 0).  The
    # query goes to that lane block of a zero row, so a row's other heads
    # score nothing; the output keeps that block of the value rows.  A
    # block's (T * R, W) view is token-major, row-minor.
    assert width % hd == 0, (width, hd)
    pack = width // hd
    assert h % (kh * pack) == 0, (h, kh, pack)
    rep = h // (kh * pack)
    ids = lambda n: jnp.arange(n, dtype=jnp.int32)
    lane = jnp.tile(jnp.repeat(ids(pack), rep), kh)         # g % pack
    row = jnp.repeat(ids(kh), rep * pack)                   # g // pack
    # XLA does not fold the lane block's multiply and take at pack = 1
    qk = q
    if pack > 1:
        blk = jax.nn.one_hot(lane, pack, dtype=q.dtype)
        qk = (q[:, :, None, :] * blk[None, :, :, None]).reshape(b, h, width)
    grp = (jnp.tile(ids(kh), ppb * page)[None, :]
           == row[:, None]).astype(jnp.int32)
    tok = jnp.repeat(ids(ppb * page), kh)[None, :]
    if scale is None:
        scale = hd ** -0.5
    out = _paged_call(qk, k_stack, v_stack, layer, block_table, lengths,
                      grp, tok, ppb=ppb, scale=scale, interpret=interpret)
    if pack == 1:
        return out
    out = out.reshape(b, h, pack, hd)
    return jnp.take_along_axis(out, lane[None, :, None, None], axis=2)[:, :, 0]


def _paged_call(q, k_stack, v_stack, layer, block_table, lengths, grp, tok,
                *, ppb, scale, interpret):
    b, h, hd = q.shape
    _, _, page, kh, _ = k_stack.shape
    n_pp = block_table.shape[1]
    rows = ppb * page * kh

    # index maps take the grid index and the three prefetched operands
    def _slot(i, layer_s, bt_s, len_s):
        return (i, 0, 0)

    def _whole(i, layer_s, bt_s, len_s):
        return (0, 0)

    buf = pltpu.VMEM((2, ppb, page, kh, hd), k_stack.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, hd), _slot),
            pl.BlockSpec((h, rows), _whole),
            pl.BlockSpec((1, rows), _whole),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, hd), _slot),
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    kernel = functools.partial(_kernel, ppb, page, n_pp, scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=PAGED_ATTENTION,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), block_table.reshape(-1),
      lengths.astype(jnp.int32), q.astype(k_stack.dtype), grp, tok,
      k_stack, v_stack)
