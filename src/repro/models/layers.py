"""Shared LM layers: norms, RoPE, embeddings, FFN, GQA attention with
flash-scan (online softmax over KV blocks) and decode caches.

Everything is pure-functional: ``init_*`` builds param dicts keyed by layer
name; ``*_fwd`` applies them.  Shardings are applied by the caller
(sharding/rules.py) via NamedSharding on the param pytree; activations get
with_sharding_constraint hints at the block level (model.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro import scopes
from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import paged_attention


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, shape_d: int):
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((shape_d,), cfg.pdtype)}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((shape_d,), cfg.pdtype),
                "bias": jnp.zeros((shape_d,), cfg.pdtype)}
    return {}  # nonparam_ln (olmo): no learnable affine


def norm_fwd(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        r = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
        return (xf * r).astype(x.dtype) * p["scale"].astype(x.dtype)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype)
    if cfg.norm == "layernorm":
        y = y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# RoPE (partial rotary supported — stablelm-2 uses 25%)
# ---------------------------------------------------------------------------

def rope_freqs(cfg: ModelConfig) -> jax.Array:
    rot = int(cfg.hd * cfg.rope_pct) // 2 * 2
    return 1.0 / (cfg.rope_base ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def apply_rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    rot = int(cfg.hd * cfg.rope_pct) // 2 * 2
    if rot == 0:
        return x
    freqs = rope_freqs(cfg)                               # (rot/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, rot/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out, xp], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    p = {"tok": jax.random.normal(k1, (cfg.vocab, cfg.d_model), cfg.pdtype) * 0.02}
    if not cfg.tie_embeddings:
        p["unembed"] = jax.random.normal(k2, (cfg.d_model, cfg.vocab), cfg.pdtype) * 0.02
    return p


def embed_fwd(cfg: ModelConfig, p, tokens_or_embeds: jax.Array) -> jax.Array:
    if cfg.input_mode == "embeddings":
        return tokens_or_embeds.astype(cfg.adtype)
    x = jnp.take(p["tok"], tokens_or_embeds, axis=0).astype(cfg.adtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


@jax.named_scope(scopes.UNEMBED)
def unembed_fwd(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    w = p["tok"].T if cfg.tie_embeddings else p["unembed"]
    logits = jnp.dot(x, w.astype(x.dtype))
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return logits


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------

_ACT = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu,
        "tanh": jnp.tanh}


def init_ffn(key, cfg: ModelConfig, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {"w_in": jax.random.normal(ks[0], (d, f), cfg.pdtype) * s_in,
         "w_out": jax.random.normal(ks[1], (f, d), cfg.pdtype) * s_out}
    if cfg.gated_ffn:
        p["w_gate"] = jax.random.normal(ks[2], (d, f), cfg.pdtype) * s_in
    return p


def ffn_fwd(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    act = _ACT[cfg.act if cfg.act != "swiglu" else "silu"]
    h = jnp.dot(x, p["w_in"].astype(x.dtype))
    if cfg.gated_ffn:
        h = act(jnp.dot(x, p["w_gate"].astype(x.dtype))) * h
    else:
        h = act(h)
    return jnp.dot(h, p["w_out"].astype(x.dtype))


# ---------------------------------------------------------------------------
# GQA attention with flash-scan
# ---------------------------------------------------------------------------

def init_attn(key, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.hd
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {"wq": jax.random.normal(ks[0], (d, nh * hd), cfg.pdtype) * s,
         "wk": jax.random.normal(ks[1], (d, nkv * hd), cfg.pdtype) * s,
         "wv": jax.random.normal(ks[2], (d, nkv * hd), cfg.pdtype) * s,
         "wo": jax.random.normal(ks[3], (nh * hd, d), cfg.pdtype) * (nh * hd) ** -0.5}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nh * hd,), cfg.pdtype)
        p["bk"] = jnp.zeros((nkv * hd,), cfg.pdtype)
        p["bv"] = jnp.zeros((nkv * hd,), cfg.pdtype)
    return p


def _qkv(cfg: ModelConfig, p, x: jax.Array):
    b, s, _ = x.shape
    q = jnp.dot(x, p["wq"].astype(x.dtype))
    k = jnp.dot(x, p["wk"].astype(x.dtype))
    v = jnp.dot(x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q, k, v = (q + p["bq"].astype(x.dtype), k + p["bk"].astype(x.dtype),
                   v + p["bv"].astype(x.dtype))
    q = q.reshape(b, s, cfg.n_heads, cfg.hd)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    return q, k, v


def _repeat_kv(cfg: ModelConfig, k: jax.Array) -> jax.Array:
    """(B, S, Kh, hd) -> (B, S, H, hd) by repeating each kv head."""
    rep = cfg.n_heads // cfg.n_kv_heads
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


def flash_attention(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
                    *, causal: bool = True, q_offset: int = 0) -> jax.Array:
    """Memory-bounded attention: scan over KV blocks with online softmax,
    vmapped-by-scan over Q blocks.  q: (B, Sq, H, hd); k, v: (B, Skv, H, hd).

    ``q_offset``: absolute position of q[0] (prefill continuation / decode).
    Sliding window masking honors cfg.sliding_window when set.
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    qb, kb = min(cfg.q_block, sq), min(cfg.kv_block, skv)
    nq, nk = sq // qb, skv // kb
    assert sq % qb == 0 and skv % kb == 0, (sq, qb, skv, kb)
    scale = cfg.attn_scale
    q = q.reshape(b, nq, qb, h, hd)
    k = k.reshape(b, nk, kb, h, hd)
    v = v.reshape(b, nk, kb, h, hd)
    win = cfg.sliding_window

    def q_step(_, qi):
        qblk, iq = qi                                   # (b, qb, h, hd), scalar
        q_pos = q_offset + iq * qb + jnp.arange(qb)

        def kv_step(carry, ki):
            acc, m, l = carry                           # acc: (b, h, qb, hd)
            kblk, vblk, ik = ki
            k_pos = ik * kb + jnp.arange(kb)
            s_ = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk).astype(jnp.float32) * scale
            mask = jnp.ones((qb, kb), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if win:
                mask &= q_pos[:, None] - k_pos[None, :] < win
            s_ = jnp.where(mask, s_, -1e30)
            m_new = jnp.maximum(m, s_.max(-1))          # (b, h, qb)
            p_ = jnp.exp(s_ - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p_.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p_.astype(vblk.dtype), vblk).astype(jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, h, qb, hd), jnp.float32)
        m0 = jnp.full((b, h, qb), -1e30, jnp.float32)
        l0 = jnp.zeros((b, h, qb), jnp.float32)
        # checkpoint the block body: the backward pass recomputes each
        # block's scores instead of materializing the (nq x nk) grid of
        # (qb, kb) probability tiles — this IS flash attention's backward
        (acc, m, l), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (acc0, m0, l0),
            (k.swapaxes(0, 1), v.swapaxes(0, 1), jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.swapaxes(1, 2).astype(cfg.adtype)  # (b, qb, h, hd)

    _, o = jax.lax.scan(jax.checkpoint(q_step), None,
                        (q.swapaxes(0, 1), jnp.arange(nq)))
    # o: (nq, b, qb, h, hd) -> (b, sq, h, hd)
    return o.swapaxes(0, 1).reshape(b, sq, h, hd)


LANES = 128


def pool_rows(cfg: ModelConfig) -> tuple[int, int]:
    """(rows, width) of one token in a paged K/V pool.  Heads narrower
    than the TPU's 128 lanes are packed, 128 // hd of them to a row, so
    that the paged kernel copies whole aligned rows: granite-4.0-h's 8
    KV heads of 64 are 4 rows of 128.  Other heads keep one row each."""
    kh, hd = cfg.n_kv_heads, cfg.hd
    if hd % LANES and LANES % hd == 0 and (kh * hd) % LANES == 0:
        return kh * hd // LANES, LANES
    return kh, hd


def _to_pool(x: jax.Array, pool: jax.Array) -> jax.Array:
    """K/V heads (..., Kh, hd) as rows of ``pool``, in its dtype."""
    x = x.astype(pool.dtype)
    rows = pool.shape[-2:]
    return x if x.shape[-2:] == rows else x.reshape(*x.shape[:-2], *rows)


def _from_pool(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Rows read from a pool, as K/V heads (..., Kh, hd)."""
    heads = (cfg.n_kv_heads, cfg.hd)
    return x if x.shape[-2:] == heads else x.reshape(*x.shape[:-2], *heads)


@jax.named_scope(scopes.KV_GATHER)
def _gather_pages(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """Gather a paged pool back into a per-slot dense view.

    pool: (n_pages, page_size, Kh, hd); block_table: (B, n_pp) int32 with
    -1 marking unallocated entries.  Returns (B, n_pp * page_size, Kh, hd).
    Holes clamp to page 0 — whatever lives there is garbage for that slot,
    but every attended position (kpos <= pos) sits in a page the slot
    owns, and the -1e30 mask ahead of the softmax zeroes the rest, so the
    garbage never reaches an output.  With page_size dividing max_len the
    gathered view has the dense cache's exact (B, max_len) reduction
    shape — paged attention is bit-identical to the dense oracle."""
    b, n_pp = block_table.shape
    g = jnp.take(pool, jnp.maximum(block_table, 0), axis=0)
    return g.reshape(b, n_pp * pool.shape[1], *pool.shape[2:])


def _attention_chunk(cfg: ModelConfig, q, k, v, cache):
    """Chunked-prefill attention against the DECODE cache layout.

    q/k/v: (B, S, ·, hd) — S prompt tokens per slot, each slot at its own
    sequence offset ``cache["pos"]`` with ``cache["n_valid"]`` (B,) real
    tokens this chunk (the tail is padding).  Writes are a per-slot scatter
    with ``mode="drop"``: padded tokens and any index at/past the cache end
    write NOWHERE, so the cache can never be clamp-corrupted by an
    oversized prompt — the overflow family's model-level guarantee.  The
    causal mask is per-query (kpos <= pos + i), so a chunk's logits match
    feeding its tokens one decode tick at a time.  Returns (out, new_cache)
    with ``pos`` advanced by ``n_valid``.

    A cache carrying ``block_table`` is PAGED: k/v are (n_pages,
    page_size, Kh, hd) pools and each token's write lands inside its
    slot's page for that position.  Padded tokens, positions past the
    block table, and unallocated (-1) entries all remap to the
    out-of-bounds page index ``n_pages`` so ``mode="drop"`` discards them
    (-1 itself would WRAP to the last page under numpy index
    normalization and corrupt it).
    """
    b, sq = q.shape[0], q.shape[1]
    pos, nv = cache["pos"], cache["n_valid"]
    off = jnp.arange(sq)
    tok_ok = off[None, :] < nv[:, None]                     # (B, Sq)
    qpos = pos[:, None] + off[None, :]                      # (B, Sq)
    if "block_table" in cache:
        bt = cache["block_table"]
        n_pages, page_size = cache["k"].shape[0], cache["k"].shape[1]
        n_pp = bt.shape[1]
        skv = n_pp * page_size
        pg_idx, within = jnp.divmod(qpos, page_size)
        pg = jnp.take_along_axis(bt, jnp.minimum(pg_idx, n_pp - 1), axis=1)
        pg = jnp.where(tok_ok & (pg_idx < n_pp) & (pg >= 0), pg, n_pages)
        with jax.named_scope(scopes.KV_WRITE):
            ck = cache["k"].at[pg, within].set(
                _to_pool(k, cache["k"]), mode="drop")
            cv = cache["v"].at[pg, within].set(
                _to_pool(v, cache["v"]), mode="drop")
        ak = _from_pool(_gather_pages(ck, bt), cfg)
        av = _from_pool(_gather_pages(cv, bt), cfg)
        new_cache = {"k": ck, "v": cv, "block_table": bt, "pos": pos + nv}
    else:
        skv = cache["k"].shape[1]
        idx = jnp.where(tok_ok, qpos, skv)
        write = jax.vmap(lambda c, new, i: c.at[i].set(new, mode="drop"))
        with jax.named_scope(scopes.KV_WRITE):
            ck = write(cache["k"], k.astype(cache["k"].dtype), idx)
            cv = write(cache["v"], v.astype(cache["v"].dtype), idx)
        ak, av = ck, cv
        new_cache = {"k": ck, "v": cv, "pos": pos + nv}
    valid = jnp.arange(skv)[None, None, :] <= qpos[:, :, None]  # (B, Sq, Skv)
    rep = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, sq, cfg.n_kv_heads, rep, cfg.hd)
    s_ = jnp.einsum("bqgrd,bkgd->bgrqk", qg, ak).astype(jnp.float32) \
        * cfg.attn_scale
    s_ = jnp.where(valid[:, None, None, :, :], s_, -1e30)
    w = jax.nn.softmax(s_, axis=-1).astype(av.dtype)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", w, av)
    o = o.reshape(b, sq, cfg.n_heads, cfg.hd)
    return o, new_cache


def _attention_paged_in_place(cfg: ModelConfig, p, q, k, v, cache, pg, off):
    """Paged decode attention on the layer STACK (``cache["layer"]`` set,
    k/v ``(L, N, P, Kh, hd)``): the step's K/V scatter into the stack at
    ``[layer, pg, off]`` (``pg`` already remapped so holes drop), and the
    paged kernel reads each slot's first ``cache["lengths"]`` tokens from
    the stack in place — no layer pool is sliced out or written back and
    no per-slot window is gathered."""
    b, i = q.shape[0], cache["layer"]
    with jax.named_scope(scopes.KV_WRITE):
        ck = cache["k"].at[i, pg, off].set(
            _to_pool(k[:, 0], cache["k"]), mode="drop")
        cv = cache["v"].at[i, pg, off].set(
            _to_pool(v[:, 0], cache["v"]), mode="drop")
    o = paged_attention(q[:, 0], ck, cv, i, cache["block_table"],
                        cache["lengths"], scale=cfg.attn_scale,
                        interpret=cfg.approx.interpret)
    o = o.reshape(b, 1, cfg.n_heads * cfg.hd)
    return jnp.dot(o, p["wo"].astype(o.dtype)), dict(cache, k=ck, v=cv,
                                                     pos=cache["pos"] + 1)


@jax.named_scope(scopes.ATTENTION)
def attention_fwd(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array,
                  cache: dict | None = None):
    """Self-attention.  Without a cache: full-sequence flash attention
    (train/prefill).  With a cache: single-step decode — update the cache at
    ``positions`` and attend over it.  A cache carrying ``n_valid`` takes
    the chunked-prefill path instead (S tokens per slot appended at per-slot
    offsets; dense caches only — ring buffers feed token-by-token).  A
    cache carrying ``block_table`` is PAGED (init_attn_cache(page_size=))
    on either path: writes scatter into the slot's pages with
    ``mode="drop"`` and attention gathers the pages back into the dense
    per-slot view — the block table is a TRACED input, so page
    allocation changes never retrace.  A paged decode cache that also
    carries ``layer`` (and ``lengths``) holds the whole layer stack: the
    write lands in the stack and the paged kernel reads the held pages
    in place (``_attention_paged_in_place``).

    Returns (out, new_cache).
    """
    b = x.shape[0]
    q, k, v = _qkv(cfg, p, x)
    q = apply_rope(cfg, q, positions)
    k = apply_rope(cfg, k, positions)

    if cache is not None and "n_valid" in cache:
        assert not cfg.sliding_window, \
            "chunked prefill targets dense decode caches; sliding-window " \
            "ring buffers feed their prompts token-by-token"
        o, new_cache = _attention_chunk(cfg, q, k, v, cache)
        o = o.reshape(b, o.shape[1], cfg.n_heads * cfg.hd)
        return jnp.dot(o, p["wo"].astype(o.dtype)), new_cache

    if cache is None:
        o = flash_attention(cfg, q, _repeat_kv(cfg, k), _repeat_kv(cfg, v))
        # Return the full-seq K/V (post-rope) so prefill can stack a decode
        # cache from scan outputs; the train path drops them (XLA while-loop
        # simplification DCEs unused scan ys).
        new_cache = {"k": k, "v": v}
    else:
        # decode: cache["k"]: (B, Skv, Kh, hd); cache["pos"]: (B,) per-slot
        # positions (continuous batching: every row may be at a different
        # sequence offset).  Writes are a vmapped dynamic_update_slice.
        # A cache carrying ``block_table`` is PAGED: k/v are (n_pages,
        # page_size, Kh, hd) pools shared by every slot, the block table
        # (B, n_pp) maps each slot's page index to a pool page (-1 =
        # unallocated), and attention runs over the gathered per-slot
        # view — same reduction shape as dense, so greedy tokens are
        # bit-identical to the page_size=0 oracle.
        pos = cache["pos"]
        if "block_table" in cache:
            assert not cfg.sliding_window, \
                "paged KV caches need absolute positions (no ring buffers)"
            bt = cache["block_table"]
            # (N, P, Kh, hd) layer pool, or the (L, N, P, Kh, hd) stack
            n_pages, page_size = cache["k"].shape[-4:-2]
            skv = bt.shape[1] * page_size
            pg_idx, off = jnp.divmod(pos, page_size)
            pg = jnp.take_along_axis(
                bt, jnp.minimum(pg_idx, bt.shape[1] - 1)[:, None], 1)[:, 0]
            # unallocated entries are -1, which numpy-style indexing would
            # WRAP onto the last pool page — remap to the out-of-bounds
            # index n_pages so mode="drop" discards the write instead of
            # corrupting a live page
            pg = jnp.where(pg < 0, n_pages, pg)
            if "layer" in cache:
                return _attention_paged_in_place(cfg, p, q, k, v, cache, pg,
                                                 off)
            with jax.named_scope(scopes.KV_WRITE):
                ck = cache["k"].at[pg, off].set(
                    _to_pool(k[:, 0], cache["k"]), mode="drop")
                cv = cache["v"].at[pg, off].set(
                    _to_pool(v[:, 0], cache["v"]), mode="drop")
            ak = _from_pool(_gather_pages(ck, bt), cfg)
            av = _from_pool(_gather_pages(cv, bt), cfg)
            valid = jnp.arange(skv)[None, :] <= pos[:, None]
            new_cache = {"k": ck, "v": cv, "block_table": bt, "pos": pos + 1}
        else:
            skv = cache["k"].shape[1]
            if cfg.sliding_window:
                slot = jnp.mod(pos, skv)                   # ring buffer
            else:
                slot = pos
            write = jax.vmap(
                lambda c, new, i: jax.lax.dynamic_update_slice(
                    c, new, (i, 0, 0)))
            with jax.named_scope(scopes.KV_WRITE):
                ck = write(cache["k"], k.astype(cache["k"].dtype), slot)
                cv = write(cache["v"], v.astype(cache["v"].dtype), slot)
            kpos = jnp.arange(skv)
            if cfg.sliding_window:
                valid = (kpos[None, :] <= slot[:, None]) \
                    | (pos[:, None] >= skv)
            else:
                valid = kpos[None, :] <= pos[:, None]      # (B, Skv)
            ak, av = ck, cv
            new_cache = {"k": ck, "v": cv, "pos": pos + 1}
        # grouped-query attention without materializing the head repeat:
        # q -> (B, 1, KV, rep, hd) and contract against the raw cache.
        rep = cfg.n_heads // cfg.n_kv_heads
        qg = q.reshape(b, q.shape[1], cfg.n_kv_heads, rep, cfg.hd)
        s_ = jnp.einsum("bqgrd,bkgd->bgrqk", qg, ak).astype(jnp.float32) \
            * cfg.attn_scale
        s_ = jnp.where(valid[:, None, None, None, :], s_, -1e30)
        w = jax.nn.softmax(s_, axis=-1).astype(av.dtype)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", w, av)
        o = o.reshape(b, q.shape[1], cfg.n_heads, cfg.hd)

    o = o.reshape(b, o.shape[1], cfg.n_heads * cfg.hd)
    return jnp.dot(o, p["wo"].astype(o.dtype)), new_cache


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                    page_size: int = 0, n_pages: int = 0):
    """Decode KV cache; sliding-window archs get a ring buffer of window
    size.  ``pos`` is per-slot (continuous batching).

    ``page_size > 0`` builds the PAGED layout instead: k/v become a fixed
    pool of ``(n_pages, page_size, *pool_rows(cfg))`` blocks shared by
    every slot (Kh rows of hd, or heads narrower than 128 lanes packed),
    plus a ``block_table`` (batch, max_len // page_size) int32 mapping
    each slot's page index to a pool page (-1 = unallocated — writes to a
    hole are dropped, never clamped).  ``page_size`` must divide
    ``max_len`` so the gathered per-slot view keeps the dense reduction
    shape (bit-exactness against the ``page_size=0`` oracle)."""
    if page_size:
        assert not cfg.sliding_window, \
            "paged KV caches need absolute positions (no ring buffers)"
        assert max_len % page_size == 0, (
            f"page_size={page_size} must divide max_len={max_len}")
        assert n_pages >= 1, f"paged cache needs n_pages >= 1, got {n_pages}"
        n_pp = max_len // page_size
        shape = (n_pages, page_size, *pool_rows(cfg))
        return {"k": jnp.zeros(shape, cfg.adtype),
                "v": jnp.zeros(shape, cfg.adtype),
                "block_table": jnp.full((batch, n_pp), -1, jnp.int32),
                "pos": jnp.zeros((batch,), jnp.int32)}
    length = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    shape = (batch, length, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, cfg.adtype), "v": jnp.zeros(shape, cfg.adtype),
            "pos": jnp.zeros((batch,), jnp.int32)}
