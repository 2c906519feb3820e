"""The paged decode attention kernel (kernels/paged_attention.py) against
the gathered XLA path it replaces, in interpret mode.

Pins:
  * the kernel equals the gathered path (``layers._gather_pages`` of the
    layer's pool, masked softmax over the per-slot window) on the same
    stack, for several layer indices, positions at the page edges
    (P-1, P, P+1, 2P+1) and the window's end (max_len-1), idle rows with
    all-hole tables and masked rows (length 0), GQA rep 1 and 2;
  * pages past a slot's length are never read (NaN there changes
    nothing), and a slot with nothing held returns finite zeros;
  * ``attention_fwd`` on the stack writes the step's K/V exactly where
    the gathered path writes them, and attends to the same result;
  * the selection: a Pallas dispatch backend off a mesh reads in place,
    ``xla`` and a mesh keep the gathered path;
  * at server level the kernel's greedy tokens equal the ``xla`` oracle's,
    and block-table churn never retraces the decode step.

Tolerance: f32 operands, the two paths differ only in summation order
(online softmax over blocks against one softmax over the window), so
outputs agree to 2e-6 absolute on unit-scale inputs; bf16 operands to
2e-2 (the gathered path rounds its scores to bf16 before the softmax).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes
from repro.analysis.jit_cache import assert_zero_retrace
from repro.configs.registry import get_config, smoke_config
from repro.kernels.paged_attention import held_lengths, paged_attention
from repro.models import layers
from repro.models import model as M
from repro.runtime import steps
from repro.runtime.options import ServeOptions
from repro.runtime.server import DecodeServer, Request

jax.config.update("jax_platform_name", "cpu")

L, N, P, HD, MAX_LEN = 3, 40, 8, 16, 64
N_PP = MAX_LEN // P
F32_TOL, BF16_TOL = 2e-6, 2e-2


def _pool(kh, dtype, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    shape = (L, N, P, kh, HD)
    return (jax.random.normal(k1, shape, jnp.float32).astype(dtype),
            jax.random.normal(k2, shape, jnp.float32).astype(dtype))


def _tables(pos, held, seed=0):
    """Block tables holding pages 0..pos//P of each slot (a scrambled
    free list); ``held[b]`` False leaves slot b all holes."""
    perm = np.random.default_rng(seed).permutation(N)
    bt = np.full((len(pos), N_PP), -1, np.int32)
    used = 0
    for b, p in enumerate(pos):
        if held[b]:
            n = p // P + 1
            bt[b, :n] = perm[used:used + n]
            used += n
    return jnp.asarray(bt)


def _gathered(q, ks, vs, layer, bt, lengths, scale=None):
    """The gathered XLA path's arithmetic on one layer of the stack."""
    b, h, hd = q.shape
    kh = ks.shape[3]
    ak = layers._gather_pages(ks[layer], bt)
    av = layers._gather_pages(vs[layer], bt)
    qg = q.reshape(b, kh, h // kh, hd)
    s = jnp.einsum("bgrd,bkgd->bgrk", qg, ak).astype(jnp.float32) \
        * (hd ** -0.5 if scale is None else scale)
    valid = jnp.arange(ak.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(av.dtype)
    o = jnp.einsum("bgrk,bkgd->bgrd", w, av).reshape(b, h, hd)
    return jnp.where((lengths > 0)[:, None, None], o, 0)


# positions at the page edges and the window's end, an idle slot (all
# holes) and a masked slot (pages held, length 0 by the row mask)
POS = [P - 1, P, P + 1, 2 * P + 1, MAX_LEN - 1, 0, 2 * P]
HELD = [True] * 5 + [False, True]
MASK = [True] * 6 + [False]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("rep", [1, 2])
def test_kernel_matches_gathered_path(rep, dtype, tol):
    kh = 2
    ks, vs = _pool(kh, dtype)
    q = jax.random.normal(jax.random.PRNGKey(1), (len(POS), kh * rep, HD),
                          jnp.float32).astype(dtype)
    bt = _tables(POS, HELD)
    lengths = jnp.where(jnp.asarray(MASK),
                        held_lengths(jnp.asarray(POS), bt, P), 0)
    assert lengths.tolist() == [P, P + 1, P + 2, 2 * P + 2, MAX_LEN, 0, 0]
    for layer in range(L):
        got = paged_attention(q, ks, vs, jnp.int32(layer), bt, lengths,
                              interpret=True)
        want = _gathered(q, ks, vs, layer, bt, lengths)
        assert got.dtype == dtype and got.shape == q.shape
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol)
        # idle and masked slots: finite zeros
        assert not np.asarray(got[5:], np.float32).any()


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)],
                         ids=["f32", "bf16"])
def test_packed_heads_of_64_match_the_gathered_path(dtype, tol):
    """granite-4.0-h's attention: 32 query heads and 8 KV heads of 64, the
    KV heads packed two to a page row of 128 lanes (``layers.pool_rows``;
    a TPU page copy moves whole rows of 128), at its softmax scale 1/64
    rather than 1/sqrt(64)."""
    kh, hd, h = 8, 64, 32
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(6), 3)
    shape = (L, N, P, kh, hd)
    ks = jax.random.normal(k1, shape, jnp.float32).astype(dtype)
    vs = jax.random.normal(k2, shape, jnp.float32).astype(dtype)
    q = jax.random.normal(k3, (len(POS), h, hd), jnp.float32).astype(dtype)
    bt = _tables(POS, HELD)
    lengths = jnp.where(jnp.asarray(MASK),
                        held_lengths(jnp.asarray(POS), bt, P), 0)
    rows = lambda a: a.reshape(L, N, P, kh * hd // 128, 128)
    for layer in range(L):
        got = paged_attention(q, rows(ks), rows(vs), jnp.int32(layer), bt,
                              lengths, scale=1 / 64, interpret=True)
        want = _gathered(q, ks, vs, layer, bt, lengths, scale=1 / 64)
        assert got.dtype == dtype and got.shape == q.shape
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol)
        assert not np.asarray(got[5:], np.float32).any()


def test_pages_past_the_length_are_never_read():
    kh, pos = 2, [3, P + 2, 0]
    ks, vs = _pool(kh, jnp.float32)
    bt = _tables(pos, [True, True, False])
    lengths = held_lengths(jnp.asarray(pos), bt, P)
    q = jax.random.normal(jax.random.PRNGKey(2), (3, 4, HD), jnp.float32)
    want = paged_attention(q, ks, vs, jnp.int32(1), bt, lengths,
                           interpret=True)
    # NaN in every page no slot holds, and a table entry past slot 0's
    # length pointing at one of them
    held = set(np.asarray(bt).ravel().tolist()) - {-1}
    free = np.asarray([p for p in range(N) if p not in held])
    ks_nan = ks.at[:, free].set(jnp.nan)
    vs_nan = vs.at[:, free].set(jnp.nan)
    bt_past = bt.at[0, 1].set(int(free[0]))
    got = paged_attention(q, ks_nan, vs_nan, jnp.int32(1), bt_past, lengths,
                          interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got[2]).any()


def test_held_lengths_clip_to_the_leading_run_of_pages():
    bt = jnp.asarray([[3, 4, -1, -1], [5, -1, 6, -1], [-1, -1, -1, -1],
                      [0, 1, 2, 7]], jnp.int32)
    pos = jnp.asarray([9, 9, 0, 31], jnp.int32)
    # slot 1 has a hole at page 1: only its first page is read
    assert held_lengths(pos, bt, 8).tolist() == [10, 8, 0, 32]


def _cfg(backend="pallas", **over):
    cfg = smoke_config(get_config("internlm2-1.8b"))
    return dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True, backend=backend, interpret=True,
        exact_frac=1.0, invoke_frac=1.0, **over))


def test_attention_fwd_on_the_stack_matches_the_gathered_layer():
    cfg = _cfg()
    p = layers.init_attn(jax.random.PRNGKey(3), cfg)
    kh, b = cfg.n_kv_heads, len(POS)
    ks, vs = _pool(kh, jnp.float32, seed=4)
    ks, vs = ks[..., :cfg.hd], vs[..., :cfg.hd]
    bt = _tables(POS, HELD)
    pos = jnp.asarray(POS, jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(5), (b, 1, cfg.d_model))
    layer = 2
    lengths = held_lengths(pos, bt, P)
    out_k, stack = layers.attention_fwd(
        cfg, p, x, pos[:, None],
        {"k": ks, "v": vs, "pos": pos, "block_table": bt,
         "layer": jnp.int32(layer), "lengths": lengths})
    out_g, pool = layers.attention_fwd(
        cfg, p, x, pos[:, None],
        {"k": ks[layer], "v": vs[layer], "pos": pos, "block_table": bt})
    # the write: the same bits land in the same place, nothing else moves
    np.testing.assert_array_equal(np.asarray(stack["k"][layer]),
                                  np.asarray(pool["k"]))
    np.testing.assert_array_equal(np.asarray(stack["v"][layer]),
                                  np.asarray(pool["v"]))
    others = np.asarray([i for i in range(L) if i != layer])
    np.testing.assert_array_equal(np.asarray(stack["k"][others]),
                                  np.asarray(ks[others]))
    assert stack["pos"].tolist() == (pos + 1).tolist()
    # the idle slot (5) holds no page: the gathered path attends to a
    # clamped garbage page there, the kernel returns zeros; compare the rest
    live = np.asarray(HELD)
    np.testing.assert_allclose(np.asarray(out_k)[live],
                               np.asarray(out_g)[live], atol=1e-5, rtol=1e-5)


def _decode_jaxpr(cfg, mesh_ctx=None):
    cache = M.init_cache(cfg, 2, MAX_LEN, page_size=P, kv_pages=2 * N_PP)
    params = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0), cfg))
    step = steps.make_decode_step(cfg)
    return str(jax.make_jaxpr(step)(params, cache,
                                    jnp.zeros((2, 1), jnp.int32)))


@pytest.mark.parametrize("backend,in_place", [
    ("pallas", True), ("pallas_fused", True), ("xla", False)])
def test_the_backend_selects_the_path(backend, in_place):
    text = _decode_jaxpr(_cfg(backend))
    assert (scopes.PAGED_ATTENTION in text) == in_place
    assert M._pages_read_in_place(_cfg(backend)) == in_place


def test_a_mesh_keeps_the_gathered_path():
    from jax.sharding import Mesh, PartitionSpec as Ps
    from repro.sharding.activations import activation_sharding
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    with mesh, activation_sharding(Ps("data", None, None)):
        assert not M._pages_read_in_place(_cfg("pallas"))
    assert M._pages_read_in_place(_cfg("pallas"))


def _requests(vocab, seed=0):
    rng = np.random.default_rng(seed)
    lens = (7, 8, 9, 17, 3, 25, 12, 31, 5)
    return [Request(rid=i, prompt=rng.integers(1, vocab, n).astype(np.int32),
                    max_new=6) for i, n in enumerate(lens)]


def test_server_tokens_match_the_xla_oracle_and_never_retrace():
    """9 requests through 4 slots on a pool smaller than the slots'
    worst case: alloc/free churn, chunked prefill, greedy decode."""
    cfg = smoke_config(get_config("internlm2-1.8b"))
    cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True, exact_frac=1.0, invoke_frac=1.0))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    out = {}
    for backend in ("xla", "pallas"):
        reqs = _requests(cfg.vocab)
        srv = DecodeServer(cfg, params, options=ServeOptions(
            batch=4, max_len=MAX_LEN, admission="fifo",
            use_mcma_dispatch=True, route_scope="tick", backend=backend,
            prefill_chunk=8, kv_page_size=P, kv_pages=12))
        for r in reqs:
            srv.submit(r)
        st = srv.run_until_drained(2000)
        assert all(r.done and not r.aborted for r in reqs)
        assert st["pages_in_use"] == 0
        assert_zero_retrace(srv.decode, f"block-table churn ({backend})")
        out[backend] = [r.out for r in reqs]
    assert out["pallas"] == out["xla"]
