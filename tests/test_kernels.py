"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref

jax.config.update("jax_platform_name", "cpu")


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=3e-5, atol=3e-5)


def _mk_mlp(key, d_in, d_h, d_out, dtype):
    ks = jax.random.split(key, 4)
    w1 = (jax.random.normal(ks[0], (d_in, d_h)) * 0.2).astype(dtype)
    b1 = (jax.random.normal(ks[1], (d_h,)) * 0.1).astype(dtype)
    w2 = (jax.random.normal(ks[2], (d_h, d_out)) * 0.2).astype(dtype)
    b2 = (jax.random.normal(ks[3], (d_out,)) * 0.1).astype(dtype)
    return w1, b1, w2, b2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,d_in,d_h,d_out", [
    (64, 8, 8, 1),          # paper-scale approximator (padded to lanes)
    (300, 100, 40, 60),     # unaligned everything
    (512, 256, 128, 256),   # aligned LM-scale ApproxFFN slice
    (1, 6, 8, 2),           # single row
])
def test_mlp_forward_matches_ref(dtype, t, d_in, d_h, d_out):
    key = jax.random.PRNGKey(hash((t, d_in, d_h, d_out)) % 2**31)
    x = (jax.random.normal(key, (t, d_in)) * 0.5).astype(dtype)
    w1, b1, w2, b2 = _mk_mlp(jax.random.fold_in(key, 1), d_in, d_h, d_out, dtype)
    got = ops.mlp_apply(x, w1, b1, w2, b2, block_t=128, interpret=True)
    want = ref.mlp_forward_ref(x, w1, b1, w2, b2)
    assert got.shape == (t, d_out) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,n,d_in,d_h,d_out,block", [
    (500, 3, 64, 32, 64, 128),   # MCMA default: 3 approximators
    (96, 1, 16, 8, 16, 32),      # degenerate single approximator
    (1024, 8, 128, 64, 128, 256),
    (33, 4, 10, 6, 4, 32),       # tiny ragged groups
])
def test_switched_mlp_matches_ref(dtype, t, n, d_in, d_h, d_out, block):
    key = jax.random.PRNGKey(hash((t, n, d_in)) % 2**31)
    x = (jax.random.normal(key, (t, d_in)) * 0.5).astype(dtype)
    ks = jax.random.split(jax.random.fold_in(key, 7), 4)
    w1 = (jax.random.normal(ks[0], (n, d_in, d_h)) * 0.2).astype(dtype)
    b1 = (jax.random.normal(ks[1], (n, d_h)) * 0.1).astype(dtype)
    w2 = (jax.random.normal(ks[2], (n, d_h, d_out)) * 0.2).astype(dtype)
    b2 = (jax.random.normal(ks[3], (n, d_out)) * 0.1).astype(dtype)
    cls = jax.random.randint(jax.random.fold_in(key, 9), (t,), 0, n)
    got = ops.switched_apply(x, cls, w1, b1, w2, b2, block_t=block, interpret=True)
    want = ref.switched_mlp_ref(x, cls, w1, b1, w2, b2)
    assert got.shape == (t, d_out) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_switched_mlp_skewed_classes():
    """All rows on one class (the common post-convergence MCMA regime)."""
    key = jax.random.PRNGKey(3)
    t, n, d = 257, 3, 32
    x = jax.random.normal(key, (t, d))
    w1 = jax.random.normal(jax.random.fold_in(key, 1), (n, d, 16)) * 0.2
    b1 = jnp.zeros((n, 16))
    w2 = jax.random.normal(jax.random.fold_in(key, 2), (n, 16, d)) * 0.2
    b2 = jnp.zeros((n, d))
    cls = jnp.full((t,), 2, jnp.int32)
    got = ops.switched_apply(x, cls, w1, b1, w2, b2, block_t=64, interpret=True)
    want = ref.switched_mlp_ref(x, cls, w1, b1, w2, b2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# switched_apply edge cases (each against the pure-jnp oracle in ref.py)
# ---------------------------------------------------------------------------


def _mk_switched(key, n, d_in, d_h, d_out, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    w1 = (jax.random.normal(ks[0], (n, d_in, d_h)) * 0.2).astype(dtype)
    b1 = (jax.random.normal(ks[1], (n, d_h)) * 0.1).astype(dtype)
    w2 = (jax.random.normal(ks[2], (n, d_h, d_out)) * 0.2).astype(dtype)
    b2 = (jax.random.normal(ks[3], (n, d_out)) * 0.1).astype(dtype)
    return w1, b1, w2, b2


def _check_switched(x, cls, w1, b1, w2, b2, block):
    got = ops.switched_apply(x, cls, w1, b1, w2, b2, block_t=block,
                             interpret=True)
    want = ref.switched_mlp_ref(x, cls, w1, b1, w2, b2)
    assert got.shape == want.shape and got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_switched_mlp_empty_class():
    """A class with zero rows must not perturb its neighbours' tiles."""
    key = jax.random.PRNGKey(11)
    t, n, d = 120, 4, 24
    x = jax.random.normal(key, (t, d))
    w = _mk_switched(jax.random.fold_in(key, 1), n, d, 8, d)
    cls = jax.random.randint(jax.random.fold_in(key, 2), (t,), 0, n)
    cls = jnp.where(cls == 1, 3, cls)        # class 1 is now empty
    _check_switched(x, cls, *w, block=32)


def test_switched_mlp_t_smaller_than_block():
    """T < block_t: everything lives inside partial tiles."""
    key = jax.random.PRNGKey(12)
    t, n, d = 7, 3, 16
    x = jax.random.normal(key, (t, d))
    w = _mk_switched(jax.random.fold_in(key, 1), n, d, 8, d)
    cls = jax.random.randint(jax.random.fold_in(key, 2), (t,), 0, n)
    _check_switched(x, cls, *w, block=64)


def test_switched_mlp_single_approximator():
    """n_approx == 1 degenerates to a plain grouped MLP (no switching)."""
    key = jax.random.PRNGKey(13)
    t, d = 150, 20
    x = jax.random.normal(key, (t, d))
    w = _mk_switched(jax.random.fold_in(key, 1), 1, d, 12, d)
    cls = jnp.zeros((t,), jnp.int32)
    _check_switched(x, cls, *w, block=64)


def test_switched_mlp_all_nc_zero_class():
    """All rows on a zero-weight "nC" class (the dispatch engine's trick
    for exact/over-capacity rows) must come out exactly zero."""
    key = jax.random.PRNGKey(14)
    t, n, d = 90, 3, 24
    x = jax.random.normal(key, (t, d))
    w1, b1, w2, b2 = _mk_switched(jax.random.fold_in(key, 1), n, d, 8, d)
    zc = lambda w: jnp.concatenate([w, jnp.zeros_like(w[:1])], 0)
    cls = jnp.full((t,), n, jnp.int32)       # everyone on the zero class
    got = ops.switched_apply(x, cls, zc(w1), zc(b1), zc(w2), zc(b2),
                             block_t=32, interpret=True)
    assert not np.asarray(got).any()
    _check_switched(x, cls, zc(w1), zc(b1), zc(w2), zc(b2), block=32)


# ---------------------------------------------------------------------------
# Property tests
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(t=st.integers(1, 300), d_in=st.integers(1, 80), d_h=st.integers(1, 40),
       d_out=st.integers(1, 80), seed=st.integers(0, 2**30))
def test_mlp_forward_property(t, d_in, d_h, d_out, seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (t, d_in)) * 0.5
    w1, b1, w2, b2 = _mk_mlp(jax.random.fold_in(key, 1), d_in, d_h, d_out, jnp.float32)
    got = ops.mlp_apply(x, w1, b1, w2, b2, block_t=64, interpret=True)
    want = ref.mlp_forward_ref(x, w1, b1, w2, b2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-5, atol=5e-5)


@settings(max_examples=15, deadline=None)
@given(t=st.integers(1, 200), n=st.integers(1, 5), seed=st.integers(0, 2**30))
def test_switched_permutation_invariance(t, n, seed):
    """Permuting the rows permutes the outputs identically (dispatch is
    row-wise — the sort/scatter machinery must be order-free)."""
    key = jax.random.PRNGKey(seed)
    d = 24
    x = jax.random.normal(key, (t, d))
    ks = jax.random.split(jax.random.fold_in(key, 5), 4)
    w1 = jax.random.normal(ks[0], (n, d, 8)) * 0.3
    b1 = jax.random.normal(ks[1], (n, 8)) * 0.1
    w2 = jax.random.normal(ks[2], (n, 8, d)) * 0.3
    b2 = jax.random.normal(ks[3], (n, d)) * 0.1
    cls = jax.random.randint(jax.random.fold_in(key, 6), (t,), 0, n)
    perm = jax.random.permutation(jax.random.fold_in(key, 8), t)
    y = ops.switched_apply(x, cls, w1, b1, w2, b2, block_t=32, interpret=True)
    y_perm = ops.switched_apply(x[perm], cls[perm], w1, b1, w2, b2,
                                block_t=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y[perm]), np.asarray(y_perm),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# sLSTM recurrence kernel (VMEM-resident state)
# ---------------------------------------------------------------------------

from repro.kernels import slstm_scan as SK


@pytest.mark.parametrize("s,b,h,hd", [
    (8, 2, 2, 8),       # tiny
    (32, 4, 4, 16),     # smoke-model scale
    (16, 1, 4, 128),    # lane-aligned head dim
])
def test_slstm_scan_matches_ref(s, b, h, hd):
    key = jax.random.PRNGKey(s * 100 + b)
    xg = jax.random.normal(key, (s, b, h, 4 * hd), jnp.float32) * 0.5
    wh = (jax.random.normal(jax.random.fold_in(key, 1),
                            (h, hd, 4 * hd)) * 0.2).astype(jnp.float32)
    z = jnp.zeros((b, h, hd), jnp.float32)
    m0 = jnp.full((b, h, hd), -1e30, jnp.float32)
    ys, (hf, cf, nf, mf) = SK.slstm_scan(xg, wh, z, z, z, m0, interpret=True)
    ys2, (h2, c2, n2, m2) = ref.slstm_scan_ref(xg, wh, z, z, z, m0)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ys2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(h2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cf), np.asarray(c2),
                               rtol=1e-5, atol=1e-5)


def test_slstm_scan_matches_model_layer():
    """Kernel == the model's slstm core (same gate layout end to end)."""
    import dataclasses
    from repro.configs.registry import get_config, smoke_config
    from repro.models import xlstm as X
    cfg = smoke_config(get_config("xlstm-1.3b"))
    p = X.init_slstm(jax.random.PRNGKey(3), cfg)
    b, s = 2, 32
    x = jax.random.normal(jax.random.PRNGKey(4), (b, s, cfg.d_model),
                          jnp.float32) * 0.3
    # model path (without the post-FFN): reproduce slstm_fwd's core
    d, h, hd = X.slstm_dims(cfg)
    xg = (jnp.dot(x, p["w_x"]) + p["b"]).reshape(b, s, 4, h, hd) \
        .transpose(1, 0, 3, 2, 4).reshape(s, b, h, 4 * hd)
    z0 = jnp.zeros((b, h, hd), jnp.float32)
    m0 = jnp.full((b, h, hd), -1e30, jnp.float32)
    ys, _ = SK.slstm_scan(xg, p["w_h"].astype(jnp.float32),
                          z0, z0, z0, m0, interpret=True)
    y_kernel = ys.transpose(1, 0, 2, 3).reshape(b, s, d)

    y_model, _ = X.slstm_fwd(cfg, p, x)
    # undo the model's post up/down FFN by re-projecting the kernel output
    up = jnp.dot(y_kernel, p["w_up"])
    u, g = jnp.split(up, 2, axis=-1)
    y_kernel_full = jnp.dot(u * jax.nn.gelu(g), p["w_down"])
    np.testing.assert_allclose(np.asarray(y_kernel_full), np.asarray(y_model),
                               rtol=2e-4, atol=2e-4)


def test_slstm_scan_trainable_grads_match_ref():
    """custom_vjp wrapper: kernel fwd + reference bwd == reference grads."""
    s, b, h, hd = 12, 2, 2, 8
    key = jax.random.PRNGKey(7)
    xg = jax.random.normal(key, (s, b, h, 4 * hd), jnp.float32) * 0.5
    wh = (jax.random.normal(jax.random.fold_in(key, 1),
                            (h, hd, 4 * hd)) * 0.2).astype(jnp.float32)
    z = jnp.zeros((b, h, hd), jnp.float32)
    m0 = jnp.full((b, h, hd), -1e30, jnp.float32)

    def loss_kernel(xg, wh):
        ys, _ = SK.slstm_scan_trainable(xg, wh, z, z, z, m0, True)
        return jnp.sum(ys ** 2)

    def loss_ref(xg, wh):
        ys, _ = ref.slstm_scan_ref(xg, wh, z, z, z, m0)
        return jnp.sum(ys ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1))(xg, wh)
    gr = jax.grad(loss_ref, argnums=(0, 1))(xg, wh)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# gather_resident_stacks: duplicate / out-of-range residency ids are pinned
# ---------------------------------------------------------------------------

def test_gather_resident_stacks_duplicates_and_oob():
    """Degenerate residency vectors have DEFINED semantics: duplicates
    duplicate the weight row; any id outside [0, library_size) resolves
    to the zero pseudo-class row (the slot serves exact zeros, like an
    empty slot) instead of whatever jax's gather clamping would pick."""
    from repro.analysis.jit_cache import assert_zero_retrace
    key = jax.random.PRNGKey(3)
    lib, d, d_h = 4, 8, 8
    ks = jax.random.split(key, 4)
    stacks = ops.prepad_switched_weights(
        jax.random.normal(ks[0], (lib, d, d_h)),
        jax.random.normal(ks[1], (lib, d_h)),
        jax.random.normal(ks[2], (lib, d_h, d)),
        jax.random.normal(ks[3], (lib, d)))

    # duplicates: both slots serve class 2's weights, deterministically
    dup = ops.gather_resident_stacks(*stacks,
                                     jnp.asarray([2, 2], jnp.int32))
    for full, got in zip(stacks, dup):
        assert got.shape[0] == 3                   # n_resident + pseudo
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(full[2]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(full[2]))
        # the trailing row stays the zero pseudo-class
        assert not np.any(np.asarray(got[-1]))

    # out of range on both sides (negative, == library_size, way past)
    for bad in ([-1, lib], [lib + 3, -7]):
        oob = ops.gather_resident_stacks(*stacks,
                                         jnp.asarray(bad, jnp.int32))
        for got in oob:
            assert not np.any(np.asarray(got[:2])), \
                f"OOB residency {bad} must serve the zero pseudo-class"

    # residency stays a TRACED input under the pinning
    fn = jax.jit(lambda r: ops.gather_resident_stacks(*stacks, r))
    for r in ([0, 1], [3, 3], [-1, 99]):
        fn(jnp.asarray(r, jnp.int32))
    assert_zero_retrace(fn, "a residency swap")
