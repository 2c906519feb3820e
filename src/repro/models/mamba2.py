"""Mamba2 (State-Space Duality) block, chunked-scan formulation.

Implements the SSD recurrence  h_t = exp(a_t) * h_{t-1} + b_t x_t^T,
y_t = c_t^T h_t  with scalar-per-head decay a_t = -softplus(dt) (Mamba2's
``A`` is scalar per head).  Training/prefill uses the chunkwise algorithm:
within-chunk quadratic attention-like term + cross-chunk recurrent state
pass (one lax.scan over chunks), so memory is O(S * chunk) and the sequential
depth is S / chunk.  Decode is the O(1) recurrent update.

With ``ssm.conv_width`` K > 0 the layer is the published Mamba2 mixer:
x, B and C pass through a causal depthwise conv of width K (with a bias
under ``ssm.conv_bias``) and a SiLU before the SSD.  The conv carries its
own state at decode: the last K - 1 inputs of each slot.

State layout: h (B, H, P, N) with P = head dim, N = d_state, in float32;
with a conv also ``conv`` (B, K - 1, C), C = d_in + 2N, in the activation
dtype (it holds raw inputs).  A token mask keeps the state of the tokens
it masks out: a masked token's step size is 0, so it neither decays nor
feeds the SSD state, and the conv state advances by the masked rows'
valid (leading) tokens only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import scopes
from repro.configs.base import ModelConfig


def conv_channels(cfg: ModelConfig) -> int:
    """Channels of the conv: x, B and C side by side."""
    return cfg.ssm.expand * cfg.d_model + 2 * cfg.ssm.d_state


def init_mamba(key, cfg: ModelConfig):
    d = cfg.d_model
    d_in = cfg.ssm.expand * d
    n, p_hd = cfg.ssm.d_state, cfg.ssm.head_dim
    n_heads = d_in // p_hd
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    # Mamba2's step-size init: softplus(dt_bias) spread log-evenly over
    # the heads from 1e-3 to 1e-1 (dt_bias is its inverse softplus)
    dt0 = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), n_heads))
    p = {
        # input projections: x (value path), z (gate), B, C, dt
        "w_xz": jax.random.normal(ks[0], (d, 2 * d_in), cfg.pdtype) * s,
        "w_bc": jax.random.normal(ks[1], (d, 2 * n), cfg.pdtype) * s,
        "w_dt": jax.random.normal(ks[2], (d, n_heads), cfg.pdtype) * s,
        "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(cfg.pdtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(cfg.pdtype),
        "d_skip": jnp.ones((n_heads,), cfg.pdtype),
        "w_out": jax.random.normal(ks[3], (d_in, d), cfg.pdtype) * d_in ** -0.5,
        "norm_scale": jnp.ones((d_in,), cfg.pdtype),
    }
    k = cfg.ssm.conv_width
    if k:
        c = conv_channels(cfg)
        p["conv_w"] = jax.random.normal(ks[4], (k, c), cfg.pdtype) * k ** -0.5
        if cfg.ssm.conv_bias:
            p["conv_b"] = jax.random.normal(ks[5], (c,), cfg.pdtype) \
                * k ** -0.5
    return p


def _split_heads(x, n_heads, p_hd):
    return x.reshape(*x.shape[:-1], n_heads, p_hd)


def _gated_rmsnorm(x, z, scale):
    xf = (x * jax.nn.silu(z)).astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
    return (xf * r).astype(x.dtype) * scale.astype(x.dtype)


def _conv(cfg: ModelConfig, p, xbc: jax.Array, state: jax.Array | None,
          n_adv: jax.Array | None):
    """Causal depthwise conv of width K over xbc (B, S, C), continuing from
    ``state`` (B, K - 1, C) of earlier inputs (zeros when None), then SiLU.
    Returns (out, new_state): the state is the last K - 1 inputs up to
    each row's ``n_adv`` (B,) valid tokens (all S when None)."""
    k = cfg.ssm.conv_width
    b, s, c = xbc.shape
    if state is None:
        state = jnp.zeros((b, k - 1, c), xbc.dtype)
    ext = jnp.concatenate([state.astype(xbc.dtype), xbc], axis=1)
    w = p["conv_w"].astype(jnp.float32)
    out = sum(ext[:, j:j + s].astype(jnp.float32) * w[j] for j in range(k))
    if "conv_b" in p:
        out = out + p["conv_b"].astype(jnp.float32)
    with jax.named_scope(scopes.SSM_STATE):
        if n_adv is None:
            new = ext[:, s:]
        else:
            new = jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(
                e, n, k - 1, 0))(ext, n_adv)
    return jax.nn.silu(out).astype(xbc.dtype), new


def _proj(cfg: ModelConfig, p, u: jax.Array, state: dict | None,
          tok_mask: jax.Array | None):
    """Input projections, the conv and the step sizes.  u: (B, S, d)."""
    d_in = cfg.ssm.expand * cfg.d_model
    p_hd = cfg.ssm.head_dim
    n_heads = d_in // p_hd
    xz = jnp.dot(u, p["w_xz"].astype(u.dtype))
    x, z = jnp.split(xz, 2, axis=-1)                      # (B, S, d_in) each
    bc = jnp.dot(u, p["w_bc"].astype(u.dtype))            # (B, S, 2N)
    conv_state = None
    if cfg.ssm.conv_width:
        n_adv = None if tok_mask is None \
            else jnp.sum(tok_mask, axis=1, dtype=jnp.int32)
        xbc, conv_state = _conv(
            cfg, p, jnp.concatenate([x, bc], axis=-1),
            None if state is None else state["conv"], n_adv)
        x, bc = xbc[..., :d_in], xbc[..., d_in:]
    b, c = jnp.split(bc, 2, axis=-1)                      # (B, S, N) each
    dt = jax.nn.softplus(jnp.dot(u, p["w_dt"].astype(u.dtype)).astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))      # (B, S, H)
    if tok_mask is not None:
        # a masked token neither decays nor feeds the state
        dt = jnp.where(tok_mask[..., None], dt, 0.0)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))          # (H,)
    da = dt * a                                           # log-decay, (B, S, H)
    xh = _split_heads(x, n_heads, p_hd)                   # (B, S, H, P)
    return xh, z, b, c, dt, da, conv_state


@jax.named_scope(scopes.SSM_MIXER)
def mamba_fwd(cfg: ModelConfig, p, u: jax.Array, state: dict | None = None,
              *, tok_mask: jax.Array | None = None):
    """Mamba2 SSD.  u: (B, S, d) -> (y, new_state).

    ``state`` (decode, chunked prefill): {"h": (B, H, P, N)[, "conv":
    (B, K - 1, C)]}, the layer's state before these tokens — a one-token
    update when S == 1, else the chunked SSD starting from it.
    ``tok_mask`` (B, S) bool marks the valid tokens (a prefix of each
    row); the state advances over those alone.  The new state keeps the
    dtype of the one given.
    """
    bsz, s, d = u.shape
    xh, z, b, c, dt, da, conv_state = _proj(cfg, p, u, state, tok_mask)
    n_heads, p_hd = xh.shape[2], xh.shape[3]
    h_dtype = jnp.float32 if state is None else state["h"].dtype
    h0 = (state["h"].astype(jnp.float32) if state is not None
          else jnp.zeros((bsz, n_heads, p_hd, cfg.ssm.d_state), jnp.float32))

    if state is not None and s == 1:
        # O(1) decode update: h = exp(da) h + dt * x b^T ; y = h c
        with jax.named_scope(scopes.SSM_STATE):
            decay = jnp.exp(da[:, 0]).astype(jnp.float32)     # (B, H)
            xb = jnp.einsum("bhp,bn->bhpn", xh[:, 0].astype(jnp.float32),
                            b[:, 0].astype(jnp.float32))
            h = h0 * decay[..., None, None] + xb * dt[:, 0][..., None, None]
            y = jnp.einsum("bhpn,bn->bhp", h, c[:, 0].astype(jnp.float32))
        y = y + xh[:, 0].astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, :, None]
        y = y.reshape(bsz, 1, n_heads * p_hd).astype(u.dtype)
        y = _gated_rmsnorm(y, z, p["norm_scale"])
        new = {"h": h.astype(h_dtype)}
        if conv_state is not None:
            new["conv"] = conv_state
        return jnp.dot(y, p["w_out"].astype(u.dtype)), new

    # ----- chunked SSD (train / prefill) ------------------------------------
    ck = min(cfg.ssm.chunk, s)
    assert s % ck == 0, (s, ck)
    nc = s // ck
    rs = lambda t: t.reshape(bsz, nc, ck, *t.shape[2:]).swapaxes(0, 1)
    xh_c, b_c, c_c, dt_c, da_c = map(rs, (xh, b, c, dt, da))

    def chunk_step(h, inp):
        xc, bc_, cc, dtc, dac = inp                       # (B, ck, ...)
        # cumulative log-decay within chunk (inclusive)
        cum = jnp.cumsum(dac, axis=1)                     # (B, ck, H)
        total = cum[:, -1]                                # (B, H)
        # 1) contribution of the incoming state: y_intra_state[t] = c_t (prod decay<=t) h
        decay_in = jnp.exp(cum)                           # (B, ck, H)
        with jax.named_scope(scopes.SSM_STATE):
            y_state = jnp.einsum("bln,bhpn->blhp", cc.astype(jnp.float32),
                                 h) * decay_in[..., None]
        # 2) within-chunk "attention": L[t, s_] = exp(cum_t - cum_s) for s_ <= t
        rel = cum[:, :, None, :] - cum[:, None, :, :]     # (B, ck, ck, H)
        mask = jnp.tril(jnp.ones((ck, ck), bool))
        # mask BEFORE exp: where(mask, exp(rel), 0) NaNs the backward pass
        # when the masked upper triangle overflows (0 * inf cotangent).
        rel = jnp.where(mask[None, :, :, None], rel, -1e30)
        l_mat = jnp.exp(rel)
        scores = jnp.einsum("bln,bsn->bls", cc.astype(jnp.float32),
                            bc_.astype(jnp.float32))      # (B, ck, ck)
        w = scores[..., None] * l_mat                     # (B, l, s_, H)
        y_intra = jnp.einsum("blsh,bsh,bshp->blhp", w, dtc,
                             xh_cast := xc.astype(jnp.float32))
        # 3) state update: h' = exp(total) h + sum_s exp(total - cum_s) dt_s x_s b_s^T
        # contract s directly — do NOT materialize the (B, ck, H, P, N)
        # outer product (see xlstm.py chunk_step, §Perf A.1)
        carry_decay = jnp.exp(total[:, None] - cum)       # (B, ck, H)
        xz = xh_cast * (carry_decay * dtc)[..., None]
        with jax.named_scope(scopes.SSM_STATE):
            h_new = h * jnp.exp(total)[..., None, None] + jnp.einsum(
                "bshp,bsn->bhpn", xz, bc_.astype(jnp.float32))
        return h_new, (y_state + y_intra)

    # checkpoint the chunk body: backward recomputes the within-chunk
    # (ck x ck) decay/score tiles instead of saving them for every chunk
    step_fn = jax.checkpoint(chunk_step) if cfg.remat and state is None \
        else chunk_step
    h_fin, y_c = jax.lax.scan(step_fn, h0, (xh_c, b_c, c_c, dt_c, da_c))
    y = y_c.swapaxes(0, 1).reshape(bsz, s, n_heads, p_hd)
    y = y + xh.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(bsz, s, n_heads * p_hd).astype(u.dtype)
    y = _gated_rmsnorm(y, z, p["norm_scale"])
    new = {"h": h_fin.astype(h_dtype)}
    if conv_state is not None:
        new["conv"] = conv_state
    return jnp.dot(y, p["w_out"].astype(u.dtype)), new


def init_mamba_state(cfg: ModelConfig, batch: int):
    d_in = cfg.ssm.expand * cfg.d_model
    n_heads = d_in // cfg.ssm.head_dim
    st = {"h": jnp.zeros((batch, n_heads, cfg.ssm.head_dim, cfg.ssm.d_state),
                         jnp.float32)}
    if cfg.ssm.conv_width:
        st["conv"] = jnp.zeros((batch, cfg.ssm.conv_width - 1,
                                conv_channels(cfg)), cfg.adtype)
    return st
