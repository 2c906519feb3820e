"""granite_hybrid_mcma: plain float32 reference of a layered hybrid decoder
after granite-4.0-h (``granitemoehybrid``): each layer an RMSNorm, a mixer
(a Mamba2 layer or a grouped-query attention layer without positional
encoding, as the configuration's ``layer_types`` lists them), a residual
scaled by ``residual_multiplier``, then an RMSNorm and the layer's own
FFN, an MCMA layer (an exact gated FFN beside ``n_approx`` small tanh
MLPs, one class per row picked once per tick by a router on the token's
scaled embedding), with the same scaled residual.  Embeddings are
multiplied by ``embedding_multiplier``, the attention's softmax scale is
``attention_multiplier`` and the logits, from the tied embedding, are
divided by ``logits_scaling``.

The Mamba2 layer follows the published mixer: in-projection to z, x, B,
C and dt; a causal depthwise conv of ``conv_width`` taps with bias over
x, B and C, written here as the explicit sum of its taps, then SiLU;
``dt = softplus(dt + dt_bias)``; the state-space recurrence
``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = C_t h_t + D x_t``
run position by position (no chunked form); a gated RMSNorm
(``y * silu(z)``) and the out-projection.

Independent of the program: it reads the configuration file, makes its
own weights from the seed with JAX's random functions (the same draws,
in the same order and in the configuration's parameter type, as a model
initialised from that seed), and computes in float32 under
``jax.default_matmul_precision("highest")``, one layer at a time, over
whole sequences: no cache, no pages, no chunks, no kernels.  Which FFN
each position ran is an input, worked out by ``check.replay``.

Departures from the published model, each the program's own:
  * the norms' epsilon: the reference takes the published
    ``rms_norm_eps`` (1e-5) for every RMSNorm, the mixer's gated one
    included, where the program fixes 1e-6; that departure is inside the
    gap read;
  * the in-projection is three matrices (x and z; B and C; dt) where the
    source has one ([z, x, B, C, dt]); with random weights a fixed
    relabelling;
  * the step-size bias and ``A`` are set as Mamba2 initialises them
    (``softplus(dt_bias)`` log-spaced over the heads from 1e-3 to 1e-1,
    ``A_log`` the log of 1..16 evenly), without the random draw;
  * the FFN is the MCMA layer, which no published model has;
  * the router's input, the embedding times ``embedding_multiplier``, is
    rounded to the activation type and its logits to bfloat16 before the
    argmax, as the program's activations and router matmul round them
    (a discrete decision, so it follows the program's stated arithmetic).

``control="fp8"`` rounds both operands of every weight matmul (the
projections, the FFNs and approximators, the unembedding) to float8
e4m3, and ``"int8"`` to symmetric int8, as in ``chipbench/reference.py``;
the conv and the recurrence, which are no weight matmuls, stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.check import EXACT
from chipbench.reference import _logit_stats, _mm, _norm

MAMBA, ATTENTION = "mamba", "attention"


class Reference:
    def __init__(self, conf: dict, key, *, control: str | None = None):
        self.m, self.a = conf["model"], conf["approx"]
        self.eps = float(conf["rms_norm_eps"])
        self.control = control
        self.key = key
        m = self.m
        self.hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
        self.types = tuple(m["layer_types"])
        assert len(self.types) == m["n_layers"], (len(self.types),
                                                  m["n_layers"])
        assert not float(m.get("rope_pct", 0.0)), \
            "this reference's attention has no positional encoding"
        assert m.get("tie_embeddings", False), \
            "this reference unembeds with the tied embedding"

    def _mult(self, name: str) -> float:
        return float(self.m.get(name, 1.0))

    # -- weights, drawn exactly as the model's initialisation draws them --
    def _keys(self):
        ke, kb, _ = jax.random.split(self.key, 3)
        return ke, kb

    @functools.cached_property
    def tables(self) -> dict:
        m, a = self.m, self.a
        d, v = m["d_model"], m["vocab"]
        dtype = m.get("param_dtype", "bfloat16")

        @jax.jit
        def make(ke):
            k1, _ = jax.random.split(ke)
            return {"tok": _normal(k1, (v, d), 0.02, dtype),
                    "router": _normal(jax.random.fold_in(ke, 1),
                                      (d, a["n_approx"] + 1), d ** -0.5,
                                      dtype)}
        return make(self._keys()[0])

    def _shape(self):
        m, a = self.m, self.a
        s = m["ssm"]
        return (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"],
                self.hd, m["d_ff"], s["d_state"], s["expand"],
                s["head_dim"], s["conv_width"], bool(s.get("conv_bias")),
                a["n_approx"], a["d_hidden"],
                m.get("param_dtype", "bfloat16"))

    def layer_weights(self, i: int) -> dict:
        return _layer_weights(self._shape(), self.types[i],
                              self._keys()[1], i)

    # -- routing ------------------------------------------------------------
    def routing(self):
        """Per token id: its class (the argmax of the router's logits on
        the token's scaled embedding in the activation type, the logits
        rounded to bfloat16; the first index wins a tie; 0 is the exact
        FFN, c >= 1 approximator c), the other of its two best classes,
        and whether those two lie within two bfloat16 steps of each
        other, where the program's own rounding may pick either."""
        t = self.tables
        mult = self._mult("embedding_multiplier")
        act = jnp.dtype(self.m.get("act_dtype", "bfloat16"))

        @jax.jit
        def route(tok, router):
            # the product of a stored weight and the multiplier rounded
            # once to the activation type; reduce_precision, because a
            # compiler may keep a product it is free to round in excess
            # precision
            x = tok.astype(jnp.float32) * mult
            if act != jnp.float32:
                x = jax.lax.reduce_precision(
                    x, exponent_bits=jnp.finfo(act).nexp,
                    mantissa_bits=jnp.finfo(act).nmant)
            with jax.default_matmul_precision("highest"):
                lg = jnp.dot(x, router.astype(jnp.float32))
            cls = jnp.argmax(lg.astype(jnp.bfloat16).astype(jnp.float32), -1)
            top2 = jax.lax.top_k(lg, 2)[1]
            alt = jnp.where(top2[:, 0] == cls, top2[:, 1], top2[:, 0])
            best = jnp.take_along_axis(lg, top2, 1)
            near = best[:, 0] - best[:, 1] <= jnp.abs(best[:, 0]) * 2.0 ** -7
            return cls, alt, near
        return tuple(np.asarray(a) for a in route(t["tok"], t["router"]))

    # -- forward ------------------------------------------------------------
    def hidden(self, tokens: np.ndarray, modes: np.ndarray) -> jax.Array:
        """Final normed hidden states (R, L, d) of token rows (R, L) with
        per-position FFN modes (R, L): 0 exact, c >= 1 approximator c,
        -1 no FFN (over capacity)."""
        t = self.tables
        toks = jnp.asarray(tokens, jnp.int32)
        modes = jnp.asarray(modes, jnp.int32)
        x = jnp.take(t["tok"], toks, axis=0).astype(jnp.float32) \
            * self._mult("embedding_multiplier")
        for i, kind in enumerate(self.types):
            x = _layer_fn(self._static(kind))(self.layer_weights(i), x,
                                              modes)
        return _final_norm(self.eps)(x)

    def _static(self, kind):
        m = self.m
        return (kind, self.eps, m["n_heads"], m["n_kv_heads"], self.hd,
                float(m.get("attention_multiplier") or self.hd ** -0.5),
                self._mult("residual_multiplier"), m["ssm"]["head_dim"],
                self.a["n_approx"], self.control)

    def logit_stats(self, h: jax.Array, served: np.ndarray):
        """Per position: the best logit, the logit of ``served`` (R, L),
        and the id the logits put first; the logits are the tied
        embedding's, divided by ``logits_scaling`` (a power of two here,
        so dividing the matrix first is exact)."""
        w = self.tables["tok"].T.astype(jnp.float32) \
            / self._mult("logits_scaling")
        return _logit_stats(self.control)(h, w,
                                          jnp.asarray(served, jnp.int32))


def _normal(key, shape, scale, dtype):
    return jax.random.normal(key, shape, jnp.dtype(dtype)) * scale


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_weights(shape, kind, kb, i):
    (n_layers, d, nh, nkv, hd, f, n_state, expand, p_hd, k_conv, conv_bias,
     n, hdn, dtype) = shape
    key = jax.random.split(kb, n_layers)[i]
    k_mix, k_ffn = jax.random.split(key)
    nrm = lambda k, s, sc: _normal(k, s, sc, dtype)
    w = {"ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype)}
    if kind == MAMBA:
        d_in = expand * d
        heads = d_in // p_hd
        c = d_in + 2 * n_state
        ks = jax.random.split(k_mix, 6)
        dt0 = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), heads))
        w.update(
            w_xz=nrm(ks[0], (d, 2 * d_in), d ** -0.5),
            w_bc=nrm(ks[1], (d, 2 * n_state), d ** -0.5),
            w_dt=nrm(ks[2], (d, heads), d ** -0.5),
            dt_bias=(dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
            a_log=jnp.log(jnp.linspace(1.0, 16.0, heads)).astype(dtype),
            d_skip=jnp.ones((heads,), dtype),
            w_out=nrm(ks[3], (d_in, d), d_in ** -0.5),
            norm_scale=jnp.ones((d_in,), dtype),
            conv_w=nrm(ks[4], (k_conv, c), k_conv ** -0.5),
            conv_b=(nrm(ks[5], (c,), k_conv ** -0.5) if conv_bias
                    else jnp.zeros((c,), dtype)))
    else:
        ka = jax.random.split(k_mix, 4)
        w.update(wq=nrm(ka[0], (d, nh * hd), d ** -0.5),
                 wk=nrm(ka[1], (d, nkv * hd), d ** -0.5),
                 wv=nrm(ka[2], (d, nkv * hd), d ** -0.5),
                 wo=nrm(ka[3], (nh * hd, d), (nh * hd) ** -0.5))
    km = jax.random.split(k_ffn, 4)
    kf = jax.random.split(km[0], 3)
    w.update(w_in=nrm(kf[0], (d, f), d ** -0.5),
             w_out_ffn=nrm(kf[1], (f, d), f ** -0.5),
             w_gate=nrm(kf[2], (d, f), d ** -0.5),
             a_w1=nrm(km[2], (n, d, hdn), d ** -0.5),
             a_w2=nrm(km[3], (n, hdn, d), hdn ** -0.5),
             a_b1=jnp.zeros((n, hdn), dtype),
             a_b2=jnp.zeros((n, d), dtype))
    return w


def _rmsnorm(eps, x, scale):
    return _norm("rmsnorm", eps, x, scale, 0.0)


@functools.lru_cache(maxsize=None)
def _layer_fn(static):
    kind, eps, nh, nkv, hd, scale, res, p_hd, n, control = static
    mm = functools.partial(_mm, control)
    f32 = lambda a: a.astype(jnp.float32)

    def mamba(w, h):                                       # h (R, L, d)
        r, length, _ = h.shape
        xz = mm(h, w["w_xz"])
        x, z = jnp.split(xz, 2, axis=-1)
        d_in = x.shape[-1]
        xbc = jnp.concatenate([x, mm(h, w["w_bc"])], axis=-1)
        k = w["conv_w"].shape[0]
        ext = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        conv = f32(w["conv_b"]) + sum(ext[:, j:j + length] * f32(w["conv_w"][j])
                                      for j in range(k))
        xbc = jax.nn.silu(conv)
        x, b, c = jnp.split(xbc, [d_in, d_in + (xbc.shape[-1] - d_in) // 2],
                            axis=-1)
        dt = jax.nn.softplus(mm(h, w["w_dt"]) + f32(w["dt_bias"]))
        a = -jnp.exp(f32(w["a_log"]))
        heads = dt.shape[-1]
        xh = x.reshape(r, length, heads, p_hd)

        def step(hs, inp):                                 # hs (R, H, P, N)
            dt_t, x_t, b_t, c_t = inp
            hs = jnp.exp(dt_t * a)[..., None, None] * hs \
                + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
            return hs, jnp.einsum("rhpn,rn->rhp", hs, c_t)
        h0 = jnp.zeros((r, heads, p_hd, b.shape[-1]), jnp.float32)
        t_major = lambda v: jnp.swapaxes(v, 0, 1)
        _, y = jax.lax.scan(step, h0, tuple(map(t_major, (dt, xh, b, c))))
        y = t_major(y) + xh * f32(w["d_skip"])[:, None]
        g = y.reshape(r, length, d_in) * jax.nn.silu(z)
        return mm(_rmsnorm(eps, g, f32(w["norm_scale"])), w["w_out"])

    def attend(w, h):                                      # h (L, d)
        length = h.shape[0]
        pos = jnp.arange(length)
        q = mm(h, w["wq"]).reshape(length, nh, hd)
        k = mm(h, w["wk"]).reshape(length, nkv, hd)
        v = mm(h, w["wv"]).reshape(length, nkv, hd)
        rep = nh // nkv
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * scale
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        return mm(o.reshape(length, nh * hd), w["wo"])

    def ffn(w, h, mode):                                   # h (L, d)
        hid = jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_in"])
        out = jnp.where((mode == EXACT)[:, None], mm(hid, w["w_out_ffn"]),
                        0.0)
        for cl in range(n):
            t = jnp.tanh(mm(h, w["a_w1"][cl]) + f32(w["a_b1"][cl]))
            y = mm(t, w["a_w2"][cl]) + f32(w["a_b2"][cl])
            out = jnp.where((mode == cl + 1)[:, None], y, out)
        return out

    @jax.jit
    def step(w, x, modes):                                 # x (R, L, d)
        with jax.default_matmul_precision("highest"):
            h = _rmsnorm(eps, x, f32(w["ln1"]))
            y = mamba(w, h) if kind == MAMBA else jax.lax.map(
                lambda hr: attend(w, hr), h)
            x = x + res * y
            h = _rmsnorm(eps, x, f32(w["ln2"]))
            return x + res * jax.lax.map(lambda hm: ffn(w, *hm), (h, modes))
    return step


@functools.lru_cache(maxsize=None)
def _final_norm(eps):
    return jax.jit(lambda x: _rmsnorm(eps, x, 1.0))
