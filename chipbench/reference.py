"""Plain float32 reference of a dense decoder whose FFN is an MCMA layer
(an exact gated FFN beside ``n_approx`` small tanh MLPs, one class per
row picked once per tick by a router on the token's embedding).

Independent of the program: it reads the configuration file, makes its
own weights from the seed with JAX's random functions (the same draws,
in the same order, as a model initialised from that seed), and computes
every layer in float32 with ``jax.default_matmul_precision("highest")``,
one layer at a time, over whole sequences with a causal mask: no cache,
no pages, no chunks, no kernels.  Which FFN each position ran (a class,
or nothing for a row over capacity) is an input, worked out by
``check.replay`` from the router and the capacities.

Departures from the published models, each the program's own:
  * rotary embedding rotates interleaved feature pairs (the source models
    rotate halves); with random weights a fixed relabelling;
  * none for the norms' epsilon: the reference takes the published one
    from the configuration file, where the program fixes its own (see
    the file's ``assumed``), so that departure is inside the gap read;
  * the FFN is the MCMA layer, which no published model has;
  * the router's logits are rounded to bfloat16 before the argmax, as the
    program's bfloat16 router matmul rounds them (a discrete decision,
    so it follows the program's stated arithmetic).

``control="fp8"`` rounds both operands of every weight matmul to
float8 e4m3 (weights per output channel, activations per row, scaled to
the format's largest value): the step below bfloat16, and the control
that sets the upper reading of the correctness limit.  ``"int8"`` rounds
them to symmetric int8 instead, a reading kept beside it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.check import EXACT


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.bfloat16) * scale


class Reference:
    def __init__(self, conf: dict, key, *, control: str | None = None):
        self.m, self.a = conf["model"], conf["approx"]
        self.eps = float(conf["rms_norm_eps"] if "rms_norm_eps" in conf
                         else conf["layer_norm_eps"])
        self.control = control
        m = self.m
        self.hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
        self.key = key

    # -- weights, drawn exactly as the model's initialisation draws them --
    def _keys(self):
        ke, kb, _ = jax.random.split(self.key, 3)
        return ke, kb

    @functools.cached_property
    def tables(self) -> dict:
        m, a = self.m, self.a
        d, v = m["d_model"], m["vocab"]

        @jax.jit
        def make(ke):
            k1, k2 = jax.random.split(ke)
            t = {"tok": _normal(k1, (v, d), 0.02),
                 "router": _normal(jax.random.fold_in(ke, 1),
                                   (d, a["n_approx"] + 1), d ** -0.5)}
            if not m.get("tie_embeddings", False):
                t["unembed"] = _normal(k2, (d, v), 0.02)
            return t
        return make(self._keys()[0])

    def layer_weights(self, i: int) -> dict:
        return _layer_weights(self.m["n_layers"], self._keys()[1], i,
                              self._shape_key())

    def _shape_key(self):
        m, a = self.m, self.a
        return (m["d_model"], m["n_heads"], m["n_kv_heads"], self.hd,
                m["d_ff"], bool(m.get("gated_ffn", True)),
                bool(m.get("qkv_bias", False)), a["n_approx"], a["d_hidden"])

    # -- routing ------------------------------------------------------------
    def routing(self):
        """Per token id: its class (the argmax of the router's logits on
        the token's embedding, rounded to bfloat16; the first index wins
        a tie; 0 is the exact FFN, c >= 1 approximator c), the other of
        its two best classes, and whether those two lie within two
        bfloat16 steps of each other, where the program's own rounding
        may pick either."""
        t = self.tables

        @jax.jit
        def route(tok, router):
            with jax.default_matmul_precision("highest"):
                lg = jnp.dot(tok.astype(jnp.float32),
                             router.astype(jnp.float32))
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
        x = jnp.take(t["tok"], toks, axis=0).astype(jnp.float32)
        step = _layer_fn(self._static())
        for i in range(self.m["n_layers"]):
            x = step(self.layer_weights(i), x, modes)
        return _final_norm(self._static(), x)

    def _static(self):
        m = self.m
        return (m["norm"], self.eps, m["n_heads"], m["n_kv_heads"], self.hd,
                float(m.get("rope_pct", 1.0)), float(m.get("rope_base",
                                                           10000.0)),
                bool(m.get("gated_ffn", True)), m.get("act", "silu"),
                bool(m.get("parallel_block", False)), self.a["n_approx"],
                self.control)

    def logit_stats(self, h: jax.Array, served: np.ndarray):
        """Per position: the best logit, the logit of ``served`` (R, L),
        and the id the logits put first."""
        t = self.tables
        w = t["unembed"] if "unembed" in t else t["tok"].T
        return _logit_stats(self.control)(h, w,
                                          jnp.asarray(served, jnp.int32))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_weights(n_layers, kb, i, shape):
    d, nh, nkv, hd, f, gated, qkv_bias, n, hdn = shape
    key = jax.random.split(kb, n_layers)[i]
    k1, _, k3, _ = jax.random.split(key, 4)
    ka = jax.random.split(k1, 4)
    w = {"wq": _normal(ka[0], (d, nh * hd), d ** -0.5),
         "wk": _normal(ka[1], (d, nkv * hd), d ** -0.5),
         "wv": _normal(ka[2], (d, nkv * hd), d ** -0.5),
         "wo": _normal(ka[3], (nh * hd, d), (nh * hd) ** -0.5)}
    if qkv_bias:
        w["bq"] = jnp.zeros((nh * hd,), jnp.bfloat16)
        w["bk"] = jnp.zeros((nkv * hd,), jnp.bfloat16)
        w["bv"] = jnp.zeros((nkv * hd,), jnp.bfloat16)
    km = jax.random.split(k3, 4)
    kf = jax.random.split(km[0], 3)
    w["w_in"] = _normal(kf[0], (d, f), d ** -0.5)
    w["w_out"] = _normal(kf[1], (f, d), f ** -0.5)
    if gated:
        w["w_gate"] = _normal(kf[2], (d, f), d ** -0.5)
    w["a_w1"] = _normal(km[2], (n, d, hdn), d ** -0.5)
    w["a_w2"] = _normal(km[3], (n, hdn, d), hdn ** -0.5)
    w["a_b1"] = jnp.zeros((n, hdn), jnp.bfloat16)
    w["a_b2"] = jnp.zeros((n, d), jnp.bfloat16)
    w["ln1"] = jnp.ones((d,), jnp.bfloat16)
    w["ln2"] = jnp.ones((d,), jnp.bfloat16)
    w["ln1_b"] = jnp.zeros((d,), jnp.bfloat16)
    w["ln2_b"] = jnp.zeros((d,), jnp.bfloat16)
    return w


def _norm(kind, eps, x, scale, bias):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y * scale + bias if kind == "layernorm" else y


def _int8(x, axis):
    """Symmetric int8 rounding along ``axis`` (values stay in float32)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(x / s) * s


def _fp8(x, axis):
    """float8 (e4m3) rounding along ``axis``, scaled so the largest
    magnitude maps to the format's largest value."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


_LOWER = {"int8": _int8, "fp8": _fp8}


def _mm(control, x, w):
    """x (..., k) @ w (k, n) in float32, or for the control with both
    operands rounded to the lower precision first."""
    w = w.astype(jnp.float32)
    if control:
        q = _LOWER[control]
        x, w = q(x, -1), q(w, 0)
    return jnp.dot(x, w)


def _rope(x, pos, pct, base):
    hd = x.shape[-1]
    rot = int(hd * pct) // 2 * 2
    if rot == 0:
        return x
    freqs = 1.0 / (base ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos[:, None].astype(jnp.float32) * freqs          # (L, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.reshape(*x.shape[:-1], rot), x[..., rot:]],
                           -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(static):
    (norm, eps, nh, nkv, hd, pct, base, gated, act, parallel, n,
     control) = static
    actf = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu,
            "tanh": jnp.tanh}[act]

    def attend(w, h):                                      # h (L, d)
        L = h.shape[0]
        pos = jnp.arange(L)
        q = _mm(control, h, w["wq"]) + w.get("bq", 0.0)
        k = _mm(control, h, w["wk"]) + w.get("bk", 0.0)
        v = _mm(control, h, w["wv"]) + w.get("bv", 0.0)
        q = _rope(q.reshape(L, nh, hd), pos, pct, base)
        k = _rope(k.reshape(L, nkv, hd), pos, pct, base)
        v = v.reshape(L, nkv, hd)
        rep = nh // nkv
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        return _mm(control, o.reshape(L, nh * hd), w["wo"])

    def ffn(w, h, mode):                                   # h (L, d)
        up = _mm(control, h, w["w_in"])
        hid = actf(_mm(control, h, w["w_gate"])) * up if gated else actf(up)
        out = jnp.where((mode == EXACT)[:, None],
                        _mm(control, hid, w["w_out"]), 0.0)
        for c in range(n):
            t = jnp.tanh(_mm(control, h, w["a_w1"][c])
                         + w["a_b1"][c].astype(jnp.float32))
            y = _mm(control, t, w["a_w2"][c]) + w["a_b2"][c].astype(
                jnp.float32)
            out = jnp.where((mode == c + 1)[:, None], y, out)
        return out

    def one(w, x, mode):                                   # x (L, d)
        f32 = lambda k: w[k].astype(jnp.float32)
        h = _norm(norm, eps, x, f32("ln1"), f32("ln1_b"))
        if parallel:
            return x + attend(w, h) + ffn(w, h, mode)
        x = x + attend(w, h)
        return x + ffn(w, _norm(norm, eps, x, f32("ln2"), f32("ln2_b")),
                       mode)

    @jax.jit
    def step(w, x, modes):
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(lambda xm: one(w, *xm), (x, modes))
    return step


@functools.lru_cache(maxsize=None)
def _final_norm_fn(norm, eps):
    return jax.jit(lambda x: _norm(norm, eps, x, 1.0, 0.0))


def _final_norm(static, x):
    return _final_norm_fn(static[0], static[1])(x)


@functools.lru_cache(maxsize=None)
def _logit_stats(control):
    @jax.jit
    def stats(h, w, served):
        def one(hs):
            hr, sr = hs
            with jax.default_matmul_precision("highest"):
                lg = _mm(control, hr, w)                   # (L, V)
            return (jnp.max(lg, -1),
                    jnp.take_along_axis(lg, sr[:, None], 1)[:, 0],
                    jnp.argmax(lg, -1).astype(jnp.int32))
        return jax.lax.map(one, (h, served))
    return stats
